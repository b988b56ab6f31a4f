"""NA04 fixture companion: the Python-side length of the stats array."""

STATS_FIELDS = 7

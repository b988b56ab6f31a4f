"""import.land_rows: rows the global's import landings took through a
work set in a tick.

`AggregationEngine._last_flush_info["import_land_rows"]`, which the
tick record copies into `flush_path.global`: a clustered landing
gathers the rows it touches into a fixed work set, compresses, fills
and compresses that part and scatters it back, and the engine counts
those rows under its lock where a landing is decided, the interval's
sum at the flush (padding not counted). Beside it `import_land_bank`
counts the landings that passed over the whole bank instead, the dear
arm: a cell's traffic should leave it at 0, and a bank no larger than
the smallest set (a rehearsal's) takes nothing else. The median over
the timed ticks; a program without the counter leaves the metric out.
"""

import statistics


def read(ctx):
    rows = [t.get("flush_path", {}).get("global", {}).get("import_land_rows")
            for t in ctx["ticks"]]
    rows = [n for n in rows if n is not None]
    return float(statistics.median(rows)) if rows else None

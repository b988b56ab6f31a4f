"""ingest.ring_fill_most: how full the fullest sample sub-ring got within
a tick.

A bank's samples wait for the pump in `RING_WAYS` sub-rings, a reader
thread staging into one of them; a sub-ring that fills drops and counts
(`ring_drops`). The bridge keeps each sub-ring's high water since the
flush last took it, and `bridge.stats()["ring_high_<bank>"]` is a
bank's fullest; the driver reads it after the settle into the tick
record's `ring` beside a sub-ring's capacity. 100 x the worst bank's
high water over that capacity; the median over the timed ticks. The
sender keeps the samples parsed and not yet pumped under half a
sub-ring, all banks and ways together, so a reading near 50 says one
way of one bank took nearly all of them, and one well over 50 that the
pump left a way waiting while it drained the others. A program that
keeps no such mark leaves the metric out.
"""

import statistics


def read(ctx):
    fills = []
    for t in ctx["ticks"]:
        ring = t.get("ring")
        if ring and ring.get("high") and ring.get("way_capacity"):
            fills.append(100.0 * max(ring["high"].values())
                         / ring["way_capacity"])
    return float(statistics.median(fills)) if fills else None

"""ingest.overflow_rows: rows the local tier's histogram landings
compressed one by one in a tick, because their 256-deep buffers filled.

`AggregationEngine._last_flush_info["overflow_rows"]`, which the tick
record copies into `flush_path.local`: the landing program counts on
the device and the flush's own fetch brings the count back. Beside it
`overflow_bank` counts the passes that compressed the whole bank, the
dear arm: a cell's traffic should leave it at 0. The median over the
timed ticks; a program without the counter leaves the metric out.
"""

import statistics


def read(ctx):
    rows = [t.get("flush_path", {}).get("local", {}).get("overflow_rows")
            for t in ctx["ticks"]]
    rows = [n for n in rows if n is not None]
    return float(statistics.median(rows)) if rows else None

"""ingest.pump_batches: dispatches the native pump made in a tick.

One `ingest.pump.batch` phase per dispatch reaches the local's flush
tick; dispatches past the pump's in-code budget, or past the slots the
tick has free, lengthen the last row, so the count stops there (about
170 beside a one-chunk forward). The median over the timed ticks.
"""

import statistics

ROW = "local:ingest.pump.batch"


def read(ctx):
    counts = [sum(1 for row in t["phase_rows"] if row[0] == ROW)
              for t in ctx["ticks"]]
    counts = [n for n in counts if n]
    return float(statistics.median(counts)) if counts else None

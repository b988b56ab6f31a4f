"""ingest.reader_lines_most: the busiest UDP reader's share of a tick's
lines.

With `SO_REUSEPORT` the kernel gives a flow to a reader by the flow's
hash, so how a host's client processes fall over the readers differs
from run to run. The driver reads each reader's running count of lines
parsed (`bridge.stats()["readers"]`) before the send and after the
settle; the tick record's `readers` holds the differences. 100 x the
largest over their sum; the median over the timed ticks. 100 / readers
when the flows fall evenly (25 with four). A program that keeps no such
counts leaves the metric out.
"""

import statistics


def read(ctx):
    shares = []
    for t in ctx["ticks"]:
        lines = [r["lines"] for r in t.get("readers", ())]
        if lines and sum(lines) > 0:
            shares.append(100.0 * max(lines) / sum(lines))
    return float(statistics.median(shares)) if shares else None

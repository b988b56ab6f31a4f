"""ingest.reader_busy_most: how much of the send phase the busiest UDP
reader thread spent at work.

A tier that reads with several `SO_REUSEPORT` sockets stamps one
`local:ingest.reader.busy` phase a reader a tick (the reader's seconds
from a receive's return to its burst parsed, interned and staged in the
rings; a tally the pump lays to end where its last batch did, so the
rows of one tick share a name and tell the readers apart by length
alone). 100 x the longest of a tick's rows over the tick's `bench.send`
span; the median over the timed ticks. Near 100 that reader binds the
send whatever the others do; the kernel's hash of the flows decides
which reader it is. A program that stamps no such phase leaves the
metric out.
"""

import statistics

PHASE = "local:ingest.reader.busy"


def read(ctx):
    shares = []
    for t in ctx["ticks"]:
        busy = [t1 - t0 for name, t0, t1 in t["phase_rows"] if name == PHASE]
        send = t["spans"].get("bench.send")
        if busy and send:
            shares.append(100.0 * max(busy) / 1e9 / send)
    return float(statistics.median(shares)) if shares else None

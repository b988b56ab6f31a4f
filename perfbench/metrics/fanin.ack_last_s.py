"""fanin.ack_last_s: release of the fleet's senders to the last
sender's acknowledgement, in a tick.

The fan-in driver releases every sender at once and stamps each one's
acknowledgement on the benchmark's clock; the tick record holds the
first, the median and the last under `acks_s` (`perfbench/layers.py`).
The last is what the fleet's forward takes: a local's flush is not
done until its request is acknowledged, and the global acknowledges a
request once it is decoded, admitted by the dedupe ledger and routed
to its engine's queue, with eight handler threads and the import
worker on one interpreter. The median over the timed ticks; a
topology without senders of the benchmark's own (the two-tier cells,
whose forward the local tier times) gives nothing to read.
"""

import statistics


def read(ctx):
    last = [t["acks_s"]["last"] for t in ctx["ticks"]
            if "last" in (t.get("acks_s") or {})]
    return float(statistics.median(last)) if last else None

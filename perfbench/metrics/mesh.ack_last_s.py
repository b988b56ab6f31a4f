"""mesh.ack_last_s: release of the fleet's senders to the last
sender's acknowledgement, in a tick, where the global is the mesh
engine.

What `fanin.ack_last_s` reads in the one-chip fan-in cells, under a
name of the four-chip fan-in cell's own: that entry's list of cells is
held to its two by a test (`tests/perfbench/
test_perfbench_fixed_landing.py`), so the cell cannot be appended to
it. The fan-in driver stamps each sender's acknowledgement on the
benchmark's clock and the tick record holds the first, the median and
the last under `acks_s`; the global acknowledges a request once it is
decoded, admitted by the dedupe ledger and routed to the engine's
queue, whatever engine applies it afterwards. The median over the
timed ticks; a topology without senders of the benchmark's own gives
nothing to read.
"""

import statistics


def read(ctx):
    last = [t["acks_s"]["last"] for t in ctx["ticks"]
            if "last" in (t.get("acks_s") or {})]
    return float(statistics.median(last)) if last else None

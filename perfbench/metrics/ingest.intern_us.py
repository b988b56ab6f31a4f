"""ingest.intern_us: what the bridge spends minting a key into a slot.

1e6 x the seconds of the phase `local:ingest.intern` of a tick (the
readers' time inside `intern_key`'s slow path: a free slot taken, the
key put into its shard's map, the new-key record queued for the pump;
a tally the pump stamps once a tick) over the keys the local's tables
minted in it (`counters["keys.interned.local"]`, the engines' own
count). Not the map hit a key that holds a slot pays, not the pump's
`register`. The median over the timed ticks that minted a key; a
program without the tally stamps no such phase, and there is nothing
to read.
"""

import statistics

from perfbench.harness import phase_seconds

PHASE = "local:ingest.intern"


def read(ctx):
    costs = []
    for t in ctx["ticks"]:
        took = phase_seconds(t["phase_rows"]).get(PHASE)
        keys = t["counters"].get("keys.interned.local")
        if took is not None and keys:
            costs.append(1e6 * took / keys)
    return float(statistics.median(costs)) if costs else None

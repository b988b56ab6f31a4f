"""mesh.import_dispatches: device programs the mesh global dispatched
for a tick's imports.

`MeshAggregationEngine._last_flush_info["mesh_import_dispatches"]`,
which the tick record copies into `flush_path.global`: every call of the
routed SPMD ingest on behalf of an import (staged centroids, 8,192
points a call; imported counters and gauges at the flush), of
`merge_histo_scalars` (the exact-stats deltas of a landing) and of
`merge_set_rows` (64 forwarded register rows a call), counted under the
engine's lock where each is made, the interval's sum at the flush. Each
is a fixed-shape program over all four chips whatever the rows it
carries, so the count is what the import costs the device. The median
over the timed ticks; an engine without the counter leaves the metric
out.
"""

import statistics


def read(ctx):
    n = [t.get("flush_path", {}).get("global", {}).get(
        "mesh_import_dispatches") for t in ctx["ticks"]]
    n = [v for v in n if v is not None]
    return float(statistics.median(n)) if n else None

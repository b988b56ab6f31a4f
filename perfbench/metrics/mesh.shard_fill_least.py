"""mesh.shard_fill_least: how evenly the mesh global's histogram keys
lie over its shards.

`MeshAggregationEngine._last_flush_info["mesh_shard_rows"]`, which the
tick record copies into `flush_path.global`: the rows of each shard's
slice of the histogram bank that hold a key at the flush. The metric is
100 x the least of them over their mean: 100 where every chip owns the
same share of the keys, 0 where some chip owns none (slots handed out
in order 0, 1, 2, ... fill shard 0 first). The median over the timed
ticks; an engine without the counter, or a tick whose bank holds no key,
leaves the metric out.
"""

import statistics


def read(ctx):
    fills = []
    for t in ctx["ticks"]:
        rows = t.get("flush_path", {}).get("global", {}).get(
            "mesh_shard_rows")
        if rows and sum(rows) > 0:
            fills.append(100.0 * min(rows) * len(rows) / sum(rows))
    return float(statistics.median(fills)) if fills else None

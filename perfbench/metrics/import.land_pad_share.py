"""import.land_pad_share: the share of the lanes the global's import
landings handed the cluster program that carried no centroid.

A clustered landing lays its piles side by side in an [R, L] matrix
whose shape follows from the engine's configuration: R a work set
(1,024 or 8,192 rows at the north-star bank), L a step of the lane
ladder (128 .. 2,048, then the pre-cluster cap). The engine counts,
under its lock where a landing is decided, R x L
(`import_land_lanes`) and the lanes of it the piles fill
(`import_land_lanes_filled`); the flush notes the interval's sums in
`_last_flush_info`, which the tick record copies into
`flush_path.global`. 100 x (1 - filled / lanes) a tick is what the
fixed shapes cost in padding, the median over the timed ticks that
landed anything. `import_land_prechunked`, the piles the pre-cluster
loop cut first, rides beside them unread and should read 0 in every
cell. A program without the counters leaves the metric out.
"""

import statistics


def read(ctx):
    shares = []
    for t in ctx["ticks"]:
        info = t.get("flush_path", {}).get("global", {})
        lanes = info.get("import_land_lanes")
        filled = info.get("import_land_lanes_filled")
        if lanes and filled is not None:
            shares.append(100.0 * (1.0 - filled / lanes))
    return float(statistics.median(shares)) if shares else None

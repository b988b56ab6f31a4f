"""import.cluster_roofline: how close the import landing's cluster
program comes to the HBM roofline, by the work it is there to do.

The program (`ops/tdigest.py:cluster_rows`, `jit_cluster_rows` in a
profile) sorts each row's pile of (mean, weight) pairs and merges it
to at most C centroids. It must read every centroid staged, 8 bytes
(two f32), and write C clustered pairs for every row landed:

    least bytes = 8 x import_land_lanes_filled + 8 x C x import_land_rows

from the engine's own counters in every traced tick's
`flush_path.global`, never from the operands' padded shapes: a
landing padded to [8192, 4096] for one wide pile has no more to do
than its piles hold, and the share then says so. The least time is
those bytes over the chip's HBM bandwidth
(`perfbench/peaks/peaks.json`); the share is that over the program's
device time, both summed over the traced ticks. C follows from the
deployment's compression as `ops/tdigest.py:init` pads it. Useful
bytes cannot exceed what the program moves, so the share cannot pass
100%. A program without the counters, or a trace without the
program, leaves the metric out.
"""

import math

PROGRAM = "jit_cluster_rows"


def centroids_per_row(compression: float) -> int:
    return int(math.ceil((2.0 * compression + 8) / 128.0) * 128)


def least_bytes(lanes_filled: int, rows: int, compression: float) -> int:
    return 8 * lanes_filled + 8 * centroids_per_row(compression) * rows


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or ctx.get("peaks") is None:
        return None
    seconds = tr.get("module_seconds", {}).get(PROGRAM, 0.0)
    infos = [t.get("flush_path", {}).get("global", {})
             for t in ctx.get("ticks") or []]
    filled = [i["import_land_lanes_filled"] for i in infos
              if "import_land_lanes_filled" in i]
    if seconds <= 0 or not filled:
        return None
    compression = float(ctx["config"]["sketches"]["tdigest_compression"])
    least = least_bytes(
        sum(filled), sum(i.get("import_land_rows", 0) for i in infos),
        compression) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds

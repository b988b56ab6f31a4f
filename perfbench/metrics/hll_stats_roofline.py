"""hll_stats_roofline: how close the set-estimate kernel comes to the
HBM roofline.

The estimate has to read every register once: rows x 2^p bytes (u8
registers). The kernel is HBM-bound (it does a handful of integer and
exp2 operations per byte), so the least time is bytes over the chip's
HBM bandwidth (perfbench/peaks/peaks.json); the share is that over the
kernel's device time, summed over the traced ticks. Rows per call come
from the operand's shape in the trace where the trace carries it, and
otherwise from the deployment's `tpu_set_slots`.
"""

import re

KERNEL = re.compile(r"hll_stats|_stats_kernel")
SHAPE = re.compile(r"u8\[(\d+),(\d+)\]")


def bytes_read(rows: int, precision: int) -> int:
    return rows * (1 << precision)


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or ctx.get("peaks") is None:
        return None
    seconds = sum(s for name, s in tr["op_seconds"].items()
                  if KERNEL.search(name))
    calls = sum(n for name, n in tr["op_calls"].items()
                if KERNEL.search(name))
    if not calls or seconds <= 0:
        return None
    cfg = ctx["config"]
    rows = int(cfg["common"]["tpu_set_slots"])
    for name, text in tr.get("op_text", {}).items():
        m = KERNEL.search(name) and SHAPE.search(text)
        if m:
            rows = int(m.group(1))
            break
    least = (calls * bytes_read(rows, int(cfg["sketches"]["hll_precision"]))
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds

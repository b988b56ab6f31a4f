"""mesh.device_busy_least: the least busy chip's busy seconds over the
busiest's, in the traced window.

From the reduced trace's `busy_s_by_device` (`perfbench/tracered.py`:
the union of the intervals in which an operation ran on each device,
clipped to the timed ticks). The mesh global's programs run on all four
chips at fixed shapes, so the chips that hold only shards of the global
should be as busy as each other; the chip a local tier shares (device
0 of the two-tier cells) is busier by the local tier's programs. A trace
of fewer than two devices, or one in which no device ran anything, gives
nothing to read.
"""


def read(ctx):
    tr = ctx.get("trace")
    busy = list((tr or {}).get("busy_s_by_device", {}).values())
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * min(busy) / max(busy)

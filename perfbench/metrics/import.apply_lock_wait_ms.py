"""import.apply_lock_wait_ms: what the import worker waits for the
engine's lock, in a tick.

The phase `global:import.apply.lock_wait`, which `import_list` stamps
from its decode's return to the engine's lock acquired: the flusher's
swap, the history tier or a second worker holding it. A tick's
requests summed, the median over the timed ticks, in milliseconds. A
reader of its own because the harness keeps only phases with t1 > t0
and a line that lacks a listed metric is refused: a tick that decoded
a request and kept no lock_wait row waited 0.0 ms, and only ticks with
neither (a program without the phases) give nothing to read
(`perfbench/apply_split.py`).
"""

import statistics

from perfbench.apply_split import split


def read(ctx):
    waits = [s["lock_wait"] for s in map(split, ctx["ticks"]) if s]
    return 1000.0 * statistics.median(waits) if waits else None

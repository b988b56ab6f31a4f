"""import.compress_device_ms: device time a timed tick of the standalone
whole-bank t-digest compress, the program a profile shows as
`jit__compress_impl` (`ops/tdigest.py:compress`, the plain jit of
`_compress_impl` that the sketch engine's `compress(bank)` calls).

Call sites it sums: the one-chip engine's import landing,
`models/pipeline.py:_land_imports_clustered`, which compresses the whole
histogram bank once before `merge_centroids` and once after it: two
passes a landing, whatever the landing carries (111 ms a pass at the
north-star bank's 131,072 rows). Call sites it does not see: the
ingest's overflow compress (inside `jit_add_batch_impl` since PR 27),
the hot-slot sidestep's compress of the ingest executables
(`jit_compress_impl`, one underscore: `_kern["compress"]`), the flush
programs' own compress (fused into the flush executable) and the mesh
engine, whose sharded landing and flush compress inside `jit_merge` /
`jit_local`: a cell whose global is the mesh engine has nothing to read.

Traced seconds of the program's executions on the "XLA Modules" line,
clipped to the timed ticks and summed over the devices, over the number
of timed ticks, in milliseconds. A rate over all the work of the window,
not a median: a tick with one landing more pays two passes more.
"""

PROGRAM = "jit__compress_impl"


def read(ctx):
    tr = ctx.get("trace")
    ticks = ctx.get("ticks") or []
    if tr is None or not ticks:
        return None
    seconds = tr.get("module_seconds", {}).get(PROGRAM, 0.0)
    if seconds <= 0:
        return None
    return 1000.0 * seconds / len(ticks)

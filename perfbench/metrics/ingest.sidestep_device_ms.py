"""ingest.sidestep_device_ms: device time a timed tick of the hot-slot
sidestep's compress, the program a profile shows as `jit_compress_impl`
(one underscore: the ingest executables' `_kern["compress"]`,
`models/pipeline.py:_land_histos`). Where a pump batch holds more
samples of one key than a row's buffer is deep (256), the hot keys'
samples are clustered on the host and landed with one compress of the
whole histogram bank, one `merge_centroids` and one `merge_scalars`,
so that `add_batch` never sorts the bank once a buffer's depth of one
key's samples.

Under a Zipf law a few timer keys take thousands of samples a tick and
nearly every pump batch carries more of one than a buffer is deep, so
the sidestep runs a batch and its compress is most of the device's
seconds. Not the ingest's overflow compress (inside `jit_add_batch_impl`),
not the import landing's (`jit__compress_impl`, two underscores:
`import.compress_device_ms`), not the flush programs' own.

Traced seconds of the program's executions on the "XLA Modules" line,
clipped to the timed ticks and summed over the devices, over the number
of timed ticks, in milliseconds: a rate over all the work of the window.
A trace without the program leaves the metric out.
"""

PROGRAM = "jit_compress_impl"


def read(ctx):
    tr = ctx.get("trace")
    ticks = ctx.get("ticks") or []
    if tr is None or not ticks:
        return None
    seconds = tr.get("module_seconds", {}).get(PROGRAM, 0.0)
    if seconds <= 0:
        return None
    return 1000.0 * seconds / len(ticks)

"""Programs by name in one kept trace: executions and device seconds.

  python3 perfbench/study/module_calls.py <trace dir> [<trace dir> ...]

`run.py --trace 1 --keep-trace` leaves the profiler's files under
`chiprun_out/perfbench_trace/<cell>.<seed>`; the trace starts after
set-up, so everything in it is the window's. For each device's "XLA
Modules" line: every program's executions and seconds, largest first.
It is how a metric that sums one program's device time says which call
sites it sums: `jit__compress_impl` runs twice where `jit_cluster_rows`
runs once if the import landing is its only caller
(`perfbench/metrics/import.compress_device_ms.py`).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(dirs):
    from perfbench import tracered
    for log_dir in dirs:
        rows = tracered.load_xplane(tracered.find_xplane(log_dir))
        for dev, events in sorted(rows["modules"].items()):
            calls, secs = {}, {}
            for name, _start, dur in events:
                key = tracered.stable_name(name)
                calls[key] = calls.get(key, 0) + 1
                secs[key] = secs.get(key, 0.0) + dur / 1e9
            table = [[k, calls[k], round(secs[k], 6)]
                     for k in sorted(secs, key=lambda k: -secs[k])]
            print("MODULES " + json.dumps({"trace": log_dir, "device": dev,
                                           "programs": table}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])

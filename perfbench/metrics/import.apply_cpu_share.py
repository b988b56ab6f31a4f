"""import.apply_cpu_share: the share of its decode and stage seconds
the import worker spent on a CPU.

100 x the applying thread's CPU seconds (`time.thread_time_ns` around
the two stretches, the engine's `import_decode_cpu_ns` +
`import_stage_cpu_ns` of the tick's `flush_path.global`) over the wall
seconds of the phases `global:import.apply.decode` and `.stage` of the
same ticks, both summed over the timed ticks. Near 100 the worker
computes for as long as it runs, and only less work a sketch shortens
the tick; what is missing from 100 it spent runnable and not running
(the interpreter's lock, which the handler and sender threads share,
or the scheduler) and, in `stage`, waiting on a landing's fetches. On
the chip's host the thread's CPU clock advances in steps of 10 ms (every
reading of PR 39's runs is a multiple of it), so one request's reading
is coarse and only the window's sum says anything: hence sums, not a
median of ticks. A share on the host's clocks, so a rehearsal prints
none. A program without the counters gives nothing to read
(`perfbench/apply_split.py`).
"""

from perfbench.apply_split import CPU_NS, global_info, split


def read(ctx):
    cpu_ns, wall_s = 0, 0.0
    for t in ctx["ticks"]:
        s, info = split(t), global_info(t)
        if s and all(k in info for k in CPU_NS):
            cpu_ns += sum(info[k] for k in CPU_NS)
            wall_s += s["decode"] + s["stage"]
    return 100.0 * cpu_ns / 1e9 / wall_s if wall_s > 0 else None

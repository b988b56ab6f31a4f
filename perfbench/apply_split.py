"""`import.apply`, opened from inside: what its five readers share.

Since PR 39 `AggregationEngine.import_list` stamps three child phases
of the worker's `import.apply` run a request, into the engine's stamp
log, and the global's flush tick carries them beside the run:

  global:import.apply.decode     `wire.decode_metric_batch`, outside
                                 the engine's lock
  global:import.apply.lock_wait  decode returned -> lock acquired
  global:import.apply.stage      the lock hold: staging, tally and the
                                 landings that fall inside the batch

and the interval's tally (`_last_flush_info`, which the drivers copy
into the tick's `flush_path.global`) holds the applying thread's CPU
nanoseconds over the first and the third (`time.thread_time_ns`):
`import_decode_cpu_ns`, `import_stage_cpu_ns`.

`split(tick)` reads one tick record, or one row of a `--ticks-out` file
(which holds the phases' summed seconds under `phases`). A tick whose
global stamped no decode gives None: a program without the phases (the
parent of PR 39), or a tick that applied no request. The harness keeps
only phases with t1 > t0, so a tick with decode rows and no lock_wait
row waited 0.0 s.
"""

from __future__ import annotations

from perfbench.harness import phase_seconds

DECODE, LOCK_WAIT, STAGE = ("global:import.apply." + n
                            for n in ("decode", "lock_wait", "stage"))
CPU_NS = ("import_decode_cpu_ns", "import_stage_cpu_ns")


def split(tick: dict):
    """{"decode": s, "lock_wait": s, "stage": s} of a tick, or None."""
    secs = (phase_seconds(tick["phase_rows"]) if "phase_rows" in tick
            else tick.get("phases", {}))
    if DECODE not in secs:
        return None
    return {"decode": secs[DECODE], "lock_wait": secs.get(LOCK_WAIT, 0.0),
            "stage": secs.get(STAGE, 0.0)}


def global_info(tick: dict) -> dict:
    """The global engine's own note on the flush it ran."""
    return (tick.get("flush_path") or {}).get("global") or {}

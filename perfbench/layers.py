"""Per-layer metrics, each from a file of its own.

`perfbench/metrics/<name>.json` says where the number comes from, as
data:

  {"read": {"from": "spans",    "names": ["bench.local_flush"], ...}}
      the benchmark's own spans around calls into the servers
  {"read": {"from": "phases",   "names": ["local:engine.device.exec", ...]}}
      flight recorder phases, as the program names them, behind
      "local:" or "global:"
  {"read": {"from": "counters", "names": ["forward.bytes"]}}
      per-tick counter deltas
  {"read": {"from": "tick",     "names": ["emit_latency_s"]}}
      a field of the tick record
  {"read": {"from": "trace",    "what": "idle_share"}}
  {"read": {"from": "trace",    "what": "busy_share_in_span",
            "span": "bench.send"}}
      the reduced profiler trace (traced run only)
  {"read": {"from": "device",   "what": "peak_hbm_bytes"}}
  {"read": {"from": "run",      "what": "setup_s"}}

`"per": {"from": ..., "names": [...]}` turns the reduction into a
ratio of sums over the timed ticks (lines per second of send phase).

Per tick the named items are summed; over the run's timed ticks the
per-tick values are reduced by `"reduce"`: "sum", "mean", "median" or
"max" (default "median"); `"scale"` multiplies (seconds to ms: 1000). A metric
that needs other arithmetic brings `perfbench/metrics/<name>.py` with
`read(ctx) -> float | None`; the harness finds it by the metric's name.
A reader that finds nothing to read returns None and the metric is left
out of the line.

**The tick record** is the contract between a driver and these readers:
what `Driver.tick` returns for one tick, and `run.py` adds `index`,
`timed`, `payload` and `compared` to. Every topology gives `TICK_KEYS`:

  t_first_ns, t_last_ns, t_end_ns   the tick's edges on the monotonic
                  clock: its first operation offered, its last, and the
                  global's sink holding its flush. The window's clock
                  and the trace count first -> end
  emit_latency_s  (t_end - t_last) / 1e9;  wall_s  (t_end - t_first) / 1e9
  attempted       operations the tick offered (the driver's `OPS`)
  spans           {name: seconds} of the benchmark's own `bench.*` spans
  phase_rows      [(tier:name, t0_ns, t1_ns)] flight recorder phases of
                  each server's flush tick, behind `local:` / `global:`
  counters        {name: delta}, `compile.programs` always among them
  compiled        the names of the programs compiled in the tick
  gc_n, gc_s      collections inside last -> end, and their seconds

and a topology gives these where it has the thing (`OPTIONAL_TICK_KEYS`;
a reader of one finds nothing to read elsewhere and reports nothing):
`lines`, `ingest_s`, `gen_wait_s`, `t_landed_ns`,
`counters["bridge.lost_lines"]`, `counters["forward.bytes"]`,
`flush_path` (`{"local": ..., "global": ...}`, the engines' own notes on
the flush they ran) with a local tier; `acks_s` from the fan-in's
driver, and in a study's run (`--ticks-out`) `landing_shapes`, every
`[S, W]` the import landing clustered in the tick. `cpu_s`, `threads`,
`loadavg` ride along for the noise study.
"""

from __future__ import annotations

import statistics

from perfbench.harness import HERE, load_code, load_data, phase_seconds

REDUCTIONS = {"sum": sum, "mean": statistics.fmean,
              "median": statistics.median, "max": max}

TICK_KEYS = ("t_first_ns", "t_last_ns", "t_end_ns", "emit_latency_s",
             "wall_s", "attempted", "spans", "phase_rows", "counters",
             "compiled", "gc_n", "gc_s")
OPTIONAL_TICK_KEYS = ("lines", "ingest_s", "gen_wait_s", "t_landed_ns",
                      "flush_path", "landing_shapes", "acks_s", "cpu_s",
                      "threads", "loadavg")


def missing_keys(rec: dict) -> list:
    """The keys of the contract a tick record lacks."""
    out = [k for k in TICK_KEYS if k not in rec]
    if "compile.programs" not in rec.get("counters", {}):
        out.append("counters[compile.programs]")
    return out


def load_metric(name: str, root: str = HERE) -> dict:
    return load_data("metrics", name, root=root)


def _custom_reader(name: str, root: str):
    mod = load_code("metrics", name, root)
    return None if mod is None else mod.read


def _per_tick(tick: dict, src: str, names: list):
    if src == "spans":
        table = tick["spans"]
    elif src == "phases":
        table = phase_seconds(tick["phase_rows"])
    elif src == "counters":
        table = tick["counters"]
    elif src == "tick":
        table = tick
    else:
        raise ValueError(f"unknown source {src!r}")
    found = [table[n] for n in names if n in table]
    return sum(found) if found else None


def read_metric(name: str, ctx: dict, root: str = HERE):
    """The metric's value, or None where there is nothing to read.
    `ctx`: {"ticks": timed tick records, "trace": reduced trace or None,
    "device": {...}, "config": ..., "mix": ...}."""
    custom = _custom_reader(name, root)
    if custom is not None:
        return custom(ctx)
    spec = load_metric(name, root)
    read = spec["read"]
    src = read["from"]
    scale = float(read.get("scale", 1.0))
    if src == "trace":
        tr = ctx.get("trace")
        if tr is None:
            return None
        if read["what"] == "idle_share":
            v = tr["idle_share"]
        elif read["what"] == "busy_share_in_span":
            v = tr["busy_share_in_span"].get(read["span"])
        else:
            raise ValueError(f"unknown trace reading {read['what']!r}")
        return None if v is None else v * scale
    if src in ("device", "run"):
        v = ctx[src].get(read["what"])
        return None if v is None else v * scale
    vals = [v for v in (_per_tick(t, src, read["names"])
                        for t in ctx["ticks"]) if v is not None]
    if not vals:
        return None
    if "per" in read:
        # a rate or a share taken over all the work and all the time of
        # the window: sum over ticks of one thing per sum of another
        per = read["per"]
        den = [v for v in (_per_tick(t, per["from"], per["names"])
                           for t in ctx["ticks"]) if v is not None]
        return sum(vals) / sum(den) * scale if den and sum(den) > 0 else None
    return REDUCTIONS[read.get("reduce", "median")](vals) * scale

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
"""

from __future__ import annotations

import importlib.util
import os
import statistics

from perfbench.tiers import phase_seconds
from perfbench.traffic import HERE, load_data

REDUCTIONS = {"sum": sum, "mean": statistics.fmean,
              "median": statistics.median, "max": max}


def load_metric(name: str, root: str = HERE) -> dict:
    return load_data("metrics", name, root=root)


def _custom_reader(name: str, root: str):
    path = os.path.join(root, "metrics", name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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

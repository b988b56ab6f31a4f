"""From a profiler trace to numbers: the reduction every PR shares.

`load_xplane` flattens the profiler's `.xplane.pb` (read with nothing
but `jax.profiler.ProfileData`) into plain rows
`{"device": {ordinal: [[name, start_ns, dur_ns], ...]},
  "modules": {ordinal: [[name, start_ns, dur_ns], ...]},
  "host": [[name, start_ns, dur_ns], ...]}` —
device rows are the operations of each device's "XLA Ops" line (the
name is the HLO text, shapes included), module rows its "XLA Modules"
line (one row per executed program), host rows the `bench.*`
TraceAnnotations. `reduce_trace` then works on those
rows alone (a small recorded trace in this form is kept with the tests):

  busy            union of the intervals in which an operation ran on
                  the device, clipped to the window
  idle share      1 - busy / window
  gap attribution the idle time inside each host span, by the deepest
                  span that covers it: `bench.local_flush/local:forward.send`
  op seconds      summed device time by operation, under stable names
                  (no fingerprints, no operand shapes)

The host's spans are on `time.monotonic_ns`; the trace's clock starts
at 0 with the trace. `bench.clock_sync`, a span the harness opens first
inside the trace, gives the offset between the two.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

SYNC = "bench.clock_sync"
_LINES = {"XLA Ops": "device", "XLA Modules": "modules"}
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load_xplane(path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = {"device": {}, "modules": {}, "host": []}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name not in _LINES:
                    continue
                rows = out[_LINES[line.name]].setdefault(int(m.group(1)), [])
                for ev in line.events:
                    rows.append([ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)])
    return out


def stable_name(name: str) -> str:
    """`jit__compress_impl(1710561601329)` -> `jit__compress_impl`;
    `%fusion.3 = f32[131072,256]{1,0:T(8,128)} fusion(...)` -> `fusion.3`."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name).strip()


def merge_intervals(starts, ends):
    """Union of [start, end) intervals as two sorted arrays."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts, float)[order], np.asarray(ends, float)[order]
    run_end = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > run_end[:-1]])
    idx = np.nonzero(new)[0]
    return s[idx], np.maximum.reduceat(e, idx)


class Busy:
    """Cumulative busy time of one device: `upto(t)` is the nanoseconds
    the device was busy before t."""

    def __init__(self, rows):
        s = np.array([r[1] for r in rows], float)
        e = s + np.array([r[2] for r in rows], float)
        self.s, self.e = merge_intervals(s, e)
        self.cum = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def upto(self, t):
        t = np.asarray(t, float)
        i = np.searchsorted(self.s, t, side="right")     # intervals begun
        full = self.cum[i]
        # the last begun interval may still be running at t
        last = np.clip(i - 1, 0, None)
        over = np.where(i > 0, np.maximum(self.e[last] - t, 0.0), 0.0) \
            if len(self.s) else np.zeros_like(t)
        return full - over

    def within(self, a, b):
        return float(self.upto(b) - self.upto(a))


def _partition(spans):
    """Cut time at every span edge; label each piece by the covering
    spans from the outermost to the deepest. `spans`: (name, t0, t1,
    depth). Returns [(t0, t1, label)] for pieces some span covers."""
    edges = sorted({t for _n, a, b, _d in spans for t in (a, b)})
    out = []
    for a, b in zip(edges[:-1], edges[1:]):
        mid = (a + b) / 2
        cover = [(d, t1 - t0, n) for n, t0, t1, d in spans if t0 <= mid < t1]
        if not cover:
            continue
        top = min(c for c in cover if c[0] == 0) if any(
            c[0] == 0 for c in cover) else None
        deep = [c for c in cover if c[0] > 0]
        label = top[2] if top else ""
        if deep:
            # the deepest phase is the shortest one that covers the piece
            label = (label + "/" if label else "") + min(
                deep, key=lambda c: c[1])[2]
        out.append((a, b, label))
    return out


def clock_offset(trace: dict, bench_rows: list) -> float:
    """trace_ns = monotonic_ns - offset, from the sync span."""
    ev = [r for r in trace["host"] if r[0] == SYNC]
    mono = [r for r in bench_rows if r[0] == SYNC]
    if not ev or not mono:
        raise ValueError("the trace has no bench.clock_sync span")
    return mono[0][1] - ev[0][1]


def reduce_trace(trace: dict, bench_rows: list, phase_rows: list,
                 windows: list, top: int = 10) -> dict:
    """`bench_rows` / `phase_rows`: (name, t0_ns, t1_ns) on the
    monotonic clock (benchmark spans; flight recorder phases);
    `windows`: the timed ticks, [(t0_ns, t1_ns)] on the monotonic clock.
    What the benchmark does between ticks (checking against the
    reference) is outside every window, so it is nobody's idle time."""
    off = clock_offset(trace, bench_rows)
    wins = sorted((a - off, b - off) for a, b in windows)
    devices = sorted(trace["device"])
    if not devices or not wins:
        raise ValueError("the trace has no device plane, or no window")
    busy = {d: Busy(trace["device"][d]) for d in devices}
    busy_ns = {d: sum(busy[d].within(a, b) for a, b in wins)
               for d in devices}
    window_ns = sum(b - a for a, b in wins)
    first = busy[devices[0]]

    def clip(a, b):
        return [(max(a, w0), min(b, w1)) for w0, w1 in wins
                if min(b, w1) > max(a, w0)]

    spans = [(n, a - off, b - off, 0) for n, a, b in bench_rows if n != SYNC]
    spans += [(n, a - off, b - off, 1) for n, a, b in phase_rows]
    spans = [s for s in spans if clip(s[1], s[2])]
    gaps: dict = {}
    covered = 0.0
    for a, b, label in _partition(spans):
        for a1, b1 in clip(a, b):
            idle = (b1 - a1) - first.within(a1, b1)
            gaps[label] = gaps.get(label, 0.0) + idle
            covered += idle
    gaps["(no span)"] = max(0.0, window_ns - busy_ns[devices[0]] - covered)
    in_span: dict = {}
    for name in {s[0] for s in spans if s[3] == 0}:
        rows = [r for n, a, b, d in spans if n == name and d == 0
                for r in clip(a, b)]
        total = sum(b - a for a, b in rows)
        in_span[name] = (sum(first.within(a, b) for a, b in rows) / total
                         if total > 0 else None)
    def totals(table):
        secs, calls, text = {}, {}, {}
        for d in devices:
            for name, start, dur in table.get(d, []):
                if not clip(start, start + dur):
                    continue
                key = stable_name(name)
                secs[key] = secs.get(key, 0.0) + dur / 1e9
                calls[key] = calls.get(key, 0) + 1
                text.setdefault(key, name)
        return secs, calls, text

    ops, calls, shapes = totals(trace["device"])
    mods, _mod_calls, _ = totals(trace.get("modules", {}))
    # the programs that took most time, then the operations inside them
    ranked = (sorted(mods.items(), key=lambda kv: -kv[1])[:4]
              + sorted(ops.items(), key=lambda kv: -kv[1]))
    gap_rank = sorted(gaps.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": float(np.mean([busy_ns[d] for d in devices])) / 1e9,
        "busy_s_by_device": {str(d): busy_ns[d] / 1e9 for d in devices},
        "idle_share": 1.0 - busy_ns[devices[0]] / window_ns,
        "busy_share_in_span": in_span,
        "op_seconds": ops, "op_calls": calls, "op_text": shapes,
        "module_seconds": mods,
        "breakdown": {
            "device_ops": [[k, v] for k, v in ranked[:top]],
            "idle_gaps": [[k, v / 1e9] for k, v in gap_rank[:top] if v > 0]},
    }

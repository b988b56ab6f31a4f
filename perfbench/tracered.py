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

import bisect
import glob
import heapq
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
    depth). Returns [(t0, t1, label)] for pieces some span covers.
    One sweep over the sorted edges: a heap of the benchmark's spans
    (depth 0) and one of the phases, each with the shortest span that
    covers the piece on top; a span leaves when the sweep has passed its
    end."""
    edges = sorted({t for _n, a, b, _d in spans for t in (a, b)})
    begun = sorted(range(len(spans)), key=lambda i: spans[i][1])
    heaps = ([], [])            # (length, tie, end, name): depth 0, deeper
    out, nxt = [], 0
    for a, b in zip(edges[:-1], edges[1:]):
        while nxt < len(begun) and spans[begun[nxt]][1] <= a:
            i = begun[nxt]
            n, t0, t1, d = spans[i]
            # ties as a scan in the spans' order broke them: the
            # benchmark's spans by name, the phases by the first listed
            heapq.heappush(heaps[d > 0], (t1 - t0, i if d else n, t1, n))
            nxt += 1
        for heap in heaps:
            while heap and heap[0][2] <= a:
                heapq.heappop(heap)
        top, deep = heaps
        if not top and not deep:
            continue
        label = top[0][3] if top else ""
        if deep:
            # the deepest phase is the shortest one that covers the piece
            label = (label + "/" if label else "") + deep[0][3]
        out.append((a, b, label))
    return out


class Windows:
    """The timed ticks on the trace's clock, sorted and apart from each
    other, so that what overlaps an interval is found by bisection."""

    def __init__(self, wins):
        self.wins = sorted(wins)
        self.w0 = np.array([a for a, _b in self.wins], float)
        self.w1 = np.array([b for _a, b in self.wins], float)
        if (self.w1 <= self.w0).any() or (self.w0[1:] < self.w1[:-1]).any():
            raise ValueError("a window is empty, or two windows overlap")

    def clip(self, a, b):
        """The parts of [a, b) inside the windows."""
        i = bisect.bisect_right(self.w1, a)       # windows that end after a
        j = bisect.bisect_left(self.w0, b)        # and start before b
        return [(max(a, w0), min(b, w1))
                for w0, w1 in (self.wins[i:j] if b > a else [])]

    def touched(self, starts, ends):
        """For each [start, end) whether any of it is inside a window."""
        i = np.searchsorted(self.w1, starts, side="right")
        j = np.searchsorted(self.w0, ends, side="left")
        return (j > i) & (ends > starts)


def clock_offset(trace: dict, bench_rows: list) -> float:
    """trace_ns = monotonic_ns - offset, from the sync span."""
    ev = [r for r in trace["host"] if r[0] == SYNC]
    mono = [r for r in bench_rows if r[0] == SYNC]
    if not ev or not mono:
        raise ValueError("the trace has no bench.clock_sync span")
    return mono[0][1] - ev[0][1]


def refuse_cut_short(trace: dict, bench_rows: list, windows: list):
    """The profiler keeps so many events a device line and drops what
    comes after. A line whose last event ends before the last timed tick
    starts was cut short: its busy seconds, idle share and gaps are the
    missing tail, not readings. Every tick of every cell dispatches
    device work, so a whole trace cannot trip it. Raises ValueError
    with both times; returns `({"<device>/<line>": end_s}, (start_s,
    end_s))`, every line that has events and the last timed tick, in
    seconds on the trace's clock."""
    off = clock_offset(trace, bench_rows)
    t0, t1 = ((t - off) / 1e9 for t in max(windows))
    ends = {f"{d}/{line}": max(r[1] + r[2] for r in rows) / 1e9
            for line in ("device", "modules")
            for d, rows in sorted(trace.get(line, {}).items()) if rows}
    if not any(k.endswith("/device") for k in ends):
        raise ValueError("the trace has no device event")
    short = {k: e for k, e in ends.items() if e < t0}
    if short:
        raise ValueError(
            f"the trace was cut short: the last timed tick runs from "
            f"{t0:.3f}s to {t1:.3f}s on the trace's clock, and these "
            f"device lines end before it starts: {short}")
    return ends, (t0, t1)


def reduce_trace(trace: dict, bench_rows: list, phase_rows: list,
                 windows: list, top: int = 10) -> dict:
    """`bench_rows` / `phase_rows`: (name, t0_ns, t1_ns) on the
    monotonic clock (benchmark spans; flight recorder phases);
    `windows`: the timed ticks, [(t0_ns, t1_ns)] on the monotonic clock.
    What the benchmark does between ticks (checking against the
    reference) is outside every window, so it is nobody's idle time."""
    off = clock_offset(trace, bench_rows)
    devices = sorted(trace["device"])
    if not devices or not windows:
        raise ValueError("the trace has no device plane, or no window")
    wins = Windows((a - off, b - off) for a, b in windows)
    busy = {d: Busy(trace["device"][d]) for d in devices}
    busy_ns = {d: float(np.sum(busy[d].upto(wins.w1) - busy[d].upto(wins.w0)))
               for d in devices}
    window_ns = float(np.sum(wins.w1 - wins.w0))
    first = busy[devices[0]]

    def idle_and_busy(pieces):
        """Of [(a, b)] inside the windows: each piece's idle and busy
        nanoseconds on the first device."""
        a = np.array([p[0] for p in pieces], float)
        b = np.array([p[1] for p in pieces], float)
        ran = first.upto(b) - first.upto(a)
        return (b - a) - ran, ran

    spans = [(n, a - off, b - off, 0) for n, a, b in bench_rows if n != SYNC]
    spans += [(n, a - off, b - off, 1) for n, a, b in phase_rows]
    inside = wins.touched(np.array([s[1] for s in spans], float),
                          np.array([s[2] for s in spans], float))
    spans = [s for s, keep in zip(spans, inside) if keep]
    pieces = [(a1, b1, label) for a, b, label in _partition(spans)
              for a1, b1 in wins.clip(a, b)]
    gaps: dict = {}
    covered = 0.0
    for (_a, _b, label), idle in zip(pieces,
                                     idle_and_busy(pieces)[0].tolist()):
        gaps[label] = gaps.get(label, 0.0) + idle
        covered += idle
    gaps["(no span)"] = max(0.0, window_ns - busy_ns[devices[0]] - covered)
    in_span: dict = {}
    for name in {s[0] for s in spans if s[3] == 0}:
        rows = [r for n, a, b, d in spans if n == name and d == 0
                for r in wins.clip(a, b)]
        total = sum(b - a for a, b in rows)
        in_span[name] = (float(np.sum(idle_and_busy(rows)[1])) / total
                         if total > 0 else None)

    def totals(table):
        """Seconds, calls and the first full text of every operation
        that ran inside a window, by stable name, in the order met."""
        rows = [r for d in devices for r in table.get(d, [])]
        start = np.array([r[1] for r in rows], float)
        dur = np.array([r[2] for r in rows], float)
        met = np.nonzero(wins.touched(start, start + dur))[0]
        texts: dict = {}                # full text -> column, as met
        col = np.array([texts.setdefault(rows[i][0], len(texts))
                        for i in met], int)
        text_s = np.bincount(col, weights=dur[met] / 1e9,
                             minlength=len(texts))
        text_n = np.bincount(col, minlength=len(texts))
        secs, calls, text = {}, {}, {}
        for name, n in texts.items():
            key = stable_name(name)
            secs[key] = secs.get(key, 0.0) + float(text_s[n])
            calls[key] = calls.get(key, 0) + int(text_n[n])
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

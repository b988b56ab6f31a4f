"""What a traced run costs is fixed by the benchmark (PR 41): the
window's stop rule (`run.window_open`: at most `TRACED_TICKS` timed
ticks under the profiler, `--seconds` alone without it), a rehearsal
that ends on it, the trace that was cut short refused
(`tracered.refuse_cut_short`), and the reduction, which no longer walks
every window for every event, held to what it returned: `_partition`
and `reduce_trace` as they were at PR 39 are kept below as the
reference, on random spans, on a synthetic trace of many ticks, on the
hand-made trace and on the two traces recorded on the v5e."""

import json
import math
import os
import random
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import run, tracered  # noqa: E402
from perfbench.tracered import SYNC, Busy, clock_offset, stable_name  # noqa: E402

MS = 1_000_000          # ns
OFF = 5_000 * MS        # monotonic clock = trace clock + OFF


# ------------------------------------------------------------ the stop rule

def ticks_taken(tick_s, seconds, traced):
    k, measured_s = 0, 0.0
    while run.window_open(k, measured_s, seconds, traced):
        measured_s += tick_s
        k += 1
    return k


@pytest.mark.parametrize("tick_s, seconds, traced, want", [
    (0.9, 51, True, 16),        # the four-chip fleet_1k: 55 ticks untraced
    (0.45, 51, True, 16),       # the same on a tree that ticks twice as fast
    (0.05, 51, True, 16),
    (15.2, 51, True, 4),        # wide_100k: `--seconds` comes first
    (7.9, 51, True, 7),
    (60.0, 51, True, 1),        # a tick longer than the window is still run
    (0.5, 4, True, 8),
    (0.9, 51, False, 57),       # untraced: `--seconds` alone, 16 ignored
    (0.45, 51, False, 114),
    (15.2, 51, False, 4),
    (60.0, 51, False, 1),
    (0.05, 1, False, 20),
])
def test_the_window_closes_as_the_rule_says(tick_s, seconds, traced, want):
    assert ticks_taken(tick_s, seconds, traced) == want


def test_the_rule_by_the_case():
    assert run.TRACED_TICKS == 16
    for traced in (False, True):
        assert run.window_open(0, 0.0, 51, traced)          # always a first
        assert run.window_open(0, 99.0, 51, traced)
        assert run.window_open(15, 50.9, 51, traced)
        assert not run.window_open(15, 51.0, 51, traced)
        assert not run.window_open(3, 51.2, 51, traced)
    assert not run.window_open(16, 1.0, 51, True)
    assert not run.window_open(17, 1.0, 51, True)
    assert run.window_open(16, 1.0, 51, False)
    assert run.window_open(1000, 50.0, 51, False)


def test_the_command_has_no_new_switch():
    """The cap is a constant of the benchmark: nothing the driver's
    command, the environment or BENCHMARK.json could set."""
    with open(os.path.join(REPO, "perfbench", "run.py")) as f:
        src = f.read()
    flags = set(re.findall(r'add_argument\("(--[a-z-]+)"', src))
    assert flags == {"--workload", "--seed", "--seconds", "--trace",
                     "--rehearsal", "--control", "--keep-trace",
                     "--ticks-out"}
    assert "TRACED_TICKS" not in json.dumps(run.load_manifest())
    assert not re.search(r"environ[^\n]*TRACED", src)


# ------------------------------------------------- a rehearsal ends on it

CELL = "fanin32_global_1chip.fleet_1k"        # the shortest set-up


def rehearse(trace, seconds, cache_dir):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 41), "--seconds",
         str(seconds), "--trace", str(trace), "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    m = re.search(r"^window: (\d+) timed ticks, ([\d.]+)s of ticks",
                  p.stdout, re.M)
    timed = [ln for ln in p.stdout.splitlines()
             if re.match(r"tick \d+ timed: ", ln)]
    assert len(timed) == int(m.group(1))
    # every timed tick was compared: its numbers stand on its line
    assert all("exact_mismatches 0 (limit 0)" in ln for ln in timed)
    assert out["correct"] is True and out["failed"] == 0
    return int(m.group(1)), float(m.group(2)), p.stdout


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perfbench_jax_cache"))


def test_a_traced_rehearsal_ends_at_sixteen_ticks_and_an_untraced_does_not(
        cache_dir):
    """A rehearsal's tick is some 0.12 s, so sixteen of them cannot
    reach 8 s of ticks."""
    ticks, measured_s, out = rehearse(1, 8, cache_dir)
    assert ticks == run.TRACED_TICKS == 16 and measured_s < 8
    line = next(ln for ln in out.splitlines()
                if ln.startswith("traced run, 16 timed ticks: "))
    for stage in ("set-up", "ticks", "between ticks", "comparisons",
                  "stop_trace"):
        assert re.search(rf"\b{stage} [\d.]+s", line), line
    ticks, measured_s, out = rehearse(0, 8, cache_dir)
    assert ticks > 16 and measured_s >= 8
    assert "traced run" not in out


# ------------------------------------ the reduction as it was, for reference

def partition_before(spans):
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
            label = (label + "/" if label else "") + min(
                deep, key=lambda c: c[1])[2]
        out.append((a, b, label))
    return out


def reduce_before(trace, bench_rows, phase_rows, windows, top=10):
    off = clock_offset(trace, bench_rows)
    wins = sorted((a - off, b - off) for a, b in windows)
    devices = sorted(trace["device"])
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
    gaps = {}
    covered = 0.0
    for a, b, label in partition_before(spans):
        for a1, b1 in clip(a, b):
            idle = (b1 - a1) - first.within(a1, b1)
            gaps[label] = gaps.get(label, 0.0) + idle
            covered += idle
    gaps["(no span)"] = max(0.0, window_ns - busy_ns[devices[0]] - covered)
    in_span = {}
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


def assert_same(a, b, path=""):
    """Key for key, in the same order (a rank follows it), floats to
    1e-9 relative."""
    assert type(a) is type(b), (path, a, b)
    if isinstance(a, dict):
        assert list(a) == list(b) or (
            path == "/busy_share_in_span" and set(a) == set(b)), path
        for k in a:
            assert_same(a[k], b[k], f"{path}/{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            assert_same(x, y, f"{path}[{i}]")
    elif isinstance(a, float):
        assert math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-15), (path, a, b)
    else:
        assert a == b, (path, a, b)


def random_spans(rnd, n):
    """Nested, overlapping, touching and empty spans on few distinct
    edges, so that ties of start, end and length all occur."""
    spans = []
    for _ in range(n):
        a = rnd.randint(0, 1000)
        b = a + rnd.choice([0, 1, 5, 50, 300, rnd.randint(0, 1000)])
        spans.append((f"s{rnd.randint(0, 20)}", float(a), float(b),
                      rnd.choice([0, 0, 1, 1, 1])))
    return spans


@pytest.mark.parametrize("seed, n", [(1, 1), (2, 2), (3, 7), (4, 40),
                                     (5, 300), (6, 300), (7, 500),
                                     (8, 800)])
def test_the_sweep_cuts_and_labels_as_the_scan_did(seed, n):
    spans = random_spans(random.Random(seed), n)
    assert tracered._partition(spans) == partition_before(spans)


def test_the_sweep_on_a_ticks_rows():
    """Eight ticks as a fan-in cell hands them over: four benchmark
    spans and 179 phase rows a tick, nested three deep."""
    rnd = random.Random(41)
    spans = []
    for k in range(8):
        t0 = k * 1500.0
        for j, name in enumerate(("bench.forwards", "bench.global_drain",
                                  "bench.global_flush", "bench.sink_wait")):
            spans.append((name, t0 + j * 250.0, t0 + (j + 1) * 250.0, 0))
        for j in range(179):
            a = t0 + rnd.uniform(0, 990)
            spans.append((f"global:import.p{j % 40}", a,
                          a + rnd.uniform(0.01, 200) * (1 if j % 5 else 0.01),
                          1))
    got = tracered._partition(spans)
    assert got == partition_before(spans) and len(got) > 2000
    assert tracered._partition([]) == []


def synthetic_trace(ticks, per_tick, devices=4):
    """`ticks` windows of 1 s, 0.7 s apart, on `devices` device lines of
    `per_tick` operations a tick that also run between the windows and
    across their edges; 179 phase rows and four benchmark spans a tick."""
    rnd = random.Random(9)
    trace = {"device": {}, "modules": {}, "host": [[SYNC, 0.0, 1000.0]]}
    bench = [(SYNC, float(OFF), OFF + 1000.0)]
    phases, wins = [], []
    t = 1e6
    for _k in range(ticks):
        t0, t1 = t, t + 1e9
        wins.append((t0 + OFF, t1 + OFF))
        for j, name in enumerate(("bench.a", "bench.b", "bench.c",
                                  "bench.d")):
            bench.append((name, OFF + t0 + j * 2.5e8,
                          OFF + t0 + (j + 1) * 2.5e8))
        for j in range(179):
            a = t0 + rnd.uniform(-1e8, 9.9e8)
            phases.append((f"global:p{j % 40}", OFF + a,
                           OFF + a + rnd.uniform(1e4, 2e8)))
        for d in range(devices):
            ops = trace["device"].setdefault(d, [])
            mods = trace["modules"].setdefault(d, [])
            s = t0 - 2e8
            for i in range(per_tick):
                s += rnd.uniform(1e3, 3.2e9 / per_tick)
                dur = rnd.choice([0.0, rnd.uniform(0, 1e9 / per_tick)])
                ops.append([f"%fusion.{i % 50} = f32[{i % 7},128] "
                            f"fusion(...)", s, dur])
                if i % 20 == 0:
                    mods.append([f"jit_prog{i % 3}({i % 5})", s, dur * 10])
        t = t1 + 7e8
    return trace, bench, phases, wins


def test_the_reduction_returns_what_it_returned_on_many_ticks():
    trace, bench, phases, wins = synthetic_trace(12, 400)
    got = tracered.reduce_trace(trace, bench, phases, wins)
    assert_same(got, reduce_before(trace, bench, phases, wins))
    assert len(got["op_seconds"]) == 50 and len(got["module_seconds"]) == 3
    assert got["window_s"] == pytest.approx(12.0)
    assert tracered.refuse_cut_short(trace, bench, wins)[1] == (
        pytest.approx(wins[-1][0] / 1e9 - 5.0),
        pytest.approx(wins[-1][1] / 1e9 - 5.0))


def mono(name, a_ms, b_ms):
    return (name, a_ms * MS + OFF, b_ms * MS + OFF)


def hand_made():
    """`test_perfbench_trace.py`'s: device 0 runs 0-10, 5-20, 40-50 and
    90-100 ms, device 1 only 0-10; one tick of 0-100 ms."""
    op = "%fusion.3 = f32[8,128]{1,0} fusion(...)"
    trace = {
        "device": {0: [[op, 0, 10 * MS], [op, 5 * MS, 15 * MS],
                       ["%sort.8 = (f32[8]) sort(...)", 40 * MS, 10 * MS],
                       ["%sort.9 = (f32[8]) sort(...)", 90 * MS, 10 * MS]],
                   1: [[op, 0, 10 * MS]]},
        "modules": {0: [["jit__compress_impl(17105616013299372607)", 0,
                         20 * MS], ["jit_flush(99)", 40 * MS, 10 * MS]]},
        "host": [[SYNC, -2 * MS, 1 * MS]],
    }
    bench = [mono(SYNC, -2, -1), mono("bench.send", 0, 30),
             mono("bench.local_flush", 30, 100)]
    phases = [mono("local:forward.send", 60, 100),
              mono("local:egress.attempt", 70, 95)]
    return trace, bench, phases


@pytest.mark.parametrize("windows_ms", [[(0, 100)], [(0, 30), (60, 100)],
                                        [(10, 45), (45, 95)], [(20, 40)]])
def test_the_reduction_returns_what_it_returned_on_the_hand_made_trace(
        windows_ms):
    trace, bench, phases = hand_made()
    windows = [(a * MS + OFF, b * MS + OFF) for a, b in windows_ms]
    assert_same(tracered.reduce_trace(trace, bench, phases, windows),
                reduce_before(trace, bench, phases, windows))


def recorded(name):
    with open(os.path.join(HERE, name)) as f:
        rows = json.load(f)
    return rows, {"host": rows["host"],
                  "device": {int(d): ev for d, ev in rows["device"].items()},
                  "modules": {int(d): ev
                              for d, ev in rows["modules"].items()}}


def recorded_wide_100k():
    """As `test_perfbench_trace.py` reduces it: one window over the
    recording's host rows."""
    rows, trace = recorded("recorded_trace_wide_100k.json")
    bench = [(n, a, a + d) for n, a, d in rows["host"]]
    tick = [r for r in bench if r[0] != SYNC]
    return trace, bench, [(min(r[1] for r in tick), max(r[2] for r in tick))]


def recorded_fleet_1k():
    """As `test_perfbench_fixed_landing.py` reduces it: its first two
    ticks."""
    rows, trace = recorded("recorded_trace_fleet_1k.json")
    off = 7_000 * MS
    bench = [(n, a + off, a + dur + off) for n, a, dur in rows["host"]]
    first = [r for r in bench if r[0] == "bench.forwards"][:2]
    last = [r for r in bench if r[0] == "bench.sink_wait"][:2]
    return trace, bench, [(a[1], b[2]) for a, b in zip(first, last)]


@pytest.mark.parametrize("load", [recorded_wide_100k, recorded_fleet_1k])
def test_the_reduction_returns_what_it_returned_on_the_recorded_traces(load):
    trace, bench, windows = load()
    got = tracered.reduce_trace(trace, bench, [], windows)
    assert_same(got, reduce_before(trace, bench, [], windows))
    assert got["busy_s"] > 0 and len(got["op_seconds"]) > 10
    # the recording's own spans as phases: the labels nest
    phases = [r for r in bench if r[0] != SYNC]
    assert_same(tracered.reduce_trace(trace, bench, phases, windows),
                reduce_before(trace, bench, phases, windows))


def test_windows_that_overlap_are_refused():
    trace, bench, phases = hand_made()
    for bad in ([(0, 60), (50, 100)], [(0, 50), (70, 70)]):
        with pytest.raises(ValueError, match="overlap"):
            tracered.reduce_trace(trace, bench, phases, [
                (a * MS + OFF, b * MS + OFF) for a, b in bad])


# --------------------------------------------- a trace that was cut short

def test_a_device_line_that_ends_before_the_last_tick_starts_is_refused():
    trace, bench, _phases = hand_made()
    trace["device"].pop(1)
    two = [(0 * MS + OFF, 30 * MS + OFF), (101 * MS + OFF, 130 * MS + OFF)]
    with pytest.raises(ValueError, match="cut short") as e:
        tracered.refuse_cut_short(trace, bench, two)
    # both times, and every line that is short
    assert "from 0.101s to 0.130s" in str(e.value)
    assert "'0/device': 0.1" in str(e.value)
    assert "'0/modules': 0.05" in str(e.value)


def test_a_device_line_that_ends_inside_the_last_tick_is_not():
    trace, bench, _phases = hand_made()
    trace["device"].pop(1)
    trace["modules"][0].append(["jit_flush(99)", 95 * MS, 3 * MS])
    two = [(0 * MS + OFF, 30 * MS + OFF), (90 * MS + OFF, 130 * MS + OFF)]
    ends, tick = tracered.refuse_cut_short(trace, bench, two)
    assert ends == {"0/device": pytest.approx(0.100),
                    "0/modules": pytest.approx(0.098)}
    assert tick == (pytest.approx(0.090), pytest.approx(0.130))
    # an event that ends on the tick's first nanosecond is inside it
    edge = [(0 * MS + OFF, 30 * MS + OFF), (98 * MS + OFF, 130 * MS + OFF)]
    assert tracered.refuse_cut_short(trace, bench, edge)[0] == ends


def test_any_line_with_events_counts_and_one_without_does_not():
    trace, bench, _phases = hand_made()
    one = [(0 * MS + OFF, 100 * MS + OFF)]
    ends, _tick = tracered.refuse_cut_short(trace, bench, one)
    assert sorted(ends) == ["0/device", "0/modules", "1/device"]
    # device 1 ran 0-10 ms only: short of a tick that starts at 60 ms
    two = [(0 * MS + OFF, 30 * MS + OFF), (60 * MS + OFF, 100 * MS + OFF)]
    with pytest.raises(ValueError, match="cut short") as e:
        tracered.refuse_cut_short(trace, bench, two)
    assert "'1/device': 0.01" in str(e.value)
    assert "0/device" not in str(e.value)
    # a chip the cell does not use has a plane and no events
    trace["device"][1] = []
    trace["modules"][0].append(["jit_flush(99)", 80 * MS, 3 * MS])
    assert sorted(tracered.refuse_cut_short(trace, bench, two)[0]) == [
        "0/device", "0/modules"]


def test_a_trace_without_a_device_event_or_the_sync_span_is_refused():
    trace, bench, _phases = hand_made()
    one = [(0 * MS + OFF, 100 * MS + OFF)]
    with pytest.raises(ValueError, match="no device event"):
        tracered.refuse_cut_short(dict(trace, device={0: []}), bench, one)
    with pytest.raises(ValueError, match="clock_sync"):
        tracered.refuse_cut_short(dict(trace, host=[]), bench, one)


@pytest.mark.parametrize("load", [recorded_wide_100k, recorded_fleet_1k])
def test_the_recorded_traces_device_lines_reach_their_last_window(load):
    trace, bench, windows = load()
    ends, (tick0, tick1) = tracered.refuse_cut_short(trace, bench, windows)
    assert tick0 < ends["0/device"] and tick0 < tick1

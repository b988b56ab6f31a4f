"""The reduction from a profiler trace to numbers, on rows whose answer
is known by hand and on a small trace recorded on the v5e
(`recorded_trace_wide_100k.json`, written by
`perfbench/study/dump_trace.py` from a traced `wide_100k` run): busy
union, idle share, gap attribution to the covering host span, stable
operation names, and `hll_stats_roofline`'s byte count from shapes."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import layers, tracered  # noqa: E402

MS = 1_000_000          # ns
OFF = 5_000 * MS        # monotonic clock = trace clock + OFF


def mono(name, a_ms, b_ms):
    return (name, a_ms * MS + OFF, b_ms * MS + OFF)


@pytest.fixture()
def hand_made():
    """Device 0 runs 0-10, 5-20 (overlapping), 40-50 and 90-100 ms;
    device 1 only 0-10. One tick spans 0-100 ms: bench.send 0-30,
    bench.local_flush 30-100 with the phases forward.send 60-100 and,
    inside it, egress.attempt 70-95."""
    trace = {
        "device": {0: [["%fusion.3 = f32[8,128]{1,0} fusion(...)", 0, 10 * MS],
                       ["%fusion.3 = f32[8,128]{1,0} fusion(...)", 5 * MS,
                        15 * MS],
                       ["%sort.8 = (f32[8]) sort(...)", 40 * MS, 10 * MS],
                       ["%hll_stats.1 = (f32[4096,1]{1,0}, f32[4096,1]{1,0}) "
                        "custom-call(u8[4096,16384]{1,0:T(8,128)(4,1)} %p), "
                        "custom_call_target=\"tpu_custom_call\"",
                        90 * MS, 10 * MS]],
                   1: [["%fusion.3 = f32[8,128]{1,0} fusion(...)", 0,
                        10 * MS]]},
        "modules": {0: [["jit__compress_impl(17105616013299372607)", 0,
                         20 * MS],
                        ["jit_flush(99)", 40 * MS, 10 * MS]]},
        "host": [[tracered.SYNC, -2 * MS, 1 * MS]],
    }
    bench = [mono(tracered.SYNC, -2, -1), mono("bench.send", 0, 30),
             mono("bench.local_flush", 30, 100)]
    phases = [mono("local:forward.send", 60, 100),
              mono("local:egress.attempt", 70, 95)]
    return trace, bench, phases, [(0 * MS + OFF, 100 * MS + OFF)]


def test_busy_union_and_idle_share(hand_made):
    trace, bench, phases, windows = hand_made
    r = tracered.reduce_trace(trace, bench, phases, windows)
    assert r["window_s"] == pytest.approx(0.100)
    # union on device 0: 0-20, 40-50, 90-100 = 40 ms; device 1: 10 ms
    assert r["busy_s_by_device"] == {"0": pytest.approx(0.040),
                                     "1": pytest.approx(0.010)}
    assert r["busy_s"] == pytest.approx(0.025)         # mean over chips
    assert r["idle_share"] == pytest.approx(0.60)      # device 0
    assert r["busy_share_in_span"]["bench.send"] == pytest.approx(20 / 30)


def test_gaps_go_to_the_deepest_covering_span(hand_made):
    trace, bench, phases, windows = hand_made
    r = tracered.reduce_trace(trace, bench, phases, windows)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["bench.send"] == pytest.approx(0.010)            # 20-30
    # 30-60 of the flush under no phase, 10 ms of it busy
    assert gaps["bench.local_flush"] == pytest.approx(0.020)
    # forward.send's own share: 60-70 and 95-100, the last 5 ms busy
    assert gaps["bench.local_flush/local:forward.send"] == pytest.approx(
        0.010)
    # egress.attempt 70-95, of it 90-95 busy
    assert gaps["bench.local_flush/local:egress.attempt"] == pytest.approx(
        0.020)
    assert sum(gaps.values()) == pytest.approx(0.060)   # all of the idle
    assert list(gaps.values()) == sorted(gaps.values(), reverse=True)


def test_time_between_ticks_is_nobodys_idle_time(hand_made):
    trace, bench, phases, _ = hand_made
    two = [(0 * MS + OFF, 30 * MS + OFF), (60 * MS + OFF, 100 * MS + OFF)]
    r = tracered.reduce_trace(trace, bench, phases, two)
    assert r["window_s"] == pytest.approx(0.070)
    assert r["busy_s_by_device"]["0"] == pytest.approx(0.030)
    assert sum(v for _k, v in r["breakdown"]["idle_gaps"]) == pytest.approx(
        0.040)


def test_operations_get_stable_names(hand_made):
    trace, bench, phases, windows = hand_made
    assert tracered.stable_name(
        "jit__compress_impl(17105616013299372607)") == "jit__compress_impl"
    assert tracered.stable_name(
        "%fusion.3 = f32[131072,256]{1,0:T(8,128)} fusion(f32[1] %p)") \
        == "fusion.3"
    r = tracered.reduce_trace(trace, bench, phases, windows)
    ops = r["breakdown"]["device_ops"]
    assert ops[0] == ["jit__compress_impl", pytest.approx(0.020)]
    assert ops[1] == ["jit_flush", pytest.approx(0.010)]
    assert ["fusion.3", pytest.approx(0.035)] in ops    # both devices
    assert len(ops) <= 10 and len(r["breakdown"]["idle_gaps"]) <= 10


def test_a_trace_without_the_sync_span_is_refused(hand_made):
    trace, bench, phases, windows = hand_made
    trace["host"] = []
    with pytest.raises(ValueError, match="clock_sync"):
        tracered.reduce_trace(trace, bench, phases, windows)


def test_merge_intervals_and_busy_upto():
    s, e = tracered.merge_intervals([5, 0, 30, 31], [20, 10, 40, 35])
    assert s.tolist() == [0, 30] and e.tolist() == [20, 40]
    b = tracered.Busy([["a", 0, 10], ["b", 5, 15], ["c", 30, 10]])
    assert b.upto(np.array([0, 7, 20, 25, 33, 50])).tolist() == [
        0, 7, 20, 20, 23, 30]
    assert b.within(7, 33) == 16


def roofline_reader():
    spec = importlib.util.spec_from_file_location(
        "hll_stats_roofline", os.path.join(
            REPO, "perfbench", "metrics", "hll_stats_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_roofline_bytes_come_from_shapes(hand_made):
    trace, bench, phases, windows = hand_made
    mod = roofline_reader()
    assert mod.bytes_read(4096, 14) == 4096 * 16384 == 67_108_864
    r = tracered.reduce_trace(trace, bench, phases, windows)
    ctx = {"trace": r, "peaks": {"hbm_bytes_per_s": 819e9},
           "config": {"common": {"tpu_set_slots": 1024},
                      "sketches": {"hll_precision": 14}}}
    # one call of 10 ms reading [4096, 16384] u8 (rows from the
    # operand's shape in the trace, not from the config's 1024)
    want = 100.0 * (67_108_864 / 819e9) / 0.010
    assert mod.read(ctx) == pytest.approx(want)
    assert layers.read_metric("hll_stats_roofline", dict(
        ctx, ticks=[], device={}, run={})) == pytest.approx(want)
    # a cell whose flush never runs the kernel has nothing to read
    r["op_seconds"] = {"fusion.3": 1.0}
    r["op_calls"] = {"fusion.3": 3}
    assert mod.read(ctx) is None
    assert mod.read(dict(ctx, trace=None)) is None


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_trace_wide_100k.json")


def test_recorded_v5e_trace_reduces():
    with open(RECORDED) as f:
        rec = json.load(f)
    trace = {"device": {int(d): v for d, v in rec["device"].items()},
             "modules": {int(d): v for d, v in rec["modules"].items()},
             "host": rec["host"]}
    # the host rows of the recording stand in for the harness's own
    # spans: the same names on the same clock (offset 0)
    bench = [(n, a, a + d) for n, a, d in rec["host"]]
    tick = [r for r in bench if r[0] != tracered.SYNC]
    windows = [(min(r[1] for r in tick), max(r[2] for r in tick))]
    r = tracered.reduce_trace(trace, bench, [], windows)
    assert 0.0 < r["idle_share"] < 1.0 and r["busy_s"] > 0
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] * r["idle_share"], rel=1e-6)
    assert "bench.local_flush" in gaps
    names = [n for n, _s in r["breakdown"]["device_ops"]]
    assert all("(" not in n and "=" not in n and "%" not in n for n in names)
    assert any(n.startswith("jit_") for n in names)
    mod = roofline_reader()
    kernels = [n for n in r["op_seconds"] if mod.KERNEL.search(n)]
    assert kernels, sorted(r["op_seconds"])[:20]
    ctx = {"trace": r, "peaks": {"hbm_bytes_per_s": 819e9},
           "config": {"common": {"tpu_set_slots": 4096},
                      "sketches": {"hll_precision": 14}}}
    # 64 MiB in ~174 us: 385 GB/s of the v5e's 819
    assert mod.read(ctx) == pytest.approx(47.0, abs=1.0)

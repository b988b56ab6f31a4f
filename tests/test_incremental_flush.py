"""Incremental dirty-slot flush oracle suite (ISSUE 11).

The tentpole's correctness claim is structural: banks are interval-
scoped (the swap re-zeroes every row), so a cold pile is fresh-init by
construction and the flush body maps a fresh row to the cached baseline
row bit-for-bit — gathering only dirty piles and scattering over the
baseline must equal the full program EXACTLY, not approximately. These
tests pin that claim across adversarial dirty patterns (0%, 1 slot,
~10%, 100%, all-cold-then-one-hot) and all four engine backends
(tdigest|req × hll|ull), on both the local-only and forwarding builds,
and pin the two-consumer dirty-bitmap reset semantics the delta
checkpoints depend on. The chaos criterion (exactly-once + kill-restart
ON the incremental path) is carried by the existing suites — incremental
+ double-buffer are the config defaults, which
test_server_defaults_run_the_incremental_path pins so those suites can
never silently fall back to the full path.
"""

import numpy as np
import pytest

from veneur_tpu.ingest.parser import MetricKey, UDPMetric
from veneur_tpu.models.pipeline import (AggregationEngine, EngineConfig,
                                        _inc_bucket)

K_H = 512


def _mk_engine(inc, hb="tdigest", sb="hll", fwd=False, threshold=1.0,
               dbuf=None):
    return AggregationEngine(EngineConfig(
        histogram_slots=K_H, counter_slots=64, gauge_slots=64,
        set_slots=32, batch_size=256, buffer_depth=32,
        percentiles=(0.5, 0.99), aggregates=("min", "max", "count"),
        histogram_backend=hb, set_backend=sb,
        forward_enabled=fwd,
        flush_incremental=inc,
        flush_incremental_threshold=threshold,
        flush_double_buffer=inc if dbuf is None else dbuf))


def _touch(eng, rng, histo_keys, counters=8, gauges=4, sets=3):
    """Deterministically land samples on the named histo keys plus a
    scalar/set mix (same rng stream => identical banks per arm)."""
    for k in histo_keys:
        s = eng.histo_keys.lookup(MetricKey(f"m.t{k}", "timer", ""), 0)
        n = int(rng.integers(5, 40))
        eng.ingest_histo_batch(np.full(n, s, np.int32),
                               rng.gamma(2, 20, n).astype(np.float32),
                               np.ones(n, np.float32), count=n)
    for k in range(counters):
        s = eng.counter_keys.lookup(MetricKey(f"m.c{k}", "counter", ""), 0)
        eng.ingest_counter_batch(np.full(2, s, np.int32),
                                 rng.normal(5, 1, 2).astype(np.float32),
                                 np.ones(2, np.float32), count=2)
    for k in range(gauges):
        s = eng.gauge_keys.lookup(MetricKey(f"m.g{k}", "gauge", ""), 0)
        eng.ingest_gauge_batch(np.full(2, s, np.int32),
                               rng.normal(0, 1, 2).astype(np.float32),
                               count=2)
    for k in range(sets):
        for v in range(20):
            eng.process(UDPMetric(MetricKey(f"m.s{k}", "set", ""),
                                  0, f"u{v}", 1.0, 0))


def _canon(res):
    """Canonical, bit-exact view of one flush result: frame rows plus
    the forward export payloads."""
    rows = sorted((m.name, tuple(m.tags), m.type, repr(m.value))
                  for m in res.metrics)
    exp = res.export
    hist = sorted(
        ((k.name, tuple(np.asarray(m).tobytes() for m in (mn, w)),
          tuple(repr(x) for x in rest))
         for k, mn, w, *rest in exp.histograms), key=lambda t: t[0])
    sets = sorted((k.name, np.asarray(r).tobytes())
                  for k, r in exp.sets)
    ctr = sorted((k.name, repr(v)) for k, v in exp.counters)
    gag = sorted((k.name, repr(v)) for k, v in exp.gauges)
    return rows, hist, sets, ctr, gag


def _run_pattern(inc, intervals, hb="tdigest", sb="hll", fwd=False):
    """Run a sequence of intervals (each a list of histo key ids to
    touch; None = idle) through one engine; return canonical results
    + the device path each flush took."""
    rng = np.random.default_rng(42)
    eng = _mk_engine(inc, hb=hb, sb=sb, fwd=fwd)
    out = []
    for i, keys in enumerate(intervals):
        if keys is not None:
            _touch(eng, rng, keys)
        res = eng.flush(timestamp=10 + i)
        out.append((_canon(res), res.stats["flush_path"]["path"]))
    return out


PATTERNS = {
    "idle_0pct": [None],
    "ten_pct": [list(range(0, K_H, 10))],
    "all_hot_100pct": [list(range(K_H))],
    # hot interval, idle interval, then ONE slot re-touched among
    # hundreds of active-but-cold keys — covers the 1-slot pattern AND
    # the cold-active-key case in one sequence
    "all_cold_then_one_hot": [list(range(0, K_H, 3)), None, [7]],
}


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_incremental_bit_identical_to_full_default_engines(name):
    pattern = PATTERNS[name]
    inc = _run_pattern(True, pattern)
    full = _run_pattern(False, pattern)
    for i, ((ci, pi), (cf, pf)) in enumerate(zip(inc, full)):
        assert pi == "incremental" and pf == "full"
        assert ci == cf, f"{name}: interval {i} diverged"


@pytest.mark.parametrize("hb,sb", [
    # req+ull exercises both non-default backends in tier-1; the two
    # cross pairs add engine-independence coverage on the slow tier
    # (each pair costs its own executable compiles on this one-core box)
    pytest.param("tdigest", "ull", marks=pytest.mark.slow),
    pytest.param("req", "hll", marks=pytest.mark.slow),
    ("req", "ull"),
])
def test_incremental_bit_identical_every_engine_backend(hb, sb):
    # the non-default pairs, on the discriminating pattern (hot
    # interval, idle interval, then a single re-touched slot among
    # hundreds of active-but-cold keys)
    pattern = PATTERNS["all_cold_then_one_hot"]
    inc = _run_pattern(True, pattern, hb=hb, sb=sb)
    full = _run_pattern(False, pattern, hb=hb, sb=sb)
    for i, ((ci, pi), (cf, pf)) in enumerate(zip(inc, full)):
        assert pi == "incremental" and pf == "full"
        assert ci == cf, f"{hb}/{sb}: interval {i} diverged"


def test_incremental_bit_identical_on_forwarding_build():
    # fwd_out echoes the raw sketch state (h_* leaves + s_regs):
    # incremental must reconstruct those full-[K] leaves from the
    # baseline + dirty rows bit-exactly too
    pattern = PATTERNS["all_cold_then_one_hot"]
    inc = _run_pattern(True, pattern, fwd=True)
    full = _run_pattern(False, pattern, fwd=True)
    assert any(c[1] for (c, _p) in inc), "forward export was empty"
    for (ci, _), (cf, _) in zip(inc, full):
        assert ci == cf


def test_import_path_bit_identical_and_landed_outside_lock():
    # the global-tier Combine path: staged imports retire at the tick
    # boundary and land into the retired snapshot outside the lock —
    # results must equal the legacy under-the-lock ordering exactly
    def run(inc, dbuf):
        rng = np.random.default_rng(3)
        eng = _mk_engine(inc, dbuf=dbuf)
        for k in range(40):
            means = np.sort(rng.normal(100, 9, 16).astype(np.float32))
            eng.import_histogram(MetricKey(f"i.h{k}", "timer", ""),
                                 means, np.ones(16, np.float32),
                                 float(means.min()), float(means.max()),
                                 float(means.sum()), 16.0, 0.2)
        for k in range(10):
            eng.import_counter(MetricKey(f"i.c{k}", "counter", ""), 2.5)
        for k in range(4):
            eng.import_gauge(MetricKey(f"i.g{k}", "gauge", ""), 1.5)
        return _canon(eng.flush(timestamp=5))

    ref = run(False, dbuf=False)
    assert run(True, dbuf=True) == ref
    # orthogonality: each half of the tentpole alone is also identical
    assert run(True, dbuf=False) == ref
    assert run(False, dbuf=True) == ref


def test_dirty_bitmap_two_consumer_reset_semantics():
    """The bitmap now feeds checkpoints AND the flush: the retiring
    interval's bitmap must travel to the flush (marks made by the
    out-of-lock retired landing included), while the post-swap live
    bitmap stays zero — a checkpoint taken at the flush boundary must
    never see the flushed interval's marks (that would re-serialize
    rows the swap already re-zeroed)."""
    eng = _mk_engine(True)
    eng.enable_dirty_tracking()          # checkpoint consumer armed too
    rng = np.random.default_rng(0)
    _touch(eng, rng, [1, 2, 3])
    # stage an import that will retire and land OUTSIDE the lock
    means = np.sort(rng.normal(50, 5, 8).astype(np.float32))
    eng.import_histogram(MetricKey("i.h", "timer", ""), means,
                         np.ones(8, np.float32), float(means.min()),
                         float(means.max()), float(means.sum()), 8.0,
                         0.1)
    res = eng.flush(timestamp=1)
    info = res.stats["flush_path"]
    assert info["path"] == "incremental"
    assert info["dirty"][0] == 4         # 3 touched keys + the import
    # post-swap: the live bitmap is clean — the checkpoint's delta
    # degenerate case (zero dirty piles), exactly as before ISSUE 11
    snap = eng.checkpoint_state()
    assert snap["piles_dirty"] == 0
    # and the flushed rows really materialized (not lost to the reset)
    names = {m.name for m in res.metrics}
    assert {"m.t1.50percentile", "i.h.50percentile"} <= names


def test_incremental_falls_back_to_full_above_threshold():
    eng = _mk_engine(True, threshold=0.05)
    rng = np.random.default_rng(0)
    _touch(eng, rng, list(range(64)))    # 12.5% > 5% threshold
    res = eng.flush(timestamp=1)
    assert res.stats["flush_path"]["path"] == "full"


def test_idle_interval_skips_the_device_program():
    eng = _mk_engine(True)
    res = eng.flush(timestamp=1)
    info = res.stats["flush_path"]
    assert info["path"] == "incremental"
    assert info["dirty"] == [0, 0, 0, 0]
    assert "buckets" not in info         # no dispatch at all
    assert res.metrics == []


def test_double_buffer_phases_and_lock_window():
    """The tick's phase stamps carry the new engine.swap/gather/scatter
    names, and the lock-held window (the `swap` row) excludes the
    retired drain + device + materialize work."""
    eng = _mk_engine(True)
    rng = np.random.default_rng(0)
    _touch(eng, rng, list(range(0, K_H, 10)))
    res = eng.flush(timestamp=1)
    names = [p[0] for p in res.stats["phases"]]
    assert names[:2] == ["swap", "drain"]
    assert "gather" in names and "scatter" in names
    total_ns = sum(p[2] - p[1] for p in res.stats["phases"])
    (_n, s0, s1), drain = res.stats["phases"][:2]
    assert 0 < s1 - s0 < total_ns  # lock window is a slice, not the
    # tick: drain/device/materialize happen outside it, after it
    assert drain[1] == s1
    assert res.stats["phases"][-1][0] == "materialize"
    # the same edges are not served a second way
    assert not {"swap_ns", "merge_ns", "assembly_ns"} & set(res.stats)


def test_server_defaults_run_the_incremental_path():
    """The chaos criterion rides on this: exactly-once / kill-restart
    suites run config-built servers, so the defaults MUST take the
    incremental + double-buffered path — a silent fallback to full
    would un-test the tentpole."""
    from veneur_tpu.config import read_config
    from veneur_tpu.server import Server
    from veneur_tpu.sinks.basic import CaptureMetricSink

    cfg = read_config(text="""
interval: "3600s"
hostname: h
tpu_histogram_slots: 256
tpu_counter_slots: 128
tpu_gauge_slots: 128
tpu_set_slots: 64
tpu_batch_size: 256
tpu_buffer_depth: 16
""")
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[])
    srv.start()
    try:
        eng = srv.engines[0]
        assert eng._use_incremental and eng._use_double_buffer
        srv.handle_packet(b"inc.t:3.5|ms")
        assert srv.drain(20.0)
        srv.flush_once(timestamp=10)
        assert eng._last_flush_info["path"] == "incremental"
        tick = srv.flight.last_tick()
        phase_names = {p[0] for p in tick.phases()}
        assert {"engine.swap", "engine.gather",
                "engine.scatter"} <= phase_names
    finally:
        srv.stop()


def test_inc_bucket_ladder():
    assert _inc_bucket(1, 100_000) == 64
    assert _inc_bucket(64, 100_000) == 64
    assert _inc_bucket(65, 100_000) == 128
    assert _inc_bucket(4096, 100_000) == 4096
    assert _inc_bucket(4097, 100_000) == 8192
    assert _inc_bucket(10_000, 100_000) == 12288
    assert _inc_bucket(10_000, 48) == 48   # never above the bank

"""The incremental flush hands flush()'s assembly the compact rows it
computed plus a row map a bank kind, not a rebuilt full-[K] bank
(ISSUE 34). What has to hold: frames AND forward exports equal the full
program's bit for bit for both forward kinds, cold rows included (a
FULL resync ships idle interned counters and sets, which now leave
through the cached baseline row); an active key whose slot is not dirty
reads the baseline; the process-wide baseline rows cannot be written
through a view an export handed out; nothing the path allocates grows
with K x row bytes; `_last_flush_info` says how many rows a bank kind
handed over; and the full program and the mesh engine build no row map.
"""

import tracemalloc

import numpy as np
import pytest

from veneur_tpu.cluster import wire
from veneur_tpu.ingest.parser import (GLOBAL_ONLY, LOCAL_ONLY, MIXED_SCOPE,
                                      MetricKey, UDPMetric)
from veneur_tpu.models import pipeline
from veneur_tpu.models.pipeline import AggregationEngine, EngineConfig

from test_incremental_flush import PATTERNS, _canon

K_H = 512


def _mk_engine(inc, slots=(K_H, 64, 64, 32), fwd=True):
    """A forwarding engine on the incremental path, or on the full
    program with the dirty bitmaps armed all the same (a delta export
    needs them on either path)."""
    eng = AggregationEngine(EngineConfig(
        histogram_slots=slots[0], counter_slots=slots[1],
        gauge_slots=slots[2], set_slots=slots[3], batch_size=256,
        buffer_depth=32, percentiles=(0.5, 0.99),
        aggregates=("min", "max", "count", "sum"),
        forward_enabled=fwd, flush_incremental=inc,
        flush_incremental_threshold=1.0, flush_double_buffer=inc))
    if not inc:
        eng.enable_dirty_tracking()
    return eng


def _intern(eng):
    """Keys of every kind and scope that are interned and never fed:
    active in their first interval with a cold slot, idle afterwards
    (what a FULL resync ships from the whole table)."""
    for scope in (MIXED_SCOPE, LOCAL_ONLY, GLOBAL_ONLY):
        eng.histo_keys.lookup(MetricKey(f"idle.t{scope}", "timer", ""),
                              scope)
        eng.counter_keys.lookup(
            MetricKey(f"idle.c{scope}", "counter", ""), scope)
        eng.gauge_keys.lookup(MetricKey(f"idle.g{scope}", "gauge", ""),
                              scope)
        eng.set_keys.lookup(MetricKey(f"idle.s{scope}", "set", ""), scope)


def _touch(eng, rng, histo_keys):
    """Samples on the named timers (a third of them global-only, so
    they leave through the export) plus counters, gauges and sets of
    every scope."""
    for k in histo_keys:
        s = eng.histo_keys.lookup(MetricKey(f"m.t{k}", "timer", ""),
                                  (MIXED_SCOPE, LOCAL_ONLY,
                                   GLOBAL_ONLY)[k % 3])
        n = int(rng.integers(5, 40))
        eng.ingest_histo_batch(np.full(n, s, np.int32),
                               rng.gamma(2, 20, n).astype(np.float32),
                               np.ones(n, np.float32), count=n)
    for k in range(9):
        scope = (MIXED_SCOPE, LOCAL_ONLY, GLOBAL_ONLY)[k % 3]
        s = eng.counter_keys.lookup(MetricKey(f"m.c{k}", "counter", ""),
                                    scope)
        eng.ingest_counter_batch(np.full(2, s, np.int32),
                                 rng.normal(5, 1, 2).astype(np.float32),
                                 np.ones(2, np.float32), count=2)
        s = eng.gauge_keys.lookup(MetricKey(f"m.g{k}", "gauge", ""), scope)
        eng.ingest_gauge_batch(np.full(2, s, np.int32),
                               rng.normal(0, 1, 2).astype(np.float32),
                               count=2)
    for k in range(3):
        for v in range(20):
            eng.process(UDPMetric(MetricKey(f"m.s{k}", "set", ""),
                                  0, f"u{v}", 1.0, (MIXED_SCOPE, LOCAL_ONLY,
                                                    GLOBAL_ONLY)[k]))


def _run(inc, intervals, kind):
    rng = np.random.default_rng(34)
    eng = _mk_engine(inc)
    _intern(eng)
    out = []
    for i, keys in enumerate(intervals):
        if keys is not None:
            _touch(eng, rng, keys)
        res = eng.flush(timestamp=10 + i, forward_kind=kind)
        out.append((_canon(res), res.export.kind,
                    res.stats["flush_path"]["path"]))
    return out


@pytest.mark.parametrize("kind", ["full", "delta"])
@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_frames_and_exports_equal_the_full_programs(name, kind):
    # the last interval of every pattern is idle, so the interned keys
    # are idle there whatever the pattern: a FULL export ships their
    # cold rows, a delta export none
    pattern = PATTERNS[name] + [None]
    inc = _run(True, pattern, kind)
    full = _run(False, pattern, kind)
    for i, ((ci, ki, pi), (cf, kf, pf)) in enumerate(zip(inc, full)):
        assert (pi, pf) == ("incremental", "full")
        assert ki == kf == kind
        assert ci == cf, f"{name}/{kind}: interval {i} diverged"
    rows, hist, sets, ctr, _gag = inc[-1][0]
    assert rows == [] and hist == []
    if kind == "full":
        zeros = bytes(1 << 14)
        assert ("idle.s0", zeros) in sets and ("idle.s2", zeros) in sets
        assert ("idle.c2", "0.0") in ctr
        if pattern[0] is not None:      # keys that went idle with it
            assert ("m.s0", zeros) in sets and ("m.c2", "0.0") in ctr
    else:
        assert sets == [] and ctr == []


def test_an_active_key_with_a_cold_slot_reads_the_baseline():
    def run(inc):
        eng = _mk_engine(inc)
        _intern(eng)                    # active this interval, never fed
        _touch(eng, np.random.default_rng(1), [3, 4, 5])
        return eng, eng.flush(timestamp=1)

    (eng, res), (_e, ref) = run(True), run(False)
    assert res.stats["flush_path"]["path"] == "incremental"
    assert _canon(res) == _canon(ref)
    by_name = {m.name: m.value for m in res.metrics}
    # a counter and a set emit their cold row, a timer and a gauge none
    assert by_name["idle.c0"] == 0.0 and by_name["idle.c1"] == 0.0
    assert by_name["idle.s1"] == 0.0
    assert not any(n.startswith(("idle.t", "idle.g")) for n in by_name)
    base = eng._flush_baseline_rows()
    cold = {k.name: r for k, r in res.export.sets
            if k.name.startswith("idle.")}
    assert set(cold) == {"idle.s0", "idle.s2"}
    assert all(r is base["s_regs"] for r in cold.values())


def test_the_cached_baseline_rows_are_read_only_and_stay_as_they_were():
    eng = _mk_engine(True)
    _intern(eng)
    _touch(eng, np.random.default_rng(2), [1, 2])
    eng.flush(timestamp=1)
    base = eng._flush_baseline_rows()
    before = {k: np.array(v) for k, v in base.items()}
    arrays = [v for v in base.values() if isinstance(v, np.ndarray)]
    assert {"h_mean", "h_weight", "s_regs", "q"} <= set(base)
    assert arrays and not any(v.flags.writeable for v in arrays)
    res = eng.flush(timestamp=2)        # idle: a full resync of cold rows
    cold = [r for _k, r in res.export.sets]
    assert cold and all(r is base["s_regs"] for r in cold)
    with pytest.raises(ValueError):
        cold[0][0] = 1
    sent = wire.export_to_metrics(res.export)
    assert sum(m.HasField("set") for m in sent) == len(cold)
    for k, v in base.items():
        assert np.array_equal(before[k], v) and before[k].dtype == v.dtype


def _flush_device_peak(slots):
    """tracemalloc's peak over _flush_device of a warm engine's second
    flush (the same three timers, counters, gauges and sets dirty)."""
    eng = _mk_engine(True, slots=slots)
    peak = []
    inner = eng._flush_device

    def measured(*a, **kw):
        tracemalloc.start()
        try:
            return inner(*a, **kw)
        finally:
            peak.append(tracemalloc.get_traced_memory()[1])
            tracemalloc.stop()

    for ts in (1, 2):
        _touch(eng, np.random.default_rng(ts), [1, 2, 3])
        if ts == 2:
            eng._flush_device = measured
        res = eng.flush(timestamp=ts)
    info = res.stats["flush_path"]
    assert info["path"] == "incremental"
    return peak[0], info


def test_the_incremental_path_allocates_nothing_sized_by_the_bank():
    small, large = (256, 64, 64, 64), (4096, 1024, 1024, 512)
    p_small, i_small = _flush_device_peak(small)
    p_large, i_large = _flush_device_peak(large)
    assert i_small["buckets"] == i_large["buckets"] == [64] * 4
    assert i_small["host_rows"] == i_large["host_rows"] == [65] * 4
    maps = 4 * (sum(large) - sum(small))        # int32 a slot a kind
    # the rebuilt set bank alone was (512 - 64) x 16,384 bytes more
    assert p_large - p_small <= maps + 2 * sum(large) + 65536, (
        p_small, p_large)
    assert (large[3] - small[3]) << 14 > 8 * (maps + 65536)


def test_last_flush_info_carries_host_rows_on_both_paths():
    eng = _mk_engine(True)
    assert eng._last_flush_info["host_rows"] == [K_H, 64, 64, 32]
    idle = eng.flush(timestamp=1).stats["flush_path"]
    assert (idle["path"], idle["host_rows"]) == ("incremental", [1] * 4)
    _touch(eng, np.random.default_rng(0), list(range(70)))
    inc = eng.flush(timestamp=2).stats["flush_path"]
    assert inc["path"] == "incremental" and inc["buckets"][0] == 128
    assert inc["host_rows"] == [b + 1 for b in inc["buckets"]]
    full = _mk_engine(False)
    _touch(full, np.random.default_rng(0), [1])
    info = full.flush(timestamp=1).stats["flush_path"]
    assert (info["path"], info["host_rows"]) == ("full",
                                                 [K_H, 64, 64, 32])


@pytest.mark.parametrize("kind", ["full_program", "above_threshold",
                                  "mesh"])
def test_dense_outputs_flush_through_the_identity_map(kind, monkeypatch):
    def no_maps(*a, **kw):
        raise AssertionError("a row map was built for dense outputs")

    monkeypatch.setattr(pipeline, "_row_maps", no_maps)
    cfg = dict(histogram_slots=256, counter_slots=128, gauge_slots=128,
               set_slots=64, batch_size=512, buffer_depth=128)
    if kind == "mesh":
        from veneur_tpu.parallel.engine import MeshAggregationEngine
        eng = MeshAggregationEngine(EngineConfig(**cfg), n_devices=8)
    else:
        eng = AggregationEngine(EngineConfig(
            flush_incremental=(kind == "above_threshold"),
            flush_incremental_threshold=0.01, **cfg))
    seen = []
    inner = eng._flush_device

    def spy(*a, **kw):
        seen.append(inner(*a, **kw))
        return seen[-1]

    eng._flush_device = spy
    _touch(eng, np.random.default_rng(0), list(range(8)))
    res = eng.flush(timestamp=1)
    (host, row_of), = seen
    assert row_of is None
    assert all(isinstance(v, np.ndarray) for v in host.values())
    assert len(host["q"]) == 256 and len(host["c_hi"]) == 128
    assert res.stats["flush_path"]["path"] == "full"
    assert res.stats["flush_path"]["host_rows"] == [256, 128, 128, 64]
    assert {"m.t3.50percentile", "m.c0", "m.g0", "m.s0"} <= {
        m.name for m in res.metrics}

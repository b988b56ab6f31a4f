"""Multi-chip serving path: a running Server backed by a mesh-sharded
engine (tpu_num_devices > 1) on the virtual 8-device CPU mesh.

This is VERDICT r3 item 4 / SURVEY §7 step 7: UDP datagrams in → slot
routing over the ("dp", "shard") mesh → SPMD scatter ingest → collective
flush merge → correct global percentiles out of the server — the
in-process "multi-node" test strategy of the reference (two-server
loopback tests in server_test.go), mapped onto XLA host devices.
"""

import socket
import time

import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.models.pipeline import EngineConfig
from veneur_tpu.parallel.engine import MeshAggregationEngine
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink


def test_mesh_engine_unit_all_types():
    """Direct engine test across every bank type and many slots, so
    samples land on every shard column."""
    eng = MeshAggregationEngine(EngineConfig(
        histogram_slots=64, counter_slots=32, gauge_slots=32,
        set_slots=16, buffer_depth=32, batch_size=256,
        percentiles=(0.5, 0.9), aggregates=("min", "max", "count")),
        n_devices=8)
    eng.warmup()
    rng = np.random.default_rng(3)
    from veneur_tpu.ingest import parser
    vals = {}
    lines = []
    for k in range(16):  # 16 keys spread across 8 shards
        v = rng.gamma(2.0, 20.0, 40)
        vals[f"t{k}"] = v
        lines += [f"t{k}:{x:.4f}|ms".encode() for x in np.round(v, 4)]
    lines += [b"c:2|c|@0.5"] * 5 + [b"g:1|g", b"g:9|g"]
    lines += [f"s:m{i % 23}|s".encode() for i in range(200)]
    for ln in lines:
        eng.process(parser.parse_packet(ln))
    by = {m.name: m.value for m in eng.flush(timestamp=7).metrics}
    for k, v in vals.items():
        v = np.round(v, 4)
        assert by[f"{k}.count"] == 40.0
        assert by[f"{k}.min"] == float(np.float32(v.min()))
        assert by[f"{k}.max"] == float(np.float32(v.max()))
        exp = np.quantile(v, 0.5)
        assert abs(by[f"{k}.50percentile"] - exp) / exp < 0.02
    assert by["c"] == 20.0
    assert by["g"] == 9.0
    assert abs(by["s"] - 23) / 23 < 0.15
    # second flush is empty (interval semantics survive the mesh swap)
    assert len(eng.flush(timestamp=8).metrics) == 0


def test_mesh_server_end_to_end_udp():
    cap = CaptureMetricSink()
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 interval="3600s", hostname="mesh-host",
                 tpu_num_devices=8,
                 tpu_histogram_slots=64, tpu_counter_slots=32,
                 tpu_gauge_slots=32, tpu_set_slots=16,
                 tpu_buffer_depth=32, tpu_batch_size=256,
                 percentiles=[0.5, 0.99], aggregates=["count"])
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    assert type(srv.engines[0]).__name__ == "MeshAggregationEngine"
    srv.start()
    try:
        port = srv.bound_port()
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rng = np.random.default_rng(11)
        v = np.round(rng.gamma(2.0, 20.0, 600), 3)
        for i, x in enumerate(v):
            s.sendto(f"pod.ms:{x:.3f}|ms".encode(), ("127.0.0.1", port))
        s.sendto(b"pod.hits:5|c", ("127.0.0.1", port))
        deadline = time.monotonic() + 10
        while (srv.packets_received < len(v) + 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert srv.drain(10)
        srv.flush_once(timestamp=99)
        assert cap.wait_for_flush()
        by = {m.name: m for m in cap.all_metrics}
        assert by["pod.ms.count"].value == float(len(v))
        for q in (0.5, 0.99):
            exp = float(np.quantile(v, q))
            got = by[f"pod.ms.{q*100:g}percentile"].value
            assert abs(got - exp) / exp < 0.02, (q, got, exp)
        assert by["pod.hits"].value == 5.0
        assert by["pod.ms.count"].timestamp == 99
    finally:
        srv.stop()


def test_mesh_engine_rejects_forwarding():
    # a multi-chip pod is a root of the aggregation tree: it accepts
    # imports (is_global) but never forwards upstream
    with pytest.raises(ValueError):
        MeshAggregationEngine(EngineConfig(forward_enabled=True),
                              n_devices=8)


def test_mesh_hot_slot_batch():
    """A batch overfilling one slot's buffer takes the host pre-cluster
    sidestep on the mesh path too: exact count/sum/min/max, tail
    quantiles within 1%."""
    eng = MeshAggregationEngine(EngineConfig(
        histogram_slots=64, counter_slots=32, gauge_slots=32,
        set_slots=16, buffer_depth=64, batch_size=4096,
        percentiles=(0.5, 0.99),
        aggregates=("min", "max", "count", "sum")), n_devices=8)
    eng.warmup()
    rng = np.random.default_rng(5)
    hv = rng.gamma(2.0, 20.0, 4096).astype(np.float32)
    hot = eng.histo_keys.lookup(MetricKey("hot", "timer", ""), 0)
    cold = eng.histo_keys.lookup(MetricKey("cold", "timer", ""), 0)
    slots = np.full(4096, hot, np.int32)
    slots[::8] = cold
    eng.ingest_histo_batch(slots, hv, np.ones(4096, np.float32))
    by = {m.name: m.value for m in eng.flush(timestamp=3).metrics}
    hot_vals = hv[slots == hot].astype(np.float64)
    assert by["hot.count"] == float(len(hot_vals))
    assert abs(by["hot.sum"] - hot_vals.sum()) / hot_vals.sum() < 1e-5
    assert by["hot.min"] == float(hot_vals.min())
    assert by["hot.max"] == float(hot_vals.max())
    for q in (0.5, 0.99):
        exp = float(np.quantile(hot_vals, q))
        got = by[f"hot.{q*100:g}percentile"]
        assert abs(got - exp) / exp < 0.01, (q, got, exp)
    assert by["cold.count"] == float((slots == cold).sum())


def test_mesh_global_tier_imports():
    """The mesh engine as GLOBAL tier: 32 shards' forwarded digests,
    sets, counters and gauges Combine over the 8-device mesh and flush
    globally-accurate values (BASELINE configs 4+5 fused)."""
    eng = MeshAggregationEngine(EngineConfig(
        histogram_slots=64, counter_slots=32, gauge_slots=32,
        set_slots=16, buffer_depth=128, batch_size=2048,
        hll_precision=10, percentiles=(0.5, 0.99),
        aggregates=("min", "max", "count", "sum", "hmean"),
        is_global=True), n_devices=8)
    eng.warmup()
    rng = np.random.default_rng(9)
    n_shards, keys = 32, 8
    all_vals = {k: [] for k in range(keys)}
    for shard in range(n_shards):
        for k in range(keys):
            vals = rng.gamma(2.0, 20.0, 100).astype(np.float64)
            all_vals[k].append(vals)
            # a shard forwards its samples as weighted centroids +
            # exact scalar stats — what a local flush exports
            eng.import_histogram(
                MetricKey(f"t.{k}", "timer", ""), vals,
                np.ones(100), float(vals.min()), float(vals.max()),
                float(vals.sum()), 100.0, float((1.0 / vals).sum()))
        eng.import_counter(MetricKey("hits", "counter", ""), 2.5)
        eng.import_gauge(MetricKey("g", "gauge", ""), float(shard))
        # each shard saw members [0, 40*(shard%4+1)) of a shared set
        from veneur_tpu.ops import hll as hll_ops
        from veneur_tpu.utils import hashing
        regs = np.zeros(1 << 10, np.uint8)
        for mem in range(40 * (shard % 4 + 1)):
            h = hashing.set_member_hash(f"m{mem}")
            idx, rho = hll_ops.host_hash_to_updates(
                np.array([h], np.uint64), 10)
            regs[idx[0]] = max(regs[idx[0]], rho[0])
        eng.import_set(MetricKey("u", "set", ""), regs)

    by = {m.name: m.value for m in eng.flush(timestamp=4).metrics}
    for k in range(keys):
        union = np.concatenate(all_vals[k])
        assert by[f"t.{k}.count"] == float(len(union))
        assert abs(by[f"t.{k}.sum"] - union.sum()) / union.sum() < 1e-5
        # the exact-stats delta correction makes hmean track the
        # forwarded reciprocal sums, not the centroid approximation
        hm_exact = len(union) / (1.0 / union).sum()
        assert abs(by[f"t.{k}.hmean"] - hm_exact) / hm_exact < 1e-4
        assert by[f"t.{k}.min"] == float(np.float32(union.min()))
        assert by[f"t.{k}.max"] == float(np.float32(union.max()))
        for q in (0.5, 0.99):
            exp = float(np.quantile(union, q))
            got = by[f"t.{k}.{q*100:g}percentile"]
            assert abs(got - exp) / exp < 0.015, (k, q, got, exp)
    assert by["hits"] == 2.5 * n_shards
    assert by["g"] == float(n_shards - 1)   # last shard's write wins
    # union of the shards' sets = members [0, 160)
    assert abs(by["u"] - 160) / 160 < 0.1


def test_mesh_global_tier_adversarial_landing():
    """The global tier's exact-stats delta correction (engine.py
    host-replicates the device's f32 per-term arithmetic so the deltas
    cancel) must not depend on landing order, chunk boundaries, or
    interleaving with live ingest. Forwarded digests of random odd
    sizes land in a shuffled order, import rounds are cut at random
    points, and live samples for the SAME keys arrive in between —
    count stays exact, sum near-exact, hmean within tolerance."""
    from veneur_tpu.ingest import parser

    eng = MeshAggregationEngine(EngineConfig(
        histogram_slots=64, counter_slots=32, gauge_slots=32,
        set_slots=16, buffer_depth=128, batch_size=2048,
        percentiles=(0.5, 0.99),
        aggregates=("min", "max", "count", "sum", "hmean"),
        is_global=True), n_devices=8)
    eng.warmup()
    rng = np.random.default_rng(17)
    keys, n_shards = 6, 12
    expected = {k: [] for k in range(keys)}
    jobs = []
    for _ in range(n_shards):
        for k in range(keys):
            n = int(rng.integers(3, 160))    # odd sizes straddle chunks
            vals = rng.gamma(2.0, 20.0, n).astype(np.float64)
            jobs.append((k, vals))
            expected[k].append(vals)
    live = []
    for k in range(keys):
        n = int(rng.integers(5, 60))
        vals = np.round(rng.gamma(2.0, 20.0, n), 4)
        live.append((k, vals))
        expected[k].append(vals.astype(np.float64))
    rng.shuffle(jobs)
    li = 0
    for k, vals in jobs:
        eng.import_histogram(
            MetricKey(f"t.{k}", "timer", ""), vals, np.ones(len(vals)),
            float(vals.min()), float(vals.max()), float(vals.sum()),
            float(len(vals)), float((1.0 / vals).sum()))
        if rng.random() < 0.2:               # random chunk boundary
            eng._flush_import_centroids()
        if li < len(live) and rng.random() < 0.2:
            k2, lv = live[li]
            li += 1
            for x in lv:
                eng.process(parser.parse_packet(
                    f"t.{k2}:{x:.4f}|ms".encode()))
    for k2, lv in live[li:]:
        for x in lv:
            eng.process(parser.parse_packet(f"t.{k2}:{x:.4f}|ms".encode()))

    by = {m.name: m.value for m in eng.flush(timestamp=5).metrics}
    for k in range(keys):
        union = np.concatenate(expected[k])
        assert by[f"t.{k}.count"] == float(len(union)), k
        assert abs(by[f"t.{k}.sum"] - union.sum()) / union.sum() < 1e-5
        hm = len(union) / (1.0 / union).sum()
        assert abs(by[f"t.{k}.hmean"] - hm) / hm < 1e-3, (k, hm)
        assert by[f"t.{k}.min"] == float(np.float32(union.min()))
        assert by[f"t.{k}.max"] == float(np.float32(union.max()))
        for q in (0.5, 0.99):
            exp = float(np.quantile(union, q))
            got = by[f"t.{k}.{q*100:g}percentile"]
            assert abs(got - exp) / exp < 0.02, (k, q, got, exp)


def test_mesh_import_keeps_forwarded_extremes_exact():
    """A forwarded digest's centroid means are cumsum differences and
    can sit a few ulp outside its exact [min, max]. The mesh engine
    lands centroids as samples, whose values feed the extremes scatter
    — so an unclamped mean moved the slot's max off the forwarded
    exact one (the single-device engine merges extremes separately)."""
    eng = MeshAggregationEngine(EngineConfig(
        histogram_slots=64, counter_slots=32, gauge_slots=32,
        set_slots=16, buffer_depth=32, batch_size=256,
        percentiles=(0.5,), aggregates=("min", "max", "count"),
        is_global=True), n_devices=8)
    vmin, vmax = float(np.float32(88.1)), float(np.float32(99.45))
    over = float(np.nextafter(np.float32(vmax), np.float32(np.inf)))
    under = float(np.nextafter(np.float32(vmin), np.float32(-np.inf)))
    eng.import_histogram(
        MetricKey("t", "timer", ""), [under, 91.0, 95.0, over],
        [1.0, 1.0, 1.0, 1.0], vmin, vmax, 373.55, 4.0)
    by = {m.name: m.value for m in eng.flush(timestamp=1).metrics}
    assert by["t.min"] == vmin
    assert by["t.max"] == vmax
    assert by["t.count"] == 4.0

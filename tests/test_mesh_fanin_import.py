"""A fleet's forward into the mesh global (ISSUE 36), on four virtual
devices: eight senders' requests from the benchmark's generator
(`forward_payloads`, rehearsal size) through `import_list`, against the
generator's plain numpy reference and, in the exact fields, against the
one-chip engine; a key that takes 32 digests in one landing; the
landing's phases and the interval's counters.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import harness, reference  # noqa: E402
from veneur_tpu import observe  # noqa: E402
from veneur_tpu.cluster.protos import forward_pb2  # noqa: E402
from veneur_tpu.ingest.parser import MetricKey  # noqa: E402
from veneur_tpu.models.pipeline import (LAND_PHASES,  # noqa: E402
                                        AggregationEngine, EngineConfig)

SEED = 2**31 + 36
EXACT = (".count", ".min", ".max")


@pytest.fixture(scope="module")
def fleet():
    """(configuration, payload): the fan-in cell's own files at
    rehearsal size, one payload of the seeded fleet."""
    cfg = harness.load_config("fanin32_mesh_global_4chip", rehearsal=True)
    mix = harness.load_mix("fleet_1k", rehearsal=True)
    cfg["control"] = None
    payloads, _ = harness.load_generator(mix).build(
        cfg, mix, SEED, lambda _msg: None)
    return cfg, payloads[0]


def _engine(kind, cfg=None, **over):
    common = cfg["common"] if cfg else {}
    ecfg = EngineConfig(**{**dict(
        histogram_slots=common.get("tpu_histogram_slots", 512),
        counter_slots=128, gauge_slots=128,
        set_slots=common.get("tpu_set_slots", 64),
        buffer_depth=common.get("tpu_buffer_depth", 256),
        batch_size=common.get("tpu_batch_size", 512),
        compression=100.0, hll_precision=14,
        percentiles=(0.5, 0.75, 0.99),
        aggregates=("min", "max", "count", "sum"), is_global=True),
        **over})
    if kind == "mesh":
        from veneur_tpu.parallel.engine import MeshAggregationEngine
        return MeshAggregationEngine(ecfg, n_devices=4)
    return AggregationEngine(ecfg)


def _import_fleet(eng, payload):
    for op, body in enumerate(payload["requests"], 1):
        pbs = forward_pb2.MetricList.FromString(body).metrics
        rerouted, rejected = eng.import_list(op, pbs)
        assert rerouted == [] and rejected == []


@pytest.fixture(scope="module")
def flushed(fleet):
    """{engine kind: (answers by name, _last_flush_info, phase rows)} of
    the fleet's requests imported and flushed; the mesh engine with the
    flight recorder's stamp log armed and its programs' calls counted."""
    cfg, payload = fleet
    out = {}
    for kind in ("mesh", "single"):
        eng = _engine(kind, cfg)
        eng.land_stamps = observe.StampLog(dict.fromkeys(LAND_PHASES, 64))
        calls = []
        if kind == "mesh":
            for name in ("ingest", "merge_histo_scalars", "merge_set_rows"):
                def counting(*a, _inner=getattr(eng.me, name), _n=name):
                    calls.append(_n)
                    return _inner(*a)
                setattr(eng.me, name, counting)
        _import_fleet(eng, payload)
        res = eng.flush(timestamp=36)
        out[kind] = (reference.sink_values(res.metrics),
                     dict(eng._last_flush_info),
                     res.stats["import_phases"], calls)
    return out


@pytest.mark.parametrize("kind", ["mesh", "single"])
def test_the_engine_answers_as_the_fleets_numpy_reference(fleet, flushed,
                                                          kind):
    """Exact count, min, max and counters over all eight senders, the
    sum within 5e-7, hot p50 / p99 and the sets inside the rehearsal's
    limits, every percentile inside its key's extremes."""
    cfg, payload = fleet
    tol = cfg["guarantees"]["tolerances"]
    assert tol["sum"] == 5e-07 and tol["pct_outside"] == 2e-05
    v = reference.check_tick(payload["ref"], None, flushed[kind][0], tol)
    assert v["mismatches"] == []
    assert set(v["numbers"]) == {
        "exact_mismatches", "worst_pct_outside_rel", "worst_p50_rel",
        "worst_p99_rel", "worst_set_rel", "worst_sum_rel"}
    assert reference.within(v["numbers"]), v["numbers"]
    assert v["accounted_lines"] == sum(
        count for count, _lo, _hi in payload["ref"]["timer"].values())


def test_the_exact_fields_are_the_one_chip_engines(flushed):
    mesh, single = flushed["mesh"][0], flushed["single"][0]
    assert set(mesh) == set(single)
    exact = [n for n in mesh if n.endswith(EXACT)
             or n.startswith("smoke.counter.")]
    assert len(exact) == 3 * 40 + 10
    assert {n: repr(mesh[n]) for n in exact} \
        == {n: repr(single[n]) for n in exact}


def test_the_counters_are_what_the_payload_holds(fleet, flushed):
    _cfg, payload = fleet
    _answers, info, _rows, calls = flushed["mesh"]
    # 8 senders x (4 hot keys x 64 + 36 x 4 unit centroids, two extremes
    # riders a digest); 8 requests of 40 + 2 + 10 sketches
    assert info["mesh_import_points"] == 8 * (4 * 66 + 36 * 6)
    assert (info["import_batches"], info["import_metrics"]) \
        == (8, payload["n_sketches"]) == (8, 8 * 52)
    # every program called for the imports, and nothing else: routed
    # ingest (centroids, then the counters at the flush), the
    # exact-stats deltas, 16 set rows in one call
    assert info["mesh_import_dispatches"] == len(calls)
    assert calls.count("merge_set_rows") == 1
    assert calls.count("merge_histo_scalars") >= 1
    # a sender's request holds a key once, so a landing needs a second
    # round only where two senders' digests of a key share a stage
    assert 8 <= info["mesh_import_rounds"] <= calls.count("ingest") - 1
    assert info["mesh_import_preclustered"] == 0
    assert info["mesh_interner_spills"] == 0
    assert sum(info["mesh_shard_rows"]) == 40
    assert len(info["mesh_shard_rows"]) == 4
    # the one-chip engine keeps no such tally
    assert not [k for k in flushed["single"][1] if k.startswith("mesh_")]


def test_the_landing_phases_nest_under_import_land(flushed):
    rows = flushed["mesh"][2]
    by = {name: sorted((t0, t1) for n, t0, t1 in rows if n == name)
          for name in LAND_PHASES}
    land, stage = by["import.land"], by["import.land.stage"]
    dispatch = by["import.land.dispatch"]
    assert len(land) == len(stage) == len(dispatch) >= 2
    assert by["import.land.cluster"] == []
    for (l0, l1), (s0, s1), (d0, d1) in zip(land, stage, dispatch):
        assert l0 == s0 <= s1 == d0 <= d1 == l1
    # the one-chip engine's landing keeps its own two children
    names = {n for n, _t0, _t1 in flushed["single"][2]}
    assert names == {"import.land", "import.land.stage",
                     "import.land.cluster"}


def test_a_key_taking_32_digests_against_a_256_deep_buffer():
    """32 x 66 = 2,112 staged points on one row in one landing: 32
    scatter rounds, the in-program compress firing some eight times.
    The count stays exact, the extremes exact, the sum within 5e-7 and
    the p99 within 2% of numpy's."""
    rng = np.random.default_rng([SEED, 32])
    samples = np.maximum(1, np.rint(rng.lognormal(
        np.log(100.0), 0.1, (32, 64)) * 1000.0)) / 1000.0
    eng = _engine("mesh", batch_size=4096)
    hot = MetricKey("smoke.timer.hot", "timer", "env:prod")
    cold = MetricKey("smoke.timer.cold", "timer", "env:prod")
    for x in np.sort(samples, axis=1):
        eng.import_histogram(hot, x, np.ones(64), x[0], x[-1], x.sum(), 64,
                             (1.0 / x).sum())
    eng.import_histogram(cold, samples[0, :4], np.ones(4),
                         samples[0, :4].min(), samples[0, :4].max(),
                         samples[0, :4].sum(), 4, 0.0)
    res = eng.flush(timestamp=36)
    got = {m.name: m.value for m in res.metrics}
    info = eng._last_flush_info
    assert info["mesh_import_points"] == 32 * 66 + 6
    assert info["mesh_import_rounds"] == 32
    assert info["mesh_import_preclustered"] == 0
    assert got["smoke.timer.hot.count"] == 2048.0
    assert got["smoke.timer.cold.count"] == 4.0
    assert got["smoke.timer.hot.min"] == float(np.float32(samples.min()))
    assert got["smoke.timer.hot.max"] == float(np.float32(samples.max()))
    assert abs(got["smoke.timer.hot.sum"] / samples.sum() - 1.0) <= 5e-7
    for q, limit in ((0.5, 0.01), (0.99, 0.02)):
        want = float(np.quantile(samples, q))
        name = f"smoke.timer.hot{reference.pct_suffix(q)}"
        assert abs(got[name] / want - 1.0) <= limit, (q, got[name], want)


def test_a_mesh_server_drains_the_tally_as_self_metrics(fleet):
    """veneur.import.mesh.*_total: the interval's tally through a
    config-built server's flush, present at zero and reset a flush; a
    one-chip server emits none of them."""
    from veneur_tpu.config import Config
    from veneur_tpu.server import Server
    from veneur_tpu.sinks.basic import CaptureMetricSink
    _cfg, payload = fleet
    pbs = list(forward_pb2.MetricList.FromString(
        payload["requests"][0]).metrics)
    names = {}
    for devices in (4, 1):
        cap = CaptureMetricSink()
        srv = Server(Config(
            interval="3600s", hostname="h", tpu_num_devices=devices,
            grpc_listen_addresses=["127.0.0.1:0"], tpu_histogram_slots=512,
            tpu_counter_slots=128, tpu_gauge_slots=128, tpu_set_slots=64,
            tpu_batch_size=512), sinks=[cap], plugins=[], span_sinks=[])
        srv.start()
        try:
            assert srv._submit_import_batch(pbs) == len(pbs) == 52
            assert srv.drain(30.0)
            srv.flush_once(timestamp=1)
            srv.flush_once(timestamp=2)
            cap.wait_for_flush(2)
            names[devices] = [
                {m.name.removeprefix("veneur.import.mesh."): m.value
                 for m in f if m.name.startswith("veneur.import.mesh.")}
                for f in cap.flushes[:2]]
            info = srv.engines[0]._last_flush_info
        finally:
            srv.stop()
    first, second = names[4]
    assert first["points_total"] == 4 * 66 + 36 * 6
    assert first["rounds_total"] >= 1 and first["dispatches_total"] >= 3
    assert first["preclustered_total"] == first["interner_spills_total"] == 0
    assert set(second) == set(first) and set(second.values()) == {0}
    assert names[1] == [{}, {}]
    assert "mesh_import_points" not in info        # the one-chip engine's

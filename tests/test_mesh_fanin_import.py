"""A fleet's forward into the mesh global (ISSUE 36), on four virtual
devices: eight senders' requests from the benchmark's generator
(`forward_payloads`, rehearsal size) through `import_list`, against the
generator's plain numpy reference and, in the exact fields, against the
one-chip engine; a key that takes 32 digests in one landing; the
landing's phases and the interval's counters. And the columnar stage
(ISSUE 37): a request staged by one vectorised pass against the per-key
arithmetic it replaced, kept below as the reference; what a flush
dispatches for an interval of two landings.
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
from veneur_tpu.cluster import wire  # noqa: E402
from veneur_tpu.cluster.protos import forward_pb2, metric_pb2  # noqa: E402
from veneur_tpu.ingest.parser import GLOBAL_ONLY, MetricKey  # noqa: E402
from veneur_tpu.models.pipeline import (APPLY_PHASES,  # noqa: E402
                                        IMPORT_PHASES, LAND_PHASES,
                                        AggregationEngine, EngineConfig,
                                        _precluster_k1)

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
        eng.land_stamps = observe.StampLog(dict.fromkeys(IMPORT_PHASES, 64))
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


def _import_as_the_parent_did(eng, pbs):
    """One request into the mesh engine the way the parent commit
    staged it (ISSUE 44 took the loop out): a tuple a sketch from
    `decode_metric_batch`, a row looked up a record, the digests' table
    rebuilt from the tuples (`table.append(rec[3:])`,
    `np.asarray(table, np.float64)`), then the other metrics one by
    one."""
    records, means, weights, bad = wire.decode_metric_batch(pbs)
    assert bad == []
    with eng.lock:
        slots, table = [], []
        for rec in records:
            if rec[0] == wire.IMPORT_HISTOGRAM:
                slot = eng._import_slot(eng.histo_keys, rec[1])
                if slot >= 0:
                    slots.append(slot)
                    table.append(rec[3:])
        table = np.asarray(table, np.float64).reshape(len(slots), 7)
        starts = table[:, 0].astype(np.int64)
        eng._stage_digests(
            np.asarray(slots, np.int32), starts,
            table[:, 1].astype(np.int64) - starts, table[:, 2:].T,
            means, weights)
        for rec in records:
            if rec[0] == wire.IMPORT_SET:
                eng._import_set_locked(rec[1], rec[3], rec[4])
            elif rec[0] == wire.IMPORT_COUNTER:
                eng._import_counter_locked(rec[1], rec[3])
            elif rec[0] == wire.IMPORT_GAUGE:
                eng._import_gauge_locked(rec[1], rec[3])


def test_the_flush_is_the_parents_record_loop_value_for_value(fleet,
                                                              flushed):
    """The fleet's requests staged as blocks of columns flush what the
    parent's loop over a tuple a sketch flushed: every name, every
    value, bit for bit."""
    cfg, payload = fleet
    eng = _engine("mesh", cfg)
    for body in payload["requests"]:
        _import_as_the_parent_did(
            eng, forward_pb2.MetricList.FromString(body).metrics)
    want = reference.sink_values(eng.flush(timestamp=36).metrics)
    got, info = flushed["mesh"][:2]
    assert len(want) > 40 * 6
    assert {n: repr(v) for n, v in got.items()} \
        == {n: repr(v) for n, v in want.items()}
    n_digests = 8 * 40
    assert (info["import_digests_block"], info["import_digests_single"],
            info["mesh_import_staged"]) == (n_digests, 0, n_digests)
    assert eng._last_flush_info["mesh_import_staged"] == n_digests


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
    assert names - set(APPLY_PHASES) == {
        "import.land", "import.land.stage", "import.land.cluster"}


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


# ---------------- the columnar stage (ISSUE 37) ----------------

STAGE = dict(buffer_depth=64, batch_size=512)


def _digest(ml, name, means, weights=None, lo=None, hi=None):
    """One forwarded timer. `lo` / `hi` stand in for the exact extremes
    where a case wants a centroid mean outside them."""
    means = np.asarray(means, np.float64)
    weights = np.ones(len(means)) if weights is None \
        else np.asarray(weights, np.float64)
    m = ml.metrics.add(name=name, type=metric_pb2.Timer, tags=["env:prod"])
    td = m.histogram.t_digest
    for mean, w in zip(means, weights):
        td.centroids.add(mean=float(mean), weight=float(w))
    td.min = float(means.min() if lo is None else lo)
    td.max = float(means.max() if hi is None else hi)
    td.sum = float((means * weights).sum())
    td.count = float(weights.sum())
    nz = means != 0
    td.reciprocal_sum = float((weights[nz] / means[nz]).sum())


def _requests(case):
    """The case's traffic: a list of steps, each a request's metrics
    (through import_list) or one digest's fields (through
    import_histogram)."""
    rng = np.random.default_rng([SEED, 37])

    def draw(n):
        return np.sort(rng.lognormal(np.log(100.0), 0.3, n))

    ml = forward_pb2.MetricList()
    if case == "cold_4_centroids":
        for k in range(40):
            x = draw(4)
            # a mean a few ulp outside the exact extremes is clipped
            _digest(ml, f"c.k{k}", x, lo=np.nextafter(x[0], np.inf),
                    hi=np.nextafter(x[-1], -np.inf))
    elif case == "hot_64_centroids":
        for k in range(6):
            _digest(ml, f"c.hot{k}", draw(62), rng.integers(1, 40, 62))
    elif case == "wider_than_the_buffer":
        _digest(ml, "c.k0", draw(4))
        _digest(ml, "c.wide", draw(150), rng.integers(1, 9, 150))
        _digest(ml, "c.k1", draw(4))
        _digest(ml, "c.wide2", draw(63))
    elif case == "zero_mean":
        _digest(ml, "c.zero", [0.0, 0.0, 1.5, 4.0], [3.0, 1.0, 2.0, 1.0])
        _digest(ml, "c.neg", [-2.0, 0.0, 2.0])
    elif case == "one_slot_from_several_senders":
        steps = []
        for _sender in range(3):
            ml = forward_pb2.MetricList()
            _digest(ml, "c.shared", draw(20), rng.integers(1, 9, 20))
            _digest(ml, "c.shared", draw(4))
            for k in range(5):
                _digest(ml, f"c.k{k}", draw(4))
            steps.append(list(ml.metrics))
        return steps
    elif case == "stage_fills_inside_a_request":
        # 100 x 6 = 600 points against a stage of 512: the 86th digest
        # would overfill it (510 staged), so the landing holds 85
        for k in range(100):
            _digest(ml, f"c.k{k}", draw(4))
    elif case == "corrupt_metric_mid_batch":
        _digest(ml, "c.k0", draw(4))
        bad = ml.metrics.add(name="c.evil", type=metric_pb2.Timer)
        bad.histogram.t_digest.packed_centroids = b"\xff\x00garbage"
        _digest(ml, "c.k1", draw(9), rng.integers(1, 9, 9))
        alien = ml.metrics.add(name="c.alien", type=metric_pb2.Set)
        alien.set.hyper_log_log = wire.encode_set_payload(
            "ull", np.zeros(1 << 14, np.uint8))
        _digest(ml, "c.k2", draw(4))
    elif case == "batch_of_one_through_import_histogram":
        x = draw(30)
        w = rng.integers(1, 9, 30).astype(np.float64)
        return [(MetricKey("c.one", "timer", "env:prod"), x, w,
                 float(np.nextafter(x[0], np.inf)), float(x[-1]),
                 float((x * w).sum()), float(w.sum()),
                 float((w / x).sum()))]
    else:
        raise AssertionError(case)
    return [list(ml.metrics)]


# case -> (requests' rejects, digests the columnar pass staged, digests
# that took the per-key fallback, calls of the routed ingest)
STAGE_CASES = {
    "cold_4_centroids": (0, 40, 0, 1),
    "hot_64_centroids": (0, 6, 0, 1),
    "wider_than_the_buffer": (0, 4, 2, 1),
    "zero_mean": (0, 2, 0, 1),
    "one_slot_from_several_senders": (0, 21, 0, 6),
    "stage_fills_inside_a_request": (0, 100, 0, 2),
    "corrupt_metric_mid_batch": (2, 3, 0, 1),
    "batch_of_one_through_import_histogram": (0, 1, 0, 1),
}


def _per_key_reference(eng, digests):
    """PR 36's arithmetic, a key at a time
    (`MeshAggregationEngine._import_histogram_locked` and
    `_stage_landing` as they stood): each digest's staged points and
    what it adds to its slot's exact-minus-staged deltas. `digests` are
    (key, means, weights, min, max, sum, count, reciprocal sum) in
    staging order -> ([(slot, values f32, weights f32)], {slot: [dsum,
    dcount, drecip]}, {slot: the sums' magnitudes})."""
    B = eng.cfg.buffer_depth - 2
    points, deltas, scale = [], {}, {}
    for key, means, weights, vmin, vmax, vsum, count, recip in digests:
        slot = eng.histo_keys.lookup(key, GLOBAL_ONLY)
        means = np.asarray(means, np.float64)
        weights = np.asarray(weights, np.float64)
        if len(means) > B:
            means, weights = _precluster_k1(means, weights, B)
        means = np.clip(means, vmin, vmax)
        m32 = means.astype(np.float32)
        w32 = weights.astype(np.float32)
        staged_sum = float((m32 * w32).astype(np.float64).sum())
        staged_cnt = float(w32.astype(np.float64).sum())
        nz = m32 != 0
        staged_rcp = float((w32[nz] / m32[nz]).astype(np.float64).sum())
        d = deltas.setdefault(slot, [0.0, 0.0, 0.0])
        d[0] += float(vsum) - staged_sum
        d[1] += float(count) - staged_cnt
        d[2] += float(recip) - staged_rcp
        sc = scale.setdefault(slot, [0.0, 0.0, 0.0])
        sc[0] += float(np.abs(m32.astype(np.float64) * w32).sum())
        sc[1] += staged_cnt
        sc[2] += float(np.abs(w32[nz] / m32[nz]).astype(np.float64).sum())
        points.append((
            slot,
            np.concatenate([means, [vmin, vmax]]).astype(np.float32),
            np.concatenate([weights, [0.0, 0.0]]).astype(np.float32)))
    return points, deltas, scale


def _reference_calls(eng, points):
    """The routed ingest's operands for `points` under the stage's
    schedule: a landing is cut before the digest that would overfill
    the batch, and holds a slot's digests one a scatter round."""
    n = eng.cfg.batch_size
    landings, used = [[]], 0
    for item in points:
        if used + len(item[1]) > n:
            landings.append([])
            used = 0
        landings[-1].append(item)
        used += len(item[1])
    calls = []
    for items in landings:
        rounds, seen = [], {}
        for item in items:
            r = seen[item[0]] = seen.get(item[0], -1) + 1
            if r == len(rounds):
                rounds.append([])
            rounds[r].append(item)
        for round_items in rounds:
            slots = np.full(n, -1, np.int32)
            vals = np.zeros(n, np.float32)
            wts = np.zeros(n, np.float32)
            at = 0
            for slot, v, w in round_items:
                slots[at:at + len(v)] = slot
                vals[at:at + len(v)] = v
                wts[at:at + len(v)] = w
                at += len(v)
            calls.append(eng._route(
                eng.me.histogram_slots // eng.S, slots, vals, wts))
    return calls


def _bits(operands):
    return [np.asarray(a).tobytes() for a in operands]


@pytest.fixture(scope="module")
def stage_engine():
    return _engine("mesh", **STAGE)


@pytest.mark.parametrize("case", STAGE_CASES)
def test_the_columnar_stage_is_the_per_key_arithmetic(stage_engine, case):
    """The routed ingest's operands bit for bit, the exact-stats deltas
    to the order of an f64 summation, and the two counts of the pass."""
    eng = stage_engine
    n_rejected, n_staged, n_fallback, n_ingests = STAGE_CASES[case]
    ingests, folds = [], []

    def spy(name, into, keep):
        inner = getattr(eng.me, name)

        def call(*a):
            into.append(a[:keep])
            return inner(*a)
        setattr(eng.me, name, call)
        return inner

    restore = {"ingest": spy("ingest", ingests, 3),
               "merge_histo_scalars": spy("merge_histo_scalars", folds, 6)}
    digests, rejected = [], []
    try:
        before = (eng._mesh_import_staged, eng._mesh_import_staged_fallback)
        for step in _requests(case):
            if isinstance(step, tuple):
                eng.import_histogram(*step)
                digests.append(step)
                continue
            op = eng.last_import_op + 1
            rerouted, bad = eng.import_list(op, step)
            assert rerouted == []
            assert eng.last_import_op == op
            rejected += [pb.name for pb, _e in bad]
            records, means, weights, _bad = wire.decode_metric_batch(step)
            digests += [(rec[1], means[rec[3]:rec[4]],
                         weights[rec[3]:rec[4]], *rec[5:])
                        for rec in records
                        if rec[0] == wire.IMPORT_HISTOGRAM]
        assert len(rejected) == n_rejected
        assert (eng._mesh_import_staged - before[0],
                eng._mesh_import_staged_fallback - before[1]) \
            == (n_staged, n_fallback)
        assert folds == []           # the deltas wait for the flush
        mid_interval = len(ingests)
        staged_deltas = eng._h_deltas.copy()
        with eng.lock:
            eng._flush_import_centroids()
    finally:
        for name, inner in restore.items():
            setattr(eng.me, name, inner)

    points, deltas, scale = _per_key_reference(eng, digests)
    want = _reference_calls(eng, points)
    assert len(ingests) == len(want) == n_ingests
    for got, ref in zip(ingests, want):
        assert _bits(got) == _bits(ref)
    if case == "stage_fills_inside_a_request":
        # one dispatch inside the request, cut on the 85th digest's edge
        assert mid_interval == 1
        assert int((ingests[0][0] >= 0).sum()) == 85 * 6
        assert int((ingests[1][0] >= 0).sum()) == 15 * 6
    else:
        assert mid_interval == 0

    # the deltas: host f64 a slot until the flush, there once
    touched = np.flatnonzero(staged_deltas.any(axis=0))
    assert set(touched.tolist()) <= set(deltas)
    for slot, want_d in deltas.items():
        for row in range(3):
            assert abs(staged_deltas[row, slot] - want_d[row]) \
                <= 1e-12 * scale[slot][row], (case, slot, row)
    assert not eng._h_deltas.any()
    assert len(folds) == 1
    per_shard = eng.me.histogram_slots // eng.S
    rs, rmin, rmax, rsum, rcnt, rrcp = (np.asarray(a)[0] for a in folds[0])
    at = np.flatnonzero(rs >= 0)
    n = eng.cfg.batch_size
    assert (at // n * per_shard + rs[at]).tolist() == touched.tolist()
    for row, operand in enumerate((rsum, rcnt, rrcp)):
        assert operand[at].tobytes() \
            == staged_deltas[row, touched].astype(np.float32).tobytes()
    assert np.all(rmin == np.inf) and np.all(rmax == -np.inf)


def test_a_flush_of_two_landings_dispatches_them_one_fold_and_the_sets():
    """An interval whose digests fill the stage once: the landing
    inside the request, the landing at the flush, one fold of the
    exact-stats deltas and one set-row merge; before ISSUE 37 every
    landing was two calls of the routed ingest and one fold."""
    eng = _engine("mesh", **STAGE)
    calls = []
    for name in ("ingest", "merge_histo_scalars", "merge_set_rows"):
        def counting(*a, _inner=getattr(eng.me, name), _n=name):
            calls.append(_n)
            return _inner(*a)
        setattr(eng.me, name, counting)
    ml = forward_pb2.MetricList()
    for m in _requests("stage_fills_inside_a_request")[0]:
        ml.metrics.add().CopyFrom(m)
    rng = np.random.default_rng([SEED, 38])
    for k in range(3):
        m = ml.metrics.add(name=f"c.users{k}", type=metric_pb2.Set)
        m.set.hyper_log_log = wire.encode_set_payload(
            "hll", rng.integers(0, 6, 1 << 14).astype(np.uint8))
    assert eng.import_list(1, list(ml.metrics)) == ([], [])
    assert calls == ["ingest"]
    res = eng.flush(timestamp=37)
    info = eng._last_flush_info
    assert calls == ["ingest", "ingest", "merge_histo_scalars",
                     "merge_set_rows"]
    assert info["mesh_import_dispatches"] == 2 + 1 + 1
    assert info["mesh_import_rounds"] == 2
    assert info["mesh_import_points"] == 600
    assert (info["mesh_import_staged"],
            info["mesh_import_staged_fallback"]) == (100, 0)
    got = {m.name: m.value for m in res.metrics}
    assert sum(v for name, v in got.items()
               if name.endswith(".count")) == 400.0

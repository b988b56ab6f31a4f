"""The import stage takes the decoder's columns (ISSUE 44): a request's
digests travel from `wire.BatchDecoder` to the landing's device matrix
as arrays, a block a request, and the landing fills its `[R, L]` by one
indexed write a plane.

The reference is what the stage and the landing were before, kept here
as plain functions: one tuple a digest appended to a list, a landing at
the digest that brings the list to either bound (`_parent_landings`),
then a dictionary of piles a slot, a `sum(len(...))` a pile, and two
slice assignments a digest (`_parent_operands`). Every operand the
landing hands a device program must be the reference's bit for bit:
the pre-cluster chunks, both planes of `[R, L]`, `slot_ids` and the
work set, merge_scalars' slots and five statistics, the direct
strategy's point batches; and the five `import_land_*` counts.

Beside them, what a request keeps when it is staged as a block: a
poison pill and a fold re-route in the middle of it name one metric by
its position, the watermark and the batch counts move under the one
lock hold, a checkpoint saves the stage a tuple a digest as it always
did, and the two counters say which way the digests came.
"""

import numpy as np
import pytest

from veneur_tpu import observe
from veneur_tpu.cluster import wire
from veneur_tpu.cluster.protos import forward_pb2, metric_pb2
from veneur_tpu.durability import records as drecords
from veneur_tpu.ingest.admission import AdmissionController
from veneur_tpu.ingest.parser import GLOBAL_ONLY, MetricKey
from veneur_tpu.models import pipeline
from veneur_tpu.models.pipeline import (STAGE_TALLY, AggregationEngine,
                                        DigestStage, EngineConfig,
                                        ImportFoldReroute)

LADDER = (8, 32)


def _timer(ml, name, means, weights=None, packed=False):
    m = ml.metrics.add(name=name, type=metric_pb2.Timer, tags=["env:prod"])
    td = m.histogram.t_digest
    means = np.asarray(means, np.float64)
    weights = np.ones(len(means)) if weights is None \
        else np.asarray(weights, np.float64)
    if packed:
        td.packed_centroids = wire.encode_q16_centroids(means, weights)
    else:
        for mean, w in zip(means, weights):
            td.centroids.add(mean=float(mean), weight=float(w))
    td.min, td.max = float(means.min()), float(means.max())
    td.sum = float((means * weights).sum())
    td.count = float(weights.sum())
    td.reciprocal_sum = float((weights / means).sum())


def _draw(rng, n):
    return np.sort(rng.lognormal(4.6, 0.3, n)), rng.integers(1, 9, n)


def _request(rng, digests, packed=False, scalars=True):
    """One request: `digests` as (key name, centroids); a counter and a
    gauge among them unless `scalars` is off."""
    ml = forward_pb2.MetricList()
    for i, (name, n) in enumerate(digests):
        _timer(ml, name, *_draw(rng, n), packed=packed)
        if scalars and i == len(digests) // 2:
            ml.metrics.add(name="c.hits", type=metric_pb2.Counter) \
                .counter.value = 3
            ml.metrics.add(name="c.level", type=metric_pb2.Gauge) \
                .gauge.value = 1.5
    return ml.SerializeToString()


def _requests(case, rng):
    if case == "piles_one_digest_deep":
        return [_request(rng, [(f"k{i}", 4) for i in range(20)]
                         + [(f"w{i}", 130) for i in range(3)])]
    if case == "piles_32_deep_on_hot_rows":
        # 32 senders, each with the fleet's four hot keys and eight of
        # its cold ones
        return [_request(rng, [(f"hot{i}", 64) for i in range(4)]
                         + [(f"cold{i}", 4) for i in range(8)])
                for _sender in range(32)]
    if case == "a_pile_over_the_cap_both_passes":
        # 17 chunks of the cap cluster to 17 x C > cap lanes: a second
        # pass, on the first one's outputs (sorted_prefix)
        return [_request(rng, [("k0", 100), ("huge", 17 * 4096),
                               ("k0", 100), ("k1", 7)])]
    if case == "stage_fills_in_the_middle_of_a_request":
        return [_request(rng, [(f"k{i % 30}", 4) for i in range(130)]),
                _request(rng, [(f"k{i}", 9) for i in range(30)])]
    if case == "centroid_bound_in_the_middle_of_a_request":
        return [_request(rng, [(f"k{i % 30}", 30) for i in range(100)])]
    if case == "two_requests_interleaved_by_slot":
        return [_request(rng, [("shared", 20)]
                         + [(f"k{i}", 4) for i in range(0, 24, 2)]),
                _request(rng, [(f"k{i}", 4) for i in range(1, 24, 2)]
                         + [("shared", 33), ("k4", 9)])]
    if case == "every_metric_falls_back":
        return [_request(rng, [(f"k{i % 9}", 12 + i) for i in range(25)],
                         packed=True, scalars=False)]
    if case in ("direct_strategy", "whole_bank_arm"):
        return [_request(rng, [(f"k{i % 12}", 5 + i % 7)
                               for i in range(40)]),
                _request(rng, [(f"k{i}", 60) for i in range(6)])]
    raise AssertionError(case)


# case -> what differs from a 256-slot t-digest bank under work sets of
# 8 and 32 rows and the module's own stage bounds
CASES = {
    "piles_one_digest_deep": {},
    "piles_32_deep_on_hot_rows": {},
    "a_pile_over_the_cap_both_passes": {},
    "stage_fills_in_the_middle_of_a_request": {"digests": 48},
    "centroid_bound_in_the_middle_of_a_request": {"centroids": 1000},
    "two_requests_interleaved_by_slot": {},
    "every_metric_falls_back": {},
    "direct_strategy": {"backend": "req"},
    "whole_bank_arm": {"ladder": None},
}


def _engine(backend="tdigest", slots=256):
    return AggregationEngine(EngineConfig(
        histogram_slots=slots, counter_slots=8, gauge_slots=8,
        set_slots=8, hll_precision=10, batch_size=256, buffer_depth=256,
        percentiles=(0.5, 0.99), histogram_backend=backend,
        aggregates=("min", "max", "count", "sum"), is_global=True))


# ---- the reference: the stage and the landing as they were ----

def _parent_landings(eng, requests):
    """The parent's stage over `requests` (each a list of parsed
    metrics): one `(slot, means, weights, min, max, sum, count,
    reciprocal sum)` a digest, appended in wire order, a landing at the
    digest that brings the list to `_IMPORT_STAGE_DIGESTS` or its
    centroids to `_IMPORT_STAGE_CENTROIDS` -> the landings' items. The
    keys are the engine's own (looked up after it staged them)."""
    landings, stage, total = [], [], 0
    for pbs in requests:
        records, means, weights, _bad = wire.decode_metric_batch(pbs)
        for rec in records:
            if rec[0] != wire.IMPORT_HISTOGRAM:
                continue
            slot = eng.histo_keys.lookup(rec[1], GLOBAL_ONLY)
            if slot < 0:
                continue
            a, b = rec[3:5]
            stage.append((slot, means[a:b], weights[a:b],
                          *(float(v) for v in rec[5:])))
            total += b - a
            if (len(stage) >= pipeline._IMPORT_STAGE_DIGESTS
                    or total >= pipeline._IMPORT_STAGE_CENTROIDS):
                landings.append(stage)
                stage, total = [], 0
    return landings + ([stage] if stage else [])


def _parent_scalars(items):
    """merge_scalars' operands as the parent built them."""
    n = pipeline._IMPORT_STAGE_DIGESTS
    out = []
    for i in range(0, max(len(items), 1), n):
        part = items[i:i + n]
        slots = np.full(n, -1, np.int32)
        stats = np.zeros((5, n), np.float32)
        if part:
            slots[:len(part)] = [it[0] for it in part]
            stats[:, :len(part)] = np.array(
                [it[3:8] for it in part], np.float32).T
        out.append((slots, *stats))
    return out


def _parent_operands(eng, items, cluster_rows):
    """The host half of the parent's `_land_imports_clustered` for one
    landing's `items`: the piles a dictionary of lists, the widths a
    `sum(len(...))` a pile, the `[R, L]` fill two slice assignments a
    digest. -> every cluster_rows call as (sorted_prefix, values,
    weights), (R, slot_ids), merge_scalars' operands, the tally."""
    bank = eng.histo_bank
    K, C = bank.num_slots, bank.num_centroids
    by_slot = {}
    for s, means, weights, *_ in items:
        by_slot.setdefault(s, []).append((means, weights))
    lanes = eng._land_lanes(C)
    cap = lanes[-1]
    calls, trusted = [], set()
    while True:
        oversized = [s for s, piles in by_slot.items()
                     if sum(len(m) for m, _ in piles) > cap]
        if not oversized:
            break
        batches = {0: ([], []), C: ([], [])}
        piles_per_chunk = cap // C
        for s in oversized:
            piles = by_slot[s]
            if s in trusted:
                owners, chunks = batches[C]
                for i in range(0, len(piles), piles_per_chunk):
                    group = piles[i:i + piles_per_chunk]
                    chunk = np.zeros((2, cap), np.float32)
                    for g, (m, w) in enumerate(group):
                        chunk[0, g * C:g * C + len(m)] = m
                        chunk[1, g * C:g * C + len(m)] = w
                    owners.append(s)
                    chunks.append(chunk)
            else:
                owners, chunks = batches[0]
                flat = np.stack([
                    np.concatenate([np.asarray(p[i], np.float32)
                                    for p in piles]) for i in (0, 1)])
                for i in range(0, flat.shape[1], cap):
                    chunk = np.zeros((2, cap), np.float32)
                    part = flat[:, i:i + cap]
                    chunk[:, :part.shape[1]] = part
                    owners.append(s)
                    chunks.append(chunk)
            by_slot[s] = []
        rows = pipeline._IMPORT_CHUNK_ROWS
        for prefix, (owners, chunks) in batches.items():
            for i in range(0, len(owners), rows):
                part = chunks[i:i + rows]
                both = np.zeros((2, rows, cap), np.float32)
                both[:, :len(part)] = np.stack(part, axis=1)
                calls.append((prefix, both[0], both[1]))
                cm, cw = (np.asarray(a) for a in cluster_rows(
                    eng._heng, *both, num_centroids=C,
                    sorted_prefix=prefix, lanes=lanes))
                for row, s in enumerate(owners[i:i + rows]):
                    by_slot[s].append((cm[row], cw[row]))
        trusted.update(oversized)
    by_slot = dict(sorted(by_slot.items()))
    slot_ids = np.fromiter(by_slot.keys(), np.int32, len(by_slot))
    S = len(slot_ids)
    R = eng._land_rows(S, K)
    widest = max(sum(len(m) for m, _ in piles)
                 for piles in by_slot.values())
    L = next(n for n in lanes if n >= widest)
    both = np.zeros((2, R or K, L), np.float32)
    filled = 0
    for row, piles in enumerate(by_slot.values()):
        off = 0
        for m, w in piles:
            n = len(m)
            both[0, row, off:off + n] = m
            both[1, row, off:off + n] = w
            off += n
        filled += off
    calls.append((0, both[0], both[1]))
    return calls, (R, slot_ids), _parent_scalars(items), {
        "import_land_rows": 0 if R is None else S,
        "import_land_bank": int(R is None),
        "import_land_lanes": (R or K) * L,
        "import_land_lanes_filled": filled,
        "import_land_prechunked": len(trusted)}


def _parent_direct(eng, items):
    """The parent's `_land_imports_direct` host half: merge_centroids'
    operands, a fixed width a call."""
    W = eng._DIRECT_LAND_WIDTH
    slots = np.concatenate([
        np.full(len(it[1]), it[0], np.int32) for it in items])
    means = np.concatenate([np.asarray(it[1], np.float32) for it in items])
    wts = np.concatenate([np.asarray(it[2], np.float32) for it in items])
    out = []
    for i in range(0, len(slots), W):
        seg = slice(i, min(len(slots), i + W))
        n = seg.stop - seg.start
        ps = np.full(W, -1, np.int32)
        pm = np.zeros(W, np.float32)
        pw = np.zeros(W, np.float32)
        ps[:n], pm[:n], pw[:n] = slots[seg], means[seg], wts[seg]
        out.append((ps, pm, pw))
    return out


# ---- the landing under test, watched at the device's door ----

def _bits(operands):
    return [(np.asarray(a).dtype.str, np.asarray(a).shape,
             np.asarray(a).tobytes()) for a in operands]


def _watch(eng, monkeypatch):
    """Every operand the engine's landings hand a device program, in
    order, and each landing's own tally."""
    seen = {"cluster": [], "work": [], "scalars": [], "points": [],
            "did": []}
    heng = type(eng._heng)
    inner = {}

    def spy(name, record):
        orig = inner[name] = getattr(heng, name)

        def call(self, *a, **kw):
            record(*a, **kw)
            return orig(self, *a, **kw)
        monkeypatch.setattr(heng, name, call)

    if eng._heng.import_strategy == "cluster":
        spy("cluster_rows", lambda values, weights, **kw: seen[
            "cluster"].append((kw.get("sorted_prefix", 0),
                               *_bits((values, weights)))))
    else:
        spy("merge_centroids", lambda bank, *a: seen["points"].append(
            _bits(a)))
    spy("merge_scalars", lambda bank, *a: seen["scalars"].append(_bits(a)))
    land_clustered, land = eng._land_clustered, eng._land_import_centroids

    def clustered(bank, R, slot_ids, cmeans, cwts):
        seen["work"].append((R, *_bits((slot_ids,))))
        return land_clustered(bank, R, slot_ids, cmeans, cwts)

    def landing(bank, stage, dirty):
        bank, did = land(bank, stage, dirty)
        if stage.digests:
            seen["did"].append(did)
        return bank, did
    eng._land_clustered = clustered
    eng._land_import_centroids = landing
    return seen, inner


@pytest.mark.parametrize("case", list(CASES))
def test_the_landing_hands_the_device_what_the_per_digest_loops_did(
        case, monkeypatch):
    opts = CASES[case]
    monkeypatch.setattr(pipeline, "_IMPORT_LAND_ROWS",
                        opts.get("ladder", LADDER) or ())
    for name in ("digests", "centroids"):
        if name in opts:
            monkeypatch.setattr(
                pipeline, "_IMPORT_STAGE_" + name.upper(), opts[name])
    rng = np.random.default_rng(44)
    raws = _requests(case, rng)
    requests = [forward_pb2.MetricList.FromString(raw).metrics
                for raw in raws]
    eng = _engine(opts.get("backend", "tdigest"))
    eng.enable_dirty_tracking()
    if case == "two_requests_interleaved_by_slot":
        # rows minted in key order, so the two requests' rows alternate
        for i in range(24):
            eng.histo_keys.lookup(
                MetricKey(f"k{i}", "timer", "env:prod"), GLOBAL_ONLY)
    seen, inner = _watch(eng, monkeypatch)
    for op, (pbs, raw) in enumerate(zip(requests, raws), 1):
        assert eng.import_list(op, pbs, raw) == ([], [])
    mid_interval = len(seen["did"])
    with eng.lock:
        eng._flush_import_centroids()
    n_digests = sum(pb.WhichOneof("value") == "histogram"
                    for pbs in requests for pb in pbs)
    assert (eng._import_digests_block, eng._import_digests_single) \
        == (n_digests, 0)
    if wire.native_decode_fn() is not None:
        fallback = n_digests if case == "every_metric_falls_back" else 0
        assert eng._import_decode_fallback == fallback
        assert eng._import_decode_native \
            == sum(len(pbs) for pbs in requests) - fallback

    landings = _parent_landings(eng, requests)
    assert len(seen["did"]) == len(landings)
    assert sum(len(items) for items in landings) == n_digests
    want = {"cluster": [], "work": [], "scalars": [], "points": [],
            "did": []}
    for items in landings:
        if opts.get("backend") == "req":
            want["points"] += [_bits(a) for a in _parent_direct(eng, items)]
            want["scalars"] += [_bits(a) for a in _parent_scalars(items)]
            want["did"].append({})
            continue
        calls, (R, slot_ids), scalars, did = _parent_operands(
            eng, items, inner["cluster_rows"])
        want["cluster"] += [(p, *_bits((v, w))) for p, v, w in calls]
        want["work"].append((R, *_bits((slot_ids,))))
        want["scalars"] += [_bits(a) for a in scalars]
        want["did"].append(did)
    for what in want:
        assert seen[what] == want[what], what
    touched = sorted({it[0] for items in landings for it in items})
    assert np.flatnonzero(eng._dirty[0]).tolist() == touched

    # and each case is the case it says it is
    sizes = [len(items) for items in landings]
    if case == "piles_one_digest_deep":
        assert sizes == [23] and len(touched) == 23
    elif case == "piles_32_deep_on_hot_rows":
        assert want["did"][0]["import_land_lanes"] == 32 * 2048
        assert want["did"][0]["import_land_lanes_filled"] \
            == 32 * (4 * 64 + 8 * 4)
    elif case == "a_pile_over_the_cap_both_passes":
        assert [p for p, *_ in want["cluster"]] == [0, 256, 0]
        assert want["did"][0]["import_land_prechunked"] == 1
    elif case == "stage_fills_in_the_middle_of_a_request":
        assert (mid_interval, sizes) == (3, [48, 48, 48, 16])
    elif case == "centroid_bound_in_the_middle_of_a_request":
        assert (mid_interval, sizes) == (2, [34, 34, 32])
    elif case == "two_requests_interleaved_by_slot":
        assert len(eng._import_centroids.blocks) == 0 and sizes == [27]
        shared = eng.histo_keys.lookup(
            MetricKey("shared", "timer", "env:prod"), GLOBAL_ONLY)
        assert sum(it[0] == shared for it in landings[0]) == 2
    elif case == "whole_bank_arm":
        assert [d["import_land_bank"] for d in want["did"]] == [1]
    if case not in ("whole_bank_arm", "direct_strategy"):
        assert all(d["import_land_bank"] == 0 for d in want["did"])


# ---- what a request keeps when it is staged as a block ----

def _staged_names(eng):
    by_slot = {info.slot: key.name
               for key, info in eng.histo_keys._map.items()}
    return [by_slot[it[0]] for it in eng._import_centroids.items()]


def test_a_poison_pill_mid_block_rejects_itself_by_its_position(
        monkeypatch):
    """A digest whose row cannot be looked up rejects that metric alone,
    named by its place in the request; its neighbours on both sides
    stage, in their order."""
    rng = np.random.default_rng(1)
    raw = _request(rng, [(f"k{i}", 4) for i in range(5)] + [("evil", 4)]
                   + [(f"k{i}", 4) for i in range(5, 9)])
    pbs = forward_pb2.MetricList.FromString(raw).metrics
    eng = _engine()
    lookup = eng.histo_keys.lookup

    def poisoned(key, scope):
        if key.name == "evil":
            raise ValueError("poison pill")
        return lookup(key, scope)
    monkeypatch.setattr(eng.histo_keys, "lookup", poisoned)
    rerouted, rejected = eng.import_list(3, pbs, raw)
    assert rerouted == []
    assert [(pb.name, str(e)) for pb, e in rejected] \
        == [("evil", "poison pill")]
    assert rejected[0][0] is pbs[5]
    assert _staged_names(eng) == [f"k{i}" for i in range(9)]
    assert (eng._import_digests_block, eng.last_import_op) == (9, 3)
    assert list(eng._import_counter_acc.values()) == [3.0]


@pytest.mark.parametrize("way", ["bytes", "messages"])
def test_a_fold_reroute_mid_block_leaves_alone_by_its_position(way):
    """Two engines and a prefix budget of three keys: the over-budget
    digests, whose fold key is homed on the other engine, leave the
    block as (ImportFoldReroute, metric) pairs in wire order, the
    scalars' among them in their place; the admitted ones stage."""
    rng = np.random.default_rng(2)
    raw = _request(rng, [(f"svc.k{i}", 4) for i in range(8)])
    pbs = forward_pb2.MetricList.FromString(raw).metrics
    eng = _engine()
    adm = AdmissionController(registry=observe.TelemetryRegistry(),
                              max_keys_per_prefix=3)
    _fk, digest = adm.fold_key(MetricKey("svc.k0", "timer", "env:prod"))
    eng.attach_admission(adm, index=1 - digest % 2, n=2,
                         reroute=lambda m: None)
    rerouted, rejected = eng.import_list(
        5, pbs, raw if way == "bytes" else None)
    assert rejected == []
    # wire order: k0..k4, the counter, the gauge, k5..k7; the budget of
    # three is spent on k0, k1, k2 and every later key of the prefix
    # folds, onto a key homed elsewhere
    names = [pb.name for _fr, pb in rerouted]
    assert names == ["svc.k3", "svc.k4", "svc.k5", "svc.k6", "svc.k7"]
    assert all(isinstance(fr, ImportFoldReroute) for fr, _pb in rerouted)
    assert [pb for _fr, pb in rerouted] \
        == [pbs[i] for i in (3, 4, 7, 8, 9)]
    assert _staged_names(eng) == ["svc.k0", "svc.k1", "svc.k2"]
    assert eng._import_digests_block == 3


def test_the_watermark_and_the_counts_move_under_the_one_lock_hold():
    """While the request's block is being staged the engine's lock is
    held and neither the applied-op watermark nor the batch counts have
    moved; when import_list returns all three have."""
    rng = np.random.default_rng(3)
    raw = _request(rng, [(f"k{i}", 4) for i in range(6)])
    pbs = forward_pb2.MetricList.FromString(raw).metrics
    eng = _engine()
    inside = []
    stage = eng._stage_digests

    def watched(*cols):
        inside.append((eng.lock.locked(), eng.last_import_op,
                       eng._import_batches, eng._import_metrics))
        return stage(*cols)
    eng._stage_digests = watched
    eng.import_list(9, pbs, raw)
    assert inside == [(True, 0, 0, 0)]
    assert not eng.lock.locked()
    assert (eng.last_import_op, eng._import_batches,
            eng._import_metrics) == (9, 1, len(pbs)) == (9, 1, 8)


def _flush_rows(eng):
    return sorted((m.name, tuple(m.tags), repr(m.value))
                  for m in eng.flush(timestamp=44).metrics)


def test_a_checkpoint_saves_the_stage_a_tuple_a_digest(monkeypatch):
    """Blocks staged, one of them cut by a landing: checkpoint_state()
    saves what the parent saved (a tuple a digest, in arrival order),
    the journal's encoding round-trips it, and an engine restored from
    it, or from the parent's own list, flushes what this one does."""
    monkeypatch.setattr(pipeline, "_IMPORT_STAGE_DIGESTS", 48)
    rng = np.random.default_rng(4)
    raws = _requests("stage_fills_in_the_middle_of_a_request", rng)
    requests = [forward_pb2.MetricList.FromString(raw).metrics
                for raw in raws]
    eng = _engine()
    eng.enable_dirty_tracking()
    for op, (pbs, raw) in enumerate(zip(requests, raws), 1):
        eng.import_list(op, pbs, raw)
    assert [len(b[0]) for b in eng._import_centroids.blocks] == [16]
    raws.append(_request(rng, [(f"k{i}", 3) for i in range(5)]))
    requests.append(forward_pb2.MetricList.FromString(raws[-1]).metrics)
    eng.import_list(3, requests[-1], raws[-1])
    assert [len(b[0]) for b in eng._import_centroids.blocks] == [16, 5]
    state = eng.checkpoint_state()
    saved = state["staged"]["centroids"]
    parents = _parent_landings(eng, requests)[-1]
    assert len(saved) == len(parents) == 21

    def plain(items):
        return [(it[0], it[1].tobytes(), it[2].tobytes(), *it[3:])
                for it in items]
    assert plain(saved) == plain(parents)
    _idx, decoded = drecords.decode_engine_staged(
        drecords.encode_engine_staged(0, state["staged"]))
    assert plain(decoded["centroids"]) == plain(parents)

    want = _flush_rows(eng)
    for centroids in (decoded["centroids"], parents):
        fresh = _engine()
        fresh.enable_dirty_tracking()
        fresh.restore_checkpoint(
            state["fingerprint"], state["gauge_seq"],
            state["last_import_op"], state["interner"], state["banks"],
            dict(decoded, centroids=centroids))
        assert fresh._import_centroids.digests == 21
        assert fresh._import_centroids.centroids == 16 * 9 + 5 * 3
        assert _flush_rows(fresh) == want
    assert any(name == "k3.count" for name, _t, _v in want)


def test_the_stage_round_trips_its_two_forms():
    rng = np.random.default_rng(5)
    items = [(int(s), *(a.astype(np.float32) for a in _draw(rng, n)),
              1.0, 2.0, 3.0 + s, 4.0, 0.5) for s, n in
             ((7, 3), (2, 0), (7, 40), (9, 1))]
    stage = DigestStage.of_items(items)
    assert (stage.digests, stage.centroids) == (4, 44)
    assert [(it[0], it[1].tolist(), it[2].tolist(), *it[3:])
            for it in stage.items()] \
        == [(it[0], it[1].tolist(), it[2].tolist(), *it[3:])
            for it in items]
    empty = DigestStage.of_items([])
    assert (empty.digests, empty.centroids, empty.items()) == (0, 0, [])


def test_the_two_counters_say_which_way_the_digests_came():
    """A request's digests count as a block, `import_histogram`'s as a
    block of one; both ride `_last_flush_info` (the benchmark's
    `flush_path.global`) and reset with the interval."""
    assert STAGE_TALLY == ("import_digests_block", "import_digests_single")
    rng = np.random.default_rng(6)
    raw = _request(rng, [(f"k{i}", 4) for i in range(11)])
    eng = _engine()
    eng.import_list(1, forward_pb2.MetricList.FromString(raw).metrics, raw)
    eng.import_list(2, forward_pb2.MetricList.FromString(raw).metrics)
    for i in range(3):
        v, w = _draw(rng, 6)
        eng.import_histogram(MetricKey(f"one{i}", "timer", ""), v, w,
                             v[0], v[-1], float((v * w).sum()),
                             float(w.sum()))
    res = eng.flush(timestamp=1)
    for info in (eng._last_flush_info, res.stats["flush_path"]):
        assert (info["import_digests_block"],
                info["import_digests_single"]) == (22, 3)
    by = {m.name: m.value for m in res.metrics}
    assert by["k0.count"] > 0 and by["one0.count"] > 0
    eng.flush(timestamp=2)
    info = eng._last_flush_info
    assert (info["import_digests_block"],
            info["import_digests_single"]) == (0, 0)

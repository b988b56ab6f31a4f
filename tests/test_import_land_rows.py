"""The import landing's work set (ISSUE 32): the bank-side device work
of a clustered landing — compress, fill the emptied buffers, compress —
runs over the rows the landing touches, gathered out of the bank and
scattered back, not over the bank.

The oracle is the chain the landing was before, built here from the
`ops/tdigest.py` functions on a copy of the bank: `compress(bank)` ->
`merge_centroids` of the clustered centroids flattened -> `compress(
bank)`. A touched row's five leaves must be that chain's bit for bit;
an untouched row must keep the leaves it had, a waiting buffer
included; the exact scalars and the dirty bitmap are as before. Work
sets of 8 and 32 rows stand in for the module's 1,024 and 8,192
(`pipeline._IMPORT_LAND_ROWS`, read at call time), so a 256-slot bank
takes the row arm on the CPU.
"""

import numpy as np
import pytest

import jax

from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.models import pipeline
from veneur_tpu.models.pipeline import AggregationEngine, EngineConfig
from veneur_tpu.ops import tdigest

LADDER = (8, 32)
LEAVES = ("mean", "weight", "buf_value", "buf_weight", "buf_n")
SCALARS = ("vmin", "vmax", "vsum", "count", "recip", "vsum_lo",
           "count_lo", "recip_lo")


@pytest.fixture
def ladder(monkeypatch):
    monkeypatch.setattr(pipeline, "_IMPORT_LAND_ROWS", LADDER)


@pytest.fixture
def compress_rows(monkeypatch):
    """Row counts of every `tdigest.compress` dispatch, in order: a
    landing's passes, at the size they ran at."""
    seen = []
    orig = tdigest.compress

    def compress(bank, **kw):
        seen.append(bank.num_slots)
        return orig(bank, **kw)
    monkeypatch.setattr(tdigest, "compress", compress)
    return seen


def _engine(slots=256, **kw):
    kw.setdefault("buffer_depth", 256)
    return AggregationEngine(EngineConfig(
        histogram_slots=slots, counter_slots=8, gauge_slots=8,
        set_slots=8, hll_precision=10, batch_size=256,
        percentiles=(0.5, 0.99),
        aggregates=("min", "max", "count", "sum"), is_global=True, **kw))


def _digest(rng, n):
    v = rng.lognormal(4.6, 0.3, n).astype(np.float32)
    w = rng.integers(1, 5, n).astype(np.float32)
    return v, w


def _item(rng, slot, n):
    v, w = _digest(rng, n)
    return (slot, v, w, float(v.min()), float(v.max()),
            float((v * w).sum()), float(w.sum()), float((w / v).sum()))


def _host(bank):
    return {k: np.array(v) for k, v in bank._asdict().items()}


def _device(host):
    return tdigest.TDigestBank(**{k: jax.numpy.asarray(v)
                                  for k, v in host.items()})


def _seeded_bank(eng, rng, landed, waiting):
    """A bank as a landing finds one mid-interval: `landed` rows hold
    centroids of an earlier landing, `waiting` rows a half-full buffer
    of samples no compress has folded yet."""
    B = eng.histo_bank.buf_size
    bank = eng._land_import_centroids(
        eng.histo_bank, [_item(rng, s, 150) for s in landed], None)
    n = B // 2
    slots = np.repeat(np.asarray(waiting, np.int32), n)
    vals = rng.lognormal(4.6, 0.3, slots.size).astype(np.float32)
    return _host(tdigest.add_batch(
        bank, slots, vals, np.ones(slots.size, np.float32),
        compression=eng.cfg.compression))


def _reference(eng, before, items):
    """compress -> merge_centroids -> compress over the whole bank (in
    chunks of the buffer depth where it is under C), then the exact
    scalars: what a landing was before it had a work set."""
    comp = eng.cfg.compression
    bank = _device(before)
    C, B = bank.num_centroids, bank.buf_size
    piles = {}
    for it in items:
        piles.setdefault(it[0], []).append(it)
    ids = np.array(sorted(piles), np.int32)
    W = max(128, -(-max(sum(len(it[1]) for it in piles[s])
                        for s in ids) // 128) * 128)
    vals = np.zeros((len(ids), W), np.float32)
    wts = np.zeros((len(ids), W), np.float32)
    for row, s in enumerate(ids):
        m = np.concatenate([it[1] for it in piles[s]])
        vals[row, :len(m)] = m
        wts[row, :len(m)] = np.concatenate([it[2] for it in piles[s]])
    cm, cw = (np.asarray(x) for x in tdigest.cluster_rows(
        vals, wts, compression=comp, num_centroids=C))
    for c0 in range(0, C, B):
        width = min(C, c0 + B) - c0
        bank = tdigest.compress(bank, compression=comp)
        bank = tdigest.merge_centroids(
            bank, np.repeat(ids, width),
            cm[:, c0:c0 + width].reshape(-1),
            cw[:, c0:c0 + width].reshape(-1))
    bank = tdigest.compress(bank, compression=comp)
    cols = [np.array([it[i] for it in items], np.float32)
            for i in range(3, 8)]
    return _host(tdigest.merge_scalars(
        bank, np.array([it[0] for it in items], np.int32), *cols))


# (bank slots, rows the landing touches, buffer depth) -> the work set
# it must take, None for the whole-bank passes
LANDINGS = {
    "one_under_the_first_set": ((256, 7, 256), 8),
    "the_first_set_exactly": ((256, 8, 256), 8),
    "one_over_it_takes_the_next": ((256, 9, 256), 32),
    "the_top_set_exactly": ((256, 32, 256), 32),
    "over_the_top_set_takes_the_bank": ((256, 33, 256), None),
    "a_bank_no_larger_than_the_set": ((32, 9, 256), None),
    "a_bank_smaller_than_every_set": ((8, 5, 256), None),
    "buffer_under_C_lands_in_chunks": ((256, 9, 64), 32),
    "buffer_under_C_over_the_bank": ((256, 33, 64), None),
}


@pytest.mark.parametrize("case", list(LANDINGS))
def test_landing_is_the_whole_bank_chain_on_the_rows_it_touches(
        case, ladder, compress_rows):
    (K, S, B), want = LANDINGS[case]
    rng = np.random.default_rng(32)
    eng = _engine(K, buffer_depth=B)
    touched = sorted(rng.choice(K, S, replace=False).tolist())
    rest = [s for s in range(K) if s not in touched]
    # earlier state on both sides of the landing: a touched and an
    # untouched row with centroids, one of each with a waiting buffer
    before = _seeded_bank(
        eng, rng, landed=touched[:2] + rest[:2],
        waiting=[touched[0], touched[-1], rest[0], rest[-1]])
    assert before["buf_n"][rest[-1]] == B // 2
    # digests in arrival order, not slot order; some rows twice, one
    # wide enough for a second cluster_rows width
    items = [_item(rng, s, 40) for s in touched[::-1]]
    items += [_item(rng, s, 130) for s in touched[:3]]
    ref = _reference(eng, before, items)

    del compress_rows[:]
    dirty = [np.zeros(K, bool)] + [np.zeros(8, bool)] * 3
    got = _host(eng._land_import_centroids(_device(before), items, dirty))

    chunks = -(-eng.histo_bank.num_centroids // B)
    assert compress_rows == [want or K] * (chunks + 1)
    def same(a, b, rows):
        return a[rows].tobytes() == b[rows].tobytes()
    for leaf in LEAVES:
        assert same(got[leaf], ref[leaf], touched), leaf
        if want is not None:
            # rows the landing did not touch keep what they had, a
            # waiting buffer included
            assert same(got[leaf], before[leaf], rest), leaf
    if want is not None:
        assert got["buf_n"][rest[-1]] == B // 2
    assert not got["buf_n"][touched].any()
    # the exact scalars and the dirty bitmap, as before
    for leaf in SCALARS:
        assert got[leaf].tobytes() == ref[leaf].tobytes(), leaf
    assert np.flatnonzero(dirty[0]).tolist() == touched
    # and the tally, taken where the landing is decided
    eng._count_landing(items)
    assert (eng._import_land_rows, eng._import_land_bank) == \
        ((S, 0) if want is not None else (0, 1))


def test_untouched_rows_are_folded_by_the_flush(ladder):
    """A buffer the landing left waiting is not lost: the flush
    program's own compress folds it, and the key's count is exact."""
    eng = _engine()
    eng.warmup()
    a = eng.histo_keys.lookup(MetricKey("a", "timer", ""), 0)
    eng.ingest_histo_batch(np.full(100, a, np.int32),
                           np.arange(100, dtype=np.float32) + 1,
                           np.ones(100, np.float32))
    rng = np.random.default_rng(1)
    for i in range(5):
        v, w = _digest(rng, 30)
        eng.import_histogram(MetricKey(f"k{i}", "timer", ""), v, w,
                             v.min(), v.max(), (v * w).sum(), w.sum())
    with eng.lock:
        eng._flush_import_centroids()
    assert int(eng.histo_bank.buf_n[a]) == 100
    by = {m.name: m.value for m in eng.flush(timestamp=1).metrics}
    assert (by["a.count"], by["a.min"], by["a.max"]) == (100.0, 1.0, 100.0)
    assert by["a.50percentile"] == pytest.approx(50.5, rel=0.02)
    assert eng._last_flush_info["import_land_rows"] == 5


def _traffic(rng, n_keys, n_digests):
    out = []
    for i in range(n_digests):
        v, w = _digest(rng, 30)
        out.append((MetricKey(f"lat.k{i % n_keys}", "timer", ""), v, w,
                    float(v.min()), float(v.max()), float((v * w).sum()),
                    float(w.sum()), float((w / v).sum())))
    return out


def _run(eng, traffic, seen):
    eng.warmup()
    del seen[:]     # the warm-up's own passes
    for d in traffic:
        eng.import_histogram(*d)
    res = eng.flush(timestamp=1)
    return ({m.name: m.value for m in res.metrics},
            dict(eng._last_flush_info), res.stats)


@pytest.mark.parametrize("ordering", ["double_buffered", "legacy"])
def test_both_callers_take_the_work_set(ordering, monkeypatch,
                                        compress_rows):
    """A landing mid-interval (the stage fills at its 24th digest, on
    the importing thread) and the flush's own (`_land_retired`, or
    `_flush_import_centroids` under the legacy ordering) both go
    through a work set; the flush is that of an engine whose landings
    pass over the whole bank, metric by metric the same import."""
    monkeypatch.setattr(pipeline, "_IMPORT_STAGE_DIGESTS", 24)
    kw = {} if ordering == "double_buffered" else {
        "flush_double_buffer": False}
    traffic = _traffic(np.random.default_rng(7), 20, 40)

    monkeypatch.setattr(pipeline, "_IMPORT_LAND_ROWS", ())
    want, info, _ = _run(_engine(**kw), traffic, compress_rows)
    assert (info["import_land_rows"], info["import_land_bank"]) == (0, 2)
    assert set(compress_rows) == {256}

    monkeypatch.setattr(pipeline, "_IMPORT_LAND_ROWS", LADDER)
    got, info, stats = _run(_engine(**kw), traffic, compress_rows)
    # 24 digests over 20 keys land mid-interval, 16 more at the flush
    assert compress_rows == [32, 32, 32, 32]
    assert (info["import_land_rows"], info["import_land_bank"]) == (36, 0)
    assert (stats["import_land_rows"], stats["import_land_bank"]) == (36, 0)
    assert stats["flush_path"]["import_land_rows"] == 36
    assert got.keys() == want.keys() and len(got) == 20 * 6
    for name, value in want.items():
        if name.endswith("percentile"):
            # the whole-bank passes re-cluster the first landing's rows
            # during the second: a mean may move by an ulp of its sum
            assert got[name] == pytest.approx(value, rel=1e-5), name
        else:
            assert got[name] == value, name


def test_the_tally_is_the_intervals():
    """Counted under the lock where a landing is decided, noted at the
    flush, reset with the interval: an idle interval reads 0 / 0, and a
    64-slot bank (no set is smaller) counts its landings as passes over
    the bank."""
    eng = _engine(64)
    traffic = _traffic(np.random.default_rng(3), 10, 10)
    _, info, _ = _run(eng, traffic, [])
    assert (info["import_land_rows"], info["import_land_bank"]) == (0, 1)
    eng.flush(timestamp=2)
    info = eng._last_flush_info
    assert (info["import_land_rows"], info["import_land_bank"]) == (0, 0)


@pytest.mark.parametrize("slots, sizes", [
    (64, ()), (1024, ()), (2048, (1024,)), (8192, (1024,)),
    (8193, (1024, 8192)), (131072, (1024, 8192))])
def test_the_ladder_a_bank_takes_follows_from_its_shape(slots, sizes):
    """The module's own ladder: its top is the stage's digest bound, a
    set serves only a bank larger than it, and a landing takes the
    smallest set that holds it."""
    assert pipeline._IMPORT_LAND_ROWS[-1] == pipeline._IMPORT_STAGE_DIGESTS
    land = AggregationEngine._land_rows
    assert tuple(R for R in pipeline._IMPORT_LAND_ROWS
                 if land(R, slots) == R) == sizes
    for R in sizes:
        assert land(R - 1, slots) == land(R, slots) == R
    top = sizes[-1] if sizes else 0
    assert land(top + 1, slots) is None
    if sizes:
        assert land(1, slots) == sizes[0]


def test_warmup_compiles_every_work_set_program():
    """After warmup() on a default EngineConfig (32,768 slots: both
    sets serve it), landings of 1, 1,000, 1,808 and 8,192 rows compile
    nothing of the work set's: gather, compress, fill and scatter have
    shapes that follow from the configuration alone. What still
    compiles at a landing is what draws its shape from the data, as
    before: cluster_rows' [S, W] and merge_scalars' digest count."""
    compiled = []
    armed = [False]

    def listen(event, duration, **kw):
        if armed[0] and event == \
                "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name", "?"))
    jax.monitoring.register_event_duration_secs_listener(listen)

    eng = AggregationEngine(EngineConfig(
        counter_slots=8, gauge_slots=8, set_slots=8, hll_precision=10,
        is_global=True))
    assert eng.histo_bank.num_slots == 1 << 15
    eng.warmup()
    rng = np.random.default_rng(5)
    armed[0] = True
    try:
        for S in (1, 1000, 1808, 8192):
            items = [_item(rng, s, 8) for s in range(S)]
            with eng.lock:
                eng._import_centroids = items
                eng._flush_import_centroids()
            jax.block_until_ready(eng.histo_bank)
    finally:
        armed[0] = False
    assert eng._import_land_rows == 1 + 1000 + 1808 + 8192
    assert eng._import_land_bank == 0
    assert set(compiled) <= {"jit(cluster_rows)",
                             "jit(merge_scalars)"}, compiled
    assert compiled.count("jit(cluster_rows)") == 4

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
from veneur_tpu.models.pipeline import (AggregationEngine, DigestStage,
                                        EngineConfig)
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
    bank, _did = eng._land_import_centroids(
        eng.histo_bank,
        DigestStage.of_items([_item(rng, s, 150) for s in landed]), None)
    n = B // 2
    slots = np.repeat(np.asarray(waiting, np.int32), n)
    vals = rng.lognormal(4.6, 0.3, slots.size).astype(np.float32)
    return _host(tdigest.add_batch(
        bank, slots, vals, np.ones(slots.size, np.float32),
        compression=eng.cfg.compression))


def _reference(eng, before, items, scalars=None):
    """compress -> merge_centroids -> compress over the whole bank (in
    chunks of the buffer depth where it is under C), then the exact
    scalars (`scalars`' where the piles in `items` are no longer the
    staged digests): what a landing was before it had a work set."""
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
    items = scalars or items
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
    bank, did = eng._land_import_centroids(
        _device(before), DigestStage.of_items(items), dirty)
    got = _host(bank)

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
    # and the tally, the landing's own account of what it did
    assert (did["import_land_rows"], did["import_land_bank"]) == \
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


def _piles(rng, S, digests, centroids):
    """Staged items for rows 0..S-1: row s gets digests[s % len]
    digests of centroids[s % len] centroids each."""
    return [_item(rng, s, centroids[s % len(centroids)])
            for s in range(S)
            for _ in range(digests[s % len(digests)])]


# bank slots -> (rows a landing touches, digests a pile, centroids a
# digest): widths on every step of the lane ladder, the stage's own
# row counts (PERF.md 5c: 984, 1,000; steady_10k's tail of 1,808) and,
# on the 512-slot bank, the whole-bank arm
NO_COMPILE = {
    1 << 15: [(1, (1,), (8,)), (984, (8, 32), (64, 4)),
              (1000, (32, 12), (120, 4)), (1808, (1,), (130,)),
              (8192, (1, 2), (118, 130)), (7, (3,), (1500,))],
    512: [(1, (32,), (8,)), (100, (1, 16), (130, 64)),
          (512, (2,), (300,))],
}


@pytest.mark.parametrize("slots", list(NO_COMPILE))
def test_after_warmup_no_import_compiles(slots, compiled):
    """After warmup() nothing an import dispatches compiles: histogram
    landings of any row count and pile width up to the stage's bounds,
    an oversized pile through both arms of the pre-cluster loop, set
    tails, imported counters and gauges. Every shape follows from the
    EngineConfig and the module's constants; on the default 32,768-slot
    bank both work sets serve, a 512-slot bank takes the whole-bank
    arm."""
    names, armed = compiled
    scalars = min(slots, 2048)
    eng = AggregationEngine(EngineConfig(
        histogram_slots=slots, counter_slots=scalars,
        gauge_slots=scalars, set_slots=64, hll_precision=10,
        is_global=True))
    eng.warmup()
    rng = np.random.default_rng(5)
    C = eng.histo_bank.num_centroids
    cap = eng._land_lanes(C)[-1]
    armed[0] = True
    landings = NO_COMPILE[slots] + [
        # 17 chunks of the cap cluster to 17 x C > cap lanes: a second,
        # trusted pass (the sorted_prefix arm)
        (2, (1,), (17 * cap,))]
    for S, digests, centroids in landings:
        with eng.lock:
            eng._import_centroids = DigestStage.of_items(
                _piles(rng, S, digests, centroids))
            eng._flush_import_centroids()
        jax.block_until_ready(eng.histo_bank)
    assert eng._import_land_prechunked == 2 + (7 if slots > 512 else 0)
    for n in (1, 255, 256, 300):
        for i in range(n):
            eng.import_set(MetricKey(f"s{i % 50}", "set", ""),
                           rng.integers(0, 9, 1 << 10).astype(np.uint8))
        with eng.lock:
            eng._flush_import_sets()
    for n in (1, 300, scalars - 1, scalars):
        for i in range(n):
            eng.import_counter(MetricKey(f"c{i}", "counter", ""), 1.0)
            eng.import_gauge(MetricKey(f"g{i}", "gauge", ""), float(i))
        with eng.lock:
            eng._flush_import_scalars()
    jax.block_until_ready((eng.set_bank, eng.counter_bank,
                           eng.gauge_bank))
    armed[0] = False
    assert names == []
    # and it all landed: the flush after it compiles nothing either
    by = {m.name: m.value for m in eng.flush(timestamp=1).metrics}
    assert by[f"c{scalars - 1}"] == 1.0 and by["c0"] == 4.0
    assert by[f"g{scalars - 1}"] == float(scalars - 1)


def _parent_landing(eng, before, items):
    """The landing as the parent commit computed it, every device
    operand shaped by the data: oversized piles cut in [n_chunks, cap]
    matrices (full sort, then the sorted_prefix arm on its own
    outputs), the piles in [S, W] with W the widest rounded up to 128,
    then `_reference`'s whole-bank chain and merge_scalars at the
    digest count."""
    comp = eng.cfg.compression
    C = before["mean"].shape[1]
    cap = max(pipeline._IMPORT_W_CAP, 2 * C)
    by_slot = {}
    for it in items:
        by_slot.setdefault(it[0], []).append((it[1], it[2]))
    trusted = set()
    while True:
        over = [s for s, piles in by_slot.items()
                if sum(len(m) for m, _ in piles) > cap]
        if not over:
            break
        for s in over:
            if s in trusted:
                per = cap // C
                groups = [by_slot[s][i:i + per]
                          for i in range(0, len(by_slot[s]), per)]
                rows = [[np.concatenate(
                    [np.pad(p[i], (0, C - len(p[i]))) for p in g])
                    for i in (0, 1)] for g in groups]
                prefix = C
            else:
                flat = [np.concatenate([p[i] for p in by_slot[s]])
                        for i in (0, 1)]
                rows = [[flat[0][i:i + cap], flat[1][i:i + cap]]
                        for i in range(0, len(flat[0]), cap)]
                prefix = 0
            v, w = (np.stack([np.pad(r[i], (0, cap - len(r[i])))
                              for r in rows]) for i in (0, 1))
            cm, cw = (np.asarray(x) for x in tdigest.cluster_rows(
                v, w, compression=comp, num_centroids=C,
                sorted_prefix=prefix))
            by_slot[s] = list(zip(cm, cw))
        trusted.update(over)
    flat = [(s, np.concatenate([m for m, _ in piles]),
             np.concatenate([w for _, w in piles]))
            for s, piles in by_slot.items()]
    scalars = [(it[0], None, None) + tuple(it[3:]) for it in items]
    return _reference(eng, before, flat, scalars)


# name -> (bank slots, [(row, digests, centroids a digest)]): piles
# whose width falls on, under and over steps of the lane ladder, on a
# work set and over the whole bank
EXACT = {
    "one_digest_rows": (256, [(3, 1, 8), (200, 1, 130), (7, 1, 118)]),
    "piles_8_to_32_wide": (256, [(s, 8 + s, 30) for s in range(0, 25, 4)]),
    "a_step_exactly_and_one_over": (256, [(1, 4, 128), (2, 1, 513)]),
    "the_widest_step_below_the_cap": (256, [(9, 16, 128), (4, 1, 2049)]),
    "the_cap_exactly": (256, [(5, 32, 128), (6, 1, 40)]),
    "oversized_one_pass": (256, [(5, 33, 128), (6, 1, 40)]),
    "oversized_then_the_trusted_arm": (256, [(0, 1, 17 * 4096),
                                             (255, 2, 100)]),
    "whole_bank_arm": (32, [(s, 1 + s % 5, 90) for s in range(0, 32, 3)]),
    "whole_bank_arm_oversized": (8, [(7, 1, 9000), (0, 1, 1)]),
}


@pytest.mark.parametrize("case", list(EXACT))
def test_fixed_shape_landing_is_the_data_shaped_one_bit_for_bit(
        case, ladder):
    """Padding lanes weigh 0 and padding rows are empty, and the
    clustering leaves both out: for the same staged piles the landing
    at its configured [R, L] leaves every leaf of the bank as the
    parent's landing at the data's [S, W] did."""
    K, piles = EXACT[case]
    rng = np.random.default_rng(33)
    eng = _engine(K)
    items = [_item(rng, s, n) for s, digests, n in piles
             for _ in range(digests)]
    rng.shuffle(items)
    before = _seeded_bank(eng, rng, landed=[piles[0][0]],
                          waiting=[piles[-1][0]])
    ref = _parent_landing(eng, before, items)
    got = _host(eng._land_import_centroids(
        _device(before), DigestStage.of_items(items), None)[0])
    touched = sorted({s for s, _, _ in piles})
    for leaf in LEAVES:
        assert got[leaf][touched].tobytes() == \
            ref[leaf][touched].tobytes(), leaf
    for leaf in SCALARS:
        assert got[leaf].tobytes() == ref[leaf].tobytes(), leaf


@pytest.mark.parametrize("slots, C, sets", [
    (64, 256, (64,)), (1024, 256, (1024,)), (2048, 256, (1024, 2048)),
    (8192, 256, (1024, 8192)), (1 << 15, 256, (1024, 8192)),
    (1 << 17, 256, (1024, 8192)), (1 << 17, 2560, (1024, 8192))])
def test_the_cluster_shapes_follow_from_the_bank(slots, C, sets):
    """Rows: the work sets that serve the bank, and the bank's own
    count where a stage can outgrow them. Lanes: the module's steps
    under the pre-cluster cap, then the cap. Sixteen programs or
    fewer with the two pre-cluster arms."""
    lanes = AggregationEngine._land_lanes(C)
    cap = max(pipeline._IMPORT_W_CAP, 2 * C)
    assert lanes[-1] == cap and list(lanes) == sorted(set(lanes))
    assert lanes[:-1] == tuple(
        n for n in pipeline._IMPORT_LAND_LANES if n < cap)
    shapes = AggregationEngine._cluster_shapes(slots, C)
    assert shapes == [(R, L) for R in sets for L in lanes]
    assert len(shapes) + 2 <= 16
    # every landing the stage can hold finds its shape among them
    for S in {1, min(slots, 1000), min(slots, 1025),
              min(slots, pipeline._IMPORT_STAGE_DIGESTS)}:
        R = AggregationEngine._land_rows(S, slots) or slots
        assert {(R, L) for L in lanes} <= set(shapes)


@pytest.mark.parametrize("slots, want", [
    (8, (8,)), (1024, (1024,)), (1025, (1024, 1025)),
    (1 << 14, (1024, 1 << 14))])
def test_the_scalar_rows_follow_from_the_bank(slots, want):
    assert AggregationEngine._scalar_rows(slots) == want


# name -> (piles as (digests, centroids a digest), then what the tally
# must read: rows of the cluster dispatch x lanes, lanes filled, piles
# pre-chunked) on a 256-slot bank under work sets of 8 and 32 rows
TALLIES = {
    "one_narrow_pile": ([(1, 8)], 8 * 128, 8, 0),
    "nine_rows_take_the_next_set": ([(1, 100)] * 8 + [(2, 100)],
                                    32 * 256, 1000, 0),
    "a_pile_32_digests_wide": ([(32, 64), (1, 4)], 8 * 2048, 2052, 0),
    "over_the_top_set": ([(1, 8)] * 33, 256 * 128, 264, 0),
    "an_oversized_pile_is_cut_first": ([(1, 9000), (1, 130)],
                                       8 * 1024, 3 * 256 + 130, 1),
    "cut_twice": ([(1, 17 * 4096)], 8 * 512, 2 * 256, 1),
}


@pytest.mark.parametrize("case", list(TALLIES))
def test_the_tally_counts_lanes_as_the_landing_pads_them(
        case, ladder, monkeypatch):
    """`import_land_lanes` is the [R, L] the cluster program was handed
    (padding included), `import_land_lanes_filled` the lanes of it the
    piles filled, `import_land_prechunked` the piles the pre-cluster
    loop cut first; the landing returns them, its caller adds them to
    the interval's tally, and they are checked here against what the
    landing dispatched."""
    piles, lanes, filled, cut = TALLIES[case]
    rng = np.random.default_rng(2)
    eng = _engine(256)
    handed = []
    orig = eng._heng.cluster_program

    def spy(rows, n, C, sorted_prefix=0):
        handed.append((rows, n))
        return orig(rows, n, C, sorted_prefix=sorted_prefix)
    monkeypatch.setattr(type(eng._heng), "cluster_program",
                        lambda self, *a, **kw: spy(*a, **kw))
    items = [_item(rng, s, n) for s, (digests, n) in enumerate(piles)
             for _ in range(digests)]
    with eng.lock:
        eng._import_centroids = DigestStage.of_items(items)
        eng._flush_import_centroids()
    assert (eng._import_land_lanes, eng._import_land_lanes_filled,
            eng._import_land_prechunked) == (lanes, filled, cut)
    assert handed[-1][0] * handed[-1][1] == lanes
    assert all(shape == (pipeline._IMPORT_CHUNK_ROWS, 4096)
               for shape in handed[:-1])
    eng.flush(timestamp=1)
    info = eng._last_flush_info
    assert (info["import_land_lanes"], info["import_land_lanes_filled"],
            info["import_land_prechunked"]) == (lanes, filled, cut)
    assert eng._import_land_lanes == 0

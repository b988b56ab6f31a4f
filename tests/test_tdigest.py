"""Parity tests for the batched t-digest bank.

Mirrors the property-style strategy of tdigest/merging_digest_test.go:
distributional quantile-error bounds, merge-of-shards == single digest,
plus exact-aggregate checks, all against (a) numpy exact quantiles and
(b) the OracleDigest port of the Go algorithm.
"""

import jax
import numpy as np
import pytest

from veneur_tpu.ops import tdigest
from oracle_tdigest import OracleDigest

QS = np.array([0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99], np.float32)


def _bank_quantiles(values, weights=None, compression=100.0, buf_size=256,
                    batch=4096):
    """Feed one slot of a 4-slot bank and return its quantiles."""
    bank = tdigest.init(4, compression=compression, buf_size=buf_size)
    n = len(values)
    weights = np.ones(n, np.float32) if weights is None else weights
    for i in range(0, n, batch):
        v = np.asarray(values[i:i + batch], np.float32)
        w = np.asarray(weights[i:i + batch], np.float32)
        s = np.full(len(v), 1, np.int32)
        bank = tdigest.add_batch(bank, s, v, w, compression=compression)
    bank = tdigest.compress(bank, compression=compression)
    out = np.asarray(tdigest.quantile(bank, QS))
    return bank, out[1]


@pytest.mark.parametrize("dist", ["uniform", "normal", "lognormal",
                                  "sequential", "bimodal", "constant",
                                  "heavy_tail", "negative_mixed"])
def test_quantile_accuracy_vs_exact(dist):
    rng = np.random.default_rng(42)
    n = 50_000
    if dist == "uniform":
        data = rng.uniform(0, 100, n)
    elif dist == "normal":
        data = rng.normal(50, 10, n)
    elif dist == "lognormal":
        data = rng.lognormal(3, 1, n)
    elif dist == "bimodal":
        data = np.concatenate([rng.normal(10, 1, n // 2),
                               rng.normal(1000, 5, n - n // 2)])
    elif dist == "constant":
        data = np.full(n, 42.5)
    elif dist == "heavy_tail":
        data = rng.pareto(1.5, n) * 10 + 1   # long right tail
    elif dist == "negative_mixed":
        data = rng.normal(-500, 200, n)
    else:
        data = np.arange(n, dtype=np.float64)
    data = data.astype(np.float32)

    _, got = _bank_quantiles(data)
    exact = np.quantile(data, QS)
    spread = exact.max() - exact.min()
    # t-digest error bound: tight at tails, looser mid-distribution.
    # 1% of spread everywhere is well within the reference's own error.
    np.testing.assert_allclose(got, exact, atol=0.01 * spread + 1e-4)


def test_parity_vs_go_oracle():
    rng = np.random.default_rng(7)
    data = rng.gamma(2.0, 30.0, 20_000).astype(np.float32)
    _, got = _bank_quantiles(data)
    oracle = OracleDigest()
    for v in data:
        oracle.add(float(v))
    want = np.array([oracle.quantile(float(q)) for q in QS])
    spread = data.max() - data.min()
    # ±1% of spread parity with the Go-algorithm oracle (BASELINE target).
    np.testing.assert_allclose(got, want, atol=0.01 * spread)


def test_aggregates_exact():
    rng = np.random.default_rng(3)
    data = rng.uniform(1, 100, 10_000).astype(np.float32)
    rates = np.full(len(data), 0.5, np.float32)  # sample_rate 0.5 -> weight 2
    bank, _ = _bank_quantiles(data, weights=1.0 / rates)
    agg = {k: np.asarray(v)[1] for k, v in tdigest.aggregates(bank).items()}
    w = 2.0
    assert agg["min"] == pytest.approx(data.min())
    assert agg["max"] == pytest.approx(data.max())
    assert agg["count"] == pytest.approx(w * len(data), rel=1e-6)
    assert agg["sum"] == pytest.approx(w * data.sum(), rel=1e-4)
    assert agg["avg"] == pytest.approx(data.mean(), rel=1e-4)
    assert agg["hmean"] == pytest.approx(
        len(data) / np.sum(1.0 / data), rel=1e-3)


def test_merge_of_shards_matches_single():
    """32 local shards merged into a global digest ~= one digest fed
    everything (BASELINE config 4: forwardrpc merge of 32 shards)."""
    rng = np.random.default_rng(11)
    data = rng.normal(0, 1, 64_000).astype(np.float32)
    shards = np.array_split(data, 32)

    # Global bank receives each shard's centroids via merge_centroids.
    comp = 100.0
    glob = tdigest.init(2, compression=comp)
    for sh in shards:
        local = tdigest.init(1, compression=comp)
        local = tdigest.add_batch(
            local, np.zeros(len(sh), np.int32), sh,
            np.ones(len(sh), np.float32), compression=comp)
        local = tdigest.compress(local, compression=comp)
        means = np.asarray(local.mean[0])
        wts = np.asarray(local.weight[0])
        slots = np.zeros(len(means), np.int32)
        glob = tdigest.merge_centroids(glob, slots, means, wts)
        glob = tdigest.merge_scalars(
            glob, np.array([0], np.int32),
            np.asarray(local.vmin[:1]), np.asarray(local.vmax[:1]),
            np.asarray(local.vsum[:1]), np.asarray(local.count[:1]),
            np.asarray(local.recip[:1]))
        glob = tdigest.compress(glob, compression=comp)

    got = np.asarray(tdigest.quantile(glob, QS))[0]
    exact = np.quantile(data, QS)
    spread = exact.max() - exact.min()
    np.testing.assert_allclose(got, exact, atol=0.015 * spread)
    agg = {k: np.asarray(v)[0] for k, v in tdigest.aggregates(glob).items()}
    assert agg["count"] == pytest.approx(len(data))
    assert agg["min"] == pytest.approx(data.min())
    assert agg["max"] == pytest.approx(data.max())


def test_buffer_overflow_single_hot_slot():
    """A batch far larger than the buffer must be fully absorbed
    (worker channel backpressure has no analogue here — no sample loss)."""
    rng = np.random.default_rng(5)
    data = rng.uniform(0, 1, 5_000).astype(np.float32)
    bank = tdigest.init(2, buf_size=64)
    bank = tdigest.add_batch(
        bank, np.zeros(len(data), np.int32), data,
        np.ones(len(data), np.float32))
    bank = tdigest.compress(bank, compression=100.0)
    assert np.asarray(bank.count)[0] == pytest.approx(len(data))
    got = np.asarray(tdigest.quantile(bank, QS))[0]
    np.testing.assert_allclose(got, np.quantile(data, QS), atol=0.02)


def test_many_slots_and_padding():
    rng = np.random.default_rng(9)
    k = 64
    per = 500
    slots = np.repeat(np.arange(k, dtype=np.int32), per)
    values = (slots.astype(np.float32) * 10.0
              + rng.uniform(0, 1, k * per).astype(np.float32))
    # interleave padding
    pad = np.full(1000, -1, np.int32)
    slots = np.concatenate([slots, pad])
    values = np.concatenate([values, np.full(1000, 1e9, np.float32)])
    perm = rng.permutation(len(slots))
    slots, values = slots[perm], values[perm]

    bank = tdigest.init(k)
    bank = tdigest.add_batch(bank, slots, values,
                             np.ones(len(slots), np.float32))
    bank = tdigest.compress(bank, compression=100.0)
    med = np.asarray(tdigest.quantile(bank, np.array([0.5], np.float32)))
    cnt = np.asarray(bank.count)
    assert np.all(cnt == per)
    for i in range(k):
        assert abs(med[i, 0] - (i * 10.0 + 0.5)) < 0.1


def test_empty_bank():
    bank = tdigest.init(3)
    bank = tdigest.compress(bank, compression=100.0)
    out = np.asarray(tdigest.quantile(bank, QS))
    assert out.shape == (3, len(QS))
    assert np.all(out == 0.0)
    agg = tdigest.aggregates(bank)
    assert np.all(np.asarray(agg["count"]) == 0.0)
    assert np.all(np.asarray(agg["min"]) == 0.0)


# ---- the ingest's overflow compress: the rows that overflowed, not
# the bank (ISSUE 27). The work-set sizes are handed in as the static
# `overflow_rows`, so the row arm runs at sizes the CPU likes. ---------

_COUNTED = jax.jit(tdigest._add_batch_counted,
                   static_argnames=("compression", "overflow_rows"))
_COMPRESS = jax.jit(tdigest._compress_impl,
                    static_argnames=("compression",))
_OV_K, _OV_B = 64, 16
_BUFFERED = ("mean", "weight", "buf_value", "buf_weight", "buf_n")


def _ov_batch(rng, per_slot: dict, pad: int = 0):
    slots = np.concatenate(
        [np.full(n, s, np.int32) for s, n in per_slot.items()]
        + [np.full(pad, -1, np.int32)])
    rng.shuffle(slots)
    vals = rng.lognormal(np.log(100.0), 0.1, slots.size).astype(np.float32)
    return slots, vals, np.ones(slots.size, np.float32)


def _ov_prestate(rng):
    """A bank whose rows hold centroids AND part-filled buffers."""
    bank = tdigest.init(_OV_K, buf_size=_OV_B)
    first = {s: int(n) for s, n in enumerate(
        rng.integers(0, 3 * _OV_B, _OV_K))}
    bank, _ = _COUNTED(bank, *_ov_batch(rng, first), overflow_rows=())
    return bank


def _ov_reference(bank, slots, vals, wts):
    """The loop in numpy: write what fits, compress ONLY the rows with
    samples still waiting — each alone, through _compress_impl on a
    one-row bank — and go round. Returns the buffered leaves and the
    set of rows compressed."""
    leaves = {f: np.array(getattr(bank, f)) for f in _BUFFERED}
    order = np.argsort(np.where(slots < 0, 2 ** 30, slots), kind="stable")
    waiting = {}
    for i in order:
        if slots[i] >= 0:
            waiting.setdefault(int(slots[i]), []).append(i)
    compressed = set()
    while True:
        for s, idx in waiting.items():
            n = int(leaves["buf_n"][s])
            fit, waiting[s] = idx[:_OV_B - n], idx[_OV_B - n:]
            leaves["buf_value"][s, n:n + len(fit)] = vals[fit]
            leaves["buf_weight"][s, n:n + len(fit)] = wts[fit]
            leaves["buf_n"][s] = n + len(fit)
        waiting = {s: idx for s, idx in waiting.items() if idx}
        if not waiting:
            return leaves, compressed
        for s in waiting:
            one = jax.tree.map(lambda a: a[s:s + 1], bank)._replace(
                **{f: leaves[f][s:s + 1] for f in _BUFFERED})
            one = _COMPRESS(one, compression=100.0)
            for f in _BUFFERED:
                leaves[f][s] = np.asarray(getattr(one, f))[0]
            compressed.add(s)


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("case", [
    "compressed_rows_are_compress_of_the_row_alone",
    "other_rows_are_the_plain_write",
    "more_rows_than_the_work_set_take_the_whole_bank",
    "two_work_sets_pick_the_smallest_that_holds",
    "three_buffer_depths_in_one_batch",
    "padding_rows",
    "all_padding_batch",
    "ids_past_the_bank_end_the_loop",
])
def test_overflow_compresses_the_rows_that_overflowed(case):
    rng = np.random.default_rng(27)
    pre = _ov_prestate(rng)
    room = _OV_B - np.asarray(pre.buf_n)
    # rows 3, 7 and 40 overfill by a few samples, 11 exactly fills,
    # the rest of the batch fits
    over = {3: int(room[3]) + 5, 7: int(room[7]) + 1,
            40: int(room[40]) + _OV_B, 11: int(room[11])}
    fits = {s: int(room[s]) // 2 for s in (0, 5, 12, 33, 63)}
    slots, vals, wts = _ov_batch(rng, {**over, **fits})

    if case in ("compressed_rows_are_compress_of_the_row_alone",
                "other_rows_are_the_plain_write"):
        got, counted = _COUNTED(pre, slots, vals, wts, overflow_rows=(4,))
        ref, compressed = _ov_reference(pre, slots, vals, wts)
        assert compressed == {3, 7, 40}
        assert [int(c) for c in counted] == [3, 0]
        rows = sorted(compressed) \
            if case.startswith("compressed") \
            else sorted(set(range(_OV_K)) - compressed)
        for f in _BUFFERED:
            assert _same_bits(np.asarray(getattr(got, f))[rows],
                              ref[f][rows]), f
        if case.startswith("other"):
            # nobody's centroids moved but the compressed rows'
            for f in ("mean", "weight"):
                assert _same_bits(np.asarray(getattr(got, f))[rows],
                                  np.asarray(getattr(pre, f))[rows]), f
            assert int(np.asarray(got.buf_n)[11]) == _OV_B
        # exact fields are computed before the loop: the whole-bank
        # arm's, bit for bit
        whole, _ = _COUNTED(pre, slots, vals, wts, overflow_rows=())
        for f in set(tdigest.TDigestBank._fields) - set(_BUFFERED):
            assert _same_bits(getattr(got, f), getattr(whole, f)), f
    elif case == "more_rows_than_the_work_set_take_the_whole_bank":
        got, counted = _COUNTED(pre, slots, vals, wts, overflow_rows=(2,))
        whole, counted_whole = _COUNTED(pre, slots, vals, wts,
                                        overflow_rows=())
        # three rows wait after the first pass: over the set of two
        assert [int(c) for c in counted] == [0, 1]
        assert [int(c) for c in counted_whole] == [0, 1]
        for f in tdigest.TDigestBank._fields:
            assert _same_bits(getattr(got, f), getattr(whole, f)), f
        # which is what every overflow did before this change: a row
        # the batch never touched had its buffer merged too
        cold = next(r for r in range(_OV_K) if r not in {**over, **fits}
                    and int(pre.buf_n[r]) > 0)
        assert int(whole.buf_n[cold]) == 0
        rows_only, _ = _COUNTED(pre, slots, vals, wts, overflow_rows=(4,))
        assert int(rows_only.buf_n[cold]) == int(pre.buf_n[cold])
    elif case == "two_work_sets_pick_the_smallest_that_holds":
        # 3 rows wait: past the set of 2, inside the set of 8; a bank
        # no larger than a set never takes it (static shape)
        got, counted = _COUNTED(pre, slots, vals, wts,
                                overflow_rows=(2, 8))
        one, _ = _COUNTED(pre, slots, vals, wts, overflow_rows=(8,))
        assert [int(c) for c in counted] == [3, 0]
        for f in tdigest.TDigestBank._fields:
            assert _same_bits(getattr(got, f), getattr(one, f)), f
        _, counted = _COUNTED(pre, slots, vals, wts,
                              overflow_rows=(_OV_K,))
        assert [int(c) for c in counted] == [0, 1]
    elif case == "three_buffer_depths_in_one_batch":
        n = int(room[9]) + 3 * _OV_B + 5
        slots, vals, wts = _ov_batch(rng, {9: n, **fits})
        got, counted = _COUNTED(pre, slots, vals, wts, overflow_rows=(4,))
        assert [int(c) for c in counted] == [4, 0]
        mine = vals[slots == 9].astype(np.float64)
        before = {f: float(np.asarray(getattr(pre, f))[9])
                  for f in ("count", "vsum", "vmin", "vmax")}
        assert float(got.count[9]) == before["count"] + n
        assert float(got.vsum[9]) + float(got.vsum_lo[9]) == \
            pytest.approx(before["vsum"] + mine.sum(), rel=1e-6)
        assert float(got.vmin[9]) == min(before["vmin"], mine.min())
        assert float(got.vmax[9]) == max(before["vmax"], mine.max())
        held = float(np.asarray(got.weight)[9].sum()
                     + np.asarray(got.buf_weight)[9].sum())
        assert held == float(got.count[9])
        assert int(got.buf_n[9]) == 5
    elif case == "padding_rows":
        got, counted = _COUNTED(pre, slots, vals, wts, overflow_rows=(4,))
        at = np.sort(rng.choice(slots.size + 40, slots.size,
                                replace=False))
        ps = np.full(slots.size + 40, -1, np.int32)
        pv = np.full(slots.size + 40, 1e9, np.float32)
        pw = np.ones(slots.size + 40, np.float32)
        ps[at], pv[at], pw[at] = slots, vals, wts
        padded, counted_p = _COUNTED(pre, ps, pv, pw, overflow_rows=(4,))
        assert [int(c) for c in counted_p] == [int(c) for c in counted]
        for f in tdigest.TDigestBank._fields:
            assert _same_bits(getattr(got, f), getattr(padded, f)), f
    elif case == "all_padding_batch":
        got, counted = _COUNTED(pre, np.full(32, -1, np.int32),
                                np.full(32, 5.0, np.float32),
                                np.ones(32, np.float32),
                                overflow_rows=(4,))
        assert [int(c) for c in counted] == [0, 0]
        for f in tdigest.TDigestBank._fields:
            assert _same_bits(getattr(got, f), getattr(pre, f)), f
    else:
        # an id past the bank lands nowhere and waits on nothing, so
        # the loop ends however many of them a batch brings
        stray = np.full(3 * _OV_B, _OV_K + 3, np.int32)
        got, counted = _COUNTED(
            pre, np.concatenate([slots, stray]),
            np.concatenate([vals, np.ones(stray.size, np.float32)]),
            np.ones(slots.size + stray.size, np.float32),
            overflow_rows=(4,))
        clean, _ = _COUNTED(pre, slots, vals, wts, overflow_rows=(4,))
        assert [int(c) for c in counted] == [3, 0]
        for f in tdigest.TDigestBank._fields:
            assert _same_bits(getattr(got, f), getattr(clean, f)), f


@pytest.mark.parametrize("overflow_rows", [(8,), ()],
                         ids=["row_arm", "whole_bank_arm"])
def test_hot_keys_at_the_cells_shape_stay_in_the_rank_contract(
        overflow_rows):
    """The benchmark's own shape: 2,000 samples a hot key landing about
    30 a batch among cold keys of 4 samples, buffer 256, compression
    100, lognormal sigma 0.1 — p50 within 1%, p99 within 2% of
    numpy.quantile (chip_smoke.py derives both from the digest's rank
    contract), count/min/max exact."""
    rng = np.random.default_rng(2027)
    K, hot, cold = 256, 10, 100
    keys = rng.permutation(K)[:hot + cold].astype(np.int32)
    slots = np.concatenate([np.repeat(keys[:hot], 2000),
                            np.repeat(keys[hot:], 4)])
    rng.shuffle(slots)
    vals = rng.lognormal(np.log(100.0), 0.1, slots.size).astype(np.float32)
    bank = tdigest.init(K)
    rows = bank_passes = 0
    for s, v in zip(np.array_split(slots, 67), np.array_split(vals, 67)):
        bank, counted = _COUNTED(bank, s, v, np.ones(s.size, np.float32),
                                 overflow_rows=overflow_rows)
        rows += int(counted[0])
        bank_passes += int(counted[1])
    if overflow_rows:
        assert (rows, bank_passes) == (hot * 7, 0)   # 2000 // 256 fills
    else:
        assert rows == 0 and bank_passes > 7
    bank = _COMPRESS(bank, compression=100.0)
    q = np.asarray(tdigest.quantile(bank, np.array([0.5, 0.99],
                                                   np.float32)))
    for k in keys[:hot]:
        mine = vals[slots == k]
        want = np.quantile(mine.astype(np.float64), [0.5, 0.99])
        assert float(bank.count[k]) == 2000.0
        assert float(bank.vmin[k]) == mine.min()
        assert float(bank.vmax[k]) == mine.max()
        assert abs(q[k, 0] - want[0]) / want[0] < 0.01
        assert abs(q[k, 1] - want[1]) / want[1] < 0.02


def test_full_sort_env_is_inert(monkeypatch):
    """VENEUR_TPU_TDIGEST_FULL_SORT was the switch to the retired
    full-row-sort arm: set, it changes no program and warns of
    nothing."""
    import warnings

    bank = jax.eval_shape(lambda: tdigest.init(64))
    unset = jax.jit(tdigest._compress_impl,
                    static_argnames=("compression",)).lower(
        bank, compression=100.0).as_text()
    monkeypatch.setenv("VENEUR_TPU_TDIGEST_FULL_SORT", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        set_ = jax.jit(tdigest._compress_impl,
                       static_argnames=("compression",)).lower(
            bank, compression=100.0).as_text()
    assert set_ == unset


@pytest.mark.parametrize("n", [25, 45, 90, 250])
def test_quantiles_stay_inside_min_and_max_for_a_few_dozen_samples(n):
    """A cluster's mean is a difference of f32 running sums over its
    row, so a singleton at the top of a row of some dozens of samples
    (a sum in the thousands) lands a few 1e-6 of its value beside the
    sample it holds: above the row's exact max as often as below. The
    quantile is clamped into [min, max]; before PR 43 a p99 of such a
    key came out above its max (`worst_pct_outside_rel` 3e-6 against
    a limit of 1e-6 in `ssf_two_tier_1chip.spans_10k`, whose indicator
    keys are the first of that size in any cell)."""
    rng = np.random.default_rng(n)
    worst = 0.0
    for _ in range(20):
        values = rng.lognormal(np.log(100.0), 0.1, n).astype(np.float32)
        bank, _qs = _bank_quantiles(values)
        q = np.asarray(tdigest.quantile(
            bank, np.array([0.0, 0.01, 0.5, 0.75, 0.99, 1.0], np.float32)))[1]
        lo, hi = float(values.min()), float(values.max())
        assert lo <= q.min() and q.max() <= hi, (q, lo, hi)
        assert np.all(np.diff(q) >= 0)
        worst = max(worst, float(np.max(np.asarray(bank.mean)[1]) - hi))
    # the means themselves do stray: the clamp is what holds the answer
    assert worst > 0 or n < 45

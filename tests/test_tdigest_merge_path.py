"""Exact-equivalence gate for the merge-path compress (ISSUE 3).

The sorted-run merge compress must reproduce the legacy full-row
comparator sort BIT-FOR-BIT — value order is load-bearing for the ±1%
accuracy contract, so the rewrite is only safe if the outputs are
indistinguishable, not merely close. Every test here compares the
serving compress with a full-row-sort reference built here from
`_cluster_core(..., sorted_prefix=0)` through the f32 bit
patterns (NaN-safe, sign-of-zero-exact), on adversarial banks:
duplicate values, ±0.0 mixes, empty rows, inf-padded empties, rows
mid-overflow-loop. Oracle parity for the new path rides in
tests/test_tdigest.py, whose whole suite runs through the merge arm by
default.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veneur_tpu.ops import tdigest


def bits_eq(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == np.float32:
        return np.array_equal(a.view(np.uint32), b.view(np.uint32))
    return np.array_equal(a, b)


def assert_banks_identical(old, new):
    for field in tdigest.TDigestBank._fields:
        assert bits_eq(getattr(old, field), getattr(new, field)), \
            f"bank field {field} diverged between sort arms"


@functools.partial(jax.jit, static_argnames="comp")
def _whole_row_cluster(bank, comp):
    """The reference: the concatenated centroid+buffer row through the
    full-row sort (no ordered-prefix assumption)."""
    vals = jnp.concatenate([bank.mean, bank.buf_value], axis=1)
    wts = jnp.concatenate([bank.weight, bank.buf_weight], axis=1)
    return tdigest._cluster_core(vals, wts, comp, bank.mean.shape[1],
                                 sorted_prefix=0)


def whole_row_compress(bank, comp):
    mean, weight = _whole_row_cluster(bank, comp)
    return bank._replace(
        mean=mean, weight=weight,
        buf_value=jnp.zeros_like(bank.buf_value),
        buf_weight=jnp.zeros_like(bank.buf_weight),
        buf_n=jnp.zeros_like(bank.buf_n))


def compress_both(bank, comp):
    old = whole_row_compress(bank, comp)
    new = jax.jit(lambda b: tdigest._compress_impl(b, comp))(bank)
    return old, new


def adversarial_bank(comp=10.0, buf_size=32, seed=0):
    rng = np.random.default_rng(seed)
    bank = tdigest.init(8, compression=comp, buf_size=buf_size)
    B = buf_size
    bv = np.zeros((8, B), np.float32)
    bw = np.zeros((8, B), np.float32)
    # signed zeros + duplicates + inf, distinct weights so any
    # tie-order divergence shows up in the outputs
    bv[0, :6] = [-0.0, 0.0, 5.0, 5.0, -0.0, np.inf]
    bw[0, :6] = [1, 2, 3, 4, 5, 6]
    bv[1, :] = rng.normal(0, 1, B)
    bw[1, :] = 1
    bv[2, :4] = [7, 7, 7, 7]           # pure duplicates
    bw[2, :4] = [1, 2, 3, 4]
    # row 3 stays empty (inf-padded empties path)
    bv[4, 0] = 3.25                    # singleton
    bw[4, 0] = 1
    bv[5, :] = np.repeat(rng.normal(0, 1, 4), B // 4)  # duplicate blocks
    bw[5, :] = rng.integers(0, 2, B)   # interleaved zero-weight padding
    bv[6, :] = -np.abs(rng.normal(0, 100, B))
    bw[6, :] = 1
    # +inf is in contract (it sorts last, so the cumsum-diff cluster
    # sums stay finite-or-inf); -inf and NaN are NOT — a leading -inf
    # turns every later cluster diff into inf-inf=NaN even in the
    # legacy full-sort path, and NaN ordering is comparator-undefined
    bv[7, :] = rng.choice(
        np.array([0.0, -0.0, 1.5, -1.5, np.inf], np.float32), B)
    bw[7, :] = rng.integers(0, 3, B)
    return bank._replace(
        buf_value=jnp.asarray(bv), buf_weight=jnp.asarray(bw),
        buf_n=jnp.asarray((bw > 0).sum(1).astype(np.int32))), bv, bw


def test_compress_arms_bitwise_identical_adversarial():
    comp = 10.0
    bank, bv, bw = adversarial_bank(comp)
    # three rounds: round 0 merges against an all-empty prefix, later
    # rounds against a warm (cluster-ordered) prefix — the case the
    # sorted-prefix invariant actually protects
    for _ in range(3):
        old, new = compress_both(bank, comp)
        assert_banks_identical(old, new)
        bank = old._replace(
            buf_value=jnp.asarray(bv), buf_weight=jnp.asarray(bw),
            buf_n=jnp.asarray((bw > 0).sum(1).astype(np.int32)))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_compress_arms_bitwise_identical_randomized(seed):
    rng = np.random.default_rng(seed)
    K, B, comp = 64, 64, 100.0
    bank = tdigest.init(K, compression=comp, buf_size=B)
    # quantized values force heavy cross-run duplication; random
    # weights make tie order observable
    bv = np.round(rng.gamma(2.0, 20.0, (K, B)) * 4) / 4
    bw = rng.integers(0, 4, (K, B)).astype(np.float32)
    for _ in range(3):
        bank = bank._replace(
            buf_value=jnp.asarray(bv.astype(np.float32)),
            buf_weight=jnp.asarray(bw),
            buf_n=jnp.asarray((bw > 0).sum(1).astype(np.int32)))
        old, new = compress_both(bank, comp)
        assert_banks_identical(old, new)
        bank = new
        bv = np.round(rng.gamma(2.0, 20.0, (K, B)) * 4) / 4
        bw = rng.integers(0, 4, (K, B)).astype(np.float32)


def warm_bank(seed, K=37, comp=100.0, B=256, adversarial=False):
    """A bank at the serving widths with a LEGAL cluster-ordered prefix
    (built by the compress itself) under a refilled buffer. The
    adversarial one: ±0.0 keys, duplicates, a buffer value equal to a
    prefix mean, a zero-weight tail, an empty buffer over a live
    prefix, fresh rows, and in row 0 a NaN with a payload."""
    rng = np.random.default_rng(seed)
    bank = tdigest.init(K, comp, B)
    slots = rng.integers(0, K, 4096).astype(np.int32)
    vals = rng.lognormal(3, 1, 4096).astype(np.float32)
    bank = tdigest._add_batch_impl(
        bank, jnp.asarray(slots), jnp.asarray(vals),
        jnp.ones(4096, jnp.float32), comp)
    bank = tdigest._compress_impl(bank, comp)
    bv = rng.normal(20, 30, (K, B)).astype(np.float32)
    bw = (np.abs(rng.normal(1, 0.5, (K, B))) + 0.01).astype(np.float32)
    if adversarial:
        bv[:, 0] = -0.0
        bv[:, 1] = 0.0
        bv[:, 2] = bv[:, 3]
        bv[:, 5] = np.asarray(bank.mean)[:, 0]
        bv[0, 4] = np.uint32(NAN_PAYLOAD).view(np.float32)
        bw[2, 100:] = 0.0
        bw[3, :] = 0.0
        bw[np.asarray(bank.weight).sum(axis=1) == 0] = 0.0
    return bank._replace(buf_value=jnp.asarray(bv),
                         buf_weight=jnp.asarray(bw),
                         buf_n=jnp.full((K,), B, jnp.int32))


NAN_PAYLOAD = 0x7FC01234


def overflow_clip_bank():
    """More natural clusters than centroid lanes (C = 64 under
    compression 100): the greedy cluster ids run past C and are
    clipped to C - 1, so the last lane absorbs the tail."""
    rng = np.random.default_rng(9)
    K, C, B = 5, 64, 512
    bv = np.sort(rng.normal(0, 100, (K, B))).astype(np.float32)
    bank = tdigest.init(K, compression=100.0, buf_size=B)
    zc = jnp.zeros((K, C), jnp.float32)
    return bank._replace(
        mean=zc, weight=zc, buf_value=jnp.asarray(bv),
        buf_weight=jnp.ones((K, B), jnp.float32),
        buf_n=jnp.full((K,), B, jnp.int32))


@pytest.mark.parametrize("case", ["adversarial", "warm1", "warm2",
                                  "overflow_clip"])
def test_compress_arms_bitwise_identical_warm_prefix(case):
    """The serving compress against the full-row sort where the prefix
    is warm at the serving widths, and where the cluster ids clip."""
    comp = 100.0
    bank = (overflow_clip_bank() if case == "overflow_clip" else
            warm_bank({"adversarial": 0, "warm1": 1, "warm2": 2}[case],
                      adversarial=case == "adversarial"))
    old, new = compress_both(bank, comp)
    # the full-row reference cannot take a NaN key (its place in a
    # comparator sort is undefined, see adversarial_bank): row 0 of the
    # adversarial bank is held to the compress's own invariants below
    rows = slice(1, None) if case == "adversarial" else slice(None)
    assert_banks_identical(
        jax.tree_util.tree_map(lambda x: x[rows], old),
        jax.tree_util.tree_map(lambda x: x[rows], new))
    if case == "overflow_clip":
        assert float(np.asarray(new.weight)[:, -1].min()) > 1.0
    if case == "adversarial":
        mean, weight = np.asarray(new.mean)[0], np.asarray(new.weight)[0]
        total = (np.asarray(bank.weight)[0].sum(dtype=np.float64)
                 + np.asarray(bank.buf_weight)[0].sum(dtype=np.float64))
        assert weight.sum(dtype=np.float64) == pytest.approx(total,
                                                             rel=1e-6)
        # the NaN leaves as a centroid of its own, payload bits and
        # weight kept; the other live means are cluster-ordered
        nan = np.isnan(mean)
        assert mean[nan].view(np.uint32).tolist() == [NAN_PAYLOAD]
        # (a cluster's weight is a difference of running sums)
        assert weight[nan] == pytest.approx(
            np.asarray(bank.buf_weight)[0, 4], rel=1e-4)
        assert np.all(np.diff(mean[(weight > 0) & ~nan]) >= 0)


def test_add_batch_overflow_loop_arms_identical():
    """Rows mid-overflow-loop: a batch far larger than the buffer runs
    compress inside the while_loop body — it must land what the
    full-row-sort reference lands when applied buffer by buffer (write
    a buffer's worth, compress while more waits, go round)."""
    rng = np.random.default_rng(7)
    n, B, comp = 3000, 64, 50.0
    slots = np.zeros(n, np.int32)
    vals = np.round(rng.gamma(2.0, 20.0, n) * 2).astype(np.float32) / 2
    wts = rng.integers(1, 3, n).astype(np.float32)
    got = tdigest.add_batch(tdigest.init(2, compression=comp, buf_size=B),
                            slots, vals, wts, compression=comp)
    ref = tdigest.init(2, compression=comp, buf_size=B)
    for at in range(0, n, B):
        if at:
            ref = whole_row_compress(ref, comp)
        m = min(B, n - at)
        ref = ref._replace(
            buf_value=ref.buf_value.at[0, :m].set(vals[at:at + m]),
            buf_weight=ref.buf_weight.at[0, :m].set(wts[at:at + m]),
            buf_n=ref.buf_n.at[0].set(m))
    for field in ("mean", "weight", "buf_value", "buf_weight", "buf_n"):
        assert bits_eq(getattr(ref, field), getattr(got, field)), \
            f"bank field {field} diverged from the full-sort reference"


def test_cluster_rows_sorted_prefix_arm_identical():
    """cluster_rows' sorted_prefix fast arm (the importsrv re-merge)
    must match the full sort when the prefix really is ordered."""
    rng = np.random.default_rng(11)
    S, C = 16, 128
    # prefix: a genuine cluster_rows output (cluster-ordered rows)
    raw_v = rng.gamma(2.0, 20.0, (S, 256)).astype(np.float32)
    raw_w = np.ones((S, 256), np.float32)
    pm, pw = tdigest.cluster_rows(raw_v, raw_w, compression=20.0,
                                  num_centroids=C)
    tail_v = rng.gamma(2.0, 20.0, (S, C)).astype(np.float32)
    tail_w = rng.integers(0, 2, (S, C)).astype(np.float32)
    vals = np.concatenate([np.asarray(pm), tail_v], axis=1)
    wts = np.concatenate([np.asarray(pw), tail_w], axis=1)
    full = tdigest.cluster_rows(vals, wts, compression=20.0,
                                num_centroids=C)
    fast = tdigest.cluster_rows(vals, wts, compression=20.0,
                                num_centroids=C, sorted_prefix=C)
    assert bits_eq(full[0], fast[0])
    assert bits_eq(full[1], fast[1])


def test_compress_output_prefix_is_cluster_ordered():
    """The invariant the merge path depends on: positive-weight means
    non-decreasing per row, zero-weight empties as a suffix — enforced
    exactly (cummax clamp) even against f32 rounding of the cluster
    division."""
    rng = np.random.default_rng(5)
    K, B = 128, 128
    bank = tdigest.init(K, compression=100.0, buf_size=B)
    for _ in range(2):
        bank = bank._replace(
            buf_value=jnp.asarray(
                rng.gamma(2.0, 20.0, (K, B)).astype(np.float32)),
            buf_weight=jnp.ones((K, B), jnp.float32),
            buf_n=jnp.full((K,), B, jnp.int32))
        bank = tdigest.compress(bank, compression=100.0)
    mean = np.asarray(bank.mean)
    weight = np.asarray(bank.weight)
    for r in range(K):
        n = int((weight[r] > 0).sum())
        assert np.all(weight[r, n:] == 0), "empties must be a suffix"
        assert np.all(np.diff(mean[r, :n]) >= 0), \
            "positive-weight means must be non-decreasing"


@pytest.mark.parametrize("K,M,C,rate", [(23, 512, 256, 0.3),
                                        (5, 576, 64, 0.9)],
                         ids=["random", "overflow_clip"])
def test_tpu_forms_equal_the_gather_forms(K, M, C, rate):
    """The compress picks, per platform, between forms that must give
    ONE result: a binary search or a count for the cluster end
    positions, a gather or a select-and-sum to read the cumulative
    rows there, the merge path or the plain row sort. The TPU forms run
    here on the CPU, next to the forms the CPU serves. `overflow_clip`
    is overflow_clip_bank()'s shape: ids that run past C and park on
    C - 1 from a different lane in every row, and that bank's own row
    (an empty prefix under a sorted buffer) through both sorts."""
    rng = np.random.default_rng(11)
    steps = (rng.random((K, M)) < rate).astype(np.int32)
    cluster = np.clip(np.cumsum(steps, axis=1) - 1, 0, C - 1)
    cluster[3] = 0                      # one cluster takes the row
    cluster[4] = C - 1                  # everything parked on the last
    if rate > 0.5:
        assert (cluster[:3, M // 2:] == C - 1).all()    # it clipped
    cluster = jnp.asarray(cluster.astype(np.int32))
    ends_s = jax.jit(tdigest._ends_by_search, static_argnums=1)(cluster, C)
    ends_c = jax.jit(tdigest._ends_by_count, static_argnums=1)(cluster, C)
    np.testing.assert_array_equal(np.asarray(ends_s), np.asarray(ends_c))

    cum = np.cumsum(rng.lognormal(0, 2, (K, M)), axis=1).astype(np.float32)
    cum[2, 100:] = np.inf               # a real +inf rides through
    padded = jnp.asarray(np.concatenate(
        [np.zeros((K, 1), np.float32), cum], axis=1))
    got_g = jax.jit(tdigest._lanes_by_gather)(padded, ends_s)
    got_s = jax.jit(tdigest._lanes_by_select)(padded, ends_s)
    np.testing.assert_array_equal(
        np.asarray(got_g).view(np.uint32), np.asarray(got_s).view(np.uint32))

    if rate > 0.5:
        bank = overflow_clip_bank()
        S = bank.mean.shape[1]
        wts = np.concatenate([bank.weight, bank.buf_weight], axis=1)
        vals = np.where(wts > 0, np.concatenate(
            [bank.mean, bank.buf_value], axis=1), np.inf)
        assert vals.shape == (K, M)
    else:
        S = M // 2
        vals = rng.normal(0, 50, (K, M)).astype(np.float32)
        vals[1, 5], vals[1, M - 1] = 0.0, -0.0             # signed zeros
        vals[:, :S] = np.sort(vals[:, :S], axis=1)         # ordered prefix
        vals[0, S] = vals[0, 3]                            # a tie
        wts = np.ones((K, M), np.float32)
    a = jax.jit(tdigest._row_sort)(jnp.asarray(vals), jnp.asarray(wts))
    b = jax.jit(tdigest._merge_path_sort, static_argnames="S")(
        jnp.asarray(vals), jnp.asarray(wts), S=S)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x).view(np.uint32),
                                      np.asarray(y).view(np.uint32))

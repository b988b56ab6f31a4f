"""Batched merging t-digest bank — the TPU-native heart of the framework.

The reference keeps one `tdigest.MergingDigest` per distinct histogram/timer
key inside a Go map (tdigest/merging_digest.go sym: MergingDigest.Add /
.mergeAllTemps / .Merge / .Quantile; used by samplers/samplers.go sym:
Histo.Sample / Histo.Combine). Sample adds append to a temp buffer; when the
buffer fills, the centroids+buffer are sorted and greedily re-clustered under
the k1 scale function k(q) = delta * (asin(2q-1) + pi/2) / pi.

This module re-designs that as a *bank*: K digests live in fixed-shape device
arrays and every operation is batched over K, so "compress every digest" is
ONE sorted-run merge + scan over a [K, C+B] array — the shape XLA tiles well
on TPU — instead of 100k independent pointer-chasing loops.

Sort -> merge redesign (the compress hot path): a compress used to row-sort
the full [K, C+B] concatenation of centroids+buffer. But the centroid prefix
[K, :C] is ALREADY cluster-ordered — every _cluster_core output has its
positive-weight means non-decreasing with the zero-weight empties as a
suffix — so only the buffer [K, B] needs sorting (a stable packed-key radix
sort, _stable_sort_perm); the two sorted runs are then combined with an
exact, quantization-free rank-merge — a log-depth bitonic merge network
with lexicographic (canonical key, concatenation-order tag) exchanges
(_merge_sorted_runs) — reproducing the old full stable sort bit-for-bit,
including ±0.0 and duplicate values (lax.sort canonicalizes -0.0 to +0.0
before comparing; the canonical u32 key embeds the same order). This
mirrors the reference's mergeAllTemps, which likewise sorts only the temp
buffer against the already-ordered centroid list.

ORDERING INVARIANT (load-bearing): `mean`/`weight` rows must stay exactly
as _cluster_core emits them — positive-weight means non-decreasing, then
zero-weight empties. quantile() always relied on this to skip a defensive
re-sort; the merge-path compress now relies on it for CORRECTNESS, not just
speed. Only this module may write those fields (vlint SR02 enforces it);
writes elsewhere need a documented suppression proving the order survives.
WHICH FORM RUNS IS THE PLATFORM'S BUSINESS (_sort_rows, _cluster_ends,
_lanes_at; jax.lax.platform_dependent, one result either way): the
merge path trades comparator-sort work for row gathers, the right trade
on XLA-CPU and the wrong one on the TPU, where a [131072, 512] row sort
takes 22 ms and ONE row gather of that size 1.9 s. As first brought up
on the v5e a full-bank compress took 16.6 s (the flush interval is
10 s); with the row sort, a counted binary search and select-and-sum
reads it takes 112 ms (my chip runs, PR 23).

State layout (per bank):
  mean, weight : f32[K, C]   merged centroids (weight 0 == empty slot)
  buf_value, buf_weight : f32[K, B]  unmerged sample buffer
  buf_n  : i32[K]            fill level of each buffer row
  vmin, vmax : f32[K]        exact extremes (+inf / -inf when empty)
  vsum, count, recip : f32[K]  sample-rate-weighted sum / count / sum(w/v)
                               (recip backs the `hmean` aggregate)
  vsum_lo, count_lo, recip_lo : f32[K]  2Sum compensation terms: a hot
                               timer at north-star rates pushes >2^24
                               samples through one slot per interval,
                               saturating plain f32; each batch folds its
                               dense delta into the (hi, lo) pair with an
                               error-free transformation, exactly like the
                               counter bank (scalar.py). Exact totals are
                               float64(hi) + float64(lo) on host.

Semantics parity notes:
  * Sample weight = 1/sample_rate, matching Histo.Sample's weight handling.
  * Compression (delta) defaults to 100 like veneur's config default; the
    centroid axis C is padded to >= delta+2 lanes.
  * Clustering uses the same k1 scale function as the reference; the greedy
    sequential merge is re-expressed as a lax.scan over the sorted axis
    (carrying cluster-start k-values per bank row), which reproduces the
    greedy boundaries exactly, followed by a parallel segment-reduce.
  * Quantile() interpolates between centroid-mean positions at
    (cum - w/2) / W, clamped by exact min/max — the standard merging-digest
    interpolation; parity with the Go implementation is asserted
    distributionally (±1%) in tests, mirroring tdigest/merging_digest_test.go.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import scatter
from .scalar import _two_sum

_INF = jnp.inf

class TDigestBank(NamedTuple):
    mean: jax.Array        # f32[K, C]
    weight: jax.Array      # f32[K, C]
    buf_value: jax.Array   # f32[K, B]
    buf_weight: jax.Array  # f32[K, B]
    buf_n: jax.Array       # i32[K]
    vmin: jax.Array        # f32[K]
    vmax: jax.Array        # f32[K]
    vsum: jax.Array        # f32[K]
    count: jax.Array       # f32[K]
    recip: jax.Array       # f32[K]
    vsum_lo: jax.Array     # f32[K] 2Sum compensation for vsum
    count_lo: jax.Array    # f32[K] 2Sum compensation for count
    recip_lo: jax.Array    # f32[K] 2Sum compensation for recip

    @property
    def num_slots(self):
        return self.mean.shape[0]

    @property
    def num_centroids(self):
        return self.mean.shape[1]

    @property
    def buf_size(self):
        return self.buf_value.shape[1]


def init(num_slots: int, compression: float = 100.0, buf_size: int = 256,
         dtype=jnp.float32) -> TDigestBank:
    """Fresh bank of `num_slots` empty digests.

    The greedy k1 merge can produce up to ~2*compression clusters in the
    worst case (pairs of adjacent clusters each span >= 1 k-unit of the
    total `compression` k-range), so C is padded to a multiple of 128 lanes
    >= 2*compression + 8 to map onto TPU vector lanes with headroom.
    """
    c = int(math.ceil((2.0 * compression + 8) / 128.0) * 128)
    k = num_slots
    return TDigestBank(
        mean=jnp.zeros((k, c), dtype),
        weight=jnp.zeros((k, c), dtype),
        buf_value=jnp.zeros((k, buf_size), dtype),
        buf_weight=jnp.zeros((k, buf_size), dtype),
        buf_n=jnp.zeros((k,), jnp.int32),
        vmin=jnp.full((k,), _INF, dtype),
        vmax=jnp.full((k,), -_INF, dtype),
        vsum=jnp.zeros((k,), dtype),
        count=jnp.zeros((k,), dtype),
        recip=jnp.zeros((k,), dtype),
        vsum_lo=jnp.zeros((k,), dtype),
        count_lo=jnp.zeros((k,), dtype),
        recip_lo=jnp.zeros((k,), dtype),
    )


def _k1(q, compression):
    """The k1 scale function used by the reference merging digest
    (tdigest/merging_digest.go sym: integratedLocation-equivalent)."""
    q = jnp.clip(q, 0.0, 1.0)
    return compression * (jnp.arcsin(2.0 * q - 1.0) + jnp.pi / 2.0) / jnp.pi


def _compress_impl(bank: TDigestBank, compression: float) -> TDigestBank:
    """Merge every bank row's buffer into its centroid list.

    Equivalent of MergingDigest.mergeAllTemps, batched over K:
      1. concat centroids+buffer -> [K, M]; the centroid prefix is
         already cluster-ordered (the module invariant), so only the
         buffer half is row-sorted and the two runs are rank-merged —
         bit-identical to sorting the whole row at roughly half the
         comparator-sort work (empties sort to +inf with weight 0)
      2. greedy k1 clustering via lax.scan over the sorted axis: an element
         starts a new cluster when k1(q_right) - k1(q_cluster_start) > 1
      3. cluster ids are non-decreasing per row, so per-cluster weighted
         sums reduce to diffs of row cumsums at cluster boundaries
         (searchsorted per row) — no sequential per-digest loop remains.
    """
    K, C = bank.mean.shape

    vals = jnp.concatenate([bank.mean, bank.buf_value], axis=1)
    wts = jnp.concatenate([bank.weight, bank.buf_weight], axis=1)
    new_mean, w_c = _cluster_core(vals, wts, compression, C,
                                  sorted_prefix=C)

    return bank._replace(
        mean=new_mean,
        weight=w_c,
        buf_value=jnp.zeros_like(bank.buf_value),
        buf_weight=jnp.zeros_like(bank.buf_weight),
        buf_n=jnp.zeros_like(bank.buf_n),
    )


def _canonical_sort_key(x):
    """f32 -> u32 monotone key reproducing lax.sort's float comparator
    order EXACTLY: jax canonicalizes -0.0 -> +0.0 (and all NaNs to one
    standard NaN) before comparing with `lt`, so after the same zero
    canonicalization the usual sign-magnitude -> biased bit twiddle is
    a strict order-embedding of the comparator's equivalence classes.
    (NaN placement is outside the accuracy contract, as it always was
    for the full-row comparator sort.)"""
    x = jnp.where(x == 0.0, jnp.zeros((), x.dtype), x)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    neg = bits >= jnp.uint32(0x80000000)
    return jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))


def _stable_sort_perm(key):
    """Stable ascending row-sort of u32 keys, returning (sorted_key,
    perm) with perm the original lane of each sorted position — the
    exact permutation `lax.sort((key, lane))` would produce, computed
    ~3x cheaper on the CPU backend as two LSD-radix passes of
    SINGLE-operand u32 sorts over lossless packed (key-half ‖ 16-bit
    lane) words (multi-operand comparator sorts are the expensive form
    there). Pass 1 orders by the key's low half with the original lane
    as tiebreak; pass 2 by the high half with the pass-1 position as
    tiebreak — the classic stable-radix composition, so ties land in
    original-lane order. No quantization anywhere: the full 32-bit key
    is consumed across the two passes."""
    B = key.shape[1]
    if B > (1 << 16):
        raise ValueError(f"row width {B} exceeds the 16-bit lane pack")
    lane = jax.lax.broadcasted_iota(jnp.uint32, key.shape, 1)
    p1 = jax.lax.sort((key & jnp.uint32(0xFFFF)) << 16 | lane,
                      dimension=-1)
    i1 = (p1 & jnp.uint32(0xFFFF)).astype(jnp.int32)   # original lanes
    hi1 = jnp.take_along_axis(key >> 16, i1, axis=1)   # hi half, p1 order
    p2 = jax.lax.sort(hi1 << 16 | lane, dimension=-1)
    r2 = (p2 & jnp.uint32(0xFFFF)).astype(jnp.int32)   # pass-1 positions
    perm = jnp.take_along_axis(i1, r2, axis=1)
    sorted_key = (p2 & ~jnp.uint32(0xFFFF)) \
        | jnp.take_along_axis(p1 >> 16, r2, axis=1)
    return sorted_key, perm


def _merge_sorted_runs(akey, bkey, S: int, M: int):
    """Exact rank-merge of two row-sorted u32 key runs — akey [K, S]
    (the cluster-ordered centroid prefix) and bkey [K, M-S] (the
    freshly sorted buffer) — returning the merged CONCATENATION-ORDER
    TAGS [K, M]: tag t < S is prefix lane t, tag >= S is sorted-buffer
    position t-S. Gathering payloads through the tags is bit-for-bit
    the stable `lax.sort` of the whole row.

    Executed as a log-depth BITONIC MERGE network rather than the
    textbook searchsorted-both-ways + scatter: on the CPU backend the
    explicit form measured ~3.5s (per-element binary search is
    gather-bound) + ~13s (XLA scatter is a per-element loop) @100k x
    512, while the network is log2(M) stages of elementwise
    compare-exchanges — [prefix | pad(max) | reversed(buffer)] is
    bitonic, and merging carries only (key, tag), with the payloads
    gathered once afterwards. Each exchange compares lexicographic
    (canonical key, tag): the tag makes every element distinct, which
    (a) turns the network's fixed exchange pattern into a deterministic
    total order — comparison networks are not otherwise stable — and
    (b) encodes exactly the stable sort's tie-break: prefix lanes
    before buffer lanes at equal value, and within each run the
    original (stable) order."""
    K = akey.shape[0]
    P = 1 << (M - 1).bit_length()          # pad to a power of two
    pad = P - M
    # pads sit between the ascending and descending runs, keyed above
    # every real key (0xFFFFFFFF, the canonical-key maximum) and tagged
    # past every real tag, so the padded sequence stays bitonic and the
    # pads sink to the row tail; ties among pads are broken by tag.
    # Tags are u16 when P allows (halves the network's memory traffic);
    # strict < so the `+ tdt(M)` pad-tag base stays representable even
    # at the P == M == 65536 boundary, where pad is 0 but the constant
    # is still evaluated at trace time.
    tdt = jnp.uint16 if P < (1 << 16) else jnp.uint32
    padk = jnp.full((K, pad), jnp.uint32(0xFFFFFFFF))
    key = jnp.concatenate([akey, padk, bkey[:, ::-1]], axis=1)
    atag = jax.lax.broadcasted_iota(tdt, (K, S), 1)
    ptag = jax.lax.broadcasted_iota(tdt, (K, pad), 1) + tdt(M)
    btag = jax.lax.broadcasted_iota(tdt, (K, M - S), 1) + tdt(S)
    tag = jnp.concatenate([atag, ptag, btag[:, ::-1]], axis=1)

    stride = P // 2
    while stride >= 1:
        shape = (K, P // (2 * stride), 2, stride)
        k4 = key.reshape(shape)
        t4 = tag.reshape(shape)
        klo, khi = k4[:, :, 0, :], k4[:, :, 1, :]
        tlo, thi = t4[:, :, 0, :], t4[:, :, 1, :]
        swap = (klo > khi) | ((klo == khi) & (tlo > thi))
        key = jnp.stack([jnp.where(swap, khi, klo),
                         jnp.where(swap, klo, khi)], axis=2) \
            .reshape(K, P)
        tag = jnp.stack([jnp.where(swap, thi, tlo),
                         jnp.where(swap, tlo, thi)], axis=2) \
            .reshape(K, P)
        stride //= 2
    return tag[:, :M].astype(jnp.int32)


def _row_sort(vals, wts):
    """The full stable row sort of (value, weight) rows by value."""
    return tuple(jax.lax.sort((vals, wts), dimension=-1, num_keys=1))


def _merge_path_sort(vals, wts, S: int):
    """The same order as _row_sort for rows whose first S lanes are
    already cluster-ordered: sort only the suffix (packed radix passes),
    rank-merge the two runs, gather the payloads once."""
    M = vals.shape[1]
    akey = _canonical_sort_key(vals[:, :S])
    bkey, perm = _stable_sort_perm(_canonical_sort_key(vals[:, S:]))
    tags = _merge_sorted_runs(akey, bkey, S, M)
    # tag t: prefix lane t when t < S, else sorted-buffer position
    # t-S -> original buffer lane through stage 1's permutation
    src = jnp.where(
        tags < S, tags,
        S + jnp.take_along_axis(
            perm, jnp.clip(tags - S, 0, M - S - 1), axis=1))
    return (jnp.take_along_axis(vals, src, axis=1),
            jnp.take_along_axis(wts, src, axis=1))


def _sort_rows(vals, wts, S: int):
    """Row-sort rows with an ordered S-lane prefix, in the form the
    platform is good at — one result, bit for bit (the merge path
    reproduces the stable row sort exactly; tests/
    test_tdigest_merge_path.py). On XLA-CPU the comparator sort is the
    cost and the merge path halves it. On the v5e it is the reverse:
    per full [131072, 512] bank the row sort takes 22 ms while the
    merge path's pieces take 2.6 s (radix passes with their gathers)
    plus three more row gathers at 1.9 s each — a lane gather there
    costs ~28 ns an element (my chip run, PR 23)."""
    return jax.lax.platform_dependent(
        vals, wts, tpu=_row_sort,
        default=partial(_merge_path_sort, S=S))


def _cluster_core(vals, wts, compression: float, C: int,
                  sorted_prefix: int = 0):
    """Greedy k1 clustering of arbitrary [K, M] (value, weight) rows into
    at most C centroids per row — the shared core of compress and the
    batched foreign-digest merge. Zero-weight entries are padding.

    `sorted_prefix=S` asserts vals[:, :S] is already cluster-ordered
    (positive-weight values non-decreasing, zero-weight entries last —
    the module's ordering invariant); then only vals[:, S:] needs
    sorting and the runs are rank-merged, bit-identical to the full
    sort. Callers must only pass S > 0 for prefixes they can PROVE
    ordered — an unordered prefix silently mis-clusters. Whether the
    claim is USED is the platform's business (_sort_rows): the merge
    path is the cheap form where a comparator sort is dear and a gather
    free (XLA-CPU), the plain row sort where it is the other way round
    (the TPU)."""
    K, M = vals.shape
    vals = jnp.where(wts > 0, vals, _INF)

    # Value order must be EXACT here: a quantized packed-key sort (float
    # monotonic bits | column index in an int32) was measured ~4x faster
    # on the CPU backend but shifts cluster membership by ±1 element at
    # quantization-step distances — a 9% p50 swing on bimodal gap data,
    # outside the pinned 1%-of-range accuracy contract. That rejection
    # is superseded by the sorted-run merge above: it removes most of
    # the comparator-sort work while keeping value order bit-exact.
    # (The ingest kernel's packed sort, scatter.sort_by_slot, is
    # different — its key is the integer slot id, packed losslessly.)
    if 0 < sorted_prefix < M:
        vals, wts = _sort_rows(vals, wts, sorted_prefix)
    elif sorted_prefix >= M:
        pass  # the whole row is one ordered run — nothing to do
    else:
        vals, wts = _row_sort(vals, wts)

    return _cluster_tail(vals, wts, compression, C)


def _boundaries(k_left, k_right, wts):
    """Greedy cluster boundaries, scanned over the sorted axis (length
    M), carrying per-row k-value at current cluster start. Initial
    carry is derived from data (k_left[:,0] - 2 <= any k minus 1, so
    the first weighted element always opens a cluster) rather than a
    constant: inside shard_map a constant carry would lack the varying
    mesh-axes type and fail the scan type check."""
    def step(k_start, xs):
        kl, kr, w = xs
        new = (kr - k_start > 1.0) & (w > 0)
        k_start = jnp.where(new, kl, k_start)
        return k_start, new

    _, is_new = jax.lax.scan(
        step,
        k_left[:, 0] - 2.0,
        (k_left.T, k_right.T, wts.T),
    )
    return is_new.T                                      # [K, M] bool


def _ends_by_search(cluster, C: int):
    targets = jnp.arange(C, dtype=jnp.int32)
    return jax.vmap(lambda row: jnp.searchsorted(
        row, targets, side="right"))(cluster).astype(jnp.int32)


def _ends_by_count(cluster, C: int):
    targets = jnp.arange(C, dtype=jnp.int32)
    return jnp.sum(cluster[:, None, :] <= targets[None, :, None],
                   axis=2, dtype=jnp.int32)


def _cluster_ends(cluster, C: int):
    """ends[k, c] = how many lanes of row k carry a cluster id <= c —
    the end position of cluster c in the (non-decreasing) id row. A
    per-row binary search where gathers are cheap; on the TPU, where
    each of its ~10 probes is a row gather, a plain count over the row
    (compare + reduce, which XLA fuses without materializing the
    [K, C, M] mask). Integers either way: the same `ends`."""
    return jax.lax.platform_dependent(
        cluster, tpu=partial(_ends_by_count, C=C),
        default=partial(_ends_by_search, C=C))


def _lanes_by_gather(padded, idx):
    return jnp.take_along_axis(padded, idx, axis=1)


def _lanes_by_select(padded, idx):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, padded.shape[1]), 2)
    return jnp.sum(jnp.where(lane == idx[:, :, None],
                             padded[:, None, :], 0), axis=2)


def _lanes_at(c, idx):
    """c_padded[k, idx[k, j]] with c_padded = [0 | c] — a cumulative
    row read at cluster end positions. A gather where gathers are
    cheap; on the TPU a select-and-sum over the row, whose only
    non-zero term is the wanted lane (exact: x + 0 is x)."""
    padded = jnp.concatenate(
        [jnp.zeros((c.shape[0], 1), c.dtype), c], axis=1)
    return jax.lax.platform_dependent(
        padded, idx, tpu=_lanes_by_select, default=_lanes_by_gather)


def _cluster_tail(vals, wts, compression: float, C: int):
    """The numeric tail of the greedy clustering. Inputs are the
    SORTED (value, weight) rows (empties already +inf-keyed, weight
    0)."""
    K, M = vals.shape
    total = jnp.sum(wts, axis=1, keepdims=True)          # [K, 1]
    safe_total = jnp.where(total > 0, total, 1.0)
    cum = jnp.cumsum(wts, axis=1)                        # [K, M] right edges

    k_right = _k1(cum / safe_total, compression)         # [K, M]
    k_left = _k1((cum - wts) / safe_total, compression)  # [K, M]
    is_new = _boundaries(k_left, k_right, wts)           # [K, M] bool

    cluster = jnp.cumsum(is_new.astype(jnp.int32), axis=1) - 1  # [K, M]
    cluster = jnp.where(wts > 0, cluster, C - 1)  # empties -> last cluster id
    cluster = jnp.clip(cluster, 0, C - 1)  # pathological-overflow safety

    # Per-cluster sums = diff of cumsums at cluster end positions.
    # Empties carry value +inf for the SORT only; in the weighted sum
    # they must contribute 0, not 0*inf=NaN — a NaN here poisons the
    # cumsum for every element after the first empty whenever a row
    # holds a real +inf, and a NaN mean in the output prefix would make
    # the next compress's ordering comparator-undefined in both arms.
    cw = jnp.cumsum(wts, axis=1)
    cwv = jnp.cumsum(wts * jnp.where(wts > 0, vals, 0.0), axis=1)
    ends = _cluster_ends(cluster, C)                     # [K, C] in [0, M]
    w_upto = _lanes_at(cw, ends)
    wv_upto = _lanes_at(cwv, ends)
    w_c = jnp.diff(w_upto, axis=1, prepend=jnp.zeros((K, 1), cw.dtype))
    wv_c = jnp.diff(wv_upto, axis=1, prepend=jnp.zeros((K, 1), cw.dtype))

    # The empties parked on cluster C-1 contributed weight 0, so no mask
    # fixup is needed; real data can also land on C-1 legitimately.
    new_mean = jnp.where(w_c > 0, wv_c / jnp.where(w_c > 0, w_c, 1.0), 0.0)
    # Enforce the ordering invariant EXACTLY: consecutive clusters
    # partition a sorted row, so their exact means are non-decreasing —
    # but the f32 rounding of the cumsum-diff / division above can nudge
    # a mean a couple of ulp past its successor. The merge-path compress
    # consumes this output as an already-sorted run, so a rounding-level
    # inversion would silently reorder the next merge. A running max
    # over the positive-weight prefix pins the invariant at <= a few ulp
    # of adjustment (far inside the accuracy contract), identically in
    # both sort arms — A/B stays bitwise-equal.
    new_mean = jnp.where(
        w_c > 0,
        jax.lax.cummax(jnp.where(w_c > 0, new_mean, -_INF), axis=1),
        0.0)
    return new_mean, w_c


compress = partial(jax.jit, static_argnames=("compression",),
                   donate_argnames=("bank",))(_compress_impl)


@partial(jax.jit, static_argnames=("compression", "num_centroids",
                                   "sorted_prefix"))
def cluster_rows(values, weights, compression: float = 100.0,
                 num_centroids: int = 256, sorted_prefix: int = 0):
    """Cluster arbitrary padded centroid rows: f32[S, M] x2 ->
    (means f32[S, C], weights f32[S, C]).

    The batched foreign-digest merge for the global tier: a whole
    interval's forwarded digests, grouped per slot and padded into one
    matrix, collapse to <= C centroids per slot in ONE device program —
    instead of squeezing thousands of digests through the B-sized sample
    buffer with a compress pass per chunk (importsrv's Combine loop,
    worker.go sym: Worker.ImportMetricGRPC, turned into a batch op).

    Foreign rows arrive unordered, so the default is the full row sort.
    `sorted_prefix=S` is the fast arm for re-merge call sites that can
    PROVE values[:, :S] is cluster-ordered in every row (e.g. the
    importsrv re-chunk passes whose rows lead with a previous
    cluster_rows output) — never pass it for untrusted payloads."""
    return _cluster_core(values, weights, compression, num_centroids,
                         sorted_prefix=sorted_prefix)


@lru_cache(maxsize=None)
def cluster_program(rows: int, lanes: int, compression: float,
                    num_centroids: int, sorted_prefix: int = 0):
    """cluster_rows compiled ahead of time for f32[rows, lanes]
    operands: what the import landing runs. The landing pads its
    piles to a shape that follows from the engine's configuration
    (models/pipeline._IMPORT_LAND_ROWS x _IMPORT_LAND_LANES) and the
    engine's warmup() asks for each of them here, so that no landing
    compiles. Compiled, not run: the largest pair of operands is
    268 MB, and a warm-up has no use for the answer. The program
    keeps cluster_rows' name in a profile."""
    spec = jax.ShapeDtypeStruct((rows, lanes), jnp.float32)
    return cluster_rows.lower(
        spec, spec, compression=compression, num_centroids=num_centroids,
        sorted_prefix=sorted_prefix).compile()


def _take_rows(bank: TDigestBank, rows) -> TDigestBank:
    """The [R, .] part of `bank` at `rows` (ascending ids). An id of
    num_slots or more is padding: it reads the last row here and
    _put_rows drops it."""
    take = jnp.minimum(rows, bank.num_slots - 1)
    return jax.tree.map(lambda a: a[take], bank)


def _put_rows(bank: TDigestBank, rows, part: TDigestBank) -> TDigestBank:
    """Write back what a compress of `part` = _take_rows(bank, rows)
    changed: the centroid and buffer leaves (the scalar leaves are not
    a compress's to touch)."""
    put = lambda leaf, new: leaf.at[rows].set(
        new, mode="drop", indices_are_sorted=True)
    return bank._replace(
        mean=put(bank.mean, part.mean),
        weight=put(bank.weight, part.weight),
        buf_value=put(bank.buf_value, part.buf_value),
        buf_weight=put(bank.buf_weight, part.buf_weight),
        buf_n=put(bank.buf_n, part.buf_n))


# Work-set sizes of the ingest's overflow compress, ascending: a batch
# that leaves samples waiting on n rows compresses the smallest set
# that holds n, and the whole bank when none does. On the v5e at the
# north-star bank ([131072, 256+256]) a whole-bank pass costs 111.5 ms
# and an overflowing batch's row pass 1.7-2.0 ms all told. One set of
# 1,024 covers what the benchmark's cells bring a pump batch (a dozen
# rows in steady_10k, 40 in hot_1k, 1,000 in its first batches); a
# second set of 128 saved 4% of a steady_10k tick's landing time and a
# set of 4,096 cost 13% (builder's chip runs, PR 27; PERF.md 5b).
# (The import landing's work sets are models/pipeline._IMPORT_LAND_ROWS.)
_OVERFLOW_ROWS = (1024,)


def _add_batch_counted(bank: TDigestBank, slots, values, weights,
                       compression: float = 100.0,
                       overflow_rows: tuple | None = None):
    """Scatter a batch of (slot, value, weight) samples into the bank.

    Batched equivalent of Histo.Sample -> MergingDigest.Add. Samples append
    to per-slot buffers; a row whose buffer fills is compressed and its
    leftover samples are re-scattered, looping until the batch is fully
    absorbed (ceil(max_per_slot / B) iterations worst case). Like the
    reference, which merges the temp buffer of the ONE digest that
    filled it, the compress runs over the rows that still have samples
    waiting: they are gathered into the smallest `overflow_rows` work
    set that holds them, clustered by the same _compress_impl, and
    scattered back; every other row keeps its buffer. More waiting
    rows than the largest set (or a bank no larger than a set — decided
    from the static shape) compress the whole bank, as every overflow
    did before. `overflow_rows` is for tests (no caller sets it): None
    is the module's _OVERFLOW_ROWS.
    slot == -1 marks padding and is dropped via out-of-bounds scatter.

    Returns (bank, i32[2]): rows the row arms compressed, and passes of
    the whole-bank arm."""
    K = bank.num_slots
    B = bank.buf_size
    if overflow_rows is None:
        overflow_rows = _OVERFLOW_ROWS

    s, v, w = scatter.sort_by_slot(slots, values, weights, num_slots=K)
    rank = scatter.run_ranks(s)
    # an id past the bank lands nowhere: were it `valid`, its samples
    # would wait on a row no compress can empty and the loop not end
    valid = (s >= 0) & (s < K)
    sd = jnp.where(valid, s, K)  # OOB -> dropped by mode="drop"

    # Exact scalar statistics never need the buffer: pure segment reduces.
    # Sums fold through the 2Sum hi/lo pairs — the per-batch delta is a
    # dense f32 scatter-add (a batch holds at most `batch` samples per
    # slot, so the delta itself is near-exact), then the running totals
    # absorb it with an error-free transformation (scalar.py counters).
    dsum = jnp.zeros_like(bank.vsum).at[sd].add(w * v, mode="drop")
    dcount = jnp.zeros_like(bank.count).at[sd].add(w, mode="drop")
    drecip = jnp.zeros_like(bank.recip).at[sd].add(
        jnp.where(v != 0, w / jnp.where(v != 0, v, 1.0), 0.0), mode="drop")
    vsum, vsum_lo = _two_sum(bank.vsum, dsum + bank.vsum_lo)
    count, count_lo = _two_sum(bank.count, dcount + bank.count_lo)
    recip, recip_lo = _two_sum(bank.recip, drecip + bank.recip_lo)
    bank = bank._replace(
        vmin=bank.vmin.at[sd].min(jnp.where(valid, v, _INF), mode="drop"),
        vmax=bank.vmax.at[sd].max(jnp.where(valid, v, -_INF), mode="drop"),
        vsum=vsum, count=count, recip=recip,
        vsum_lo=vsum_lo, count_lo=count_lo, recip_lo=recip_lo,
    )

    # Where each sample goes follows from the fills at entry alone:
    # every compress the loop makes empties the rows that still wait
    # (and a waiting row's buffer is full), so with `at` the sample's
    # place in its row's stream — fill at entry + rank in the batch —
    # it lands in turn at // B, lane at % B, and the rows that wait
    # before turn j are those holding more than j buffers' worth. No
    # pass needs a per-slot count of its own.
    fill = bank.buf_n + scatter.segment_count(s, valid, K)
    at = bank.buf_n[jnp.where(valid, s, 0)] + rank
    turn = jnp.where(valid, at // B, -1)
    lane = at % B

    def write(bank, j):
        row = jnp.where(turn == j, s, K)
        return bank._replace(
            buf_value=bank.buf_value.at[row, lane].set(v, mode="drop"),
            buf_weight=bank.buf_weight.at[row, lane].set(w, mode="drop"),
            buf_n=jnp.where(fill > j * B, jnp.minimum(fill - j * B, B),
                            bank.buf_n))

    def compress_bank(bank, waiting):
        return _compress_impl(bank, compression)

    def compress_rows(R):
        def arm(bank, waiting):
            # `waiting` marks one sample of each row to compress; the
            # batch is sorted by slot, so a sort brings their ids to
            # the front. Padding ids are K: gathered clamped, dropped
            # at the scatter.
            rows = jnp.sort(jnp.where(waiting, s, K))[:R]
            return _put_rows(
                bank, rows,
                _compress_impl(_take_rows(bank, rows), compression))
        return arm

    # a work set as large as the bank saves nothing over the bank arm
    sizes = tuple(R for R in overflow_rows if R < K)
    arms = tuple(compress_rows(R) for R in sizes) + (compress_bank,)
    last = scatter.run_lasts(s)

    def body(state):
        bank, j, counted = state
        # a slot's samples are placed in rank order, so the last of its
        # run has the latest turn: it stands for the row
        waiting = last & (turn >= j)
        n = jnp.sum(waiting, dtype=jnp.int32)
        arm = jnp.sum(n > jnp.asarray(sizes, jnp.int32), dtype=jnp.int32)
        bank = write(jax.lax.switch(arm, arms, bank, waiting), j)
        whole = arm == len(sizes)
        counted = counted + jnp.stack(
            [jnp.where(whole, 0, n), whole.astype(jnp.int32)])
        return bank, j + 1, counted

    # The common case — no slot's buffer overflows — is the one write
    # and a loop of no turns. The carry starts from the data, not from
    # constants: inside shard_map a constant would lack the varying
    # mesh-axes type the body gives it back with.
    zero = 0 * s[:1]
    turns = jnp.max(turn)
    bank, _, counted = jax.lax.while_loop(
        lambda state: state[1] <= turns, body,
        (write(bank, 0), 1 + zero[0], jnp.zeros((2,), jnp.int32) + zero))
    return bank, counted


def _add_batch_impl(bank: TDigestBank, slots, values, weights,
                    compression: float = 100.0,
                    overflow_rows: tuple | None = None) -> TDigestBank:
    """_add_batch_counted without its counts."""
    return _add_batch_counted(bank, slots, values, weights, compression,
                              overflow_rows)[0]


add_batch = partial(jax.jit, static_argnames=("compression",
                                              "overflow_rows"),
                    donate_argnames=("bank",))(_add_batch_impl)


@partial(jax.jit, donate_argnames=("bank",))
def merge_centroids(bank: TDigestBank, slots, means, weights) -> TDigestBank:
    """Append foreign centroids (e.g. a forwarded digest's) into per-slot
    buffers, to be absorbed by the next compress.

    Batched equivalent of MergingDigest.Merge / Histo.Combine
    (samplers/samplers.go sym: Histo.Combine): merging a digest is just
    re-adding its centroids as weighted samples. Callers must compress
    first if buffers may overflow (the engine guarantees headroom).
    `slots`/`means`/`weights` are flat arrays, one entry per centroid,
    slot == -1 padding. Scalar stats (min/max/sum/count) are merged
    separately via `merge_scalars` since they are exact, not sketched.
    """
    K, B = bank.num_slots, bank.buf_size
    # Zero-weight padding centroids must not consume ranks (they'd shift
    # buffer positions and corrupt later writes), so mask them to slot -1
    # before the sort.
    slots = jnp.where(weights > 0, slots, -1)
    s, v, w = scatter.sort_by_slot(slots, means, weights, num_slots=K)
    rank = scatter.run_ranks(s)
    valid = (s >= 0) & (w > 0)
    pos = bank.buf_n[jnp.where(valid, s, 0)] + rank
    can = valid & (pos < B)
    row = jnp.where(can, s, K)
    col = jnp.clip(pos, 0, B - 1)
    return bank._replace(
        buf_value=bank.buf_value.at[row, col].set(v, mode="drop"),
        buf_weight=bank.buf_weight.at[row, col].set(w, mode="drop"),
        buf_n=bank.buf_n + scatter.segment_count(s, can, K),
    )


@partial(jax.jit, donate_argnames=("bank",))
def merge_scalars(bank: TDigestBank, slots, vmins, vmaxs, vsums, counts,
                  recips) -> TDigestBank:
    """Merge the exact per-digest scalar stats of forwarded digests."""
    K = bank.num_slots
    valid = slots >= 0
    sd = jnp.where(valid, slots, K)
    dsum = jnp.zeros_like(bank.vsum).at[sd].add(
        jnp.where(valid, vsums, 0.0), mode="drop")
    dcount = jnp.zeros_like(bank.count).at[sd].add(
        jnp.where(valid, counts, 0.0), mode="drop")
    drecip = jnp.zeros_like(bank.recip).at[sd].add(
        jnp.where(valid, recips, 0.0), mode="drop")
    vsum, vsum_lo = _two_sum(bank.vsum, dsum + bank.vsum_lo)
    count, count_lo = _two_sum(bank.count, dcount + bank.count_lo)
    recip, recip_lo = _two_sum(bank.recip, drecip + bank.recip_lo)
    return bank._replace(
        vmin=bank.vmin.at[sd].min(jnp.where(valid, vmins, _INF), mode="drop"),
        vmax=bank.vmax.at[sd].max(jnp.where(valid, vmaxs, -_INF), mode="drop"),
        vsum=vsum, count=count, recip=recip,
        vsum_lo=vsum_lo, count_lo=count_lo, recip_lo=recip_lo,
    )


# The import landing's work set (models/pipeline._land_imports_clustered):
# gather_rows -> compress -> fill_buffers -> compress -> scatter_rows
# over the [R, .] rows one landing touches, instead of compress ->
# merge_centroids -> compress over the bank. The compress passes are
# this module's `compress`, on the part. (The ingest's hot-slot
# sidestep, models/pipeline._land_hot, takes the first three steps and
# the scatter over its hot rows, with the ingest executables' compress.)

@jax.jit
def gather_rows(bank: TDigestBank, rows) -> TDigestBank:
    """The [R, .] part at `rows` (ascending; ids of num_slots or more
    are padding), for compress and fill_buffers to work on."""
    return _take_rows(bank, rows)


@partial(jax.jit, donate_argnames=("part",))
def fill_buffers(part: TDigestBank, means, weights) -> TDigestBank:
    """Write clustered centroid rows f32[R, W] (W <= buffer depth, row
    i for part row i, weight 0 == empty lane) into the buffers of a
    part that was JUST compressed, so that every buffer is empty: what
    merge_centroids does with the same centroids flattened, without its
    sort by slot. A cluster_rows row leads with its positive weights,
    so they sit where merge_centroids would have ranked them; and were
    an empty lane between them, the next compress tells it by its
    weight and sorts the others in the order they have here."""
    live = weights > 0
    pad = ((0, 0), (0, part.buf_size - means.shape[1]))
    return part._replace(
        buf_value=jnp.pad(jnp.where(live, means, 0.0), pad),
        buf_weight=jnp.pad(jnp.where(live, weights, 0.0), pad),
        buf_n=jnp.sum(live, axis=1, dtype=jnp.int32))


@partial(jax.jit, donate_argnames=("bank",))
def scatter_rows(bank: TDigestBank, rows, part: TDigestBank) -> TDigestBank:
    """Put the compressed part back at `rows`: ascending, ids of
    num_slots or more dropped."""
    return _put_rows(bank, rows, part)


def merge_banks(a: TDigestBank, b: TDigestBank,
                compression: float = 100.0) -> TDigestBank:
    """Slot-aligned union of two whole banks, BIT-COMMUTATIVE:
    merge_banks(a, b) == merge_banks(b, a) bit-for-bit (the sketch-
    engine property contract, tests/test_sketches.py). Both banks are
    compressed, their centroid rows concatenated and CANONICALLY
    sorted — lexicographic (canonical value key, weight bits, empties
    strictly last), so the sorted multiset is order-independent — then
    re-clustered through the ordinary k1 core. Scalar stats merge in
    f64 (each 2Sum pair's exact value is f64(hi)+f64(lo); f64 addition
    of the two exact values is commutative, unlike chained _two_sum
    folds). Host-level API (the import/oracle path), not a serving
    kernel."""
    a = _compress_impl(a, compression)
    b = _compress_impl(b, compression)
    C = a.num_centroids
    vals = jnp.concatenate([a.mean, b.mean], axis=1)
    wts = jnp.concatenate([a.weight, b.weight], axis=1)
    kv = _canonical_sort_key(jnp.where(wts > 0, vals, _INF))
    # weights are non-negative, so their raw bits are order-monotone;
    # empties key ABOVE any real weight so they sort strictly last even
    # against genuine +inf values
    kw = jnp.where(wts > 0,
                   jax.lax.bitcast_convert_type(wts, jnp.uint32),
                   jnp.uint32(0xFFFFFFFF))
    _kv, _kw, vals, wts = jax.lax.sort((kv, kw, vals, wts), dimension=-1,
                                       num_keys=2)
    mean, weight = _cluster_core(vals, wts, compression, C,
                                 sorted_prefix=vals.shape[1])
    # the bit-commutative f64 scalar merge is single-homed in
    # sketches/base.py (the engines' shared property contract);
    # imported at call time — module-level would cycle through the
    # sketches package's engine adapters back into this module
    from ..sketches.base import merge_scalar_banks_np
    scal = {k: jnp.asarray(v)
            for k, v in merge_scalar_banks_np(a, b).items()}
    return a._replace(mean=mean, weight=weight, **scal)


@jax.jit
def quantile(bank: TDigestBank, qs) -> jax.Array:
    """Batched MergingDigest.Quantile: [K] digests x [P] quantiles -> [K, P].

    Requires compressed, cluster-ordered state (empty buffers) — the
    output of _compress_impl/_cluster_core: per-row means non-decreasing
    over the positive-weight prefix, with zero-weight empties as a
    suffix (cluster ids are consecutive by construction, so an interior
    cluster always has weight > 0; the cummax clamp in _cluster_core
    makes the ordering exact, and vlint SR02 forbids outside writes).
    Every caller compresses first, which is why no defensive re-sort
    happens here: it would be a second full row sort per flush,
    measured at ~30% of the whole CPU flush @100k.

    Centroid i's mass is centered at quantile (cum_i - w_i/2) / W;
    linear interpolation between adjacent centroid means, clamped into
    [vmin, vmax], with the min/max themselves used below the first / above
    the last centroid midpoint (matching the reference's edge handling).
    """
    K, C = bank.mean.shape
    qs = jnp.asarray(qs, bank.mean.dtype)
    P = qs.shape[0]

    means, w = bank.mean, bank.weight

    total = jnp.sum(w, axis=1, keepdims=True)
    safe_total = jnp.where(total > 0, total, 1.0)
    cum = jnp.cumsum(w, axis=1)
    mid_q = (cum - w / 2.0) / safe_total                 # [K, C]
    # Empty clusters (sorted to the end) become duplicate q=1 knots with
    # value vmax, keeping knot_q ascending for jnp.interp.
    mid_q = jnp.where(w > 0, mid_q, 1.0)

    # Build interpolation knots: (0 -> vmin), (mid_q_i -> mean_i), (1 -> vmax)
    knot_q = jnp.concatenate(
        [jnp.zeros((K, 1), mid_q.dtype), mid_q,
         jnp.full((K, 1), 1.0, mid_q.dtype)], axis=1)
    vmin = jnp.where(jnp.isfinite(bank.vmin), bank.vmin, 0.0)[:, None]
    vmax = jnp.where(jnp.isfinite(bank.vmax), bank.vmax, 0.0)[:, None]
    knot_v = jnp.concatenate([vmin, jnp.where(w > 0, means, vmax), vmax],
                             axis=1)

    # The clamp is not a formality: a cluster's mean is the difference of
    # two f32 running sums over its row (_cluster_tail), so it is off by
    # up to an ulp of the ROW's sum, not of the value. With some dozens
    # of samples of ~100 a row (a sum in the thousands) the top
    # singleton landed 3e-6 of its value above the row's exact max, and
    # a p99 read between it and vmax came out above the max (PR 43).
    out = jnp.clip(_interp_knots(knot_q, knot_v, qs), vmin, vmax)
    # Empty digests -> 0 (host layer skips unallocated slots anyway).
    return jnp.where(total > 0, out, 0.0)


def _interp_knots(knot_q, knot_v, qs):
    """Row-wise linear interpolation at qs over ascending knots —
    [K, M] x [P] -> [K, P] — with NO gathers.

    jnp.interp's searchsorted+gather lowers to a pathologically slow
    per-element path under the SPMD partitioner (shard_map), which made
    the mesh flush ~1000x slower than the single-chip program. Because
    knot_q is ascending per row, `knot_q < q` is a prefix mask, so the
    bracketing knots are the mask's last-True / first-False boundary
    positions, recoverable with masked reductions (elementwise ops only —
    partitioner-friendly on every path).
    """
    # Static unroll over the (small) P axis: keeping every intermediate
    # [K, M] leaves M in the lane dimension — a [K, M, P] broadcast would
    # put P (often 2-4) minor-most and waste 126/128 lanes per tile.
    if qs.shape[0] == 0:
        return jnp.zeros((knot_q.shape[0], 0), knot_q.dtype)
    zero = jnp.zeros((), knot_q.dtype)
    cols = []
    for p in range(qs.shape[0]):
        q = qs[p]
        mask = knot_q < q                              # [K, M] prefix
        nxt = jnp.concatenate(
            [mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1)
        lo_b = mask & ~nxt                             # last True
        prv = jnp.concatenate(
            [jnp.ones_like(mask[:, :1]), mask[:, :-1]], axis=1)
        hi_b = (~mask) & prv                           # first False
        q_lo = jnp.sum(jnp.where(lo_b, knot_q, zero), axis=1)   # [K]
        v_lo = jnp.sum(jnp.where(lo_b, knot_v, zero), axis=1)
        q_hi = jnp.sum(jnp.where(hi_b, knot_q, zero), axis=1)
        v_hi = jnp.sum(jnp.where(hi_b, knot_v, zero), axis=1)
        denom = q_hi - q_lo
        t = jnp.where(denom > 0,
                      (q - q_lo) / jnp.where(denom > 0, denom, 1.0), 0.0)
        out = v_lo + t * (v_hi - v_lo)
        # q at/below the first knot: prefix mask empty -> first value
        cols.append(jnp.where(jnp.any(mask, axis=1), out, knot_v[:, 0]))
    return jnp.stack(cols, axis=1)


@jax.jit
def aggregates(bank: TDigestBank):
    """The non-percentile flush aggregates of samplers.Histo
    (samplers/samplers.go sym: HistogramAggregates): max, min, sum, avg,
    count, hmean (median comes from quantile(0.5)).

    The single fold hi + lo here rounds once (relative error ~2^-24) —
    fine for on-device consumers; hosts needing exact counts past 2^24
    read the bank's (hi, lo) pairs directly and sum in float64."""
    cnt = bank.count + bank.count_lo
    vsum = bank.vsum + bank.vsum_lo
    recip = bank.recip + bank.recip_lo
    safe = jnp.where(cnt > 0, cnt, 1.0)
    return {
        "min": jnp.where(cnt > 0, bank.vmin, 0.0),
        "max": jnp.where(cnt > 0, bank.vmax, 0.0),
        "sum": vsum,
        "count": cnt,
        "avg": jnp.where(cnt > 0, vsum / safe, 0.0),
        "hmean": jnp.where(recip > 0, cnt / jnp.where(
            recip > 0, recip, 1.0), 0.0),
    }


def reset(bank: TDigestBank) -> TDigestBank:
    """Fresh interval state with the same shapes (the Worker.Flush map-swap
    equivalent, worker.go sym: Worker.Flush)."""
    k = bank.num_slots
    dt = bank.mean.dtype
    return TDigestBank(
        mean=jnp.zeros_like(bank.mean),
        weight=jnp.zeros_like(bank.weight),
        buf_value=jnp.zeros_like(bank.buf_value),
        buf_weight=jnp.zeros_like(bank.buf_weight),
        buf_n=jnp.zeros_like(bank.buf_n),
        vmin=jnp.full((k,), _INF, dt),
        vmax=jnp.full((k,), -_INF, dt),
        vsum=jnp.zeros((k,), dt),
        count=jnp.zeros((k,), dt),
        recip=jnp.zeros((k,), dt),
        vsum_lo=jnp.zeros((k,), dt),
        count_lo=jnp.zeros((k,), dt),
        recip_lo=jnp.zeros((k,), dt),
    )

"""Fused t-digest compress — one Pallas kernel per bucket.

The XLA merge-path compress (ops/tdigest.py `_cluster_core` with
`sorted_prefix=C`) is three logical dispatches XLA fuses only loosely:
the packed-key radix sort of the sample buffer, the log-depth bitonic
rank-merge against the cluster-ordered centroid prefix, and the greedy
k1 cluster + cummax-clamp. Each stage's [D, M] intermediates (canonical
keys, tags, permutations, merged payload rows, cumsums) round-trip
through HBM between fusion islands. This kernel runs the WHOLE pipeline
per row-block inside one `pallas_call`, so every intermediate lives in
VMEM and the bucket pays exactly one kernel invocation — the structural
HBM win tests assert (one pallas_call in the flush program's jaxpr).

TWO IN-KERNEL SORT ARMS, one numeric pipeline:

  * `network=False` (the interpret/CPU serving arm): the kernel body
    runs `_cluster_core`'s EXACT op sequence — the packed-key radix
    sort, the bitonic rank-merge, the tag gather, and the numeric
    stages, verbatim. Same ops on same inputs in the same order =
    same bits AND same speed as the XLA program (the "no slower than
    XLA on CPU-interpret" gate), with the whole compress living in
    one pallas_call.
  * `network=True` (the form written for Mosaic — which refuses it
    today, see TPU_AUTO_ARM): `lax.sort` has no Mosaic lowering, so
    the sort/merge stages are explicit compare-exchange NETWORKS — a
    bitonic full
    sort of the buffer run carrying the payload lanes, then
    `_merge_sorted_runs`' exchange network replicated literally (same
    pad placement, same reversed run, same lexicographic predicate).
    Every (canonical key, tag) pair is DISTINCT, so the stable-by-key
    order is the unique ascending one and any correct comparison sort
    produces the identical sequence; exchanges MOVE payload bits,
    never compute on them, so ±0.0 canonicalization lives only in the
    keys and NaN payloads ride untouched. The numeric stages are the
    identical jnp/lax ops as `_cluster_core` (cumsum, arcsin-k1, the
    greedy boundary recurrence, searchsorted + take_along_axis,
    cumsum-diff segment sums, the SR02 cummax clamp) — re-derivations
    are exactly where a few-ulp (or NaN-vs-zero) divergence would
    creep in, so there are none.

BIT-IDENTITY (the acceptance bar, tests/test_pallas.py): BOTH arms
reproduce `_compress_impl` bit-for-bit under `interpret=True` on CPU —
±0.0/NaN key canonicalization, duplicate-key stability, NaN payload
bits, cluster-id overflow clipping, and the cummax clamp included.
The network arm is additionally fuzzed as plain jnp against
`_stable_sort_perm`/`_merge_sorted_runs` directly, so its order math
carries a CPU proof of its own.

The row axis is embarrassingly parallel, so the grid blocks rows:
`_BLOCK_ROWS` per program when compiled (VMEM-bounded),
`_BLOCK_ROWS_INTERPRET` under interpret (bounds the simulator's live
temporaries on big banks).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import count_fallback
# kernels/ is a blessed sketch-math module (sk01_allow): this kernel
# IS the t-digest compress (fused arm) and shares the canonical key,
# k1, and cluster-core definitions instead of duplicating them
from ..ops import tdigest as _td

_INF = jnp.inf

# Mosaic's verdict (jax 0.9.0 / jaxlib 0.9.0 / libtpu 0.0.34, TPU v5e,
# the serving shape C=256 B=256 over the 256-row block; chip_smoke.py's
# kernel leg asks again on every run and prints the answer):
#
#   NotImplementedError: Unimplemented primitive in Pallas TPU lowering
#   for KernelType.TC: rev. Please file an issue on
#   https://github.com/jax-ml/jax/issues.
#
# `rev` (the reversed buffer run feeding the bitonic merge) is only the
# first: the same lowering has no rule for asin (the k1 scale), cumsum
# and cummax (the shared cluster tail), nor dynamic_slice /
# dynamic_update_slice (the boundary loop) — operations this kernel is
# built on, not a matter of block size or placement. So on a TPU `auto`
# serves the XLA compress; `on` raises (require_engine_kernels). The
# interpret arm on the CPU is unaffected. ROADMAP carries the rewrite.
TPU_AUTO_ARM = "xla"

_BLOCK_ROWS = 256        # compiled-arm row block (VMEM need unmeasured:
#                          Mosaic refuses the kernel before allocating)
# interpret-arm row block: the simulator holds every intermediate of a
# block live at once, so an unbounded block over a 100k bank would
# peak at GBs of [K, M] temporaries; 4096 rows bounds it at the
# incremental path's largest bucket while keeping the grid loop short
_BLOCK_ROWS_INTERPRET = 4096


# --------------------------------------------------------------- networks

def _cmp_swap(asc, key, tag, vals, wts, stride):
    """One compare-exchange pass at `stride`: element i pairs with
    i XOR stride; `asc` is the per-pair-block direction (True =
    ascending), broadcast over [R, P/(2*stride), 1]. The predicate is
    strict lexicographic (key, tag) — every pair distinct, so the
    network's fixed pattern yields a deterministic total order and the
    payload lanes (vals, wts) are MOVED bit-for-bit, never computed."""
    R, P = key.shape
    shape = (R, P // (2 * stride), 2, stride)

    def split(x):
        x4 = x.reshape(shape)
        return x4[:, :, 0, :], x4[:, :, 1, :]

    klo, khi = split(key)
    tlo, thi = split(tag)
    vlo, vhi = split(vals)
    wlo, whi = split(wts)
    gt = (klo > khi) | ((klo == khi) & (tlo > thi))
    swap = gt ^ (~asc)

    def join(lo, hi):
        return jnp.stack([jnp.where(swap, hi, lo),
                          jnp.where(swap, lo, hi)], axis=2).reshape(R, P)

    return join(klo, khi), join(tlo, thi), join(vlo, vhi), join(wlo, whi)


def _bitonic_sort(key, tag, vals, wts):
    """Full ascending bitonic sort of [R, P] rows (P a power of two)
    by lexicographic (key, tag), payloads carried. With distinct
    (key, tag) pairs this produces THE stable-by-key order — the same
    sequence as ops/tdigest._stable_sort_perm's packed radix passes."""
    P = key.shape[1]
    k = 2
    while k <= P:
        nblk_dir = jax.lax.broadcasted_iota(
            jnp.int32, (1, P // k, 1), 1)
        j = k // 2
        while j >= 1:
            # direction constant over each 2j-block: ascending iff the
            # block's k-bit is clear ((i & k) == 0; i = block_start)
            blocks = P // (2 * j)
            rep = blocks // (P // k)          # 2j-blocks per k-block
            asc = jnp.repeat(nblk_dir % 2 == 0, rep, axis=1) \
                if rep > 1 else (nblk_dir % 2 == 0)
            key, tag, vals, wts = _cmp_swap(asc, key, tag, vals, wts, j)
            j //= 2
        k *= 2
    return key, tag, vals, wts


def _bitonic_merge(key, tag, vals, wts):
    """`_merge_sorted_runs`' exchange network verbatim (stride P/2
    down to 1, always-ascending lexicographic exchanges), with payload
    lanes riding along instead of the XLA path's tag-gather epilogue —
    the same tags select the same elements either way."""
    P = key.shape[1]
    asc = jnp.ones((1, 1, 1), bool)
    stride = P // 2
    while stride >= 1:
        key, tag, vals, wts = _cmp_swap(asc, key, tag, vals, wts,
                                        stride)
        stride //= 2
    return key, tag, vals, wts


# ----------------------------------------------------------- kernel body

def _fused_cluster_network(vals, wts, compression: float, C: int,
                           S: int):
    """The network-arm twin of ops/tdigest._cluster_core(
    sorted_prefix=S) for 0 < S < M: bitonic-sort the suffix run,
    rank-merge against the prefix through _merge_sorted_runs' exchange
    network, then the identical numeric pipeline.
    [R, M] x2 -> [R, C] x2."""
    R, M = vals.shape
    vals = jnp.where(wts > 0, vals, _INF)
    key = _td._canonical_sort_key(vals)

    # -- phase A: stable sort of the buffer run (lanes S..M-1) --------
    nb = M - S
    Pb = 1 << (nb - 1).bit_length()
    bk, bv, bw = key[:, S:], vals[:, S:], wts[:, S:]
    btag = jax.lax.broadcasted_iota(jnp.int32, (R, nb), 1)
    if Pb != nb:
        # pads: canonical-key maximum with tags past every real lane —
        # strictly largest (key, tag), so they sink to the tail even
        # against real 0xFFFFFFFF keys (all-ones-payload NaNs)
        pk = jnp.full((R, Pb - nb), jnp.uint32(0xFFFFFFFF))
        pt = jax.lax.broadcasted_iota(
            jnp.int32, (R, Pb - nb), 1) + nb
        pz = jnp.zeros((R, Pb - nb), vals.dtype)
        bk = jnp.concatenate([bk, pk], axis=1)
        btag = jnp.concatenate([btag, pt], axis=1)
        bv = jnp.concatenate([bv, pz], axis=1)
        bw = jnp.concatenate([bw, pz], axis=1)
    bk, _bt, bv, bw = _bitonic_sort(bk, btag, bv, bw)
    bk, bv, bw = bk[:, :nb], bv[:, :nb], bw[:, :nb]

    # -- phase B: rank-merge against the prefix (network of
    #    _merge_sorted_runs: [prefix | pads | reversed buffer]) -------
    P = 1 << (M - 1).bit_length()
    pad = P - M
    atag = jax.lax.broadcasted_iota(jnp.int32, (R, S), 1)
    sbt = jax.lax.broadcasted_iota(jnp.int32, (R, nb), 1) + S

    # [prefix | pads | reversed run]. Mosaic has no zero-width vectors,
    # and at the default shape (M = C + B = 512, already a power of
    # two) there is no pad piece to build
    pk = pt = pz = None
    if pad:
        pk = jnp.full((R, pad), jnp.uint32(0xFFFFFFFF))
        pt = jax.lax.broadcasted_iota(jnp.int32, (R, pad), 1) + M
        pz = jnp.zeros((R, pad), vals.dtype)

    def bitonic(prefix, pads, run):
        mid = [pads] if pad else []
        return jnp.concatenate([prefix, *mid, run[:, ::-1]], axis=1)

    mk = bitonic(key[:, :S], pk, bk)
    mt = bitonic(atag, pt, sbt)
    mv = bitonic(vals[:, :S], pz, bv)
    mw = bitonic(wts[:, :S], pz, bw)
    _mk, _mt, mv, mw = _bitonic_merge(mk, mt, mv, mw)
    vals, wts = mv[:, :M], mw[:, :M]

    # -- numeric pipeline: the ONE shared tail (_cluster_tail) with
    #    the greedy boundary recurrence as a Mosaic-friendly fori_loop
    #    (compare/select only, so any loop form is bit-equal to the
    #    XLA arm's lax.scan)
    def boundaries(k_left, k_right, w_all):
        def step(i, carry):
            k_start, is_new = carry
            kl = jax.lax.dynamic_slice_in_dim(k_left, i, 1, axis=1)
            kr = jax.lax.dynamic_slice_in_dim(k_right, i, 1, axis=1)
            w = jax.lax.dynamic_slice_in_dim(w_all, i, 1, axis=1)
            new = (kr - k_start > 1.0) & (w > 0)
            k_start = jnp.where(new, kl, k_start)
            is_new = jax.lax.dynamic_update_slice_in_dim(
                is_new, new, i, axis=1)
            return k_start, is_new

        k0 = jax.lax.dynamic_slice_in_dim(k_left, 0, 1, axis=1) - 2.0
        _, is_new = jax.lax.fori_loop(
            0, M, step, (k0, jnp.zeros((R, M), bool)))
        return is_new

    return _td._cluster_tail(vals, wts, compression, C, boundaries)


def _compress_kernel(compression, C, network, mean_ref, weight_ref,
                     bv_ref, bw_ref, out_mean_ref, out_weight_ref):
    vals = jnp.concatenate([mean_ref[:], bv_ref[:]], axis=1)
    wts = jnp.concatenate([weight_ref[:], bw_ref[:]], axis=1)
    if network:
        nm, nw = _fused_cluster_network(vals, wts, compression, C, S=C)
    else:
        nm, nw = _td._cluster_core(vals, wts, compression, C,
                                   sorted_prefix=C)
    out_mean_ref[:] = nm
    out_weight_ref[:] = nw


# ---------------------------------------------------------- entry point

def fused_compress(mean, weight, buf_value, buf_weight,
                   compression: float, interpret: bool,
                   network: bool | None = None):
    """One fused compress dispatch over a [K, C] centroid block + its
    [K, B] buffers -> (new_mean, new_weight) [K, C]. jit-composable
    (callers embed it in the flush program; `interpret` is a
    trace-time constant from the resolved arm).

    `network` picks the in-kernel sort arm (see the module
    docstring); the default — compare-exchange networks when
    compiling for a real backend, `_cluster_core`'s lax.sort form
    under interpret — serves both gates (Mosaic compilability there,
    XLA speed parity here). Tests override it to prove the network
    arm's bit-identity on CPU.

    Counted fallback branch (vlint PK01): shapes the networks cannot
    serve (a buffer wider than the 16-bit lane pack, mirroring
    _stable_sort_perm's own bound, or a degenerate axis) degrade to
    the XLA `_cluster_core` — loud, counted, bit-identical."""
    if network is None:
        network = not interpret
    K, C = mean.shape
    B = buf_value.shape[1]
    if B > (1 << 16) or K == 0 or C < 2 or B < 1:
        count_fallback(
            f"fused_compress: unsupported shape K={K} C={C} B={B}")
        vals = jnp.concatenate([mean, buf_value], axis=1)
        wts = jnp.concatenate([weight, buf_weight], axis=1)
        return _td._cluster_core(vals, wts, compression, C,
                                 sorted_prefix=C)
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    except Exception as e:          # noqa: BLE001 — pallas absent
        count_fallback(f"fused_compress: pallas unavailable ({e})")
        vals = jnp.concatenate([mean, buf_value], axis=1)
        wts = jnp.concatenate([weight, buf_weight], axis=1)
        return _td._cluster_core(vals, wts, compression, C,
                                 sorted_prefix=C)

    import functools

    br = min(_BLOCK_ROWS_INTERPRET if interpret else _BLOCK_ROWS, K)
    Kp = -(-K // br) * br
    if Kp != K:
        pad = ((0, Kp - K), (0, 0))
        mean = jnp.pad(mean, pad)
        weight = jnp.pad(weight, pad)
        buf_value = jnp.pad(buf_value, pad)
        buf_weight = jnp.pad(buf_weight, pad)

    kern = functools.partial(_compress_kernel, float(compression), C,
                             bool(network))
    vmem = pltpu.VMEM
    nm, nw = pl.pallas_call(
        kern,
        grid=(Kp // br,),
        in_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((br, C), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((br, B), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((br, B), lambda i: (i, 0), memory_space=vmem),
        ],
        out_specs=[
            pl.BlockSpec((br, C), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((br, C), lambda i: (i, 0), memory_space=vmem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, C), mean.dtype),
            jax.ShapeDtypeStruct((Kp, C), mean.dtype),
        ],
        interpret=interpret,
    )(mean, weight, buf_value, buf_weight)
    return nm[:K], nw[:K]


def fused_compress_bank(bank, compression: float, interpret: bool,
                        network: bool | None = None):
    """Whole-bank fused compress: the engine-facing twin of
    ops/tdigest._compress_impl (scalar leaves untouched, buffers
    zeroed). Writes bank.mean/weight with the kernel's output — the
    kernel enforces the SR02 cummax clamp exactly as _cluster_core
    does (tests pin bitwise equality), and this module is on the SR02
    allow list as a second invariant-preserving writer."""
    nm, nw = fused_compress(bank.mean, bank.weight, bank.buf_value,
                            bank.buf_weight, compression, interpret,
                            network)
    return bank._replace(
        mean=nm, weight=nw,
        buf_value=jnp.zeros_like(bank.buf_value),
        buf_weight=jnp.zeros_like(bank.buf_weight),
        buf_n=jnp.zeros_like(bank.buf_n))

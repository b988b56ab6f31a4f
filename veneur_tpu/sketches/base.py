"""Engine interface contracts + helpers shared by every sketch engine.

A *sketch engine* is the pluggable unit behind the aggregation
pipeline's fixed call surface (ISSUE 10): the histogram/timer banks and
the set-cardinality banks are no longer hard-wired to one sketch pair
(t-digest + 6-bit-HLL-in-u8) but selected through the registry in
`veneur_tpu/sketches/__init__.py` via the `histogram_backend` /
`set_backend` config keys (the `aggregation_backend` selection pattern).

Engines are FROZEN dataclasses: their fields are the static shape/
accuracy parameters (compression, register precision, level budget), so
an engine instance is hashable and keys the pipeline's lru_cached
executable factories — every AggregationEngine with the same backend
and parameters shares one compiled program per device.

Contract (duck-typed; the default engines are the reference
implementations):

HISTOGRAM ENGINES — own a bank NamedTuple with:
  * item state of engine-specific layout, PLUS the shared exact-scalar
    leaves `vmin/vmax/vsum/count/recip` with `*_lo` 2Sum compensation
    twins (identical names across engines — the flush program and the
    generic aggregate/merge helpers below consume them by name);
  * `num_slots` / `num_centroids` / `buf_size` properties (buf_size =
    what a row takes in one landing before it must be compressed. A
    batch that brings one slot more is the hot-slot sidestep's case,
    models/pipeline._land_histos: the slot's samples are pre-clustered
    on the host to buf_size points, which land after one compress of
    the hot rows in place of add_batch's ceil(n / buf_size) row
    passes; over those rows alone where the engine has gather_rows /
    fill_buffers / scatter_rows, the import_strategy "cluster").
  Methods (pure, jit-composable unless noted):
    init(num_slots) -> bank
    add_batch_impl(bank, slots, values, weights) -> bank
    add_batch_counted_impl(...) -> (bank, i32[2])  (optional: the same
        landing plus what its overflow handling did — rows compressed
        one by one, passes over the whole bank; an engine without it
        counts nothing)
    compress_impl(bank) -> bank
    merge_centroids_impl(bank, slots, means, weights) -> bank
    merge_scalars_impl(bank, slots, mins, maxs, sums, counts, recips)
    quantile_impl(bank, qs) -> f32[K, P]
    aggregates_impl(bank) -> dict (min/max/sum/count/avg/hmean)
    forward_leaves(bank) -> dict of h_* arrays (h_mean/h_weight are the
        flattened weighted-point export every engine shares on the wire:
        a t-digest exports centroids, a compactor sketch exports its
        retained items — both merge at the global tier as weighted
        points, so ONE wire row shape serves every engine)
    donation_split() -> (core_names, buf_names) | None  (host)
    reassemble(core, bufs) -> bank                      (jit-composable)
    merge_banks(a, b) -> bank  (host-level, bit-commutative: the
        cross-engine property suite pins merge(a,b) == merge(b,a))
    state_bytes(num_slots=1) -> int                     (host)
  Attributes: id, wire_version, import_strategy ("cluster"|"direct"),
  bank_leaves (durability leaf order), error_contract (doc string).

INCREMENTAL-FLUSH CONTRACT (ISSUE 11 — holds for every engine, pinned
per backend by tests/test_incremental_flush.py): the flush body may be
evaluated over a row-gathered [D, ·] SLICE of the bank (the dirty
work set) instead of the full [K, ·] bank, so every jit-composable op
must be (a) shape-generic in the slot axis and (b) strictly
row-independent — no op may couple one slot's output to another
slot's state. Additionally a FRESH-INIT row must be a fixed point of
compress and must materialize to a constant baseline row (quantiles/
aggregates/estimate of an empty row depend on nothing but the engine
params): the incremental flush scatters dirty-row outputs over that
cached baseline, and cold piles keep their fresh-init state verbatim
— bit-identity to the full program is the acceptance bar, not an
approximation.

SET ENGINES — own a bank NamedTuple with `registers: u8[K, m]` plus
  `num_slots`/`num_registers` properties. Methods:
    init(num_slots) -> bank
    insert_impl(bank, slots, reg_idx, vals) -> bank
    merge_rows_impl(bank, slots, registers) -> bank
    merge_banks(a, b) -> bank   (bit-commutative lattice join)
    hash_update(h) -> (reg_idx, val)   (host hot path, python ints)
    estimate_device(bank) -> dict  (flush-program outputs)
    estimate_finalize(host_dict) -> None      (host; writes "s_est")
    merge_registers_np(a, b) -> np.ndarray    (host join, spill re-merge)
    encode_registers(regs) -> bytes / decode via the registry codec
  Attributes: id, wire_version, precision, bank_leaves, error_contract.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops.scalar import _two_sum

_INF = jnp.inf

# Shared exact-scalar leaves every histogram engine's bank carries (the
# durability codecs and the generic helpers below walk these by name).
SCALAR_LEAVES = ("vmin", "vmax", "vsum", "count", "recip",
                 "vsum_lo", "count_lo", "recip_lo")


def scalar_aggregates(bank):
    """The non-percentile flush aggregates from the shared exact-scalar
    leaves — one definition for every histogram engine (the t-digest
    engine delegates to ops/tdigest.aggregates, which this mirrors)."""
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


def merge_scalar_stats(bank, slots, vmins, vmaxs, vsums, counts, recips):
    """Merge exact per-digest scalar stats into the shared leaves —
    engine-agnostic (min/max scatter, 2Sum-compensated sums). Returns
    a _replace'd bank."""
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
        vmin=bank.vmin.at[sd].min(
            jnp.where(valid, vmins, _INF), mode="drop"),
        vmax=bank.vmax.at[sd].max(
            jnp.where(valid, vmaxs, -_INF), mode="drop"),
        vsum=vsum, count=count, recip=recip,
        vsum_lo=vsum_lo, count_lo=count_lo, recip_lo=recip_lo,
    )


def add_scalar_stats(bank, sd, valid, v, w):
    """Fold one batch's exact scalar deltas (per-sample form) into the
    shared leaves — the add_batch twin of merge_scalar_stats. `sd` is
    the drop-mapped slot vector (OOB for padding)."""
    dsum = jnp.zeros_like(bank.vsum).at[sd].add(w * v, mode="drop")
    dcount = jnp.zeros_like(bank.count).at[sd].add(w, mode="drop")
    drecip = jnp.zeros_like(bank.recip).at[sd].add(
        jnp.where(v != 0, w / jnp.where(v != 0, v, 1.0), 0.0),
        mode="drop")
    vsum, vsum_lo = _two_sum(bank.vsum, dsum + bank.vsum_lo)
    count, count_lo = _two_sum(bank.count, dcount + bank.count_lo)
    recip, recip_lo = _two_sum(bank.recip, drecip + bank.recip_lo)
    return bank._replace(
        vmin=bank.vmin.at[sd].min(jnp.where(valid, v, _INF), mode="drop"),
        vmax=bank.vmax.at[sd].max(jnp.where(valid, v, -_INF), mode="drop"),
        vsum=vsum, count=count, recip=recip,
        vsum_lo=vsum_lo, count_lo=count_lo, recip_lo=recip_lo,
    )


def merge_scalar_banks_np(a, b):
    """Bit-commutative whole-bank scalar merge for merge_banks: the
    exact value of each 2Sum pair is f64(hi) + f64(lo); f64 addition of
    the two exact values is commutative bit-for-bit, unlike chaining
    _two_sum folds in either order. Returns dict of numpy leaves."""
    import numpy as np
    out = {}
    out["vmin"] = np.minimum(np.asarray(a.vmin), np.asarray(b.vmin))
    out["vmax"] = np.maximum(np.asarray(a.vmax), np.asarray(b.vmax))
    for hi, lo in (("vsum", "vsum_lo"), ("count", "count_lo"),
                   ("recip", "recip_lo")):
        s = (np.asarray(getattr(a, hi), np.float64)
             + np.asarray(getattr(a, lo), np.float64)) \
            + (np.asarray(getattr(b, hi), np.float64)
               + np.asarray(getattr(b, lo), np.float64))
        h = s.astype(np.float32)
        out[hi] = h
        out[lo] = (s - h.astype(np.float64)).astype(np.float32)
    return out

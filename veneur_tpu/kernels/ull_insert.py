"""Pallas scatter-join insert for UltraLogLog register banks.

The XLA insert (sketches/ull.py `_insert_impl`) cannot ride a
scatter-max — the ULL register state is only PARTIALLY ordered, so it
sorts the batch by flat register address, collapses duplicates with a
segmented associative scan of the lattice join, and lands the unique
survivors with a gather-join-scatter. On XLA-CPU that scan is the
single slowest sketch op in the tree.

This kernel is the scatter-join the lattice actually wants: ONE pass
over the batch doing an in-place read-join-write per update against
the aliased register buffer. No sort, no scan, no dedup — the join is
associative, commutative, and idempotent, so ANY application order
(including duplicate (slot, idx) targets hitting the same register
repeatedly) folds to the identical final register value the
sort+scan+dedup path computes. Registers are u8 integers, so
"identical" here is exact equality, not an up-to-rounding claim —
tests/test_pallas.py fuzzes byte equality against `_insert_impl`.

Placement, as the TPU wants it: the batch's (slot, index, value)
triples are scalars and ride in SMEM (scalar prefetch); the register
file is far larger than VMEM ([4096, 8192] u8 = 32 MiB at the default
config), so a grid walks it in row blocks and every program applies
the updates whose slot falls in its block; a u8 register is not an
addressable store on the (32, 128) u8 tile, so each update reads the
aligned tile around its register, joins under a one-hot mask, and
writes the tile back. `input_output_aliases` keeps the update in
place (the enclosing ingest executable donates the bank)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import count_fallback
# kernels/ is a blessed sketch-math module (sk01_allow): this kernel
# IS the ULL insert (fused arm) and shares the one lattice-join
# definition instead of duplicating it
from ..sketches import ull as _ull

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _PALLAS_ERR = None
except Exception as _e:             # noqa: BLE001 — probed at entry
    pl = pltpu = None
    _PALLAS_ERR = _e

# Mosaic (jax 0.9.0 / libtpu 0.0.34, TPU v5e) builds this kernel at
# the serving shape ([4096, 8192] u8 x batch 8192) and its registers
# come out byte-equal to the XLA insert's on the chip (PR 23's chip
# runs; chip_smoke.py's kernel leg re-checks both on every run) —
# `auto` serves it. As first written Mosaic refused it: "ValueError:
# Cannot store scalars to VMEM" (no grid, the batch's scalars and
# single-byte stores in VMEM refs); the placement below is the repair.
# At this shape it ran 6.2 ms a batch against the XLA insert's 1.5 ms
# (smoke observation, one chip): every row block walks the whole
# batch. ROADMAP has the follow-up.
TPU_AUTO_ARM = "fused"

_BLOCK_ROWS = 128        # [128, 8192] u8 = 1 MiB per VMEM block
_TILE = (32, 128)        # the u8 vector tile (sublanes x lanes)


def _insert_kernel(th, tw, slots_ref, idx_ref, vals_ref, regs_ref,
                   out_ref):
    br = out_ref.shape[0]
    row0 = pl.program_id(0) * br
    out_ref[...] = regs_ref[...]
    ri = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 1)

    def body(j, carry):
        r = slots_ref[j] - row0      # padding (slot -1) is in no block

        def land():
            col = idx_ref[j]
            r0 = pl.multiple_of((r // th) * th, th)
            c0 = pl.multiple_of((col // tw) * tw, tw)
            at = (pl.ds(r0, th), pl.ds(c0, tw))
            tile = out_ref[at].astype(jnp.int32)
            joined = _ull._join_i32(tile, vals_ref[j])
            hit = (ri == r - r0) & (ci == col - c0)
            out_ref[at] = jnp.where(hit, joined, tile).astype(jnp.uint8)

        pl.when((r >= 0) & (r < br))(land)
        return carry

    jax.lax.fori_loop(0, slots_ref.shape[0], body, 0)


def fused_insert(bank, slots, reg_idx, vals, interpret: bool):
    """Batched ULL insert through the scatter-join kernel — the fused
    twin of sketches/ull._insert_impl (same signature minus the
    trace-time `interpret` arm constant; jit-composable, caller
    donates the bank).

    Counted fallback branch (vlint PK01): an unavailable pallas, a
    degenerate batch, or a register file the compiled arm cannot tile
    (rows off the 32-row u8 tile) degrades to the XLA sort+scan path —
    loud, counted, value-identical."""
    K, m = bank.registers.shape
    n = int(slots.shape[0])
    if n == 0 or K == 0:
        count_fallback(f"ull fused_insert: degenerate shape n={n} K={K}")
        return _ull._insert_impl(bank, slots, reg_idx, vals)
    if not interpret and (K % _TILE[0] or m % _TILE[1]):
        count_fallback(
            f"ull fused_insert: [{K}, {m}] is off the {_TILE} u8 tile")
        return _ull._insert_impl(bank, slots, reg_idx, vals)
    if pl is None:
        count_fallback(
            f"ull fused_insert: pallas unavailable ({_PALLAS_ERR})")
        return _ull._insert_impl(bank, slots, reg_idx, vals)

    # row block: the widest of 128/32 that divides K; a small or odd
    # bank (interpret-arm tests) is one block with whole-block tiles
    br = next((b for b in (_BLOCK_ROWS, _TILE[0]) if K % b == 0), K)
    th = _TILE[0] if br % _TILE[0] == 0 else br
    tw = _TILE[1] if m % _TILE[1] == 0 else m
    regs = pl.pallas_call(
        functools.partial(_insert_kernel, th, tw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(K // br,),
            in_specs=[pl.BlockSpec((br, m), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((br, m), lambda i, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((K, m), jnp.uint8),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(slots.astype(jnp.int32), reg_idx.astype(jnp.int32),
      vals.astype(jnp.int32), bank.registers)
    return _ull.ULLBank(registers=regs)

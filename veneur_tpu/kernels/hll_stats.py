"""Pallas TPU kernel for the HLL estimate reduction.

The pure-JAX estimator in ops/hll.py materializes `registers.astype(f32)`
and `exp2(-regs)` intermediates of shape [K, m] — 8x the HBM traffic of
the u8 register file itself when XLA doesn't fuse the whole chain. This
kernel streams each u8 block through VMEM exactly once, accumulating the
two row statistics the LogLog-Beta estimator needs:

    ez   = #(register == 0)          (per row)
    zsum = sum(2^-register)          (per row)

Grid: one program per BK-row block; each program walks the m register
columns in 512-lane chunks with a fori_loop, accumulating [BK, 512]
partials that are lane-reduced at the end. The final (tiny, [K]-shaped)
beta-polynomial arithmetic stays in plain jnp outside the kernel.

Use `hll_stats(registers, interpret=True)` on CPU for tests; on a TPU
the compiled kernel runs wherever ops/hll.will_use_pallas says so (the
single-device flush through hll.estimate, the mesh flush inside its
shard_map). vlint PK01 single-homes every pl.* primitive under
veneur_tpu/kernels/.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import count_fallback

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _PALLAS_ERR = None
except Exception as _e:             # noqa: BLE001 — probed at entry
    pl = pltpu = None
    _PALLAS_ERR = _e

# Mosaic (jax 0.9.0 / libtpu 0.0.34, TPU v5e) builds this kernel at
# the serving shape ([4096, 16384] u8) and on the chip its statistics
# agree with the jnp reduction: ez exact, zsum 3.1e-7 and the estimate
# 3.8e-7 relative at worst (PR 23's chip runs; chip_smoke.py's kernel
# leg re-checks against 1e-4 on every run), alone and inside the mesh
# flush's shard_map. 1.2 ms against the jnp reduction's 1.3 ms there
# (smoke observation).

# u8 min tile is (32, 128); BK=32 rows keeps every block aligned.
_BK = 32
_LANES = 512


def _stats_kernel(regs_ref, ez_ref, zsum_ref):
    m = regs_ref.shape[1]
    steps = m // _LANES

    def body(i, carry):
        ez_acc, zsum_acc = carry
        # mosaic has no direct u8->f32 cast; widen through i32
        chunk = regs_ref[:, pl.ds(i * _LANES, _LANES)].astype(jnp.int32)
        f = chunk.astype(jnp.float32)
        ez_acc = ez_acc + jnp.where(chunk == 0, 1.0, 0.0)
        zsum_acc = zsum_acc + jnp.exp2(-f)
        return ez_acc, zsum_acc

    ez_acc, zsum_acc = jax.lax.fori_loop(
        0, steps, body,
        (jnp.zeros((_BK, _LANES), jnp.float32),
         jnp.zeros((_BK, _LANES), jnp.float32)))
    ez_ref[:] = jnp.sum(ez_acc, axis=1, keepdims=True)
    zsum_ref[:] = jnp.sum(zsum_acc, axis=1, keepdims=True)


def _stats_jnp(registers):
    """The plain-jnp twin (the fallback arm): identical statistics
    without the streaming pass — what ops/hll._estimate_jnp reduces."""
    ez = jnp.sum(registers == 0, axis=1).astype(jnp.float32)
    zsum = jnp.sum(jnp.exp2(-registers.astype(jnp.float32)), axis=1)
    return ez, zsum


@functools.partial(jax.jit, static_argnames=("interpret",))
def hll_stats(registers, interpret: bool = False):
    """(ez[K], zsum[K]) for a u8[K, m] register bank via one streaming
    pass. K is padded up to the 32-row block internally.

    Counted fallback branch (vlint PK01): a register width off the
    512-lane chunk grid (no real precision >= 9 hits this) or an
    unavailable pallas degrades to the jnp reduction — same
    statistics, no streaming claim."""
    K, m = registers.shape
    if m % _LANES != 0:
        count_fallback(f"hll_stats: m={m} not a multiple of {_LANES}")
        return _stats_jnp(registers)
    if pl is None:
        count_fallback(f"hll_stats: pallas unavailable ({_PALLAS_ERR})")
        return _stats_jnp(registers)
    Kp = (K + _BK - 1) // _BK * _BK
    if Kp != K:
        registers = jnp.pad(registers, ((0, Kp - K), (0, 0)))
    ez, zsum = pl.pallas_call(
        _stats_kernel,
        grid=(Kp // _BK,),
        in_specs=[pl.BlockSpec((_BK, m), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((_BK, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BK, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Kp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(registers)
    return ez[:K, 0], zsum[:K, 0]

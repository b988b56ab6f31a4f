"""Batched HyperLogLog bank — set-cardinality sketches on TPU.

The reference's samplers.Set (samplers/samplers.go sym: Set.Sample /
Set.Combine) wraps a vendored axiomhq/hyperloglog with 2^14 registers;
inserts hash the member string and take max(register, rho); merge is
elementwise register max; estimation uses the LogLog-Beta bias-corrected
harmonic mean.

Here K sets live as one u8[K, m] register matrix. Hashing happens on the
host (the device never sees strings — see veneur_tpu.utils.hashing);
the device ops are scatter-max (insert), elementwise max (merge — which is
also how cross-chip union rides ICI as a single collective), and a
row-reduction (estimate).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class HLLBank(NamedTuple):
    registers: jax.Array  # u8[K, m], m = 2^precision

    @property
    def num_slots(self):
        return self.registers.shape[0]

    @property
    def num_registers(self):
        return self.registers.shape[1]


def init(num_slots: int, precision: int = 14) -> HLLBank:
    return HLLBank(registers=jnp.zeros((num_slots, 1 << precision), jnp.uint8))


def host_hash_to_updates(hashes64, precision: int = 14):
    """Split host-side 64-bit member hashes into (register index, rho).

    index = top `precision` bits; rho = 1 + leading zeros of the remaining
    bits (capped so it fits u8) — the standard HLL decomposition the
    vendored sketch uses.
    """
    import numpy as np
    h = np.asarray(hashes64, np.uint64)
    idx = (h >> np.uint64(64 - precision)).astype(np.int32)
    rest = (h << np.uint64(precision)) | np.uint64((1 << precision) - 1)
    # clz via exact float64 log2 of the top 53 bits (rho is capped at
    # 64 - precision + 1, so truncating the low 11 bits never matters).
    y = (rest >> np.uint64(11)).astype(np.float64)
    clz = np.where(y > 0, 52.0 - np.floor(np.log2(np.maximum(y, 1.0))), 64.0)
    rho = np.minimum(clz + 1.0, 64 - precision + 1).astype(np.uint8)
    return idx, rho


@partial(jax.jit, donate_argnames=("bank",))
def insert(bank: HLLBank, slots, reg_idx, rho) -> HLLBank:
    """Batched Set.Sample: registers[slot, idx] = max(., rho).
    slot == -1 marks padding (dropped via OOB scatter)."""
    K = bank.num_slots
    row = jnp.where(slots >= 0, slots, K)
    return HLLBank(
        registers=bank.registers.at[row, reg_idx].max(
            rho.astype(jnp.uint8), mode="drop"))


@partial(jax.jit, donate_argnames=("bank",))
def merge_rows(bank: HLLBank, slots, registers) -> HLLBank:
    """Batched Set.Combine: union forwarded sketches into local slots.
    `registers` is u8[n, m]; slots[n] == -1 padding."""
    K = bank.num_slots
    row = jnp.where(slots >= 0, slots, K)
    return HLLBank(
        registers=bank.registers.at[row, :].max(registers, mode="drop"))


def merge_banks(a: HLLBank, b: HLLBank) -> HLLBank:
    """Slot-aligned union of two whole banks (the ICI collective is
    jax.lax.pmax of registers over the mesh axis — same op)."""
    return HLLBank(registers=jnp.maximum(a.registers, b.registers))


# LogLog-Beta coefficients for p=14 (m=16384), as used by the vendored
# axiomhq/hyperloglog estimator.
_BETA14 = (-0.370393911, 0.070471823, 0.17393686, 0.16339839,
           -0.09237745, 0.03738027, -0.005384159, 0.00042419)


def will_use_pallas(num_registers: int) -> bool:
    """True when estimate() will take the Pallas kernel for banks of
    this register width: on a TPU, for widths on its 512-lane chunk
    grid; plain jnp elsewhere. The one place that decides which kernel
    runs: everything else is the XLA program. Exposed so mesh program
    builders can PLACE the estimate consistently with this choice: the
    Pallas kernel belongs inside shard_map (device-local block compute,
    the recommended pallas-under-shard_map pattern), while the jnp
    estimator belongs in the plain-jit epilogue (see
    parallel/mesh.py:_build_flush)."""
    from ..kernels import hll_stats
    from ..utils.platform import is_tpu
    return is_tpu() and num_registers % hll_stats._LANES == 0


def estimate(bank: HLLBank, force_jnp: bool = False) -> jax.Array:
    """Batched cardinality estimate, one f32 per slot.

    LogLog-Beta estimator: m * alpha * (m - ez) / (beta(ez) + sum 2^-reg),
    with beta a degree-7 polynomial in ln(ez + 1). Valid across the whole
    range (no linear-counting switchover needed).

    `force_jnp` pins the pure-jnp path for callers that manage kernel
    placement themselves (the mesh flush, parallel/mesh.py).
    """
    if not force_jnp and will_use_pallas(bank.num_registers):
        return _estimate_pallas(bank)
    return _estimate_jnp(bank)


@jax.jit
def _estimate_pallas(bank: HLLBank) -> jax.Array:
    from ..kernels.hll_stats import hll_stats
    ez, zsum = hll_stats(bank.registers)
    return _estimate_from_stats(bank, ez, zsum)


@jax.jit
def _estimate_jnp(bank: HLLBank) -> jax.Array:
    regs = bank.registers.astype(jnp.float32)
    ez = jnp.sum(bank.registers == 0, axis=1).astype(jnp.float32)
    zsum = jnp.sum(jnp.exp2(-regs), axis=1)
    return _estimate_from_stats(bank, ez, zsum)


def _estimate_from_stats(bank: HLLBank, ez, zsum) -> jax.Array:
    m = bank.num_registers
    zl = jnp.log(ez + 1.0)
    beta = ez * _BETA14[0]
    acc = zl
    for c in _BETA14[1:]:
        beta = beta + c * acc
        acc = acc * zl
    alpha = 0.7213 / (1.0 + 1.079 / m)
    est = alpha * m * (m - ez) / (beta + zsum)
    # ez == m  <=>  every register is zero  <=>  empty set
    return jnp.where(ez < m, est, 0.0)


def reset(bank: HLLBank) -> HLLBank:
    return HLLBank(registers=jnp.zeros_like(bank.registers))

"""UltraLogLog set engine — smaller register banks for equal error.

The UltraLogLog sketch (arxiv 2308.16862, Ertl) packs more information
per register than HyperLogLog: each u8 register stores
``u = 4*q + 2*b1 + b2`` where ``q`` is the LARGEST update value seen
(HLL's rho: 1 + leading zeros of the hash remainder) and the two low
bits record whether updates with values ``q-1`` (b1) and ``q-2`` (b2)
were ALSO seen. The retained event set is exact: an event at level k
survives every later max m' <= k+2, and the final max IS the largest
level, so (q, b1, b2) always reports E_q / E_{q-1} / E_{q-2} truthfully
(lower levels are forgotten). That extra information lets m = 2^13
registers match the estimation error of HLL's 2^14 — the ~28%-state
claim of the paper; in THIS repo's u8-register layout the bank is
literally half the bytes (8 KiB vs 16 KiB per slot) for the same
nominal ~1% error class, which shrinks forward-wire bytes, journal/
checkpoint bytes, and register-bank HBM alike.

Register update/merge is a lattice JOIN, not an elementwise max (the
state space is only partially ordered: (q=5,b=00) and (q=4,b=11) have
no order), so the insert kernel cannot ride a scatter-max. Batched
insert instead sorts the batch by flat register address, collapses
duplicates with a segmented associative-scan of the join, and lands
the now-unique updates with one gather-join-scatter — O(batch log
batch) on device, deterministic (unique scatter indices).

Estimation is the paper's ML estimator, split across the flush
contract: the DEVICE half reduces the register file to a per-slot
value histogram (u8 -> [K, 256] counts, one bincount — the only part
that touches the m-wide state); the HOST half solves the 1-D Poisson
maximum-likelihood per slot by vectorized geometric bisection over the
sufficient statistics. Under the standard Poisson model each register
contributes independent evidence: no event above q (prob e^{-lam z},
z = 2^-q), the event at q, and Bernoulli evidence at q-1 / q-2 from
the indicator bits; the derivative in lam is monotone, so bisection is
exact to float precision. Measured relative stderr at m = 8192 is
~0.85% (tests/test_sketches.py pins a 4-sigma bound).

Incremental-flush contract (sketches/base.py): _value_counts vmaps
per row and ml_estimate solves per slot — both row-independent and
shape-generic in K — and an all-zero register row yields the constant
baseline (counts[0] = m, estimate 0), so the [D, m] dirty-slice
evaluation is exact; only active rows reach the host ML solve either
way.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ULLBank(NamedTuple):
    registers: jax.Array   # u8[K, m], m = 2^precision

    @property
    def num_slots(self):
        return self.registers.shape[0]

    @property
    def num_registers(self):
        return self.registers.shape[1]


def _join_i32(u, v):
    """Elementwise ULL register join on i32 operands (commutative,
    associative, idempotent — the lattice union of retained events)."""
    qu, qv = u >> 2, v >> 2
    qm = jnp.maximum(qu, qv)

    def ev(x, q, k):
        # does register x (max q) prove an event at level k >= 1?
        b1 = (x >> 1) & 1
        b2 = x & 1
        return ((q >= 1) & (k >= 1)
                & ((q == k) | ((q == k + 1) & (b1 == 1))
                   | ((q == k + 2) & (b2 == 1))))

    b1 = ev(u, qu, qm - 1) | ev(v, qv, qm - 1)
    b2 = ev(u, qu, qm - 2) | ev(v, qv, qm - 2)
    out = (qm << 2) | (b1.astype(jnp.int32) << 1) | b2.astype(jnp.int32)
    return jnp.where(qm > 0, out, 0)


def join_registers_np(a, b) -> np.ndarray:
    """Numpy twin of the register join (spill re-merge, oracle tests)."""
    u = np.asarray(a, np.uint8).astype(np.int32)
    v = np.asarray(b, np.uint8).astype(np.int32)
    qu, qv = u >> 2, v >> 2
    qm = np.maximum(qu, qv)

    def ev(x, q, k):
        return ((q >= 1) & (k >= 1)
                & ((q == k) | ((q == k + 1) & ((x >> 1) & 1 == 1))
                   | ((q == k + 2) & (x & 1 == 1))))

    b1 = ev(u, qu, qm - 1) | ev(v, qv, qm - 1)
    b2 = ev(u, qu, qm - 2) | ev(v, qv, qm - 2)
    out = (qm << 2) | (b1.astype(np.int32) << 1) | b2.astype(np.int32)
    return np.where(qm > 0, out, 0).astype(np.uint8)


def _insert_impl(bank: ULLBank, slots, reg_idx, vals) -> ULLBank:
    """Batched insert: join `vals` (pre-packed 4*q register values)
    into registers[slot, reg_idx]. slot == -1 marks padding. Duplicate
    (slot, idx) targets are collapsed with a segmented scan BEFORE the
    scatter so every landed index is unique (deterministic)."""
    K, m = bank.registers.shape
    n = slots.shape[0]
    valid = slots >= 0
    oob = jnp.uint32(K * m)
    flat = jnp.where(valid,
                     slots.astype(jnp.uint32) * jnp.uint32(m)
                     + reg_idx.astype(jnp.uint32),
                     oob)
    order = jnp.argsort(flat)
    f = flat[order]
    v = vals[order].astype(jnp.int32)

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fb, jnp.where(fa == fb, _join_i32(va, vb), vb)

    ff, vv = jax.lax.associative_scan(comb, (f, v))
    last = jnp.concatenate([ff[1:] != ff[:-1],
                            jnp.ones((1,), jnp.bool_)])
    live = last & (ff < oob)
    rows = jnp.where(live, (ff // jnp.uint32(m)).astype(jnp.int32), K)
    cols = jnp.where(live, (ff % jnp.uint32(m)).astype(jnp.int32), 0)
    cur = bank.registers[jnp.minimum(rows, K - 1), cols].astype(jnp.int32)
    joined = _join_i32(cur, vv).astype(jnp.uint8)
    return ULLBank(registers=bank.registers.at[rows, cols].set(
        joined, mode="drop"))


def _merge_rows_impl(bank: ULLBank, slots, registers) -> ULLBank:
    """Union forwarded register rows into local slots (the Combine
    path). `registers` is u8[n, m]; slots[n] == -1 padding; duplicate
    slots in one batch are pre-joined with a segmented scan so the row
    scatter lands unique indices."""
    K = bank.num_slots
    s = jnp.where(slots >= 0, slots, K)
    order = jnp.argsort(s)
    s = s[order]
    regs = registers[order].astype(jnp.int32)

    def comb(a, b):
        sa, va = a
        sb, vb = b
        return sb, jnp.where(sa == sb, _join_i32(va, vb), vb)

    ss, vv = jax.lax.associative_scan(
        comb, (s[:, None].astype(jnp.int32), regs))
    ss = ss[:, 0]
    last = jnp.concatenate([ss[1:] != ss[:-1],
                            jnp.ones((1,), jnp.bool_)])
    row = jnp.where(last & (ss < K), ss, K)
    cur = bank.registers[jnp.minimum(row, K - 1), :].astype(jnp.int32)
    joined = _join_i32(cur, vv).astype(jnp.uint8)
    return ULLBank(registers=bank.registers.at[row, :].set(
        joined, mode="drop"))


# module-level jit: one trace/compile per shape, shared by every
# engine instance (a per-call jax.jit wrapper would retrace each flush)
_merge_rows_j = jax.jit(_merge_rows_impl)


@jax.jit
def _value_counts(registers) -> jax.Array:
    """u8[K, m] -> i32[K, 256] per-slot register-value histogram — the
    ML estimator's sufficient statistic (the device half of estimate)."""
    return jax.vmap(
        lambda r: jnp.bincount(r.astype(jnp.int32), length=256))(
        registers).astype(jnp.int32)


@lru_cache(maxsize=None)
def _ml_terms():
    """Per-register-value likelihood terms: Z[256, 4] probability
    weights, OBS[256, 4] observed flags, MASK[256, 4] validity."""
    Z = np.zeros((256, 4))
    OBS = np.zeros((256, 4), bool)
    MASK = np.zeros((256, 4), bool)
    for u in range(256):
        q, b1, b2 = u >> 2, (u >> 1) & 1, u & 1
        terms = []
        if u == 0:
            terms.append((1.0, False))        # no event at any level
        elif q >= 1:
            terms.append((2.0 ** -q, False))  # nothing above q
            terms.append((2.0 ** -q, True))   # the max event itself
            if q >= 2:
                terms.append((2.0 ** -(q - 1), bool(b1)))
            if q >= 3:
                terms.append((2.0 ** -(q - 2), bool(b2)))
        for t, (z, obs) in enumerate(terms):
            Z[u, t] = z
            OBS[u, t] = obs
            MASK[u, t] = True
    return Z, OBS, MASK


def ml_estimate(counts, num_registers: int) -> np.ndarray:
    """Per-slot ML cardinality from register-value histograms
    (i32[K, 256] -> f64[K]). Solves d/dlam log-likelihood = 0 by
    vectorized geometric bisection (the derivative is strictly
    decreasing in lam); estimate = lam * m. Cost is bounded for the
    flush path: only slots with any nonzero register are solved, the
    observed-event terms collapse onto the <= ~60 distinct probability
    weights (z = 2^-k), and 40 bisection steps reach ~1e-8 relative
    resolution — far inside the sketch's own ~1% noise."""
    counts = np.asarray(counts, np.float64)
    K = counts.shape[0]
    m = float(num_registers)
    out = np.zeros(K)
    active = counts[:, 0] < m                 # any nonzero register
    if not active.any():
        return out
    c_all = counts[active]                    # [A, 256]
    Z, OBS, MASK = _ml_terms()
    used = np.nonzero(c_all.sum(axis=0) > 0)[0]
    c = c_all[:, used]                        # [A, U]
    z = Z[used]
    obs = OBS[used] & MASK[used]
    unobs = (~OBS[used]) & MASK[used]
    # constant part of the derivative: -sum of unobserved weights
    neg = -(c @ (z * unobs).sum(axis=1))      # [A]
    # group observed terms by their (few) distinct z values:
    # f(lam) = sum_z wz * z/expm1(lam*z) + neg
    zvals = np.unique(z[obs])                 # [nz]
    A_map = np.zeros((len(used), len(zvals)))
    for t in range(4):
        col = np.searchsorted(zvals, z[:, t])
        ok = obs[:, t] & (col < len(zvals))
        np.add.at(A_map, (np.nonzero(ok)[0], col[ok]), 1.0)
    wz = c @ A_map                            # [A, nz]

    lo = np.full(c.shape[0], 2.0 ** -40)
    hi = np.full(c.shape[0], 2.0 ** 44)
    with np.errstate(over="ignore", divide="ignore", invalid="ignore"):
        for _ in range(40):
            lam = np.sqrt(lo * hi)
            lz = np.minimum(lam[:, None] * zvals[None, :], 700.0)
            f = (wz * (zvals[None, :] / np.expm1(lz))).sum(axis=1) + neg
            bigger = f > 0                    # root is above lam
            lo = np.where(bigger, lam, lo)
            hi = np.where(bigger, hi, lam)
    out[active] = np.sqrt(lo * hi) * m
    return out


@dataclass(frozen=True)
class ULLEngine:
    precision: int = 13

    id = "ull"
    wire_version = 1
    bank_leaves = ("registers",)
    error_contract = ("ML estimation, relative stderr ~0.85% at "
                      "p=13 (8 KiB/slot — half the HLL p=14 bank)")

    @property
    def num_registers(self) -> int:
        return 1 << self.precision

    def init(self, num_slots: int):
        return ULLBank(registers=jnp.zeros(
            (num_slots, self.num_registers), jnp.uint8))

    def insert_impl(self, bank, slots, reg_idx, vals):
        return _insert_impl(bank, slots, reg_idx, vals)

    def merge_rows_impl(self, bank, slots, registers):
        return _merge_rows_impl(bank, slots, registers)

    def merge_rows(self, bank, slots, registers):
        return _merge_rows_j(bank, slots, registers)

    def merge_banks(self, a, b):
        return ULLBank(registers=_join_i32(
            a.registers.astype(jnp.int32),
            b.registers.astype(jnp.int32)).astype(jnp.uint8))

    def hash_update(self, h: int) -> tuple:
        """(register index, packed 4*q update value) from one 64-bit
        member hash — same index/rank decomposition as HLL, packed
        into the ULL register encoding."""
        p = self.precision
        idx = h >> (64 - p)
        rest = ((h << p) & 0xFFFFFFFFFFFFFFFF) | ((1 << p) - 1)
        q = 65 - rest.bit_length()
        return idx, q << 2

    def host_hash_to_updates(self, hashes64):
        from ..ops import hll as _hll
        idx, rho = _hll.host_hash_to_updates(hashes64, self.precision)
        return idx, (rho.astype(np.int32) << 2).astype(np.uint8)

    def estimate_device(self, bank) -> dict:
        return {"s_counts": _value_counts(bank.registers)}

    def estimate_finalize(self, host: dict) -> None:
        counts = host.pop("s_counts")
        host["s_est"] = ml_estimate(counts, self.num_registers).astype(
            np.float32)

    def merge_registers_np(self, a, b):
        return join_registers_np(a, b)

    def nominal_error(self) -> float:
        # measured ML-estimator stderr constant (~0.76/sqrt(m)); the
        # oracle suite pins a 4-sigma bound on a fixed stream
        return 0.76 / (self.num_registers ** 0.5)

    def state_bytes(self, num_slots: int = 1) -> int:
        return num_slots * self.num_registers

"""The default set engine: batched HyperLogLog register banks.

Adapter over `ops/hll.py` presenting the set-engine contract of
`sketches/base.py`. Selecting `set_backend: hll` (the default) is
behavior-identical to the pre-registry tree.

Error contract: LogLog-Beta estimation, relative standard error
~1.04/sqrt(m) (~0.81% at the default precision 14). State: m = 2^p
u8 registers per slot (16 KiB at p=14).

Incremental-flush contract (sketches/base.py): the register
scatter-max and the LogLog-Beta estimate are per-row and
shape-generic in K, and an all-zero row estimates to the constant
baseline 0.0 — the [D, m] dirty-slice evaluation is exact.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..ops import hll


@dataclass(frozen=True)
class HLLEngine:
    precision: int = 14

    id = "hll"
    wire_version = 1
    bank_leaves = ("registers",)
    error_contract = "relative stderr ~1.04/sqrt(m) (LogLog-Beta)"

    @property
    def num_registers(self) -> int:
        return 1 << self.precision

    def init(self, num_slots: int):
        return hll.init(num_slots, self.precision)

    def insert_impl(self, bank, slots, reg_idx, vals):
        return hll.insert.__wrapped__(bank, slots, reg_idx, vals)

    def merge_rows_impl(self, bank, slots, registers):
        return hll.merge_rows.__wrapped__(bank, slots, registers)

    def merge_rows(self, bank, slots, registers):
        return hll.merge_rows(bank, slots, registers)

    def merge_banks(self, a, b):
        return hll.merge_banks(a, b)

    def hash_update(self, h: int) -> tuple:
        """(register index, rho) from one 64-bit member hash — the
        per-sample ingest hot path (python ints, no numpy)."""
        p = self.precision
        idx = h >> (64 - p)
        rest = ((h << p) & 0xFFFFFFFFFFFFFFFF) | ((1 << p) - 1)
        rho = 65 - rest.bit_length()   # clz + 1; sentinel caps range
        return idx, rho

    def host_hash_to_updates(self, hashes64):
        return hll.host_hash_to_updates(hashes64, self.precision)

    def estimate_device(self, bank) -> dict:
        return {"s_est": hll.estimate(bank)}

    def estimate_finalize(self, host: dict) -> None:
        host["s_est"] = np.asarray(host["s_est"])

    def merge_registers_np(self, a, b):
        """Host-side register union (spill re-merge path)."""
        return np.maximum(np.asarray(a, np.uint8),
                          np.asarray(b, np.uint8))

    def nominal_error(self) -> float:
        return 1.04 / (self.num_registers ** 0.5)

    def state_bytes(self, num_slots: int = 1) -> int:
        return num_slots * self.num_registers

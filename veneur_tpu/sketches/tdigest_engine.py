"""The default histogram engine: batched merging t-digest banks.

A thin adapter over `ops/tdigest.py` (which stays the single home of
the centroid math and the SR02 ordering invariant) presenting the
engine contract of `sketches/base.py`. Selecting
`histogram_backend: tdigest` (the default) routes every pipeline call
through this object with behavior identical to the pre-registry tree —
the exactly-once / overload / kill-restart chaos suites run unmodified
against it.

Error contract: t-digest bounds ABSOLUTE rank error (~1/compression of
total rank per cluster, k1 tail-dense); value error at a quantile
follows the local density. count/sum/min/max are exact.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..ops import tdigest


@dataclass(frozen=True)
class TDigestEngine:
    compression: float = 100.0
    buffer_depth: int = 256

    id = "tdigest"
    wire_version = 1
    import_strategy = "cluster"   # precluster foreign piles (cluster_rows)
    bank_leaves = ("mean", "weight", "buf_value", "buf_weight", "buf_n",
                   "vmin", "vmax", "vsum", "count", "recip", "vsum_lo",
                   "count_lo", "recip_lo")
    error_contract = ("absolute rank error ~1/compression per cluster "
                      "(k1 tail-dense); exact count/sum/min/max")

    # ---- pure, jit-composable ops ----

    def init(self, num_slots: int):
        return tdigest.init(num_slots, self.compression,
                            self.buffer_depth)

    def add_batch_impl(self, bank, slots, values, weights):
        return tdigest._add_batch_impl(bank, slots, values, weights,
                                       self.compression)

    def add_batch_counted_impl(self, bank, slots, values, weights):
        """add_batch_impl plus i32[2]: rows its overflow compressed
        one by one, and passes over the whole bank (the landing
        program sums them into the engine's overflow counter)."""
        return tdigest._add_batch_counted(bank, slots, values, weights,
                                          self.compression)

    def compress_impl(self, bank):
        return tdigest._compress_impl(bank, self.compression)

    def merge_centroids_impl(self, bank, slots, means, weights):
        # caller compresses first (buffer headroom), like the ops
        # module's contract
        return tdigest.merge_centroids.__wrapped__(bank, slots, means,
                                                   weights)

    def merge_scalars_impl(self, bank, slots, vmins, vmaxs, vsums,
                           counts, recips):
        return tdigest.merge_scalars.__wrapped__(
            bank, slots, vmins, vmaxs, vsums, counts, recips)

    def quantile_impl(self, bank, qs):
        return tdigest.quantile.__wrapped__(bank, qs)

    def aggregates_impl(self, bank):
        return tdigest.aggregates.__wrapped__(bank)

    def forward_leaves(self, bank) -> dict:
        return dict(
            h_mean=bank.mean, h_weight=bank.weight,
            h_min=bank.vmin, h_max=bank.vmax,
            h_sum=bank.vsum, h_sum_lo=bank.vsum_lo,
            h_count=bank.count, h_count_lo=bank.count_lo,
            h_recip=bank.recip, h_recip_lo=bank.recip_lo)

    # ---- device-dispatching helpers (module-level jits) ----

    def compress(self, bank):
        return tdigest.compress(bank, compression=self.compression)

    def merge_centroids(self, bank, slots, means, weights):
        return tdigest.merge_centroids(bank, slots, means, weights)

    def merge_scalars(self, bank, slots, vmins, vmaxs, vsums, counts,
                      recips):
        return tdigest.merge_scalars(bank, slots, vmins, vmaxs, vsums,
                                     counts, recips)

    # the import landing's work set: gather -> compress -> fill ->
    # compress -> scatter over the rows a landing touches (and the
    # hot-slot sidestep's, which leaves the points in the buffers)

    def gather_rows(self, bank, rows):
        return tdigest.gather_rows(bank, rows)

    def fill_buffers(self, part, means, weights):
        return tdigest.fill_buffers(part, means, weights)

    def scatter_rows(self, bank, rows, part):
        return tdigest.scatter_rows(bank, rows, part)

    def cluster_program(self, rows: int, lanes: int, num_centroids: int,
                        sorted_prefix: int = 0):
        """The compiled clustering of f32[rows, lanes] (value, weight)
        piles to <= num_centroids a row (ops/tdigest.cluster_program):
        warmup() asks for it, cluster_rows calls it."""
        return tdigest.cluster_program(rows, lanes, self.compression,
                                       num_centroids, sorted_prefix)

    def cluster_rows(self, values, weights, num_centroids: int, *,
                     lanes: tuple, sorted_prefix: int = 0):
        """The import landing's way onto the device: cluster piles
        f32[R, W] through the program compiled for [R, L], L the
        narrowest step of `lanes` (the landing's ladder,
        models/pipeline._land_lanes) that holds W. The landing hands
        over W == L. A width between two steps is padded up with
        lanes of weight 0, which the clustering leaves out: only the
        benchmark's perfbench/harness.LandingWatch sends one (it
        replays each recorded landing at every multiple of 128), and
        the branch goes when the watch does (PERF.md 7); a width past
        the ladder is a caller's bug."""
        rows, width = values.shape
        L = next(n for n in lanes if n >= width)
        if L != width:
            pad = ((0, 0), (0, L - width))
            values, weights = np.pad(values, pad), np.pad(weights, pad)
        return self.cluster_program(rows, L, num_centroids,
                                    sorted_prefix)(values, weights)

    # ---- donation (the fwd_out split the flush executable uses) ----

    def donation_split(self):
        """mean/weight + the eight scalar leaves alias h_* outputs of
        identical shape; the buffer leaves never do (donating them
        would bring the partial-donation warning back)."""
        return (("mean", "weight", "vmin", "vmax", "vsum", "count",
                 "recip", "vsum_lo", "count_lo", "recip_lo"),
                ("buf_value", "buf_weight", "buf_n"))

    def reassemble(self, core, bufs):
        (mean, weight, vmin, vmax, vsum, count, recip,
         vsum_lo, count_lo, recip_lo) = core
        # vlint: disable=SR02 reason=reassembling the caller's own bank
        # from its unmodified leaves — centroid order is untouched
        return tdigest.TDigestBank(
            mean=mean, weight=weight, buf_value=bufs[0],
            buf_weight=bufs[1], buf_n=bufs[2], vmin=vmin, vmax=vmax,
            vsum=vsum, count=count, recip=recip, vsum_lo=vsum_lo,
            count_lo=count_lo, recip_lo=recip_lo)

    # ---- host-level API ----

    def merge_banks(self, a, b):
        """Bit-commutative union for the cross-engine property suite
        (ops/tdigest.merge_banks owns the canonical-sort + recluster)."""
        return tdigest.merge_banks(a, b, compression=self.compression)

    def state_bytes(self, num_slots: int = 1) -> int:
        bank = tdigest.init(1, self.compression, self.buffer_depth)
        per = sum(np.asarray(leaf).nbytes for leaf in bank)
        return per * num_slots

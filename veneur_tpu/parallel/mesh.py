"""MeshEngine: the full aggregation step SPMD over a ("dp", "shard") mesh.

State layout: every bank array grows a leading dp axis and keeps its slot
axis sharded — t-digest means are f32[D, K, C] with sharding
P("dp", "shard", None): D ingest replicas × K slots split over shard
columns. Sample batches are pre-routed on host (global slot id → owning
shard column; any stream can feed any dp row), mirroring how veneur's
digest sharding keeps the hot path synchronization-free
(server.go: `Workers[Digest % len(Workers)]`).

ingest_step: shard_map over both axes — each (dp, shard) program instance
scatters its own [N] sample batch into its local bank slices with the
single-chip kernels. Zero cross-chip traffic, by construction.

flush_merged: the north-star kernel. ONE jitted SPMD program per interval:
per shard column, the dp replicas' sketches merge over ICI —
counters/count/sum psum; min/max pmin/pmax; HLL registers max-reduce;
t-digest centroids all_gather along dp then recluster via the batched
compress — then quantiles, aggregates and HLL estimates are computed for
every slot. This one program subsumes the reference's Worker.Flush +
Server.Flush tally/merge + the local→global Combine tier (flusher.go,
importsrv/) for the intra-pod case; inter-pod (DCN) forwarding stays on
veneur_tpu.cluster's forwardrpc contract.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import hll, scalar, tdigest
from ..ops.tdigest import TDigestBank


class MeshBanks(NamedTuple):
    histo: TDigestBank           # arrays [D, K, ...]
    counter: scalar.CounterBank  # [D, K]
    gauge: scalar.GaugeBank      # [D, K]
    sets: hll.HLLBank            # [D, K2, m]


def make_mesh(n_dp: int = 1, n_shard: int | None = None,
              devices=None) -> Mesh:
    devices = np.asarray(devices if devices is not None else jax.devices())
    if n_shard is None:
        n_shard = len(devices) // n_dp
    return Mesh(devices[: n_dp * n_shard].reshape(n_dp, n_shard),
                ("dp", "shard"))


def _bank_specs(banks: MeshBanks) -> MeshBanks:
    """P("dp", "shard", None...) for every array: dp leading, slot axis
    sharded, trailing dims local."""
    return jax.tree.map(
        lambda a: P("dp", "shard", *([None] * (a.ndim - 2))), banks)


class MeshEngine:
    """Owns the distributed banks and the two compiled SPMD programs."""

    def __init__(self, mesh: Mesh, histogram_slots=1024, counter_slots=512,
                 gauge_slots=512, set_slots=256, compression=100.0,
                 buf_size=128, hll_precision=12,
                 percentiles=(0.5, 0.75, 0.99)):
        self.mesh = mesh
        self.D = mesh.shape["dp"]
        self.S = mesh.shape["shard"]
        if histogram_slots % self.S or counter_slots % self.S \
                or gauge_slots % self.S or set_slots % self.S:
            raise ValueError("slot counts must divide the shard axis")
        self.histogram_slots = histogram_slots
        self.counter_slots = counter_slots
        self.gauge_slots = gauge_slots
        self.set_slots = set_slots
        self.compression = compression
        self.buf_size = buf_size
        self.hll_precision = hll_precision
        # kept as host numpy and always passed as an argument: a device
        # array closed over by a jitted function is baked into the
        # executable as a constant
        self.qs = np.asarray(percentiles, np.float32)
        # One-device mesh: skip the partitioner entirely — all "dp"
        # collectives are identities, so the plain single-device
        # programs are the same computation.
        self._single = (self.D * self.S == 1)
        # where the merged flush estimates set cardinality: the Pallas
        # kernel inside the shard_map, or jnp in the epilogue
        self.pallas_estimate = hll.will_use_pallas(1 << hll_precision)
        self._specs = None
        self.batch_sharding = NamedSharding(mesh, P("dp", "shard"))
        self.banks = self._init_banks()
        if self._single:
            self._ingest_fn = self._build_ingest_single()
            self._flush_fn = self._build_flush_single()
        else:
            self._ingest_fn = self._build_ingest()
            self._flush_fn = self._build_flush()
        # Interval reset runs ON DEVICE (zeros materialize under the
        # existing shardings): re-uploading fresh host banks every flush
        # would move the whole state over PCIe/DCN each interval.
        def _reset(b: MeshBanks) -> MeshBanks:
            return MeshBanks(
                histo=jax.tree.map(jnp.zeros_like, b.histo),
                counter=jax.tree.map(jnp.zeros_like, b.counter),
                # gauge seq sentinel is -1 ("never written"), not 0
                gauge=scalar.GaugeBank(
                    value=jnp.zeros_like(b.gauge.value),
                    seq=jnp.full_like(b.gauge.seq, -1)),
                sets=jax.tree.map(jnp.zeros_like, b.sets))

        if self._single:
            # out_shardings pinned to the device: bank pytrees coming out
            # of a plain jit are "uncommitted", and the next program
            # would compile a second time against those
            dev = self.mesh.devices.reshape(-1)[0]
            sds = jax.sharding.SingleDeviceSharding(dev)
            out_sh = jax.tree.map(lambda _: sds, self.banks)
            self._reset_fn = jax.jit(_reset, donate_argnums=0,
                                     out_shardings=out_sh)
        else:
            # out_shardings pinned: a plain jit would emit
            # UnspecifiedValue shardings, and the NEXT ingest call would
            # silently recompile its whole SPMD program every interval
            shardings = jax.tree.map(
                lambda spec: NamedSharding(self.mesh, spec), self._specs,
                is_leaf=lambda x: isinstance(x, P))
            self._reset_fn = jax.jit(_reset, donate_argnums=0,
                                     out_shardings=shardings)
        # Non-donating fresh banks: the engine-integration swap needs new
        # banks WHILE the snapshot is still feeding the merge program, so
        # it cannot reuse the donating reset (flush_merged's pattern).
        # _template_banks is pure jnp construction, so jitting it yields
        # the fresh state with no closed-over device constants.
        out_sh = (jax.tree.map(lambda _: sds, self.banks) if self._single
                  else shardings)
        self._fresh_fn = jax.jit(self._template_banks,
                                 out_shardings=out_sh)

    # -------------- state --------------

    def _template_banks(self) -> MeshBanks:
        def rep(bank):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (self.D,) + a.shape),
                bank)

        return MeshBanks(
            histo=rep(tdigest.init(self.histogram_slots, self.compression,
                                   self.buf_size)),
            counter=rep(scalar.init_counters(self.counter_slots)),
            gauge=rep(scalar.init_gauges(self.gauge_slots)),
            sets=rep(hll.init(self.set_slots, self.hll_precision)),
        )

    def _init_banks(self) -> MeshBanks:
        banks = self._template_banks()
        if self._specs is None:
            self._specs = _bank_specs(banks)
        if self._single:
            # plain single-device placement — no NamedShardings, so every
            # downstream jit compiles the fast unpartitioned executable
            dev = self.mesh.devices.reshape(-1)[0]
            return jax.tree.map(lambda a: jax.device_put(a, dev), banks)
        shardings = jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec), self._specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.tree.map(jax.device_put, banks, shardings)

    # -------------- ingest step --------------

    def _build_ingest(self):
        comp = self.compression
        batch_spec = P("dp", "shard")  # [D, S*N] -> per-instance [1, N]

        # each SPMD program is named for what it does: a profile lists
        # it as jit_<name>
        def mesh_ingest(banks, hs, hv, hw, cs, cv, cw, gs, gv, gq, ss, si,
                        sr):
            sq = lambda a: a[0]
            histo = jax.tree.map(sq, banks.histo)
            histo = tdigest._add_batch_impl(histo, sq(hs), sq(hv), sq(hw),
                                            comp)
            counter = scalar.counter_add(jax.tree.map(sq, banks.counter),
                                         sq(cs), sq(cv), sq(cw))
            gauge = scalar.gauge_set(jax.tree.map(sq, banks.gauge),
                                     sq(gs), sq(gv), sq(gq))
            sets = hll.insert(jax.tree.map(sq, banks.sets),
                              sq(ss), sq(si), sq(sr))
            ex = lambda a: a[None]
            return MeshBanks(jax.tree.map(ex, histo),
                             jax.tree.map(ex, counter),
                             jax.tree.map(ex, gauge),
                             jax.tree.map(ex, sets))

        shmapped = jax.shard_map(
            mesh_ingest, mesh=self.mesh,
            in_specs=(self._specs,) + (batch_spec,) * 12,
            out_specs=self._specs)
        return jax.jit(shmapped, donate_argnums=(0,))

    def ingest(self, h_slots, h_vals, h_wts, c_slots, c_vals, c_wts,
               g_slots, g_vals, g_seqs, s_slots, s_idx, s_rho):
        """Sample arrays are [D, S*N]: row d feeds dp replica d; columns
        are S per-shard segments of N, each holding LOCAL slot ids
        (-1 padding)."""
        # every operand committed under the program's input sharding
        # (one executable whoever built them), so an operand that never
        # changes can be put once by its owner and handed over again
        batches = jax.device_put(
            (h_slots, h_vals, h_wts, c_slots, c_vals, c_wts, g_slots,
             g_vals, g_seqs, s_slots, s_idx, s_rho), self.batch_sharding)
        self.banks = self._ingest_fn(self.banks, *batches)

    def _build_merge_set_rows(self):
        """SPMD union of forwarded HLL register rows into the sharded
        set bank (the global tier's Set.Combine): rows are pre-routed on
        host into the [D, S*N] segment layout (slot ids shard-local,
        -1 padding), registers ride as u8[D, S*N, m]."""
        if self._single:
            def step(banks, slots, regs):
                sq = lambda a: a[0]
                ex = lambda a: a[None]
                sets = hll.merge_rows(jax.tree.map(sq, banks.sets),
                                      slots[0], regs[0])
                return banks._replace(sets=jax.tree.map(ex, sets))

            dev = self.mesh.devices.reshape(-1)[0]
            sds = jax.sharding.SingleDeviceSharding(dev)
            out_sh = jax.tree.map(lambda _: sds, self.banks)
            return jax.jit(step, donate_argnums=(0,), out_shardings=out_sh)

        def mesh_merge_set_rows(banks, slots, regs):
            sq = lambda a: a[0]
            sets = hll.merge_rows(jax.tree.map(sq, banks.sets),
                                  slots[0], regs[0])
            return banks._replace(
                sets=jax.tree.map(lambda a: a[None], sets))

        shmapped = jax.shard_map(
            mesh_merge_set_rows, mesh=self.mesh,
            in_specs=(self._specs, P("dp", "shard"),
                      P("dp", "shard", None)),
            out_specs=self._specs)
        return jax.jit(shmapped, donate_argnums=(0,))

    def merge_set_rows(self, slots, registers):
        """slots i32[D, S*N] (shard-local ids, -1 padding), registers
        u8[D, S*N, m]."""
        if not hasattr(self, "_merge_set_fn"):
            self._merge_set_fn = self._build_merge_set_rows()
        self.banks = self._merge_set_fn(self.banks, slots, registers)

    def _build_merge_histo_scalars(self):
        """Routed fold of exact per-slot scalar deltas into the t-digest
        bank's 2Sum pairs (the global tier's exact-stats correction; the
        min/max args accept +/-inf sentinels to no-op)."""
        def mesh_merge_histo_scalars(banks, slots, dmin, dmax, dsum, dcnt,
                                     drcp):
            sq = lambda a: a[0]
            histo = tdigest.merge_scalars.__wrapped__(
                jax.tree.map(sq, banks.histo), slots[0], dmin[0],
                dmax[0], dsum[0], dcnt[0], drcp[0])
            return banks._replace(
                histo=jax.tree.map(lambda a: a[None], histo))

        if self._single:
            dev = self.mesh.devices.reshape(-1)[0]
            sds = jax.sharding.SingleDeviceSharding(dev)
            out_sh = jax.tree.map(lambda _: sds, self.banks)
            return jax.jit(mesh_merge_histo_scalars, donate_argnums=(0,),
                           out_shardings=out_sh)
        shmapped = jax.shard_map(
            mesh_merge_histo_scalars, mesh=self.mesh,
            in_specs=(self._specs,) + (P("dp", "shard"),) * 6,
            out_specs=self._specs)
        return jax.jit(shmapped, donate_argnums=(0,))

    def merge_histo_scalars(self, slots, dmin, dmax, dsum, dcnt, drcp):
        if not hasattr(self, "_merge_hs_fn"):
            self._merge_hs_fn = self._build_merge_histo_scalars()
        self.banks = self._merge_hs_fn(self.banks, slots, dmin, dmax,
                                       dsum, dcnt, drcp)

    # -------------- single-device fast paths --------------

    def _build_ingest_single(self):
        comp = self.compression

        def step(banks, hs, hv, hw, cs, cv, cw, gs, gv, gq, ss, si, sr):
            sq = lambda a: a[0]
            ex = lambda a: a[None]
            histo = tdigest._add_batch_impl(
                jax.tree.map(sq, banks.histo), hs[0], hv[0], hw[0], comp)
            counter = scalar.counter_add(
                jax.tree.map(sq, banks.counter), cs[0], cv[0], cw[0])
            gauge = scalar.gauge_set(
                jax.tree.map(sq, banks.gauge), gs[0], gv[0], gq[0])
            sets = hll.insert(
                jax.tree.map(sq, banks.sets), ss[0], si[0], sr[0])
            return MeshBanks(jax.tree.map(ex, histo),
                             jax.tree.map(ex, counter),
                             jax.tree.map(ex, gauge),
                             jax.tree.map(ex, sets))

        # committed outputs for the same reason as _reset_fn (see __init__)
        dev = self.mesh.devices.reshape(-1)[0]
        sds = jax.sharding.SingleDeviceSharding(dev)
        out_sh = jax.tree.map(lambda _: sds, self.banks)
        return jax.jit(step, donate_argnums=(0,), out_shardings=out_sh)

    def _build_flush_single(self):
        """D = S = 1: every "dp" collective is the identity, so the merged
        flush is exactly the single-chip program."""
        comp = self.compression

        @jax.jit
        def flush_one(banks: MeshBanks, qs):
            sq = lambda a: a[0]
            hb = tdigest._compress_impl(jax.tree.map(sq, banks.histo),
                                        comp)
            cb = jax.tree.map(sq, banks.counter)
            gb = jax.tree.map(sq, banks.gauge)
            sb = jax.tree.map(sq, banks.sets)
            q = tdigest.quantile(hb, qs)
            agg = tdigest.aggregates(hb)
            est = hll.estimate(sb, force_jnp=not self.pallas_estimate)
            pairs = (hb.count, hb.count_lo, hb.vsum, hb.vsum_lo)
            return (q, agg, cb.hi, cb.lo, gb.seq,
                    jnp.where(gb.seq >= 0, gb.value, -jnp.inf), est,
                    pairs)

        return lambda banks: flush_one(banks, self.qs)

    # -------------- merged flush --------------

    def _build_flush(self):
        """Two programs, deliberately split:

        1. shard_map MERGE — everything that needs the "dp" collectives
           (all_gather of centroids, psum/pmin/pmax of scalars, register
           union), plus the Pallas HLL estimate when the kernel is in
           play (hll.will_use_pallas): a Pallas call is opaque
           device-local block compute — immune to the partitioner slow
           path below — and the post-pmax registers are exactly its
           per-device block shape.
        2. plain-jit EPILOGUE — quantile/aggregates (and the jnp HLL
           estimate when Pallas is NOT in play) over the merged state.
           These are slot-parallel with no cross-shard dependence, so
           XLA's automatic partitioning handles the sharded inputs;
           keeping them OUT of shard_map matters because several of
           their op compositions (sort feeding masked reductions,
           closed-over scalar indexing, the jnp estimator's masked
           reductions) were seen to lower badly inside
           manually-partitioned regions (not re-measured on an
           attached chip).
        """
        comp = self.compression
        # Estimate PLACEMENT follows the kernel choice (hll.will_use_
        # pallas): the Pallas kernel runs inside the shard_map — after
        # the dp pmax union the registers are shard-local [s_local, R],
        # exactly the per-device block the kernel is written for — while
        # the jnp estimator stays in the plain-jit epilogue (see the
        # docstring). CPU meshes therefore keep the epilogue path.
        pallas_ok = self.pallas_estimate

        def mesh_flush_merge(histo, counter, gauge, sets):
            sq = lambda a: a[0]
            hb = jax.tree.map(sq, histo)
            cb = jax.tree.map(sq, counter)
            gb = jax.tree.map(sq, gauge)
            sb = jax.tree.map(sq, sets)

            # ---- t-digest: all_gather centroids over dp, recluster ----
            hb = tdigest._compress_impl(hb, comp)
            means = jax.lax.all_gather(hb.mean, "dp", axis=1, tiled=True)
            wts = jax.lax.all_gather(hb.weight, "dp", axis=1, tiled=True)
            # vlint: disable=SR02 reason=mean/weight are all-zero rows
            # (trivially cluster-ordered: no positive-weight entries),
            # so the sorted-prefix invariant the merge-path compress
            # depends on holds; the gathered centroids ride in the
            # BUFFER, which compress sorts itself
            merged = TDigestBank(
                mean=jnp.zeros_like(hb.mean),
                weight=jnp.zeros_like(hb.weight),
                buf_value=means, buf_weight=wts,
                buf_n=jnp.zeros_like(hb.buf_n),
                vmin=jax.lax.pmin(hb.vmin, "dp"),
                vmax=jax.lax.pmax(hb.vmax, "dp"),
                vsum=jax.lax.psum(hb.vsum, "dp"),
                count=jax.lax.psum(hb.count, "dp"),
                recip=jax.lax.psum(hb.recip, "dp"),
                # compensation terms sum independently: D small terms
                # cannot reintroduce meaningful rounding error
                vsum_lo=jax.lax.psum(hb.vsum_lo, "dp"),
                count_lo=jax.lax.psum(hb.count_lo, "dp"),
                recip_lo=jax.lax.psum(hb.recip_lo, "dp"),
            )
            merged = tdigest._compress_impl(merged, comp)

            # ---- scalars / HLL: pure collectives ----
            c_hi = jax.lax.psum(cb.hi, "dp")
            c_lo = jax.lax.psum(cb.lo, "dp")
            g_seq = jax.lax.pmax(gb.seq, "dp")
            g_val = jax.lax.pmax(
                jnp.where((gb.seq == g_seq) & (g_seq >= 0), gb.value,
                          -jnp.inf), "dp")
            regs = jax.lax.pmax(sb.registers.astype(jnp.int32), "dp")
            if pallas_ok:   # kernel on the local block; else raw regs
                out = hll.estimate(hll.HLLBank(regs.astype(jnp.uint8)))
            else:           # jnp estimate runs in the epilogue
                out = regs
            return merged, c_hi, c_lo, g_seq, g_val, out

        # vlint: disable=SR02 reason=a pytree of PartitionSpecs, not
        # centroid data — no ordering to break
        bank_spec = TDigestBank(
            mean=P("shard", None), weight=P("shard", None),
            buf_value=P("shard", None), buf_weight=P("shard", None),
            buf_n=P("shard"), vmin=P("shard"), vmax=P("shard"),
            vsum=P("shard"), count=P("shard"), recip=P("shard"),
            vsum_lo=P("shard"), count_lo=P("shard"),
            recip_lo=P("shard"))
        out_specs = (bank_spec, P("shard"), P("shard"), P("shard"),
                     P("shard"),
                     P("shard") if pallas_ok else P("shard", None))
        # check_vma=False: outputs ARE dp-replicated (they come from
        # all_gather/psum/pmax over "dp"), but the varying-axes inference
        # can't prove it for all_gather-derived values.
        merge_fn = jax.jit(jax.shard_map(
            mesh_flush_merge, mesh=self.mesh,
            in_specs=tuple(self._specs), out_specs=out_specs,
            check_vma=False))

        @jax.jit
        def mesh_flush_epilogue(merged, est_or_regs, qs):
            q = tdigest.quantile(merged, qs)
            agg = tdigest.aggregates(merged)
            if pallas_ok:
                est = est_or_regs          # computed in the shard_map
            else:
                est = hll.estimate(hll.HLLBank(
                    est_or_regs.astype(jnp.uint8)), force_jnp=True)
            pairs = (merged.count, merged.count_lo,
                     merged.vsum, merged.vsum_lo)
            return q, agg, est, pairs

        def flush(banks):
            merged, c_hi, c_lo, g_seq, g_val, eor = merge_fn(*banks)
            q, agg, est, pairs = mesh_flush_epilogue(merged, eor, self.qs)
            return q, agg, c_hi, c_lo, g_seq, g_val, est, pairs

        return flush

    def flush_merged(self):
        """Run the merged flush, reset state, return full-K host arrays."""
        out = jax.device_get(self.flush_device(self.banks))
        self.banks = self._reset_fn(self.banks)
        return out

    def flush_device(self, banks) -> dict:
        """Dispatch the merged-flush program on `banks`; device arrays
        out (callers device_get). `counters` folds the 2Sum pair for
        compatibility; `c_hi`/`c_lo` carry the exact halves."""
        q, agg, c_hi, c_lo, g_seq, g_val, est, pairs = \
            self._flush_fn(banks)
        cnt_hi, cnt_lo, sum_hi, sum_lo = pairs
        return {
            "quantiles": q, "agg": agg, "counters": c_hi + c_lo,
            "c_hi": c_hi, "c_lo": c_lo,
            "gauge_seq": g_seq, "gauge_val": g_val, "set_est": est,
            "cnt_hi": cnt_hi, "cnt_lo": cnt_lo,
            "sum_hi": sum_hi, "sum_lo": sum_lo,
        }

    # -------------- host-side batch routing helper --------------

    def route_batch(self, slots, *arrays, slots_per_shard, n_per_segment,
                    dp_row=0, n_dp=None, fill=0.0):
        """Pack a host batch with GLOBAL slot ids into the [D, S*N]
        layout ingest() expects: segment s holds the samples owned by
        shard s with slot ids rebased to the shard-local range.

        One vectorized pass (stable sort by shard + rank-within-run),
        not one scan per shard. Returns (out_slots, *outs, n_overflow):
        samples beyond a shard's segment capacity are NOT packed —
        callers must re-route them in the next batch (or size
        n_per_segment for the worst case); the count is returned so
        drops are never silent."""
        n_dp = n_dp or self.D
        slots = np.asarray(slots)
        out_slots = np.full((n_dp, self.S * n_per_segment), -1, np.int32)
        outs = [np.full((n_dp, self.S * n_per_segment), fill,
                        np.asarray(a).dtype) for a in arrays]
        valid = np.nonzero(slots >= 0)[0]
        if valid.size == 0:
            return (out_slots, *outs, 0)
        shard = slots[valid] // slots_per_shard
        order = np.argsort(shard, kind="stable")
        vidx = valid[order]
        shard = shard[order]
        # rank of each sample within its shard run: position minus the
        # run's start offset (runs are contiguous after the stable sort)
        starts = np.searchsorted(shard, np.arange(self.S), side="left")
        pos = np.arange(len(shard)) - starts[shard]
        keep = pos < n_per_segment
        overflow = int((~keep).sum())
        vidx, shard, pos = vidx[keep], shard[keep], pos[keep]
        dest = shard * n_per_segment + pos
        out_slots[dp_row, dest] = (slots[vidx] % slots_per_shard)
        for o, a in zip(outs, arrays):
            o[dp_row, dest] = np.asarray(a)[vidx]
        return (out_slots, *outs, overflow)

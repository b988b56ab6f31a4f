"""MeshAggregationEngine: the serving engine over a multi-chip Mesh.

This is the `tpu_num_devices > 1` serving path (SURVEY §7 step 7): one
engine whose banks are sharded over a ("dp", "shard") mesh, fed by the
same staging machinery as the single-device engine. The host keeps
GLOBAL slot ids (slot g lives on shard g // slots_per_shard), and the
key tables mint a new key's slot on shard digest(key) % S
(parallel/interner.py), so each chip owns a slice of the key space;
each staged batch is routed into the [D, S*N] segment layout in one
vectorized pass and landed by the MeshEngine's SPMD scatter program;
flush is the MeshEngine's collective merge (all_gather + psum/pmax over
ICI) followed by the shared host assembly.

Parity: this subsumes the reference's in-process worker sharding
(`Workers[Digest % len(Workers)]`, server.go) — the hash space is
partitioned over chips instead of goroutines — while the cluster tier
(forwardrpc over DCN) stays above it, unchanged.

The mesh engine also serves as the GLOBAL tier (is_global): forwarded
digests merge through the same routed ingest — centroids are weighted
samples, and the exact forwarded min/max ride as ZERO-WEIGHT samples
(they update the extremes scatter but contribute nothing to
sum/count/recip); forwarded HLL registers union via a dedicated SPMD
row-merge program; counters/gauges accumulate on host and land through
the scalar scatter kernels. Only upstream forwarding from a mesh engine
is rejected (a multi-chip pod is a root of the aggregation tree; pods
chain via the cluster tier's importsrv).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..ingest.parser import GLOBAL_ONLY
from ..models.pipeline import (AggregationEngine, EngineConfig,
                               _precluster_k1)
from ..models.worker import FOLD_SLOT
from .interner import ShardedKeyInterner
from .mesh import MeshEngine, make_mesh

# What an interval's import landings did, beside the base engine's
# tally: engine attributes `_<name>`, added to under the lock, noted in
# _last_flush_info at the flush and reset. Points staged (the two
# extremes riders of each digest among them), programs dispatched for
# imports (routed ingest, scalar and set-row merges), scatter rounds,
# and slots _stage_histos pre-clustered on the host (the landing's
# schedule keeps it 0).
_MESH_TALLY = ("mesh_import_points", "mesh_import_dispatches",
               "mesh_import_rounds", "mesh_import_preclustered")

# The server's self-metric (veneur.<name>_total) for each count of an
# interval that the flush notes in _last_flush_info.
MESH_TELEMETRY = {"mesh_import_points": "import.mesh.points",
                  "mesh_import_dispatches": "import.mesh.dispatches",
                  "mesh_import_rounds": "import.mesh.rounds",
                  "mesh_import_preclustered": "import.mesh.preclustered",
                  "mesh_interner_spills": "import.mesh.interner_spills"}


class MeshAggregationEngine(AggregationEngine):
    # ISSUE 11 paths stay off here: the mesh engine owns SHARDED banks
    # (no per-slot dirty bitmaps — it is likewise excluded from delta
    # checkpoints) and its landing paths write self.me.banks in place,
    # so the retired-snapshot landing of the double buffer does not
    # apply. Flush keeps the legacy drain-under-lock ordering and the
    # full collective merge.
    _incremental_capable = False
    _double_buffer_capable = False

    def __init__(self, config: EngineConfig, n_devices: int | None = None,
                 mesh=None, n_dp: int = 1):
        if config.forward_enabled:
            raise ValueError(
                "mesh engine cannot forward upstream; point local "
                "veneurs at this server's import listener instead")
        if config.histogram_backend != "tdigest" \
                or config.set_backend != "hll":
            raise ValueError(
                "mesh engine supports only the default sketch "
                "backends (its sharded banks are built directly on "
                "the t-digest/HLL ops)")
        self._mesh_cfg = (mesh, n_devices, n_dp)
        self._pad_cache: dict = {}
        self._import_h_points = 0
        self._import_h_deltas: dict = {}
        self._set_rows_chunk = 64
        for name in _MESH_TALLY:
            setattr(self, "_" + name, 0)
        super().__init__(config)

    # ---------------- device setup ----------------

    def _setup_device(self):
        cfg = self.cfg
        mesh, n_devices, n_dp = self._mesh_cfg
        if mesh is None:
            devs = jax.devices()
            if n_devices is not None:
                devs = devs[:n_devices]
            mesh = make_mesh(n_dp, len(devs) // n_dp, devices=devs)
        self._device = mesh.devices.reshape(-1)[0]

        def pad_to(total, s):
            return -(-total // s) * s

        self.me = MeshEngine(
            mesh,
            histogram_slots=pad_to(cfg.histogram_slots, mesh.shape["shard"]),
            counter_slots=pad_to(cfg.counter_slots, mesh.shape["shard"]),
            gauge_slots=pad_to(cfg.gauge_slots, mesh.shape["shard"]),
            set_slots=pad_to(cfg.set_slots, mesh.shape["shard"]),
            compression=cfg.compression,
            buf_size=cfg.buffer_depth,
            hll_precision=cfg.hll_precision,
            percentiles=tuple(cfg.percentiles))
        self.S = self.me.S
        # the mesh flush is its own sharded program: XLA compress and
        # insert whatever the knob says, and the estimate reduction
        # through the Pallas kernel exactly where the MeshEngine
        # placed it (hll.will_use_pallas)
        self._kernel_arms = {
            "histogram": "xla", "set": "xla",
            "estimate": "fused" if self.me.pallas_estimate else "xla"}

    def _setup_flush_exec(self):
        # the MeshEngine owns the compiled flush; the single-device
        # _flush_executable is never built for a mesh engine
        self._flush_exec = None
    # _fetch_flush is inherited from AggregationEngine.

    def _key_table(self, slots: int) -> ShardedKeyInterner:
        """Each chip owns a slice of the key space: a new key's row is
        minted on shard digest(key) % S (parallel/interner.py), over the
        bank as the MeshEngine padded it."""
        return ShardedKeyInterner(-(-slots // self.S) * self.S, self.S,
                                  self.cfg.idle_ttl_intervals)

    # ---------------- ingest ----------------
    # Staged batches carry GLOBAL slot ids straight from the interners;
    # each dispatch routes one bank's batch into the segment layout and
    # runs the SPMD scatter with all-padding batches for the other
    # banks (fixed shapes, so there is exactly one ingest executable).

    def _route(self, per_shard, slots, *arrays, fill=0.0):
        out = self.me.route_batch(
            slots, *arrays, slots_per_shard=per_shard,
            n_per_segment=len(np.asarray(slots)), fill=fill)
        assert out[-1] == 0  # segments are batch-sized: cannot overflow
        return out[:-1]

    def _pad(self, dtype=np.float32, fill=0.0):
        # all-padding batches are constant; build each once and share
        # (JAX never mutates jit inputs, and neither do we)
        key = (np.dtype(dtype).name, fill)
        cached = self._pad_cache.get(key)
        if cached is None:
            shape = (self.me.D, self.S * self.cfg.batch_size)
            cached = np.full(shape, fill, dtype)
            cached.setflags(write=False)
            # vlint: disable=TH01 reason=every caller (dispatch paths,
            # warmup, import landing) already holds the engine lock —
            # taking self.lock here would self-deadlock
            self._pad_cache[key] = cached
        return cached

    def _pads_for(self, *banks):
        out = []
        for b in banks:
            if b == "histo" or b == "counter":
                out += [self._pad(np.int32, -1), self._pad(), self._pad()]
            elif b == "gauge":
                out += [self._pad(np.int32, -1), self._pad(),
                        self._pad(np.int32)]
            else:
                out += [self._pad(np.int32, -1), self._pad(np.int32),
                        self._pad(np.uint8)]
        return out

    def _stage_histos(self, slots, values, weights) -> tuple:
        """The host half of a histogram batch: (slots, values, weights)
        routed into the segment layout."""
        # Hot-slot sidestep, mesh flavor: a batch overfilling one slot's
        # buffer would loop full-shard sorts inside the SPMD ingest
        # program. Pre-cluster hot slots on host to <= B weighted points
        # (k1-spaced, with the true min/max kept as singletons so the
        # exact extremes survive) and push them through the SAME routed
        # ingest as ordinary weighted samples — sum/count are exactly
        # preserved by the weights; only recip/hmean degrades to the
        # digest's own approximation class for the hot batch.
        slots = np.asarray(slots)
        B = self.cfg.buffer_depth
        valid = slots >= 0
        uniq, cnt = (np.unique(slots[valid], return_counts=True)
                     if valid.any() else (np.array([]), np.array([])))
        if cnt.size and cnt.max() > B:
            values = np.asarray(values, np.float32)
            weights = np.asarray(weights, np.float32)
            hot = uniq[cnt > B]
            self._mesh_import_preclustered += len(hot)
            # compact the cold rows first: cold + (<= B points per hot
            # slot, each of which had > B raw samples) always fits the
            # original batch width, so nothing can truncate below
            cold_m = valid & ~np.isin(slots, hot)
            out_s = [slots[cold_m].astype(np.int32)]
            out_v, out_w = [values[cold_m]], [weights[cold_m]]
            for s in hot.tolist():
                m = (slots == s) & valid
                cm, cw = _precluster_k1(
                    values[m].astype(np.float64),
                    weights[m].astype(np.float64), B,
                    keep_extremes=True)
                out_s.append(np.full(len(cm), s, np.int32))
                out_v.append(cm.astype(np.float32))
                out_w.append(cw.astype(np.float32))
            # pad the combined arrays back to the fixed batch width
            n = self.cfg.batch_size
            slots = np.full(n, -1, np.int32)
            values = np.zeros(n, np.float32)
            weights = np.zeros(n, np.float32)
            fs = np.concatenate(out_s)
            fv = np.concatenate(out_v)
            fw = np.concatenate(out_w)
            # cold rows + <=B points per hot slot always fit the batch
            slots[:len(fs)] = fs[:n]
            values[:len(fs)] = fv[:n]
            weights[:len(fs)] = fw[:n]
        return self._route(
            self.me.histogram_slots // self.S, slots, values, weights)

    def _add_histos(self, slots, values, weights):
        self.me.ingest(*self._stage_histos(slots, values, weights),
                       *self._pads_for("counter", "gauge", "set"))

    def _dispatch_histos(self):
        a = self._histo_stage.drain()
        self._add_histos(a["slots"], a["values"], a["weights"])

    def _dispatch_counters(self):
        a = self._counter_stage.drain()
        cs, cv, cw = self._route(
            self.me.counter_slots // self.S, a["slots"], a["values"],
            a["weights"])
        self.me.ingest(*self._pads_for("histo"), cs, cv, cw,
                       *self._pads_for("gauge", "set"))

    def _dispatch_gauges(self):
        a = self._gauge_stage.drain()
        gs, gv, gq = self._route(
            self.me.gauge_slots // self.S, a["slots"], a["values"],
            a["seqs"])
        self.me.ingest(*self._pads_for("histo", "counter"), gs, gv, gq,
                       *self._pads_for("set"))

    def _dispatch_sets(self):
        a = self._set_stage.drain()
        ss, si, sr = self._route(
            self.me.set_slots // self.S, a["slots"], a["reg_idx"],
            a["rho"])
        self.me.ingest(*self._pads_for("histo", "counter", "gauge"),
                       ss, si, sr)

    def ingest_histo_batch(self, slots, values, weights, count=None,
                           mark=None):
        def apply(n):
            self._add_histos(slots, values, weights)
        self._ingest_batch(slots, count, mark, apply)

    def ingest_counter_batch(self, slots, values, weights, count=None,
                             mark=None):
        def apply(n):
            cs, cv, cw = self._route(
                self.me.counter_slots // self.S, slots, values, weights)
            self.me.ingest(*self._pads_for("histo"), cs, cv, cw,
                           *self._pads_for("gauge", "set"))
        self._ingest_batch(slots, count, mark, apply)

    def ingest_gauge_batch(self, slots, values, count=None, mark=None):
        def apply(n):
            seqs = np.arange(1, len(slots) + 1, dtype=np.int32) \
                + self._gauge_seq
            self._gauge_seq += n
            gs, gv, gq = self._route(
                self.me.gauge_slots // self.S, slots, values, seqs)
            self.me.ingest(*self._pads_for("histo", "counter"),
                           gs, gv, gq, *self._pads_for("set"))
        self._ingest_batch(slots, count, mark, apply)

    def ingest_set_batch(self, slots, reg_idx, rho, count=None, mark=None):
        def apply(n):
            ss, si, sr = self._route(
                self.me.set_slots // self.S, slots, reg_idx, rho,
                fill=0)
            self.me.ingest(*self._pads_for("histo", "counter", "gauge"),
                           ss, si, sr)
        self._ingest_batch(slots, count, mark, apply)

    # ---------------- flush ----------------

    def _take_tally(self, names) -> dict:
        """The base engine's tally of the interval with this engine's
        own, and where the keys sit: live histogram rows a shard
        (`mesh_shard_rows`) and keys a full shard spilled onto another
        (`mesh_interner_spills`, all four tables), read after the
        interval's evictions."""
        tally = super()._take_tally(names + _MESH_TALLY)
        # a native bridge's views (Server._setup_native_ingest) place
        # keys themselves and count neither
        sharded = [ki for ki in (self.histo_keys, self.counter_keys,
                                 self.gauge_keys, self.set_keys)
                   if isinstance(ki, ShardedKeyInterner)]
        if isinstance(self.histo_keys, ShardedKeyInterner):
            tally["mesh_shard_rows"] = self.histo_keys.shard_rows()
        tally["mesh_interner_spills"] = sum(ki.spills for ki in sharded)
        for ki in sharded:
            ki.spills = 0
        return tally

    def _swap_banks(self):
        snap = self.me.banks
        self.me.banks = self.me._fresh_fn()
        return snap

    def _flush_device(self, snap, phases=None, dirty=None,
                      overflow=None) -> tuple:
        """Collective merge over the mesh, mapped onto the host-dict
        contract the shared assembly consumes: (host, None), every
        output dense over the slots, so the assembly's row map is the
        identity. `phases` (the flight
        recorder's stamp list), `dirty` and `overflow` (always None
        here — the mesh engine carries no per-slot bitmaps and its
        in-program overflow compress counts nothing) are accepted for
        signature parity with the single-device engine. With `phases`
        the one collective program is stamped device.dispatch /
        device.exec (bounded by block_until_ready) / device.fetch, as
        the single-device engine's is (_timed_fetch)."""
        if phases is None:
            dev = self._fetch_flush(self.me.flush_device(snap))
        else:
            t0 = time.monotonic_ns()
            out = self.me.flush_device(snap)
            dev = self._timed_fetch(out, t0, time.monotonic_ns(), phases)
        agg = dev["agg"]
        host = {
            "q": dev["quantiles"],
            "c_hi": dev["c_hi"], "c_lo": dev["c_lo"],
            "g_value": dev["gauge_val"], "g_seq": dev["gauge_seq"],
            "s_est": dev["set_est"],
        }
        cols = []
        for a in self._agg_emit:
            if a == "count":
                cols.append(dev["cnt_hi"])
                host["lo_count"] = dev["cnt_lo"]
            elif a == "sum":
                cols.append(dev["sum_hi"])
                host["lo_sum"] = dev["sum_lo"]
            else:
                cols.append(agg[a])
        if cols:
            host["aggcols"] = np.stack(cols, axis=1)
        if "count" not in self._agg_emit:
            host["cnt"] = agg["count"]
        self._last_flush_info = self._full_flush_info()
        return host, None

    def warmup(self):
        """Compile the SPMD ingest + merged flush (+ the global tier's
        register-row merge) before serving."""
        with self.lock:
            self.me.ingest(*self._pads_for("histo", "counter", "gauge",
                                           "set"))
            if self.cfg.is_global:
                nrow = self._set_rows_chunk
                m = 1 << self.cfg.hll_precision
                self.me.merge_set_rows(
                    np.full((self.me.D, self.S * nrow), -1, np.int32),
                    np.zeros((self.me.D, self.S * nrow, m), np.uint8))
                # the exact-stats delta fold compiles here too, not
                # under the engine lock at the first forwarded digest
                shape = (self.me.D, self.S * self.cfg.batch_size)
                zf = np.zeros(shape, np.float32)
                self.me.merge_histo_scalars(
                    np.full(shape, -1, np.int32),
                    np.full(shape, np.inf, np.float32),
                    np.full(shape, -np.inf, np.float32), zf, zf, zf)
        self._fetch_flush(self.me.flush_device(self.me._fresh_fn()))
        jax.block_until_ready(self.me.banks.histo.mean)

    # ---------------- import (global tier Combine path) ----------------
    # Overrides: the single-device engine merges imports with dedicated
    # cluster/merge programs; on the mesh everything lands through the
    # routed SPMD ingest instead (see module docstring). The overrides
    # are the `_locked` halves: the base class's import_histogram /
    # import_set take the lock and import_list holds it across a batch.

    def _import_histogram_locked(self, key, means, weights, vmin, vmax,
                                 vsum, count, recip=0.0):
        slot = self.histo_keys.lookup(key, GLOBAL_ONLY)
        if slot == FOLD_SLOT:
            # overload defense: over-budget forwarded keys fold
            # into `<prefix>.__other__` here too (the mesh server
            # is a single engine, so the fold is always local)
            slot = self._fold_import_slot(self.histo_keys, key)
        if slot < 0:
            return
        means = np.asarray(means, np.float64)
        weights = np.asarray(weights, np.float64)
        # cap at B-2 so item + extreme riders never exceeds B — the
        # landing batches are scheduled so one slot never overflows
        # its buffer in a single scatter, keeping the hot-slot
        # pre-cluster (whose recip is approximate) OFF this path
        B = self.cfg.buffer_depth - 2
        if len(means) > B:
            means, weights = _precluster_k1(means, weights, B)
        # a centroid mean comes out of a cumsum difference and can
        # sit a few ulp outside the digest's exact [vmin, vmax];
        # staged as a sample it would then move this slot's
        # extremes off the forwarded exact ones
        means = np.clip(means, vmin, vmax)
        self._import_centroids.append(
            (slot, means, weights, float(vmin), float(vmax)))
        self._import_h_points += len(means) + 2
        # The staged centroids flow through the ingest scatter, so
        # they CONTRIBUTE approximate vsum/count/recip; accumulate
        # the exact-minus-staged delta per slot (f64 host math) and
        # fold it in via merge_histo_scalars — making the flushed
        # sum/count/hmean match the forwarded exact values, like
        # the single-device merge_scalars path.
        # replicate the device's f32 per-term arithmetic so the
        # delta cancels the staged contribution to rounding level
        m32 = means.astype(np.float32)
        w32 = weights.astype(np.float32)
        staged_sum = float((m32 * w32).astype(np.float64).sum())
        staged_cnt = float(w32.astype(np.float64).sum())
        nz = m32 != 0
        staged_rcp = float((w32[nz] / m32[nz])
                           .astype(np.float64).sum())
        d = self._import_h_deltas.setdefault(slot, [0.0, 0.0, 0.0])
        d[0] += float(vsum) - staged_sum
        d[1] += float(count) - staged_cnt
        d[2] += float(recip) - staged_rcp
        if self._import_h_points >= self.cfg.batch_size:
            self._flush_import_centroids_locked()

    def _import_set_locked(self, key, registers, engine_id=None):
        # the mesh engine is hll-only (constructor guard): a wire row
        # tagged with another engine must reject THIS metric, matching
        # the single-device engine's belt check
        if engine_id is not None and engine_id != "hll":
            raise ValueError(
                f"set sketch engine mismatch: payload {engine_id!r}, "
                "mesh banks run 'hll'")
        slot = self.set_keys.lookup(key, GLOBAL_ONLY)
        if slot == FOLD_SLOT:
            slot = self._fold_import_slot(self.set_keys, key)
        if slot < 0:
            return
        self._import_sets.append(
            (slot, np.asarray(registers, np.uint8)))
        if len(self._import_sets) >= self._set_rows_chunk:
            self._flush_import_sets_locked()

    # import_counter / import_gauge: the base class's host accumulation
    # works unchanged; only the landing (in _flush_import_scalars) moves
    # onto the routed scalar kernels.

    def _flush_import_centroids(self):
        self._flush_import_centroids_locked()

    def _flush_import_centroids_locked(self):
        """One landing of the staged digests, stamped `import.land`
        with its two halves under it: `import.land.stage`, the host's
        (rounds by slot, concatenation, padding to the batch width, the
        hot-slot sidestep, route_batch), then `import.land.dispatch`,
        the calls of the routed ingest and of merge_histo_scalars."""
        if not self._import_centroids:
            return
        t0 = time.monotonic_ns()
        batches, deltas = self._stage_landing()
        t1 = time.monotonic_ns()
        pads = self._pads_for("counter", "gauge", "set")
        for routed in batches:
            self.me.ingest(*routed, *pads)
        for routed in deltas:
            self.me.merge_histo_scalars(*routed)
        self._mesh_import_dispatches += len(batches) + len(deltas)
        if self.land_stamps is not None:
            t2 = time.monotonic_ns()
            self.land_stamps.add("import.land", t0, t2)
            self.land_stamps.add("import.land.stage", t0, t1)
            self.land_stamps.add("import.land.dispatch", t1, t2)

    def _stage_landing(self) -> tuple:
        """Take the staged digests and their exact-stats deltas and
        route them: (ingest batches, merge_histo_scalars batches), each
        the operands of one program, in dispatch order."""
        items, self._import_centroids = self._import_centroids, []
        self._mesh_import_points += self._import_h_points
        self._import_h_points = 0
        # schedule landing so each slot contributes at most one item
        # (<= buffer_depth points) per scatter round: the recip scatter
        # then sees the staged points verbatim and the exact-stats
        # deltas cancel to rounding level
        by_slot: dict = {}
        for item in items:
            by_slot.setdefault(item[0], []).append(item)
        batches = []
        while by_slot:
            self._mesh_import_rounds += 1
            round_items = []
            for slot in list(by_slot):
                round_items.append(by_slot[slot].pop(0))
                if not by_slot[slot]:
                    del by_slot[slot]
            slots, vals, wts = [], [], []
            for slot, means, weights, vmin, vmax in round_items:
                n = len(means) + 2
                slots.append(np.full(n, slot, np.int32))
                vals.append(np.concatenate(
                    [means, [vmin, vmax]]).astype(np.float32))
                # exact extremes as zero-weight samples: they update
                # the min/max scatter, add nothing to sum/count/recip
                wts.append(np.concatenate(
                    [weights, [0.0, 0.0]]).astype(np.float32))
            fs = np.concatenate(slots)
            fv = np.concatenate(vals)
            fw = np.concatenate(wts)
            for cs, (cv, cw) in self._batched(fs, fv, fw):
                batches.append(self._stage_histos(cs, cv, cw))
        # exact-stats correction deltas (see import_histogram)
        deltas, self._import_h_deltas = self._import_h_deltas, {}
        routed_deltas = []
        if deltas:
            dslots = np.fromiter(deltas.keys(), np.int32, len(deltas))
            arr = np.array(list(deltas.values()), np.float64)
            per_shard = self.me.histogram_slots // self.S
            inf = np.float32(np.inf)
            for cs, (dsum, dcnt, drcp) in self._batched(
                    dslots, arr[:, 0].astype(np.float32),
                    arr[:, 1].astype(np.float32),
                    arr[:, 2].astype(np.float32)):
                rs, rsum, rcnt, rrcp = self._route(
                    per_shard, cs, dsum, dcnt, drcp)
                routed_deltas.append(
                    (rs, np.full_like(rsum, inf),
                     np.full_like(rsum, -inf), rsum, rcnt, rrcp))
        return batches, routed_deltas

    def _flush_import_sets(self):
        self._flush_import_sets_locked()

    def _flush_import_sets_locked(self):
        if not self._import_sets:
            return
        items, self._import_sets = self._import_sets, []
        m = 1 << self.cfg.hll_precision
        per_shard = self.me.set_slots // self.S
        nrow = self._set_rows_chunk
        for i in range(0, len(items), nrow):
            chunk = items[i:i + nrow]
            slots = np.array([s for s, _ in chunk], np.int32)
            regs = np.stack([r for _, r in chunk])
            out_s = np.full((self.me.D, self.S * nrow), -1, np.int32)
            out_r = np.zeros((self.me.D, self.S * nrow, m), np.uint8)
            shard = slots // per_shard
            order = np.argsort(shard, kind="stable")
            starts = np.searchsorted(shard[order], np.arange(self.S))
            pos = np.arange(len(order)) - starts[shard[order]]
            dest = shard[order] * nrow + pos
            out_s[0, dest] = slots[order] % per_shard
            out_r[0, dest] = regs[order]
            self.me.merge_set_rows(out_s, out_r)
            self._mesh_import_dispatches += 1

    def _batched(self, flat_slots, *flat_cols):
        """Yield (slots, cols) batch_size-padded chunks of flat
        per-sample arrays (-1 slot padding) — the shared pad idiom of
        every import landing path, at the ingest kernels' fixed shape."""
        n = self.cfg.batch_size
        for i in range(0, len(flat_slots), n):
            seg = slice(i, min(len(flat_slots), i + n))
            m = seg.stop - seg.start
            cs = np.full(n, -1, np.int32)
            cs[:m] = flat_slots[seg]
            cols = []
            for c in flat_cols:
                buf = np.zeros(n, c.dtype)
                buf[:m] = c[seg]
                cols.append(buf)
            yield cs, cols

    def _flush_import_scalars(self):
        if self._import_counter_acc:
            acc, self._import_counter_acc = self._import_counter_acc, {}
            slots = np.fromiter(acc.keys(), np.int32, len(acc))
            vals = np.fromiter(acc.values(), np.float32, len(acc))
            for cs, (cv,) in self._batched(slots, vals):
                rs, rv, rw = self._route(
                    self.me.counter_slots // self.S, cs, cv,
                    np.ones(len(cs), np.float32))
                self.me.ingest(*self._pads_for("histo"), rs, rv, rw,
                               *self._pads_for("gauge", "set"))
                self._mesh_import_dispatches += 1
        if self._import_gauge_acc:
            acc, self._import_gauge_acc = self._import_gauge_acc, {}
            slots = np.fromiter(acc.keys(), np.int32, len(acc))
            vals = np.fromiter(acc.values(), np.float32, len(acc))
            for cs, (cv,) in self._batched(slots, vals):
                n = len(cs)
                seqs = np.arange(1, n + 1, dtype=np.int32) \
                    + self._gauge_seq
                self._gauge_seq += n
                gs, gv, gq = self._route(
                    self.me.gauge_slots // self.S, cs, cv, seqs)
                self.me.ingest(*self._pads_for("histo", "counter"),
                               gs, gv, gq, *self._pads_for("set"))
                self._mesh_import_dispatches += 1

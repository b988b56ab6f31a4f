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

The import stage is columnar: three point columns (slot, value,
weight) of the routed ingest's batch width, and beside them the slot
and point count of every staged digest. A request's digests are staged
by one vectorised pass over the decoded batch's flat centroid columns
(_stage_digests; a single forwarded digest is a batch of one), cut on
a digest's edge where the next one would not fit: a landing is one
full batch, one call of the routed ingest a scatter round. What the
staged f32 points will add to a row's sum, count and reciprocal sum is
worked out in the same pass, and the exact-minus-staged difference
accumulates a slot in host f64 until the flush, which folds it in with
merge_histo_scalars once. A dispatch of the routed ingest feeds one
bank; the all-padding operands of the other three are put on the
devices once (_pad) and handed over again every call.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..models.pipeline import (AggregationEngine, EngineConfig,
                               _f32_pair, _precluster_k1, _spans)
from .interner import ShardedKeyInterner
from .mesh import MeshEngine, make_mesh

# What an interval's import landings did, beside the base engine's
# tally: engine attributes `_<name>`, added to under the lock, noted in
# _last_flush_info at the flush and reset. Points staged (the two
# extremes riders of each digest among them), programs dispatched for
# imports (routed ingest, scalar and set-row merges), scatter rounds,
# slots _stage_histos pre-clustered on the host (the landing's
# schedule keeps it 0), digests the columnar pass staged and, of those,
# the ones too wide for a row's buffer that it first pre-clustered by
# themselves.
_MESH_TALLY = ("mesh_import_points", "mesh_import_dispatches",
               "mesh_import_rounds", "mesh_import_preclustered",
               "mesh_import_staged", "mesh_import_staged_fallback")

# The server's self-metric (veneur.<name>_total) for each count of an
# interval that the flush notes in _last_flush_info.
MESH_TELEMETRY = {"mesh_import_points": "import.mesh.points",
                  "mesh_import_dispatches": "import.mesh.dispatches",
                  "mesh_import_rounds": "import.mesh.rounds",
                  "mesh_import_preclustered": "import.mesh.preclustered",
                  "mesh_import_staged": "import.mesh.staged",
                  "mesh_import_staged_fallback":
                      "import.mesh.staged_fallback",
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
        # the import stage (module docstring): point columns of one
        # batch, the staged digests' slots and point counts (a digest
        # is two points at least), and the exact-stats deltas a slot
        n = cfg.batch_size
        self._h_slots = np.full(n, -1, np.int32)
        self._h_vals = np.zeros(n, np.float32)
        self._h_wts = np.zeros(n, np.float32)
        self._h_dslots = np.zeros(n // 2, np.int32)
        self._h_dpoints = np.zeros(n // 2, np.int64)
        self._h_n = self._h_nd = 0
        self._h_deltas = np.zeros((3, self.me.histogram_slots), np.float64)

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

    def _route(self, per_shard, slots, *arrays, fill=0.0, segment=None):
        """`slots` and their columns in the segment layout, a shard's
        segment `segment` wide: as wide as the batch unless the caller
        has seen to it that no shard takes more."""
        out = self.me.route_batch(
            slots, *arrays, slots_per_shard=per_shard,
            n_per_segment=segment or len(np.asarray(slots)), fill=fill)
        assert out[-1] == 0  # no segment can overflow
        return out[:-1]

    def _pad(self, dtype=np.float32, fill=0.0):
        # all-padding batches are constant: each is built once, put on
        # the devices under the ingest program's input sharding and
        # shared (the program donates its banks, never a batch)
        key = (np.dtype(dtype).name, fill)
        cached = self._pad_cache.get(key)
        if cached is None:
            shape = (self.me.D, self.S * self.cfg.batch_size)
            cached = jax.device_put(np.full(shape, fill, dtype),
                                    self.me.batch_sharding)
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

    def ingest_gauge_batch(self, slots, values, count=None, mark=None,
                           order=None):
        def apply(n):
            gs, gv, gq = self._route(
                self.me.gauge_slots // self.S,
                *self._gauge_rows(slots, values, n, order))
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
    # run under the lock: the stage of a block of digests (the base
    # class looks the rows up, a request's block or import_histogram's
    # block of one) and the `_locked` half of a set.

    def _stage_digests(self, slots, starts, lens, stats, means, weights):
        """Stage digests into the point columns, landing a batch
        wherever the next digest would not fit. A digest an entry of
        the columns: `slots` its row in the bank, its centroids
        `means[start:start + len]`, `weights[start:start + len]`, and
        `stats[:, j]` its min, max, sum, count and reciprocal sum."""
        vmin, vmax = stats[0], stats[1]
        # a row's buffer takes one digest a scatter round, its two
        # extremes riders with it: a wider digest is pre-clustered to
        # that width first, a key at a time. The hot-slot sidestep of
        # _stage_histos (whose recip is approximate) then never runs.
        # (Nor can the stage take a digest wider than a batch.)
        cap = min(self.cfg.buffer_depth, self.cfg.batch_size) - 2
        wide = np.flatnonzero(lens > cap)
        self._mesh_import_staged += len(slots)
        self._mesh_import_staged_fallback += len(wide)
        if len(wide):
            starts, lens = starts.copy(), lens.copy()
            cols_m = [np.asarray(means, np.float64)]
            cols_w = [np.asarray(weights, np.float64)]
            end = len(cols_m[0])
            for i in wide.tolist():
                seg = slice(starts[i], starts[i] + lens[i])
                cm, cw = _precluster_k1(cols_m[0][seg], cols_w[0][seg], cap)
                cols_m.append(cm)
                cols_w.append(cw)
                starts[i], lens[i] = end, len(cm)
                end += len(cm)
            means, weights = np.concatenate(cols_m), np.concatenate(cols_w)
        n, total = len(slots), int(lens.sum())
        # the digests' centroids side by side, `of` the digest of each
        of = np.repeat(np.arange(n), lens)
        take = _spans(starts, lens)
        m64 = np.asarray(means)[take].astype(np.float64)
        # a centroid mean comes out of a cumsum difference and can
        # sit a few ulp outside the digest's exact [vmin, vmax];
        # staged as a sample it would then move this slot's
        # extremes off the forwarded exact ones
        m32 = np.clip(m64, vmin[of], vmax[of]).astype(np.float32)
        w32 = np.asarray(weights)[take].astype(np.float32)
        # The staged centroids flow through the ingest scatter, so
        # they CONTRIBUTE approximate vsum/count/recip; accumulate
        # the exact-minus-staged delta per slot (f64 host math) for
        # the flush to fold in via merge_histo_scalars — making the
        # flushed sum/count/hmean match the forwarded exact values,
        # like the single-device merge_scalars path. Replicate the
        # device's f32 per-term arithmetic so the delta cancels the
        # staged contribution to rounding level
        rcp = np.zeros(total, np.float32)
        np.divide(w32, m32, out=rcp, where=m32 != 0)
        for row, terms in enumerate((m32 * w32, w32, rcp)):
            np.add.at(self._h_deltas[row], slots,
                      stats[2 + row] - np.bincount(of, terms, n))
        # the points: a digest's centroids, then its exact extremes as
        # zero-weight samples: they update the min/max scatter, add
        # nothing to sum/count/recip
        points = lens + 2
        last = np.cumsum(points)
        p_vals = np.empty(total + 2 * n, np.float32)
        p_wts = np.zeros(total + 2 * n, np.float32)
        at = np.arange(total) + 2 * of
        p_vals[at], p_wts[at] = m32, w32
        p_vals[last - 2], p_vals[last - 1] = vmin, vmax
        p_slots = np.repeat(slots, points)
        # into the stage, a batch at a time, cut on a digest's edge
        i = done = 0
        while i < n:
            room = self.cfg.batch_size - self._h_n
            j = int(np.searchsorted(last, done + room, side="right"))
            if j > i:
                upto = int(last[j - 1])
                dst = slice(self._h_n, self._h_n + upto - done)
                self._h_slots[dst] = p_slots[done:upto]
                self._h_vals[dst] = p_vals[done:upto]
                self._h_wts[dst] = p_wts[done:upto]
                dst = slice(self._h_nd, self._h_nd + j - i)
                self._h_dslots[dst] = slots[i:j]
                self._h_dpoints[dst] = points[i:j]
                self._h_n += upto - done
                self._h_nd += j - i
                i, done = j, upto
            if i < n:
                self._land_stage_locked()

    def _import_set_locked(self, key, registers, engine_id=None):
        # the mesh engine is hll-only (constructor guard): a wire row
        # tagged with another engine must reject THIS metric, matching
        # the single-device engine's belt check
        if engine_id is not None and engine_id != "hll":
            raise ValueError(
                f"set sketch engine mismatch: payload {engine_id!r}, "
                "mesh banks run 'hll'")
        slot = self._import_slot(self.set_keys, key)
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
        """The flush's drain of the stage: what is left lands, and the
        interval's exact-stats deltas are folded in."""
        self._land_stage_locked(fold=True)

    def _land_stage_locked(self, fold=False):
        """One landing of the staged points, stamped `import.land`
        with its two halves under it: `import.land.stage`, the host's
        (rounds by slot, the hot-slot sidestep, route_batch), then
        `import.land.dispatch`, the calls of the routed ingest and,
        with `fold`, of merge_histo_scalars."""
        if not self._h_n and not (fold and self._h_deltas.any()):
            return
        t0 = time.monotonic_ns()
        batches = self._stage_landing()
        deltas = self._stage_deltas() if fold else []
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

    def _stage_landing(self) -> list:
        """Take the staged points and route them: the operands of each
        call of the routed ingest, in dispatch order."""
        n, nd = self._h_n, self._h_nd
        if not n:
            return []
        self._mesh_import_points += n
        # schedule the landing so each slot contributes at most one
        # digest (<= buffer_depth points) per scatter round: the recip
        # scatter then sees the staged points verbatim and the
        # exact-stats deltas cancel to rounding level. A digest's round
        # is its rank among the stage's digests of its slot
        dslots = self._h_dslots[:nd]
        order = np.argsort(dslots, kind="stable")
        run = np.flatnonzero(np.diff(dslots[order], prepend=-1))
        rank = np.empty(nd, np.int64)
        rank[order] = np.arange(nd) - np.repeat(
            run, np.diff(run, append=nd))
        rounds = int(rank.max()) + 1
        self._mesh_import_rounds += rounds
        # a round is the stage with the other rounds' points masked
        # out: route_batch packs the valid ones, in their order
        p_rank = np.full(len(self._h_slots), -1, np.int64)
        p_rank[:n] = np.repeat(rank, self._h_dpoints[:nd])
        batches = [
            self._stage_histos(np.where(p_rank == r, self._h_slots, -1),
                               self._h_vals, self._h_wts)
            for r in range(rounds)]
        self._h_slots[:n] = -1
        self._h_n = self._h_nd = 0
        return batches

    def _stage_deltas(self) -> list:
        """Take the interval's exact-stats deltas and route them: the
        operands of each call of merge_histo_scalars, a shard's segment
        filled before a second call is made."""
        slots = np.flatnonzero(self._h_deltas.any(axis=0)).astype(np.int32)
        if not len(slots):
            return []
        dsum, dcnt, drcp = self._h_deltas[:, slots].astype(np.float32)
        self._h_deltas[:, slots] = 0.0
        n = self.cfg.batch_size
        per_shard = self.me.histogram_slots // self.S
        shard = slots // per_shard
        call = (np.arange(len(slots)) - np.searchsorted(shard, shard)) // n
        inf = np.float32(np.inf)
        routed = []
        for c in range(int(call.max()) + 1):
            take = np.flatnonzero(call == c)
            rs, rsum, rcnt, rrcp = self._route(
                per_shard, slots[take], dsum[take], dcnt[take], drcp[take],
                segment=n)
            routed.append((rs, np.full_like(rsum, inf),
                           np.full_like(rsum, -inf), rsum, rcnt, rrcp))
        return routed

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
            hi, lo = _f32_pair(np.fromiter(acc.values(), np.float64,
                                           len(acc)))
            # a total past 2^24 in two batches, never both halves of a
            # key in one: a batch's delta is one f32 sum a slot
            for vals in (hi, lo) if lo.any() else (hi,):
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

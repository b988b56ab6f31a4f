"""AggregationEngine — banks + staging + the jitted flush program.

This is the TPU-native replacement for the reference's hot path from
Worker.ProcessMetric down through Server.Flush (worker.go, flusher.go):

  ingest thread:  parsed UDPMetric -> host staging buffers (numpy, fixed
                  batch shape) -> one scatter program per full batch
  flush tick:     ONE fused XLA program over all four banks (compress +
                  quantiles + aggregates + HLL estimate + scalar
                  finalization) -> one device_get of its outputs ->
                  host assembles a columnar MetricFrame from the
                  slot->key map

Interval semantics match Worker.Flush's map swap: flush takes the current
immutable device arrays (JAX arrays are persistent, so the "swap" is just
rebinding fresh banks) and ingest continues immediately; the merge program
runs on the snapshot — double buffering for free.

Scope routing (flusher.go semantics):
  * no forwarding configured -> everything flushes locally in full.
  * forwarding on: mixed-scope histograms/timers emit the configured local
    aggregates and forward their digest (percentiles are computed globally);
    mixed sets forward the sketch; `veneurlocalonly` keys flush fully
    locally; `veneurglobalonly` keys only forward. Counters/gauges stay
    local unless global-only.
"""

from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import sketches
from ..ingest.parser import (
    GLOBAL_ONLY, LOCAL_ONLY, MetricKey, UDPMetric)
from ..metrics import InterMetric, MetricFrame, MetricType
from ..ops import scalar
from ..utils import hashing
from .worker import FOLD_SLOT, KeyInterner


# Widest per-slot centroid pile the import path will hand to one device
# program; wider (untrusted) forwarded digests are pre-clustered in
# chunks of this size first.
_IMPORT_W_CAP = 4096

# Stage forwarded digests until this many centroids (or digests) are
# pending, then land them in one batched round. Bigger piles = fewer
# device dispatches AND higher merge fidelity (one k1 clustering over
# more of the interval's data — measured ~0.3pp closer to the Go oracle
# at p99 than landing every 512 digests); the bounds cap host staging
# memory at ~8MB of float32 centroids.
_IMPORT_STAGE_CENTROIDS = 1 << 20
_IMPORT_STAGE_DIGESTS = 8192

# Work-set sizes of a clustered landing's bank-side device work,
# ascending: a landing that touches S rows gathers the smallest set
# that holds them, compresses, fills and compresses that [R, .] part
# and scatters it back; the rows it does not touch are left alone. A
# stage holds at most _IMPORT_STAGE_DIGESTS digests, so no more
# distinct rows: the top set serves every landing of the default
# stage. A bank no larger than a set (most tests, the history tier's
# scratch engines) and a landing over the top set (only a raised
# digest bound could bring one) take the whole-bank passes. PERF.md
# 5f has the sizes tried on the v5e.
_IMPORT_LAND_ROWS = (1024, _IMPORT_STAGE_DIGESTS)

# Lane widths of a clustered landing's [R, L] matrix, ascending: the
# piles are padded (weight 0) to the narrowest step that holds the
# widest, and to the pre-cluster cap above the last step (_land_lanes:
# no pile is wider once the pre-cluster loop has cut it). With the row
# sets above these are all the shapes the cluster program is ever
# handed, so warmup() compiles every one. Powers of two: a digest of
# compression 100 holds 118-130 centroids (128 or 256 lanes), a hot
# key behind 32 senders 512 to 2,048.
_IMPORT_LAND_LANES = (128, 256, 512, 1024, 2048)

# Rows of one pre-cluster dispatch, [_IMPORT_CHUNK_ROWS, cap]: a full
# centroid stage cut into chunks of the cap is this many.
_IMPORT_CHUNK_ROWS = _IMPORT_STAGE_CENTROIDS // _IMPORT_W_CAP

# Forwarded set rows staged before they land, and the rows of that
# landing's one program (a flush-time tail is padded to it).
_IMPORT_STAGE_SETS = 256

# Imported counters and gauges land once a flush, padded to this many
# entries or, past it, to the bank's own slot count (_scalar_rows).
_IMPORT_SCALAR_ROWS = 1024

# Flight-recorder phases one import landing stamps (into
# engine.land_stamps, when the server armed it): the whole landing;
# host piles + the [R, L] fill; the cluster program's dispatch + fetch.
# The gather/compress/fill/scatter dispatches (or the whole-bank
# compress and merge) and merge_scalars are the rest of `import.land`.
# The mesh engine's landing has the two children of its own: the host
# half up to `route_batch`, and the calls of its routed SPMD programs.
LAND_PHASES = ("import.land", "import.land.stage", "import.land.cluster",
               "import.land.dispatch")
# ... and the three one import request stamps into the same log, inside
# the worker's `import.apply` run that holds it (import_list): the
# request's protobuf walk outside the lock; the wait for the engine's
# lock; the lock hold, staging and tally, the landings that fall in
# the batch inside it. IMPORT_PHASES is every name the log holds.
APPLY_PHASES = ("import.apply.decode", "import.apply.lock_wait",
                "import.apply.stage")
IMPORT_PHASES = LAND_PHASES + APPLY_PHASES

# The interval's import tally: engine attributes `_<name>`, added to
# under the lock (a landing returns its own share, which the flush
# adds for its retired stage), noted in _last_flush_info at the flush
# and reset. APPLY_CPU_TALLY is the applying thread's CPU nanoseconds
# (time.thread_time_ns) over the stretches `import.apply.decode` and
# `import.apply.stage` time on the wall clock; 0 with no stamp log.
APPLY_CPU_TALLY = ("import_decode_cpu_ns", "import_stage_cpu_ns")
# DECODE_TALLY is what wire.BatchDecoder.decode counts a request, in
# its order: sketches read from the request's bytes by the native
# pass, sketches read from parsed messages in Python (the two add up
# to import_metrics), and the hits and misses of the dictionary that
# finds a natively read sketch's key by its bytes.
DECODE_TALLY = ("import_decode_native", "import_decode_fallback",
                "import_decode_key_hits", "import_decode_key_misses")
# STAGE_TALLY is how the interval's digests reached the stage: in a
# request's block of columns (import_list), or as a block of one
# through a per-metric entry point (import_histogram).
STAGE_TALLY = ("import_digests_block", "import_digests_single")
_IMPORT_TALLY = ("import_batches", "import_metrics", "import_land_rows",
                 "import_land_bank", "import_land_lanes",
                 "import_land_lanes_filled", "import_land_prechunked",
                 ) + APPLY_CPU_TALLY + DECODE_TALLY + STAGE_TALLY


def _open_clocks() -> tuple:
    """(monotonic_ns, the calling thread's CPU ns) where a stretch
    begins: the wall clock first, and last in _close_clocks, so the
    CPU reading's window lies inside the wall reading's and a thread
    that never left the CPU reads no more CPU than wall time."""
    t = time.monotonic_ns()
    return t, time.thread_time_ns()


def _close_clocks() -> tuple:
    c = time.thread_time_ns()
    return time.monotonic_ns(), c


def _no_clock() -> tuple:
    return 0, 0


def _precluster_k1(v, w, n_points, keep_extremes=False):
    """Sort one hot slot's (value, weight) samples and cluster them into
    <= n_points weighted points over k1-spaced (tail-dense) bucket edges
    — the shared core of both engines' hot-slot sidesteps. Weighted sum
    and count are exactly preserved; with keep_extremes the true min and
    max survive as singleton points (for paths with no separate exact-
    stats merge). Returns (means f64[n], weights f64[n])."""
    order = np.argsort(v, kind="stable")
    v, w = v[order], w[order]
    if keep_extremes:
        if len(v) <= 2 or n_points <= 3:
            return v, w  # nothing (or no room) to cluster between ends
        nb = n_points - 2
        qi = (np.sin(np.pi * np.arange(nb + 1) / nb - np.pi / 2)
              + 1.0) / 2.0
        edges = np.unique(
            np.floor(1 + qi * (len(v) - 2)).astype(np.int64))
        edges = edges[(edges >= 1) & (edges < len(v) - 1)]
        if edges.size == 0:
            edges = np.array([1], np.int64)
        wsum = np.add.reduceat(w[1:-1], edges - 1)
        vsum = np.add.reduceat((v * w)[1:-1], edges - 1)
        keep = wsum > 0
        return (np.concatenate([[v[0]], vsum[keep] / wsum[keep],
                                [v[-1]]]),
                np.concatenate([[w[0]], wsum[keep], [w[-1]]]))
    nb = max(1, n_points)
    qi = (np.sin(np.pi * np.arange(nb + 1) / nb - np.pi / 2) + 1.0) / 2.0
    edges = np.unique(np.floor(qi * len(v)).astype(np.int64))
    edges = edges[edges < len(v)]
    wsum = np.add.reduceat(w, edges)
    vsum = np.add.reduceat(v * w, edges)
    keep = wsum > 0
    return vsum[keep] / wsum[keep], wsum[keep]


# ---------------- compiled flush programs (shared across engines) --------
#
# The flush is ONE XLA dispatch, not a chain of compress -> quantile ->
# aggregates -> estimate jits, and its inputs and outputs are COMMITTED
# to a concrete device (out_shardings pinned): the bank lineage then
# never changes sharding between programs, so no executable retraces
# against an uncommitted twin of the arrays it already compiled for.
# The factories are lru_cached on the static config so every engine with
# the same shape shares one executable and one compile;
# release_executables() drops them all.

@functools.lru_cache(maxsize=None)
def _fresh_banks_executable(device, heng, seng, histogram_slots,
                            counter_slots, gauge_slots, set_slots):
    """One jitted program materializing a full set of fresh interval banks
    on `device` — the Worker.Flush map-swap costs one dispatch, not ~15
    host-built zero arrays. `heng`/`seng` are the selected sketch
    engines (frozen dataclasses — hashable cache keys carrying the
    static shape params)."""
    sds = jax.sharding.SingleDeviceSharding(device)

    def make():
        return (heng.init(histogram_slots),
                scalar.init_counters(counter_slots),
                scalar.init_gauges(gauge_slots),
                seng.init(set_slots))

    return jax.jit(make, out_shardings=sds)


@functools.lru_cache(maxsize=None)
def _ingest_executables(device, heng, seng):
    """Committed-output builds of the four ingest scatter kernels.

    The module-level ops (tdigest.add_batch & co) are plain jits whose
    outputs are UNCOMMITTED; pinning out_shardings here keeps the whole
    bank lineage committed from _fresh_banks onward, so every ingest
    batch and the following flush dispatch the executables compiled
    for exactly these arrays. Every sketch op routes through the
    engine objects — the registry boundary (vlint SK01)."""
    sds = jax.sharding.SingleDeviceSharding(device)

    jit = functools.partial(jax.jit, donate_argnums=(0,),
                            out_shardings=sds)

    def add_batch_impl(bank, overflow, slots, values, weights):
        # (the name is the program's in a profile: jit_add_batch_impl)
        # the landing keeps its own overflow counter (i32[2], see
        # AggregationEngine._overflow) on the device, so counting
        # costs no dispatch and no sync; not donated: eight bytes, and
        # an interval's first landing reads the engine's shared zero
        if hasattr(heng, "add_batch_counted_impl"):
            bank, counted = heng.add_batch_counted_impl(
                bank, slots, values, weights)
            return bank, overflow + counted
        return heng.add_batch_impl(bank, slots, values, weights), overflow

    return {
        "histo": jit(add_batch_impl),
        "counter": jit(scalar.counter_add.__wrapped__),
        "gauge": jit(scalar.gauge_set.__wrapped__),
        "set": jit(seng.insert_impl),
        # hot-slot sidestep programs (see _land_hot; `compress` at
        # the work set's shape or the bank's)
        "compress": jit(heng.compress_impl),
        "merge_centroids": jit(heng.merge_centroids_impl),
        "merge_scalars": jit(heng.merge_scalars_impl),
    }


def _flush_program_body(heng, seng, fwd_out, agg_emit):
    """The flush computation itself — compress + quantiles + the
    configured aggregates + counter/gauge/set finalization — as a
    jit-composable closure over (hb, cb, gb, sb, qs). Shared by the
    full-bank executable (_flush_executable) and the incremental
    dirty-slot executable (_inc_flush_executable), so both paths run
    the IDENTICAL math and differ only in which rows they see.

    Output contract (all f32 unless noted):
      q        [K, P']      quantile matrix (P' includes a median column
                            when configured)
      aggcols  [K, A]       one column per configured aggregate, in
                            `agg_emit` order; `count`/`sum` columns carry
                            the 2Sum hi term only
      lo_count/lo_sum [K]   the matching lo terms (only when configured):
                            exact value = f64(hi) + f64(lo) on host
      cnt      [K]          folded count for liveness (only when `count`
                            is NOT a configured aggregate)
      c_hi/c_lo [Kc], g_value [Kg], g_seq i32[Kg], s_est [Ks]
      h_* / s_regs          raw forward-export state (fwd_out only)
    """
    def program(hb, cb, gb, sb, qs):
        hb = heng.compress_impl(hb)
        agg = heng.aggregates_impl(hb)
        q = heng.quantile_impl(hb, qs)
        out = {
            "c_hi": cb.hi, "c_lo": cb.lo,
            "g_value": gb.value, "g_seq": gb.seq,
        }
        # set estimate: HLL emits the finished per-slot estimate; ULL
        # emits its device-side sufficient statistic and the host half
        # of estimate (estimate_finalize) finishes it after the fetch
        out.update(seng.estimate_device(sb))
        cols = []
        for a in agg_emit:
            if a == "count":
                out["lo_count"] = hb.count_lo
                cols.append(hb.count)
            elif a == "sum":
                out["lo_sum"] = hb.vsum_lo
                cols.append(hb.vsum)
            else:
                cols.append(agg[a])
        out["q"] = q
        if cols:
            out["aggcols"] = jnp.stack(cols, axis=1)
        if "count" not in agg_emit:
            out["cnt"] = agg["count"]
        if fwd_out:
            out.update(heng.forward_leaves(hb))
            out["s_regs"] = sb.registers
        return out

    return program


@functools.lru_cache(maxsize=None)
def _flush_executable(device, heng, seng, fwd_out, agg_emit):
    """The fused interval-flush program over the FULL banks: ONE XLA
    call over every slot (see _flush_program_body for the output
    contract). The incremental dirty-slot path (_inc_flush_executable)
    is the serving default when most slots are cold; this full build
    remains the oracle, the warmup/baseline program, and the serving
    path above the dirty-fraction threshold."""
    sds = jax.sharding.SingleDeviceSharding(device)
    program = _flush_program_body(heng, seng, fwd_out, agg_emit)

    # Donation audit (ISSUE 3 satellite): an argument is donated iff
    # EVERY one of its leaves aliases an output of identical shape —
    # partial donation is what made every compile warn "Some donated
    # buffers were not usable" since r3. Counter and gauge banks always
    # qualify (c_hi/c_lo, g_value/g_seq); nothing else does in the
    # local-only build (the t-digest/HLL state reduces to [K, P']/[K]
    # outputs).
    if not fwd_out:
        return jax.jit(program, donate_argnums=(1, 2),
                       out_shardings=sds)

    # fwd_out: the histo bank's item matrices and eight scalar leaves
    # are echoed verbatim (h_*), as are the set registers (s_regs) —
    # real aliasing worth ~2 x [K, C] f32 of transient memory per flush
    # at 100k slots. The engine's donation_split names the leaves with
    # same-shaped outputs; the rest (sample buffers, level counters)
    # would bring the partial-donation warning back, so the bank is
    # split into a donated core and an un-donated remainder behind a
    # signature-preserving wrapper (engine.reassemble).
    split = heng.donation_split()
    if split is None:
        return jax.jit(program, donate_argnums=(1, 2),
                       out_shardings=sds)
    core_names, buf_names = split

    def flat(core, bufs, cb, gb, sb, qs):
        hb = heng.reassemble(core, bufs)
        return program(hb, cb, gb, sb, qs)

    jitted = jax.jit(flat, donate_argnums=(0, 2, 3, 4),
                     out_shardings=sds)

    def call(hb, cb, gb, sb, qs):
        core = tuple(getattr(hb, n) for n in core_names)
        bufs = tuple(getattr(hb, n) for n in buf_names)
        return jitted(core, bufs, cb, gb, sb, qs)

    return call


def _inc_bucket(n: int, num_slots: int) -> int:
    """Padded work-set width for `n` dirty slots of a `num_slots` bank:
    powers of two up to 4096 (one executable per bucket, compiled once
    and cached), then 4096-aligned (tight enough that the exec-time
    ratio tracks the touched ratio at 100k — a pure power-of-two ladder
    would pad 10% dirty to 16% of the bank). Never below 64 (tiny
    buckets would mint executables per handful of slots) and never
    above the bank itself."""
    b = 64
    while b < n and b < 4096:
        b *= 2
    if n > 4096:
        b = -(-n // 4096) * 4096
    return min(b, num_slots)


def _pad_dirty_ids(ids, num_slots: int):
    """One bank's dirty-id vector padded to its _inc_bucket width with
    index 0 (padding rows duplicate row 0's compute; no row map points
    at them, see _row_maps, so the flush's assembly never reads one)."""
    b = _inc_bucket(max(ids.size, 1), num_slots)
    pad = np.zeros(b, np.int32)
    pad[:ids.size] = ids
    return pad


@functools.lru_cache(maxsize=None)
def _inc_flush_executable(device, heng, seng, fwd_out, agg_emit):
    """The INCREMENTAL interval-flush program (ISSUE 11 tentpole):
    gather only the dirty piles into a compact [D, ·] work set, run the
    SAME flush body (_flush_program_body) over that slice, and return
    compact [D, ·] outputs, which stay compact on the host: the
    flush's assembly reads them through a row map that sends a dirty
    slot to its row and every cold slot to the cached empty-bank
    baseline row (_flush_device_incremental). Cold piles are
    fresh-init by construction (the swap re-zeroes every row; restore
    re-marks restored rows dirty), and the flush body maps a fresh row
    to the baseline row bit-for-bit, so skipping cold rows is exact —
    the oracle suite pins incremental == full per engine backend.

    `ih/ic/ig/is_` are per-bank dirty-slot index vectors, padded to
    their _inc_bucket width with index 0 (a padding row duplicates row
    0's compute; no row map points past the true-D prefix, so the
    duplicate work is dropped). One executable per (engine pair,
    bucket-shape) combination — jit retraces per input shape under the
    one cached wrapper.

    No donation: the gathered outputs cannot alias the full-bank
    inputs (different shapes), and requesting donation anyway would
    re-introduce the "donated buffers were not usable" warning the
    ISSUE 3 audit pins at zero."""
    sds = jax.sharding.SingleDeviceSharding(device)
    program = _flush_program_body(heng, seng, fwd_out, agg_emit)

    def gather(bank, idx):
        return jax.tree_util.tree_map(lambda leaf: leaf[idx], bank)

    def inc(hb, cb, gb, sb, qs, ih, ic, ig, is_):
        return program(gather(hb, ih), gather(cb, ic), gather(gb, ig),
                       gather(sb, is_), qs)

    return jax.jit(inc, out_shardings=sds)


@functools.lru_cache(maxsize=None)
def _flush_baseline_cached(device, heng, seng, fwd_out, agg_emit, qs):
    """Empty-flush baseline rows (see _flush_baseline_rows), cached at
    module level so every engine with the same sketch pair + flush
    config shares one K=1 compile. The rows are read-only: a full
    resync's export hands out cold set rows as views of them, so a
    writer must raise, not corrupt every engine's cold rows."""
    from ..ops import scalar as _scalar
    body = _flush_program_body(heng, seng, fwd_out, agg_emit)
    fresh = jax.device_put(
        (heng.init(1), _scalar.init_counters(1),
         _scalar.init_gauges(1), seng.init(1)), device)
    host = jax.device_get(
        jax.jit(body)(*fresh, np.asarray(qs, np.float32)))
    if "s_est" in host or "s_counts" in host:
        seng.estimate_finalize(host)
    rows = {k: np.asarray(v)[0]
            for k, v in host.items() if np.asarray(v).ndim}
    for row in rows.values():
        if isinstance(row, np.ndarray):     # [1] leaves give scalars
            row.setflags(write=False)
    return rows


def release_executables() -> None:
    """Drop every executable the factories above hold (their caches are
    unbounded and process-wide, so compiled programs otherwise live as
    long as the process). Engines built afterwards compile afresh;
    live engines keep what they already hold. The test harness calls
    this between modules: XLA's CPU executables are memory mappings,
    and a whole tier-1 run never released would walk the process into
    vm.max_map_count."""
    for factory in (_fresh_banks_executable, _ingest_executables,
                    _flush_executable, _inc_flush_executable,
                    _flush_baseline_cached):
        factory.cache_clear()
    sketches.release_executables()


def _out_bank_kind(key: str) -> int:
    """Which bank's row map an incremental output key is read
    through: 0=histogram, 1=counter, 2=gauge, 3=set. Keys are
    grouped by prefix — h_*/q/agg* and the 2Sum lo_* terms ride the
    histogram bank, c_* the counter bank, g_* the gauge bank, s_* the
    set bank."""
    if key.startswith("c_"):
        return 1
    if key.startswith("g_"):
        return 2
    if key.startswith("s_"):
        return 3
    return 0


# The flush outputs whose rows are whole sketches (centroid rows,
# register rows): flush()'s assembly reads them one row at a time, so
# the incremental path never copies them (_ColdTail).
_WIDE_LEAVES = frozenset(("h_mean", "h_weight", "s_regs"))


class _ColdTail:
    """A wide leaf of an incremental flush: the compact [D, ·] fetch as
    it came, with the cached empty-flush row answering for index D,
    where the row maps send every cold slot. Read a row at a time."""

    __slots__ = ("rows", "cold")

    def __init__(self, rows, cold):
        self.rows = rows
        self.cold = cold

    def __getitem__(self, r):
        return self.rows[r] if r < len(self.rows) else self.cold

    def take(self, rows, lanes) -> np.ndarray:
        """[len(rows), lanes] of the leaf: rows `rows` (an index
        array), their first `lanes` lanes; the cold row for index D."""
        d = len(self.rows)
        cold = rows >= d
        if not d:
            return np.repeat(self.cold[None, :lanes], len(rows), axis=0)
        if not cold.any():
            return self.rows[rows, :lanes]
        out = self.rows[np.where(cold, 0, rows), :lanes]
        out[cold] = self.cold[:lanes]
        return out


def _take_rows(leaf, rows, lanes=None) -> np.ndarray:
    """A fresh [len(rows), lanes] of a wide flush leaf, a plain [S, C]
    plane or a _ColdTail; every lane where `lanes` is None."""
    if isinstance(leaf, _ColdTail):
        return leaf.take(rows, lanes)
    return np.asarray(leaf)[rows, :lanes]


# Rows a block of _live_points: a block of the widest plane stays
# some 4 MiB, which the allocator hands back warm to the next block
# (a whole [n, C] pick of 100,000 rows is 100 MB of fresh pages a
# plane, ten times the cost of the pick itself).
_POINT_BLOCK_BYTES = 4 << 20


def _joined(parts: list) -> np.ndarray:
    """The float64 arrays of `parts` end to end."""
    return (np.concatenate(parts) if parts
            else np.empty(0, np.float64))


def _live_points(h_weight, h_mean, rows) -> tuple:
    """The live centroids of rows `rows` of the two centroid leaves,
    as the forward wire takes them: (cent_off int64[n + 1], means,
    weights), a row's points with weight > 0 in lane order, row after
    row (the concatenation of `mean[row][w > 0]`, `w[w > 0]` over the
    rows). A block of rows at a time, and of the mean plane only the
    lanes up to the block's widest occupied one."""
    n = len(rows)
    cent_off = np.zeros(n + 1, np.int64)
    if not n:
        return (cent_off, np.empty(0, np.float32),
                np.empty(0, np.float32))
    width = _take_rows(h_weight, rows[:1]).shape[1]
    step = max(1, _POINT_BLOCK_BYTES // (4 * max(1, width)))
    counts, means, weights = [], [], []
    for a in range(0, n, step):
        blk = rows[a:a + step]
        w = _take_rows(h_weight, blk)
        live = w > 0
        lanes = np.flatnonzero(live.any(axis=0))
        hi = int(lanes[-1]) + 1 if lanes.size else 0
        live = live[:, :hi]
        counts.append(live.sum(axis=1))
        weights.append(w[:, :hi][live])
        means.append(_take_rows(h_mean, blk, hi)[live])
    np.cumsum(np.concatenate(counts), out=cent_off[1:])
    return cent_off, np.concatenate(means), np.concatenate(weights)


def _row_maps(ids, dirty, nrows) -> list:
    """Per bank kind, slot -> row of the compact flush outputs: a dirty
    slot's position in `ids`, and for every cold slot `nrows` (the
    fetched row count), where the baseline row sits. K x 4 bytes a
    kind: all that is left of the host scatter."""
    maps = []
    for i, d, n in zip(ids, dirty, nrows):
        m = np.full(d.size, n, np.int32)
        m[i] = np.arange(i.size, dtype=np.int32)
        maps.append(m)
    return maps


class ImportFoldReroute(Exception):
    """An over-budget IMPORTED key's fold target is homed on another
    engine (overload defense, multi-worker server): raised out of the
    engine's import_* before any staging, carrying the fold key so the
    worker loop can rewrite the aggregate's pb onto it and re-route.
    Deliberately an Exception subclass raised BEFORE the worker loop's
    poison-pill guard gets to see it (the loop catches this type
    first); it must never escape to a caller that treats it as a
    corrupted metric."""

    def __init__(self, key: MetricKey, digest: int):
        super().__init__(f"fold of imported key rehomes to {key.name}")
        self.key = key
        self.digest = digest


@dataclass
class EngineConfig:
    histogram_slots: int = 1 << 15
    counter_slots: int = 1 << 14
    gauge_slots: int = 1 << 14
    set_slots: int = 1 << 12
    compression: float = 100.0
    buffer_depth: int = 256
    hll_precision: int = 14
    # Sketch-engine selection (veneur_tpu/sketches/ registry, ISSUE
    # 10): which sketch implements the histogram/timer banks and the
    # set-cardinality banks. The defaults are the pre-registry pair
    # (behavior-identical); "req" = relative-error adaptive-compactor
    # quantiles (tail-accurate), "ull" = UltraLogLog registers (half
    # the state at equal nominal error). The per-engine shape knobs
    # below only apply to their engine.
    histogram_backend: str = "tdigest"
    set_backend: str = "hll"
    ull_precision: int = 13
    req_levels: int = 2
    req_capacity: int = 256
    batch_size: int = 8192
    percentiles: tuple = (0.5, 0.75, 0.99)
    aggregates: tuple = ("min", "max", "count")
    idle_ttl_intervals: int = 16
    forward_enabled: bool = False
    is_global: bool = False      # global tier: emit percentiles for imports
    hostname: str = ""
    # Incremental dirty-slot flush (ISSUE 11): the flush program
    # consumes the SAME dirty-slot bitmap the delta checkpoints mark at
    # every device-landing site, gathers only touched piles into a
    # compact [D, ·] work set, and hands the assembly the compact
    # results with a row map that sends every cold slot to the cached
    # empty-bank baseline row — cold piles read their (fresh-init)
    # state and materialized rows verbatim, bit-identical to the full
    # program by construction. Above `flush_incremental_threshold`
    # dirty fraction on the histogram bank the full program runs
    # instead (a near-full gather costs more than it saves).
    flush_incremental: bool = True
    flush_incremental_threshold: float = 0.75
    # Double-buffered flush (ISSUE 11): the tick boundary only RETIRES
    # the interval under the ingest lock (stage buffers, staged
    # imports, banks, dirty bitmaps swap against fresh shadows in one
    # rebind); draining the retired stages, landing the retired
    # imports, and the flush program itself all run outside the lock —
    # admit/ingest never stalls behind them. Off = the legacy ordering
    # (drain+land under the lock before the swap; the mesh engine
    # always uses it — its landing paths write sharded banks in
    # place).
    flush_double_buffer: bool = True


class FlushColumns(NamedTuple):
    """A flush's export as it had it in hand, before any tuple a key:
    the keys of each kind in wire order and the values as arrays, the
    form wire.export_columns hands the native pass (ForwardExport
    carries it; the entry lists are built from it on their first
    read)."""
    keys: tuple             # four lists of MetricKey: h, sets, c, g
    cent_off: np.ndarray    # int64[n_h + 1] into means / weights
    means: np.ndarray       # float32, live centroids only, row-major
    weights: np.ndarray     # float32, every one > 0
    stats: np.ndarray       # float64[n_h, 5]: min max sum count recip
    regs: list              # a register row u8[m] a set
    counters: np.ndarray    # float64[n_c]
    gauges: np.ndarray      # float64[n_g]


class _Entries(list):
    """One entry list of a ForwardExport that still has its flush's
    columns: a list in every way, and the first mutation tells the
    export, which drops the columns (what the forwarder then sends is
    read from the lists)."""

    __slots__ = ("_export",)


def _telling_first(name):
    method = getattr(list, name)

    def through(self, *args, **kw):
        export, self._export = self._export, None
        if export is not None:
            export._drop_columns()
        return method(self, *args, **kw)
    through.__name__ = name
    return through


for _name in ("append", "extend", "insert", "pop", "remove", "clear",
              "sort", "reverse", "__setitem__", "__delitem__",
              "__iadd__", "__imul__"):
    setattr(_Entries, _name, _telling_first(_name))
del _name


class ForwardExport:
    """Global-scope state to send upstream, one entry per key — the
    Export()/Metric() payloads of samplers (samplers.go sym: Histo.Metric,
    Set.Export, Counter.Export).

    Built by hand it is four lists of tuples, as it always was. Built
    by a flush it holds the flush's own columns (`columns`, a
    FlushColumns) and no tuple: the gRPC forwarder's native pass takes
    the columns as they are (wire.export_columns), `counts()` sizes it,
    and whoever reads an entry list (the q16 codec, the HTTP forwarder,
    a partial delivery's tail, the spill, the journal, the history
    tier) gets that list built from the columns on its first read, the
    tuples the flush used to build, counted in `lazy_built`. Changing a
    list, or assigning one, drops the columns, so the two can never
    disagree."""

    _KINDS = ("histograms", "sets", "counters", "gauges")

    def __init__(self, histograms=None, sets=None, counters=None,
                 gauges=None, set_engine: str = "hll",
                 prefix_sketches=None, kind: str = "full",
                 columns: FlushColumns | None = None):
        # (key, means f32[n], weights f32[n], min, max, sum, count, recip)
        # (key, registers u8[m]); (key, value); (key, value); None = not
        # read yet, the columns have it
        lists = [histograms, sets, counters, gauges]
        self._lists = (lists if columns is not None else
                       [[] if e is None else e for e in lists])
        self._columns = columns
        # entries built from the columns for a reader of tuples
        self.lazy_built = 0
        # which set engine produced `sets` (selects the register wire code
        # and the spill re-merge join); histograms are engine-agnostic
        # weighted points on the wire
        self.set_engine = set_engine
        # per-prefix Huffman-Bucket cardinality sketches riding to the
        # global tier (overload-defense satellite): [(prefix, bytes regs)];
        # merge-by-max, advisory — excluded from the durability journal
        self.prefix_sketches = ([] if prefix_sketches is None
                                else prefix_sketches)
        # What this export IS (ISSUE 13 delta forwarding): "full" = the
        # sender's COMPLETE interned counter/set key set (idle keys ship
        # their zero totals / empty register banks — the receiver-liveness
        # refresh a resync exists for); "delta" = only the keys the
        # dirty-slot bitmap saw land this interval. Histograms and gauges
        # are touched-only under EITHER kind, deliberately: a zero-count
        # histogram row would be live-filtered out of the receiver's own
        # flush anyway (pure wire waste), and a synthetic zero gauge would
        # CLOBBER the receiver's last-write-wins state. The forwarder
        # stamps the kind onto the interval's envelope so the receiver can
        # gap-check deltas.
        self.kind = kind

    def __repr__(self):
        return ("ForwardExport(%s, set_engine=%r, kind=%r%s)" % (
            ", ".join("%s=%d" % nc for nc in zip(self._KINDS,
                                                   self.counts())),
            self.set_engine, self.kind,
            "" if self._columns is None else ", columns"))

    @property
    def columns(self) -> FlushColumns | None:
        """The flush's columns while no list has been changed."""
        return self._columns

    def counts(self) -> tuple:
        """Entries of each kind (histograms, sets, counters, gauges),
        without building one."""
        cols = self._columns
        if cols is not None:
            return tuple(map(len, cols.keys))
        return tuple(map(len, self._lists))

    def take_lazy_built(self) -> int:
        """`lazy_built`, read and reset (a forwarder counts it a send)."""
        n, self.lazy_built = self.lazy_built, 0
        return n

    def _entries(self, i: int) -> list:
        got = self._lists[i]
        if got is None:
            got = self._lists[i] = _Entries(self._build(i))
            got._export = self
            self.lazy_built += len(got)
        return got

    def _build(self, i: int):
        """Kind `i`'s tuples from the columns, as the flush built them
        a key: centroid arrays of a digest's own length, Python floats."""
        cols = self._columns
        keys = cols.keys[i]
        if i == 0:
            off = cols.cent_off.tolist()
            return [(key, cols.means[a:b], cols.weights[a:b], *five)
                    for key, a, b, five in zip(keys, off, off[1:],
                                               cols.stats.tolist())]
        if i == 1:
            return zip(keys, cols.regs)
        return zip(keys, (cols.counters if i == 2
                          else cols.gauges).tolist())

    def _drop_columns(self):
        """A list is about to change: every list that was not read yet
        is built first, then the columns go."""
        if self._columns is None:
            return
        for i, got in enumerate(self._lists):
            if got is None:
                got = self._entries(i)
            if isinstance(got, _Entries):
                got._export = None
        self._columns = None

    def _assign(self, i: int, entries):
        self._drop_columns()
        self._lists[i] = entries

    @classmethod
    def joined(cls, exports: list) -> "ForwardExport":
        """The exports of a server's engines as one, engine after
        engine within each kind (wire order): the one export itself
        where there is one, their columns end to end where every one
        still has them, else their lists. Set engine and prefix rows
        are the last one's; the caller sets `kind`."""
        if len(exports) == 1:
            return exports[0]
        out = cls(set_engine=exports[-1].set_engine,
                  prefix_sketches=exports[-1].prefix_sketches)
        parts = [e.columns for e in exports]
        if None in parts:
            for e in exports:
                for mine, theirs in zip(out._lists, (
                        e.histograms, e.sets, e.counters, e.gauges)):
                    mine.extend(theirs)
            return out
        # a later engine's centroid offsets start where the one
        # before it ended
        ends = np.cumsum([0] + [len(c.means) for c in parts])
        out._lists = [None] * 4
        out._columns = FlushColumns(
            tuple(sum((c.keys[i] for c in parts), []) for i in range(4)),
            np.concatenate([parts[0].cent_off[:1]] + [
                c.cent_off[1:] + end for c, end in zip(parts, ends)]),
            np.concatenate([c.means for c in parts]),
            np.concatenate([c.weights for c in parts]),
            np.concatenate([c.stats for c in parts]),
            sum((c.regs for c in parts), []),
            np.concatenate([c.counters for c in parts]),
            np.concatenate([c.gauges for c in parts]))
        return out

    histograms = property(lambda self: self._entries(0),
                          lambda self, v: self._assign(0, v))
    sets = property(lambda self: self._entries(1),
                    lambda self, v: self._assign(1, v))
    counters = property(lambda self: self._entries(2),
                        lambda self, v: self._assign(2, v))
    gauges = property(lambda self: self._entries(3),
                      lambda self, v: self._assign(3, v))


class FlushResult:
    """Flush output. `frame` is the columnar MetricFrame the engine
    assembles (cheap); `metrics` materializes the InterMetric list from it
    lazily, so callers that re-serialize anyway can consume the frame."""

    __slots__ = ("frame", "export", "stats", "_metrics",
                 "status_metrics")

    def __init__(self, frame=None, export=None, stats=None, metrics=None,
                 status_metrics=None):
        self.frame = frame
        self.export = export if export is not None else ForwardExport()
        self.stats = stats if stats is not None else {}
        self._metrics = metrics
        self.status_metrics = status_metrics or []

    @property
    def metrics(self) -> list:
        if self._metrics is None:
            self._metrics = ((self.frame.to_list() if self.frame else [])
                             + self.status_metrics)
        return self._metrics


class _Stage:
    """Fixed-shape numpy staging buffer feeding one scatter kernel."""

    def __init__(self, batch_size, fields):
        self.n = 0
        self.batch_size = batch_size
        self.arrays = {
            name: np.full(batch_size, fill, dtype)
            for name, (dtype, fill) in fields.items()}

    def full(self):
        return self.n >= self.batch_size

    def put(self, **vals):
        i = self.n
        for k, v in vals.items():
            self.arrays[k][i] = v
        self.n = i + 1

    def drain(self):
        """Return padded arrays and reset. Rows past self.n keep their
        fill value (slot -1 => dropped by the kernels)."""
        out = {k: a.copy() for k, a in self.arrays.items()}
        n = self.n
        if n < self.batch_size:
            out["slots"][n:] = -1
        self.n = 0
        return out


def _spans(starts, lens):
    """Indices of the stretches [start, start + len) laid end to end,
    in the order given."""
    first = np.cumsum(lens) - lens
    return np.arange(int(lens.sum())) + np.repeat(starts - first, lens)


class DigestStage:
    """Forwarded digests waiting for a landing, as columns and in
    arrival order: a block a request (or the part of one a landing's
    edge cut off), `(slots i32[n], starts i64[n], lens i64[n], stats
    f64[5, n], means f32[], weights f32[])`, digest `j` on bank row
    `slots[j]` with the centroids `means[starts[j]:starts[j] + lens[j]]`
    and the exact min, max, sum, count and reciprocal sum
    `stats[:, j]`. `digests` and `centroids` are what the stage's two
    bounds count."""

    __slots__ = ("blocks", "digests", "centroids")

    def __init__(self):
        self.blocks: list = []
        self.digests = 0
        self.centroids = 0

    def add(self, slots, starts, lens, stats, means, weights):
        """Take digests (one at least) of the columns `means` /
        `weights`: views of the stretch they lie in, no copy."""
        lo, hi = int(starts.min()), int((starts + lens).max())
        self.blocks.append((slots, starts - lo, lens, stats,
                            means[lo:hi], weights[lo:hi]))
        self.digests += len(slots)
        self.centroids += int(lens.sum())

    def columns(self) -> tuple:
        """The stage (not empty) as one block."""
        if len(self.blocks) == 1:
            return self.blocks[0]
        slots, starts, lens, stats, means, weights = zip(*self.blocks)
        at = np.cumsum([0] + [len(m) for m in means[:-1]])
        return (np.concatenate(slots),
                np.concatenate([s + o for s, o in zip(starts, at)]),
                np.concatenate(lens), np.concatenate(stats, axis=1),
                np.concatenate(means), np.concatenate(weights))

    def items(self) -> list:
        """A tuple a digest, `(slot, means, weights, min, max, sum,
        count, reciprocal sum)`: the form a checkpoint saves
        (durability/records.py), for the edges that run once a
        checkpoint and not once a sketch."""
        return [(slot, means[a:a + n], weights[a:a + n], *five)
                for slots, starts, lens, stats, means, weights
                in self.blocks
                for slot, a, n, five in zip(
                    slots.tolist(), starts.tolist(), lens.tolist(),
                    stats.T.tolist())]

    @classmethod
    def of_items(cls, items) -> "DigestStage":
        """The stage holding `items` (the form `items()` gives) as one
        block."""
        stage = cls()
        if items:
            slots, means, weights, *five = zip(*items)
            lens = np.array([len(m) for m in means], np.int64)
            stage.add(np.array(slots, np.int32), np.cumsum(lens) - lens,
                      lens, np.array(five, np.float64),
                      *(np.concatenate([np.asarray(c, np.float32)
                                        for c in col])
                        for col in (means, weights)))
        return stage


def _f32_pair(totals) -> tuple:
    """f64 totals as two f32 columns, the f32 nearest each and the f32
    of what that left: a forwarded counter total past 2^24 is not an
    f32, and folded into the bank's 2Sum pair one column after the
    other it lands exact to 48 bits."""
    hi = totals.astype(np.float32)
    return hi, (totals - hi).astype(np.float32)


class AggregationEngine:
    # Subclass gates for the ISSUE 11 flush paths: the mesh engine owns
    # sharded banks (no per-slot bitmaps, landing paths write banks in
    # place) and turns both off in its constructor.
    _incremental_capable = True
    _double_buffer_capable = True
    # What the histogram landings' overflow handling did this interval,
    # summed on the device by the landing program itself: i32[2] = rows
    # compressed one by one, passes over the whole bank. Retired with
    # the banks at the swap and read with the flush's own fetch
    # (_last_flush_info "overflow_rows" / "overflow_bank"). None on an
    # engine whose landing counts nothing (the mesh engine).
    _overflow = None
    _overflow_zero = None
    # What the hot-slot sidestep did this interval, counted on the host
    # where _land_hot chooses its arm: [hot rows landed through a work
    # set, passes over the whole bank]. Retired with the overflow
    # counter (_last_flush_info "sidestep_rows" / "sidestep_bank");
    # None on the mesh engine, whose sidestep is its own.
    _sidestep = None

    def _setup_device(self):
        """Build the device-side state: committed banks plus the shared
        fresh-banks and ingest executables (see the factory comments
        above). Overridden by the mesh engine, which owns sharded banks
        over a Mesh instead of single-device ones."""
        cfg = self.cfg
        self._device = jax.devices()[0]
        self._fresh_fn = _fresh_banks_executable(
            self._device, self._heng, self._seng, cfg.histogram_slots,
            cfg.counter_slots, cfg.gauge_slots, cfg.set_slots)
        # vlint: disable=DS01 reason=initial fresh-bank build, not a
        # data landing — every row is exactly fresh init (zero dirty)
        (self.histo_bank, self.counter_bank,
         self.gauge_bank, self.set_bank) = self._fresh_fn()
        self._kern = _ingest_executables(self._device, self._heng,
                                         self._seng)
        self._overflow_zero = jax.device_put(
            np.zeros(2, np.int32),
            jax.sharding.SingleDeviceSharding(self._device))
        self._overflow = self._overflow_zero
        self._sidestep = [0, 0]

    def _setup_flush_exec(self):
        self._flush_exec = _flush_executable(
            self._device, self._heng, self._seng, self._fwd_out,
            tuple(self._agg_emit))

    def __init__(self, config: EngineConfig | None = None):
        self.cfg = config or EngineConfig()
        if self.cfg.buffer_depth < 8:
            raise ValueError("buffer_depth must be >= 8 (hot-slot "
                             "pre-clustering needs usable bucket room)")
        if not (0.0 < self.cfg.flush_incremental_threshold <= 1.0):
            raise ValueError(
                "flush_incremental_threshold must be in (0, 1]: it is "
                "the dirty fraction above which the full flush program "
                f"runs, got {self.cfg.flush_incremental_threshold!r}")
        # One ingest thread owns process(); flush() may run from another
        # thread. The lock is the Worker.Flush mutex-swap equivalent:
        # ingest holds it per item; flush holds it ONLY across
        # drain+swap+bookkeeping, then runs the merge program on the
        # immutable snapshot lock-free while ingest continues.
        self.lock = threading.Lock()
        cfg = self.cfg
        # Selected sketch engines (sketches/ registry): frozen
        # dataclasses carrying the static shape params; every sketch
        # call in this module routes through them (vlint SK01).
        self._heng = sketches.histogram_engine(cfg)
        self._seng = sketches.set_engine(cfg)
        self._setup_device()

        self.histo_keys = self._key_table(cfg.histogram_slots)
        self.counter_keys = self._key_table(cfg.counter_slots)
        self.gauge_keys = self._key_table(cfg.gauge_slots)
        self.set_keys = self._key_table(cfg.set_slots)
        # each table's running `interned` at the last flush (a flush
        # notes what the interval minted)
        self._keys_interned_seen = [0, 0, 0, 0]

        b = cfg.batch_size
        f32, i32 = (np.float32, 0.0), (np.int32, 0)
        self._histo_stage = _Stage(b, {"slots": (np.int32, -1),
                                       "values": f32, "weights": f32})
        self._counter_stage = _Stage(b, {"slots": (np.int32, -1),
                                         "values": f32, "weights": f32})
        self._gauge_stage = _Stage(b, {"slots": (np.int32, -1),
                                       "values": f32, "seqs": i32})
        self._set_stage = _Stage(b, {"slots": (np.int32, -1),
                                     "reg_idx": i32, "rho": (np.uint8, 0)})
        self._gauge_seq = 0
        # Where a clock outside the engine orders the gauges (the
        # native bridge's arrival numbers, 32 bits that wrap): the
        # clock a gauge of the Python path asks (`gauge_clock`, which
        # the server sets beside the pump; None = the engine's own
        # count), the stamp the interval's sequence numbers are counted
        # from, and the farthest stamp past it the interval has landed
        self.gauge_clock = None
        self._gauge_base = 0
        self._gauge_hi = 0
        # Quantile program input: configured percentiles, plus 0.5 when the
        # `median` aggregate is requested (veneur's median IS quantile(0.5)).
        qs = list(cfg.percentiles)
        self._median_idx = None
        if "median" in cfg.aggregates:
            self._median_idx = len(qs)
            qs.append(0.5)
        self._qs = np.asarray(qs, np.float32)
        # %g formatting matches veneur's suffixes ("99percentile",
        # "99.9percentile") and avoids int() truncation (0.29 -> 28).
        self._pct_names = [f".{p * 100:g}percentile" for p in cfg.percentiles]
        # Flush-assembly presentation caches: per-key metric names and
        # split tag lists are immutable across flushes, so they're built
        # once and re-used; the columnar frame then only moves numpy
        # values. Bounded (cleared when oversized) because the native
        # bridge's interner evicts keys without telling us.
        self._pct_sufs = list(self._pct_names)
        if self._median_idx is not None:
            self._pct_sufs.append(".median")
        self._agg_emit = [a for a in cfg.aggregates
                          if a in ("min", "max", "sum", "count",
                                   "avg", "hmean")]
        agg_types = tuple(MetricType.COUNTER if a == "count"
                          else MetricType.GAUGE for a in self._agg_emit)
        self._histo_full_types = (
            (MetricType.GAUGE,) * len(self._pct_sufs) + agg_types)
        self._histo_agg_types = agg_types
        self._agg_idx = {a: i for i, a in enumerate(self._agg_emit)}
        self._fwd_out = cfg.forward_enabled and not cfg.is_global
        self._setup_flush_exec()
        self._tags_cache: dict[str, list] = {}
        self._pres_bound = 4 * (cfg.histogram_slots + cfg.counter_slots
                                + cfg.gauge_slots + cfg.set_slots)
        self.samples_processed = 0
        # Dirty-slot bitmaps per bank, with TWO consumers (ISSUE 9 +
        # ISSUE 11): flush-boundary delta checkpoints serialize only
        # dirty rows, and the incremental flush program compresses only
        # dirty piles. Marked at every DEVICE LANDING site (scatter/
        # merge dispatch — machine-checked by vlint DS01), retired at
        # the flush swap (the retiring interval's bitmap travels with
        # its bank snapshot; a FRESH zero bitmap replaces it in the
        # same rebind), so at any instant `fresh init + dirty rows` is
        # exactly the live bank state — what keeps delta checkpoints
        # self-contained with the flush as a second consumer.
        # Armed by default for the incremental flush; None only when
        # flush_incremental is off AND enable_dirty_tracking was never
        # called (then landing sites cost one attribute load).
        # last_import_op is the applied-op watermark recovery filters
        # the replay log by.
        self._dirty = None
        self._delta_threshold = 0.5
        self._use_incremental = (cfg.flush_incremental
                                 and self._incremental_capable)
        self._use_double_buffer = (cfg.flush_double_buffer
                                   and self._double_buffer_capable)
        if self._use_incremental:
            self._dirty = [
                np.zeros(getattr(self, attr).num_slots, bool)
                for _kind, attr, _ki in self._bank_table()]
        # per-output-key baseline rows of an EMPTY flush (what every
        # cold pile materializes to) — computed lazily on a 1-slot
        # fresh bank set (engine-pair-shaped, slot-count-independent)
        self._flush_baseline = None
        self._last_flush_info = self._full_flush_info()
        self.last_import_op = 0
        # import batches applied since the last flush and the metrics
        # in them (_last_flush_info "import_batches" / "import_metrics")
        self._import_batches = 0
        self._import_metrics = 0
        # what the interval's clustered landings did: rows that went
        # through a work set, landings that took the whole-bank
        # passes, lanes handed to the cluster program (padding
        # included), lanes of them that carried a centroid, and piles
        # the pre-cluster loop cut first (_last_flush_info
        # "import_land_rows" / "_bank" / "_lanes" / "_lanes_filled" /
        # "_prechunked")
        self._import_land_rows = 0
        self._import_land_bank = 0
        self._import_land_lanes = 0
        self._import_land_lanes_filled = 0
        self._import_land_prechunked = 0
        # the applying threads' CPU time in import_list, outside and
        # under the lock (_last_flush_info "import_decode_cpu_ns" /
        # "import_stage_cpu_ns"; counted only with land_stamps armed)
        self._import_decode_cpu_ns = 0
        self._import_stage_cpu_ns = 0
        # how the interval's sketches were decoded (DECODE_TALLY) and
        # its digests staged (STAGE_TALLY), and the decoder, whose key
        # dictionary holds as many entries as the banks hold keys
        for name in DECODE_TALLY + STAGE_TALLY:
            setattr(self, "_" + name, 0)
        from ..cluster import wire
        self._import_decoder = wire.BatchDecoder(
            cfg.histogram_slots + cfg.counter_slots + cfg.gauge_slots
            + cfg.set_slots)
        # Overload defense (ingest/admission.py): attached by the
        # Server via attach_admission; None = every key mints freely
        # (direct engine construction, the pre-defense behavior).
        self._adm = None
        self._adm_index = 0
        self._adm_n = 1
        self._adm_reroute = None
        # Imported (Combine) staging for the global tier — everything is
        # batched so a 32-shard import costs a handful of device calls,
        # not one per key.
        self._import_centroids = DigestStage()
        # observe.StampLog for IMPORT_PHASES, set by a Server whose
        # flight recorder is on; flush() hands its rows to the tick
        self.land_stamps = None
        self._import_sets: list = []          # (slot, registers u8[m])
        self._import_counter_acc: dict = {}   # slot -> host f64 sum
        self._import_gauge_acc: dict = {}     # slot -> last value
        self._pending_events: list = []
        # StatusCheck sampler state (samplers.go sym: StatusCheck): last
        # status/message per (name, tags) per interval, flushed as
        # status-typed InterMetrics — NOT passed through raw.
        self._status: dict = {}

    def _key_table(self, slots: int) -> KeyInterner:
        """The key table of a bank of `slots` rows."""
        return KeyInterner(slots, self.cfg.idle_ttl_intervals)

    # ---------------- ingest ----------------

    def attach_admission(self, adm, *, index: int = 0, n: int = 1,
                         reroute=None):
        """Wire the Server's admission controller into this engine's
        slot minting (overload defense): each KeyInterner consults it
        before allocating, and over-budget keys' samples re-stage onto
        their prefix's `__other__` key via `_fold` instead of minting
        a bank slot. A map-hit key never touches the controller, so
        the steady-state ingest path is unchanged.

        `index`/`n`/`reroute` single-home the fold keys in a
        multi-worker server: a fold rewrite whose digest routes to a
        DIFFERENT engine is handed back to the server's router
        (`reroute`) instead of minting a local slot, so one flush
        never emits the same `__other__` series from two engines —
        duplicate same-name rows are last-write-wins on several
        backends, which would silently lose folded volume."""
        self._adm = adm
        self._adm_index = index
        self._adm_n = n
        self._adm_reroute = reroute
        for ki in (self.histo_keys, self.counter_keys,
                   self.gauge_keys, self.set_keys):
            ki.admission = adm

    def _fold(self, interner, m: UDPMetric):
        """Resolve an over-budget sample (lookup returned FOLD_SLOT)
        into (fold-rewritten metric, slot), or (None, -1) when the
        sample left this engine: sampled out (admission counts it —
        and it must then not count as processed either, the accounting
        identity `received == applied + counted_degraded` is exact),
        re-routed to the fold key's home engine (counted as folded
        here; the home engine processes it as an ordinary sample), or
        refused by the full bank (the interner's dropped_no_slot
        accounting, exactly like any over-full sample — NOT counted
        as a fold)."""
        fm = self._adm.fold_metric(m, self._fwd_out)
        if fm is None:
            self.samples_processed -= 1
            return None, -1
        if self._adm_n > 1 and fm.digest % self._adm_n != self._adm_index:
            self.samples_processed -= 1      # the home engine counts it
            self._adm.count_folded()
            self._adm_reroute(fm)
            return None, -1
        slot = interner.lookup(fm.key, fm.scope)
        if slot < 0:
            return None, -1
        self._adm.count_folded()
        return fm, slot

    def _fold_import_slot(self, interner, key: MetricKey) -> int:
        """Import-path fold (the global tier's Combine): redirect an
        over-budget forwarded key's slot to the fold key — the merge
        machinery is unchanged, the aggregate just lands in
        `<prefix>.__other__` (no sampling: a forwarded digest is an
        interval aggregate, not a sample). In a multi-worker server a
        fold key homed on another engine raises ImportFoldReroute so
        the worker loop re-routes the aggregate there (single-homed,
        like the ingest path)."""
        if self._adm is None:
            return -1
        fk, digest = self._adm.fold_key(key)
        if self._adm_n > 1 and digest % self._adm_n != self._adm_index:
            self._adm.count_folded()
            raise ImportFoldReroute(fk, digest)
        slot = interner.lookup(fk, GLOBAL_ONLY)
        if slot >= 0:
            self._adm.count_folded()
        return slot

    def process(self, m: UDPMetric):
        """Route one parsed sample to its bank's staging buffer — the
        Worker.ProcessMetric equivalent. Thread-safe against flush()."""
        with self.lock:
            self._process_locked(m)

    def _process_locked(self, m: UDPMetric):
        t = m.key.type
        self.samples_processed += 1
        if t in ("timer", "histogram"):
            slot = self.histo_keys.lookup(m.key, m.scope)
            if slot < 0:
                if slot != FOLD_SLOT:
                    return
                m, slot = self._fold(self.histo_keys, m)
                if m is None:
                    return
            st = self._histo_stage
            st.put(slots=slot, values=m.value, weights=1.0 / m.sample_rate)
            if st.full():
                self._dispatch_histos()
        elif t == "counter":
            slot = self.counter_keys.lookup(m.key, m.scope)
            if slot < 0:
                if slot != FOLD_SLOT:
                    return
                m, slot = self._fold(self.counter_keys, m)
                if m is None:
                    return
            st = self._counter_stage
            st.put(slots=slot, values=m.value, weights=1.0 / m.sample_rate)
            if st.full():
                self._dispatch_counters()
        elif t == "gauge":
            slot = self.gauge_keys.lookup(m.key, m.scope)
            if slot < 0:
                if slot != FOLD_SLOT:
                    return
                m, slot = self._fold(self.gauge_keys, m)
                if m is None:
                    return
            st = self._gauge_stage
            if self.gauge_clock is None:
                self._gauge_seq += 1
                seq = self._gauge_seq
            else:
                seq = int(self._stamp_seqs(
                    np.array([self.gauge_clock()]))[0])
            st.put(slots=slot, values=m.value, seqs=seq)
            if st.full():
                self._dispatch_gauges()
        elif t == "set":
            slot = self.set_keys.lookup(m.key, m.scope)
            if slot < 0:
                if slot != FOLD_SLOT:
                    return
                m, slot = self._fold(self.set_keys, m)
                if m is None:
                    return
            # Engine-specific hash decomposition (int bit ops, no
            # numpy round-trip) — this is the per-sample hot path.
            h = hashing.set_member_hash(str(m.value))
            idx, val = self._seng.hash_update(h)
            st = self._set_stage
            st.put(slots=slot, reg_idx=idx, rho=val)
            if st.full():
                self._dispatch_sets()

    # ---- pre-interned batch ingest (the native C++ bridge's path) ----
    # Slots were assigned by the bridge's interner; rows with slot -1 are
    # padding the kernels drop. `mark` (if given) runs under the engine
    # lock so the caller's touched-set stays consistent with the bank the
    # samples land in across a concurrent flush swap.
    #
    # ALIASING CONTRACT: callers must not mutate the passed arrays after
    # the call returns. The dispatch is async and jax's CPU client
    # zero-copies page-aligned numpy arrays into executable arguments,
    # so a later overwrite races the kernel's read (the native pump
    # copies its reused poll buffers for exactly this reason).

    def _ingest_batch(self, slots, count, mark, apply):
        with self.lock:
            n = int(count if count is not None else len(slots))
            if mark is not None:
                mark(slots[:n])
            self.samples_processed += n
            apply(n)

    def ingest_histo_batch(self, slots, values, weights, count=None,
                           mark=None):
        def apply(n):
            self._add_histos(slots, values, weights)
        self._ingest_batch(slots, count, mark, apply)

    def _add_histos(self, slots, values, weights):
        """Live-bank histogram landing (ingest path; mesh overrides
        this wholesale — its landing routes the sharded ingest)."""
        self.histo_bank, self._overflow = self._land_histos(
            self.histo_bank, self._overflow, self._sidestep, self._dirty,
            slots, values, weights)

    def _land_histos(self, bank, overflow, sidestep, dirty, slots, values,
                     weights):
        """Land one histogram batch into `bank` (live or a retired
        double-buffer snapshot — the caller owns the rebind of the bank
        and of its `overflow` counter, and hands the interval's
        `sidestep` counts to bump in place), marking `dirty`,
        sidestepping the hot-slot worst case. add_batch absorbs a
        buffer's depth of one slot's samples a turn of its loop, with a
        row pass (gather, compress, scatter over _OVERFLOW_ROWS rows)
        between turns: a batch that brings one slot n samples costs
        ceil(n / B) of them, 128 at the pump's 32,768 against B = 256.
        The sidestep makes it one: when a batch overfills any slot,
        the hot slots' samples are taken out of it and pre-clustered
        on host to <= B weighted points each (numpy sort + bucketed
        segment means — the same two-level scheme the digest itself
        uses, so accuracy is unchanged within the k1 clustering's own
        granularity), and the points land in the hot rows' buffers
        after ONE compress that empties them, with their exact stats
        through merge_scalars.

        The compress runs over the hot rows alone — a work set of
        _hot_widths' row count, gathered, compressed, filled and
        scattered back, the import landing's pattern (_land_work_set)
        — where the engine has the row primitives (import_strategy
        "cluster") and the bank is larger than the work set; every
        other row keeps its buffer. Otherwise (a compactor engine, a
        small bank) the whole bank is compressed and merge_centroids
        lands the points. Every op is row-independent, so the hot rows
        read the same bit for bit either way."""
        slots = np.asarray(slots)
        B = bank.buf_size
        valid = slots >= 0
        vs = slots[valid]
        if dirty is not None and vs.size:
            dirty[0][vs] = True
        # Hot-slot detection, cheapest-first (this runs on EVERY pump
        # batch): a batch with <= B valid rows cannot overfill any slot,
        # so skip counting entirely. Otherwise bincount — one O(n + max)
        # pass — EXCEPT when the live slot ids dwarf the batch (sparse
        # high-slot batches against a 1M-slot bank would allocate and
        # scan a multi-MB count array per batch); there np.unique's
        # O(n log n) on the small batch is the cheaper form.
        if vs.size <= B:
            return self._kern["histo"](bank, overflow, slots, values,
                                       weights)
        if vs.max() > 16 * vs.size:
            uniq, cnt = np.unique(vs, return_counts=True)
            hot_ids = uniq[cnt > B]
        else:
            cnt = np.bincount(vs, minlength=1)
            hot_ids = np.nonzero(cnt > B)[0]
        if hot_ids.size == 0:
            return self._kern["histo"](bank, overflow, slots, values,
                                       weights)
        values = np.asarray(values)
        weights = np.asarray(weights)
        cold_slots = np.where(np.isin(slots, hot_ids), -1,
                              slots).astype(np.int32)
        bank, overflow = self._kern["histo"](bank, overflow, cold_slots,
                                             values, weights)

        # ONE fixed shape per batch width (worst case: every sample in
        # the batch belongs to a hot slot) — a width that varied with
        # the data would JIT a new executable inline, under the ingest
        # lock, per width. Row i is hot_ids[i]'s (ascending); the rows
        # past them are padding.
        S, lanes = self._hot_widths(len(slots))
        ids = np.full(S, -1, np.int32)
        ids[:hot_ids.size] = hot_ids
        means = np.zeros((S, lanes), np.float32)
        wts = np.zeros_like(means)
        stats = np.zeros((5, S), np.float32)
        for i, s in enumerate(hot_ids):
            m = slots == s
            v = values[m].astype(np.float64)
            w = weights[m].astype(np.float64)
            cm, cw = _precluster_k1(v, w, B)
            means[i, :len(cm)] = cm
            wts[i, :len(cm)] = cw
            nz = v != 0
            stats[:, i] = (v.min(), v.max(), (v * w).sum(), w.sum(),
                           (w[nz] / v[nz]).sum())
        return self._land_hot(bank, sidestep, ids, means, wts,
                              stats), overflow

    def _land_hot(self, bank, sidestep, ids, means, wts, stats):
        """The device half of the sidestep: pre-clustered points
        f32[S, <= B] and exact stats f32[5, S], row i for bank row
        ids[i] (ascending, -1 padding). Over the work set of S rows
        where the engine has the row primitives and the bank is larger
        than S (static, the test ops/tdigest._add_batch_counted makes
        of _OVERFLOW_ROWS), else over the whole bank; `sidestep`
        counts which."""
        K = bank.num_slots
        if self._heng.import_strategy == "cluster" and len(ids) < K:
            sidestep[0] += int(np.count_nonzero(ids >= 0))
            # the padding id K lies past the bank, reads its last row
            # at the gather and is dropped at the scatter
            rows = np.where(ids < 0, K, ids).astype(np.int32)
            # _kern["compress"] at the part's shape, not the engine's
            # own jit: a profile keeps the sidestep's compress under
            # its name (jit_compress_impl)
            # vlint: disable=DS01 reason=the device half of
            # _land_histos, which marked the batch's rows first (the
            # warm-up's all-padding call lands nothing)
            part = self._kern["compress"](
                self._heng.gather_rows(bank, rows))
            bank = self._heng.scatter_rows(
                bank, rows, self._heng.fill_buffers(part, means, wts))
        else:
            sidestep[1] += 1
            # compress first so merge_centroids has a full buffer of
            # headroom; it drops the lanes of weight 0
            bank = self._kern["compress"](bank)
            bank = self._kern["merge_centroids"](
                bank, np.repeat(ids, means.shape[1]), means.reshape(-1),
                wts.reshape(-1))
        return self._kern["merge_scalars"](bank, ids, *stats)

    def ingest_counter_batch(self, slots, values, weights, count=None,
                             mark=None):
        def apply(n):
            self.counter_bank = self._land_counters(
                self.counter_bank, self._dirty, slots, values, weights)
        self._ingest_batch(slots, count, mark, apply)

    def ingest_gauge_batch(self, slots, values, count=None, mark=None,
                           order=None):
        # Sequence numbers are assigned HERE, not by the producer: the
        # per-interval reset then happens under the same lock as the
        # bank swap, so a stale pre-flush sample can never outrank a
        # newer post-flush one and the counter cannot wrap within an
        # interval. `order` is each sample's arrival number (the
        # bridge's stamp, which outlives no interval: _gauge_rows
        # counts it from the interval's base); without it a sample's
        # place in the batch is its order.
        def apply(n):
            self.gauge_bank = self._land_gauges(
                self.gauge_bank, self._dirty,
                *self._gauge_rows(slots, values, n, order))
        self._ingest_batch(slots, count, mark, apply)

    # A stamp's sequence number is its distance from the interval's
    # base plus this much, so that a sample staged before the swap and
    # pumped after it, whose stamp lies below the new base, still has a
    # number of its own under every later one; an interval holds 2^30
    # datagrams before its numbers saturate (they never wrap).
    _GAUGE_SEQ_ROOM = 1 << 30

    def _stamp_seqs(self, stamps) -> np.ndarray:
        """The interval's sequence numbers of gauge samples from their
        arrival numbers (uint32 that wrap, held as any integer type):
        the signed distance from `_gauge_base` on the 32-bit circle.
        Caller holds the engine lock."""
        d = (stamps.astype(np.int64) - self._gauge_base) & 0xFFFFFFFF
        d -= (d >> 31) << 32
        self._gauge_hi = max(self._gauge_hi, int(d.max()))
        seqs = np.clip(d + self._GAUGE_SEQ_ROOM, 1,
                       np.iinfo(np.int32).max).astype(np.int32)
        self._gauge_seq = max(self._gauge_seq, int(seqs.max()))
        return seqs

    def _gauge_rows(self, slots, values, n, order) -> tuple:
        """(slots, values, seqs) of a pump batch as gauge_set takes
        them. gauge_set keeps a slot's LAST row of a batch, and the
        pump fills a batch one sub-ring after another: where the
        arrival numbers do not already rise (several readers), the rows
        are laid in their order first (stable: a datagram's own samples
        keep their places)."""
        if order is None:
            seqs = np.arange(1, len(slots) + 1, dtype=np.int32) \
                + self._gauge_seq
            self._gauge_seq += n
            return slots, values, seqs
        seqs = np.zeros(len(slots), np.int32)
        if n:
            seqs[:n] = self._stamp_seqs(order[:n])
            if np.any(seqs[1:n] < seqs[:n - 1]):
                by = np.argsort(seqs[:n], kind="stable")
                slots, values = slots.copy(), values.copy()
                slots[:n], values[:n] = slots[by], values[by]
                seqs[:n] = seqs[by]
        return slots, values, seqs

    def _retire_gauge_seq(self) -> int:
        """The interval's last sequence number, at the bank swap and
        under its lock: the next interval counts from 0 again, and its
        stamps from the farthest one this interval landed."""
        seq, self._gauge_seq = self._gauge_seq, 0
        self._gauge_base = (self._gauge_base + self._gauge_hi) & 0xFFFFFFFF
        self._gauge_hi = 0
        return seq

    def ingest_set_batch(self, slots, reg_idx, rho, count=None, mark=None):
        def apply(n):
            self.set_bank = self._land_sets(
                self.set_bank, self._dirty, slots, reg_idx, rho)
        self._ingest_batch(slots, count, mark, apply)

    def process_event(self, ev):
        with self.lock:
            self._pending_events.append(ev)

    def process_service_check(self, sc):
        """Aggregate one service check: last write wins per
        (name, tags) within the interval (samplers.go sym:
        StatusCheck.Sample — a gauge over status codes)."""
        with self.lock:
            self._status[(sc.name, tuple(sc.tags))] = sc

    def _dispatch_histos(self):
        a = self._histo_stage.drain()
        self._add_histos(a["slots"], a["values"], a["weights"])

    def _dispatch_counters(self):
        a = self._counter_stage.drain()
        self.counter_bank = self._land_counters(
            self.counter_bank, self._dirty, a["slots"], a["values"],
            a["weights"])

    def _dispatch_gauges(self):
        a = self._gauge_stage.drain()
        self.gauge_bank = self._land_gauges(
            self.gauge_bank, self._dirty, a["slots"], a["values"],
            a["seqs"])

    def _dispatch_sets(self):
        a = self._set_stage.drain()
        self.set_bank = self._land_sets(
            self.set_bank, self._dirty, a["slots"], a["reg_idx"],
            a["rho"])

    # ---- scalar/set landing cores: take and return the bank, mark
    # the PASSED bitmap — shared by the live ingest path (live banks +
    # live bitmap) and the double-buffered flush's retired landing
    # (retired banks + retired bitmap).

    def _land_counters(self, bank, dirty, slots, values, weights):
        if dirty is not None:
            self._mark_dirty_into(dirty, 1, slots)
        return self._kern["counter"](bank, slots, values, weights)

    def _land_gauges(self, bank, dirty, slots, values, seqs):
        if dirty is not None:
            self._mark_dirty_into(dirty, 2, slots)
        return self._kern["gauge"](bank, slots, values, seqs)

    def _land_sets(self, bank, dirty, slots, reg_idx, rho):
        if dirty is not None:
            self._mark_dirty_into(dirty, 3, slots)
        return self._kern["set"](bank, slots, reg_idx, rho)

    def drain_all(self):
        for st, fn in ((self._histo_stage, self._dispatch_histos),
                       (self._counter_stage, self._dispatch_counters),
                       (self._gauge_stage, self._dispatch_gauges),
                       (self._set_stage, self._dispatch_sets)):
            if st.n:
                fn()

    def _hot_widths(self, batch: int):
        """Fixed pad shape (rows, lanes) of the hot-slot sidestep of a
        `batch`-wide landing (the staging batch_size, or the native
        pump's own width): fewer than batch/B slots can be hot in one
        batch, each contributing <= B pre-clustered points. B is the
        BANK's per-landing headroom (the engine's buf_size — t-digest
        buffer depth, compactor level capacity), which need not equal
        cfg.buffer_depth."""
        B = self.histo_bank.buf_size
        return max(1, batch // max(1, B)), min(B, batch)

    def warmup(self):
        """Precompile every device program the serving path dispatches.

        Without this, flush 0 pays the full compile bill inline —
        several flush intervals on a cold start, which would trip the
        server's crash-only watchdog before the first flush ever
        completes. Ingest kernels compile against all-padding batches
        (slot -1 rows are dropped by the kernels, so live state is
        untouched); the flush program runs on throwaway fresh banks,
        which it donates away."""
        self.warm_ingest_kernels(self.cfg.batch_size)
        # Run the full configured flush path (program + staging/fetch
        # mode) so flush 0 hits only warm executables.
        self._flush_device(self._fresh_fn(), overflow=self._overflow_zero)
        if self._use_incremental:
            # the incremental path too: build the empty-flush baseline
            # and compile the smallest-bucket incremental program (one
            # dirty slot per bank — flush 0's common shape; bigger
            # dirty sets compile their bucket inline; the import's
            # programs, below, are all compiled here)
            warm_dirty = [np.zeros_like(d) for d in self._dirty]
            for d in warm_dirty:
                d[0] = True
            self._flush_device(self._fresh_fn(), dirty=warm_dirty,
                               overflow=self._overflow_zero)
        self._warm_import_landing()
        jax.block_until_ready(self.histo_bank)

    def _warm_import_landing(self):
        """Precompile every device program an import dispatches: their
        shapes follow from the engine's configuration and this
        module's constants alone, never from what was staged. The
        cluster program at every [rows, lanes] of _cluster_shapes and
        both arms of the pre-cluster loop (compiled, not run); the
        bank-side work at every row count that serves this bank
        (_land_rows' sets, the whole-bank passes where a landing can
        take them); merge_scalars at the stage's digest bound; the set
        rows' merge at the stage's; counter_merge and gauge_set at
        _scalar_rows. All-padding landings: every id is -1 or lies
        past the bank, every centroid weighs 0, so live state is
        untouched."""
        with self.lock:
            # vlint: disable=DS01 reason=all-padding warm-up landings
            # (ids -1 or past the bank, dropped at the scatter): no
            # live data lands, nothing to mark
            self.set_bank = self._land_import_sets(
                self.set_bank,
                [(-1, np.zeros(self.set_bank.num_registers, np.uint8))],
                None)
            for n in self._scalar_rows(self.counter_bank.num_slots):
                self.counter_bank = self._land_import_counters(
                    self.counter_bank, np.full(n, -1, np.int32),
                    np.zeros(n, np.float32), None)
            for n in self._scalar_rows(self.gauge_bank.num_slots):
                self.gauge_bank, _ = self._land_import_gauges(
                    self.gauge_bank, np.full(n, -1, np.int32),
                    np.zeros(n, np.float32), None, 0)
            bank = self._merge_import_scalars(
                self.histo_bank, np.zeros(0, np.int32), np.zeros((5, 0)))
            if self._heng.import_strategy == "cluster":
                K, C = bank.num_slots, bank.num_centroids
                cap = self._land_lanes(C)[-1]
                for rows, lanes in self._cluster_shapes(K, C):
                    self._heng.cluster_program(rows, lanes, C)
                for prefix in (0, C):
                    self._heng.cluster_program(
                        _IMPORT_CHUNK_ROWS, cap, C, sorted_prefix=prefix)
                for rows in self._land_row_counts(K):
                    zc = np.zeros((rows, C), np.float32)
                    bank = self._land_clustered(
                        bank, None if rows == K else rows,
                        np.zeros(0, np.int32), zc, zc)
            self.histo_bank = jax.device_put(bank, self._device)

    def warm_ingest_kernels(self, b: int):
        """Precompile the batch-ingest kernels — the four scatters and
        what the hot-slot sidestep dispatches (_land_hot: over its work
        set, or the whole-bank pair where that is the arm it takes) —
        at batch width `b`: warmup() covers the staging batch_size, and
        the Server asks again for the native pump's own width
        (native_pump_batch). Padding batches: slot -1 rows are dropped,
        live state untouched."""
        pad = np.full(b, -1, np.int32)
        zf = np.zeros(b, np.float32)
        zi = np.zeros(b, np.int32)
        zu = np.zeros(b, np.uint8)
        S, lanes = self._hot_widths(b)
        zp = np.zeros((S, lanes), np.float32)
        with self.lock:
            # vlint: disable=DS01 reason=all-padding warmup batches
            # (slot -1 rows dropped by the kernels) — no live data
            # lands, nothing to mark
            self.histo_bank, self._overflow = self._kern["histo"](
                self.histo_bank, self._overflow, pad, zf, zf)
            self.counter_bank = self._kern["counter"](
                self.counter_bank, pad, zf, zf)
            self.gauge_bank = self._kern["gauge"](
                self.gauge_bank, pad, zf, zi)
            self.set_bank = self._kern["set"](self.set_bank, pad, zi, zu)
            self.histo_bank = self._land_hot(
                self.histo_bank, [0, 0], np.full(S, -1, np.int32), zp, zp,
                np.zeros((5, S), np.float32))
        jax.block_until_ready(self.histo_bank)

    # ---------------- import (global tier Combine path) ----------------

    def import_histogram(self, key: MetricKey, means, weights, vmin, vmax,
                         vsum, count, recip=0.0):
        """Stage a forwarded digest for merging — Histo.Combine
        (importsrv path, worker.go sym: Worker.ImportMetricGRPC)."""
        with self.lock:
            self._import_histogram_locked(key, means, weights, vmin,
                                          vmax, vsum, count, recip)

    def _import_slot(self, interner, key) -> int:
        """The row a forwarded key lands in, or < 0 for none; an
        over-budget key's is its prefix's fold key's (overload
        defense), which raises ImportFoldReroute where that key is
        homed on another engine."""
        slot = interner.lookup(key, GLOBAL_ONLY)
        if slot == FOLD_SLOT:
            slot = self._fold_import_slot(interner, key)
        return slot

    def _import_histogram_locked(self, key, means, weights, vmin, vmax,
                                 vsum, count, recip=0.0):
        """One digest, staged as a block of one."""
        slot = self._import_slot(self.histo_keys, key)
        if slot < 0:
            return
        self._import_digests_single += 1
        self._stage_digests(
            np.array([slot], np.int32), np.zeros(1, np.int64),
            np.array([len(means)], np.int64),
            np.array([[vmin], [vmax], [vsum], [count], [recip]],
                     np.float64), means, weights)

    def _stage_digests(self, slots, starts, lens, stats, means, weights):
        """Stage digests given as columns (DigestStage has the form), in
        their order. A landing fires at the digest that brings the
        stage to either of its bounds, in the middle of the columns
        where that is where it falls: they are cut there, and every
        landing holds the digests it would hold had they come one by
        one. The mesh engine, whose landing is another device program,
        stages them its own way."""
        means = np.asarray(means, np.float32)
        weights = np.asarray(weights, np.float32)
        ends = np.cumsum(lens)
        i, n = 0, len(slots)
        while i < n:
            stage = self._import_centroids
            room = _IMPORT_STAGE_CENTROIDS - stage.centroids \
                + (int(ends[i - 1]) if i else 0)
            j = max(i + 1, min(
                n, i + _IMPORT_STAGE_DIGESTS - stage.digests,
                int(np.searchsorted(ends, room, side="left")) + 1))
            stage.add(slots[i:j], starts[i:j], lens[i:j], stats[:, i:j],
                      means, weights)
            if (stage.digests >= _IMPORT_STAGE_DIGESTS
                    or stage.centroids >= _IMPORT_STAGE_CENTROIDS):
                self._flush_import_centroids()
            i = j

    def import_set(self, key: MetricKey, registers, engine_id=None):
        with self.lock:
            self._import_set_locked(key, registers, engine_id)

    def _import_set_locked(self, key, registers, engine_id=None):
        # belt to the request-level stamp check's suspenders: a
        # register row of the wrong engine or width must reject THIS
        # metric (the poison-pill counter), never join a bank whose
        # update rule it does not share
        if engine_id is not None and engine_id != self._seng.id:
            raise ValueError(
                f"set sketch engine mismatch: payload {engine_id!r}, "
                f"bank runs {self._seng.id!r}")
        regs = np.asarray(registers, np.uint8)
        if regs.shape[-1] != self.set_bank.num_registers:
            raise ValueError(
                f"set register width {regs.shape[-1]} != bank width "
                f"{self.set_bank.num_registers}")
        slot = self._import_slot(self.set_keys, key)
        if slot < 0:
            return
        self._import_sets.append((slot, regs))
        if len(self._import_sets) >= _IMPORT_STAGE_SETS:
            self._flush_import_sets()

    def import_counter(self, key: MetricKey, value: float):
        with self.lock:
            self._import_counter_locked(key, value)

    def _import_counter_locked(self, key, value):
        slot = self._import_slot(self.counter_keys, key)
        if slot < 0:
            return
        # Host-side f64 accumulation — exact, one device call per flush.
        self._import_counter_acc[slot] = (
            self._import_counter_acc.get(slot, 0.0) + float(value))

    def import_gauge(self, key: MetricKey, value: float):
        with self.lock:
            self._import_gauge_locked(key, value)

    def _import_gauge_locked(self, key, value):
        slot = self._import_slot(self.gauge_keys, key)
        if slot < 0:
            return
        self._import_gauge_acc[slot] = float(value)  # last write wins

    def import_list(self, op_id: int, pbs, raw=None, at=None) -> tuple:
        """Apply one import request's metrics for this engine as a
        unit — the one way from a request to the banks' staging (the
        worker loop, recovery's replay and the history tier all call
        it). The batch is decoded in one pass outside the lock
        (wire.BatchDecoder: from `raw`, the request's serialized bytes,
        where the batch came with them, `at` the positions of `pbs` in
        the request or None for all of it; else from the parsed
        messages, wire.decode_metric_batch), its histograms into a
        block of columns (wire.DigestBlock), then staged in wire order
        under ONE lock hold in which the applied-op watermark also
        advances, so a concurrent checkpoint_state() sees either none
        of the op or all of it — the exactness the watermark's replay
        filter depends on. Staging is the engine's own
        (_stage_import_records). Returns (rerouted, rejected): fold
        keys homed on other engines as (ImportFoldReroute, pb) pairs
        the worker loop re-routes, and per-metric poison pills as
        (pb, exception) pairs it counts — one corrupt metric must
        reject itself, not the op."""
        # with the stamp log armed: the three APPLY_PHASES and the
        # thread's CPU time beside two of them, four readings of each
        # clock a request; without it no clock is read
        stamps = self.land_stamps
        opened, closed = ((_no_clock, _no_clock) if stamps is None
                          else (_open_clocks, _close_clocks))
        t0, c0 = opened()
        block, records, rejected, decoded = \
            self._import_decoder.decode(pbs, raw, at)
        t1, c1 = closed()
        # staging names a metric by its position in `pbs`
        rerouted_at, rejected_at = [], []
        with self.lock:
            t2, c2 = opened()
            self._stage_import_records(block, records, rerouted_at,
                                       rejected_at)
            self._import_batches += 1
            self._import_metrics += len(pbs)
            if op_id > self.last_import_op:
                self.last_import_op = op_id
            t3, c3 = closed()
            self._import_decode_cpu_ns += c1 - c0
            self._import_stage_cpu_ns += c3 - c2
            for name, n in zip(DECODE_TALLY, decoded):
                setattr(self, "_" + name, getattr(self, "_" + name) + n)
        if stamps is not None:
            # no merge gap: requests interleave, and a decode row
            # merged with the next would swallow the stage between
            stamps.add("import.apply.decode", t0, t1)
            stamps.add("import.apply.lock_wait", t1, t2)
            stamps.add("import.apply.stage", t2, t3)
        rejected += [(pbs[i], e) for i, e in rejected_at]
        return [(fr, pbs[i]) for fr, i in rerouted_at], rejected

    def _stage_import_records(self, block, records, rerouted, rejected):
        """Stage a decoded request under the lock: `block`, its
        histograms as columns (wire.DigestBlock), and `records`, its
        other metrics (wire.decode_metric_batch's); appending to
        `rerouted` and `rejected`, which name a metric by its position
        in the batch. A row is looked up a key, every kind's in wire
        order (an admission budget is spent in the order the metrics
        came): the one loop over the block's digests, each lookup under
        its own `try`, so a key that re-routes or rejects does so by
        itself and the rest of the request stages. The digests that
        found a row are then staged as columns (_stage_digests), the
        others by the per-metric `_import_*_locked` calls."""
        from ..cluster import wire
        slots: list = []
        # digests ahead of each record, by position
        ahead = np.searchsorted(block.at, [rec[2] for rec in records])
        for rec, upto in zip(records, ahead.tolist()):
            self._block_slots(block, upto, slots, rerouted, rejected)
            kind = rec[0]
            try:
                if kind == wire.IMPORT_SET:
                    self._import_set_locked(rec[1], rec[3], rec[4])
                elif kind == wire.IMPORT_COUNTER:
                    self._import_counter_locked(rec[1], rec[3])
                else:
                    self._import_gauge_locked(rec[1], rec[3])
            except ImportFoldReroute as fr:
                rerouted.append((fr, rec[2]))
            except Exception as e:
                rejected.append((rec[2], e))
        self._block_slots(block, len(block.keys), slots, rerouted, rejected)
        slots = np.array(slots, np.int32)
        keep = np.flatnonzero(slots >= 0)
        self._import_digests_block += len(keep)
        if len(keep):
            self._stage_digests(
                slots[keep], block.start[keep],
                (block.stop - block.start)[keep], block.stats[:, keep],
                block.means, block.weights)

    def _block_slots(self, block, upto, slots, rerouted, rejected):
        """Extend `slots` to the first `upto` digests of `block`: each
        one's bank row, or -1 for a digest that is not to be staged (no
        row left, re-routed, or rejected: the two lists say which)."""
        lookup, table, j = self._import_slot, self.histo_keys, len(slots)
        for j, key in enumerate(block.keys[j:upto], j):
            try:
                slots.append(lookup(table, key))
            except ImportFoldReroute as fr:
                rerouted.append((fr, int(block.at[j])))
                slots.append(-1)
            except Exception as e:
                rejected.append((int(block.at[j]), e))
                slots.append(-1)

    def _flush_import_sets(self):
        items, self._import_sets = self._import_sets, []
        self.set_bank = self._land_import_sets(self.set_bank, items,
                                               self._dirty)

    def _land_import_sets(self, bank, items, dirty):
        """Union staged register rows into `bank`, a stage's worth a
        dispatch: a flush-time tail is padded to the same
        [_IMPORT_STAGE_SETS, m] with slot -1, which merge_rows
        drops."""
        if not items:
            return bank
        n = _IMPORT_STAGE_SETS
        for i in range(0, len(items), n):
            part = items[i:i + n]
            slots = np.full(n, -1, np.int32)
            slots[:len(part)] = [s for s, _ in part]
            regs = np.zeros((n, bank.num_registers), np.uint8)
            regs[:len(part)] = [r for _, r in part]
            if dirty is not None:
                self._mark_dirty_into(dirty, 3, slots[:len(part)])
            bank = self._seng.merge_rows(bank, slots, regs)
        return jax.device_put(bank, self._device)

    def _flush_import_scalars(self):
        counters, self._import_counter_acc = self._import_counter_acc, {}
        gauges, self._import_gauge_acc = self._import_gauge_acc, {}
        (self.counter_bank, self.gauge_bank,
         self._gauge_seq) = self._land_import_scalars(
            self.counter_bank, self.gauge_bank, counters, gauges,
            self._dirty, self._gauge_seq)

    @staticmethod
    def _scalar_rows(K: int) -> tuple:
        """Entry counts counter_merge and gauge_set are dispatched at
        for a bank of K slots, ascending: an interval's distinct
        imported keys are padded (slot -1, dropped) to the smallest
        that holds them. No more keys than slots can be staged."""
        return tuple(n for n in (_IMPORT_SCALAR_ROWS,) if n < K) + (K,)

    def _pad_scalars(self, K: int, slots, *cols):
        n = next(r for r in self._scalar_rows(K) if r >= len(slots))
        more = (0, n - len(slots))
        return (np.pad(slots, more, constant_values=-1),
                *(np.pad(c, more) for c in cols))

    def _land_import_counters(self, bank, slots, values, dirty):
        if dirty is not None:
            self._mark_dirty_into(dirty, 1, slots)
        return jax.device_put(scalar.counter_merge(
            bank, *self._pad_scalars(bank.num_slots, slots, values)),
            self._device)

    def _land_import_gauges(self, bank, slots, values, dirty, gauge_seq):
        if dirty is not None:
            self._mark_dirty_into(dirty, 2, slots)
        seqs = np.arange(len(slots), dtype=np.int32) + gauge_seq + 1
        return jax.device_put(scalar.gauge_set(
            bank, *self._pad_scalars(bank.num_slots, slots, values, seqs)),
            self._device), gauge_seq + len(slots)

    def _land_import_scalars(self, cbank, gbank, counters, gauges,
                             dirty, gauge_seq):
        if counters:
            slots = np.fromiter(counters.keys(), np.int32, len(counters))
            hi, lo = _f32_pair(np.fromiter(counters.values(), np.float64,
                                           len(counters)))
            cbank = self._land_import_counters(cbank, slots, hi, dirty)
            if lo.any():
                cbank = self._land_import_counters(cbank, slots, lo, None)
        if gauges:
            gbank, gauge_seq = self._land_import_gauges(
                gbank, np.fromiter(gauges.keys(), np.int32, len(gauges)),
                np.fromiter(gauges.values(), np.float32, len(gauges)),
                dirty, gauge_seq)
        return cbank, gbank, gauge_seq

    def _flush_import_centroids(self):
        stage, self._import_centroids = self._import_centroids, DigestStage()
        self.histo_bank, did = self._land_import_centroids(
            self.histo_bank, stage, self._dirty)
        for name, n in did.items():
            setattr(self, "_" + name, getattr(self, "_" + name) + n)

    @staticmethod
    def _land_rows(S: int, K: int):
        """The work set for a clustered landing of S rows into a bank
        of K: the smallest of _IMPORT_LAND_ROWS that holds them and is
        smaller than the bank, or None for the whole-bank passes."""
        for R in _IMPORT_LAND_ROWS:
            if S <= R < K:
                return R
        return None

    @staticmethod
    def _land_lanes(C: int) -> tuple:
        """The lane widths a clustered landing pads its piles to, for
        a bank of C centroids a row: _IMPORT_LAND_LANES below the
        pre-cluster cap, then the cap."""
        cap = max(_IMPORT_W_CAP, 2 * C)
        return tuple(n for n in _IMPORT_LAND_LANES if n < cap) + (cap,)

    @staticmethod
    def _land_row_counts(K: int) -> list:
        """The row counts a landing into a bank of K rows can hand the
        cluster program, ascending: the work sets that serve the bank
        (_land_rows) and, where a stage can hold more rows than the
        largest of them, the bank's own count (the whole-bank arm)."""
        sets = [R for R in _IMPORT_LAND_ROWS if R < K]
        if min(K, _IMPORT_STAGE_DIGESTS) > (sets[-1] if sets else 0):
            sets.append(K)
        return sets

    @classmethod
    def _cluster_shapes(cls, K: int, C: int) -> list:
        """Every [rows, lanes] a landing can hand the cluster program:
        _land_row_counts at every step of _land_lanes."""
        return [(R, L) for R in cls._land_row_counts(K)
                for L in cls._land_lanes(C)]

    def _land_import_centroids(self, bank, stage, dirty):
        """Land staged foreign digests (a DigestStage) into `bank`
        under the engine's import strategy: "cluster" (t-digest —
        precluster each slot's pile to <= C centroids with ONE batched
        cluster program, then compress, fill the emptied buffers and
        compress again, over the rows the landing touches) or "direct"
        (compactor engines —
        the digests re-insert as weighted points in fixed-width batches;
        the engine's own compaction bounds memory, no preclustering).
        One `import.land` stamp per landing (LAND_PHASES), whichever
        thread runs it: a worker mid-interval, the flusher at flush.
        Returns the bank and what the landing did, by the names of
        _IMPORT_TALLY (a clustered landing's five `import_land_*`),
        for the caller to add to its interval's tally: the double-
        buffered flush lands its retired stage outside the lock,
        beside the next interval's landings."""
        if not stage.digests:
            return bank, {}
        stamps = self.land_stamps
        t0 = time.monotonic_ns()
        cols = stage.columns()
        if self._heng.import_strategy == "direct":
            bank, did = self._land_imports_direct(bank, cols, dirty), {}
        else:
            bank, did = self._land_imports_clustered(bank, cols, dirty,
                                                     stamps, t0)
        if stamps is not None:
            stamps.add("import.land", t0, time.monotonic_ns())
        return bank, did

    def _land_imports_clustered(self, bank, cols, dirty, stamps, t0):
        """The "cluster" import strategy over a stage's columns; `t0`
        is where the landing's `import.land.stage` phase began. Every
        device program it dispatches has a shape that follows from the
        bank's and this module's constants (_cluster_shapes,
        _IMPORT_CHUNK_ROWS, _IMPORT_STAGE_DIGESTS), and warmup() has
        compiled it: what was staged decides which of them runs, never
        a new one. A slot's pile is its digests' centroids side by side
        in arrival order; the piles are found, measured and laid into
        the device matrix by array operations over the columns, no
        loop over the digests."""
        K, C = bank.num_slots, bank.num_centroids
        slots, starts, lens, stats, means, weights = cols
        lanes = self._land_lanes(C)
        cap = lanes[-1]
        trusted: set = set()   # slots whose piles are all re-clustered
        # the digests whose centroids make the piles: the stage's own
        # until the pre-cluster loop has cut a pile
        d_slots, d_starts, d_lens = slots, starts, lens
        while True:
            # rows in ascending order (the work set's scatter is told
            # so), a row's digests in arrival order
            order = np.argsort(d_slots, kind="stable")
            run = np.flatnonzero(np.diff(d_slots[order], prepend=-1))
            slot_ids = d_slots[order[run]]
            widths = np.add.reduceat(d_lens[order], run)
            over = np.flatnonzero(widths > cap)
            if not len(over):
                break
            ends = np.append(run[1:], len(order))
            d_slots, d_starts, d_lens, means, weights = self._precluster(
                C, lanes, trusted,
                (d_slots, d_starts, d_lens, means, weights),
                [(int(slot_ids[k]), order[run[k]:ends[k]])
                 for k in over.tolist()])
        if dirty is not None:
            self._mark_dirty_into(dirty, 0, slot_ids)
        # the piles side by side in an [R, L] matrix: R the work set
        # (the bank's own rows on the whole-bank arm), L the narrowest
        # step that holds the widest pile. Padding lanes weigh 0 and
        # padding rows are empty, which the clustering leaves out, so
        # a pile's centroids do not depend on the shape it rode in.
        # One indexed write a plane: centroid `i` of the piles laid end
        # to end goes to its pile's row, at its offset in the pile
        S = len(slot_ids)
        R = self._land_rows(S, K)
        L = next(n for n in lanes if n >= widths.max())
        filled = int(widths.sum())
        take = _spans(d_starts[order], d_lens[order])
        row = np.repeat(np.arange(S), widths)
        lane = np.arange(filled) - np.repeat(np.cumsum(widths) - widths,
                                             widths)
        both = np.zeros((2, R or K, L), np.float32)
        both[0, row, lane] = means[take]
        both[1, row, lane] = weights[take]
        t1 = time.monotonic_ns()
        cmeans, cwts = (np.asarray(a) for a in self._heng.cluster_rows(
            *both, num_centroids=C, lanes=lanes))
        if stamps is not None:
            stamps.add("import.land.stage", t0, t1)
            stamps.add("import.land.cluster", t1, time.monotonic_ns())
        bank = self._land_clustered(bank, R, slot_ids, cmeans, cwts)
        bank = self._merge_import_scalars(bank, slots, stats)
        # the merge chain above ran through plain jits whose outputs are
        # uncommitted; recommit so the ingest kernels and the flush
        # program stay on their committed (fast) executables
        return jax.device_put(bank, self._device), {
            "import_land_rows": 0 if R is None else S,
            "import_land_bank": int(R is None),
            "import_land_lanes": (R or K) * L,
            "import_land_lanes_filled": filled,
            "import_land_prechunked": len(trusted)}

    def _precluster(self, C, lanes, trusted, cols, piles):
        """One pass of the pre-cluster loop over the oversized `piles`,
        each (slot, its digests' places in `cols` in arrival order);
        `cols` are (slots, starts, lens) a digest and the two centroid
        columns. Returns `cols` with those piles' digests replaced by
        the pass's outputs, a row of C lanes each, put behind the
        columns; the piles' slots join `trusted`.

        Forwarded payloads are untrusted: a digest with millions of
        centroids must not size the device matrix (resource
        exhaustion). Pre-cluster any oversized pile in fixed-width
        chunks — each pass reduces a chunk of `cap` raw centroids to C
        clustered ones, so with cap >= 2C the loop converges
        geometrically and every program shape stays bounded (cap must
        exceed C or re-chunking could never shrink a pile at high
        compression settings). Pass 1 full-sorts (foreign rows are
        unordered AND untrusted); later passes re-merge OUR OWN
        cluster outputs — each a [C] cluster-ordered row — so
        chunks hold whole rows and take the cluster program's
        sorted_prefix=C fast arm (the importsrv re-merge case: the
        leading run's order is proven, only the tail needs sorting).
        The chunks go to the device _IMPORT_CHUNK_ROWS at a time,
        the last dispatch padded with empty rows."""
        d_slots, d_starts, d_lens, means, weights = cols
        cap = lanes[-1]
        batches = {0: ([], []), C: ([], [])}   # sorted_prefix ->
        for s, digests in piles:                # (owners, chunks)
            prefix = C if s in trusted else 0
            owners, chunks = batches[prefix]
            take = _spans(d_starts[digests], d_lens[digests])
            step = cap // C * C if prefix else cap
            for i in range(0, len(take), step):
                part = take[i:i + step]
                chunk = np.zeros((2, cap), np.float32)
                chunk[0, :len(part)] = means[part]
                chunk[1, :len(part)] = weights[part]
                owners.append(s)
                chunks.append(chunk)
        out_slots, out_means, out_weights = [], [means], [weights]
        for prefix, (owners, chunks) in batches.items():
            for i in range(0, len(owners), _IMPORT_CHUNK_ROWS):
                part = chunks[i:i + _IMPORT_CHUNK_ROWS]
                both = np.zeros((2, _IMPORT_CHUNK_ROWS, cap), np.float32)
                both[:, :len(part)] = np.stack(part, axis=1)
                cm, cw = (np.asarray(a) for a in
                          self._heng.cluster_rows(
                              *both, num_centroids=C,
                              sorted_prefix=prefix, lanes=lanes))
                out_slots += owners[i:i + _IMPORT_CHUNK_ROWS]
                out_means.append(cm[:len(part)].reshape(-1))
                out_weights.append(cw[:len(part)].reshape(-1))
        cut = [s for s, _digests in piles]
        trusted.update(cut)
        keep = ~np.isin(d_slots, cut)
        rows = len(out_slots)
        return (np.concatenate([d_slots[keep],
                                np.array(out_slots, d_slots.dtype)]),
                np.concatenate([d_starts[keep],
                                len(means) + C * np.arange(rows)]),
                np.concatenate([d_lens[keep], np.full(rows, C)]),
                np.concatenate(out_means), np.concatenate(out_weights))

    def _land_clustered(self, bank, R, slot_ids, cmeans, cwts):
        """Land clustered centroids f32[R or K, C], row i for bank row
        slot_ids[i] (ascending; the rows past them are padding): a
        compress empties the buffers, the centroids go into them, a
        compress folds them. A buffer holds B lanes, so the C columns
        go in chunks of B with a compress before each (one chunk in
        the default config, where B >= C). Over the work set of R
        rows, or with R None over the whole bank."""
        K, C = bank.num_slots, bank.num_centroids
        more = (0, len(cmeans) - len(slot_ids))
        if R is not None:
            # the padding id K lies past the bank, reads its last row
            # at the gather and is dropped at the scatter
            return self._land_work_set(
                bank, np.pad(slot_ids, more, constant_values=K),
                cmeans, cwts)
        # merge_centroids drops slot -1, and on buffer overflow, hence
        # the chunks
        ids = np.pad(slot_ids, more, constant_values=-1)
        B = bank.buf_size
        for c0 in range(0, C, B):
            chunk = slice(c0, min(C, c0 + B))
            bank = self._heng.compress(bank)
            # vlint: disable=DS01 reason=the bank-side half of
            # _land_imports_clustered, which marked slot_ids before
            # its cluster dispatch (the warm-up's all-padding call
            # lands nothing)
            bank = self._heng.merge_centroids(
                bank, np.repeat(ids, chunk.stop - chunk.start),
                cmeans[:, chunk].reshape(-1), cwts[:, chunk].reshape(-1))
        return self._heng.compress(bank)

    def _merge_import_scalars(self, bank, slots, stats):
        """merge_scalars of staged digests' exact stats (`stats[:, j]`
        digest j's, on row `slots[j]`), the stage's digest bound a
        dispatch: slot -1 pads, which the program masks. (No digests:
        one all-padding dispatch, the warm-up's.)"""
        n = _IMPORT_STAGE_DIGESTS
        for i in range(0, max(len(slots), 1), n):
            part = slots[i:i + n]
            ps = np.full(n, -1, np.int32)
            pstats = np.zeros((5, n), np.float32)
            ps[:len(part)] = part
            pstats[:, :len(part)] = stats[:, i:i + n]
            # vlint: disable=DS01 reason=the exact-stats half of an
            # import landing: both callers (_land_imports_clustered,
            # _land_imports_direct) marked the digests' rows first
            bank = self._heng.merge_scalars(bank, ps, *pstats)
        return bank

    def _land_work_set(self, bank, rows, means, weights):
        """Gather the [R, .] part of `bank` at `rows`, fold the
        clustered centroids f32[R, C] into it row for row (compress,
        fill the emptied buffers, compress) and scatter it back."""
        C, B = bank.num_centroids, bank.buf_size
        part = self._heng.gather_rows(bank, rows)
        for c0 in range(0, C, B):
            part = self._heng.compress(part)
            part = self._heng.fill_buffers(
                part, means[:, c0:c0 + B], weights[:, c0:c0 + B])
        part = self._heng.compress(part)
        return self._heng.scatter_rows(bank, rows, part)

    # fixed flat-batch width for the direct import landing: one program
    # shape however many centroids an interval staged
    _DIRECT_LAND_WIDTH = 4096

    def _land_imports_direct(self, bank, cols, dirty):
        """The "direct" import strategy (compactor engines): re-insert
        every forwarded weighted point through the engine's own
        merge_centroids — its internal compaction bounds memory, so no
        host-side preclustering pass is needed. Batches are fixed-width
        (padded, slot -1 dropped) so the program shape never varies."""
        W = self._DIRECT_LAND_WIDTH
        d_slots, starts, lens, stats, means, wts = cols
        take = _spans(starts, lens)
        slots, means, wts = np.repeat(d_slots, lens), means[take], wts[take]
        if dirty is not None:
            self._mark_dirty_into(dirty, 0, np.unique(d_slots))
        for i in range(0, len(slots), W):
            seg = slice(i, min(len(slots), i + W))
            n = seg.stop - seg.start
            ps = np.full(W, -1, np.int32)
            pm = np.zeros(W, np.float32)
            pw = np.zeros(W, np.float32)
            ps[:n] = slots[seg]
            pm[:n] = means[seg]
            pw[:n] = wts[seg]
            bank = self._heng.merge_centroids(bank, ps, pm, pw)
        bank = self._merge_import_scalars(bank, d_slots, stats)
        return jax.device_put(bank, self._device)

    # ---------------- flush ----------------

    def _swap_banks(self):
        """Under the lock: return the interval's bank snapshot and hand
        ingest fresh banks — the Worker.Flush swap, ONE async dispatch
        of the committed-output zeros program. Overridden by the mesh
        engine (its reset donates the sharded banks). Dirty-bitmap
        retirement happens in _retire_dirty (the caller), not here —
        the retiring bitmap must travel WITH this snapshot to its
        consumer (the incremental flush), while the fresh banks get a
        fresh zero bitmap in the same critical section."""
        snap = (self.histo_bank, self.counter_bank,
                self.gauge_bank, self.set_bank)
        # vlint: disable=DS01 reason=the fresh-bank swap, not a data
        # landing — the caller pairs it with _retire_dirty, which
        # installs a zero bitmap matching these all-fresh rows
        (self.histo_bank, self.counter_bank,
         self.gauge_bank, self.set_bank) = self._fresh_fn()
        return snap

    def _retire_dirty(self):
        """Under the lock, with the bank swap: hand the retiring
        interval's dirty bitmaps to the flush and install fresh zero
        bitmaps for the new banks. The swap re-zeroed every row, so
        `fresh init + dirty rows` describes the new banks exactly —
        the invariant BOTH consumers (delta checkpoints, incremental
        flush) rely on; a checkpoint taken after this tick sees only
        post-swap marks, never the flushed interval's."""
        retired = self._dirty
        if retired is not None:
            self._dirty = [np.zeros_like(d) for d in retired]
        return retired

    def _retire_overflow(self):
        """Under the lock, with the bank swap: the retiring interval's
        overflow counter and sidestep counts travel with its snapshot,
        and the fresh banks count from zero."""
        retired = self._overflow, self._sidestep
        self._overflow = self._overflow_zero
        if self._sidestep is not None:
            self._sidestep = [0, 0]
        return retired

    def _flush_device(self, snap, phases=None, dirty=None,
                      overflow=None) -> tuple:
        """Run the flush program on the snapshot and fetch its outputs
        as host arrays: ONE program dispatch + ONE device_get.
        Overridden by the mesh engine.

        Returns (host, row_of). `host` holds the fetched arrays as
        they are; `row_of` says where a slot's row is in them: None
        means the identity (the full program and the mesh engine:
        index with the slot), else one int32[K] vector a bank kind
        (_out_bank_kind) from slot to row of the compact incremental
        outputs. flush()'s one assembly reads both through it.

        `dirty` is the retired interval's dirty-slot bitmap set: when
        given (and incremental flush is on), only the touched piles
        run through the device — the ISSUE 11 tentpole
        (_flush_device_incremental); above the dirty-fraction
        threshold, or with dirty=None (warmup, bench harnesses, mesh),
        the full program runs. `overflow` (the retired interval's
        overflow counter) rides the same fetch into _last_flush_info.

        `phases` (flight-recorder stamp list, appended in place) splits
        the merge into dispatch / device exec / fetch."""
        if dirty is not None and self._use_incremental:
            got = self._flush_device_incremental(snap, phases, dirty,
                                                 overflow)
            if got is not None:
                return got
        self._last_flush_info = self._full_flush_info()
        hb, cb, gb, sb = snap
        t0 = time.monotonic_ns()
        out = self._flush_exec(hb, cb, gb, sb, self._qs)
        if overflow is not None:
            out["overflow"] = overflow
        t1 = time.monotonic_ns()
        if phases is None:
            return self._fetch_flush(out), None
        return self._timed_fetch(out, t0, t1, phases), None

    def _full_flush_info(self) -> dict:
        """_last_flush_info of a flush whose outputs cover every slot:
        `host_rows`, the rows a bank kind hands the assembly, are the
        banks' own."""
        cfg = self.cfg
        return {"path": "full",
                "host_rows": [cfg.histogram_slots, cfg.counter_slots,
                              cfg.gauge_slots, cfg.set_slots]}

    def _timed_fetch(self, out, t0, t1, phases):
        """Fetch flush outputs with the device.dispatch/exec/fetch
        phase stamps — shared by the full and incremental dispatch
        paths."""
        jax.block_until_ready(out)
        t2 = time.monotonic_ns()
        host = self._fetch_flush(out)
        t3 = time.monotonic_ns()
        phases.append(("device.dispatch", t0, t1))
        phases.append(("device.exec", t1, t2))
        phases.append(("device.fetch", t2, t3))
        return host

    def _flush_baseline_rows(self) -> dict:
        """Per-output-key baseline row of an EMPTY flush — what every
        cold pile materializes to. Computed ONCE per (engine pair,
        flush config) on a 1-slot fresh bank set through the same
        program body + fetch post-processing as the serving path
        (slot-count-independent: fresh rows are identical), shared
        process-wide via the module cache and read-only. The
        incremental flush reads every cold slot from these rows;
        bit-identity to the full program holds because the flush body
        maps a fresh bank row to exactly this row (pinned by the
        oracle suite)."""
        if self._flush_baseline is None:
            self._flush_baseline = _flush_baseline_cached(
                self._device, self._heng, self._seng, self._fwd_out,
                tuple(self._agg_emit),
                tuple(float(q) for q in self._qs))
        return self._flush_baseline

    def _flush_device_incremental(self, snap, phases, dirty, overflow):
        """The incremental dirty-slot flush (ISSUE 11 tentpole):
        gather only touched piles into a compact [D, ·] work set, run
        the shared flush body over that slice, and hand the assembly
        the compact outputs with a row map a bank kind (_compact_host,
        _row_maps) — cold piles read the cached empty-bank baseline
        row, which is what a fresh-init pile materializes to. Nothing
        here is sized by the bank but the four int32[K] maps. Returns
        None to fall back to the full program when the histogram
        bank's dirty fraction exceeds flush_incremental_threshold (a
        near-full gather costs more than it saves). Phase stamps:
        `gather` (host dirty-index extraction + padding), the usual
        device phases over the compact program, `scatter` (the
        baseline row's hand-over and the row maps)."""
        t0 = time.monotonic_ns()
        ids = [np.nonzero(d)[0].astype(np.int32) for d in dirty]
        if ids[0].size > (self.cfg.flush_incremental_threshold
                          * dirty[0].size):
            return None
        base = self._flush_baseline_rows()
        self._last_flush_info = {
            "path": "incremental",
            "dirty": [int(i.size) for i in ids],
            "piles": [int(d.size) for d in dirty],
        }
        if all(i.size == 0 for i in ids):
            # an idle interval: every output IS the baseline — no
            # device dispatch at all (and nothing landed to overflow)
            if overflow is not None:
                self._last_flush_info.update(overflow_rows=0,
                                             overflow_bank=0)
            got = self._compact_host({}, ids, dirty, base, [0, 0, 0, 0])
            t1 = time.monotonic_ns()
            if phases is not None:
                phases.append(("gather", t0, t1))
            return got
        hb, cb, gb, sb = snap
        idx = [_pad_dirty_ids(i, d.size) for d, i in zip(dirty, ids)]
        buckets = self._last_flush_info["buckets"] = [len(p) for p in idx]
        exec_ = _inc_flush_executable(
            self._device, self._heng, self._seng, self._fwd_out,
            tuple(self._agg_emit))
        t1 = time.monotonic_ns()
        if phases is not None:
            phases.append(("gather", t0, t1))
        t2 = time.monotonic_ns()
        out = exec_(hb, cb, gb, sb, self._qs, *idx)
        if overflow is not None:
            out["overflow"] = overflow
        t3 = time.monotonic_ns()
        if phases is not None:
            host_c = self._timed_fetch(out, t2, t3, phases)
        else:
            host_c = self._fetch_flush(out)
        t4 = time.monotonic_ns()
        got = self._compact_host(host_c, ids, dirty, base, buckets)
        t5 = time.monotonic_ns()
        if phases is not None:
            phases.append(("scatter", t4, t5))
        return got

    def _compact_host(self, host_c, ids, dirty, base, nrows) -> tuple:
        """(host, row_of) of an incremental flush: the compact fetch
        `host_c` ([nrows[kind], ·] a key; empty on an idle interval)
        with the baseline row behind its last row, and the row maps
        that send every cold slot there. The column leaves take it as
        one appended row (a few bytes a row, so the assembly's
        vectorized gathers and f64 conversions run over D + 1 rows);
        the wide leaves are not copied (_ColdTail). flush()'s assembly
        stays one implementation for both paths: it indexes with
        row_of[kind][slots] here and with the slots after the full
        program."""
        host = {}
        for k, row in base.items():
            v = host_c.get(k)
            if k in _WIDE_LEAVES:
                host[k] = _ColdTail(() if v is None else v, row)
            else:
                tail = np.asarray(row)[None]
                host[k] = tail if v is None else np.concatenate([v, tail])
        self._last_flush_info["host_rows"] = [n + 1 for n in nrows]
        return host, _row_maps(ids, dirty, nrows)

    def _fetch_flush(self, out):
        """The flush's one device_get plus its host-side finishing
        (shared with the mesh engine's _flush_device)."""
        host = jax.device_get(out)
        # host half of the set estimate (ULL's ML solve; identity for
        # engines whose device program emits the finished estimate)
        if "s_est" in host or "s_counts" in host:
            self._seng.estimate_finalize(host)
        counted = host.pop("overflow", None)
        if counted is not None:
            self._last_flush_info.update(
                overflow_rows=int(counted[0]),
                overflow_bank=int(counted[1]))
        return host

    def _flush_bookkeeping(self, full_export: bool = False) -> tuple:
        """Under the lock, at the tick boundary: snapshot the active
        key sets and per-interval counters, reset them, and advance
        the interner intervals — shared by both flush orderings.

        `full_export` (a FULL-kind forward build, ISSUE 13)
        additionally snapshots the counter/set interners' COMPLETE
        tables: the resync ships idle keys' zero/empty rows to refresh
        the receiving tier's series liveness. Snapshotted here, under
        the same lock hold as the active sets, so the full export and
        the bank snapshot describe the same instant."""
        active = {
            "histo": self.histo_keys.active_items(),
            "counter": self.counter_keys.active_items(),
            "gauge": self.gauge_keys.active_items(),
            "set": self.set_keys.active_items(),
        }
        if full_export:
            active["counter_all"] = self.counter_keys.all_items()
            active["set_all"] = self.set_keys.all_items()
        status, self._status = self._status, {}
        stats_samples = self.samples_processed
        self.samples_processed = 0
        dropped = 0
        tables = (self.histo_keys, self.counter_keys,
                  self.gauge_keys, self.set_keys)
        for ki in tables:
            dropped += ki.dropped_no_slot
            ki.dropped_no_slot = 0  # per-interval, like `samples`
        histo_key_count = len(self.histo_keys)
        # the advance of all four key tables: keys idle past the TTL
        # give their slots back. What each table minted since the last
        # flush, evicted now and holds after it, by bank (histo,
        # counter, gauge, set), with the advance's edges: the flush
        # notes the counts in `_last_flush_info` and stamps the phase
        t_adv = time.monotonic_ns()
        minted = [ki.interned for ki in tables]
        for ki in tables:
            ki.advance_interval()
        keys = {"keys_interned": [n - n0 for n, n0 in
                                  zip(minted, self._keys_interned_seen)],
                "keys_evicted": [ki.evicted for ki in tables],
                "keys_live": [len(ki) for ki in tables]}
        self._keys_interned_seen = minted
        return (active, status, stats_samples, dropped, histo_key_count,
                self._take_tally(_IMPORT_TALLY), keys,
                ("advance", t_adv, time.monotonic_ns()))

    def _take_tally(self, names) -> dict:
        """The interval's counts `_<name>`, read and reset (under the
        lock, at the flush)."""
        tally = {name: getattr(self, "_" + name) for name in names}
        for name in names:
            setattr(self, "_" + name, 0)
        return tally

    def _land_retired(self, snap, overflow, sidestep, dirty, stages,
                      imports, gauge_seq) -> tuple:
        """Outside the lock (double-buffered flush): drain the retired
        interval's stage buffers and land its staged imports into the
        retired bank snapshot — the same work the legacy ordering does
        under the lock, in the same order (stages first, then staged
        imports), so both orderings produce bit-identical banks. Marks
        go to the RETIRED bitmap: they belong to this flush's dirty
        set, not the new banks' checkpoint bitmap. Safe lock-free: the
        retired banks, stages, and import lists are no longer
        reachable from the ingest path, and the shared ingest
        executables are thread-safe to dispatch."""
        hb, cb, gb, sb = snap
        a = stages.get("histo")
        if a is not None:
            hb, overflow = self._land_histos(
                hb, overflow, sidestep, dirty, a["slots"], a["values"],
                a["weights"])
        a = stages.get("counter")
        if a is not None:
            cb = self._land_counters(cb, dirty, a["slots"], a["values"],
                                     a["weights"])
        a = stages.get("gauge")
        if a is not None:
            gb = self._land_gauges(gb, dirty, a["slots"], a["values"],
                                   a["seqs"])
        a = stages.get("set")
        if a is not None:
            sb = self._land_sets(sb, dirty, a["slots"], a["reg_idx"],
                                 a["rho"])
        centroids, sets, counters, gauges = imports
        hb, did = self._land_import_centroids(hb, centroids, dirty)
        sb = self._land_import_sets(sb, sets, dirty)
        cb, gb, _seq = self._land_import_scalars(
            cb, gb, counters, gauges, dirty, gauge_seq)
        return (hb, cb, gb, sb), overflow, did

    def flush(self, timestamp: int | None = None,
              forward_kind: str = "full") -> FlushResult:
        """The Server.Flush equivalent: snapshot banks, run the merge
        program, assemble InterMetrics + forward exports, reset state.

        `forward_kind` (ISSUE 13): "delta" asks the export build to
        consume the retired dirty-slot bitmap — the THIRD consumer,
        after the incremental flush and the delta checkpoints, under
        the same retire discipline — and ship only touched counter/set
        rows (histograms and gauges are touched-only either way, see
        ForwardExport.kind). Honored only when the bitmap exists
        (dirty tracking armed, not the mesh engine) and this engine
        forwards; the result's export.kind records what was actually
        built, so the forwarder stamps the envelope truthfully. The
        locally-flushed frame is NEVER delta-filtered — only the
        forward path is byte-bound.

        Double-buffered (the default): the lock is held ONLY across
        the retire-and-swap — stage buffers, staged imports, banks and
        dirty bitmaps swap against fresh shadows in one rebind
        (`engine.swap` phase) — and ingest proceeds into the shadow
        bank immediately; draining the retired stages, landing the
        retired imports, the merge program, and host assembly all run
        on the retired snapshot outside the lock. Legacy ordering
        (flush_double_buffer off, and always on the mesh engine):
        drain+land under the lock before the swap, as before."""
        ts = int(timestamp if timestamp is not None else time.time())
        cfg = self.cfg
        full_export = self._fwd_out and forward_kind != "delta"
        t_start = time.monotonic_ns()
        if self._use_double_buffer:
            with self.lock:
                stages = {}
                for name, st in (("histo", self._histo_stage),
                                 ("counter", self._counter_stage),
                                 ("gauge", self._gauge_stage),
                                 ("set", self._set_stage)):
                    if st.n:
                        stages[name] = st.drain()
                imports = (self._import_centroids, self._import_sets,
                           self._import_counter_acc,
                           self._import_gauge_acc)
                self._import_centroids = DigestStage()
                self._import_sets = []
                self._import_counter_acc = {}
                self._import_gauge_acc = {}
                retired_seq = self._retire_gauge_seq()
                snap = self._swap_banks()
                dirty = self._retire_dirty()
                overflow, sidestep = self._retire_overflow()
                # the applied-op watermark AT THE SWAP: per-queue
                # application is FIFO, so every op <= this id is in the
                # retiring snapshot and every later one in the shadow
                # banks — the per-interval replay cut the time-travel
                # history tier records (ISSUE 14)
                retired_wm = self.last_import_op
                (active, status, stats_samples, dropped, histo_key_count,
                 imported, keys,
                 advance) = self._flush_bookkeeping(full_export)
            t_swap = time.monotonic_ns()
            # flight-recorder stamps: (name, t0_ns, t1_ns) on the
            # shared monotonic_ns clock, returned in stats["phases"]
            # so the server grafts them into the tick's phase tree; a
            # fourth field names the earlier stamp it nests under
            phases = [("swap", t_start, t_swap)]
            snap, overflow, did = self._land_retired(
                snap, overflow, sidestep, dirty, stages, imports,
                retired_seq)
            # the retired stage's landing belongs to this flush
            for name, n in did.items():
                imported[name] += n
            t_drain = time.monotonic_ns()
            phases.append(("drain", t_swap, t_drain))
            # the advance ran under the lock: a child of `swap`
            phases.append((*advance, "swap"))
        else:
            with self.lock:
                self.drain_all()
                self._flush_import_centroids()
                self._flush_import_sets()
                self._flush_import_scalars()
                snap = self._swap_banks()
                dirty = self._retire_dirty()
                overflow, sidestep = self._retire_overflow()
                self._retire_gauge_seq()
                retired_wm = self.last_import_op
                (active, status, stats_samples, dropped, histo_key_count,
                 imported, keys,
                 advance) = self._flush_bookkeeping(full_export)
            t_swap = time.monotonic_ns()
            phases = [("drain", t_start, t_swap), (*advance, "drain")]

        fwd_out = self._fwd_out
        host, row_of = self._flush_device(snap, phases=phases, dirty=dirty,
                                          overflow=overflow)
        self._last_flush_info.update(imported)
        self._last_flush_info.update(keys)
        if sidestep is not None:
            self._last_flush_info.update(sidestep_rows=sidestep[0],
                                         sidestep_bank=sidestep[1])
        t_device = time.monotonic_ns()

        def bank_columns(kind, infos):
            """A key table of bank `kind` in one pass: its keys, its
            slots, where each slot's row is in `host` (the slot itself
            after the full program, its compact row, the baseline row
            for a cold slot, after the incremental one) and its
            scopes."""
            bkeys, slots, scopes, _holders = zip(*infos)
            slots = np.array(slots, np.int64)
            return (bkeys, slots,
                    slots if row_of is None else row_of[kind][slots],
                    np.array(scopes, np.int64))

        def picked(column, idx) -> list:
            return list(map(column.__getitem__, idx.tolist()))

        def ships(kind, bkeys, idx, values):
            """Keys `idx` of a bank's table leave with `values`."""
            x_keys[kind].extend(picked(bkeys, idx))
            x_values[kind].append(values)

        # Delta export build (ISSUE 13): honor the request only when
        # the retired bitmap exists — it travels with exactly the bank
        # snapshot this assembly reads, so "dirty" and "this
        # interval's rows" can never skew.
        want_delta = (forward_kind == "delta" and fwd_out
                      and dirty is not None)
        frame = MetricFrame(ts, cfg.hostname)
        # the export, as columns (FlushColumns): a kind's keys in wire
        # order and its values, picked by index arrays; no tuple a key
        x_keys, x_values = ([], [], [], []), ([], [], [], [])
        x_points = _live_points((), (), ())
        x_stats = np.empty((0, 5), np.float64)

        # ---- histograms: vectorized gathers over the active set ----
        infos = active["histo"]
        if infos:
            # Aggregate matrix in f64 with the 2Sum lo terms folded back
            # in — count/sum are exact past 2^24 here, unlike any f32.
            qmat = np.asarray(host["q"], np.float64)
            if self._agg_emit:
                aggmat = np.asarray(host["aggcols"]).astype(np.float64)
                ci = self._agg_idx.get("count")
                if ci is not None:
                    aggmat[:, ci] += np.asarray(host["lo_count"],
                                                np.float64)
                si = self._agg_idx.get("sum")
                if si is not None:
                    aggmat[:, si] += np.asarray(host["lo_sum"],
                                                np.float64)
            else:
                aggmat = np.zeros((qmat.shape[0], 0), np.float64)
            ci = self._agg_idx.get("count")
            live_cnt = (aggmat[:, ci] if ci is not None
                        else np.asarray(host["cnt"], np.float64))
            bkeys, _slots, rows, scopes = bank_columns(0, infos)
            live = live_cnt[rows] > 0
            if fwd_out:
                exp_m = live & (scopes != LOCAL_ONLY)
                full_m = live & (scopes == LOCAL_ONLY)
                aggonly_m = exp_m & (scopes != GLOBAL_ONLY)
                idx = np.flatnonzero(exp_m)
                erows = rows[idx]
                x_keys[0].extend(picked(bkeys, idx))
                x_points = _live_points(host["h_weight"], host["h_mean"],
                                        erows)
                # min and max widened from f32, the three sums hi + lo
                x_stats = np.empty((len(idx), 5), np.float64)
                x_stats[:, 0] = np.asarray(host["h_min"])[erows]
                x_stats[:, 1] = np.asarray(host["h_max"])[erows]
                for j, name in ((2, "h_sum"), (3, "h_count"),
                                (4, "h_recip")):
                    x_stats[:, j] = (
                        np.asarray(host[name], np.float64)[erows]
                        + np.asarray(host[name + "_lo"],
                                     np.float64)[erows])
            else:
                full_m = live
                aggonly_m = None

            idx = np.nonzero(full_m)[0].tolist()
            if idx:
                pres = [self._histo_pres_of(infos[i]) for i in idx]
                frame.add_block(
                    [p[0] for p in pres], [p[2] for p in pres],
                    np.concatenate(
                        [qmat[rows[idx]], aggmat[rows[idx]]], axis=1),
                    self._histo_full_types)
            if aggonly_m is not None and self._agg_emit:
                idx = np.nonzero(aggonly_m)[0].tolist()
                if idx:
                    pres = [self._histo_pres_of(infos[i]) for i in idx]
                    frame.add_block(
                        [p[1] for p in pres], [p[2] for p in pres],
                        aggmat[rows[idx]], self._histo_agg_types)

        # ---- counters ----
        infos = active["counter"]
        all_infos = active.get("counter_all")
        c_tot = None
        if infos or (fwd_out and all_infos):
            c_tot = (np.asarray(host["c_hi"], np.float64)
                     + np.asarray(host["c_lo"], np.float64))
        if infos:
            bkeys, slots, rows, scopes = bank_columns(1, infos)
            totals = c_tot[rows]
            keep = range(len(infos))
            if fwd_out:
                gm = scopes == GLOBAL_ONLY
                if want_delta:
                    # DELTA wire: only counters the dirty bitmap saw
                    # land this interval. `keep` (the local frame)
                    # stays scope-driven — delta filters the WIRE,
                    # never re-scopes a key into the local flush.
                    em = gm & dirty[1][slots]
                elif all_infos is not None:
                    em = None   # FULL: exported from the whole table
                else:
                    em = gm     # no full table (mesh): touched set
                if em is not None:
                    idx = np.flatnonzero(em)
                    ships(2, bkeys, idx, totals[idx])
                keep = np.nonzero(~gm)[0].tolist()
            keep = list(keep)
            if keep:
                frame.add_block(
                    [infos[i][0].name for i in keep],
                    [self._scalar_tags_of(infos[i]) for i in keep],
                    totals[keep], (MetricType.COUNTER,))
        if fwd_out and not want_delta and all_infos:
            # FULL resync: every interned global-only counter ships,
            # idle zeros included — the receiver-liveness refresh a
            # steady-state delta deliberately skips. Wire only; the
            # local frame above stays touched-keys-only.
            bkeys, _slots, rows, scopes = bank_columns(1, all_infos)
            idx = np.flatnonzero(scopes == GLOBAL_ONLY)
            ships(2, bkeys, idx, c_tot[rows[idx]])

        # ---- gauges ----
        infos = active["gauge"]
        if infos:
            bkeys, _slots, rows, scopes = bank_columns(2, infos)
            live = np.asarray(host["g_seq"])[rows] >= 0
            vals = np.asarray(host["g_value"], np.float64)[rows]
            if fwd_out:
                gm = live & (scopes == GLOBAL_ONLY)
                idx = np.flatnonzero(gm)
                ships(3, bkeys, idx, vals[idx])
                keep = np.nonzero(live & ~gm)[0].tolist()
            else:
                keep = np.nonzero(live)[0].tolist()
            if keep:
                frame.add_block(
                    [infos[i][0].name for i in keep],
                    [self._scalar_tags_of(infos[i]) for i in keep],
                    vals[keep], (MetricType.GAUGE,))

        # ---- sets ----
        infos = active["set"]
        all_infos = active.get("set_all")
        s_regs = host.get("s_regs")
        if infos:
            bkeys, slots, rows, scopes = bank_columns(3, infos)
            ests = np.asarray(host["s_est"], np.float64)[rows]
            keep = range(len(infos))
            if fwd_out:
                fm = scopes != LOCAL_ONLY
                if want_delta:
                    # untouched set slots hold all-zero registers —
                    # the single biggest idle-key wire cost (a full
                    # register bank per key per interval); a delta
                    # ships only touched ones. Local frame unchanged.
                    em = fm & dirty[3][slots]
                elif all_infos is not None:
                    em = None   # FULL: exported from the whole table
                else:
                    em = fm
                if em is not None:
                    idx = np.flatnonzero(em)
                    ships(1, bkeys, idx, picked(s_regs, rows[idx]))
                keep = np.nonzero(~fm)[0].tolist()
            keep = list(keep)
            if keep:
                frame.add_block(
                    [infos[i][0].name for i in keep],
                    [self._scalar_tags_of(infos[i]) for i in keep],
                    ests[keep], (MetricType.GAUGE,))
        if fwd_out and not want_delta and all_infos:
            # FULL resync: every interned non-local set ships its
            # registers (idle = all-zero banks, a merge no-op that
            # keeps the key alive at the receiver)
            bkeys, _slots, rows, scopes = bank_columns(3, all_infos)
            idx = np.flatnonzero(scopes != LOCAL_ONLY)
            ships(1, bkeys, idx, picked(s_regs, rows[idx]))

        export = ForwardExport(
            set_engine=self._seng.id,
            kind="delta" if want_delta else "full",
            columns=FlushColumns(
                x_keys, *x_points, x_stats, sum(x_values[1], []),
                _joined(x_values[2]), _joined(x_values[3]))
            if fwd_out else None)

        # ---- status checks (StatusCheck sampler flush shape) ----
        status_metrics = [
            InterMetric(
                name=sc.name,
                timestamp=int(sc.timestamp or ts),
                value=float(sc.status),
                tags=list(sc.tags),
                type=MetricType.STATUS,
                message=sc.message,
                hostname=sc.hostname or cfg.hostname)
            for sc in status.values()]

        phases.append(("materialize", t_device, time.monotonic_ns()))
        stats = {
            "samples": stats_samples,
            "histo_keys": histo_key_count,
            "dropped_no_slot": dropped,
            # where the flush's time went, as flight-recorder rows:
            # `swap` is the LOCK-HELD window (under double buffering
            # the retire-and-swap only), then the out-of-lock `drain`,
            # the device program's and `materialize`
            "phases": phases,
            # import requests applied and landings run since the
            # previous flush (IMPORT_PHASES rows, this flush's own
            # landing among them)
            "import_phases": ([] if self.land_stamps is None
                              else self.land_stamps.take()),
            # which device path ran (full vs incremental + dirty/pile
            # counts) — bench/test introspection, also what an
            # operator correlates the gather/scatter phases against
            "flush_path": dict(self._last_flush_info),
            # what the histogram landings' overflow handling did this
            # interval (veneur.ingest.overflow_*_total)
            "overflow_rows": self._last_flush_info.get("overflow_rows", 0),
            "overflow_bank": self._last_flush_info.get("overflow_bank", 0),
            # hot rows the hot-slot sidestep landed through a work set,
            # and whole-bank passes it made
            # (veneur.ingest.sidestep_*_total)
            "sidestep_rows": self._last_flush_info.get("sidestep_rows", 0),
            "sidestep_bank": self._last_flush_info.get("sidestep_bank", 0),
            # import batches applied this interval and the metrics in
            # them (veneur.import.batches_total / batch_metrics_total)
            "import_batches": imported["import_batches"],
            "import_metrics": imported["import_metrics"],
            # rows its clustered landings took through a work set, and
            # landings that took the whole-bank passes
            # (veneur.import.land_rows_total / land_bank_total)
            "import_land_rows": imported["import_land_rows"],
            "import_land_bank": imported["import_land_bank"],
            # keys the four tables minted in the interval, evicted at
            # this flush and hold after it (veneur.keys.interned_total
            # / .evicted_total / .live; by bank in `flush_path`)
            **{name: sum(by_bank) for name, by_bank in keys.items()},
            # its sketches by the path that decoded them, and the key
            # dictionary's hits and misses (veneur.import.decode_*)
            **{name: imported[name] for name in DECODE_TALLY},
            # what the export build actually shipped (delta requests
            # degrade to full when no bitmap exists — mesh, tracking
            # off — or the engine does not forward)
            "forward_kind": export.kind,
            # the swap-time applied-op watermark (the history tier's
            # per-interval replay cut, ISSUE 14)
            "retired_import_op": retired_wm,
        }
        return FlushResult(frame=frame, export=export, stats=stats,
                           status_metrics=status_metrics)

    # ---- presentation caches (names/tags reused across flushes) ----
    # Cached on the interner's per-key SlotInfo holder: a plain attribute
    # read per key instead of a MetricKey hash, and the cache dies with
    # the entry on eviction. The joined-tags split is additionally shared
    # across keys (many keys carry identical tag sets).

    def _tags_of(self, joined: str) -> list:
        tl = self._tags_cache.get(joined)
        if tl is None:
            if len(self._tags_cache) > self._pres_bound:
                self._tags_cache.clear()
            tl = joined.split(",") if joined else []
            self._tags_cache[joined] = tl
        return tl

    def _scalar_tags_of(self, info) -> list:
        holder = info[3]
        tl = holder.pres
        if tl is None:
            tl = holder.pres = self._tags_of(info[0].joined_tags)
        return tl

    def _histo_pres_of(self, info) -> tuple:
        holder = info[3]
        pr = holder.pres
        if pr is None:
            key = info[0]
            nm = key.name
            full = tuple([nm + s for s in self._pct_sufs]
                         + [f"{nm}.{a}" for a in self._agg_emit])
            pr = holder.pres = (full, full[len(self._pct_sufs):],
                                self._tags_of(key.joined_tags))
        return pr

    def drain_events(self):
        with self.lock:
            evs, self._pending_events = self._pending_events, []
        return evs, []

    # ------------- engine checkpoint/restore (durability, ISSUE 9) ----
    # Serialization stays single-homed in durability/records.py (vlint
    # DR02): these methods move numpy arrays, never raw bytes.

    def _bank_table(self):
        """(kind, bank attr name, interner) rows in the fixed record
        order durability/records.py's BANK_* constants name."""
        return ((0, "histo_bank", self.histo_keys),
                (1, "counter_bank", self.counter_keys),
                (2, "gauge_bank", self.gauge_keys),
                (3, "set_bank", self.set_keys))

    @property
    def engine_stamp(self) -> str:
        """The wire stamp of this engine's sketch pair — what the
        forwarders send and the import paths compare against."""
        return sketches.engine_stamp(self._heng, self._seng)

    def engines_describe(self) -> dict:
        """JSON-ready sketch-engine description (/debug/flush)."""
        return sketches.describe(self._heng, self._seng)

    def bank_leaf_names(self, kind: int) -> tuple:
        """The durability leaf order for one bank kind — engine-aware
        (the histogram and set banks' leaves are the selected engine's;
        counter/gauge leaves are engine-independent)."""
        if kind == 0:
            return self._heng.bank_leaves
        if kind == 3:
            return self._seng.bank_leaves
        from ..durability import records as drecords
        return drecords.BANK_LEAVES[kind]

    def enable_dirty_tracking(self, delta_threshold: float = 0.5):
        """Arm per-bank dirty-slot bitmaps for the CHECKPOINT consumer
        (the Server calls this when durability_engine_snapshot is on).
        The incremental flush arms the same bitmaps in __init__ by
        default; existing marks are preserved — rebuilding them here
        would desync both consumers from rows already landed.
        `delta_threshold` is the dirty fraction above which
        checkpoint_state fetches whole leaves and slices on host
        instead of a device-side row gather (a near-full gather costs
        more than the contiguous fetch)."""
        with self.lock:
            self._delta_threshold = float(delta_threshold)
            if self._dirty is None:
                self._dirty = [
                    np.zeros(getattr(self, attr).num_slots, bool)
                    for _kind, attr, _ki in self._bank_table()]

    def _mark_dirty(self, kind: int, slots):
        """Record device-landing touches on the LIVE bitmap. Call
        sites guard on self._dirty so the untracked case costs one
        attribute load."""
        self._mark_dirty_into(self._dirty, kind, slots)

    @staticmethod
    def _mark_dirty_into(dirty, kind: int, slots):
        """Record device-landing touches on an explicit bitmap set —
        the live one, or a retired interval's (the double-buffered
        flush lands retired stages/imports AFTER the swap; their
        touches belong to the retiring flush's dirty set, never the
        new banks' checkpoint bitmap)."""
        d = dirty[kind]
        s = np.asarray(slots)
        if s.size:
            d[s[(s >= 0) & (s < d.size)]] = True

    def checkpoint_state(self) -> dict:
        """One engine's flush-boundary checkpoint, taken under the
        ingest lock so it is a consistent cut: dirty bank rows (banks
        are interval-scoped, so fresh init + these rows IS the state),
        the full interner tables, the staged-but-unlanded import
        accumulators, the gauge sequence, and the applied-op watermark
        — everything restore_checkpoint needs, as numpy arrays (the
        byte encoding lives in durability/records.py)."""
        from ..durability import records as drecords
        with self.lock:
            banks: dict = {}
            piles_total = piles_dirty = 0
            for kind, attr, _ki in self._bank_table():
                bank = getattr(self, attr)
                d = self._dirty[kind]
                ids = np.nonzero(d)[0].astype(np.int32)
                piles_total += d.size
                piles_dirty += ids.size
                leaves: dict = {}
                if ids.size:
                    gather = ids.size < self._delta_threshold * d.size
                    for name in self.bank_leaf_names(kind):
                        leaf = getattr(bank, name)
                        if gather:
                            leaves[name] = np.asarray(
                                jax.device_get(leaf[ids]))
                        else:
                            leaves[name] = np.asarray(leaf)[ids]
                banks[kind] = (ids, leaves)
            interner = {
                kind: (ki.interval, ki.snapshot_entries())
                for kind, _attr, ki in self._bank_table()}
            staged = {
                "centroids": self._import_centroids.items(),
                "sets": list(self._import_sets),
                "counters": list(self._import_counter_acc.items()),
                "gauges": list(self._import_gauge_acc.items()),
            }
            return {
                "fingerprint": drecords.engine_fingerprint(
                    self.cfg, self.histo_bank.num_centroids),
                "gauge_seq": self._gauge_seq,
                "last_import_op": self.last_import_op,
                "interner": interner,
                "banks": banks,
                "staged": staged,
                "leaf_names": {
                    kind: self.bank_leaf_names(kind)
                    for kind, _attr, _ki in self._bank_table()},
                "piles_total": piles_total,
                "piles_dirty": piles_dirty,
            }

    def restore_checkpoint(self, fingerprint, gauge_seq: int,
                           watermark: int, interner: dict, banks: dict,
                           staged: dict):
        """Rebuild this (freshly constructed) engine from a decoded
        checkpoint group: leaves are composed on host from the exact
        fresh-init baseline plus the journaled rows, then committed to
        the device in one device_put per leaf. Raises ValueError on a
        shape-fingerprint mismatch — the Server refuses the whole
        recovery loudly rather than scattering rows into wrong slots."""
        from ..durability import records as drecords
        want = drecords.engine_fingerprint(self.cfg,
                                           self.histo_bank.num_centroids)
        if tuple(fingerprint) != want:
            raise ValueError(
                f"engine checkpoint fingerprint {tuple(fingerprint)} "
                f"does not match this engine's shape {want}")
        with self.lock:
            new_banks = {}
            for kind, attr, _ki in self._bank_table():
                bank = getattr(self, attr)
                ids, leaves = banks.get(kind, (np.zeros(0, np.int32), {}))
                if len(ids) == 0:
                    new_banks[attr] = bank     # fresh rows, already right
                    continue
                host = {}
                for name in self.bank_leaf_names(kind):
                    # fetch the fresh-init baseline (exact: vmin=+inf
                    # rows etc. come from the same _fresh_fn output the
                    # live process swapped in), overlay the rows
                    full = np.array(np.asarray(getattr(bank, name)))
                    full[ids] = leaves[name]
                    host[name] = jax.device_put(full, self._device)
                new_banks[attr] = type(bank)(**host)
            # SR02 invariant note: the histo rows restored above are
            # bit-exact copies of rows an invariant-holding compress
            # wrote before the checkpoint — restore preserves whatever
            # cluster order the owning kernel produced
            self.histo_bank = new_banks["histo_bank"]
            self.counter_bank = new_banks["counter_bank"]
            self.gauge_bank = new_banks["gauge_bank"]
            self.set_bank = new_banks["set_bank"]
            for kind, _attr, ki in self._bank_table():
                interval, entries = interner.get(kind, (0, []))
                ki.restore(interval, entries)
                # restored rows deviate from fresh: the next checkpoint
                # must serialize them again
                ids, _leaves = banks.get(kind,
                                         (np.zeros(0, np.int32), {}))
                if self._dirty is not None and len(ids):
                    self._dirty[kind][ids] = True
            self._import_centroids = DigestStage.of_items(
                staged.get("centroids", []))
            self._import_sets = [(int(s), np.asarray(r, np.uint8))
                                 for s, r in staged.get("sets", [])]
            self._import_counter_acc = {
                int(s): float(v) for s, v in staged.get("counters", [])}
            self._import_gauge_acc = {
                int(s): float(v) for s, v in staged.get("gauges", [])}
            self._gauge_seq = int(gauge_seq)
            self.last_import_op = int(watermark)

    def dirty_stats(self) -> tuple:
        """(dirty piles, total piles) across the four banks — the
        veneur.durability.engine_snapshot_piles_* gauges."""
        if self._dirty is None:
            return (0, 0)
        with self.lock:
            return (sum(int(d.sum()) for d in self._dirty),
                    sum(d.size for d in self._dirty))

"""The local flush hands the forwarder its export as columns (ISSUE 51).

`AggregationEngine.flush` builds no tuple a key: the export it returns
holds `FlushColumns` (key lists and value arrays picked from the
fetched planes by index arrays), `wire.export_columns` hands them to
the native pass as they are, and whoever reads an entry list gets the
parent's tuples built from the columns on the first read.

The reference is the parent's assembly, kept here as PR 47 kept
`_chunk_bounds`': `_parent_export` runs its loops, a statement a key,
over the same fetched planes, row maps and key tables the flush had
(spied out of the engine), and everything the flush-built export gives
(columns field by field and dtype by dtype, the pass's bytes, the lazy
lists through every reader of tuples) must equal what that gives.
Host code and tiny engines on the CPU; no subprocess.
"""

import json

import numpy as np
import pytest

from tests.test_wire_native_encode import _forwarder
from veneur_tpu.cluster import wire
from veneur_tpu.cluster.forward import HttpJsonForwarder, _export_tail
from veneur_tpu.cluster.protos import forward_pb2
from veneur_tpu.config import read_config
from veneur_tpu.durability import ForwardJournal, records
from veneur_tpu.ingest.parser import (GLOBAL_ONLY, LOCAL_ONLY, MIXED_SCOPE,
                                      MetricKey, UDPMetric)
from veneur_tpu.models import pipeline
from veneur_tpu.models.pipeline import (AggregationEngine, EngineConfig,
                                        ForwardExport, _ColdTail)
from veneur_tpu.observe.registry import TelemetryRegistry
from veneur_tpu.resilience import (Egress, ForwardEnvelope,
                                   PartialDeliveryError,
                                   ResilienceRegistry, ResilientForwarder,
                                   SpillBuffer, TerminalEgressError,
                                   _export_size)
from veneur_tpu.server import Server

needs_library = pytest.mark.skipif(
    wire.native_encode_fn() is None,
    reason="native/vtpu_wire.cpp cannot be built here")


# ---- the parent's assembly of the export: a statement a key ----

def _parent_export(eng, seen, forward_kind) -> ForwardExport:
    """`flush()`'s export as PR 50 built it, from what this flush had
    in hand (`seen`: the fetched planes, the row maps, the key tables,
    the retired bitmap): one tuple a key, appended in a loop."""
    host, row_of, active, dirty = (seen["host"], seen["row_of"],
                                   seen["active"], seen["dirty"])

    def slot_rows(kind, infos):
        slots = np.fromiter((t[1] for t in infos), np.int64, len(infos))
        return slots, (slots if row_of is None else row_of[kind][slots])

    want_delta = forward_kind == "delta" and dirty is not None
    export = ForwardExport(set_engine=eng._seng.id,
                           kind="delta" if want_delta else "full")
    infos = active["histo"]
    if infos:
        if eng._agg_emit and eng._agg_idx.get("count") is not None:
            live_cnt = (
                np.asarray(host["aggcols"]).astype(np.float64)[
                    :, eng._agg_idx["count"]]
                + np.asarray(host["lo_count"], np.float64))
        else:
            live_cnt = np.asarray(host["cnt"], np.float64)
        n = len(infos)
        _slots, rows = slot_rows(0, infos)
        scopes = np.fromiter((t[2] for t in infos), np.int64, n)
        live = live_cnt[rows] > 0
        h_sum = (np.asarray(host["h_sum"], np.float64)
                 + np.asarray(host["h_sum_lo"], np.float64))
        h_count = (np.asarray(host["h_count"], np.float64)
                   + np.asarray(host["h_count_lo"], np.float64))
        h_recip = (np.asarray(host["h_recip"], np.float64)
                   + np.asarray(host["h_recip_lo"], np.float64))
        exp_m = live & (scopes != LOCAL_ONLY)
        for i in np.nonzero(exp_m)[0].tolist():
            key, row = infos[i][0], rows[i]
            w = host["h_weight"][row]
            nz = w > 0
            export.histograms.append((
                key, host["h_mean"][row][nz], w[nz],
                float(host["h_min"][row]), float(host["h_max"][row]),
                float(h_sum[row]), float(h_count[row]),
                float(h_recip[row])))
    infos = active["counter"]
    all_infos = active.get("counter_all")
    c_tot = (np.asarray(host["c_hi"], np.float64)
             + np.asarray(host["c_lo"], np.float64))
    if infos:
        n = len(infos)
        slots, rows = slot_rows(1, infos)
        totals = c_tot[rows]
        scopes = np.fromiter((t[2] for t in infos), np.int64, n)
        gm = scopes == GLOBAL_ONLY
        if want_delta:
            em = gm & dirty[1][slots]
        elif all_infos is not None:
            em = None
        else:
            em = gm
        if em is not None:
            for i in np.nonzero(em)[0].tolist():
                export.counters.append((infos[i][0], float(totals[i])))
    if not want_delta and all_infos:
        _slots, rows = slot_rows(1, all_infos)
        for (key, _slot, scope, _h), row in zip(all_infos, rows):
            if scope == GLOBAL_ONLY:
                export.counters.append((key, float(c_tot[row])))
    infos = active["gauge"]
    if infos:
        n = len(infos)
        _slots, rows = slot_rows(2, infos)
        live = np.asarray(host["g_seq"])[rows] >= 0
        vals = np.asarray(host["g_value"], np.float64)[rows]
        scopes = np.fromiter((t[2] for t in infos), np.int64, n)
        gm = live & (scopes == GLOBAL_ONLY)
        for i in np.nonzero(gm)[0].tolist():
            export.gauges.append((infos[i][0], float(vals[i])))
    infos = active["set"]
    all_infos = active.get("set_all")
    if infos:
        n = len(infos)
        slots, rows = slot_rows(3, infos)
        scopes = np.fromiter((t[2] for t in infos), np.int64, n)
        fm = scopes != LOCAL_ONLY
        if want_delta:
            em = fm & dirty[3][slots]
        elif all_infos is not None:
            em = None
        else:
            em = fm
        if em is not None:
            for i in np.nonzero(em)[0].tolist():
                export.sets.append((infos[i][0], host["s_regs"][rows[i]]))
    if not want_delta and all_infos:
        _slots, rows = slot_rows(3, all_infos)
        for (key, _slot, scope, _h), row in zip(all_infos, rows):
            if scope != LOCAL_ONLY:
                export.sets.append((key, host["s_regs"][row]))
    return export


def _flush_seen(eng, **kw):
    """(the flush's result, what it had in hand for `_parent_export`)."""
    seen = {}
    device, book = eng._flush_device, eng._flush_bookkeeping

    def spy_device(snap, phases=None, dirty=None, overflow=None):
        got = device(snap, phases=phases, dirty=dirty, overflow=overflow)
        seen["host"], seen["row_of"] = got
        seen["dirty"] = dirty
        return got

    def spy_book(full_export=False):
        got = book(full_export)
        seen["active"] = got[0]
        return got

    eng._flush_device, eng._flush_bookkeeping = spy_device, spy_book
    try:
        res = type(eng).flush(eng, **kw)
    finally:
        del eng._flush_device, eng._flush_bookkeeping
    return res, seen


# ---- the engines, and what they are fed ----

def _engine(incremental: bool, tracked: bool = False):
    eng = AggregationEngine(EngineConfig(
        histogram_slots=256, counter_slots=64, gauge_slots=64,
        set_slots=32, batch_size=256, buffer_depth=32,
        percentiles=(0.5, 0.99), aggregates=("min", "max", "count"),
        forward_enabled=True, flush_incremental=incremental,
        flush_incremental_threshold=1.0,
        flush_double_buffer=incremental))
    if tracked:
        eng.enable_dirty_tracking()
    return eng


_TAGS = ("", "env:prod,host:a", "k:v,région:é", ",", "k:時")
_SCOPES = (MIXED_SCOPE, LOCAL_ONLY, GLOBAL_ONLY)


def _feed(eng, rng, timers=30):
    """Keys of all three scopes and of every kind: timers and
    histograms of a few to a few hundred samples, an imported digest
    with statistics and no centroid, one whose centroids are all dead,
    counters (one past 2^24), gauges (one of -0.0), sets, unicode and
    empty tags."""
    for k in range(timers):
        key = MetricKey(f"m.t{k}", "timer" if k % 3 else "histogram",
                        _TAGS[k % len(_TAGS)])
        s = eng.histo_keys.lookup(key, _SCOPES[k % 3])
        n = int(rng.integers(1, 300 if k % 7 == 0 else 12))
        eng.ingest_histo_batch(np.full(n, s, np.int32),
                               rng.gamma(2, 20, n).astype(np.float32),
                               np.ones(n, np.float32), count=n)
    eng.import_histogram(MetricKey("m.empty", "timer", "k:v"),
                         np.empty(0, np.float32), np.empty(0, np.float32),
                         1.0, 2.0, 3.0, 4.0, 0.5)
    eng.import_histogram(MetricKey("m.dead", "histogram", ""),
                         np.array([5.0, 6.0], np.float32),
                         np.zeros(2, np.float32), -1.0, 9.0, 11.0, 2.0, 0.0)
    for k in range(9):
        key = MetricKey(f"m.c{k}", "counter", _TAGS[k % len(_TAGS)])
        s = eng.counter_keys.lookup(key, _SCOPES[k % 3])
        v = np.float32(1e7 if k == 2 else rng.normal(5, 1))
        eng.ingest_counter_batch(np.full(3, s, np.int32),
                                 np.full(3, v, np.float32),
                                 np.ones(3, np.float32), count=3)
    for k in range(7):
        key = MetricKey(f"m.g{k}", "gauge", _TAGS[k % len(_TAGS)])
        s = eng.gauge_keys.lookup(key, _SCOPES[(k + 2) % 3])
        v = np.float32(-0.0 if k == 0 else rng.normal())
        eng.ingest_gauge_batch(np.full(2, s, np.int32),
                               np.full(2, v, np.float32), count=2)
    for k in range(4):
        for v in range(15):
            eng.process(UDPMetric(
                MetricKey(f"m.sé{k}", "set", _TAGS[k % len(_TAGS)]),
                0, f"u{v}", 1.0, _SCOPES[k % 3]))


def _intern_idle(eng):
    """Keys that hold a slot and see no sample: a FULL export ships
    their zero rows, a set's as a view of the baseline row."""
    eng.counter_keys.lookup(MetricKey("m.c.idle", "counter", ""),
                            GLOBAL_ONLY)
    eng.set_keys.lookup(MetricKey("m.s.idle", "set", "k:v"), MIXED_SCOPE)


ARMS = {
    # name: (incremental program, dirty tracking, the kind asked for)
    "full_program": (False, False, "full"),
    "incremental_cold_tail": (True, False, "full"),
    "incremental_delta": (True, False, "delta"),
    "full_program_delta": (False, True, "delta"),
}


@pytest.fixture(scope="module")
def flushed():
    """name -> [(flush-built export, the parent's tuples for it)], two
    intervals an arm: a busy one, then one that touches a few keys
    among the interned (cold rows, the baseline row)."""
    out = {}
    for name, (incremental, tracked, kind) in ARMS.items():
        rng = np.random.default_rng(51)
        eng = _engine(incremental, tracked)
        pairs = []
        for interval in range(2):
            if interval == 0:
                _feed(eng, rng)
                _intern_idle(eng)
            else:
                _feed(eng, rng, timers=4)
            res, seen = _flush_seen(eng, timestamp=10 + interval,
                                    forward_kind=kind)
            assert res.export.kind == kind
            want = "incremental" if incremental else "full"
            assert res.stats["flush_path"]["path"] == want
            assert isinstance(seen["host"]["h_mean"], _ColdTail) \
                == incremental
            pairs.append((res.export, _parent_export(eng, seen, kind)))
        out[name] = pairs
    pipeline.release_executables()
    return out


def _again(export) -> ForwardExport:
    """The same flush-built export, unread: a reader's own copy."""
    assert export.columns is not None
    return ForwardExport(set_engine=export.set_engine, kind=export.kind,
                         columns=export.columns)


def _same_field(a, b):
    if isinstance(a, bytes):
        assert a == b
        return
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


def _same_tuples(got, want):
    """Two exports' entry lists, element for element: keys, arrays bit
    for bit with their dtypes, Python floats by their bits."""
    for kind in ("histograms", "sets", "counters", "gauges"):
        a, b = getattr(got, kind), getattr(want, kind)
        assert len(a) == len(b), kind
        for x, y in zip(a, b):
            assert len(x) == len(y) and x[0] == y[0]
            for u, v in zip(x[1:], y[1:]):
                if isinstance(v, np.ndarray):
                    _same_field(np.asarray(u), v)
                else:
                    assert type(u) is type(v) is float
                    assert np.float64(u).tobytes() == np.float64(v).tobytes()
    assert got.set_engine == want.set_engine and got.kind == want.kind


CASES = [(name, i) for name in ARMS for i in range(2)]


# ---- (i) the columns and the pass's bytes ----

@pytest.mark.parametrize("name,interval", CASES)
def test_the_flushs_columns_are_the_parents_tuples_read_back(
        flushed, name, interval):
    export, parent = flushed[name][interval]
    assert export.columns is not None and parent.columns is None
    got = wire.export_columns(_again(export))
    want = wire.export_columns(parent)
    assert int(got.counts.sum()) > 0
    for field, a, b in zip(got._fields, got, want):
        _same_field(a, b)
    if interval == 0:
        # every kind is there, both wire types, a digest without a
        # centroid, a counter past 2^24 and a gauge of -0.0
        assert got.counts.min() > 0 and len(set(got.types.tolist())) == 2
        assert (np.diff(got.cent_off) == 0).sum() >= 2
        assert got.counters.max() == 3e7
        assert np.signbit(got.gauges).any() or name.endswith("delta")


@needs_library
@pytest.mark.parametrize("name,interval", CASES)
def test_the_pass_writes_the_same_bytes_from_either(flushed, name, interval):
    export, parent = flushed[name][interval]
    fn = wire.native_encode_fn()
    unread = _again(export)
    got = wire.encode_export(unread, fn)
    want = wire.encode_export(parent, fn)
    assert got.off == want.off
    assert got.sizes.tolist() == want.sizes.tolist()
    assert bytes(got.data[:got.off[-1]]) == bytes(want.data[:want.off[-1]])
    # ... and built no tuple on the way
    assert unread._lists == [None] * 4 and unread.lazy_built == 0


def _planes(rng, rows, lanes, dense):
    w = np.where(rng.random((rows, lanes)) < dense,
                 rng.random((rows, lanes)) + 0.5, 0.0).astype(np.float32)
    w[rng.random(rows) < 0.2] = 0.0             # digests with no centroid
    w[0, 0] = np.nan                            # not > 0: dropped
    return w, rng.normal(size=(rows, lanes)).astype(np.float32)


@pytest.mark.parametrize("form", ["plane", "cold_tail", "cold_tail_of_none"])
@pytest.mark.parametrize("dense", [0.05, 0.9])
def test_live_points_are_the_loops_concatenation(monkeypatch, form, dense):
    """`_live_points` against the parent's `mean[row][w > 0]` a row, over
    a plain plane and a `_ColdTail` whose index D (the cold row) is
    asked for among the rows, in blocks of a few rows."""
    monkeypatch.setattr(pipeline, "_POINT_BLOCK_BYTES", 7 * 4 * 24)
    rng = np.random.default_rng(3)
    w, m = _planes(rng, 40, 24, dense)
    rows = rng.integers(0, 40, 60)
    if form == "plane":
        hw, hm = w, m
    else:
        d = 0 if form == "cold_tail_of_none" else 40
        cold_w = np.zeros(24, np.float32)
        cold_w[3] = 2.0                         # a cold row that is live
        cold_m = np.full(24, 7.0, np.float32)
        hw = _ColdTail(w[:d] if d else (), cold_w)
        hm = _ColdTail(m[:d] if d else (), cold_m)
        rows = np.where(rng.random(60) < 0.3, d, np.minimum(rows, max(d - 1, 0)))
        if not d:
            rows[:] = 0
    off, means, weights = pipeline._live_points(hw, hm, rows)
    want_m = [hm[r][hw[r] > 0] for r in rows.tolist()]
    want_w = [hw[r][hw[r] > 0] for r in rows.tolist()]
    assert off.dtype == np.int64 and off[0] == 0
    assert off[1:].tolist() == np.cumsum(list(map(len, want_m))).tolist()
    _same_field(means, np.concatenate(want_m))
    _same_field(weights, np.concatenate(want_w))
    assert (weights > 0).all() and len(weights) > 0


# ---- (ii) the lazy lists, through every reader of tuples ----

@pytest.mark.parametrize("name,interval", CASES)
def test_the_lists_built_on_first_read_are_the_parents(flushed, name,
                                                       interval):
    export, parent = flushed[name][interval]
    lazy = _again(export)
    _same_tuples(lazy, parent)
    assert lazy.lazy_built == sum(parent.counts()) == _export_size(lazy)
    # reading changes nothing: the columns are still the flush's
    assert lazy.columns is export.columns
    assert isinstance(lazy.histograms, list)
    assert lazy.counters == parent.counters
    assert lazy.histograms[:0] == [] and lazy.counters[1:2] == \
        parent.counters[1:2]


def _serialized(metrics) -> bytes:
    return forward_pb2.MetricList(metrics=metrics).SerializeToString()


@pytest.mark.parametrize("codec", ["lossless", "q16"])
@pytest.mark.parametrize("name", ARMS)
def test_export_to_metrics_reads_the_parents_tuples(flushed, name, codec):
    export, parent = flushed[name][0]
    assert _serialized(wire.export_to_metrics(_again(export), codec)) \
        == _serialized(wire.export_to_metrics(parent, codec))


@pytest.mark.parametrize("name", ARMS)
def test_a_partial_deliverys_tail_cut_inside_each_kind(flushed, name):
    export, parent = flushed[name][0]
    n_h, n_s, n_c, n_g = parent.counts()
    cuts = [0, n_h // 2, n_h, n_h + n_s // 2, n_h + n_s + n_c // 2,
            n_h + n_s + n_c + n_g // 2, n_h + n_s + n_c + n_g]
    for cut in cuts:
        got = _export_tail(_again(export), cut)
        want = _export_tail(parent, cut)
        got.set_engine, got.kind = want.set_engine, want.kind
        _same_tuples(got, want)
        assert got.columns is None
        assert sum(got.counts()) == sum(parent.counts()) - cut


@pytest.mark.parametrize("name", ARMS)
def test_a_spill_and_its_re_merge_hold_what_the_parents_did(flushed, name):
    first, second = flushed[name]

    def through(a, b):
        spill = SpillBuffer(destination="d", registry=ResilienceRegistry())
        assert spill.spill(a) == sum(a.counts())
        return spill.merge_into(b)

    got = through(_again(first[0]), _again(second[0]))
    want = through(first[1], ForwardExport(
        histograms=list(second[1].histograms), sets=list(second[1].sets),
        counters=list(second[1].counters), gauges=list(second[1].gauges),
        set_engine=second[1].set_engine, kind=second[1].kind))
    _same_tuples(got, want)
    # the re-merge changed the lists: what is sent is read from them
    assert got.columns is None
    for a, b in zip(wire.export_columns(got), wire.export_columns(want)):
        _same_field(a, b)


@pytest.mark.parametrize("name", ARMS)
def test_a_journal_record_round_trips_the_parents_tuples(flushed, name):
    export, parent = flushed[name][0]
    kind = parent.kind
    got = records.encode_begin(7, 0, 0, 0, _again(export), kind)
    assert got == records.encode_begin(7, 0, 0, 0, parent, kind)
    seq, _off, _cnt, _age, back, back_kind = records.decode_begin(got)
    assert (seq, back_kind) == (7, kind)
    assert back.counts() == parent.counts() and back.columns is None
    # exact counters ride the side channel
    assert back.counters == parent.counters


@pytest.mark.parametrize("name", ARMS)
def test_the_http_forwarder_posts_the_parents_body(flushed, name):
    export, parent = flushed[name][0]

    def post(ex):
        bodies, reg = [], TelemetryRegistry()

        def transport(req, timeout=None):
            from veneur_tpu.utils.faults import _FakeResponse
            bodies.append(req.data)
            return _FakeResponse(200)

        fwd = HttpJsonForwarder("http://x", max_per_body=11, egress=Egress(
            "h", registry=reg, transport=transport))
        fwd(ex, envelope=ForwardEnvelope("s", 3))
        return bodies, reg

    got, reg = post(_again(export))
    want, reg_p = post(parent)
    assert got == want and len(got) > 1
    assert json.loads(got[0])
    # every sketch of the flush-built export became a tuple for it
    n = sum(parent.counts())
    assert reg.total("h", "forward.export_lazy") == n
    assert reg.total("h", "forward.export_direct") == 0
    assert reg_p.total("h", "forward.export_lazy") == 0


# ---- (iii) a mutation drops the columns ----

def _append(ex):
    ex.gauges.append((MetricKey("late.g", "gauge", ""), 1.5))


def _extend(ex):
    ex.counters.extend([(MetricKey("late.c", "counter", "a:b"), 2.0)])


def _prepend(ex):
    ex.histograms[:0] = [(MetricKey("late.h", "timer", ""),
                          np.array([1.0], np.float32),
                          np.array([2.0], np.float32), 1.0, 1.0, 2.0, 2.0,
                          2.0)]


def _assign(ex):
    ex.sets = ex.sets[:1]


def _delete(ex):
    del ex.counters[0]


def _clear_slice(ex):
    ex.gauges[:] = []


@pytest.mark.parametrize("change", [_append, _extend, _prepend, _assign,
                                    _delete, _clear_slice])
def test_a_changed_list_drops_the_columns(flushed, change):
    export, parent = flushed["full_program"][0]
    got = _again(export)
    want = ForwardExport(
        histograms=list(parent.histograms), sets=list(parent.sets),
        counters=list(parent.counters), gauges=list(parent.gauges),
        set_engine=parent.set_engine, kind=parent.kind)
    assert got.columns is not None
    change(got)
    change(want)
    assert got.columns is None
    _same_tuples(got, want)
    assert got.counts() == want.counts() == tuple(
        map(len, (got.histograms, got.sets, got.counters, got.gauges)))
    # export_columns now reads the lists, the change among them
    for a, b in zip(wire.export_columns(got), wire.export_columns(want)):
        _same_field(a, b)
    # a list changed once is a plain list's equal from then on
    got.gauges.append((MetricKey("later.g", "gauge", ""), 0.0))
    assert got.counts()[3] == want.counts()[3] + 1


def test_a_hand_built_export_is_four_plain_lists():
    ex = ForwardExport()
    assert ex.columns is None and ex.counts() == (0, 0, 0, 0)
    assert all(type(entries) is list for entries in ex._lists)
    key = MetricKey("c", "counter", "")
    ex.counters.append((key, 1.0))
    ex.sets = [(MetricKey("s", "set", ""), np.zeros(16, np.uint8))]
    assert ex.counts() == (0, 1, 1, 0) and ex.lazy_built == 0
    again = ForwardExport(counters=[(key, 1.0)], kind="delta",
                          set_engine="ull")
    assert again.counters == ex.counters
    assert (again.kind, again.set_engine, again.prefix_sketches) == (
        "delta", "ull", [])
    assert "counters=1" in repr(again)


# ---- (iv) counting is not reading ----

def _export_phase(fwd, export):
    """The `forward.export` phase's attributes of one send."""
    from veneur_tpu.observe import FlightRecorder
    from veneur_tpu.observe import recorder as rec

    flight = FlightRecorder(capacity=2)
    tick = flight.begin_tick(1)
    token = rec.set_current_tick(tick, -1)
    try:
        fwd(export)
    finally:
        rec.reset_current_tick(token)
    flight.end_tick(tick)
    (row,) = [s for s in tick._slots[:tick.n] if s.name == "forward.export"]
    return row.meta


@needs_library
@pytest.mark.parametrize("name", ARMS)
def test_a_grpc_send_builds_no_tuple_and_says_so(flushed, name):
    export, parent = flushed[name][0]
    n = sum(parent.counts())
    unread = _again(export)
    inner, sent, reg = _forwarder(max_per_batch=13)
    fwd = ResilientForwarder(inner, destination="g",
                             registry=ResilienceRegistry())
    assert _export_size(unread) == n and any(unread.counts())
    meta = _export_phase(fwd, unread)
    assert meta == {"n_metrics": n, "encode_native": n,
                    "encode_fallback": 0, "export_direct": n,
                    "export_tuples": 0}
    assert reg.total("g", "forward.export_direct") == n
    assert reg.total("g", "forward.export_lazy") == 0
    assert reg.total("g", "forward.encode_fallback") == 0
    assert unread._lists == [None] * 4 and fwd.pending_spill == 0
    # the same requests as the parent's tuples give, chunk by chunk
    inner_p, sent_p, _reg_p = _forwarder(max_per_batch=13)
    meta_p = _export_phase(ResilientForwarder(
        inner_p, destination="g", sender_id=fwd.sender_id, seq_start=1,
        registry=ResilienceRegistry()), parent)
    assert meta_p["export_tuples"] == n and meta_p["export_direct"] == 0
    assert len(sent) == len(sent_p) > 1
    strip = forward_pb2.MetricList.FromString
    for a, b in zip(sent, sent_p):
        assert strip(a).metrics == strip(b).metrics


@needs_library
def test_a_journal_reads_tuples_first_and_is_counted(flushed, tmp_path):
    export, parent = flushed["incremental_cold_tail"][0]
    n = sum(parent.counts())
    inner, sent, reg = _forwarder()
    journal = ForwardJournal(str(tmp_path), fsync="never")
    fwd = ResilientForwarder(inner, destination="g", sender_id="s",
                             seq_start=1, journal=journal,
                             registry=ResilienceRegistry())
    meta = _export_phase(fwd, _again(export))
    journal.close()
    # the write-ahead built every tuple; the send still took the columns
    assert meta["export_direct"] == n and meta["export_tuples"] == 0
    assert reg.total("g", "forward.export_lazy") == n
    assert len(sent) == 1


@needs_library
def test_the_q16_row_and_a_missing_library_read_tuples(flushed):
    export, parent = flushed["full_program"][0]
    n = sum(parent.counts())
    for how in ({"centroid_codec": "q16"}, {}):
        fwd, sent, reg = _forwarder(**how)
        if not how:
            fwd._encode = None
        meta = _export_phase(fwd, _again(export))
        assert meta["export_direct"] == 0 and meta["export_tuples"] == n
        assert meta["encode_fallback"] == n
        assert reg.total("g", "forward.export_lazy") == n
        assert reg.total("g", "forward.export_direct") == 0


@needs_library
def test_a_partial_deliverys_tuples_are_counted(flushed):
    export, parent = flushed["full_program"][0]
    calls = []

    def send(req, timeout=None):
        calls.append(req)
        if len(calls) == 2:
            raise TerminalEgressError("down")

    fwd, _sent, reg = _forwarder(send=send, max_per_batch=10)
    with pytest.raises(PartialDeliveryError) as err:
        fwd(_again(export), envelope=ForwardEnvelope("s", 1))
    want = _export_tail(parent, 10)
    want.set_engine, want.kind = "hll", "full"
    _same_tuples(err.value.undelivered, want)
    assert reg.total("g", "forward.export_lazy") == sum(parent.counts())


# ---- (v) a server's engines, joined in wire order ----

def _server(workers, **overrides):
    cfg = read_config(text=f"""
interval: "3600s"
statsd_listen_addresses: ["udp://127.0.0.1:0"]
num_workers: {workers}
num_readers: 1
percentiles: [0.5]
aggregates: ["min", "max", "count"]
hostname: testhost
forward_address: "fake:3118"
tpu_histogram_slots: 256
tpu_counter_slots: 64
tpu_gauge_slots: 64
tpu_set_slots: 32
tpu_batch_size: 256
tpu_buffer_depth: 32
""")
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return Server(cfg, sinks=[])


def _spy_engines(srv):
    """Each engine's flush wrapped: [(its export's columns, the
    parent's tuples for it)] by engine, filled at the next flush."""
    got = [None] * len(srv.engines)
    for i, eng in enumerate(srv.engines):
        def flush(timestamp=None, forward_kind="full", i=i, eng=eng):
            res, seen = _flush_seen(eng, timestamp=timestamp,
                                    forward_kind=forward_kind)
            got[i] = (res.export.columns,
                      _parent_export(eng, seen, res.export.kind))
            return res
        eng.flush = flush
    return got


def test_two_engines_exports_join_in_wire_order():
    srv = _server(2)
    try:
        exports = []
        srv.forwarder = exports.append
        rng = np.random.default_rng(5)
        for eng in srv.engines:
            _feed(eng, rng, timers=9)
        per_engine = _spy_engines(srv)
        srv.flush_once(timestamp=100)
        (joined,) = exports
        assert all(cols is not None for cols, _p in per_engine)
        assert joined.columns is not None and joined._lists == [None] * 4
        # the parent's merge: four extends an engine
        want = ForwardExport(set_engine=joined.set_engine, kind=joined.kind)
        for _cols, parent in per_engine:
            want.histograms.extend(parent.histograms)
            want.sets.extend(parent.sets)
            want.counters.extend(parent.counters)
            want.gauges.extend(parent.gauges)
        assert joined.counts() == want.counts()
        assert min(want.counts()) > 0
        for a, b in zip(wire.export_columns(joined),
                        wire.export_columns(want)):
            _same_field(a, b)
        _same_tuples(joined, want)
    finally:
        srv.stop()


def test_engines_without_columns_join_by_their_lists(flushed):
    export, parent = flushed["full_program"][0]
    hand = ForwardExport(counters=[(MetricKey("x", "counter", ""), 1.0)])
    joined = ForwardExport.joined([_again(export), hand])
    assert joined.columns is None
    assert joined.counts() == tuple(
        a + b for a, b in zip(parent.counts(), hand.counts()))
    assert joined.counters == parent.counters + hand.counters
    one = _again(export)
    assert ForwardExport.joined([one]) is one


def test_one_engines_export_goes_on_untouched_and_an_idle_tick_sends_none():
    srv = _server(1)
    try:
        exports = []
        srv.forwarder = exports.append
        _feed(srv.engines[0], np.random.default_rng(6), timers=5)
        srv.flush_once(timestamp=100)
        (export,) = exports
        # the engine's own export, its lists unread by the server
        assert export.columns is not None and export._lists == [None] * 4
        assert export.kind == "full" and export.lazy_built == 0
        # nothing but the interned counters' and sets' zero rows
        # would go next; with no key at all the forwarder is not called
        srv2 = _server(1)
        try:
            none = []
            srv2.forwarder = none.append
            srv2.flush_once(timestamp=100)
            assert none == []
        finally:
            srv2.stop()
    finally:
        srv.stop()

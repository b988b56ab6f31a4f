"""`import.apply` opened from inside (ISSUE 39): `import_list` stamps a
request's decode, its wait for the engine's lock and its lock hold as
three child phases of the worker's `import.apply` run, into the
engine's one stamp log, and adds the applying thread's CPU nanoseconds
over decode and stage to the interval's tally. Read here as the
benchmark and `/debug/flush` read them: from the global's flush tick
and from `_last_flush_info`. All on the CPU backend; the mesh engine on
four of the virtual devices tests/conftest.py pins.
"""

import logging
import threading
import time

import numpy as np
import pytest

from veneur_tpu import sketches
from veneur_tpu.cluster import wire
from veneur_tpu.cluster.forward import (SEND_METRICS, GrpcForwarder,
                                        HttpJsonForwarder)
from veneur_tpu.cluster.protos import forward_pb2, metric_pb2
from veneur_tpu.config import read_config
from veneur_tpu.ingest import native
from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.models import pipeline
from veneur_tpu.models.pipeline import (APPLY_CPU_TALLY, APPLY_PHASES,
                                        DECODE_TALLY, IMPORT_PHASES,
                                        LAND_PHASES, AggregationEngine,
                                        EngineConfig, ForwardExport)
from veneur_tpu.observe import FlightRecorder, StampLog, TelemetryRegistry
from veneur_tpu.resilience import ResilientForwarder
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink
from veneur_tpu.utils.faults import kill_journal_lock

DECODE, LOCK_WAIT, STAGE = APPLY_PHASES
CPU_KEYS = APPLY_CPU_TALLY
# import_list reads the thread's CPU clock inside the wall clock's
# window, so a CPU reading passes its phase's wall reading only by the
# clocks' resolution and what their rates differ by
SLACK_NS = 20_000 + int(1e9 * max(
    time.get_clock_info(c).resolution for c in ("monotonic", "thread_time")))

_YAML = """
interval: "3600s"
num_workers: 1
percentiles: [0.5, 0.99]
aggregates: ["min", "max", "count"]
hostname: h
tpu_histogram_slots: 512
tpu_counter_slots: 512
tpu_gauge_slots: 512
tpu_set_slots: 256
tpu_batch_size: 256
tpu_buffer_depth: 256
"""


def _rows(tick, name):
    """[(t0, t1, parent, idx)] of a tick's completed phases of one
    name, in start order."""
    return sorted((t0, t1, par, i)
                  for i, (n, t0, t1, par) in enumerate(tick.phases())
                  if n == name and t1 > t0)


def _request(n_timers, prefix="t39", centroids=4):
    rng = np.random.default_rng(39)
    ml = forward_pb2.MetricList()
    for i in range(n_timers):
        m = ml.metrics.add(name=f"{prefix}.lat.k{i}", type=metric_pb2.Timer)
        td = m.histogram.t_digest
        means = rng.lognormal(4.6, 0.1, centroids)
        for mean in means:
            td.centroids.add(mean=float(mean), weight=1.0)
        td.min, td.max = float(means.min()), float(means.max())
        td.sum, td.count = float(means.sum()), float(centroids)
        td.reciprocal_sum = float((1.0 / means).sum())
    c = ml.metrics.add(name=f"{prefix}.hits", type=metric_pb2.Counter)
    c.counter.value = 7
    return list(forward_pb2.MetricList.FromString(
        ml.SerializeToString()).metrics)


def _engine(armed=True, budget=64):
    eng = AggregationEngine(EngineConfig(
        histogram_slots=512, counter_slots=128, gauge_slots=128,
        set_slots=64, buffer_depth=256, batch_size=256,
        percentiles=(0.5, 0.99), aggregates=("min", "max", "count"),
        is_global=True))
    if armed:
        eng.land_stamps = StampLog(dict.fromkeys(IMPORT_PHASES, budget))
    return eng


def _global(monkeypatch, stage_digests=8, **over):
    monkeypatch.setattr(pipeline, "_IMPORT_STAGE_DIGESTS", stage_digests)
    cfg = read_config(text=_YAML)
    cfg.grpc_listen_addresses = ["127.0.0.1:0"]
    for k, v in over.items():
        setattr(cfg, k, v)
    glob = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                  span_sinks=[])
    glob.start()
    return glob


def _check_children(tick):
    """The three children of every request in the tick: nested under an
    `import.apply` row, in order and adjoining, their summed seconds no
    more than the runs'; every mid-interval landing inside a stage.
    Returns (decodes, waits, stages)."""
    (_i0, _i1, ipar, root), = _rows(tick, "import")
    assert ipar == -1
    applies = _rows(tick, "import.apply")
    assert applies and all(a[2] == root for a in applies)
    apply_idx = {a[3] for a in applies}
    phases = tick.phases()
    # lock_wait may read 0 ns: take those rows whatever their length
    kids = {name: sorted((t0, t1, par) for n, t0, t1, par in phases
                         if n == name) for name in APPLY_PHASES}
    decs, waits, stages = (kids[n] for n in APPLY_PHASES)
    assert len(decs) == len(waits) == len(stages) >= 1
    for d, w, s in zip(decs, waits, stages):
        assert d[0] <= d[1] == w[0] <= w[1] == s[0] <= s[1]
        assert {d[2], w[2], s[2]} <= apply_idx
        a0, a1 = next((a[0], a[1]) for a in applies if a[3] == d[2])
        assert a0 <= d[0] and s[1] <= a1
    inside = sum(r[1] - r[0] for rows in kids.values() for r in rows)
    assert 0 < inside <= sum(a[1] - a[0] for a in applies)
    for l0, l1, lpar, _i in _rows(tick, "import.land"):
        if lpar == root:        # a worker's, not the flush's own
            assert any(s[0] <= l0 and l1 <= s[1] for s in stages)
    return decs, waits, stages


def test_a_forwarded_interval_opens_import_apply_in_the_globals_tick(
        monkeypatch):
    """A local forwards over real gRPC, three chunks; the global's next
    flush tick holds each request's three children under the worker's
    run, the landings that fell inside a batch inside its stage, and
    the worker's CPU time as the `import` root's meta."""
    glob = _global(monkeypatch)
    fwd = ResilientForwarder(
        GrpcForwarder(f"127.0.0.1:{glob.grpc_port}", timeout_s=10.0,
                      max_per_batch=8),
        destination="t39-global", sender_id="t39-sender",
        registry=TelemetryRegistry())
    cfg_l = read_config(text=_YAML)
    cfg_l.forward_address = "placeholder:1"
    local = Server(cfg_l, sinks=[CaptureMetricSink()], plugins=[],
                   span_sinks=[], forwarder=fwd)
    local.start()
    try:
        lines = [b"t39.lat%d:%d|ms|#veneurglobalonly" % (k, 10 + v)
                 for k in range(20) for v in range(6)]
        local.handle_packet(b"\n".join(lines))
        assert local.drain(10.0)
        local.flush_once(timestamp=2000)
        assert glob.drain(10.0)
        merged = glob.flush_once(timestamp=2005)
        assert sum(m.name.endswith(".count") and m.value == 6.0
                   for m in merged if m.name.startswith("t39.")) == 20
        gt = glob.flight.last_tick()
        assert gt.dropped == 0
        decs, _waits, stages = _check_children(gt)
        assert len(decs) == len(_rows(gt, "import.route")) >= 3   # 20 / 8
        # 20 digests, a landing every 8: two fell inside a batch
        (_i0, _i1, _p, root), = _rows(gt, "import")
        assert sum(ld[2] == root for ld in _rows(gt, "import.land")) == 2
        # the flush-time landing still nests under engine.drain
        (_d0, _d1, _p, drain), = _rows(gt, "engine.drain")
        assert [ld[2] for ld in _rows(gt, "import.land")][-1] == drain
        # the counters: in the engine's note, and on the root for the
        # ring's reader, the same numbers
        info = glob.engines[0]._last_flush_info
        meta = gt.to_dict()["phases"][root]["meta"]
        assert meta == {k: info[k] for k in CPU_KEYS}
        wall = {DECODE: sum(d[1] - d[0] for d in decs),
                STAGE: sum(s[1] - s[0] for s in stages)}
        for key, name in zip(CPU_KEYS, (DECODE, STAGE)):
            assert 0 < info[key] <= wall[name] + len(decs) * SLACK_NS
        # the requests came over gRPC with their bytes: every sketch
        # was read from them, its key minted once (ISSUE 42)
        assert [info[k] for k in DECODE_TALLY] == [20, 0, 0, 20]
        assert info["import_metrics"] == 20
        # the next interval's tick carries nothing over
        glob.flush_once(timestamp=2010)
        assert not _rows(glob.flight.last_tick(), "import")
        assert [glob.engines[0]._last_flush_info[k]
                for k in CPU_KEYS + DECODE_TALLY] == [0] * 6
    finally:
        local.stop()
        glob.stop()


def test_the_mesh_engine_stamps_the_same_names(monkeypatch):
    """`parallel/engine.py` inherits `import_list`: the same three
    children, with the mesh landing's `import.land` inside the stage."""
    glob = _global(monkeypatch, tpu_num_devices=4)
    try:
        eng = glob.engines[0]
        assert type(eng).__name__ == "MeshAggregationEngine"
        for r in range(3):
            glob._submit_import_batch(_request(40, prefix=f"t39.r{r}"))
        assert glob.drain(30.0)
        merged = glob.flush_once(timestamp=3000)
        assert sum(m.name.endswith(".count") for m in merged
                   if m.name.startswith("t39.")) == 120
        gt = glob.flight.last_tick()
        assert gt.dropped == 0
        decs, _waits, _stages = _check_children(gt)
        assert len(decs) == 3
        info = eng._last_flush_info
        assert info["import_batches"] == 3
        assert all(info[k] > 0 for k in CPU_KEYS)
        names = {n for n, _t0, _t1, _p in gt.phases()}
        assert set(APPLY_PHASES) <= names
        assert "import.land.cluster" not in names
    finally:
        glob.stop()


def test_lock_wait_covers_a_held_lock_and_decode_ends_before_it(
        monkeypatch):
    """The test holds the engine's lock while another thread calls
    `import_list`: its decode runs (outside the lock) and ends while
    the lock is held, `lock_wait` runs from there past the release,
    and `stage` begins where the wait ended."""
    eng = _engine()
    decoded = threading.Event()
    inner = wire.decode_metric_batch

    def decode_then_tell(pbs):
        out = inner(pbs)
        decoded.set()
        return out

    monkeypatch.setattr(wire, "decode_metric_batch", decode_then_tell)
    pbs = _request(6)
    worker = threading.Thread(target=eng.import_list, args=(1, pbs),
                              daemon=True)
    with eng.lock:
        worker.start()
        assert decoded.wait(10.0)
        time.sleep(0.02)            # the worker reaches the lock
        held_ns = time.monotonic_ns()
        time.sleep(0.05)
        release_ns = time.monotonic_ns()
    worker.join(10.0)
    assert not worker.is_alive()
    rows = {name: (t0, t1) for name, t0, t1 in eng.land_stamps.take()}
    assert set(rows) == set(APPLY_PHASES)
    (d0, d1), (w0, w1), (s0, s1) = (rows[n] for n in APPLY_PHASES)
    assert d0 < d1 == w0 <= held_ns
    assert w1 >= release_ns and w1 - w0 >= 50_000_000
    assert s0 == w1 < s1
    # the wait is no CPU time of the worker's
    assert eng._import_decode_cpu_ns <= d1 - d0 + SLACK_NS
    assert eng._import_stage_cpu_ns <= s1 - s0 + SLACK_NS


def test_cpu_counters_reach_the_flush_info_and_reset():
    eng = _engine()
    for op in (1, 2):
        assert eng.import_list(op, _request(300, prefix=f"t39.o{op}")) \
            == ([], [])
    rows = eng.land_stamps.take()       # the flush would take them
    wall = {name: sum(t1 - t0 for n, t0, t1 in rows if n == name)
            for name in APPLY_PHASES}
    assert sum(n == DECODE for n, _t0, _t1 in rows) == 2
    res = eng.flush(timestamp=1)
    info = eng._last_flush_info
    assert info["import_batches"] == 2 and info["import_metrics"] == 602
    for key, name in zip(CPU_KEYS, (DECODE, STAGE)):
        assert 0 < info[key] <= wall[name] + 2 * SLACK_NS, (key, info[key])
        assert res.stats["flush_path"][key] == info[key]
    assert eng._import_decode_cpu_ns == eng._import_stage_cpu_ns == 0
    eng.flush(timestamp=2)
    assert [eng._last_flush_info[k] for k in CPU_KEYS] == [0, 0]


@pytest.mark.parametrize("armed", [False, True], ids=["off", "armed"])
def test_import_list_reads_a_clock_only_when_armed(monkeypatch, armed):
    """With `flight_recorder: false` a server arms no engine's log, and
    `import_list` then reads neither clock, stamps no row and counts no
    CPU time; armed, it reads each clock four times a request."""
    cfg = read_config(text=_YAML)
    cfg.is_global = True
    cfg.flight_recorder = armed
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[])
    eng = srv.engines[0]
    assert (eng.land_stamps is not None) == armed
    calls = {"thread_time_ns": 0, "monotonic_ns": 0}

    def counting(name, inner):
        def clock():
            if threading.current_thread() is threading.main_thread():
                calls[name] += 1
            return inner()
        return clock

    pbs = _request(6)                   # far from a landing's threshold
    for name in calls:
        monkeypatch.setattr(time, name, counting(name, getattr(time, name)))
    eng.import_list(1, pbs)
    monkeypatch.undo()
    want = 4 if armed else 0
    assert calls == {"thread_time_ns": want, "monotonic_ns": want}
    res = eng.flush(timestamp=1)
    stamped = {n for n, _t0, _t1 in res.stats["import_phases"]}
    assert stamped - set(LAND_PHASES) == (
        set(APPLY_PHASES) if armed else set())
    assert bool(stamped) == armed       # the flush's landing: no row
    assert all((eng._last_flush_info[k] > 0) == armed for k in CPU_KEYS)
    assert eng._last_flush_info["import_batches"] == 1


def test_forty_interleaved_requests_keep_each_names_seconds_exact():
    """Past the log's budget and past the tick's free slots a name's
    summed seconds stay exact; only the folded rows' edges go."""
    log = StampLog(dict.fromkeys(APPLY_PHASES, 16))
    base, want = 1_000_000, dict.fromkeys(APPLY_PHASES, 0)
    t = base
    for k in range(40):
        for name, took in zip(APPLY_PHASES, (700 + k, k % 3, 1100 + 2 * k)):
            log.add(name, t, t + took)
            want[name] += took
            t += took
        t += 50                         # the queue's get, between two
    rows = log.take()
    for name in APPLY_PHASES:
        mine = [r for r in rows if r[0] == name]
        assert len(mine) == 16
        assert sum(t1 - t0 for _n, t0, t1 in mine) == want[name]
        # rows within the budget keep their edges: no stamp was merged
        # into a neighbour's (the requests interleave)
        assert [r[2] - r[1] for r in mine[:15]] == [
            dict(zip(APPLY_PHASES, (700 + k, k % 3, 1100 + 2 * k)))[name]
            for k in range(15)]
    # ... and a tick with 20 slots left folds them again, sums exact
    fr = FlightRecorder(capacity=1, max_phases=24)
    tick = fr.begin_tick(ts=1)
    for _ in range(3):
        tick.finish(tick.start("own"))
    root = tick.graft(rows + [("import.apply", base - 10, t + 10)],
                      root="import", import_decode_cpu_ns=5)
    assert tick.dropped == 0 and tick.n == 24
    got = dict.fromkeys(APPLY_PHASES, 0)
    for name, t0, t1, _par in tick.phases():
        if name in got:
            got[name] += t1 - t0
    assert got == want
    assert tick.to_dict()["phases"][root]["meta"] == {
        "import_decode_cpu_ns": 5}


def test_the_engines_log_holds_the_landings_names_and_the_requests():
    assert IMPORT_PHASES == LAND_PHASES + APPLY_PHASES
    assert all(n.startswith("import.apply.") for n in APPLY_PHASES)
    assert Server.GRAFT_BUDGET["import.apply.request"] >= 32
    assert CPU_KEYS == ("import_decode_cpu_ns", "import_stage_cpu_ns")


# ---- a request's bytes reach the worker, or they do not (ISSUE 42) ----

N_SKETCHES = 33


def _fleet_request():
    """(serialized MetricList, the export it was made from): timers of
    two tags, a set, counters and a gauge, every value one f32 holds, so
    the JSON contract carries what the protobuf one does."""
    rng = np.random.default_rng(42)
    ex = ForwardExport()
    for i in range(24):
        x = np.sort(rng.lognormal(4.6, 0.3, 4 if i < 20 else 40)).astype(
            np.float32).astype(np.float64)
        ex.histograms.append(
            (MetricKey(f"t42.lat.k{i}", "timer", "env:prod,svc:api"), x,
             np.ones(x.size), x[0], x[-1], float(x.sum()), float(x.size),
             float((1.0 / x).sum())))
    ex.sets = [(MetricKey("t42.users", "set", "env:prod"),
                rng.integers(0, 7, 1 << 14).astype(np.uint8))]
    ex.counters = [(MetricKey(f"t42.hits.c{i}", "counter", "env:prod"),
                    float(3 * i - 5)) for i in range(7)]
    ex.gauges = [(MetricKey("t42.level", "gauge", ""), 2.5)]
    raw = forward_pb2.MetricList(
        metrics=wire.export_to_metrics(ex),
        sketch_engines=sketches.DEFAULT_STAMP).SerializeToString()
    assert len(forward_pb2.MetricList.FromString(raw).metrics) \
        == N_SKETCHES
    return raw, ex


def _send_bytes(glob, raw):
    """The request as a sender's channel puts it on the wire."""
    import grpc
    with grpc.insecure_channel(f"127.0.0.1:{glob.grpc_port}") as ch:
        ch.unary_unary(SEND_METRICS, request_serializer=lambda b: b,
                       response_deserializer=lambda b: b)(raw, timeout=10)


def _flushed(glob, ts=4200):
    """(the flush's own rows, its self-metrics by name, the engine's
    note of the interval)."""
    assert glob.drain(30.0)
    out = glob.flush_once(timestamp=ts)
    rows = sorted((m.name, tuple(m.tags), repr(m.value)) for m in out
                  if not m.name.startswith("veneur."))
    own = {m.name: m.value for m in out if m.name.startswith("veneur.")}
    return rows, own, glob.engines[0]._last_flush_info


def _decoded(info):
    return [info[k] for k in DECODE_TALLY]


@pytest.mark.parametrize("engine", ["single", "mesh"])
def test_a_request_flushes_the_same_whichever_way_it_was_decoded(
        monkeypatch, caplog, tmp_path, engine):
    """One request into four globals: by gRPC (the worker reads the
    sketches from its bytes), by HTTP /import and by recovery's replay
    of the journaled op (no bytes: Python), and by gRPC into a process
    that cannot build the library (Python, said once). The same rows
    every time; the phases stamped as before; the counts say which
    path ran."""
    over = {"tpu_num_devices": 4} if engine == "mesh" else {}
    raw, export = _fleet_request()
    # over gRPC: native
    glob = _global(monkeypatch, durability_enabled=True,
                   durability_dir=str(tmp_path), durability_fsync="never",
                   **over)
    try:
        assert (type(glob.engines[0]).__name__
                == "MeshAggregationEngine") == (engine == "mesh")
        _send_bytes(glob, raw)
        want, own, info = _flushed(glob)
        assert len(want) > N_SKETCHES
        assert _decoded(info) == [N_SKETCHES, 0, 0, N_SKETCHES]
        assert info["import_metrics"] == N_SKETCHES
        decs, _waits, _stages = _check_children(glob.flight.last_tick())
        assert len(decs) == 1
        assert all(info[k] > 0 for k in CPU_KEYS)
        # a second tick of the same keys finds every one
        _send_bytes(glob, raw)
        _rows2, own, info = _flushed(glob, 4210)
        assert _decoded(info) == [N_SKETCHES, 0, N_SKETCHES, 0]
        assert [own[f"veneur.import.decode_{k}_total"] for k in (
            "native", "fallback", "key_hits", "key_misses")] \
            == [N_SKETCHES, 0, N_SKETCHES, 0]
        # ... and a third, which no flush follows, for the replay
        _send_bytes(glob, raw)
        assert glob.drain(30.0)
    finally:
        journaled = glob._engine_journal is not None
        glob._stop.set()
        for j in (glob._engine_journal, glob._dedupe_journal):
            if j is not None:
                kill_journal_lock(j)
        glob.stop()
    # recovery's replay of that third request: Python, the same rows
    # (the mesh engine keeps no engine journal: nothing to replay)
    assert journaled == (engine == "single")
    if journaled:
        glob = _global(monkeypatch, durability_enabled=True,
                       durability_dir=str(tmp_path),
                       durability_fsync="never")
        try:
            assert glob._recovery["ops_replayed"] == 1
            rows, _own, info = _flushed(glob)
            assert rows == want
            assert _decoded(info) == [0, N_SKETCHES, 0, 0]
        finally:
            glob.stop()
    # over HTTP /import: Python
    glob = _global(monkeypatch, http_address="127.0.0.1:0", is_global=True,
                   **over)
    try:
        HttpJsonForwarder(f"http://127.0.0.1:{glob.http_api.port}",
                          engine_stamp=glob.engine_stamp)(export)
        rows, own, info = _flushed(glob)
        assert rows == want
        assert _decoded(info) == [0, N_SKETCHES, 0, 0]
        assert own["veneur.import.decode_fallback_total"] == N_SKETCHES
        _check_children(glob.flight.last_tick())
    finally:
        glob.stop()

    # a process where the library cannot be had: it starts, says so
    # once, and imports in Python
    def no_compiler(**_kw):
        raise native.NativeUnavailable("no compiler in this test")

    monkeypatch.setattr(wire, "_native_fn", None)
    monkeypatch.setattr(native, "build", no_compiler)
    with caplog.at_level(logging.WARNING, logger="veneur_tpu.cluster.wire"):
        glob = _global(monkeypatch, **over)
        try:
            _send_bytes(glob, raw)
            rows, _own, info = _flushed(glob)
            assert rows == want
            assert _decoded(info) == [0, N_SKETCHES, 0, 0]
            _send_bytes(glob, raw)
            assert _decoded(_flushed(glob, 4210)[2]) \
                == [0, N_SKETCHES, 0, 0]
        finally:
            glob.stop()
    said = [r for r in caplog.records if "libvtpu_wire" in r.getMessage()]
    assert len(said) == 1

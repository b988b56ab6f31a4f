"""Self-instrumentation + config keys that round 2 flagged as dead:
datadog APM span arm, tags_exclude, stats_address, sentry_dsn,
per-sink self-metrics, and the server tracing its own flush.
"""

import http.server
import json
import socket
import threading
import time
import zlib

import pytest

from veneur_tpu.config import Config
from veneur_tpu.ingest import parser
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink
from veneur_tpu.sinks.datadog import DatadogSpanSink
from veneur_tpu.ssf.protos import ssf_pb2


class _Capture(http.server.BaseHTTPRequestHandler):
    bodies: list = []

    def _handle(self):
        n = int(self.headers.get("Content-Length", 0))
        body = self.rfile.read(n)
        if self.headers.get("Content-Encoding") == "deflate":
            body = zlib.decompress(body)
        type(self).bodies.append((self.command, self.path, body))
        self.send_response(200)
        self.end_headers()

    do_PUT = do_POST = _handle

    def log_message(self, *a):
        pass


@pytest.fixture
def http_capture():
    class H(_Capture):
        bodies = []
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", H.bodies
    srv.shutdown()
    srv.server_close()


def make_span(trace_id=7, span_id=8, parent=0, name="op", error=False):
    s = ssf_pb2.SSFSpan(version=0, trace_id=trace_id, id=span_id,
                        parent_id=parent, name=name, service="svc",
                        start_timestamp=1_000_000,
                        end_timestamp=3_500_000, error=error)
    s.tags["env"] = "prod"
    return s


def test_datadog_span_sink_contract(http_capture):
    url, bodies = http_capture
    sink = DatadogSpanSink(trace_api_address=url)
    sink.ingest(make_span(trace_id=7, span_id=1))
    sink.ingest(make_span(trace_id=7, span_id=2, parent=1, name="child"))
    sink.ingest(make_span(trace_id=9, span_id=3, error=True))
    sink.ingest(ssf_pb2.SSFSpan(version=0))  # metric carrier: skipped
    sink.flush()
    assert sink.flushed_total == 3 and sink.dropped_total == 0
    method, path, body = bodies[0]
    assert (method, path) == ("PUT", "/v0.3/traces")
    traces = json.loads(body)
    assert len(traces) == 2
    by_trace = {t[0]["trace_id"]: t for t in traces}
    t7 = sorted(by_trace[7], key=lambda d: d["span_id"])
    assert [d["span_id"] for d in t7] == [1, 2]
    assert t7[1]["parent_id"] == 1
    assert t7[0]["duration"] == 2_500_000
    assert t7[0]["meta"] == {"env": "prod"}
    assert by_trace[9][0]["error"] == 1
    # idempotent: nothing buffered -> no second request
    sink.flush()
    assert len(bodies) == 1


def test_tags_exclude_merges_keys():
    ex = frozenset(["pod_id"])
    a = parser.parse_packet(b"api.hits:1|c|#env:prod,pod_id:abc", ex)
    b = parser.parse_packet(b"api.hits:2|c|#env:prod,pod_id:xyz", ex)
    assert a.key == b.key
    assert a.tags == ["env:prod"]
    # whole-tag (no colon) exclusion too
    c = parser.parse_packet(b"x:1|c|#debug,env:prod",
                            frozenset(["debug"]))
    assert c.tags == ["env:prod"]


def test_server_tags_exclude_end_to_end():
    cap = CaptureMetricSink()
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 interval="3600s", hostname="h",
                 tags_exclude=["pod_id"], aggregates=["count"],
                 percentiles=[],
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        port = srv.bound_port()
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.sendto(b"m:1|c|#pod_id:a,env:p", ("127.0.0.1", port))
        s.sendto(b"m:2|c|#pod_id:b,env:p", ("127.0.0.1", port))
        deadline = time.monotonic() + 5
        while srv.packets_received < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.drain(5)
        srv.flush_once(timestamp=5)
        cap.wait_for_flush()
        ms = [m for m in cap.all_metrics if m.name == "m"]
        assert len(ms) == 1           # merged into one key
        assert ms[0].value == 3.0
        assert ms[0].tags == ["env:p"]
    finally:
        srv.stop()


def test_per_sink_self_metrics():
    cap = CaptureMetricSink()
    cfg = Config(interval="3600s", hostname="h",
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        srv.flush_once(timestamp=2)   # reports flush 1's sink stats
        cap.wait_for_flush(2)
        names = {(m.name, tuple(m.tags)) for m in cap.flushes[1]}
        assert ("veneur.sink.metrics_flushed_total",
                ("sink:capture",)) in names
        assert ("veneur.sink.flush_duration_ns",
                ("sink:capture",)) in names
    finally:
        srv.stop()


def test_rows_built_ride_the_sink_phase_and_the_next_ticks_self_metrics():
    """ISSUE 50: the frame's rows_built / rows_fallback on the
    `sink.flush` phase of the sink whose thread built the list (zeros
    on the one that found it cached) and as veneur.sink.rows_built_total
    / rows_fallback_total in the next interval."""
    class Second(CaptureMetricSink):
        def name(self):
            return "second"

    cap, second = CaptureMetricSink(), Second()
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 interval="3600s", hostname="h",
                 aggregates=["count", "max"], percentiles=[0.5],
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[cap, second], plugins=[], span_sinks=[])
    srv.start()
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for line in (b"t:5|ms|#a:1", b"t:7|ms|#a:2", b"c:1|c", b"g:2|g"):
            s.sendto(line, ("127.0.0.1", srv.bound_port()))
        deadline = time.monotonic() + 5
        while srv.packets_received < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.drain(5)
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        second.wait_for_flush(1)
        tick = srv.flight.last_tick()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and any(
                p["in_flight"] for p in tick.to_dict()["phases"]
                if p["name"] == "sink.flush"):
            time.sleep(0.01)
        metas = {p["meta"]["sink"]: p["meta"]
                 for p in tick.to_dict()["phases"]
                 if p["name"] == "sink.flush"}
        assert set(metas) == {"capture", "second"}
        # the frame's rows are what a sink received less the loose
        # self-metrics: two timers x (p50, count, max) + c + g
        frame_rows = [m for m in cap.flushes[0]
                      if not m.name.startswith("veneur.")]
        assert len(frame_rows) == 8
        built = sorted(m["rows_built"] for m in metas.values())
        assert built == [0, 8]                  # built once, by one sink
        for m in metas.values():
            assert m["rows_fallback"] == 0
            assert (m["build_ns"] > 0) == (m["rows_built"] > 0)
            assert m["flushed"] == len(cap.flushes[0])
        srv.flush_once(timestamp=2)     # reports flush 1's sink stats
        cap.wait_for_flush(2)
        by = {(m.name, tuple(m.tags)): m.value for m in cap.flushes[1]}
        assert sorted(by["veneur.sink.rows_built_total", (f"sink:{n}",)]
                      for n in metas) == [0, 8]
        for n, m in metas.items():
            assert by["veneur.sink.rows_built_total",
                      (f"sink:{n}",)] == m["rows_built"]
            assert by["veneur.sink.rows_fallback_total",
                      (f"sink:{n}",)] == 0
    finally:
        srv.stop()


def test_stats_address_ships_self_metrics_over_udp():
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(5.0)
    cap = CaptureMetricSink()
    cfg = Config(interval="3600s", hostname="h",
                 stats_address=f"127.0.0.1:{rx.getsockname()[1]}",
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        srv.flush_once(timestamp=1)
        data, _ = rx.recvfrom(65536)
        lines = data.decode().splitlines()
        assert any(ln.startswith("veneur.packet.received_total:")
                   and ln.endswith("|c") for ln in lines)
        # shipped over the wire INSTEAD of injected locally
        cap.wait_for_flush()
        assert not any(m.name.startswith("veneur.")
                       for m in cap.all_metrics)
    finally:
        srv.stop()
        rx.close()


def test_server_traces_its_own_flush():
    cap = CaptureMetricSink()
    cfg = Config(ssf_listen_addresses=["udp://127.0.0.1:0"],
                 interval="3600s", hostname="h",
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        assert srv.trace_client is not None
        srv.flush_once(timestamp=1)
        srv.trace_client.flush()
        deadline = time.monotonic() + 5
        while srv.spans_received < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert srv.spans_received >= 1   # veneur.flush span came home
    finally:
        srv.stop()


def test_sentry_client(http_capture):
    url, bodies = http_capture
    from veneur_tpu.utils.sentry import SentryClient
    c = SentryClient(f"{url.replace('http://', 'http://key@')}/42")
    try:
        raise RuntimeError("boom")
    except RuntimeError as e:
        c.capture(e, "it broke", wait=True)
    assert c.sent == 1
    method, path, body = bodies[0]
    assert path == "/api/42/store/"
    ev = json.loads(body)
    assert ev["message"] == "it broke"
    exc = ev["exception"]["values"][0]
    assert exc["type"] == "RuntimeError" and exc["value"] == "boom"
    assert exc["stacktrace"]["frames"]


def test_durability_self_metrics_flow_through_telemetry(tmp_path):
    """veneur.durability.* self-metrics ride the existing telemetry
    path: journal appends / recovered intervals drain from the
    resilience registry as counters, journal_bytes and
    snapshot_duration_ns report as gauges — all inside the normal
    flush, no new plumbing."""
    from veneur_tpu import resilience
    from veneur_tpu.config import read_config

    cap = CaptureMetricSink()
    cfg = read_config(text=f"""
interval: "3600s"
hostname: h
statsd_listen_addresses: ["udp://127.0.0.1:0"]
forward_address: "placeholder:1"
durability_enabled: true
durability_dir: "{tmp_path}"
durability_fsync: "never"
tpu_histogram_slots: 256
tpu_counter_slots: 128
tpu_gauge_slots: 128
tpu_set_slots: 64
""")
    resilience.DEFAULT_REGISTRY.take()   # isolate from other tests
    sent = []
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[],
                 forwarder=lambda export: sent.append(export))
    # the explicit forwarder got wrapped AND journaled
    assert isinstance(srv.forwarder, resilience.ResilientForwarder)
    assert srv.forwarder._journal is not None
    srv.start()
    try:
        port = srv.bound_port()
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.sendto(b"dur.c:1|c|#veneurglobalonly", ("127.0.0.1", port))
        deadline = time.monotonic() + 5
        while srv.packets_received < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.drain(5)
        srv.flush_once(timestamp=1)     # forwards -> journal appends
        cap.wait_for_flush(1)
        assert sent, "forward did not run"
        # the registry drains at frameset-build time, BEFORE the
        # forward runs — tick 1's BEGIN/DONE appends report in tick 2
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        by_name = {}
        for m in cap.flushes[0] + cap.flushes[1]:
            by_name.setdefault(m.name, [])
            by_name[m.name].append(m)
        from veneur_tpu.metrics import MetricType
        appends = by_name["veneur.durability.journal_appends_total"]
        # construction META (tick-1 report) + tick 1's BEGIN and DONE
        # (tick-2 report)
        assert sum(m.value for m in appends) >= 3
        assert all(m.type == MetricType.COUNTER for m in appends)
        jb = by_name["veneur.durability.journal_bytes"][0]
        assert jb.type == MetricType.GAUGE
        assert jb.value > 0             # magic + frames on disk
        assert "veneur.durability.snapshot_duration_ns" in by_name
    finally:
        srv.stop()


def test_overload_counters_present_at_zero_and_drain():
    """veneur.overload.* rides the unified telemetry spine (ISSUE 7):
    with the defense armed, every interval reports the four
    degradation counters — ZEROS INCLUDED (a zero is the steady-state
    signal) — plus the live adaptive_sample_rate gauge; a storm
    interval carries the real counts. The same names drain from ANY
    TelemetryRegistry instance (per-server spine or the process
    default), because the name mapping lives only in the registry."""
    from veneur_tpu import resilience
    from veneur_tpu.config import read_config
    from veneur_tpu.ingest.admission import AdmissionController
    from veneur_tpu.observe import SERVER_SCOPE

    cap = CaptureMetricSink()
    cfg = read_config(text="""
interval: "3600s"
hostname: h
statsd_listen_addresses: ["udp://127.0.0.1:0"]
overload_defense_enabled: true
overload_max_keys_per_prefix: 2
flush_phase_timers: false
tpu_histogram_slots: 256
tpu_counter_slots: 128
tpu_gauge_slots: 128
tpu_set_slots: 64
""")
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        srv.flush_once(timestamp=1)      # idle interval: all zeros
        cap.wait_for_flush(1)
        zero = {m.name: m for m in cap.flushes[0]}
        for name in ("veneur.overload.folded_samples_total",
                     "veneur.overload.fold_sampled_out_total",
                     "veneur.overload.keys_over_budget_total",
                     "veneur.overload.shed_packets_total"):
            assert name in zero and zero[name].value == 0.0, name
        gauge = zero["veneur.overload.adaptive_sample_rate"]
        assert gauge.value == 1.0 and gauge.tags == []

        port = srv.bound_port()
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for k in range(10):              # 2 in budget, 8 folded
            s.sendto(b"ov.u%d:1|c" % k, ("127.0.0.1", port))
        deadline = time.monotonic() + 5
        while srv.packets_received < 10 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.drain(5)
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        storm = {m.name: m for m in cap.flushes[1]}
        assert storm["veneur.overload.folded_samples_total"].value == 8.0
        assert storm["veneur.overload.shed_packets_total"].value == 0.0

        # both registries: an admission controller counting into the
        # process-default registry drains under the SAME wire names
        resilience.DEFAULT_REGISTRY.take()
        adm = AdmissionController(registry=resilience.DEFAULT_REGISTRY,
                                  max_keys_per_prefix=1)
        assert adm.admit_key(parser.MetricKey("p.a", "counter", ""))
        assert adm.admit_key(parser.MetricKey("p.b", "counter", "")) \
            is None
        assert adm.fold_metric(parser.parse_metric(b"p.b:1|c"), 0) \
            is not None
        adm.count_folded()          # the engine counts once folds land
        names = {m.name
                 for m in resilience.DEFAULT_REGISTRY.drain(1, "h")}
        assert "veneur.overload.folded_samples_total" in names
        assert (SERVER_SCOPE, "overload.folded_samples") not in \
            resilience.DEFAULT_REGISTRY.take()   # drained clean
    finally:
        srv.stop()


def test_multi_engine_flush_overlaps():
    """Engines flush concurrently, so N engines' device programs and
    fetches overlap instead of queueing. Every fake engine parks at a
    barrier until
    all four are inside flush() at once — a serialized flush_once can
    only get one there, so the barrier breaks after the timeout
    instead of the wall-clock race a loaded box can lose."""
    from veneur_tpu.models.pipeline import FlushResult

    from veneur_tpu.metrics import MetricFrame

    all_in_flush = threading.Barrier(4, timeout=10.0)
    serialized = []

    class FakeEngine:
        def flush(self, timestamp=None, forward_kind="full"):
            try:
                all_in_flush.wait()
            except threading.BrokenBarrierError:
                serialized.append(True)
            return FlushResult(frame=MetricFrame(timestamp=1),
                               stats={"samples": 1})

        def drain_events(self):
            return [], []

    cfg = Config(interval="3600s", hostname="h",
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[], plugins=[], span_sinks=[])
    srv.engines = [FakeEngine() for _ in range(4)]
    srv.flush_once(timestamp=1)
    assert not serialized, \
        "4 engine flushes never ran concurrently (flush_once serialized)"


def test_slow_sink_does_not_delay_flush_tick():
    """A wedged vendor must not push the next tick late: the flusher
    never joins sink threads; a sink whose previous flush is still in
    flight skips the interval (counted as
    veneur.sink.flush_skipped_total) while healthy sinks keep flushing
    (flusher.go's independent per-sink goroutines)."""
    from veneur_tpu.sinks import MetricSink

    class WedgedSink(MetricSink):
        def __init__(self):
            self.release = threading.Event()
            self.calls = 0

        def name(self):
            return "wedged"

        def flush(self, metrics):
            pass

        def flush_frames(self, frames):
            self.calls += 1
            self.release.wait(20.0)
            return 0

    slow = WedgedSink()
    cap = CaptureMetricSink()
    cfg = Config(interval="3600s", hostname="h",
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[slow, cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        # pre-fix this blocked for cfg.interval (3600s) joining the
        # wedged sink's thread; now it must return promptly
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        srv.flush_once(timestamp=2)   # wedged still in flight -> skip
        cap.wait_for_flush(2)
        assert slow.calls == 1        # skipped, not re-entered
        srv.flush_once(timestamp=3)   # reports flush 2's skip counter
        cap.wait_for_flush(3)
        names = {(m.name, tuple(m.tags)) for m in cap.flushes[2]}
        assert ("veneur.sink.flush_skipped_total",
                ("sink:wedged",)) in names
        # the healthy sink saw every interval
        assert len(cap.flushes) == 3
    finally:
        slow.release.set()
        srv.stop()


def test_ingest_overflow_counters_drain_as_self_metrics():
    """veneur.ingest.overflow_rows_total / overflow_bank_total: what
    the histogram landings' overflow compress did in the interval,
    drained like samples.processed (present at zero, reset a flush)."""
    import numpy as np

    from veneur_tpu.ingest.parser import MetricKey
    cap = CaptureMetricSink()
    cfg = Config(interval="3600s", hostname="h",
                 tpu_histogram_slots=64, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64,
                 tpu_buffer_depth=16)
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        eng = srv.engines[0]
        slot = eng.histo_keys.lookup(MetricKey("lat", "timer", ""), 0)
        for _ in range(3):      # 36 samples into a 16-deep buffer
            eng.ingest_histo_batch(np.full(12, slot, np.int32),
                                   np.arange(12, dtype=np.float32),
                                   np.ones(12, np.float32))
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        first, second = ({m.name: m.value for m in f
                          if m.name.startswith("veneur.ingest.")}
                         for f in cap.flushes[:2])
        # a 64-slot bank is under every work set: whole-bank passes
        # (no batch brought a slot more than a buffer: no sidestep)
        assert first == {"veneur.ingest.overflow_rows_total": 0,
                         "veneur.ingest.overflow_bank_total": 2,
                         "veneur.ingest.sidestep_rows_total": 0,
                         "veneur.ingest.sidestep_bank_total": 0}
        assert second == {"veneur.ingest.overflow_rows_total": 0,
                          "veneur.ingest.overflow_bank_total": 0,
                          "veneur.ingest.sidestep_rows_total": 0,
                          "veneur.ingest.sidestep_bank_total": 0}
        state = srv._debug_flush_state()["registry"]["server"]
        assert state["counters"]["_server|ingest.overflow_bank"] == 2
    finally:
        srv.stop()


@pytest.mark.parametrize("workers", [1, 2])
def test_import_batch_counters_drain_as_self_metrics(workers):
    """veneur.import.batches_total / batch_metrics_total: the batches
    the engines applied in the interval and the forwarded metrics in
    them (one batch a request and engine), drained like
    samples.processed (present at zero, reset a flush); the same two
    numbers in each engine's _last_flush_info. A request of one metric
    is a batch of one, on the one engine it is homed on."""
    from veneur_tpu.cluster.protos import metric_pb2

    def counters(n, start=0):
        out = []
        for i in range(start, start + n):
            m = metric_pb2.Metric(name=f"imp.c{i}",
                                  type=metric_pb2.Counter)
            m.counter.value = i + 1
            out.append(m)
        return out

    cap = CaptureMetricSink()
    cfg = Config(interval="3600s", hostname="h", num_workers=workers,
                 tpu_histogram_slots=64, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        assert srv._submit_import_batch(counters(40)) == 40
        assert srv._submit_import_batch(counters(25, start=40)) == 25
        assert srv._submit_import_batch(counters(1, start=99)) == 1
        assert srv.drain(10.0)
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        infos = [eng._last_flush_info for eng in srv.engines]
        # two requests, each one batch an engine that had a share,
        # and the request of one
        assert sorted(i["import_batches"] for i in infos) == \
            [2] * (workers - 1) + [3]
        assert sum(i["import_metrics"] for i in infos) == 66
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        first, second = ({m.name: m.value for m in f
                          if m.name.startswith("veneur.import.batch")}
                         for f in cap.flushes[:2])
        assert first == {"veneur.import.batches_total": 2 * workers + 1,
                         "veneur.import.batch_metrics_total": 66}
        assert second == {"veneur.import.batches_total": 0,
                          "veneur.import.batch_metrics_total": 0}
        assert all(eng._last_flush_info["import_batches"] == 0
                   and eng._last_flush_info["import_metrics"] == 0
                   for eng in srv.engines)
        totals = {m.name: m.value for m in cap.flushes[0]}
        assert sum(v for k, v in totals.items()
                   if k.startswith("imp.c")) == sum(range(1, 66)) + 100
    finally:
        srv.stop()


@pytest.mark.parametrize("case", ["work_set", "whole_bank"])
def test_import_landing_counters_drain_as_self_metrics(case, monkeypatch):
    """veneur.import.land_rows_total / land_bank_total: rows the
    interval's clustered landings took through a work set, and
    landings that passed over the whole bank (a bank no larger than
    the smallest set takes nothing else), drained like
    import.batches (present at zero, reset a flush); the same two
    numbers in the engine's _last_flush_info."""
    from veneur_tpu.cluster.protos import metric_pb2
    from veneur_tpu.models import pipeline

    def timers(n, start=0):
        out = []
        for i in range(start, start + n):
            m = metric_pb2.Metric(name=f"imp.t{i}", type=metric_pb2.Timer)
            td = m.histogram.t_digest
            for v in (1.0, 2.0, 4.0):
                td.centroids.add(mean=v + i, weight=1.0)
            td.min, td.max, td.count = 1.0 + i, 4.0 + i, 3.0
            td.sum = 7.0 + 3 * i
            td.reciprocal_sum = sum(1 / (v + i) for v in (1.0, 2.0, 4.0))
            out.append(m)
        return out

    if case == "work_set":
        # sets of 8 and 32 rows stand in for the module's 1,024 and
        # 8,192, which a server's smallest bank (256 slots) is under
        monkeypatch.setattr(pipeline, "_IMPORT_LAND_ROWS", (8, 32))
    # a second landing mid-interval, at the 20th digest
    monkeypatch.setattr(pipeline, "_IMPORT_STAGE_DIGESTS", 20)
    cap = CaptureMetricSink()
    cfg = Config(interval="3600s", hostname="h", tpu_histogram_slots=256,
                 tpu_counter_slots=128, tpu_gauge_slots=128,
                 tpu_set_slots=64)
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        # 25 digests over 22 keys: 20 land mid-interval (20 rows, the
        # set of 32), 5 at the flush (5 rows, the set of 8)
        assert srv._submit_import_batch(timers(22) + timers(3)) == 25
        assert srv.drain(10.0)
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        want = (25, 0) if case == "work_set" else (0, 2)
        info = srv.engines[0]._last_flush_info
        assert (info["import_land_rows"], info["import_land_bank"]) == want
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        first, second = ({m.name: m.value for m in f
                          if m.name.startswith("veneur.import.land")}
                         for f in cap.flushes[:2])
        assert first == {"veneur.import.land_rows_total": want[0],
                         "veneur.import.land_bank_total": want[1]}
        assert second == {"veneur.import.land_rows_total": 0,
                          "veneur.import.land_bank_total": 0}
        info = srv.engines[0]._last_flush_info
        assert (info["import_land_rows"], info["import_land_bank"]) == (0, 0)
        by = {m.name: m.value for m in cap.flushes[0]}
        assert by["imp.t0.count"] == 6.0 and by["imp.t21.count"] == 3.0
    finally:
        srv.stop()

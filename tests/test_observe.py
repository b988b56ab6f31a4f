"""Observability spine tests: the unified TelemetryRegistry (naming
rules, drain/snapshot semantics), the FlightRecorder (ring bounds,
phase trees, span emission), the server integration (phase coverage,
/debug/flush, dogfood timers), and the chaos arms (ack-loss storms
surface retry/replay phases; a SimulatedKill never corrupts the ring).
"""

import json
import random
import socket
import time
import urllib.request

import pytest

from veneur_tpu.config import Config, read_config
from veneur_tpu.metrics import MetricType
from veneur_tpu.observe import (DEFAULT_REGISTRY, SERVER_SCOPE,
                                FlightRecorder, TelemetryRegistry,
                                current_tick, phase_timer_samples,
                                reset_current_tick, set_current_tick)
from veneur_tpu.resilience import (BreakerPolicy, Egress, EgressPolicy,
                                   ResilientForwarder, RetryPolicy)
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink
from veneur_tpu.utils.faults import (FakeClock, ScriptedCallable,
                                     ScriptedTransport, SimulatedKill,
                                     seeded_schedule)

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


# --------------------------------------------------------- registry

def test_registry_drain_naming_rules():
    r = TelemetryRegistry()
    r.incr("dest", "spilled", 3)                 # plain -> resilience.*
    r.incr("import", "forward.duplicates_dropped", 2)   # dotted
    r.incr(SERVER_SCOPE, "packet.received", 7)   # server scope: no tags
    r.mark("sink:cap", "sink.metrics_flushed", 0)  # zero still reports
    r.set_gauge("sink:cap", "sink.flush_duration_ns", 123.0)
    r.set_gauge(SERVER_SCOPE, "flush.total_duration_ns", 5.0)
    out = {m.name: m for m in r.drain(1, "h")}
    m = out["veneur.resilience.spilled_total"]
    assert m.value == 3 and m.tags == ["destination:dest"] \
        and m.type == MetricType.COUNTER
    m = out["veneur.forward.duplicates_dropped_total"]
    assert m.tags == ["destination:import"]
    m = out["veneur.packet.received_total"]
    assert m.value == 7 and m.tags == [] and m.hostname == "h"
    m = out["veneur.sink.metrics_flushed_total"]
    assert m.value == 0 and m.tags == ["sink:cap"]
    m = out["veneur.sink.flush_duration_ns"]
    assert m.type == MetricType.GAUGE and m.tags == ["sink:cap"]
    assert out["veneur.flush.total_duration_ns"].value == 5.0
    # drain resets counters AND gauges
    assert r.drain(2) == []


def test_registry_take_peek_compat_and_levels():
    r = TelemetryRegistry()
    r.incr("d", "attempts", 2)
    r.incr("d", "attempts")
    assert r.peek("d", "attempts") == 3
    assert r.take() == {("d", "attempts"): 3}
    assert r.take() == {}                      # drained
    assert r.total("d", "attempts") == 3       # cumulative survives
    r.incr_level(SERVER_SCOPE, "flush.count")
    r.incr_level(SERVER_SCOPE, "flush.count")
    assert r.level(SERVER_SCOPE, "flush.count") == 2
    # levels never drain; they appear in snapshots as gauges
    assert r.drain(1) == []
    snap = {m.name: m for m in r.snapshot(1)}
    assert snap["veneur.flush.count"].value == 2
    assert snap["veneur.resilience.attempts_total"].value == 3


# --------------------------------------------------------- recorder

def test_recorder_phase_tree_and_ring_bounds():
    fr = FlightRecorder(capacity=2, max_phases=8)
    for i in range(3):
        t = fr.begin_tick(100 + i)
        with t.phase("drain"):
            pass
        p = t.start("forward")
        t.start("egress.attempt", p)
        t.finish(p, outcome="ok")
        fr.end_tick(t)
    snap = fr.snapshot()
    assert len(snap) == 2                       # ring bound
    assert snap[0]["tick_id"] == 3              # newest first
    names = {p["name"]: p for p in snap[0]["phases"]}
    assert names["egress.attempt"]["parent"] == 1
    assert names["egress.attempt"]["in_flight"]   # never finished
    assert names["forward"]["meta"] == {"outcome": "ok"}
    assert fr.tick_count == 3


def test_recorder_phase_overflow_drops_counted():
    fr = FlightRecorder(capacity=1, max_phases=8)
    t = fr.begin_tick(1)
    idxs = [t.start(f"p{i}") for i in range(12)]
    assert idxs[7] >= 0 and idxs[8] == -1
    t.finish(idxs[8])                            # -1 is safe
    fr.end_tick(t)
    d = fr.snapshot()[0]
    assert len(d["phases"]) == 8 and d["dropped_phases"] == 4


def test_recorder_contextvar_scope():
    fr = FlightRecorder()
    assert current_tick() is None
    t = fr.begin_tick(1)
    tok = set_current_tick(t, parent=5)
    try:
        from veneur_tpu.observe import current_scope
        sc = current_scope()
        assert sc.tick is t and sc.parent == 5
    finally:
        reset_current_tick(tok)
    assert current_tick() is None


def test_recorder_emits_span_tree():
    class FakeClient:
        def __init__(self):
            self.spans = []

        def record(self, span):
            self.spans.append(span)
            return True

    fr = FlightRecorder()
    t = fr.begin_tick(7)
    with t.phase("drain"):
        pass
    p = t.start("forward")
    t.finish(t.start("egress.attempt", p))
    t.finish(p)
    t.start("hung")                               # in-flight: not emitted
    fr.end_tick(t)
    c = FakeClient()
    n = fr.emit_spans(t, c)
    assert n == 4                                 # root + 3 completed
    by_name = {s.name: s for s in c.spans}
    root = by_name["veneur.flush"]
    assert root.parent_id == 0 and root.tags["tick_id"] == str(t.tick_id)
    assert by_name["veneur.flush.drain"].parent_id == root.id
    fwd = by_name["veneur.flush.forward"]
    assert fwd.parent_id == root.id
    assert by_name["veneur.flush.egress.attempt"].parent_id == fwd.id
    assert all(s.end_timestamp >= s.start_timestamp for s in c.spans)


def test_phase_timer_samples_are_local_only():
    from veneur_tpu.ingest.parser import LOCAL_ONLY

    fr = FlightRecorder()
    t = fr.begin_tick(1)
    with t.phase("engine"):
        pass
    p = t.start("forward")
    t.finish(t.start("egress.attempt", p))        # child: not emitted
    t.finish(p)
    fr.end_tick(t)
    samples = phase_timer_samples(t)
    names = {m.key.name for m in samples}
    assert names == {"veneur.flush.phase.engine",
                     "veneur.flush.phase.forward",
                     "veneur.flush.phase.total"}
    assert all(m.scope == LOCAL_ONLY for m in samples)
    assert all(m.key.type == "timer" for m in samples)
    assert all(m.value >= 0.0 for m in samples)


# ----------------------------------------------------- server ticks

def _mk_server(extra_cfg=None, **server_kw):
    cfg = read_config(text=_YAML)
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    for k, v in (extra_cfg or {}).items():
        setattr(cfg, k, v)
    cap = CaptureMetricSink()
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[],
                 **server_kw)
    srv.start()
    return srv, cap


def _feed(srv, n_keys=64, n_per_key=32):
    lines = []
    for k in range(n_keys):
        for v in range(n_per_key):
            lines.append(b"obs.t%d:%d.5|ms" % (k, v))
    srv.handle_packet(b"\n".join(lines))
    assert srv.drain(10.0)


def test_flush_tick_phase_coverage_at_least_95pct():
    """The acceptance gate: completed top-level phases must account for
    >= 95% of the measured tick wall time."""
    srv, cap = _mk_server()
    try:
        _feed(srv)
        srv.flush_once(timestamp=10)
        tick = srv.flight.last_tick()
        assert tick is not None and tick.mono_end > 0
        cov = tick.attributed_ns() / tick.duration_ns()
        assert cov >= 0.95, f"phase coverage {cov:.1%} < 95%"
        names = {p[0] for p in tick.phases()}
        assert {"engine", "engine.flush", "engine.drain",
                "engine.materialize", "telemetry",
                "fanout"} <= names
        assert any(n.startswith("engine.device") for n in names)
    finally:
        srv.stop()


def test_debug_flush_endpoint_serves_the_measured_tick():
    srv, cap = _mk_server({"http_address": "127.0.0.1:0"})
    try:
        _feed(srv, n_keys=8, n_per_key=4)
        srv.flush_once(timestamp=11)
        want = srv.flight.last_tick().tick_id
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_api.port}/debug/flush",
                timeout=5) as resp:
            state = json.loads(resp.read())
        ticks = state["flight_recorder"]["ticks"]
        assert ticks[0]["tick_id"] == want
        assert ticks[0]["duration_ns"] > 0
        names = {p["name"] for p in ticks[0]["phases"]}
        assert "engine" in names and "fanout" in names
        assert state["flush_count"] == 1
        # registry view rides along
        assert "server" in state["registry"]
        # profiler trigger is OFF by default -> 403, not 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_api.port}"
                "/debug/flush/profile?ticks=1", timeout=5)
        assert ei.value.code == 403
    finally:
        srv.stop()


def test_debug_flush_profile_trigger_gated_on():
    srv, cap = _mk_server({"http_address": "127.0.0.1:0",
                           "debug_flush_profile": True,
                           "debug_flush_profile_dir": "/tmp/vprof-test"})
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_api.port}"
                "/debug/flush/profile?ticks=1", timeout=5) as resp:
            out = json.loads(resp.read())
        assert out["capture_ticks"] == 1
        srv.flush_once(timestamp=1)   # consumes the capture window
        with srv._stats_lock:
            assert not srv._profile_active
            assert srv._profile_ticks <= 0
    finally:
        srv.stop()


def test_dogfood_phase_timers_flush_as_tenant_metrics():
    srv, cap = _mk_server()
    try:
        srv.flush_once(timestamp=1)
        assert srv.drain(10.0)         # phase samples land in workers
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        names = {m.name for m in cap.flushes[1]}
        phase_metrics = {n for n in names
                         if n.startswith("veneur.flush.phase.")}
        # timers flush as percentiles + aggregates of the phase name
        assert any("veneur.flush.phase.total" in n
                   for n in phase_metrics), names
        assert any("veneur.flush.phase.engine" in n
                   for n in phase_metrics)
    finally:
        srv.stop()


def test_flight_recorder_off_is_clean():
    srv, cap = _mk_server({"flight_recorder": False,
                           "http_address": "127.0.0.1:0"})
    try:
        _feed(srv, n_keys=4, n_per_key=4)
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        assert srv.flight is None
        assert srv.flush_count == 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_api.port}/debug/flush",
                timeout=5) as resp:
            state = json.loads(resp.read())
        assert state["flight_recorder"] is None
        # no dogfood timers either (they come from the recorder)
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        assert not any(m.name.startswith("veneur.flush.phase.")
                       for m in cap.flushes[1])
    finally:
        srv.stop()


def test_per_sink_phases_and_skip_counter():
    import threading

    from veneur_tpu.sinks import MetricSink

    class WedgedSink(MetricSink):
        def __init__(self):
            self.release = threading.Event()

        def name(self):
            return "wedged"

        def flush(self, metrics):
            pass

        def flush_frames(self, frames):
            self.release.wait(20.0)
            return 0

    slow = WedgedSink()
    cfg = Config(interval="3600s", hostname="h",
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64)
    cap = CaptureMetricSink()
    srv = Server(cfg, sinks=[slow, cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        srv.flush_once(timestamp=1)
        cap.wait_for_flush(1)
        t1 = srv.flight.last_tick()
        # the wedged sink's phase is in flight in the recorded tick
        wedged = [dict(zip(("name", "t0", "t1", "parent"), p))
                  for p in t1.phases() if p[0] == "sink.flush"]
        assert any(w["t1"] == 0 for w in wedged)
        srv.flush_once(timestamp=2)    # wedged still in flight -> skip
        t2 = srv.flight.last_tick()
        assert any(p[0] == "sink.skip" for p in t2.phases())
    finally:
        slow.release.set()
        srv.stop()


# ------------------------------------------------------- chaos arms

def _scripted_forwarder(schedule, reg):
    from veneur_tpu.cluster.forward import HttpJsonForwarder

    clock = FakeClock()
    egress = Egress(
        "chaos",
        policy=EgressPolicy(
            retry=RetryPolicy(max_attempts=3, base_backoff_s=0.001,
                              max_backoff_s=0.002, deadline_s=120.0),
            breaker=BreakerPolicy(failure_threshold=10_000)),
        transport=ScriptedTransport(schedule, clock),
        clock=clock, sleep=clock.sleep, rng=random.Random(42),
        registry=reg)
    inner = HttpJsonForwarder("http://scripted:1", timeout_s=5.0,
                              max_per_body=100, egress=egress)
    return ResilientForwarder(inner, destination="chaos",
                              sender_id="obs-sender", registry=reg)


def test_ack_loss_storm_surfaces_retry_and_replay_phases():
    """A seeded ack-loss storm's retries and replays must appear as
    phases in the recorded ticks, nested under `forward`."""
    reg = TelemetryRegistry()
    # tick 1: ack lost then retry ok; tick 2: hard fail (parks);
    # tick 3: replay ok + current ok; tick 4: a SEEDED ambiguous storm
    # (ends in "ok" so the ladder terminates)
    fwd = _scripted_forwarder(
        ["ack_lost", "ok", "refused", "refused", "refused", "ok", "ok"]
        + seeded_schedule(101, 8, p_fail=0.6, ambiguous=True),
        reg)
    cfg = read_config(text=_YAML)
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg.forward_address = "placeholder:1"
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[], forwarder=fwd)
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        ticks = []
        for r in range(4):
            c.sendto(b"obs.chaos:%d|c|#veneurglobalonly" % (r + 1),
                     ("127.0.0.1", port))
            deadline = time.monotonic() + 10
            while srv.packets_received < 1 and \
                    time.monotonic() < deadline:
                time.sleep(0.005)
            assert srv.drain(10.0)
            try:
                srv.flush_once(timestamp=100 + r)
            except Exception:
                pass   # tick 2's terminal failure parks the interval
            ticks.append(srv.flight.last_tick())
        c.close()
        names0 = [p[0] for p in ticks[0].phases()]
        # tick 1: ambiguous loss then a retried attempt, both under
        # forward
        assert names0.count("egress.attempt") >= 2
        fwd_idx = names0.index("forward")
        attempts = [p for p in ticks[0].phases()
                    if p[0] == "egress.attempt"]
        assert all(p[3] == fwd_idx for p in attempts)
        assert "forward.send" in names0
        # tick 3: the parked interval replays before the current send
        names2 = [p[0] for p in ticks[2].phases()]
        assert "forward.replay" in names2
        assert names2.index("forward.replay") < \
            names2.index("forward.send")
        # tick 4 (the seeded storm): its retries show as attempt
        # phases with failure outcomes in the meta
        storm = [dict(zip(("name", "t0", "t1", "parent"), p))
                 for p in ticks[3].phases()
                 if p[0] == "egress.attempt"]
        assert len(storm) >= 2
        metas = [s for s in ticks[3]._slots[:ticks[3].n]
                 if s.name == "egress.attempt"]
        assert any(m.meta and m.meta.get("outcome") != "ok"
                   for m in metas)
        assert any(m.meta and m.meta.get("outcome") == "ok"
                   for m in metas)
        # and the storm's counters rode the unified registry
        assert reg.peek("chaos", "retries") >= 1
        assert reg.total("chaos", "replayed") >= 1
    finally:
        srv.stop()


def test_simulated_kill_never_corrupts_the_ring():
    """A SimulatedKill (BaseException, like SIGKILL) escaping
    mid-forward must leave the recorder ring readable and the next
    tick recording cleanly — recorder state is process-local, no
    journal interaction."""
    reg = TelemetryRegistry()
    kill_fwd = ScriptedCallable(["kill"])
    cfg = read_config(text=_YAML)
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg.forward_address = "placeholder:1"
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[],
                 forwarder=ResilientForwarder(
                     kill_fwd, destination="kill", registry=reg))
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        c.sendto(b"obs.k:1|c|#veneurglobalonly", ("127.0.0.1", port))
        deadline = time.monotonic() + 10
        while srv.packets_received < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv.drain(10.0)
        with pytest.raises(SimulatedKill):
            srv.flush_once(timestamp=1)
        c.close()
        # the killed tick is closed and serializable
        killed = srv.flight.last_tick()
        assert killed.mono_end > 0
        json.dumps(srv.flight.snapshot())      # no corruption
        assert current_tick() is None          # scope was restored
        # the next tick records cleanly on the same ring
        srv.forwarder = None
        srv.flush_once(timestamp=2)
        t2 = srv.flight.last_tick()
        assert t2.tick_id == killed.tick_id + 1
        assert t2.attributed_ns() > 0
        json.dumps(srv.flight.snapshot())
    finally:
        srv.stop()


# --------------------------------------------------- scrape surface

def test_prometheus_sink_exposes_unified_registry():
    from veneur_tpu.sinks.prometheus import PrometheusMetricSink

    reg = TelemetryRegistry()
    reg.incr("dest", "attempts", 5)
    reg.incr_level(SERVER_SCOPE, "flush.count", 2)
    sink = PrometheusMetricSink("127.0.0.1:0", registries=(reg,))
    sink.start()
    try:
        from veneur_tpu.metrics import InterMetric
        sink.flush([InterMetric(name="api.hits", timestamp=1, value=3,
                                type=MetricType.COUNTER)])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{sink.port}/metrics",
                timeout=5) as resp:
            text = resp.read().decode()
        assert "api_hits 3" in text
        assert 'veneur_resilience_attempts_total{destination="dest"} 5' \
            in text
        assert "veneur_flush_count 2" in text
        # cumulative across drains: a drain must not zero the scrape
        reg.drain(2)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{sink.port}/metrics",
                timeout=5) as resp:
            text = resp.read().decode()
        assert 'veneur_resilience_attempts_total{destination="dest"} 5' \
            in text
    finally:
        sink.stop()


def test_prometheus_cli_self_metrics_surface():
    from veneur_tpu.cli.prometheus import start_self_metrics_server

    reg = TelemetryRegistry()
    reg.incr(SERVER_SCOPE, "prometheus.polls", 4)
    reg.incr(SERVER_SCOPE, "prometheus.series_relayed", 17)
    sink = start_self_metrics_server("127.0.0.1:0", reg)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{sink.port}/metrics",
                timeout=5) as resp:
            text = resp.read().decode()
        assert "veneur_prometheus_polls_total 4" in text
        assert "veneur_prometheus_series_relayed_total 17" in text
    finally:
        sink.stop()


def test_default_registry_is_the_resilience_registry():
    from veneur_tpu import resilience
    assert resilience.DEFAULT_REGISTRY is DEFAULT_REGISTRY
    assert resilience.ResilienceRegistry is TelemetryRegistry


def _fake_trace_client():
    class FakeClient:
        def __init__(self):
            self.spans = []

        def record(self, span):
            self.spans.append(span)
            return True

    return FakeClient()


# ------------------------------------------------ fleet-scope tracing

def test_fleetview_e2e_and_freshness_scripted_clock():
    from veneur_tpu.observe import FleetView

    clk = {"now": 1_000 * 10**9}
    fv = FleetView(max_senders=4, window=16,
                   clock=lambda: clk["now"])
    # two chunks of one interval collapse onto one pending sample
    fv.observe_interval("a", 7, close_ns=990 * 10**9)
    fv.observe_interval("a", 7, close_ns=990 * 10**9)
    fv.observe_interval("b", 3, close_ns=995 * 10**9)
    out = fv.on_flush(1_000 * 10**9)
    assert out == {"a": [10_000.0], "b": [5_000.0]}
    fresh = fv.freshness(1_002 * 10**9)
    assert fresh["a"] == 12 * 10**9 and fresh["b"] == 7 * 10**9
    st = fv.debug_state(1_002 * 10**9)
    row = st["senders"]["a"]
    assert row["e2e_ms"] == {"count": 1, "p50": 10_000.0,
                             "p99": 10_000.0}
    assert row["freshness_age_ms"] == 12_000.0
    assert row["intervals_merged"] == 1 and row["pending"] == 0
    # a deduped chunk (close 0) bumps last-seen but never e2e
    clk["now"] = 1_050 * 10**9
    fv.observe_interval("a", 7, 0)
    assert fv.on_flush(1_050 * 10**9) == {}
    assert fv.debug_state(1_050 * 10**9)["senders"]["a"][
        "last_seen_age_s"] == 0.0


def test_fleetview_bounds_lru_and_pending_overflow():
    from veneur_tpu.observe import FleetView
    from veneur_tpu.observe.fleet import MAX_PENDING_INTERVALS

    fv = FleetView(max_senders=2, window=8, clock=lambda: 10**9)
    for i in range(5):
        fv.observe_interval(f"s{i}", 1, close_ns=1)
    assert fv.sender_count() == 2                  # LRU bound
    fv2 = FleetView(max_senders=1, window=8, clock=lambda: 10**9)
    for i in range(MAX_PENDING_INTERVALS + 10):
        fv2.observe_interval("s", i, close_ns=1)
    assert fv2.pending_dropped == 10
    assert len(fv2.on_flush(10**9)["s"]) == MAX_PENDING_INTERVALS


def test_e2e_timer_samples_are_local_only_and_sender_tagged():
    from veneur_tpu.ingest.parser import LOCAL_ONLY
    from veneur_tpu.observe import e2e_timer_samples

    samples = e2e_timer_samples({"snd-1": [12.5, 80.0], "snd-2": [3.0]})
    assert len(samples) == 3
    assert all(m.scope == LOCAL_ONLY for m in samples)
    assert all(m.key.name == "veneur.e2e.interval_latency_ms"
               for m in samples)
    assert {m.key.joined_tags for m in samples} == {"sender:snd-1",
                                                    "sender:snd-2"}
    assert all(m.key.type == "timer" for m in samples)


def test_tick_pins_trace_identity_and_forward_stamps_it():
    """The flush tick mints its trace identity at begin_tick; every
    wire chunk the forward path emits while the tick runs carries that
    identity plus the interval-close stamp (scripted timestamps stay
    scripted), and emit_spans replays the SAME ids — the contract that
    makes the receiver's parenting line up."""
    from veneur_tpu.cluster import wire

    reg = TelemetryRegistry()
    seen_headers = []

    def transport(req, timeout=None):
        seen_headers.append(dict(req.header_items()))

        class R:
            status = 200

            def read(self):
                return b"{}"

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False
        return R()

    from veneur_tpu.cluster.forward import HttpJsonForwarder
    clock = FakeClock()
    egress = Egress("t", policy=EgressPolicy(), transport=transport,
                    clock=clock, sleep=clock.sleep,
                    rng=random.Random(1), registry=reg)
    fwd = ResilientForwarder(
        HttpJsonForwarder("http://t:1", timeout_s=5.0, egress=egress),
        destination="t", sender_id="tr-sender", registry=reg)
    cfg = read_config(text=_YAML)
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg.forward_address = "placeholder:1"
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[], forwarder=fwd)
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        c.sendto(b"tr.c:1|c|#veneurglobalonly", ("127.0.0.1", port))
        deadline = time.monotonic() + 10
        while srv.packets_received < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv.drain(10.0)
        srv.flush_once(timestamp=1234)
        c.close()
        tick = srv.flight.last_tick()
        assert tick.trace_id and tick.span_id
        assert tick.close_ns == 1234 * 10**9
        assert seen_headers, "no forward happened"
        trace = wire.trace_from_headers(seen_headers[0])
        assert trace == (tick.trace_id, tick.span_id, 1234 * 10**9)
        # envelope identity rides alongside, unchanged
        env = wire.envelope_from_headers(seen_headers[0])
        assert env[0] == "tr-sender"
        # span replay uses the SAME pinned ids
        client = _fake_trace_client()
        srv.flight.emit_spans(tick, client)
        root = next(s for s in client.spans if s.name == "veneur.flush")
        assert root.trace_id == tick.trace_id
        assert root.id == tick.span_id and root.parent_id == 0
    finally:
        srv.stop()


def test_recorder_off_stamps_no_trace_headers():
    """flight_recorder: false -> no tick, no trace context on the wire
    (legacy header set, byte-identical), and forwarding still works."""
    from veneur_tpu.cluster import wire

    reg = TelemetryRegistry()
    seen = []

    def transport(req, timeout=None):
        seen.append(dict(req.header_items()))

        class R:
            status = 200

            def read(self):
                return b"{}"

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False
        return R()

    from veneur_tpu.cluster.forward import HttpJsonForwarder
    clock = FakeClock()
    egress = Egress("t", policy=EgressPolicy(), transport=transport,
                    clock=clock, sleep=clock.sleep,
                    rng=random.Random(1), registry=reg)
    fwd = ResilientForwarder(
        HttpJsonForwarder("http://t:1", timeout_s=5.0, egress=egress),
        destination="t", sender_id="tr-sender", registry=reg)
    cfg = read_config(text=_YAML)
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg.forward_address = "placeholder:1"
    cfg.flight_recorder = False
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[], forwarder=fwd)
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        c.sendto(b"tr.c:1|c|#veneurglobalonly", ("127.0.0.1", port))
        deadline = time.monotonic() + 10
        while srv.packets_received < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv.drain(10.0)
        srv.flush_once(timestamp=5)
        c.close()
        assert seen
        assert wire.trace_from_headers(seen[0]) is None
        assert wire.envelope_from_headers(seen[0])[0] == "tr-sender"
        assert not any(k.lower().startswith("x-veneur-trace")
                       for k in seen[0])
    finally:
        srv.stop()


def test_import_observer_parents_spans_on_remote_trace():
    """HTTP /import with a propagated trace context: the receiver's
    dedupe/apply phases land in the import ring AND replay as SSF
    spans carrying the SENDER's trace_id, rooted under the sender's
    flush span id — one span tree across two processes."""
    from veneur_tpu.cluster import wire

    cfg = read_config(text=_YAML)
    cfg.http_address = "127.0.0.1:0"
    cfg.is_global = True
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[])
    srv.trace_client = client = _fake_trace_client()
    srv.start()
    try:
        port = srv.http_api.port
        body = [{"name": "ft.c", "type": "counter", "tags": [],
                 "value": 2}]
        headers = {"Content-Type": "application/json",
                   "X-Veneur-Forward-Version": "jsonmetric-v1"}
        headers.update(wire.envelope_headers(
            "remote-snd", 41, 0, 1, trace_id=777_000,
            span_id=888_000, close_ns=900 * 10**9))
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/import",
            data=json.dumps(body).encode(), headers=headers,
            method="POST")
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert json.loads(resp.read()) == {"imported": 1}
        # the ring record publishes AFTER the reply (scope __exit__)
        deadline = time.monotonic() + 5
        while srv.import_observer.flight.tick_count < 1 and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        # the import tick recorded the request's phases
        snap = srv.import_observer.flight.snapshot()
        names = {p["name"] for p in snap[0]["phases"]}
        assert {"decode", "dedupe", "route", "request"} <= names
        reqmeta = next(p for p in snap[0]["phases"]
                       if p["name"] == "request")["meta"]
        assert reqmeta["sender"] == "remote-snd"
        assert reqmeta["seq"] == 41 and reqmeta["admitted"] is True
        # and replayed as spans grafted under the REMOTE flush span
        assert client.spans, "no import spans emitted"
        assert all(s.trace_id == 777_000 for s in client.spans)
        root = next(s for s in client.spans
                    if s.name == "veneur.import")
        assert root.parent_id == 888_000
        child = next(s for s in client.spans
                     if s.name == "veneur.import.route")
        assert child.parent_id == root.id
        # a replayed chunk dedupes (200) and still records its phases
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert json.loads(resp.read()) == {"imported": 0,
                                               "deduped": True}
        deadline = time.monotonic() + 5
        while srv.import_observer.flight.tick_count < 2 and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        snap = srv.import_observer.flight.snapshot()
        reqmeta = next(p for p in snap[0]["phases"]
                       if p["name"] == "request")["meta"]
        assert reqmeta["admitted"] is False
        # fleet view: the sender's interval is pending until a flush
        assert srv.drain(10.0)
        srv.flush_once(timestamp=960)
        st = srv.fleet.debug_state()
        row = st["senders"]["remote-snd"]
        assert row["e2e_ms"]["count"] == 1
        assert row["e2e_ms"]["p50"] == 60_000.0   # (960-900)s in ms
        assert row["newest_close_ns"] == 900 * 10**9
    finally:
        srv.stop()


def test_grpc_import_spans_carry_remote_trace():
    """The gRPC arm: SendMetrics with an envelope + trace context in
    the MetricList — receiver import spans carry the sender's ids."""
    grpc = pytest.importorskip("grpc")
    from veneur_tpu.cluster import wire
    from veneur_tpu.cluster.forward import SEND_METRICS
    from veneur_tpu.cluster.protos import forward_pb2, metric_pb2

    cfg = read_config(text=_YAML)
    cfg.grpc_listen_addresses = ["127.0.0.1:0"]
    cfg.is_global = True
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[])
    srv.trace_client = client = _fake_trace_client()
    srv.start()
    try:
        m = metric_pb2.Metric(name="ft.g", type=metric_pb2.Counter)
        m.counter.value = 3
        ml = forward_pb2.MetricList(metrics=[m])
        ml.envelope.CopyFrom(wire.envelope_pb(
            "grpc-snd", 9, 0, 1, trace_id=1234, span_id=5678,
            close_ns=10**9))
        with grpc.insecure_channel(
                f"127.0.0.1:{srv.grpc_port}") as ch:
            send = ch.unary_unary(
                SEND_METRICS,
                request_serializer=forward_pb2.MetricList
                .SerializeToString,
                response_deserializer=forward_pb2.Empty.FromString)
            send(ml, timeout=10)
        assert client.spans
        assert all(s.trace_id == 1234 for s in client.spans)
        root = next(s for s in client.spans
                    if s.name == "veneur.import")
        assert root.parent_id == 5678
        st = srv.fleet.debug_state()
        assert "grpc-snd" in st["senders"]
    finally:
        srv.stop()


def test_import_ring_private_records_survive_overload():
    """Regression (review finding): handler threads record into
    PRIVATE TickRecords published at request end — a ring slot handed
    out at request START would be recycled out from under a slow
    request once in-flight requests exceed ring capacity."""
    from veneur_tpu.observe import ImportObserver

    obs = ImportObserver(flight=FlightRecorder(capacity=2,
                                               max_phases=16))
    slow = obs.request(("slow", 1, 0, 1), None, "http")
    slow.__enter__()
    ph = slow.start("decode")
    # a burst larger than ring capacity completes while slow is open
    for i in range(5):
        with obs.request(("fast", i, 0, 1), None, "http") as sc:
            sc.admitted = True
    slow.finish(ph, n_metrics=1)
    slow.admitted = True
    slow.__exit__(None, None, None)
    # the slow request's record is intact and newest in the ring
    newest = obs.flight.snapshot()[0]
    req = next(p for p in newest["phases"] if p["name"] == "request")
    assert req["meta"]["sender"] == "slow"
    decode = next(p for p in newest["phases"] if p["name"] == "decode")
    assert decode["end_ns"] is not None
    assert obs.flight.tick_count == 6


def test_rejected_import_never_bumps_fleet_last_seen():
    """Regression (review finding): a request 400'd before a dedupe
    verdict must NOT feed the fleet view — a sender whose every body
    fails decode would otherwise look freshly alive on the very page
    an operator consults to find it."""
    from veneur_tpu.cluster import wire

    cfg = read_config(text=_YAML)
    cfg.http_address = "127.0.0.1:0"
    cfg.is_global = True
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[])
    srv.start()
    try:
        headers = {"Content-Type": "application/json",
                   "X-Veneur-Forward-Version": "jsonmetric-v1"}
        headers.update(wire.envelope_headers("bad-snd", 1, 0, 1))
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.http_api.port}/import",
            data=b"{not json", headers=headers, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 400
        # the ring record publishes AFTER the reply (scope __exit__):
        # wait for the handler thread to finish the scope
        deadline = time.monotonic() + 5
        while srv.import_observer.flight.tick_count < 1 and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        assert "bad-snd" not in srv.fleet.debug_state()["senders"]
        # the rejected request still left a readable ring record
        snap = srv.import_observer.flight.snapshot()
        reqmeta = next(p for p in snap[0]["phases"]
                       if p["name"] == "request")["meta"]
        assert reqmeta["admitted"] is False
    finally:
        srv.stop()


def test_healthz_and_ready_verdicts():
    """GET /healthz + /ready: structured verdicts; a wedged flusher
    flips /healthz to 503 within HEALTH_STALL_INTERVALS of interval
    (detectable from OUTSIDE the process), while degradation signals
    (queue fill, breaker) mark status without failing the probe."""
    srv, cap = _mk_server({"http_address": "127.0.0.1:0"})
    try:
        base = f"http://127.0.0.1:{srv.http_api.port}"
        for path in ("/healthz", "/ready"):
            with urllib.request.urlopen(base + path, timeout=5) as r:
                body = json.loads(r.read())
            assert r.status == 200
        assert body["healthy"] and body["ready"]
        assert body["status"] == "ok"
        assert body["checks"]["flush"]["ok"]
        assert body["checks"]["queues"]["ok"]
        # injectable clock: one interval late is NOT stalled ...
        iv = srv.cfg.interval_seconds
        now0 = srv._last_flush_ok
        assert srv.health_state(now=now0 + 1.4 * iv)["healthy"]
        # ... 1.5 intervals late IS — and the endpoint answers 503
        v = srv.health_state(now=now0 + 1.6 * iv)
        assert not v["healthy"] and v["status"] == "stalled"
        assert not v["checks"]["flush"]["ok"]
        srv._last_flush_ok -= 1.6 * iv
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "stalled"
        # /ready flips on stop
        srv._last_flush_ok = time.monotonic()
        srv._stop.set()
        assert not srv.health_state()["ready"]
        srv._stop.clear()
    finally:
        srv.stop()


def test_watchdog_counts_stalled_ticks():
    """A wedged flusher increments veneur.watchdog.stalled_ticks_total
    once per overdue interval — without the crash-only exit arm
    (flush_watchdog_missed_flushes=0, the default)."""
    cfg = Config(interval="0.05s", hostname="wd",
                 tpu_histogram_slots=64, tpu_counter_slots=32,
                 tpu_gauge_slots=32, tpu_set_slots=16)
    srv = Server(cfg, sinks=[], plugins=[], span_sinks=[])
    srv.flush_once = lambda *a, **k: time.sleep(3600)
    srv.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if srv.telemetry.total(SERVER_SCOPE,
                                   "watchdog.stalled_ticks") >= 2:
                break
            time.sleep(0.01)
        total = srv.telemetry.total(SERVER_SCOPE,
                                    "watchdog.stalled_ticks")
        assert total >= 2
        v = srv.health_state()
        assert not v["healthy"]
        assert v["checks"]["flush"]["stalled_ticks_total"] == total
    finally:
        srv._stop.set()
        srv.stop()


def test_debug_fleet_endpoint_both_tiers_view():
    """GET /debug/fleet on a forwarding server: no fleet senders (it
    receives nothing) but its OWN ladder summary; health rides along;
    always parseable JSON."""
    reg = TelemetryRegistry()
    fwd = _scripted_forwarder(["refused"] * 3 + ["ok"] * 8, reg)
    cfg = read_config(text=_YAML)
    cfg.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg.http_address = "127.0.0.1:0"
    cfg.forward_address = "placeholder:1"
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[], forwarder=fwd)
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        c.sendto(b"fl.c:1|c|#veneurglobalonly", ("127.0.0.1", port))
        deadline = time.monotonic() + 10
        while srv.packets_received < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv.drain(10.0)
        srv.flush_once(timestamp=1)   # terminal failure parks (caught)
        c.close()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_api.port}/debug/fleet",
                timeout=5) as resp:
            st = json.loads(resp.read())
        assert st["forward"]["ladder_depth"] == 1
        assert st["forward"]["sender_id"] == "obs-sender"
        assert "health" in st and "senders" in st
        assert st["import_recorder"] is None or isinstance(
            st["import_recorder"], dict)
    finally:
        srv.stop()


def test_fleet_row_for_ledger_only_sender_has_full_shape():
    """Regression (review finding): a sender known only from restored
    dedupe watermarks (journal recovery, nothing forwarded yet this
    incarnation) still gets the full documented /debug/fleet row shape
    — a dashboard indexing row["e2e_ms"] must not KeyError on a
    restarted fleet."""
    cfg = read_config(text=_YAML)
    cfg.http_address = "127.0.0.1:0"
    cfg.is_global = True
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[])
    srv.start()
    try:
        # watermark present with NO fleet-view traffic (bypasses the
        # import observer, like a journal-restored watermark)
        srv.dedupe_ledger.admit("ghost-snd", 7, 0, 1)
        row = srv._debug_fleet_state()["senders"]["ghost-snd"]
        assert row["dedupe_watermark"] == 7
        assert row["e2e_ms"] == {"count": 0, "p50": 0.0, "p99": 0.0}
        assert row["intervals_merged"] == 0 and row["pending"] == 0
        assert row["freshness_age_ms"] is None
    finally:
        srv.stop()


def test_phases_dropped_exported_as_self_metric():
    """Ring overflow reaches the registry drain: a tick that drops
    phases to the slot budget exports a nonzero
    veneur.observe.phases_dropped_total, and the counter is
    present-at-zero on clean ticks."""
    srv, cap = _mk_server({"flight_recorder_max_phases": 8})
    try:
        _feed(srv, n_keys=8, n_per_key=4)
        srv.flush_once(timestamp=1)
        assert srv.flight.last_tick().dropped > 0
        # counted after this tick's self-metric drain -> rides the NEXT
        # flush body (like every end-of-tick counter)
        assert srv.telemetry.peek(SERVER_SCOPE,
                                  "observe.phases_dropped") > 0
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        m = next(m for m in cap.flushes[1]
                 if m.name == "veneur.observe.phases_dropped_total")
        assert m.value > 0
        # present-at-zero on a clean-tick server
        srv2, cap2 = _mk_server()
        try:
            srv2.flush_once(timestamp=1)
            cap2.wait_for_flush(1)
            m = next(m for m in cap2.flushes[0]
                     if m.name == "veneur.observe.phases_dropped_total")
            assert m.value == 0
        finally:
            srv2.stop()
    finally:
        srv.stop()


def test_fanout_timers_flush_per_sink():
    """flush_phase_timers grows per-sink fan-out children: each sink's
    flush duration dogfoods as veneur.flush.phase.fanout.<sink> —
    LOCAL-ONLY, from the sink's own thread."""
    from veneur_tpu.observe import fanout_timer_sample

    s = fanout_timer_sample("vendorx", 12.5)
    from veneur_tpu.ingest.parser import LOCAL_ONLY
    assert s.key.name == "veneur.flush.phase.fanout.vendorx"
    assert s.scope == LOCAL_ONLY and s.key.type == "timer"

    srv, cap = _mk_server()
    try:
        srv.flush_once(timestamp=1)
        assert srv.drain(10.0)        # fanout samples land in workers
        srv.flush_once(timestamp=2)
        cap.wait_for_flush(2)
        names = {m.name for m in cap.flushes[1]}
        assert any(n.startswith(
            "veneur.flush.phase.fanout." + cap.name())
            for n in names), sorted(
                n for n in names if "fanout" in n)
    finally:
        srv.stop()


def test_two_tier_probe_one_span_tree_fleet_view_and_health():
    """The acceptance probe: real UDP -> local Server -> real HTTP
    forward -> global Server. One span tree spans both processes (the
    receiver's import spans carry the SENDER's trace_id, rooted under
    the sender's flush span), GET /debug/fleet on the global reports
    per-sender e2e p50/p99 and freshness consistent with the scripted
    clock, and /healthz flips unhealthy within 1.5 intervals of a
    wedged flusher — all without changing a byte of merged state
    (the exactly-once chaos oracles pin that half)."""
    from veneur_tpu.cluster.forward import HttpJsonForwarder

    cfg_g = read_config(text=_YAML)
    cfg_g.http_address = "127.0.0.1:0"
    cfg_g.is_global = True
    glob = Server(cfg_g, sinks=[CaptureMetricSink()], plugins=[])
    glob.trace_client = gclient = _fake_trace_client()
    glob.start()

    reg = TelemetryRegistry()
    base = f"http://127.0.0.1:{glob.http_api.port}"
    fwd = ResilientForwarder(
        HttpJsonForwarder(base, timeout_s=5.0),
        destination="probe-global", sender_id="probe-sender",
        registry=reg)
    cfg_l = read_config(text=_YAML)
    cfg_l.statsd_listen_addresses = ["udp://127.0.0.1:0"]
    cfg_l.forward_address = "placeholder:1"
    local = Server(cfg_l, sinks=[CaptureMetricSink()], plugins=[],
                   forwarder=fwd)
    local.start()
    try:
        port = local.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        sender_traces = []
        for r, close_ts in enumerate((1000, 1010)):
            c.sendto(b"\n".join(
                [b"probe.t:%d|ms" % (100 + r)]
                + [b"probe.total:%d|c|#veneurglobalonly" % (r + 1)]),
                ("127.0.0.1", port))
            deadline = time.monotonic() + 10
            while local.packets_received < 1 and \
                    time.monotonic() < deadline:
                time.sleep(0.005)
            assert local.drain(10.0)
            local.flush_once(timestamp=close_ts)
            t = local.flight.last_tick()
            sender_traces.append((t.trace_id, t.span_id))
        c.close()
        assert glob.drain(10.0)
        merged = glob.flush_once(timestamp=1060)

        # --- one span tree across both processes ---
        assert gclient.spans, "global recorded no import spans"
        import_roots = [s for s in gclient.spans
                        if s.name == "veneur.import"]
        got = {(s.trace_id, s.parent_id) for s in import_roots}
        assert got == set(sender_traces)
        # every IMPORT span joins its sender's trace (the global's own
        # veneur.flush tree keeps its own local trace, as it should)
        for s in gclient.spans:
            if s.name.startswith("veneur.import"):
                assert s.trace_id in {t for t, _ in sender_traces}

        # --- merged state: trace context changed nothing ---
        total = next(m for m in merged if m.name == "probe.total")
        assert total.value == 3.0         # 1 + 2, exactly once

        # --- /debug/fleet: e2e + freshness off the scripted clock ---
        with urllib.request.urlopen(base + "/debug/fleet",
                                    timeout=5) as resp:
            st = json.loads(resp.read())
        row = st["senders"]["probe-sender"]
        # closes at 1000/1010, merged at 1060 -> 60s and 50s
        assert row["e2e_ms"]["count"] == 2
        assert row["e2e_ms"]["p50"] == 50_000.0
        assert row["e2e_ms"]["p99"] == 60_000.0
        assert row["newest_close_ns"] == 1010 * 10**9
        assert row["intervals_merged"] == 2
        assert row["dedupe_watermark"] >= 1
        # the e2e timers dogfood as LOCAL-ONLY tenant metrics next tick
        assert glob.drain(10.0)
        merged2 = glob.flush_once(timestamp=1061)
        assert any(m.name.startswith("veneur.e2e.interval_latency_ms")
                   for m in merged2)

        # --- /healthz flips on a wedged flusher ---
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert json.loads(r.read())["status"] in ("ok", "degraded")
        glob._last_flush_ok -= 1.6 * glob.cfg.interval_seconds
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "stalled"
    finally:
        local.stop()
        glob.stop()


def test_storm_tick_records_fold_phases_in_the_ring():
    """ISSUE 7: a cardinality-storm tick shows its degradation IN the
    flight-recorder ring — an `overload` phase carrying the governor's
    rate, with an `overload.fold` child carrying the interval's fold
    counts — right next to the phases explaining the tick's time, and
    serialized through the same /debug/flush snapshot."""
    cfg = read_config(text=_YAML + """
statsd_listen_addresses: ["udp://127.0.0.1:0"]
overload_defense_enabled: true
overload_max_keys_per_prefix: 2
flush_phase_timers: false
""")
    cap = CaptureMetricSink()
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    try:
        port = srv.bound_port()
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for k in range(12):              # 2 in budget, 10 folded
            s.sendto(b"st.u%d:1|c" % k, ("127.0.0.1", port))
        deadline = time.monotonic() + 5
        while srv.packets_received < 12 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.drain(5)
        srv.flush_once(timestamp=7)

        tick = srv.flight.last_tick()
        by_name = {}
        for i, (name, t0, t1, parent) in enumerate(tick.phases()):
            by_name[name] = (i, parent, t1 > 0)
        assert "overload" in by_name and by_name["overload"][2]
        ov_idx = by_name["overload"][0]
        assert by_name["overload"][1] == -1          # top-level phase
        assert by_name["overload.fold"][1] == ov_idx  # nested child
        # meta rides the snapshot the /debug/flush endpoint serves
        snap = tick.to_dict()
        fold = next(p for p in snap["phases"]
                    if p["name"] == "overload.fold")
        assert fold["meta"]["folded"] == 10
        ov = next(p for p in snap["phases"] if p["name"] == "overload")
        assert ov["meta"]["rate"] == 1.0
        assert ov["meta"]["overloaded"] is False
        # a healthy (no-fold) tick records the governor phase alone
        srv.flush_once(timestamp=8)
        names = [p[0] for p in srv.flight.last_tick().phases()]
        assert "overload" in names
        assert "overload.fold" not in names
        assert "overload.shed" not in names
    finally:
        srv.stop()


# ------------------------- ISSUE 26: forward.send and the import, opened

def _rows(tick, name):
    """[(t0, t1, parent, idx)] of a tick's completed phases of one
    name, in start order."""
    return sorted((t0, t1, par, i)
                  for i, (n, t0, t1, par) in enumerate(tick.phases())
                  if n == name and t1 > t0)


def test_stamp_log_budget_merge_and_take():
    from veneur_tpu.observe import StampLog

    log = StampLog({"a": 3, "b": 2})
    for k in range(5):                      # 5 stamps, budget 3
        log.add("a", 100 * k, 100 * k + 10)
    log.add("b", 0, 50)
    log.add("b", 60, 90, merge_gap_ns=20)   # 10 ns after: one busy run
    log.add("b", 200, 210, merge_gap_ns=20)  # too late: a row of its own
    log.add("b", 205, 215, merge_gap_ns=20)  # overlaps, over budget
    got = log.take()
    a = [r for r in got if r[0] == "a"]
    b = [r for r in got if r[0] == "b"]
    # past the budget the last row lengthens: seconds stay exact
    assert [r[1] for r in a] == [0, 100, 200] and len(a) == 3
    assert sum(t1 - t0 for _n, t0, t1 in a) == 50
    assert b == [("b", 0, 90), ("b", 200, 220)]
    assert log.take() == []                 # a take empties the log
    log.add("a", 7, 9)
    assert log.take() == [("a", 7, 9)]


def test_graft_nests_children_and_clips_roots_out_of_coverage():
    fr = FlightRecorder(capacity=2, max_phases=32)
    t = fr.begin_tick(ts=1)
    own = t.start("engine")
    base = t.mono_start
    rows = [("import.land", base - 900, base - 500),
            ("import.land.stage", base - 900, base - 800),
            ("import.land.cluster", base - 800, base - 600),
            ("import.route", base - 850, base - 840),   # another thread
            ("import.land", base - 400, base - 300),
            ("import.apply", base - 1000, base - 100)]
    root = t.graft(rows, root="import")
    t.finish(own)
    fr.end_tick(t)
    ph = t.phases()
    assert ph[root][:3] == ("import", base - 1000, base - 100)
    assert ph[root][3] == -1
    by = {n: [p for p in ph if p[0] == n] for n in {p[0] for p in ph}}
    first_land = ph.index(("import.land", base - 900, base - 500, root))
    assert by["import.land.stage"][0][3] == first_land
    assert by["import.land.cluster"][0][3] == first_land
    assert by["import.route"][0][3] == root     # same window, no prefix
    assert all(p[3] == root for p in by["import.land"])
    # the grafted root lies before the tick: none of its seconds are
    # the tick's, so coverage is the own phase's share and <= 1
    assert t.attributed_ns() <= t.duration_ns()
    assert t.attributed_ns() == ph[own][2] - ph[own][1]
    d = t.to_dict()
    assert d["phases"][root]["start_ns"] == -1000     # before the tick
    assert t.graft([], root="ingest") == -1 and t.n == len(ph)
    # phase timers: the root's extent, like any top-level phase
    names = {m.key.name for m in phase_timer_samples(t)}
    assert "veneur.flush.phase.import" in names


def _two_tier_grpc(monkeypatch, stage_digests=8, per_batch=8):
    """A local forwarding over real gRPC, `per_batch` metrics a chunk,
    to a global whose import landing fires every `stage_digests`
    digests (so some landings run mid-interval, on the worker)."""
    from veneur_tpu.cluster.forward import GrpcForwarder
    from veneur_tpu.models import pipeline

    monkeypatch.setattr(pipeline, "_IMPORT_STAGE_DIGESTS", stage_digests)
    cfg_g = read_config(text=_YAML)
    cfg_g.grpc_listen_addresses = ["127.0.0.1:0"]
    glob = Server(cfg_g, sinks=[CaptureMetricSink()], plugins=[],
                  span_sinks=[])
    glob.start()
    fwd = ResilientForwarder(
        GrpcForwarder(f"127.0.0.1:{glob.grpc_port}", timeout_s=10.0,
                      max_per_batch=per_batch),
        destination="t26-global", sender_id="t26-sender",
        registry=TelemetryRegistry())
    cfg_l = read_config(text=_YAML)
    cfg_l.forward_address = "placeholder:1"
    local = Server(cfg_l, sinks=[CaptureMetricSink()], plugins=[],
                   span_sinks=[], forwarder=fwd)
    local.start()
    return local, glob


def test_forward_send_and_import_are_open_in_both_flush_ticks(
        monkeypatch):
    """One forwarded interval, read from the two flush ticks alone (as
    the benchmark and /debug/flush read them): the local's tick splits
    forward.send per chunk; the global's NEXT tick carries the import
    work done since its previous flush under one `import` root."""
    local, glob = _two_tier_grpc(monkeypatch)
    try:
        lines = [b"t26.lat%d:%d|ms|#veneurglobalonly" % (k, 10 + v)
                 for k in range(20) for v in range(6)]
        local.handle_packet(b"\n".join(lines))
        assert local.drain(10.0)
        local.flush_once(timestamp=2000)
        assert glob.drain(10.0)
        drained_ns = time.monotonic_ns()
        merged = glob.flush_once(timestamp=2005)
        assert sum(m.name.endswith(".count") and m.value == 6.0
                   for m in merged if m.name.startswith("t26.")) == 20

        # ---- local tier: forward.send, opened
        lt = local.flight.last_tick()
        assert lt.dropped == 0
        (s0, s1, fwd_par, _i), = _rows(lt, "forward.send")
        export, = _rows(lt, "forward.export")
        plan, = _rows(lt, "forward.chunk.plan")
        builds = _rows(lt, "forward.chunk.build")
        sers = _rows(lt, "forward.chunk.serialize")
        atts = _rows(lt, "egress.attempt")
        assert len(builds) == len(sers) == len(atts) >= 3    # 20 / 8
        assert s0 <= export[0] and export[1] <= plan[0] \
            and plan[1] <= builds[0][0]
        for b, s, a in zip(builds, sers, atts):
            assert b[1] <= s[0] and s[1] <= a[0]
            assert s0 <= b[0] and a[1] <= s1       # inside forward.send
        # the same scope as the ladder: all of them under `forward`
        assert {r[2] for r in [export, plan] + builds + sers + atts} \
            == {fwd_par}
        release, = _rows(lt, "forward.release")
        assert atts[-1][1] <= release[0] and release[1] <= s1
        # the byte counter reads the serialized chunks' lengths: what
        # ByteSize() said before the chunks were serialized up front
        sent = sum(sl.meta["nbytes"] for sl in lt._slots[:lt.n]
                   if sl.name == "forward.chunk.serialize")
        egress = local.forwarder.inner._egress
        assert sent == egress.registry.total(
            egress.destination, "forward.bytes") > 0
        split = sum(r[1] - r[0] for r in [export, plan, release] + builds
                    + sers + atts)
        assert split <= s1 - s0

        # ---- global tier: the import, grafted into the flush tick
        gt = glob.flight.last_tick()
        assert gt.dropped == 0
        (i0, i1, ipar, root), = _rows(gt, "import")
        assert ipar == -1 and i0 < gt.mono_start
        decs = _rows(gt, "import.decode")
        deds = _rows(gt, "import.dedupe")
        routes = _rows(gt, "import.route")
        assert len(decs) == len(deds) == len(routes) == len(atts)
        for d, e, r in zip(decs, deds, routes):
            assert d[1] <= e[0] and e[1] <= r[0]
            assert d[2] == e[2] == r[2] == root
        applies = _rows(gt, "import.apply")
        assert applies and all(a[2] == root for a in applies)
        assert routes[0][0] <= applies[0][0]
        assert applies[-1][1] <= drained_ns
        assert i0 == decs[0][0] and i1 == max(
            r[1] for r in applies + routes + _rows(gt, "import.land")
            if r[2] == root)
        # landings: 20 digests, one every 8 on the worker (under the
        # root), the rest at flush time inside engine.drain
        (d0, d1, _p, drain), = _rows(gt, "engine.drain")
        lands = _rows(gt, "import.land")
        assert [ld[2] for ld in lands] == [root, root, drain]
        assert lands[0][0] >= applies[0][0] and d0 <= lands[2][0] \
            and lands[2][1] <= d1
        for name in ("import.land.stage", "import.land.cluster"):
            kids = _rows(gt, name)
            assert [k[2] for k in kids] == [ld[3] for ld in lands]
        # coverage stays a share of the tick: the root is clipped out
        cov = gt.attributed_ns() / gt.duration_ns()
        assert 0.95 <= cov <= 1.0, cov
        # /debug/flush shape: offsets before the tick are negative
        d = gt.to_dict()["phases"][root]
        assert d["name"] == "import" and d["start_ns"] < 0

        # the next interval's tick is its own: nothing carried over
        glob.flush_once(timestamp=2010)
        assert not _rows(glob.flight.last_tick(), "import")
        # ... but the dogfood timer of the import root flushed there
        assert glob.drain(10.0)
        out = {m.name for m in glob.flush_once(timestamp=2015)}
        assert any(n.startswith("veneur.flush.phase.import") for n in out)
    finally:
        local.stop()
        glob.stop()


def test_http_forward_uses_the_same_phase_names():
    """The jsonmetric-v1 arm: same vocabulary for the same steps, and
    the import ring's `route` reaches the global's tick too."""
    from veneur_tpu.cluster.forward import HttpJsonForwarder

    cfg_g = read_config(text=_YAML)
    cfg_g.http_address = "127.0.0.1:0"
    cfg_g.is_global = True
    glob = Server(cfg_g, sinks=[CaptureMetricSink()], plugins=[],
                  span_sinks=[])
    glob.start()
    fwd = ResilientForwarder(
        HttpJsonForwarder(f"http://127.0.0.1:{glob.http_api.port}",
                          timeout_s=5.0, max_per_body=4),
        destination="t26-http", sender_id="t26-http-sender",
        registry=TelemetryRegistry())
    cfg_l = read_config(text=_YAML)
    cfg_l.forward_address = "placeholder:1"
    local = Server(cfg_l, sinks=[CaptureMetricSink()], plugins=[],
                   span_sinks=[], forwarder=fwd)
    local.start()
    try:
        local.handle_packet(b"\n".join(
            b"t26h.c%d:1|c|#veneurglobalonly" % k for k in range(10)))
        assert local.drain(10.0)
        local.flush_once(timestamp=3000)
        lt = local.flight.last_tick()
        assert len(_rows(lt, "forward.export")) == 1
        n = len(_rows(lt, "egress.attempt"))
        assert n == 3 == len(_rows(lt, "forward.chunk.build")) \
            == len(_rows(lt, "forward.chunk.serialize"))
        assert len(_rows(lt, "forward.release")) == 1
        assert glob.drain(10.0)
        # the ring record (and its stamps) publish after the reply
        deadline = time.monotonic() + 5
        while glob.import_observer.flight.tick_count < n and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        glob.flush_once(timestamp=3005)
        gt = glob.flight.last_tick()
        for name in ("import.decode", "import.dedupe", "import.route"):
            assert len(_rows(gt, name)) == n, name
        assert _rows(gt, "import.apply")
    finally:
        local.stop()
        glob.stop()


def test_flooded_tick_coalesces_within_its_budgets():
    """Far more import requests, apply runs and landings than a tick
    has rows for: every kind stops at its budget, the seconds stay
    exact, and the tick drops nothing."""
    cfg = read_config(text=_YAML)
    cfg.is_global = True
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[])
    srv.start()
    try:
        now = time.monotonic_ns()
        eng = srv.engines[0]
        budget = Server.GRAFT_BUDGET
        for k in range(500):
            t0 = now - 10_000_000 + 10_000 * k
            for name in ("import.decode", "import.dedupe",
                         "import.route"):
                srv._import_stamps.add(name, t0, t0 + 700)
            # busy runs 5 us apart in time: far closer than the merge gap
            srv._import_stamps.add("import.apply", t0 + 1000, t0 + 6000,
                                   Server.APPLY_MERGE_GAP_NS)
            eng.land_stamps.add("import.land", t0, t0 + 900)
            eng.land_stamps.add("import.land.stage", t0, t0 + 300)
            eng.land_stamps.add("import.land.cluster", t0 + 300, t0 + 600)
        srv.flush_once(timestamp=4000)
        t = srv.flight.last_tick()
        assert t.dropped == 0 and t.n < srv.flight.max_phases
        for name, per, want in (
                ("import.decode", 700, budget["import.request"]),
                ("import.route", 700, budget["import.request"]),
                ("import.land", 900, budget["import.land"]),
                ("import.land.cluster", 300, budget["import.land"])):
            rows = _rows(t, name)
            assert len(rows) == want, name
            assert sum(r[1] - r[0] for r in rows) == 500 * per, name
        # 500 adjacent busy runs are ONE run, idle slivers included
        (a0, a1, _p, _i), = _rows(t, "import.apply")
        assert a1 - a0 == 499 * 10_000 + 5000
    finally:
        srv.stop()


def test_mesh_engine_stamps_the_device_phases():
    """engine.device.* means the same on both engines: dispatch, exec
    (bounded by block_until_ready) and fetch of the one collective
    flush program, in that order, inside engine.flush. Four virtual
    CPU devices (tests/conftest.py pins eight)."""
    cfg = read_config(text=_YAML)
    cfg.tpu_num_devices = 4
    cfg.grpc_listen_addresses = ["127.0.0.1:0"]
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[])
    assert type(srv.engines[0]).__name__ == "MeshAggregationEngine"
    srv.start()
    try:
        from veneur_tpu.ingest.parser import MetricKey
        _feed(srv, n_keys=16, n_per_key=8)
        # an imported digest lands through the routed SPMD ingest
        srv.engines[0].import_histogram(
            MetricKey("t26.mesh", "timer", ""), [1.0, 2.0], [1.0, 1.0],
            1.0, 2.0, 3.0, 2.0)
        srv.flush_once(timestamp=5000)
        t = srv.flight.last_tick()
        (f0, f1, _p, _i), = _rows(t, "engine.flush")
        (a0, a1, _p, _i), = _rows(t, "engine.device.dispatch")
        (b0, b1, _p, _i), = _rows(t, "engine.device.exec")
        (c0, c1, _p, _i), = _rows(t, "engine.device.fetch")
        assert f0 <= a0 and a1 == b0 and b1 == c0 and c1 <= f1
        (d0, d1, _p, drain), = _rows(t, "engine.drain")
        (l0, l1, lpar, land), = _rows(t, "import.land")
        assert lpar == drain and d0 <= l0 and l1 <= d1
        # the landing's two halves: the host's up to route_batch, then
        # the routed programs' calls; no cluster step on the mesh
        (s0, s1, spar, _i), = _rows(t, "import.land.stage")
        (p0, p1, ppar, _i), = _rows(t, "import.land.dispatch")
        assert spar == ppar == land
        assert l0 == s0 and s1 == p0 and p1 == l1
        assert not _rows(t, "import.land.cluster")
    finally:
        srv.stop()


def test_pump_stamps_one_phase_per_dispatch():
    """The native pump leaves one `ingest.pump.batch` per dispatch for
    the local's next flush tick, under one `ingest` root that begins
    before the tick."""
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 interval="3600s", hostname="h", native_ingest=True,
                 tpu_histogram_slots=256, tpu_counter_slots=128,
                 tpu_gauge_slots=128, tpu_set_slots=64,
                 tpu_batch_size=256, native_pump_batch=64)
    srv = Server(cfg, sinks=[CaptureMetricSink()], span_sinks=[])
    eng = srv.engines[0]
    calls = []
    for bank in ("histo", "counter", "gauge", "set"):
        inner = getattr(eng, f"ingest_{bank}_batch")

        def counting(*a, _inner=inner, _bank=bank, **kw):
            calls.append(_bank)
            return _inner(*a, **kw)

        setattr(eng, f"ingest_{bank}_batch", counting)
    srv.start()
    try:
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        lines = [b"t26p.t%d:%d|ms" % (k % 7, k) for k in range(300)] \
            + [b"t26p.c:1|c"] * 5
        for ln in lines:
            sock.sendto(ln, ("127.0.0.1", srv.bound_port()))
        sock.close()
        deadline = time.monotonic() + 10
        while int(srv.native_bridge.stats()["lines"]) < len(lines):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.05)
        assert srv.drain()
        n_calls = len(calls)
        assert n_calls >= 300 // 64           # a 64-wide pump, 300 timers
        srv.flush_once(timestamp=6000)
        t = srv.flight.last_tick()
        (r0, r1, rpar, root), = _rows(t, "ingest")
        batches = _rows(t, "ingest.pump.batch")
        assert len(batches) == n_calls
        assert rpar == -1 and r0 < t.mono_start
        assert all(b[2] == root for b in batches)
        assert (r0, r1) == (batches[0][0], max(b[1] for b in batches))
        assert t.attributed_ns() <= t.duration_ns()
        # an idle interval grafts no root
        srv.flush_once(timestamp=6010)
        assert not _rows(srv.flight.last_tick(), "ingest")
        # more dispatches than the pump's budget, and more of those
        # than the tick has slots left: the rows stop at what fits, the
        # seconds stay exact, the tick drops nothing
        now = time.monotonic_ns()
        for k in range(600):
            srv.native_pump.stamps.add("ingest.pump.batch",
                                       now + 100 * k, now + 100 * k + 40)
        srv.flush_once(timestamp=6020)
        t = srv.flight.last_tick()
        batches = _rows(t, "ingest.pump.batch")
        assert Server.GRAFT_BUDGET["ingest.pump.batch"] > len(batches) > 96
        assert t.n == srv.flight.max_phases and t.dropped == 0
        assert sum(b[1] - b[0] for b in batches) == 600 * 40
    finally:
        srv.stop()


def test_graft_folds_rows_into_the_slots_that_are_left():
    fr = FlightRecorder(capacity=1, max_phases=12)
    t = fr.begin_tick(ts=1)
    t.finish(t.start("engine"))
    t.finish(t.start("fanout"))
    base = t.mono_start
    rows = [("a", base - 1000 + 10 * k, base - 1000 + 10 * k + 4)
            for k in range(20)] + [("b", base - 500, base - 450),
                                   ("b.c", base - 490, base - 480)]
    root = t.graft(rows, root="r")
    assert t.dropped == 0 and t.n == 12           # 2 own + root + 9 rows
    ph = t.phases()
    a = [p for p in ph if p[0] == "a"]
    assert len(a) == 7 and sum(p[2] - p[1] for p in a) == 20 * 4
    assert [p[1] for p in a] == [base - 1000 + 10 * k for k in range(7)]
    b = ph.index(("b", base - 500, base - 450, root))
    assert ("b.c", base - 490, base - 480, b) in ph
    # no room even for one row a name: those are counted as dropped
    t = fr.begin_tick(ts=2)
    for _ in range(10):
        t.finish(t.start("own"))
    t.graft(rows, root="r")
    assert t.n == 12 and t.dropped == 2


def test_recorder_off_stamps_nothing_anywhere():
    """flight_recorder: false arms none of the stamp logs: the
    engines', the import observer's and the pump's stay None."""
    cfg = read_config(text=_YAML)
    cfg.flight_recorder = False
    cfg.grpc_listen_addresses = ["127.0.0.1:0"]
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[],
                 span_sinks=[])
    assert srv.flight is None and srv._import_stamps is None
    assert srv.import_observer.stamps is None
    assert all(e.land_stamps is None for e in srv.engines)

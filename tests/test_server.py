"""End-to-end server tests: a real Server on loopback UDP with a capturing
fake sink (the server_test.go strategy), plus config parsing."""

import os
import socket
import time

import pytest

from veneur_tpu.config import read_config
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink, LocalFilePlugin


def make_server(tmp_yaml=None, **overrides):
    text = """
interval: "1s"
statsd_listen_addresses: ["udp://127.0.0.1:0"]
num_workers: 2
num_readers: 1
percentiles: [0.5]
aggregates: ["min", "max", "count"]
hostname: testhost
tpu_histogram_slots: 512
tpu_counter_slots: 512
tpu_gauge_slots: 512
tpu_set_slots: 256
tpu_batch_size: 512
tpu_buffer_depth: 128
"""
    cfg = read_config(text=text)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    sink = CaptureMetricSink()
    srv = Server(cfg, sinks=[sink])
    return srv, sink


def test_config_parsing_veneur_keys():
    cfg = read_config(text="""
interval: "10s"
statsd_listen_addresses:
  - udp://127.0.0.1:8126
forward_address: "veneur-global:3118"
percentiles: [0.5, 0.99]
datadog_api_key: abc
unknown_key_is_ignored: true
""")
    assert cfg.interval_seconds == 10.0
    assert cfg.forward_address == "veneur-global:3118"
    assert cfg.percentiles == [0.5, 0.99]


def test_config_env_override():
    cfg = read_config(text="interval: '10s'",
                      env={"VENEUR_INTERVAL": "500ms",
                           "VENEUR_NUM_WORKERS": "4",
                           "VENEUR_DEBUG": "true"})
    assert cfg.interval_seconds == 0.5
    assert cfg.num_workers == 4
    assert cfg.debug is True


def test_udp_end_to_end():
    srv, sink = make_server()
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        # several datagrams, incl. a multi-line one and a bad line
        for i in range(100):
            c.sendto(b"e2e.timer:%d|ms" % i, ("127.0.0.1", port))
        c.sendto(b"e2e.count:5|c\ne2e.count:3|c\nbadline", ("127.0.0.1", port))
        c.sendto(b"e2e.gauge:42|g", ("127.0.0.1", port))

        assert sink.wait_for_flush(1, timeout=15)
        # allow one more flush in case packets landed after the first tick
        if not any(m.name == "e2e.count" for m in sink.all_metrics):
            assert sink.wait_for_flush(len(sink.flushes) + 1, timeout=15)
        got = {m.name: m for m in sink.all_metrics}
        assert got["e2e.count"].value == 8.0
        assert got["e2e.gauge"].value == 42.0
        assert got["e2e.timer.count"].value == 100.0
        assert got["e2e.timer.min"].value == 0.0
        assert got["e2e.timer.max"].value == 99.0
        assert got["e2e.timer.min"].hostname == "testhost"
        # self-telemetry flows through the same pipe
        assert "veneur.packet.received_total" in got
        assert got["veneur.packet.error_total"].value >= 1.0
    finally:
        srv.stop()


def test_flush_interval_resets_and_continues():
    srv, sink = make_server()
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        c.sendto(b"tick:1|c", ("127.0.0.1", port))
        assert sink.wait_for_flush(2, timeout=20)
        vals = [m.value for fl in sink.flushes for m in fl
                if m.name == "tick"]
        assert vals == [1.0]  # reported once, not re-reported as 0
    finally:
        srv.stop()


def test_localfile_plugin(tmp_path):
    out = tmp_path / "metrics.tsv"
    srv, sink = make_server()
    srv.plugins = [LocalFilePlugin(str(out), 1)]
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        c.sendto(b"file.metric:7|c|#k:v", ("127.0.0.1", port))
        assert sink.wait_for_flush(1, timeout=15)
        deadline = time.time() + 10
        while time.time() < deadline:
            if out.exists() and "file.metric" in out.read_text():
                break
            time.sleep(0.2)
        text = out.read_text()
        assert "file.metric\tk:v\tcounter\ttesthost" in text
    finally:
        srv.stop()


def test_forwarder_receives_exports():
    exports = []
    srv, sink = make_server(forward_address="fake:3118")
    srv.forwarder = exports.append
    srv.start()
    try:
        port = srv.bound_port()
        c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for i in range(10):
            c.sendto(b"fwd.hist:%d|ms" % i, ("127.0.0.1", port))
        assert sink.wait_for_flush(1, timeout=15)
        deadline = time.time() + 10
        while not exports and time.time() < deadline:
            time.sleep(0.2)
        assert exports, "forwarder never called"
        assert any(k.name == "fwd.hist"
                   for k, *_ in exports[0].histograms)
        # mixed histo under forwarding: local aggregates still emitted
        names = {m.name for m in sink.all_metrics}
        assert "fwd.hist.count" in names
        assert "fwd.hist.50percentile" not in names
    finally:
        srv.stop()


def test_example_yaml_is_complete_and_loads():
    """example.yaml documents every Config key (the reference documents
    its whole surface in example.yaml) and round-trips through
    read_config."""
    import dataclasses

    import yaml

    from veneur_tpu import config as config_mod

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "example.yaml")
    keys = set(yaml.safe_load(open(path)))
    fields = {f.name for f in dataclasses.fields(config_mod.Config)}
    assert keys == fields - {"is_global"}   # loader-populated, not YAML
    cfg = config_mod.read_config(path)
    assert cfg.interval_seconds == 10.0
    assert cfg.tpu_compression == 100.0


def test_readme_names_only_files_that_exist():
    """Every backticked repo-relative path in README's "Where the
    evidence lives" and "Layout" sections is in the tree."""
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    text = open(os.path.join(root, "README.md")).read()
    named = []
    for title in ("Where the evidence lives", "Layout"):
        body = text.split(f"## {title}\n", 1)[1].split("\n## ", 1)[0]
        named += [t for t in re.findall(r"`([\w./-]+)`", body)
                  if "/" in t or t.endswith((".md", ".py", ".json",
                                             ".jsonl", ".yaml"))]
    assert len(named) > 15
    missing = [t for t in named
               if not os.path.exists(os.path.join(root, t))]
    assert not missing, missing


def test_config_validation_rejects_nonsense():
    with pytest.raises(ValueError):
        read_config(text="percentiles: [1.5]")
    with pytest.raises(ValueError):
        read_config(text="percentiles: [0]")
    with pytest.raises(ValueError):
        read_config(text="interval: 0s")
    with pytest.raises(ValueError):
        read_config(text="tpu_buffer_depth: 2")
    with pytest.raises(ValueError):
        read_config(text="tpu_hll_precision: 31")
    with pytest.raises(ValueError):      # no :port — clear error at load,
        read_config(text="stats_address: localhost")   # not at bind time
    with pytest.raises(ValueError):
        read_config(text="stats_address: 'host:notaport'")
    assert read_config(
        text="stats_address: '127.0.0.1:8125'"
    ).stats_address == "127.0.0.1:8125"
    # lenient like the reference: unknown aggregates warn, don't fail
    cfg = read_config(text="aggregates: ['count', 'p9999']")
    assert cfg.aggregates == ["count", "p9999"]


@pytest.mark.parametrize("key, value", [
    ("tpu_flush_fetch", "staged"), ("tpu_flush_fetch_f16", "true"),
    # PR 48: which kernel runs and which flush runs is the code's choice
    # (the benchmark's deployment files still set the first)
    ("tpu_fused_kernels", "auto"), ("tpu_fused_kernels", "on"),
    ("tpu_fused_kernels", "off"), ("tpu_flush_incremental", "false"),
    ("tpu_flush_incremental_threshold", "0.5"),
    # (out of the retired validator's range: ignored all the same)
    ("tpu_flush_incremental_threshold", "2.0"),
    ("tpu_flush_double_buffer", "false")])
def test_retired_keys_warn_and_load(key, value, caplog):
    """A config that still sets a retired key loads as any unknown key
    does — warned and ignored — and the server flushes what it flushes
    without it."""
    import logging

    base = """
interval: "3600s"
statsd_listen_addresses: []
percentiles: [0.5]
aggregates: ["min", "max", "count"]
hostname: testhost
tpu_histogram_slots: 64
tpu_counter_slots: 64
tpu_gauge_slots: 64
tpu_set_slots: 32
"""
    from veneur_tpu.ingest.parser import parse_packet

    def flushed(text):
        srv = Server(read_config(text=text), sinks=[CaptureMetricSink()],
                     plugins=[], span_sinks=[])
        srv.start()
        try:
            for line in ([b"r.hits:7|c", b"r.temp:70|g", b"r.u:a|s"]
                         + [b"r.lat:%d|ms" % v for v in range(1, 101)]):
                srv._route_metric(parse_packet(line))
            assert srv.drain(10.0)
            return {m.name: m.value
                    for m in srv.flush_once(timestamp=10)
                    if m.name.startswith("r.")}
        finally:
            srv.stop()

    with caplog.at_level(logging.WARNING, logger="veneur_tpu.config"):
        got = flushed(base + f"{key}: {value}\n")
    assert f"unknown config key {key!r} ignored" in caplog.text
    assert got == flushed(base) and got["r.lat.count"] == 100.0


_DEPLOYMENTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "configs")


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(_DEPLOYMENTS) if f.endswith(".json")))
def test_benchmark_deployments_load_with_the_retired_kernel_key(name,
                                                               caplog):
    """A deployment file of the benchmark from before PR 48 still sets
    `tpu_fused_kernels` (`on` in its rehearsal preset) and may not be
    edited: its global tier builds and warms up as the harness builds
    it, with the one warning, and no kernel entry point fell back. A
    file added since does not set the key and loads with no warning."""
    import logging

    from perfbench import harness
    from veneur_tpu import kernels

    cfg = harness.load_config(name, rehearsal=True)
    retired = "tpu_fused_kernels" in cfg["common"]
    assert cfg["common"].get("tpu_fused_kernels", "on") == "on"
    assert retired == (name != "dogstatsd_readers4_two_tier_1chip")
    before = kernels.fallback_total()
    with caplog.at_level(logging.WARNING, logger="veneur_tpu.config"):
        srv = harness.build_server(cfg, "global", {},
                                   CaptureMetricSink(), rehearsal=True)
    assert caplog.text.count("unknown config key") == int(retired)
    assert ("unknown config key 'tpu_fused_kernels' ignored"
            in caplog.text) == retired
    srv.start()
    try:
        kern = srv._debug_flush_state()["sketch_engines"]["kernels"]
    finally:
        srv.stop()
    assert kern == {"estimate": "jnp", "fallback_total": before}


@pytest.mark.slow
def test_live_flush_loop_exact_accounting_soak():
    """Full-server soak: the REAL flush loop ticks while native UDP
    statsd and SSF span traffic flows concurrently — the flush-swap vs
    pump vs listener interleaving where the r5 zero-copy aliasing
    corruption lived. At the end, the SUM of flushed counter values
    across every interval must equal exactly what landed (counters are
    exact by contract), and histogram counts must account likewise.
    Accounting is by VALUE, not by landed-counter — landed counts
    stayed perfect while the banks rotted under the aliasing bug."""
    from veneur_tpu.config import Config
    from veneur_tpu.ssf.protos import ssf_pb2

    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 ssf_listen_addresses=["udp://127.0.0.1:0"],
                 interval="1s", hostname="soak", native_ingest=True,
                 num_readers=1, aggregates=["count"],
                 percentiles=[0.5],
                 tpu_histogram_slots=1024, tpu_counter_slots=1024,
                 tpu_gauge_slots=64, tpu_set_slots=64)
    sink = CaptureMetricSink()
    srv = Server(cfg, sinks=[sink], plugins=[])
    srv.start()
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        port = srv.bound_port()
        ssf_port = srv.ssf_native_port
        sent_c = sent_t = sent_spans = 0
        # ~6 flush intervals of steady mixed traffic, throttled well
        # below the 1-core drop threshold
        deadline = time.monotonic() + 6.0
        sp = ssf_pb2.SSFSpan()
        m1 = sp.metrics.add()
        m1.metric = ssf_pb2.SSFSample.COUNTER
        m1.name = "soak.span.c"
        m1.value = 1.0
        span_bytes = sp.SerializeToString()
        while time.monotonic() < deadline:
            for j in range(20):
                s.sendto(f"soak.c{j % 7}:1|c\nsoak.t{j % 5}:3.5|ms"
                         .encode(), ("127.0.0.1", port))
                sent_c += 1
                sent_t += 1
            s.sendto(span_bytes, ("127.0.0.1", ssf_port))
            sent_spans += 1
            time.sleep(0.01)
        # settle: everything parsed, pumped, landed, flushed once more
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            st = srv.native_bridge.stats()
            if (int(st["lines"]) >= sent_c + sent_t
                    and int(st["ssf_spans"]) >= sent_spans):
                break
            time.sleep(0.05)
        st = srv.native_bridge.stats()
        assert int(st["lines"]) == sent_c + sent_t, (st, sent_c + sent_t)
        # >= : the server self-traces its own flushes through the same
        # native SSF port (veneur.* spans on top of ours)
        assert int(st["ssf_spans"]) >= sent_spans
        assert int(st["ring_drops"]) == 0, st
        assert srv.drain(30)   # rings, worker queues, AND slow paths
        srv.flush_once()

        # exact value accounting across ALL intervals. The fan-out
        # hands frames to the sink on unjoined threads, so poll until
        # the sums CONVERGE to the exact totals (flushing once more if
        # a residual remains) instead of reading sink.flushes
        # immediately.
        def sums():
            got = [0.0, 0.0, 0.0]
            with sink._cv:
                flushes = [list(f) for f in sink.flushes]
            for flush in flushes:
                for m in flush:
                    if m.name.startswith("soak.c"):
                        got[0] += m.value
                    elif m.name == "soak.span.c":
                        got[1] += m.value
                    elif m.name.startswith("soak.t") and \
                            m.name.endswith(".count"):
                        got[2] += m.value
            return got
        want = [float(sent_c), float(sent_spans), float(sent_t)]
        deadline = time.monotonic() + 20
        got = sums()
        while got != want and time.monotonic() < deadline:
            time.sleep(0.25)
            srv.flush_once()
            got = sums()
        assert got == want, (got, want)
        assert len(sink.flushes) >= 4  # the loop really ticked
    finally:
        srv.stop()
        s.close()


@pytest.mark.slow
def test_key_churn_soak_bounded_state():
    """Long-running-server soak: 40 flush intervals of fully-churning
    key sets must leave every unbounded-looking cache bounded — the
    leak class the datadog tag-memo advisor finding belonged to
    (interners evict by TTL, presentation caches clear at their bound,
    sink memos stay under their cap)."""
    from veneur_tpu.ingest import parser
    from veneur_tpu.models.pipeline import AggregationEngine, EngineConfig
    from veneur_tpu.sinks.datadog import DatadogMetricSink
    from veneur_tpu.metrics import FrameSet

    # capacity ABOVE the churn live-window (300 keys/interval, TTL 4 ->
    # ~1500 live) so slot exhaustion never masks broken eviction: if TTL
    # eviction stopped returning slots to the free list, the cumulative
    # 12k keys would exhaust the bank and dropped_no_slot would fire
    eng = AggregationEngine(EngineConfig(
        histogram_slots=2048, counter_slots=2048, gauge_slots=128,
        set_slots=64, buffer_depth=128, idle_ttl_intervals=4))
    sink = DatadogMetricSink(api_key="x", interval_s=10)
    sink._post = lambda path, body, deadline=None: None  # no real API
    dropped_total = 0
    for interval in range(40):
        for j in range(300):  # fresh names every interval -> full churn
            eng.process(parser.parse_packet(
                f"churn.{interval}.{j}:1|ms|#iter:{interval}".encode()))
            eng.process(parser.parse_packet(
                f"churn.c.{interval}.{j}:1|c".encode()))
        res = eng.flush(timestamp=interval * 10)
        # flush() reads-and-zeroes the per-interner counters each
        # interval, so accumulate from the flush status dict — reading
        # the attribute after the final flush would always see 0
        dropped_total += res.stats["dropped_no_slot"]
        sink.flush_frames(FrameSet([res.frame]))
    # eviction keeps the interner inside the live+TTL window and no key
    # was ever dropped for want of a slot (the non-vacuous check: broken
    # eviction exhausts the free list and fires dropped_no_slot)
    assert dropped_total == 0
    assert len(eng.histo_keys) <= 300 * (4 + 2)
    assert len(eng.counter_keys) <= 300 * (4 + 2)
    # presentation caches bounded by their documented caps
    assert len(eng._tags_cache) <= eng._pres_bound
    assert len(sink._tag_memo) < 65536


def test_native_listeners_receive_configured_rcvbuf(monkeypatch):
    """Both native UDP listeners — statsd AND SSF — must be started
    with the configured read buffer size (round-5 advisory / vlint CF01
    exemplar: start_ssf_udp used to be started on the ~208KB kernel
    default while start_udp got the configured 2MB)."""
    import pytest as _pytest

    from veneur_tpu.config import Config
    native = _pytest.importorskip("veneur_tpu.ingest.native")
    try:
        native.load()
    except native.NativeUnavailable as e:  # pragma: no cover
        _pytest.skip(f"native build unavailable: {e}")

    calls = {}

    def fake_start_udp(self, host, port, n_readers, rcvbuf=0):
        calls["statsd"] = rcvbuf
        return port or 1

    def fake_start_ssf_udp(self, host, port, n_readers, rcvbuf=0,
                           max_dgram=16384):
        calls["ssf"] = rcvbuf
        return port or 2

    monkeypatch.setattr(native.NativeBridge, "start_udp",
                        fake_start_udp)
    monkeypatch.setattr(native.NativeBridge, "start_ssf_udp",
                        fake_start_ssf_udp)
    cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                 ssf_listen_addresses=["udp://127.0.0.1:0"],
                 interval="1s", native_ingest=True, num_readers=1,
                 read_buffer_size_bytes=5 << 20,
                 tpu_histogram_slots=512, tpu_counter_slots=256,
                 tpu_gauge_slots=256, tpu_set_slots=128)
    srv = Server(cfg, sinks=[CaptureMetricSink()], plugins=[])
    try:
        srv._start_statsd_listener(cfg.statsd_listen_addresses[0])
        srv._start_ssf_listener(cfg.ssf_listen_addresses[0])
    finally:
        srv.stop()
    assert calls["statsd"] == 5 << 20
    assert calls["ssf"] == 5 << 20


def test_import_enqueue_waits_for_room_then_sheds_counted():
    """The import entry points hand metrics to the workers with
    backpressure, not drop-on-full: a burst larger than the queue (one
    local's flush of 100k sketches against 65,536 slots) waits for the
    worker instead of losing the overflow. The wait is bounded by
    flush_timeout, a timed-out queue sheds without waiting for that
    long, and every shed metric is counted."""
    import queue
    import threading

    from veneur_tpu.observe import SERVER_SCOPE

    srv, _sink = make_server(flush_timeout="0.3s")   # never started
    try:
        q = srv.worker_queues[0] = queue.Queue(maxsize=2)
        srv._enqueue_import(0, "a")
        srv._enqueue_import(0, "b")                  # full now
        # a consumer frees a slot a moment later: the put waits for it
        threading.Timer(0.05, q.get).start()
        srv._enqueue_import(0, "c")
        assert q.qsize() == 2
        assert srv.telemetry.total(SERVER_SCOPE, "worker.dropped") == 0
        # nobody consumes: one bounded wait, then counted shedding ...
        t0 = time.monotonic()
        srv._enqueue_import(0, "d", n=5)
        waited = time.monotonic() - t0
        assert 0.25 <= waited < 2.0
        assert srv.telemetry.total(SERVER_SCOPE, "worker.dropped") == 5
        # ... and the next items do not wait again
        t0 = time.monotonic()
        srv._enqueue_import(0, "e")
        assert time.monotonic() - t0 < 0.1
        assert srv.telemetry.total(SERVER_SCOPE, "worker.dropped") == 6
        # the other queue is unaffected
        srv._enqueue_import(1, "x")
        assert srv.worker_queues[1].qsize() == 1
    finally:
        srv.stop()

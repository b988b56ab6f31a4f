"""Cluster-tier tests: wire roundtrips, two in-process Servers over real
loopback gRPC (the reference's server_test.go/importsrv strategy), the
consistent ring, and the proxy fan-out."""

import socket
import time

import numpy as np
import pytest

from veneur_tpu.cluster import wire
from veneur_tpu.cluster.discovery import StaticDiscoverer
from veneur_tpu.cluster.forward import GrpcForwarder
from veneur_tpu.cluster.protos import forward_pb2, metric_pb2
from veneur_tpu.cluster.proxy import ConsistentRing, ProxyServer
from veneur_tpu.config import read_config
from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.models.pipeline import ForwardExport
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink


def test_wire_roundtrip():
    exp = ForwardExport()
    key = MetricKey("api.lat", "timer", "env:prod,svc:web")
    exp.histograms.append((key, np.array([1.0, 5.0], np.float32),
                           np.array([3.0, 2.0], np.float32),
                           1.0, 5.0, 13.0, 5.0, 3.4))
    exp.sets.append((MetricKey("users", "set", ""),
                     np.arange(1 << 14, dtype=np.uint8) % 16))
    exp.counters.append((MetricKey("hits", "counter", ""), 42.0))
    exp.gauges.append((MetricKey("temp", "gauge", ""), 98.6))
    pbs = wire.export_to_metrics(exp)
    data = forward_pb2.MetricList(
        metrics=pbs).SerializeToString()
    back = forward_pb2.MetricList.FromString(data)
    assert len(back.metrics) == 4
    h = back.metrics[0]
    assert h.name == "api.lat"
    assert wire.metric_key_of(h) == key
    assert len(h.histogram.t_digest.centroids) == 2
    assert h.histogram.t_digest.count == 5.0
    s = back.metrics[1]
    regs = wire.decode_hll(s.set.hyper_log_log)
    assert len(regs) == 1 << 14 and regs[17] == 17 % 16
    assert back.metrics[2].counter.value == 42
    assert back.metrics[3].gauge.value == pytest.approx(98.6)


def _mk_server(extra, sink=None):
    text = """
interval: "1s"
num_workers: 2
percentiles: [0.5, 0.99]
aggregates: ["min", "max", "count"]
hostname: h
tpu_histogram_slots: 512
tpu_counter_slots: 512
tpu_gauge_slots: 512
tpu_set_slots: 256
tpu_batch_size: 256
tpu_buffer_depth: 128
"""
    cfg = read_config(text=text)
    for k, v in extra.items():
        setattr(cfg, k, v)
    sink = sink or CaptureMetricSink()
    return Server(cfg, sinks=[sink]), sink


def test_two_servers_grpc_forward():
    """local Server --forwardrpc--> global Server, real loopback gRPC."""
    glob, gsink = _mk_server({"grpc_listen_addresses": ["127.0.0.1:0"]})
    glob.start()
    try:
        gport = glob.grpc_port
        local, lsink = _mk_server({
            "forward_address": f"127.0.0.1:{gport}",
            "statsd_listen_addresses": ["udp://127.0.0.1:0"]})
        local.start()
        try:
            port = local.bound_port()
            c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            rng = np.random.default_rng(2)
            vals = rng.normal(100, 10, 500)
            for v in vals:
                c.sendto(b"fw.lat:%.4f|ms" % v, ("127.0.0.1", port))
            c.sendto(b"fw.uniq:a|s\nfw.uniq:b|s\nfw.uniq:c|s",
                     ("127.0.0.1", port))
            c.sendto(b"fw.total:9|c|#veneurglobalonly", ("127.0.0.1", port))

            # wait until the GLOBAL tier has seen all 500 samples
            # (they may straddle local flush intervals — counts are summed
            # across global flushes)
            deadline = time.time() + 25
            names = {}

            def count_sum():
                return sum(m.value for m in gsink.all_metrics
                           if m.name == "fw.lat.count")

            while time.time() < deadline:
                names = {m.name: m for m in gsink.all_metrics}
                if count_sum() >= 500 and "fw.uniq" in names \
                        and "fw.total" in names:
                    break
                time.sleep(0.3)
            assert "fw.lat.50percentile" in names, names.keys()
            assert names["fw.lat.50percentile"].value == pytest.approx(
                np.median(vals), abs=3.0)
            assert count_sum() == 500.0
            assert names["fw.uniq"].value == pytest.approx(3, abs=0.5)
            assert sum(m.value for m in gsink.all_metrics
                       if m.name == "fw.total") == 9.0
            # local tier emitted aggregates but no percentiles for mixed
            lnames = {m.name for m in lsink.all_metrics}
            assert "fw.lat.count" in lnames
            assert "fw.lat.50percentile" not in lnames
        finally:
            local.stop()
    finally:
        glob.stop()


def test_grpc_forward_chunks_fit_the_message_limit():
    """A gRPC receiver refuses a message over 4 MiB unless told
    otherwise, and chunks used to close on metric COUNT alone: 300
    HLL p=14 sets (16 KiB of registers each) made one 4.7 MiB
    MetricList and the whole forward died RESOURCE_EXHAUSTED. Chunks
    now also close on bytes; re-chunking a tail from any chunk start
    lands on the original boundaries (replays keep their chunk ids)."""
    from veneur_tpu.cluster import forward
    from veneur_tpu.cluster.importsrv import start_import_server
    from veneur_tpu.ingest.parser import MetricKey
    from veneur_tpu.models.pipeline import ForwardExport

    rng = np.random.default_rng(5)
    exp = ForwardExport()
    for i in range(300):
        exp.sets.append((MetricKey(f"big.set{i}", "set", "env:prod"),
                         rng.integers(0, 30, 1 << 14).astype(np.uint8)))
    exp.counters.append((MetricKey("big.count", "counter", ""), 7.0))
    metrics = wire.export_to_metrics(exp)
    bounds = forward._chunk_bounds(metrics, 10_000)
    assert len(bounds) == 2 and bounds[0][0] == 0
    assert bounds[-1][1] == len(metrics)
    for a, b in bounds:
        assert sum(m.ByteSize() for m in metrics[a:b]) \
            <= forward.MAX_CHUNK_BYTES
    # greedy from a chunk start reproduces the boundaries after it
    a = bounds[1][0]
    assert [(x + a, y + a) for x, y in
            forward._chunk_bounds(metrics[a:], 10_000)] == bounds[1:]
    # count still closes chunks too
    assert forward._chunk_bounds(metrics[:5], 2) == [(0, 2), (2, 4),
                                                     (4, 5)]

    got = []
    server, port = start_import_server(
        "127.0.0.1:0",
        lambda metrics, _env, _raw=None: got.extend(metrics) or len(metrics))
    try:
        fw = GrpcForwarder(f"127.0.0.1:{port}")
        fw(exp)          # default limits on both ends
        fw.close()
    finally:
        server.stop(0)
    assert len(got) == 301


def test_ring_distribution_and_stability():
    ring = ConsistentRing(["a:1", "b:1", "c:1"])
    keys = [f"metric-{i}".encode() for i in range(3000)]
    before = {k: ring.get(k) for k in keys}
    counts = {}
    for d in before.values():
        counts[d] = counts.get(d, 0) + 1
    assert len(counts) == 3
    assert min(counts.values()) > 500  # roughly balanced
    # removing one destination must only remap its own keys
    ring.set_destinations(["a:1", "b:1"])
    moved = sum(1 for k in keys
                if before[k] != "c:1" and ring.get(k) != before[k])
    assert moved == 0


class _CaptureForwarder:
    instances: dict = {}

    def __init__(self, dest):
        self.dest = dest
        self.got = []
        _CaptureForwarder.instances[dest] = self

    def send_metrics(self, metrics):
        self.got.extend(metrics)


def test_proxy_routes_by_key():
    _CaptureForwarder.instances = {}
    proxy = ProxyServer(StaticDiscoverer(["g1:1", "g2:1", "g3:1"]),
                        forwarder_factory=_CaptureForwarder)
    metrics = []
    for i in range(300):
        m = metric_pb2.Metric(name=f"m{i}", type=metric_pb2.Counter)
        m.counter.value = i
        metrics.append(m)
    errs = proxy.handle_metric_list(forward_pb2.MetricList(metrics=metrics))
    assert not errs
    total = sum(len(f.got) for f in _CaptureForwarder.instances.values())
    assert total == 300
    assert len(_CaptureForwarder.instances) == 3
    # same key always lands on the same destination
    groups1 = proxy.route_metrics(metrics)
    groups2 = proxy.route_metrics(metrics)
    assert {d: [m.name for m in ms] for d, ms in groups1.items()} == \
        {d: [m.name for m in ms] for d, ms in groups2.items()}


def test_proxy_grpc_end_to_end():
    """client -> proxy gRPC -> (captured) destinations."""
    _CaptureForwarder.instances = {}
    proxy = ProxyServer(StaticDiscoverer(["d1:1", "d2:1"]),
                        forwarder_factory=_CaptureForwarder)
    server, port = proxy.start("127.0.0.1:0")
    try:
        fw = GrpcForwarder(f"127.0.0.1:{port}")
        exp = ForwardExport()
        for i in range(20):
            exp.counters.append(
                (MetricKey(f"c{i}", "counter", ""), float(i)))
        fw(exp)
        total = sum(len(f.got) for f in _CaptureForwarder.instances.values())
        assert total == 20
        assert len(_CaptureForwarder.instances) == 2
    finally:
        proxy.stop()


def test_discovering_forwarder_rotates_and_refreshes():
    """consul_forward_service_name path: destinations come from a
    Discoverer, rotate round-robin, and re-resolve after the refresh
    interval (discovery.go / Server.RefreshDestinations)."""
    from veneur_tpu.cluster.discovery import StaticDiscoverer
    from veneur_tpu.cluster.forward import DiscoveringForwarder

    calls = []

    class FakeFwd:
        def __init__(self, dest):
            self.dest = dest

        def __call__(self, export):
            calls.append(self.dest)

    disc = StaticDiscoverer(["a:1", "b:2"])
    fwd = DiscoveringForwarder(disc, "veneur-global",
                               refresh_interval_s=0.0,
                               forwarder_factory=FakeFwd)
    for _ in range(4):
        fwd(None)
    assert calls == ["a:1", "b:2", "a:1", "b:2"]
    disc.destinations = ["c:3"]
    fwd(None)
    assert calls[-1] == "c:3"

    class Flaky:
        def get_destinations_for_service(self, service):
            raise OSError("consul down")

    import pytest

    from veneur_tpu.resilience import TransientEgressError

    fwd2 = DiscoveringForwarder(Flaky(), "svc", refresh_interval_s=0.0,
                                forwarder_factory=FakeFwd)
    # a discovery outage with no known destinations raises (transient)
    # so the server's ResilientForwarder spills the export for re-merge
    # instead of silently dropping the interval
    with pytest.raises(TransientEgressError):
        fwd2(None)
    assert fwd2.errors >= 1


def test_http_proxy_front_distributes_consistently():
    """POST /import batches are split per metric and consistent-hashed
    across destinations on the SAME ring as the gRPC arm (proxy.go sym:
    Proxy.Handler / Proxy.ProxyMetrics)."""
    import json as _json
    import urllib.request

    from veneur_tpu.cluster.discovery import StaticDiscoverer
    from veneur_tpu.cluster.proxy import HttpProxyFront, ProxyServer

    received: dict[str, list] = {"a": [], "b": [], "c": []}

    class FakeDest:
        def __init__(self, dest):
            self.dest = dest

        def send_json(self, dicts):
            received[self.dest].extend(dicts)

    proxy = ProxyServer(StaticDiscoverer(["a", "b", "c"]),
                        refresh_interval_s=3600)
    front = HttpProxyFront(proxy, dest_factory=FakeDest)
    srv, port = front.start("127.0.0.1:0")
    try:
        batch = [{"name": f"m{i}", "type": "counter",
                  "tags": ["env:prod"], "value": i} for i in range(300)]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/import",
            data=_json.dumps(batch).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert resp.status == 200
        total = sum(len(v) for v in received.values())
        assert total == 300
        # all three destinations get a share, and the split is stable
        assert all(len(v) > 30 for v in received.values())
        first = {d: [m["name"] for m in v] for d, v in received.items()}
        for v in received.values():
            v.clear()
        with urllib.request.urlopen(req, timeout=5):
            pass
        assert {d: [m["name"] for m in v]
                for d, v in received.items()} == first
        # same metric routes to the same place as the gRPC arm's ring
        from veneur_tpu.cluster.proxy import ConsistentRing
        assert isinstance(proxy.ring, ConsistentRing)
        # malformed body -> 400, nothing crashes
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/import", data=b"{not json",
            headers={"Content-Type": "application/json"}, method="POST")
        try:
            urllib.request.urlopen(bad, timeout=5)
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
        assert front.proxied_total == 600
        # declared unknown forward format -> 400 (jsonmetric-v1
        # contract), declared v1 accepted
        for ver, want in (("gob", 400), ("jsonmetric-v1", 200)):
            req_v = urllib.request.Request(
                f"http://127.0.0.1:{port}/import",
                data=_json.dumps(batch[:3]).encode(),
                headers={"Content-Type": "application/json",
                         "X-Veneur-Forward-Version": ver},
                method="POST")
            try:
                with urllib.request.urlopen(req_v, timeout=5) as resp:
                    assert resp.status == want
            except urllib.error.HTTPError as e:
                assert e.code == want
    finally:
        front.stop()
        proxy.stop()


def test_two_servers_grpc_forward_to_mesh_global():
    """local Server --forwardrpc--> GLOBAL Server whose engine is
    sharded over the 8-device mesh: the full multi-chip global tier,
    end to end over real loopback gRPC."""
    glob, gsink = _mk_server({"grpc_listen_addresses": ["127.0.0.1:0"],
                              "tpu_num_devices": 8,
                              "tpu_histogram_slots": 64,
                              "tpu_counter_slots": 32,
                              "tpu_gauge_slots": 32,
                              "tpu_set_slots": 16})
    assert type(glob.engines[0]).__name__ == "MeshAggregationEngine"
    glob.start()
    try:
        local, _ = _mk_server({
            "forward_address": f"127.0.0.1:{glob.grpc_port}",
            "statsd_listen_addresses": ["udp://127.0.0.1:0"]})
        local.start()
        try:
            port = local.bound_port()
            c = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            rng = np.random.default_rng(6)
            vals = rng.normal(100, 10, 400)
            for v in vals:
                c.sendto(b"mg.lat:%.4f|ms" % v, ("127.0.0.1", port))
            c.sendto(b"mg.uniq:x|s\nmg.uniq:y|s", ("127.0.0.1", port))
            c.sendto(b"mg.total:4|c|#veneurglobalonly",
                     ("127.0.0.1", port))
            deadline = time.time() + 30
            names = {}
            while time.time() < deadline:
                names = {m.name: m for m in gsink.all_metrics}
                got = sum(m.value for m in gsink.all_metrics
                          if m.name == "mg.lat.count")
                if got >= 400 and "mg.uniq" in names \
                        and "mg.total" in names:
                    break
                time.sleep(0.3)
            assert "mg.lat.50percentile" in names, sorted(names)
            assert names["mg.lat.50percentile"].value == pytest.approx(
                float(np.median(vals)), abs=3.0)
            assert sum(m.value for m in gsink.all_metrics
                       if m.name == "mg.lat.count") == 400.0
            assert names["mg.uniq"].value == pytest.approx(2, abs=0.5)
            assert names["mg.total"].value == 4.0
        finally:
            local.stop()
    finally:
        glob.stop()

"""CLI tests: veneur-emit (statsd + SSF + -command), veneur-prometheus
translation, veneur-proxy config handling, main daemon flags."""

import socket
import threading

import pytest

from veneur_tpu.cli import emit as emit_cli
from veneur_tpu.cli import prometheus as prom_cli


def recv_udp():
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.settimeout(5.0)
    return sock, sock.getsockname()[1]


def test_emit_statsd_count_and_tags():
    sock, port = recv_udp()
    rc = emit_cli.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-name", "emitted.count", "-count", "3",
                        "-tag", "env:prod,team:obs"])
    assert rc == 0
    data, _ = sock.recvfrom(4096)
    assert data == b"emitted.count:3.0|c|#env:prod,team:obs"
    sock.close()


def test_emit_multiple_types():
    sock, port = recv_udp()
    emit_cli.main(["-hostport", f"udp://127.0.0.1:{port}",
                   "-name", "m", "-gauge", "1.5"])
    assert sock.recvfrom(4096)[0] == b"m:1.5|g"
    emit_cli.main(["-hostport", f"udp://127.0.0.1:{port}",
                   "-name", "m", "-timing", "12.5"])
    assert sock.recvfrom(4096)[0] == b"m:12.5|ms"
    emit_cli.main(["-hostport", f"udp://127.0.0.1:{port}",
                   "-name", "m", "-set", "user1"])
    assert sock.recvfrom(4096)[0] == b"m:user1|s"
    sock.close()


def test_emit_ssf_mode():
    from veneur_tpu.ssf.protos import ssf_pb2

    sock, port = recv_udp()
    rc = emit_cli.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-name", "ssf.metric", "-count", "2", "-ssf",
                        "-service", "mysvc"])
    assert rc == 0
    data, _ = sock.recvfrom(65536)
    span = ssf_pb2.SSFSpan.FromString(data)
    assert span.service == "mysvc"
    assert span.metrics[0].name == "ssf.metric"
    assert span.metrics[0].value == 2.0
    sock.close()


def test_emit_command_timing():
    sock, port = recv_udp()
    rc = emit_cli.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-command", "true"])
    assert rc == 0
    data, _ = sock.recvfrom(4096)
    assert data.startswith(b"veneur_emit.command:")
    assert b"|ms" in data and b"exit_status:0" in data
    # failing command: exit code propagates
    rc = emit_cli.main(["-hostport", f"udp://127.0.0.1:{port}",
                        "-command", "false"])
    assert rc == 1
    data, _ = sock.recvfrom(4096)
    assert b"exit_status:1" in data
    sock.close()


def test_emit_tcp_sends_payload():
    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    lsock.settimeout(5.0)
    port = lsock.getsockname()[1]
    got = []

    def accept():
        conn, _ = lsock.accept()
        with conn:
            conn.settimeout(5.0)
            while True:
                chunk = conn.recv(4096)
                if not chunk:
                    break
                got.append(chunk)

    t = threading.Thread(target=accept, daemon=True)
    t.start()
    rc = emit_cli.main(["-hostport", f"tcp://127.0.0.1:{port}",
                        "-name", "tcp.count", "-count", "4"])
    t.join(5.0)
    lsock.close()
    assert rc == 0
    assert b"".join(got) == b"tcp.count:4.0|c"


EXPO_1 = """\
# HELP http_requests_total Total requests.
# TYPE http_requests_total counter
http_requests_total{code="200",method="get"} 100
http_requests_total{code="500",method="get"} 3
# TYPE temp_celsius gauge
temp_celsius 21.5
# TYPE req_latency histogram
req_latency_bucket{le="0.1"} 50
req_latency_bucket{le="+Inf"} 60
req_latency_sum 12.5
req_latency_count 60
untyped_series 7
"""

EXPO_2 = EXPO_1.replace(
    'http_requests_total{code="200",method="get"} 100',
    'http_requests_total{code="200",method="get"} 140').replace(
    "temp_celsius 21.5", "temp_celsius 19.0").replace(
    'req_latency_bucket{le="+Inf"} 60', 'req_latency_bucket{le="+Inf"} 75')


def test_prometheus_parse():
    samples = prom_cli.parse_exposition(EXPO_1)
    byname = {(n, tuple(sorted(l.items()))): (v, t)
              for n, l, v, t in samples}
    v, t = byname[("http_requests_total",
                   (("code", "200"), ("method", "get")))]
    assert v == 100 and t == "counter"
    v, t = byname[("temp_celsius", ())]
    assert v == 21.5 and t == "gauge"
    v, t = byname[("req_latency_bucket", (("le", "0.1"),))]
    assert t == "histogram"
    v, t = byname[("untyped_series", ())]
    assert t == "gauge"


def test_prometheus_counter_deltas():
    prev = {}
    # first poll primes the cache: no counter lines, gauges emit
    lines1 = prom_cli.to_statsd_lines(
        prom_cli.parse_exposition(EXPO_1), prev)
    text1 = b"\n".join(lines1)
    assert b"temp_celsius:21.5|g" in text1
    assert b"http_requests_total" not in text1
    # second poll: deltas
    lines2 = prom_cli.to_statsd_lines(
        prom_cli.parse_exposition(EXPO_2), prev)
    text2 = b"\n".join(lines2)
    assert b"http_requests_total:40.0|c|#code:200,method:get" in text2
    assert b"temp_celsius:19.0|g" in text2
    # unchanged counter (code=500) suppressed; changed bucket emits
    assert b"code:500" not in text2
    assert b"req_latency_bucket:15.0|c|#le:+Inf" in text2
    # histogram _sum is cumulative: delta-ed like _count, never a gauge
    assert b"req_latency_sum" not in text1
    assert b"req_latency_sum:" not in text2  # unchanged -> suppressed


def test_prometheus_sum_delta_and_brace_labels():
    prev = {}
    expo_a = ("# TYPE lat histogram\n"
              "lat_sum 10.0\nlat_count 4\n"
              'errs{path="/a}b"} 3\n')
    expo_b = ("# TYPE lat histogram\n"
              "lat_sum 16.5\nlat_count 6\n"
              'errs{path="/a}b"} 3\n')
    prom_cli.to_statsd_lines(prom_cli.parse_exposition(expo_a), prev)
    lines = prom_cli.to_statsd_lines(prom_cli.parse_exposition(expo_b),
                                     prev)
    text = b"\n".join(lines)
    assert b"lat_sum:6.5|c" in text
    assert b"lat_count:2.0|c" in text
    # an unescaped '}' inside a quoted label value is legal exposition
    samples = prom_cli.parse_exposition(expo_a)
    errs = [s for s in samples if s[0] == "errs"]
    assert errs and errs[0][1] == {"path": "/a}b"}


def test_prometheus_end_to_end_poll():
    import http.server

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = EXPO_2.encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = http.server.HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    sock, port = recv_udp()
    try:
        rc = prom_cli.main([
            "-p", f"http://127.0.0.1:{httpd.server_port}/metrics",
            "-s", f"127.0.0.1:{port}", "--once"])
        assert rc == 0
        data, _ = sock.recvfrom(65536)   # at least the gauge arrives
        assert b"|g" in data or b"|c" in data
    finally:
        httpd.shutdown()
        sock.close()


def test_proxy_cli_static_config(tmp_path):
    from veneur_tpu.cli import proxy as proxy_cli

    # happy path: static destinations, Go-style refresh duration
    proxy = proxy_cli.proxy_from_config({
        "grpc_address": "127.0.0.1:0",
        "forward_destinations": ["127.0.0.1:9999", "127.0.0.1:9998"],
        "consul_refresh_interval": "1m",
    })
    try:
        assert len(proxy.ring) == 2
        assert proxy.ring.get(b"some.metric|c|") in (
            "127.0.0.1:9998", "127.0.0.1:9999")
        assert proxy.refresh_interval_s == 60.0
    finally:
        proxy.stop()
    # config missing both discovery modes errors out
    bad = tmp_path / "bad.yaml"
    bad.write_text("grpc_address: '127.0.0.1:0'\n")
    assert proxy_cli.main(["-f", str(bad)]) == 1


def test_daemon_validate_config(tmp_path):
    from veneur_tpu.cli import veneur as veneur_cli

    cfgfile = tmp_path / "v.yaml"
    cfgfile.write_text("interval: '10s'\nnum_workers: 2\n")
    assert veneur_cli.main(["-f", str(cfgfile),
                            "--validate-config"]) == 0


def test_daemon_refuses_tpu_backend_off_chip(tmp_path):
    """`aggregation_backend: tpu` (the default) on a machine whose JAX
    hands back the CPU must not start serving: the daemon exits
    non-zero naming the platform it found. This process is pinned to
    the CPU, which is exactly that machine."""
    import pytest

    from veneur_tpu.cli import veneur as veneur_cli

    cfgfile = tmp_path / "v.yaml"
    cfgfile.write_text("interval: '10s'\n"
                       "statsd_listen_addresses: ['udp://127.0.0.1:0']\n")
    with pytest.raises(SystemExit) as exc:
        veneur_cli.main(["-f", str(cfgfile)])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert "aggregation_backend: tpu" in str(exc.value.code)

    bad = tmp_path / "bad.yaml"
    bad.write_text("aggregation_backend: gpu\n")
    with pytest.raises(ValueError, match="aggregation_backend"):
        veneur_cli.main(["-f", str(bad)])

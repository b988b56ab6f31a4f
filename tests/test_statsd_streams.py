"""Statsd-over-stream listeners: TCP, UNIX, TLS, and mutual TLS
(networking.go's StartStatsd stream arms + the TLS triple)."""

import datetime
import socket
import ssl
import time

import pytest

from veneur_tpu.config import Config
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink


def make_server(tmp_path, addr, **cfg_kw):
    cap = CaptureMetricSink()
    cfg = Config(statsd_listen_addresses=[addr], interval="10s",
                 hostname="h", aggregates=["count"], percentiles=[],
                 **cfg_kw)
    srv = Server(cfg, sinks=[cap], span_sinks=[])
    srv.start()
    return srv, cap


def wait_packets(srv, n, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if srv.packets_received >= n:
            return True
        time.sleep(0.01)
    return False


def flush_values(srv, cap):
    assert srv.drain()
    srv.flush_once(timestamp=1000)
    cap.wait_for_flush()
    return {m.name: m.value for fl in cap.flushes for m in fl
            if not m.name.startswith("veneur.")}


def test_tcp_statsd():
    srv, cap = make_server(None, "tcp://127.0.0.1:0")
    try:
        port = srv._listen_socks[0].getsockname()[1]
        with socket.create_connection(("127.0.0.1", port), timeout=5) as c:
            # split a line across two sends to exercise reassembly
            c.sendall(b"tcp.count:1|c\ntcp.co")
            time.sleep(0.05)
            c.sendall(b"unt:2|c\n")
        assert wait_packets(srv, 2)
        vals = flush_values(srv, cap)
        assert vals["tcp.count"] == 3.0
    finally:
        srv.stop()


def test_unix_statsd(tmp_path):
    path = str(tmp_path / "statsd.sock")
    srv, cap = make_server(tmp_path, f"unix://{path}")
    try:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as c:
            c.connect(path)
            c.sendall(b"ux.g:7|g\n")
        assert wait_packets(srv, 1)
        vals = flush_values(srv, cap)
        assert vals["ux.g"] == 7.0
    finally:
        srv.stop()


def _self_signed(tmp_path, name):
    """(key_path, cert_path) for CN=name, self-signed."""
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    subject = x509.Name(
        [x509.NameAttribute(NameOID.COMMON_NAME, name)])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(subject).issuer_name(subject)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(days=1))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.SubjectAlternativeName(
                [x509.DNSName("localhost"),
                 x509.IPAddress(__import__("ipaddress")
                                .ip_address("127.0.0.1"))]),
                critical=False)
            .sign(key, hashes.SHA256()))
    kp = tmp_path / f"{name}.key"
    cp = tmp_path / f"{name}.crt"
    kp.write_bytes(key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.TraditionalOpenSSL,
        serialization.NoEncryption()))
    cp.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    return str(kp), str(cp)


def test_tls_statsd(tmp_path):
    key, cert = _self_signed(tmp_path, "server")
    srv, cap = make_server(tmp_path, "tcp://127.0.0.1:0",
                           tls_key=key, tls_certificate=cert)
    try:
        port = srv._listen_socks[0].getsockname()[1]
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.load_verify_locations(cafile=cert)
        with socket.create_connection(("127.0.0.1", port), timeout=5) as c:
            with ctx.wrap_socket(c, server_hostname="localhost") as tc:
                tc.sendall(b"tls.count:5|c\n")
        assert wait_packets(srv, 1)
        vals = flush_values(srv, cap)
        assert vals["tls.count"] == 5.0
    finally:
        srv.stop()


def test_mutual_tls_rejects_certless_client(tmp_path):
    skey, scert = _self_signed(tmp_path, "server")
    ckey, ccert = _self_signed(tmp_path, "client")
    srv, cap = make_server(tmp_path, "tcp://127.0.0.1:0",
                           tls_key=skey, tls_certificate=scert,
                           tls_authority_certificate=ccert)
    try:
        port = srv._listen_socks[0].getsockname()[1]
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.load_verify_locations(cafile=scert)
        # no client cert -> handshake must fail
        with pytest.raises((ssl.SSLError, ConnectionError, OSError)):
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=5) as c:
                with ctx.wrap_socket(c, server_hostname="localhost") as tc:
                    tc.sendall(b"x:1|c\n")
                    tc.recv(1)  # force handshake completion/alert
        # with the client cert, accepted
        ctx2 = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx2.load_verify_locations(cafile=scert)
        ctx2.load_cert_chain(certfile=ccert, keyfile=ckey)
        with socket.create_connection(("127.0.0.1", port), timeout=5) as c:
            with ctx2.wrap_socket(c, server_hostname="localhost") as tc:
                tc.sendall(b"mtls.count:9|c\n")
        assert wait_packets(srv, 1)
        vals = flush_values(srv, cap)
        assert vals["mtls.count"] == 9.0
    finally:
        srv.stop()


def test_native_mode_tcp_slow_path():
    """Stream lines in native-ingest mode route through the bridge via
    handle_packet (same conformance machinery as UDP)."""
    pytest.importorskip("veneur_tpu.ingest.native")
    srv, cap = make_server(None, "tcp://127.0.0.1:0", native_ingest=True)
    try:
        port = srv._listen_socks[0].getsockname()[1]
        with socket.create_connection(("127.0.0.1", port), timeout=5) as c:
            c.sendall(b"ntcp.count:4|c\n")
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if int(srv.native_bridge.stats()["lines"]) >= 1:
                break
            time.sleep(0.01)
        vals = flush_values(srv, cap)
        assert vals["ntcp.count"] == 4.0
    finally:
        srv.stop()


def test_oversized_line_suffix_is_discarded():
    """An oversized stream line is dropped IN FULL: its later bytes
    (arriving in subsequent reads) must not be parsed as fresh metrics
    (advisor r1: discard-until-newline)."""
    srv, cap = make_server(None, "tcp://127.0.0.1:0",
                           metric_max_length=512)
    try:
        port = srv._listen_socks[0].getsockname()[1]
        with socket.create_connection(("127.0.0.1", port), timeout=5) as c:
            # chunk 1: > max_len with no newline -> dropped, reader
            # enters discard mode
            c.sendall(b"x" * 600)
            time.sleep(0.05)
            # chunk 2: still the SAME logical line; pre-fix this parsed
            # as a fresh metric
            c.sendall(b"evil.count:1|c\n")
            time.sleep(0.05)
            # chunk 3: a real line after the terminator
            c.sendall(b"good.count:2|c\n")
        assert wait_packets(srv, 1)
        vals = flush_values(srv, cap)
        assert "evil.count" not in vals
        assert vals["good.count"] == 2.0
        # the oversized line was counted (flush_values runs flush_once,
        # which drains the counter into self-metrics — read the flushed
        # self-metric, not the already-reset live counter)
        errs = [m.value for fl in cap.flushes for m in fl
                if m.name == "veneur.packet.error_total"]
        assert errs and errs[0] >= 1
    finally:
        srv.stop()

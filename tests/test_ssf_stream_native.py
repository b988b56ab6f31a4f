"""The bridge's reader of framed SSF streams (native/vtpu_ingest.cpp
`ssf_stream_conn_loop`) against the Python loop it stands in for
(`server.py:_read_ssf_stream`), on the same bytes.

Two servers of one configuration, both on the native bridge. One takes
the bytes on its `unix://` listener, which the bridge accepts on and
reads itself; the other has `_read_ssf_stream` run on one end of a
socket pair a connection: the loop of a listener the bridge does not
read, which frames and decodes in Python (`framing.read_ssf`) and sends
every span through the span pipeline. A case is a list of
connections, each a list of writes. After it both servers must have
flushed the same series, counted the same errors, and closed the same
connections: the offending one and no other.
"""

import os
import socket
import struct
import threading
import time

import pytest

from veneur_tpu.ssf import framing
from veneur_tpu.ssf.protos import ssf_pb2


def span(i, n_samples=1, unit="ms"):
    sp = ssf_pb2.SSFSpan(version=1, id=i + 1, trace_id=7, service="svc",
                         name="op")
    for j in range(n_samples):
        m = sp.metrics.add()
        m.metric = ssf_pb2.SSFSample.HISTOGRAM
        m.name = f"par.lat{j % 3}"
        m.value = float(i + j)
        m.unit = unit
        m.tags["k"] = "v"
    c = sp.metrics.add()
    c.metric = ssf_pb2.SSFSample.COUNTER
    c.name = "par.hits"
    c.value = 1.0
    return sp


def frame(i, **kw):
    return framing.write_ssf(span(i, **kw))


def raw_frame(body: bytes, version=framing.VERSION_BYTE) -> bytes:
    return bytes([version]) + struct.pack("<I", len(body)) + body


def deep_group(depth: int) -> bytes:
    """An unknown field (number 100) holding `depth` nested groups: well
    formed, so the protobuf runtime skips it; deeper than the native
    parser follows, so the span goes to the fallback whole."""
    start, end = bytes([0xA3, 0x06]), bytes([0xA4, 0x06])   # 100: SGROUP/EGROUP
    return start * depth + end * depth


GOOD3 = frame(1) + frame(2, n_samples=3) + frame(3, unit="ns")
CASES = {
    # every byte boundary of three frames falls between two reads once
    "split_at_every_offset": [[GOOD3[:k], GOOD3[k:]]
                              for k in range(1, len(GOOD3))],
    "many_frames_in_one_read": [[b"".join(frame(i) for i in range(300))]],
    "bad_version_byte": [[frame(1) + raw_frame(
        span(2).SerializeToString(), version=0x07) + frame(3)]],
    "length_over_the_maximum": [[frame(1) + b"\x00" + struct.pack(
        "<I", framing.MAX_FRAME_LENGTH + 1) + b"x" * 64]],
    "closed_inside_a_frame": [[frame(1) + frame(2)[:-5]]],
    "closed_inside_a_header": [[frame(1) + b"\x00\x10"]],
    "malformed_protobuf": [[frame(1) + raw_frame(b"\xff\xff\xff\xff\x01")
                            + frame(3)]],
    "deep_unknown_group_falls_back": [[
        frame(1) + raw_frame(span(2).SerializeToString()
                             + deep_group(framing.PB_SKIP_MAX_DEPTH + 4))
        + frame(3)]],
    "empty_frame_is_a_span_without_samples": [[frame(1) + raw_frame(b"")
                                               + frame(3)]],
    # the one at fault is closed; its neighbour goes on
    "two_connections_at_once": [
        [frame(1), raw_frame(b"", version=0x01), frame(2)],
        [frame(10), frame(11), frame(12)]],
}


class Arm:
    """One server and the way its connections are made."""

    def __init__(self, tmp_path, native: bool):
        import jax  # noqa: F401  (conftest pins cpu)
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks.basic import CaptureMetricSink
        self.native = native
        self.path = os.path.join(str(tmp_path), "n.sock" if native
                                 else "p.sock")
        cfg = Config(ssf_listen_addresses=["unix://" + self.path],
                     interval="3600s", hostname="t", native_ingest=True,
                     num_readers=1, tpu_histogram_slots=512,
                     tpu_counter_slots=512, tpu_gauge_slots=64,
                     tpu_set_slots=64, flush_phase_timers=False)
        self.sink = CaptureMetricSink()
        self.srv = Server(cfg, sinks=[self.sink], plugins=[])
        self.srv.start()
        assert self.srv._native_ssf
        self.ts = 1000

    def connect(self):
        if self.native:
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.connect(self.path)
            return s
        ours, theirs = socket.socketpair()
        threading.Thread(target=self.srv._read_ssf_stream, args=(theirs,),
                         daemon=True).start()
        return ours

    def errors(self) -> int:
        return (self.srv.ssf_errors
                + int(self.srv.native_bridge.stats()["ssf_errors"]))

    def seen(self) -> tuple:
        st = self.srv.native_bridge.stats()
        return (st["ssf_spans"], st["ssf_fallbacks"], st["samples"],
                self.errors(), self.srv.spans_received)

    def run(self, conns: list) -> tuple:
        """Play a case; ({series: value}, errors, closed flags)."""
        err0 = self.errors()
        socks = [self.connect() for _ in conns]
        for turn in range(max(len(w) for w in conns)):
            for s, writes in zip(socks, conns):
                if turn < len(writes) and writes[turn]:
                    try:
                        s.sendall(writes[turn])
                    except OSError:
                        pass        # the far end closed on an error
            time.sleep(0.002)       # the next write is another read
        # settled: nothing counted for a while, queues and rings empty
        last, since = None, time.monotonic()
        while time.monotonic() - since < 0.25:
            now = self.seen()
            if now != last:
                last, since = now, time.monotonic()
            time.sleep(0.01)
        closed = []
        for s in socks:
            s.setblocking(False)
            try:
                closed.append(s.recv(1) == b"")
            except BlockingIOError:
                closed.append(False)
            except OSError:
                closed.append(True)
        for s in socks:
            s.close()
        time.sleep(0.15)            # the close itself is read
        assert self.srv.drain(20)
        errors = self.errors() - err0       # the flush drains the count
        self.ts += 10
        n = len(self.sink.flushes)
        self.srv.flush_once(timestamp=self.ts)
        assert self.sink.wait_for_flush(n + 1, 20)
        rows = {(m.name, tuple(m.tags)): m.value
                for m in self.sink.flushes[-1]
                if m.name.startswith("par.")
                # a digest's quantiles follow the order its samples
                # arrived in, which two connections do not fix
                and "percentile" not in m.name}
        return rows, errors, closed


@pytest.fixture(scope="module")
def arms(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ssfpar")
    native, python = Arm(tmp, True), Arm(tmp, False)
    yield native, python
    native.srv.stop()
    python.srv.stop()


@pytest.mark.parametrize("case", list(CASES))
def test_native_stream_reader_matches_the_python_loop(arms, case):
    native, python = arms
    conns = CASES[case]
    got = native.run(conns)
    want = python.run(conns)
    assert got[0] == want[0] and got[0], case
    assert got[1] == want[1], (case, "errors")
    assert got[2] == want[2], (case, "closed connections")
    if case == "two_connections_at_once":
        assert got[1] == 1 and got[2] == [True, False]
        assert got[0][("par.hits", ())] == 4.0     # 1 + the neighbour's 3
    if case == "deep_unknown_group_falls_back":
        assert got[1] == 0 and got[2] == [False]
        assert got[0][("par.hits", ())] == 3.0
    if case == "split_at_every_offset":
        assert got[1] == 0 and not any(got[2])
        assert got[0][("par.hits", ())] == 3.0 * len(conns)


def test_no_python_thread_reads_a_native_stream(arms):
    """The bridge accepts and reads: the server starts neither an accept
    loop nor a frame loop of its own for the listener it handed over."""
    native, _python = arms
    s = native.connect()
    s.sendall(frame(5))
    deadline = time.monotonic() + 5
    st = native.srv.native_bridge.stats()
    while time.monotonic() < deadline and not st["ssf_stream_frames"]:
        time.sleep(0.01)
        st = native.srv.native_bridge.stats()
    assert st["ssf_stream_conns"] >= 1 and st["ssf_stream_frames"] >= 1
    names = {t.name for t in threading.enumerate()}
    assert not [n for n in names if n.startswith("ssf-unix-accept")]
    assert "ssf-stream" not in {t.name for t in native.srv._threads}
    s.close()


def test_stop_closes_what_the_bridge_accepted(tmp_path):
    arm = Arm(tmp_path, True)
    s = arm.connect()
    s.sendall(frame(1))
    time.sleep(0.1)
    arm.srv.stop()
    s.settimeout(2.0)
    assert s.recv(1) == b""
    s.close()


def test_the_stream_readers_tallies(arms):
    native, _python = arms
    st0 = native.srv.native_bridge.stats()
    rows, errors, closed = native.run(
        [[b"".join(frame(i) for i in range(50))]])
    st = native.srv.native_bridge.stats()
    assert st["ssf_stream_frames"] - st0["ssf_stream_frames"] == 50
    assert st["ssf_stream_conns"] - st0["ssf_stream_conns"] == 1
    assert st["ssf_stream_conn_errors"] == st0["ssf_stream_conn_errors"]
    assert st["ssf_stream_read_ns"] > st0["ssf_stream_read_ns"]
    # a full ring drops and counts; a reader never waits for room
    assert st["ssf_stream_wait_ns"] == 0
    assert errors == 0 and closed == [False]
    # the flush tick that followed carries the readers' seconds as one
    # phase under its `ingest` root, and drained the tallies
    tick = native.srv.flight.last_tick()
    names = [p[0] for p in tick.phases()]
    assert names.count("ingest.ssf.read") == 1 and "ingest" in names
    from veneur_tpu.observe import SERVER_SCOPE
    assert native.srv.telemetry.total(
        SERVER_SCOPE, "ssf.stream.frames") >= 50

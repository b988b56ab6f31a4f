"""A local tier that reads with four `SO_REUSEPORT` sockets
(`num_readers: 4`), on the CPU at tiny sizes: every line counted once
and every counter exact from 16 client sockets, the key tables' counts
against the generator's plain ledger over six intervals with churn,
what each reader did summing to the bridge's totals, the sub-rings'
high water, and a gauge that is its last write in arrival order across
readers: a key handed from one flow to another reads the second
writer's value whichever sub-rings the two flows stage on, in 50 of 50
intervals, and the engine lands stamps by their order within a batch,
across batches, across a flush and across the stamp's 32-bit wrap.
"""

import os
import queue
import socket
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench.generators.dogstatsd_zipf import KeyLedger  # noqa: E402

BANKS = ("histo", "counter", "gauge", "set")
TTL = 3


def _server(**over):
    import jax  # noqa: F401  (conftest pins cpu)
    from veneur_tpu.config import Config
    from veneur_tpu.server import Server
    from veneur_tpu.sinks.basic import CaptureMetricSink
    cfg = Config(**{**dict(
        statsd_listen_addresses=["udp://127.0.0.1:0"], interval="3600s",
        hostname="t", native_ingest=True, num_readers=4,
        tpu_histogram_slots=256, tpu_counter_slots=512,
        tpu_gauge_slots=256, tpu_set_slots=64, tpu_batch_size=256,
        native_pump_batch=512, tpu_slot_idle_ttl_intervals=TTL), **over})
    sink = CaptureMetricSink()
    srv = Server(cfg, sinks=[sink], span_sinks=[])
    srv.start()
    return srv, sink


def _settle(srv, lines):
    """Every line parsed, pumped and landed (the verify notes' order:
    wait on `lines`, pause, drain)."""
    deadline = time.monotonic() + 20
    while int(srv.native_bridge.stats()["lines"]) < lines:
        assert time.monotonic() < deadline, srv.native_bridge.stats()
        time.sleep(0.005)
    time.sleep(0.05)
    assert srv.drain(20)


def _flushed(srv, sink, timestamp):
    """One flush's rows by name, once the sink's own thread (which the
    flusher does not join) has taken them."""
    n = len(sink.flushes)
    srv.flush_once(timestamp=timestamp)
    assert sink.wait_for_flush(n + 1, timeout=30)
    return {m.name: m.value for m in sink.flushes[n]}


# ------------------------------------------------ sixteen sockets, six ticks

@pytest.fixture(scope="module")
def sixteen():
    """Six intervals from 16 client sockets: a counter name of every
    socket's own, 24 names every socket sends (one key from many
    flows), 12 names only this interval has (churn), gauges owned by a
    socket, a timer and a set."""
    srv, sink = _server()
    socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
             for _ in range(16)]
    dest = ("127.0.0.1", srv.bound_port())
    for s in socks:
        s.connect(dest)
    rng = np.random.default_rng(49)
    ticks, sent_lines, sent_packets = [], 0, 0
    try:
        for tick in range(6):
            want, touched = {}, {b: set() for b in BANKS}
            for i, s in enumerate(socks):
                for burst in range(4):
                    lines = []
                    names = ([f"mr.own.s{i:02d}"]
                             + [f"mr.shared.c{k:02d}" for k in range(24)]
                             + [f"mr.tick{tick}.c{k:02d}"
                                for k in range(12)])
                    for name in names:
                        v = int(rng.integers(1, 1000))
                        lines.append(f"{name}:{v}|c|#env:t")
                        want[name] = want.get(name, 0) + v
                        touched["counter"].add(name)
                    g = int(rng.integers(0, 10_000_000))
                    lines.append(f"mr.gauge.s{i:02d}:{g // 1000}."
                                 f"{g % 1000:03d}|g")
                    want[f"mr.gauge.s{i:02d}"] = float(
                        np.float32(g / 1000.0))
                    touched["gauge"].add(f"mr.gauge.s{i:02d}")
                    lines.append(f"mr.lat:{burst + 1}|ms")
                    touched["histo"].add("mr.lat")
                    lines.append(f"mr.users:u{i}.{burst}|s")
                    touched["set"].add("mr.users")
                    s.send("\n".join(lines).encode())
                    sent_lines += len(lines)
                    sent_packets += 1
            _settle(srv, sent_lines)
            before = srv.native_bridge.stats()
            got = _flushed(srv, sink, 1_000 + 10 * tick)
            ticks.append((want, touched, before, got,
                          dict(srv.engines[0]._last_flush_info),
                          srv.flight.last_tick().phases()))
        stats = srv.native_bridge.stats()
        from veneur_tpu.observe import SERVER_SCOPE
        tel = {(scope, name): srv.telemetry.total(scope, name)
               for scope in [SERVER_SCOPE] + [f"reader:{i}" for i in range(4)]
               for name in ("packet.received", "ingest.reader.packets",
                            "ingest.reader.lines",
                            "ingest.reader.busy_ns")}
        way = srv.native_bridge.ring_way_capacity
    finally:
        for s in socks:
            s.close()
        srv.stop()
    return ticks, stats, tel, (sent_lines, sent_packets), way


def test_every_line_counted_once_and_every_counter_exact(sixteen):
    ticks, stats, _tel, (lines, packets), _way = sixteen
    assert (stats["lines"], stats["packets"]) == (lines, packets)
    assert stats["samples"] == lines
    for name in ("parse_errors", "ring_drops", "drops_no_slot",
                 "other_drops", "slow_routed"):
        assert stats[name] == 0, name
    for want, _touched, _before, got, _info, _phases in ticks:
        for name, v in want.items():
            assert got[name] == v, name
        assert got["mr.lat.count"] == 64.0 and got["mr.users"] > 0
        # nothing of another interval: this tick's own names only
        assert {n for n in got if n.startswith("mr.tick")} == {
            n for n in want if n.startswith("mr.tick")}


def test_the_key_tables_match_the_ledger_with_four_readers(sixteen):
    ticks, stats, _tel, _sent, _way = sixteen
    ledger = KeyLedger(TTL)
    minted = evicted = 0
    for i, (_want, touched, _before, _got, info, _phases) in enumerate(
            ticks):
        touched = {b: sorted(names) for b, names in touched.items()}
        # the timers a flush feeds its own server land in the interval
        # after it: the ones this tick's sink names
        own = sorted(n for n in _got if n.startswith("veneur.")
                     and n.endswith(".count"))
        assert bool(own) == bool(i)
        touched["histo"] += own
        want = ledger.tick(touched)
        for kind in ("interned", "evicted", "live"):
            assert dict(zip(BANKS, info["keys_" + kind])) == want[kind], (
                i, kind)
        minted += sum(info["keys_interned"])
        evicted += sum(info["keys_evicted"])
    assert evicted >= 24       # ticks 0 and 1's own names have gone
    assert sum(stats[f"keys_interned_{b}"] for b in BANKS) == minted
    assert sum(stats[f"keys_evicted_{b}"] for b in BANKS) == evicted


def test_what_each_reader_did_sums_to_the_totals(sixteen):
    ticks, stats, tel, (lines, packets), _way = sixteen
    from veneur_tpu.observe import SERVER_SCOPE
    readers = stats["readers"]
    assert len(readers) == 4
    assert sum(r["packets"] for r in readers) == packets
    assert sum(r["lines"] for r in readers) == lines
    # a flow stays on one reader, so a reader's datagrams come in fours
    assert all(r["packets"] % 24 == 0 for r in readers)
    busy = [r for r in readers if r["packets"]]
    assert busy and all(r["busy_ns"] > 0 and r["lines"] > 0 for r in busy)
    # the registry's counters are the same counts, tagged by reader
    for i, r in enumerate(readers):
        for key in ("packets", "lines", "busy_ns"):
            assert tel[f"reader:{i}", "ingest.reader." + key] == r[key]
    assert tel[SERVER_SCOPE, "packet.received"] == packets
    # one `ingest.reader.busy` row a reader that received, every tick,
    # under the tick's `ingest` root
    for _want, _touched, _before, _got, _info, phases in ticks:
        rows = [p for p in phases if p[0] == "ingest.reader.busy"]
        assert len(rows) == len(busy)
        root = [i for i, p in enumerate(phases) if p[0] == "ingest"]
        assert len(root) == 1 and all(p[3] == root[0] for p in rows)


def test_the_ring_high_water_is_counted_and_never_over_capacity(sixteen):
    ticks, stats, _tel, _sent, way = sixteen
    assert way == (1 << 20) // 8 + 1
    for _want, _touched, before, _got, _info, _phases in ticks:
        high = {b: before["ring_high_" + b] for b in BANKS}
        assert all(0 < h <= way for h in high.values()), high
        # 37 counters a datagram: the counter bank's rings ran fullest
        assert high["counter"] == max(high.values())
    # the flush took the mark: the idle time since reads 0
    assert all(stats["ring_high_" + b] == 0 for b in BANKS)


# ------------------------------------------------------- the handed-over key

class _Writer(threading.Thread):
    """One OS thread that hands packets to the bridge: its thread-local
    stage takes one sub-ring at its first packet and keeps it."""

    def __init__(self, bridge):
        super().__init__(daemon=True)
        self.bridge, self.q, self.done = bridge, queue.Queue(), queue.Queue()
        self.start()

    def run(self):
        while True:
            data = self.q.get()
            if data is None:
                return
            self.bridge.handle_packet(data)
            self.done.put(1)

    def write(self, data: bytes):
        self.q.put(data)
        self.done.get(timeout=10)


def test_a_handed_over_gauge_reads_its_second_writer_50_of_50():
    """Two flows through `vtpu_handle_packet` from two threads, whose
    stages take consecutive sub-rings; the old write on the lower way
    and on the higher in turn, both staged before the pump polls (the
    poll empties way 0 first, so a batch's own order is the ways')."""
    srv, sink = _server(num_readers=1)
    a, b = _Writer(srv.native_bridge), _Writer(srv.native_bridge)
    pump = srv.native_pump
    lines = 0
    try:
        # each thread's first packet fixes its way
        a.write(b"mr.warm:1|c")
        b.write(b"mr.warm:1|c")
        lines += 2
        right = 0
        for tick in range(50):
            first, second = (a, b) if tick % 2 == 0 else (b, a)
            old = [f"mr.handed:{tick}.5|g", f"mr.only.first:{tick}|g",
                   f"mr.handed:{tick}.25|g"]
            new = [f"mr.handed:{1000 + tick}.75|g",
                   f"mr.only.second:{tick}|g"]
            with pump._pump_lock:
                first.write("\n".join(old).encode())
                second.write("\n".join(new).encode())
            lines += len(old) + len(new)
            _settle(srv, lines)
            got = _flushed(srv, sink, 2_000 + 10 * tick)
            assert got["mr.only.first"] == tick
            assert got["mr.only.second"] == tick
            right += got["mr.handed"] == 1000 + tick + 0.75
        assert right == 50
    finally:
        for w in (a, b):
            w.q.put(None)
        srv.stop()


def test_a_gauge_of_the_python_path_takes_the_bridges_order():
    """A line the C++ parser routes to Python (a value float() takes
    and strtod does not) is ordered among the datagrams by when it is
    processed: a datagram received after it wins."""
    srv, sink = _server(num_readers=1)
    try:
        br = srv.native_bridge
        br.handle_packet(b"mr.slow:1|g")
        br.handle_packet(b"mr.slow:1_0|g")          # slow path: 10
        _settle(srv, 2)
        br.handle_packet(b"mr.other:3|g")
        _settle(srv, 3)
        assert srv.engines[0].gauge_clock is not None
        assert _flushed(srv, sink, 3_000)["mr.slow"] == 10.0
        br.handle_packet(b"mr.slow:2_0|g")
        _settle(srv, 4)
        br.handle_packet(b"mr.slow:7|g")
        _settle(srv, 5)
        assert _flushed(srv, sink, 3_010)["mr.slow"] == 7.0
    finally:
        srv.stop()


# ------------------------------------------------- the engine, by the stamp

def _engine():
    import jax  # noqa: F401
    from veneur_tpu.models.pipeline import AggregationEngine, EngineConfig
    return AggregationEngine(EngineConfig(
        histogram_slots=64, counter_slots=64, gauge_slots=64, set_slots=8,
        batch_size=16))


def _gauges(eng):
    return np.asarray(eng.gauge_bank.value)


def _batch(eng, rows, width=8):
    """rows: (slot, value, stamp) in the batch's own order."""
    slots = np.full(width, -1, np.int32)
    values = np.zeros(width, np.float32)
    order = np.zeros(width, np.int64)
    for i, (s, v, o) in enumerate(rows):
        slots[i], values[i], order[i] = s, v, o
    eng.ingest_gauge_batch(slots, values, count=len(rows),
                           order=order.astype(np.uint32).astype(np.int32))


def test_the_engine_lands_a_batch_by_its_stamps_not_its_places():
    eng = _engine()
    # way 1's later write polled before way 3's earlier one
    _batch(eng, [(5, 2.0, 12), (6, 9.0, 12), (5, 1.0, 11), (7, 4.0, 13)])
    assert _gauges(eng)[[5, 6, 7]].tolist() == [2.0, 9.0, 4.0]
    # one datagram's own samples keep their places (equal stamps)
    _batch(eng, [(8, 1.0, 20), (8, 2.0, 20), (8, 3.0, 20)])
    assert _gauges(eng)[8] == 3.0
    # across batches the bank's stored number arbitrates: an older
    # stamp pumped later loses, a newer one wins
    _batch(eng, [(5, 7.0, 9), (6, 8.0, 30)])
    assert _gauges(eng)[[5, 6]].tolist() == [2.0, 8.0]
    # without stamps a batch's places are its order, above all before
    eng.ingest_gauge_batch(np.array([5, 5], np.int32),
                           np.array([3.0, 4.0], np.float32))
    assert _gauges(eng)[5] == 4.0


def test_a_stamp_outlives_no_interval_and_wraps():
    eng = _engine()
    top = (1 << 32) - 2
    eng._gauge_base = top - 10      # a bridge that has run for long
    _batch(eng, [(1, 1.0, top - 5), (2, 1.0, top)])
    assert eng._gauge_seq < (1 << 31)
    eng.flush(timestamp=1)
    assert eng._gauge_seq == 0 and eng._gauge_base == top
    # after the swap: a straggler staged before it (stamp under the
    # base), then the wrap; every one has a number of its own, in order
    _batch(eng, [(1, 5.0, top + 3), (1, 4.0, top - 1), (2, 6.0, top + 2),
                 (2, 7.0, 1 << 32), (3, 8.0, top - 2), (3, 9.0, top - 3)])
    assert _gauges(eng)[[1, 2, 3]].tolist() == [5.0, 7.0, 8.0]
    room = eng._GAUGE_SEQ_ROOM
    assert eng._gauge_seq == room + 3 and eng._gauge_hi == 3
    # a gauge of the Python path and an imported one land above them
    eng.flush(timestamp=2)
    assert eng._gauge_base == (top + 3) % (1 << 32) and eng._gauge_hi == 0


def test_the_gauge_stamp_is_in_the_ring_row_one_a_datagram():
    from veneur_tpu.ingest import native
    br = native.NativeBridge(64, 64, 64, 64, ring_capacity=4096)
    try:
        first = br.next_arrival()
        br.handle_packet(b"g.a:1|g\ng.b:2|g\nc.a:1|c")
        br.handle_packet(b"g.a:3|g")
        bufs = (np.zeros(8, np.int32), np.zeros(8, np.float32),
                np.zeros(8, np.float32), np.zeros(8, np.int32))
        assert br.poll("gauge", *bufs) == 3
        assert bufs[3][:3].tolist() == [first + 1, first + 1, first + 2]
        assert br.poll("counter", *bufs) == 1 and bufs[3][0] == 0
        assert br.next_arrival() == first + 3
        assert br.stats()["readers"] == []      # no UDP reader started
        assert br.take_ring_high() == {"histo": 0, "counter": 1,
                                       "gauge": 3, "set": 0}
        assert br.take_ring_high() == dict.fromkeys(BANKS, 0)
    finally:
        br.close()

"""C++ ingest bridge tests.

Three layers, mirroring the reference's parser/worker/server test split
(samplers/parser_test.go, worker_test.go, server_test.go):
  1. parse conformance — the C++ parser must agree with the Python
     reference parser line-for-line on the shared corpus plus randomized
     lines (verdict, name, type, tags, digest, value, rate, scope).
  2. bridge mechanics — interning, ring draining, new-key records, slow
     path routing, eviction.
  3. end-to-end — a native-mode Server ingesting over loopback UDP must
     produce the same flush output as the Python path.
"""

import random
import socket
import time

import numpy as np
import pytest

from veneur_tpu.ingest import parser
from veneur_tpu.utils import hashing

native = pytest.importorskip("veneur_tpu.ingest.native")

try:
    native.load()
except native.NativeUnavailable as e:  # pragma: no cover
    pytest.skip(f"native build unavailable: {e}", allow_module_level=True)

from tests.test_parser import INVALID, VALID  # shared corpus


def py_verdict(line: bytes):
    """What the Python reference does with a line."""
    if line.startswith(b"_e{") or line.startswith(b"_sc|"):
        return "other", None
    try:
        m = parser.parse_metric(line)
    except parser.ParseError:
        return "error", None
    return "metric", m


def assert_conformant(line: bytes):
    pv, pm = py_verdict(line)
    cv, cm = native.parse_one(line)
    if cv == native.P_OTHER:
        # C++ may punt to Python on lines it can't prove bit-identical;
        # that's conformant by construction (Python handles them), but
        # events/service-checks must always punt.
        return
    if pv == "metric":
        assert cv == native.P_METRIC, f"C++ rejected valid line {line!r}"
        assert cm["name"] == pm.key.name
        assert cm["type"] == pm.key.type
        assert cm["joined_tags"] == pm.key.joined_tags
        assert cm["digest"] == pm.digest
        assert cm["sample_rate"] == pm.sample_rate
        assert cm["scope"] == pm.scope
        if pm.key.type == "set":
            assert cm["value"] == pm.value
        else:
            assert cm["value"] == pytest.approx(pm.value, rel=0, abs=0)
    else:
        assert cv == native.P_ERROR, \
            f"C++ accepted invalid line {line!r}: {cm}"


class TestParseConformance:
    @pytest.mark.parametrize("case", VALID, ids=[v[0].decode()
                                                 for v in VALID])
    def test_valid_corpus(self, case):
        assert_conformant(case[0])

    @pytest.mark.parametrize("line", INVALID,
                             ids=[repr(l) for l in INVALID])
    def test_invalid_corpus(self, line):
        assert_conformant(line)

    def test_events_and_checks_punt(self):
        assert native.parse_one(b"_e{2,3}:ab|cde")[0] == native.P_OTHER
        assert native.parse_one(b"_sc|svc|0")[0] == native.P_OTHER

    def test_invalid_utf8_punts(self):
        assert native.parse_one(b"nam\xff:1|c")[0] == native.P_OTHER

    def test_underscore_value_punts(self):
        # CPython float("1_0") == 10.0; C++ must not guess
        assert native.parse_one(b"a:1_0|c")[0] == native.P_OTHER

    def test_randomized(self):
        rng = random.Random(42)
        names = ["a", "api.req", "x.y.z", "srv-1.count", "m" * 40]
        types = ["c", "g", "ms", "h", "s", "d", "q", ""]
        tagsets = ["", "#a:b", "#b,a", "#veneurlocalonly",
                   "#veneurglobalonly,t:1", "#dup,dup"]
        rates = ["", "@0.5", "@1", "@2", "@0", "@x"]
        values = ["1", "-2.5", "1e3", "abc", "", "inf", "nan", "1.5e-2"]
        for _ in range(3000):
            line = (f"{rng.choice(names)}:{rng.choice(values)}"
                    f"|{rng.choice(types)}")
            for extra in (rng.choice(rates), rng.choice(tagsets)):
                if extra:
                    line += "|" + extra
            assert_conformant(line.encode())

    def test_bench_hook(self):
        lines = b"\n".join(
            f"api.req.time_{i % 97}:{i % 113}|ms|#svc:web,env:prod"
            .encode() for i in range(1000))
        arr = np.frombuffer(bytearray(lines), np.uint8)
        lib = native.load()
        dt = lib.vtpu_bench_parse(native._u8(arr), len(lines), 10)
        assert dt > 0


@pytest.fixture
def bridge():
    br = native.NativeBridge(histo_slots=64, counter_slots=64,
                             gauge_slots=64, set_slots=64,
                             hll_precision=14, idle_ttl=4,
                             ring_capacity=4096, max_packet=8192)
    yield br
    br.close()


def poll_all(br, bank, n=4096):
    slots = np.zeros(n, np.int32)
    a = np.zeros(n, np.float32)
    b = np.zeros(n, np.float32)
    c = np.zeros(n, np.int32)
    got = br.poll(bank, slots, a, b, c)
    return got, slots[:got], a[:got], b[:got], c[:got]


class TestBridge:
    def test_counter_roundtrip(self, bridge):
        bridge.handle_packet(b"hits:3|c|@0.5\nhits:1|c\nother:2|c")
        got, slots, vals, wts, _ = poll_all(bridge, "counter")
        assert got == 3
        keys = bridge.drain_new_keys()
        assert len(keys) == 2
        by_name = {k[4]: k for k in keys}
        assert set(by_name) == {"hits", "other"}
        hit_slot = by_name["hits"][3]
        mask = slots == hit_slot
        assert mask.sum() == 2
        # 1/rate weights
        assert sorted(wts[mask].tolist()) == [1.0, 2.0]
        assert sorted(vals[mask].tolist()) == [1.0, 3.0]

    def test_histo_timer_distinct_keys(self, bridge):
        # same name, different type -> distinct keys (digest covers type)
        bridge.handle_packet(b"x:1|ms\nx:1|h")
        keys = bridge.drain_new_keys()
        assert len(keys) == 2
        assert {k[1] for k in keys} == {2, 3}  # MT_TIMER, MT_HISTOGRAM

    def test_set_rho_matches_python(self, bridge):
        bridge.handle_packet(b"users:alice|s\nusers:bob|s")
        got, slots, rho, _, idx = poll_all(bridge, "set")
        assert got == 2
        p = 14
        expect = []
        for member in ("alice", "bob"):
            h = hashing.set_member_hash(member)
            eidx = h >> (64 - p)
            rest = ((h << p) & 0xFFFFFFFFFFFFFFFF) | ((1 << p) - 1)
            expect.append((eidx, 65 - rest.bit_length()))
        got_pairs = sorted(zip(idx.tolist(), rho.astype(int).tolist()))
        assert got_pairs == sorted(expect)

    def test_scope_tags(self, bridge):
        bridge.handle_packet(b"t:1|ms|#veneurglobalonly")
        keys = bridge.drain_new_keys()
        assert keys[0][2] == parser.GLOBAL_ONLY
        scopes = bridge.slot_scopes("histo")
        assert scopes[keys[0][3]] == parser.GLOBAL_ONLY

    def test_slow_path_routing(self, bridge):
        bridge.handle_packet(b"_e{2,2}:ab|cd\n_sc|s|0\na:1_0|c")
        other = bridge.drain_other()
        assert other == [b"_e{2,2}:ab|cd", b"_sc|s|0", b"a:1_0|c"]

    def test_parse_errors_counted(self, bridge):
        bridge.handle_packet(b"bad\n:1|c\na:1|q")
        assert bridge.stats()["parse_errors"] == 3

    def test_bank_full_drops(self, bridge):
        for i in range(200):
            bridge.handle_packet(f"m{i}:1|c".encode())
        st = bridge.stats()
        assert st["drops_no_slot"] == 200 - 64
        assert bridge.key_count("counter") == 64

    def test_eviction(self, bridge):
        bridge.handle_packet(b"old:1|c")
        for _ in range(6):
            bridge.advance_interval("counter")
            bridge.handle_packet(b"fresh:1|c")
        assert bridge.key_count("counter") == 1  # "old" evicted

    def test_intern_matches_parse_path(self, bridge):
        bridge.handle_packet(b"hits:1|c|#a:b")
        (_, _, _, slot, _, _), = bridge.drain_new_keys()
        # interning the same key from Python returns the same slot
        assert bridge.intern("counter", 0, "hits", "a:b") == slot
        assert bridge.intern("counter", 0, "hits", "a:c") != slot

    def test_udp_readers(self, bridge):
        port = bridge.start_udp("127.0.0.1", 0, 2)
        assert port > 0
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for i in range(50):
            s.sendto(f"udp.m:{i}|ms".encode(), ("127.0.0.1", port))
        s.close()
        deadline = time.monotonic() + 5
        total = 0
        while total < 50 and time.monotonic() < deadline:
            got, *_ = poll_all(bridge, "histo")
            total += got
            time.sleep(0.01)
        assert total == 50
        bridge.stop()


class TestNativeServer:
    def test_end_to_end_matches_python_path(self):
        """Same traffic through a native-mode and a Python-mode server
        must produce identical flush output."""
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks.basic import CaptureMetricSink

        lines = [b"api.t:5|ms|#svc:a", b"api.t:15|ms|#svc:a",
                 b"hits:2|c|@0.5", b"temp:70|g", b"temp:71|g",
                 b"users:alice|s", b"users:bob|s", b"users:alice|s",
                 b"_sc|db|0", b"_e{2,2}:ab|cd"]

        def run(native_on: bool):
            cap = CaptureMetricSink()
            cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                         interval="10s", hostname="h",
                         native_ingest=native_on,
                         percentiles=[0.5], aggregates=["max", "count"])
            srv = Server(cfg, sinks=[cap], span_sinks=[])
            srv.start()
            try:
                sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                port = srv.bound_port()
                for ln in lines:
                    sock.sendto(ln, ("127.0.0.1", port))
                sock.close()
                deadline = time.monotonic() + 5
                while time.monotonic() < deadline:
                    if native_on:
                        done = int(srv.native_bridge.stats()["lines"]) \
                            >= len(lines)
                    else:
                        done = srv.packets_received >= len(lines)
                    if done:
                        break
                    time.sleep(0.01)
                assert srv.drain()
                srv.flush_once(timestamp=1000)
                cap.wait_for_flush()
                out = {(m.name, tuple(m.tags)): m.value
                       for fl in cap.flushes for m in fl
                       if not m.name.startswith("veneur.")}
                ev = cap.events
                return out, ev
            finally:
                srv.stop()

        native_out, native_ev = run(True)
        py_out, py_ev = run(False)
        assert set(native_out) == set(py_out)
        for k in py_out:
            assert native_out[k] == pytest.approx(py_out[k]), k
        assert len(native_ev) == len(py_ev)


    def test_native_local_forwards_full_then_delta(self):
        """A native-ingest local tier that forwards: the FULL export
        build reads the interner's whole table (all_items), which the
        bridge-backed key view did not have — the first flush of any
        native_ingest + forward_address server died in
        _flush_bookkeeping. Full ships idle keys too; the delta after
        it only what was touched."""
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks.basic import CaptureMetricSink

        sent = []
        # forward_address makes the engine build forward exports; the
        # injected callable stands in for the wire
        cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                     interval="3600s", hostname="h", native_ingest=True,
                     forward_address="127.0.0.1:1",
                     tpu_histogram_slots=256, tpu_counter_slots=128,
                     tpu_gauge_slots=128, tpu_set_slots=64,
                     tpu_batch_size=256, native_pump_batch=256)
        srv = Server(cfg, sinks=[CaptureMetricSink()], span_sinks=[],
                     forwarder=lambda exp: sent.append(exp))
        srv.start()
        try:
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            port = srv.bound_port()

            def send(lines):
                base = int(srv.native_bridge.stats()["lines"])
                for ln in lines:
                    sock.sendto(ln, ("127.0.0.1", port))
                deadline = time.monotonic() + 5
                while int(srv.native_bridge.stats()["lines"]) \
                        < base + len(lines):
                    assert time.monotonic() < deadline
                    time.sleep(0.01)
                assert srv.drain()

            send([b"g.hits:3|c|#veneurglobalonly",
                  b"g.idle:1|c|#veneurglobalonly",
                  b"users:alice|s", b"api.t:5|ms"])
            srv.flush_once(timestamp=1000)
            full = sent[-1]
            assert full.kind == "full"
            assert {k.name: v for k, v in full.counters} == {
                "g.hits": 3.0, "g.idle": 1.0}
            send([b"g.hits:4|c|#veneurglobalonly"])
            srv.flush_once(timestamp=1010)
            assert {k.name: v for k, v in sent[-1].counters}[
                "g.hits"] == 4.0
            sock.close()
        finally:
            srv.stop()


class TestAdvisorRegressions:
    def test_thread_local_cache_is_bridge_scoped(self):
        """A thread that ingested into bridge A must not reuse A's
        key->slot memo when it later serves bridge B: pre-fix, the
        thread_local cache was validated only against intern_epoch, so
        two same-epoch bridges silently misrouted or swallowed keys."""
        def mk():
            return native.NativeBridge(
                histo_slots=64, counter_slots=64, gauge_slots=64,
                set_slots=64, hll_precision=14, idle_ttl=4,
                ring_capacity=4096, max_packet=8192)

        a = mk()
        b = mk()
        try:
            # warm this thread's memo on A: k1..k3 -> slots 0..2
            a.handle_packet(b"k1:1|c\nk2:1|c\nk3:1|c")
            a_keys = {k[4]: k[3] for k in a.drain_new_keys()}
            assert a_keys["k3"] == 2
            # B interns one unrelated key (slot 0), then sees k3 — which
            # the stale memo would resolve to A's slot 2 without ever
            # interning it in B
            b.handle_packet(b"other:1|c")
            b.handle_packet(b"k3:5|c")
            b_keys = {k[4]: k[3] for k in b.drain_new_keys()}
            assert "k3" in b_keys, "k3 swallowed by a foreign bridge memo"
            assert b_keys["k3"] == 1
            got, slots, vals, _, _ = poll_all(b, "counter")
            assert 5.0 in vals[slots == b_keys["k3"]].tolist()
        finally:
            a.close()
            b.close()

    def test_tags_exclude_in_cpp_parser(self):
        """tags_exclude is applied by the C++ parser before key
        construction, matching the Python parser's semantics."""
        br = native.NativeBridge(histo_slots=64, counter_slots=64,
                                 gauge_slots=64, set_slots=64,
                                 hll_precision=14, idle_ttl=4,
                                 ring_capacity=4096, max_packet=8192)
        try:
            br.set_tags_exclude(["pod_id", "debug"])
            br.handle_packet(b"m:1|c|#env:p,pod_id:a\n"
                             b"m:2|c|#env:p,pod_id:b\n"
                             b"m:3|c|#debug,env:p")
            keys = br.drain_new_keys()
            assert len(keys) == 1          # all three merged to one key
            assert keys[0][5] == "env:p"   # joined_tags
            got, slots, vals, _, _ = poll_all(br, "counter")
            assert got == 3
            assert sorted(vals.tolist()) == [1.0, 2.0, 3.0]
            # digest parity with the Python parser under the same excl.
            pm = parser.parse_metric(b"m:1|c|#env:p,pod_id:a",
                                     frozenset(["pod_id", "debug"]))
            assert hashing.metric_digest(
                keys[0][4], "counter", keys[0][5]) == pm.digest
        finally:
            br.close()


class TestPumpBufferAliasing:
    def test_pump_dispatches_buffer_copies(self):
        """The pump must hand the engine COPIES of its reused poll
        buffers. jax's CPU client zero-copies page-aligned numpy arrays
        into executable arguments, so an async kernel dispatch still
        holds the buffer when the next poll overwrites it — observed
        (r5) as both over- and under-counted banks at pump widths
        >= 32768, where numpy's allocation becomes mmap'd/page-aligned.
        The contract is checked structurally (no shared memory), which
        is deterministic where the corruption itself is a timing race."""
        br = native.NativeBridge(histo_slots=64, counter_slots=64,
                                 gauge_slots=64, set_slots=64,
                                 hll_precision=14, idle_ttl=4,
                                 ring_capacity=4096, max_packet=8192)
        captured = []

        class StubEngine:
            def ingest_histo_batch(self, slots, values, weights,
                                   count=None, mark=None):
                captured.append((slots, values, weights))

            def ingest_counter_batch(self, slots, values, weights,
                                     count=None, mark=None):
                captured.append((slots, values, weights))

            def ingest_gauge_batch(self, slots, values, count=None,
                                   mark=None, order=None):
                captured.append((slots, values, order))

            def ingest_set_batch(self, slots, reg_idx, rho, count=None,
                                 mark=None):
                captured.append((slots, reg_idx, rho))

        try:
            views = {b: native.BridgeKeyView(br, b)
                     for b in ("histo", "counter", "gauge", "set")}
            pump = native.NativePump(br, StubEngine(), views,
                                     lambda line: None, batch=256)
            br.handle_packet(b"t:1|ms\nc:2|c\ng:3|g\ns:x|s")
            assert pump.pump_once() == 4
            assert len(captured) == 4
            bufs = [arr for tup in pump._bufs.values() for arr in tup]
            for tup in captured:
                for arr in tup:
                    assert not any(np.shares_memory(arr, b)
                                   for b in bufs), \
                        "pump passed a live poll buffer to the engine"
        finally:
            br.close()


class TestNativeSSF:
    """The C++ SSF span fast path (vtpu_handle_ssf) against its Python
    twin (sinks/ssfmetrics.py sample_to_metric / indicator_timer)."""

    def _bridge(self, **kw):
        return native.NativeBridge(histo_slots=256, counter_slots=256,
                                   gauge_slots=64, set_slots=64,
                                   hll_precision=14, idle_ttl=4,
                                   ring_capacity=65536, max_packet=8192,
                                   **kw)

    def test_ssf_parity_randomized(self):
        """Random spans: every natively staged sample must agree with
        sample_to_metric on key identity (name/type/tags/digest), bank,
        value, and weight."""
        from veneur_tpu.sinks.ssfmetrics import sample_to_metric
        from veneur_tpu.ssf.protos import ssf_pb2

        rng = random.Random(42)
        br = self._bridge()
        expected = []  # (type, name, joined_tags, value, weight|idx/rho)
        spans = []
        for i in range(50):
            sp = ssf_pb2.SSFSpan()
            sp.version = 1
            for j in range(rng.randint(1, 4)):
                s = sp.metrics.add()
                s.metric = rng.choice([
                    ssf_pb2.SSFSample.COUNTER, ssf_pb2.SSFSample.GAUGE,
                    ssf_pb2.SSFSample.HISTOGRAM, ssf_pb2.SSFSample.SET])
                s.name = f"m{rng.randint(0, 20)}"
                s.value = round(rng.uniform(0.1, 500.0), 3)
                if s.metric == ssf_pb2.SSFSample.SET:
                    s.message = f"member-{rng.randint(0, 99)}-é"
                if s.metric == ssf_pb2.SSFSample.HISTOGRAM \
                        and rng.random() < 0.5:
                    s.unit = rng.choice(["ns", "µs", "us", "ms",
                                         "s", "bytes"])
                if rng.random() < 0.5:
                    s.sample_rate = rng.choice([0.1, 0.5, 1.0])
                for t in range(rng.randint(0, 3)):
                    s.tags[f"k{rng.randint(0, 5)}"] = \
                        rng.choice(["", "v1", "v2", "ü"])
                s.scope = rng.choice([0, 1, 2])
                it = sample_to_metric(s)
                if it is not None:
                    expected.append(it)
            spans.append(sp)
        for sp in spans:
            assert br.handle_ssf(sp.SerializeToString()) == 1
        try:
            # slots are per-bank: key records by (bank_index, slot)
            keys = {(k[0], k[3]): k for k in br.drain_new_keys()}
            bank_idx = {"histo": 0, "counter": 1, "gauge": 2, "set": 3}
            # drain all rings, grouped per bank
            staged = {b: [] for b in ("histo", "counter", "gauge", "set")}
            bufs = tuple(np.zeros(4096, dt) for dt in
                         (np.int32, np.float32, np.float32, np.int32))
            for bank in staged:
                n = br.poll(bank, *bufs)
                for i in range(n):
                    staged[bank].append((int(bufs[0][i]),
                                         float(bufs[1][i]),
                                         float(bufs[2][i]),
                                         int(bufs[3][i])))
            bank_of = {"counter": "counter", "gauge": "gauge",
                       "timer": "histo", "histogram": "histo",
                       "set": "set"}
            # order within one ring is arrival order; expectations are
            # in emission order per bank too
            per_bank_exp = {b: [] for b in staged}
            for it in expected:
                per_bank_exp[bank_of[it.key.type]].append(it)
            for bank, rows in staged.items():
                exp = per_bank_exp[bank]
                assert len(rows) == len(exp), (bank, len(rows), len(exp))
                for (slot, a, b_, c), it in zip(rows, exp):
                    rec = keys[(bank_idx[bank], slot)]
                    assert rec[4] == it.key.name
                    assert rec[5] == it.key.joined_tags
                    assert native._MTYPE_NAMES[rec[1]] == it.key.type
                    if bank == "set":
                        h = hashing.set_member_hash(str(it.value))
                        p = 14
                        assert c == h >> (64 - p)
                        rest = ((h << p) & 0xFFFFFFFFFFFFFFFF) \
                            | ((1 << p) - 1)
                        assert int(a) == 65 - rest.bit_length()
                    else:
                        assert a == pytest.approx(it.value, rel=1e-6)
                        if bank in ("histo", "counter"):
                            assert b_ == pytest.approx(
                                1.0 / it.sample_rate, rel=1e-6)
        finally:
            br.close()

    def test_ssf_duplicate_map_key_last_wins(self):
        """proto3 map semantics: for a duplicate key on the wire the
        LAST entry wins. The Python decoder's dict does this; the
        native walker must agree or one datagram builds two different
        metric identities depending on which path it rode."""
        from veneur_tpu.sinks.ssfmetrics import sample_to_metric
        from veneur_tpu.ssf.protos import ssf_pb2

        def pb_len(field, payload: bytes) -> bytes:
            return bytes([(field << 3) | 2, len(payload)]) + payload

        def tag_entry(k: bytes, v: bytes) -> bytes:
            return pb_len(8, pb_len(1, k) + pb_len(2, v))

        sample = (bytes([1 << 3, 0])                    # metric=COUNTER
                  + pb_len(2, b"dup.c")                 # name
                  + tag_entry(b"k", b"v1")
                  + tag_entry(b"k", b"v2")              # last wins
                  + tag_entry(b"a", b"x"))
        span = pb_len(12, sample)
        # the Python decoder collapses to {k: v2, a: x}
        py = ssf_pb2.SSFSpan.FromString(span)
        it = sample_to_metric(py.metrics[0])
        assert it.key.joined_tags == "a:x,k:v2"
        br = self._bridge()
        try:
            assert br.handle_ssf(span) == 1
            keys = br.drain_new_keys()
            assert len(keys) == 1
            assert keys[0][5] == it.key.joined_tags, keys[0]
        finally:
            br.close()

    def test_ssf_invalid_utf8_rejected(self):
        """proto3 string fields must be valid UTF-8: the Python decoder
        rejects the whole message, so the native walker must too — and
        must NOT stage bytes that would later kill the pump when the
        key record is strict-decoded (r5 review find)."""
        def pb_len(field, payload: bytes) -> bytes:
            return bytes([(field << 3) | 2, len(payload)]) + payload

        bad_name = (bytes([1 << 3, 0]) + pb_len(2, b"\xff\xfe"))
        bad_tag = (bytes([1 << 3, 0]) + pb_len(2, b"ok")
                   + pb_len(8, pb_len(1, b"k") + pb_len(2, b"\xc3\x28")))
        br = self._bridge()
        try:
            for sample in (bad_name, bad_tag):
                assert br.handle_ssf(pb_len(12, sample)) == -1
            assert br.stats()["samples"] == 0
            assert br.drain_new_keys() == []
        finally:
            br.close()

    def test_ssf_status_fallback_and_malformed(self):
        from veneur_tpu.ssf.protos import ssf_pb2
        br = self._bridge()
        try:
            sp = ssf_pb2.SSFSpan()
            s = sp.metrics.add()
            s.metric = ssf_pb2.SSFSample.STATUS
            s.name = "chk"
            s.status = ssf_pb2.SSFSample.CRITICAL
            m = sp.metrics.add()
            m.metric = ssf_pb2.SSFSample.COUNTER
            m.name = "c"
            m.value = 1.0
            # whole-datagram fallback: the counter must NOT have been
            # staged natively (no partial landing)
            assert br.handle_ssf(sp.SerializeToString()) == 0
            assert br.stats()["samples"] == 0
            assert br.stats()["ssf_fallbacks"] == 1
            assert br.handle_ssf(b"\xff\xff\xff\xff\x01") == -1
        finally:
            br.close()

    def test_ssf_indicator_timer(self):
        from veneur_tpu.sinks.ssfmetrics import indicator_timer
        from veneur_tpu.ssf.protos import ssf_pb2
        br = self._bridge()
        br.set_indicator_timer("veneur.indicator")
        try:
            sp = ssf_pb2.SSFSpan()
            sp.indicator = True
            sp.error = True
            sp.service = "api"
            sp.start_timestamp = 10**18
            sp.end_timestamp = 10**18 + 12_345_678  # 12.345678 ms
            assert br.handle_ssf(sp.SerializeToString()) == 1
            want = indicator_timer(sp, "veneur.indicator")
            keys = br.drain_new_keys()
            assert len(keys) == 1
            assert keys[0][4] == want.key.name
            assert keys[0][5] == want.key.joined_tags
            bufs = tuple(np.zeros(16, dt) for dt in
                         (np.int32, np.float32, np.float32, np.int32))
            n = br.poll("histo", *bufs)
            assert n == 1
            assert bufs[1][0] == pytest.approx(want.value, rel=1e-6)
        finally:
            br.close()

    def test_native_ssf_stream_and_status_fallback(self):
        """TCP-framed spans ride the native path; a STATUS-carrying
        span falls back per-datagram to the Python pipeline and still
        yields BOTH its embedded sample and the service check."""
        import jax  # noqa: F401
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks.basic import BlackholeMetricSink
        from veneur_tpu.ssf import framing
        from veneur_tpu.ssf.protos import ssf_pb2

        cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                     ssf_listen_addresses=["tcp://127.0.0.1:0"],
                     interval="3600s", hostname="t", native_ingest=True,
                     num_readers=1, tpu_histogram_slots=512,
                     tpu_counter_slots=512, tpu_gauge_slots=64,
                     tpu_set_slots=64)
        srv = Server(cfg, sinks=[BlackholeMetricSink()], plugins=[])
        srv.start()
        try:
            assert srv._native_ssf
            port = srv._listen_socks[0].getsockname()[1]

            def mk(i, status=False):
                sp = ssf_pb2.SSFSpan()
                sp.version = 1
                m = sp.metrics.add()
                m.metric = ssf_pb2.SSFSample.HISTOGRAM
                m.name = "st.lat"
                m.value = float(i)
                m.unit = "ms"
                if status:
                    s = sp.metrics.add()
                    s.metric = ssf_pb2.SSFSample.STATUS
                    s.name = "st.check"
                    s.status = 1
                return sp

            conn = socket.create_connection(("127.0.0.1", port))
            for i in range(30):
                conn.sendall(framing.write_ssf(mk(i)))
            conn.sendall(framing.write_ssf(mk(99, status=True)))

            # native spans count in the bridge; only the Python-path
            # fallback increments spans_received (no double count)
            def total():
                return (srv.native_bridge.stats()["ssf_spans"]
                        + srv.spans_received)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and total() < 31:
                time.sleep(0.02)
            assert total() == 31 and srv.spans_received == 1
            assert srv.drain(20)
            assert srv.native_pump.drain(20)
            res = srv.engines[0].flush(timestamp=1)
            vals = {m.name: m.value for m in res.metrics}
            assert vals["st.lat.count"] == 31.0
            assert any(c.name == "st.check" and c.value == 1.0
                       for c in res.status_metrics)
            st = srv.native_bridge.stats()
            assert st["ssf_spans"] == 30 and st["ssf_fallbacks"] == 1
            conn.close()
        finally:
            srv.stop()

    def test_native_ssf_server_end_to_end(self):
        """Server with native ingest: SSF datagrams land via the C++
        fast path (no Python span objects) and aggregate identically."""
        import jax  # noqa: F401  (conftest pins cpu)
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks.basic import BlackholeMetricSink
        from veneur_tpu.ssf.protos import ssf_pb2

        cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                     ssf_listen_addresses=["udp://127.0.0.1:0"],
                     interval="3600s", hostname="t", native_ingest=True,
                     num_readers=1, tpu_histogram_slots=512,
                     tpu_counter_slots=512, tpu_gauge_slots=64,
                     tpu_set_slots=64)
        srv = Server(cfg, sinks=[BlackholeMetricSink()], plugins=[])
        srv.start()
        try:
            assert srv._native_ssf
            # the native C++ listener owns the SSF socket; no Python
            # thread or socket object exists for it
            port = srv.ssf_native_port
            assert port
            out = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            n = 40
            for i in range(n):
                sp = ssf_pb2.SSFSpan()
                m1 = sp.metrics.add()
                m1.metric = ssf_pb2.SSFSample.HISTOGRAM
                m1.name = "nat.lat"
                m1.value = float(i)
                m1.unit = "ms"
                m2 = sp.metrics.add()
                m2.metric = ssf_pb2.SSFSample.COUNTER
                m2.name = "nat.calls"
                m2.value = 1.0
                out.sendto(sp.SerializeToString(), ("127.0.0.1", port))
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and \
                    srv.native_bridge.stats()["ssf_spans"] < n:
                time.sleep(0.02)
            assert srv.native_bridge.stats()["ssf_spans"] == n
            assert srv.native_pump.drain(20)
            res = srv.engines[0].flush(timestamp=1)
            vals = {m.name: m.value for m in res.metrics}
            assert vals["nat.calls"] == float(n)
            assert vals["nat.lat.count"] == float(n)
        finally:
            srv.stop()

    def test_native_ssf_listener_status_fallback(self):
        """A STATUS-carrying datagram hitting the C++ listener rides
        the ssf_other queue back through the pump into the Python span
        pipeline: the service check must surface AND the embedded
        sample must not be lost or double-landed."""
        import jax  # noqa: F401
        from veneur_tpu.config import Config
        from veneur_tpu.server import Server
        from veneur_tpu.sinks.basic import BlackholeMetricSink
        from veneur_tpu.ssf.protos import ssf_pb2

        cfg = Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                     ssf_listen_addresses=["udp://127.0.0.1:0"],
                     interval="3600s", hostname="t", native_ingest=True,
                     num_readers=1, tpu_histogram_slots=256,
                     tpu_counter_slots=256, tpu_gauge_slots=64,
                     tpu_set_slots=64)
        srv = Server(cfg, sinks=[BlackholeMetricSink()], plugins=[])
        srv.start()
        try:
            out = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sp = ssf_pb2.SSFSpan()
            m = sp.metrics.add()
            m.metric = ssf_pb2.SSFSample.COUNTER
            m.name = "fb.c"
            m.value = 3.0
            s = sp.metrics.add()
            s.metric = ssf_pb2.SSFSample.STATUS
            s.name = "fb.check"
            s.status = 2
            out.sendto(sp.SerializeToString(),
                       ("127.0.0.1", srv.ssf_native_port))
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and \
                    srv.spans_received < 1:
                srv.native_pump.pump_once()
                time.sleep(0.02)
            assert srv.spans_received == 1        # via the Python path
            assert srv.drain(20)
            res = srv.engines[0].flush(timestamp=1)
            vals = {x.name: x.value for x in res.metrics}
            assert vals["fb.c"] == 3.0
            assert any(c.name == "fb.check" and c.value == 2.0
                       for c in res.status_metrics)
            st = srv.native_bridge.stats()
            assert st["ssf_fallbacks"] == 1 and st["ssf_spans"] == 0
        finally:
            srv.stop()


class TestSSFByteFuzz:
    """Byte-level fuzz of the C++ SSF protobuf walker: it parses
    attacker-controlled datagram bytes, so it gets the same treatment
    as the statsd parser — random byte soup and mutated valid spans
    must never crash the process, the verdict must stay in {-1, 0, 1},
    and accepted spans must agree with the Python decoder on what was
    staged (a differential check, not just a no-crash check)."""

    def _mk_valid(self, rng):
        from veneur_tpu.ssf.protos import ssf_pb2
        sp = ssf_pb2.SSFSpan()
        sp.version = 1
        if rng.random() < 0.3:
            sp.indicator = True
            sp.service = "svc"
            sp.start_timestamp = 10**18
            sp.end_timestamp = 10**18 + rng.randrange(10**9)
        for _ in range(rng.randrange(0, 4)):
            s = sp.metrics.add()
            s.metric = rng.choice([0, 1, 2, 3, 4])
            s.name = rng.choice(["m.a", "m.b", "", "x" * 60])
            s.value = rng.uniform(-1e6, 1e6)
            if s.metric == 3:
                s.message = rng.choice(["u1", "ü", ""])
            if rng.random() < 0.4:
                s.unit = rng.choice(["ms", "s", "ns", "µs", "things"])
            if rng.random() < 0.5:
                s.sample_rate = rng.choice([0.0, 0.25, 1.0])
            for _ in range(rng.randrange(0, 3)):
                s.tags[rng.choice("abcd")] = rng.choice(["", "v", "ß"])
            s.scope = rng.randrange(0, 4)
        return sp.SerializeToString()

    def test_ssf_fuzz_differential(self):
        from veneur_tpu.sinks.ssfmetrics import sample_to_metric
        from veneur_tpu.ssf.protos import ssf_pb2

        rng = random.Random(23)
        br = native.NativeBridge(histo_slots=512, counter_slots=512,
                                 gauge_slots=256, set_slots=128,
                                 hll_precision=14, idle_ttl=4,
                                 ring_capacity=1 << 18, max_packet=8192)
        try:
            staged_expect = 0
            for i in range(2500):
                data = self._mk_valid(rng)
                if i % 2:
                    # mutate: flip/truncate/duplicate bytes
                    buf = bytearray(data)
                    for _ in range(rng.randrange(1, 4)):
                        op = rng.randrange(3)
                        if op == 0 and buf:
                            buf[rng.randrange(len(buf))] = \
                                rng.randrange(256)
                        elif op == 1 and buf:
                            del buf[rng.randrange(len(buf)):]
                        else:
                            j = rng.randrange(len(buf) + 1)
                            buf[j:j] = buf[:rng.randrange(6)]
                    data = bytes(buf)
                rc = br.handle_ssf(data)
                assert rc in (-1, 0, 1), rc
                if rc == 1:
                    # differential: the Python decoder must also accept
                    # it, agree there are no STATUS samples, and agree
                    # on how many samples extract
                    sp = ssf_pb2.SSFSpan.FromString(data)
                    assert not any(
                        s.metric == ssf_pb2.SSFSample.STATUS
                        and s.name for s in sp.metrics)
                    # (indicator spans stage no extra timer here — the
                    # timer name is unset on this bridge)
                    staged_expect += sum(
                        1 for s in sp.metrics
                        if sample_to_metric(s) is not None)
            st = br.stats()
            landed = int(st["samples"]) + int(st["drops_no_slot"])
            assert landed == staged_expect, (landed, staged_expect)
        finally:
            br.close()

    def test_ssf_wire_format_parity_cases(self):
        """Targeted wire-format corners where the native walker must
        agree with the Python decoder byte-for-byte: unknown groups
        (accepted when well-formed, rejected when broken), illegal
        field numbers, and enum varints truncating to int32."""
        from veneur_tpu.ssf.protos import ssf_pb2
        base = ssf_pb2.SSFSpan(version=1).SerializeToString()
        br = native.NativeBridge(histo_slots=64, counter_slots=64,
                                 gauge_slots=64, set_slots=64,
                                 hll_precision=14, idle_ttl=4,
                                 ring_capacity=4096, max_packet=8192)
        try:
            cases = [
                (bytes([0x7b, 0x7c]), True),          # empty group
                (bytes([0x7b, 0x08, 0x05, 0x7c]), True),  # inner varint
                (bytes([0x7b, 0x63, 0x64, 0x7c]), True),  # nested
                (bytes([0x7b, 0x6c]), False),         # mismatched end
                (bytes([0x7b]), False),               # unterminated
                (bytes([0x7c]), False),               # bare end group
                (bytes([0x00, 0x00]), False),         # field number 0
            ]
            for extra, py_accepts in cases:
                data = base + extra
                rc = br.handle_ssf(data)
                try:
                    ssf_pb2.SSFSpan.FromString(data)
                    assert py_accepts
                except Exception:
                    assert not py_accepts
                assert (rc >= 0) == py_accepts, (extra.hex(), rc)
            # enum varint truncation: metric = 2^32 + 4 decodes as
            # STATUS in python -> native must fall back, not stage
            sample = (bytes([1 << 3])                 # field 1 varint
                      + bytes([0x84, 0x80, 0x80, 0x80, 0x10])  # 2^32+4
                      + bytes([(2 << 3) | 2, 3]) + b"chk")
            span = bytes([(12 << 3) | 2, len(sample)]) + sample
            py = ssf_pb2.SSFSpan.FromString(span)
            assert py.metrics[0].metric == ssf_pb2.SSFSample.STATUS
            assert br.handle_ssf(span) == 0   # whole-datagram fallback
        finally:
            br.close()

    def test_ssf_random_byte_soup(self):
        rng = random.Random(29)
        br = native.NativeBridge(histo_slots=64, counter_slots=64,
                                 gauge_slots=64, set_slots=64,
                                 hll_precision=14, idle_ttl=4,
                                 ring_capacity=4096, max_packet=8192)
        try:
            for _ in range(3000):
                n = rng.randrange(0, 80)
                data = bytes(rng.randrange(256) for _ in range(n))
                assert br.handle_ssf(data) in (-1, 0, 1)
        finally:
            br.close()


class TestByteFuzz:
    """Raw byte-level fuzz: arbitrary byte soup and mutated valid lines.
    Neither parser may crash, and verdicts/values must stay conformant
    (the structured randomized test above only composes well-formed
    fragments; this one covers delimiter pile-ups, NULs, truncations,
    and high bytes — parse_test.go's malformed-input corner, widened)."""

    def test_byte_soup(self):
        rng = random.Random(7)
        alphabet = b"abc:|#@,.0123456789-+eE\x00\xffg\ns "
        for _ in range(5000):
            n = rng.randrange(0, 60)
            line = bytes(rng.choice(alphabet) for _ in range(n))
            assert_conformant(line)

    def test_mutated_valid_lines(self):
        rng = random.Random(11)
        seeds = [v[0] for v in VALID]
        for _ in range(5000):
            line = bytearray(rng.choice(seeds))
            for _ in range(rng.randrange(1, 4)):
                op = rng.randrange(3)
                if op == 0 and line:                  # flip a byte
                    line[rng.randrange(len(line))] = rng.randrange(256)
                elif op == 1 and line:                # truncate
                    del line[rng.randrange(len(line)):]
                else:                                 # duplicate a span
                    i = rng.randrange(len(line) + 1)
                    line[i:i] = line[:rng.randrange(8)]
            assert_conformant(bytes(line))


class TestDeepGroupNestingParity:
    """Unknown-field group nesting past the native depth cap must FALL
    BACK to the Python decoder (rc 0), not error (rc -1): the
    google.protobuf runtime accepts deeper well-formed groups, so a
    native reject would be a parity divergence (round-5 advisory / vlint NA02).
    The cap itself has one definition on each side, asserted equal."""

    def _bridge(self):
        return native.NativeBridge(histo_slots=64, counter_slots=64,
                                   gauge_slots=64, set_slots=64,
                                   hll_precision=14, idle_ttl=4,
                                   ring_capacity=4096, max_packet=8192)

    @staticmethod
    def _nested_group(depth):
        """An unknown SSFSpan field (15) holding `depth` nested groups:
        START_GROUP tag (15<<3)|3 = 123, END_GROUP (15<<3)|4 = 124."""
        body = b""
        for _ in range(depth):
            body = bytes([123]) + body + bytes([124])
        return body

    def test_cap_constant_parity(self):
        import os
        import re

        from veneur_tpu.ssf import framing
        cpp = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "native", "vtpu_ingest.cpp")
        with open(cpp) as fh:
            m = re.search(r"constexpr int kPbSkipMaxDepth = (\d+);",
                          fh.read())
        assert m, "kPbSkipMaxDepth missing from vtpu_ingest.cpp"
        assert int(m.group(1)) == framing.PB_SKIP_MAX_DEPTH

    def test_deep_nesting_falls_back_not_error(self):
        from veneur_tpu.ssf import framing

        br = self._bridge()
        try:
            deep = self._nested_group(framing.PB_SKIP_MAX_DEPTH + 4)
            assert br.handle_ssf(deep) == 0   # Python path, not -1
            # ...and the Python decoder really does accept it
            framing.parse_ssf_datagram(deep)
            # shallow nesting stays on the native fast path
            shallow = self._nested_group(framing.PB_SKIP_MAX_DEPTH - 4)
            assert br.handle_ssf(shallow) == 1
            # a malformed (unterminated) group is still an error on
            # both paths, at any depth
            unterminated = bytes([123]) * 4
            assert br.handle_ssf(unterminated) == -1
        finally:
            br.close()


class TestTagEntryFieldOmission:
    """A map<string,string> entry may omit field 1 (key) or 2 (value)
    entirely — the raw pointers stay null in the native parser. The
    fixed path clear()s instead of assign(nullptr, 0) (UB; round-5 advisory /
    vlint NA01) and must agree with the Python decoder, which yields ""
    for the omitted half."""

    def test_omitted_key_and_value_parse_like_python(self):
        from veneur_tpu.sinks.ssfmetrics import sample_to_metric
        from veneur_tpu.ssf import framing

        def pb_len(field, payload: bytes) -> bytes:
            return bytes([(field << 3) | 2, len(payload)]) + payload

        br = native.NativeBridge(histo_slots=64, counter_slots=64,
                                 gauge_slots=64, set_slots=64,
                                 hll_precision=14, idle_ttl=4,
                                 ring_capacity=4096, max_packet=8192)
        try:
            # counter sample "c.x" with one tag entry carrying ONLY a
            # value (no key) and one carrying ONLY a key (no value)
            sample = (bytes([1 << 3, 0]) + pb_len(2, b"c.x")
                      + bytes([(3 << 3) | 5]) + b"\x00\x00\x80\x3f"
                      + pb_len(8, pb_len(2, b"justval"))
                      + pb_len(8, pb_len(1, b"justkey")))
            dgram = pb_len(12, sample)
            assert br.handle_ssf(dgram) == 1
            (rec,) = br.drain_new_keys()
            _bank, _mt, _scope, _slot, name, joined = rec
            span = framing.parse_ssf_datagram(dgram)
            m = sample_to_metric(span.metrics[0])
            assert name == m.key.name
            assert joined == m.key.joined_tags
        finally:
            br.close()

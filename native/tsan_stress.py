"""TSAN stress harness for the native ingest bridge.

The concurrency test story for the C++ bridge (SURVEY §5 — the rebuild's
analogue of the reference's `go test -race`): exercise every cross-thread
path at once — SO_REUSEPORT UDP readers, the Python caller's
thread_local staging (two bridges to cover the bridge-scoped memo),
concurrent ring drains (the pump path), new-key/slow-path drains,
interval advancement with eviction, and the gauge's arrival stamp with
the per-reader tallies and the sub-rings' high water (two UDP flows of
gauges into two readers, a direct caller and the SSF path all taking
numbers from the one counter while a ticker reads `stats()`, takes the
high water and asks for a number of its own) — under ThreadSanitizer.

Run (from repo root; deliberately does NOT import jax/pytest — TSAN
makes them unusably slow):

    make -C native tsan
    LD_PRELOAD=$(g++ -print-file-name=libtsan.so) \
    VENEUR_TPU_NATIVE_LIB=native/build/libvtpu_ingest_tsan.so \
    TSAN_OPTIONS="exitcode=66 suppressions=native/tsan.supp" \
    python native/tsan_stress.py

(the suppression covers only glibc's TLS-teardown false positive —
see native/tsan.supp)

Exit 0 + "tsan stress ok" and no "WARNING: ThreadSanitizer" output means
a clean run; TSAN itself exits 66 on a detected race.
"""

import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from veneur_tpu.ingest import native  # noqa: E402

DURATION_S = float(os.environ.get("TSAN_STRESS_S", "5"))


def main() -> int:
    bridges = [native.NativeBridge(
        histo_slots=256, counter_slots=256, gauge_slots=128,
        set_slots=64, hll_precision=10, idle_ttl=2,
        ring_capacity=65536, max_packet=8192) for _ in range(2)]
    port = bridges[0].start_udp("127.0.0.1", 0, n_readers=2)

    stop = threading.Event()

    def sender():
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        i = 0
        while not stop.is_set():
            s.sendto(
                (f"t{i % 97}:{i % 31}|ms|#env:prod\n"
                 f"c{i % 53}:1|c|@0.5\nu:{i % 1009}|s\n"
                 f"g{i % 11}:{i}|g").encode(),
                ("127.0.0.1", port))
            i += 1

    def direct_caller():
        # alternates bridges from ONE thread: the bridge-scoped
        # thread_local memo must never cross-pollinate
        i = 0
        while not stop.is_set():
            bridges[i % 2].handle_packet(
                f"d{i % 41}:{i % 7}|ms\ng:{i}|g".encode())
            i += 1

    # pre-built SSF datagrams (protobuf import is cheap; jax stays out)
    from veneur_tpu.ssf.protos import ssf_pb2

    def mk_ssf(i):
        sp = ssf_pb2.SSFSpan()
        sp.version = 1
        sp.indicator = bool(i % 3 == 0)
        sp.service = "tsan"
        sp.start_timestamp = 10**18
        sp.end_timestamp = 10**18 + i
        m = sp.metrics.add()
        m.metric = [ssf_pb2.SSFSample.COUNTER, ssf_pb2.SSFSample.GAUGE,
                    ssf_pb2.SSFSample.HISTOGRAM,
                    ssf_pb2.SSFSample.SET][i % 4]
        m.name = f"s{i % 37}"
        m.value = float(i % 13)
        if m.metric == ssf_pb2.SSFSample.SET:
            m.message = f"mem{i % 29}"
        if i % 5 == 0:
            m.tags["env"] = "prod"
        return sp.SerializeToString()

    ssf_datagrams = [mk_ssf(i) for i in range(128)]
    # every 8th datagram carries a STATUS sample -> exercises the
    # fallback (ssf_other) queue under concurrency
    for i in range(0, 128, 8):
        sp = ssf_pb2.SSFSpan()
        s = sp.metrics.add()
        s.metric = ssf_pb2.SSFSample.STATUS
        s.name = "tsan.check"
        s.status = 1
        ssf_datagrams[i] = sp.SerializeToString()
    bridges[0].set_indicator_timer("tsan.indicator")
    ssf_port = bridges[0].start_ssf_udp("127.0.0.1", 0, n_readers=2)

    def ssf_caller():
        # the native SSF decode+stage path, concurrent with UDP
        # readers, pollers, and interval ticks on the same bridge
        i = 0
        while not stop.is_set():
            bridges[0].handle_ssf(ssf_datagrams[i % 128])
            i += 1

    def ssf_sender():
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        i = 0
        while not stop.is_set():
            s.sendto(ssf_datagrams[i % 128], ("127.0.0.1", ssf_port))
            i += 1

    import numpy as np

    def pump(br):
        slots = np.zeros(4096, np.int32)
        a = np.zeros(4096, np.float32)
        b = np.zeros(4096, np.float32)
        c = np.zeros(4096, np.int32)
        polled = 0
        while not stop.is_set():
            for bank in ("histo", "counter", "gauge", "set"):
                polled += max(0, br.poll(bank, slots, a, b, c))
            br.drain_new_keys()
            br.drain_other()
            br.drain_ssf_other()
            time.sleep(0.001)
        return polled

    def ticker(br):
        while not stop.is_set():
            for bank in ("histo", "counter", "gauge", "set"):
                br.advance_interval(bank)
            br.slot_scopes("histo")
            st = br.stats()
            assert sum(r["packets"] for r in st["readers"]) \
                <= br.stats()["packets"]
            br.take_ring_high()
            br.next_arrival()
            time.sleep(0.05)

    threads = [threading.Thread(target=f, daemon=True) for f in (
        sender, sender, direct_caller, ssf_caller, ssf_sender,
        lambda: pump(bridges[0]), lambda: pump(bridges[1]),
        lambda: ticker(bridges[0]), lambda: ticker(bridges[1]))]
    for t in threads:
        t.start()
    time.sleep(DURATION_S)
    stop.set()
    for t in threads:
        t.join(5)
    stats = bridges[0].stats()
    for br in bridges:
        br.close()
    assert stats["packets"] > 0 and stats["lines"] > 0, stats
    assert len(stats["readers"]) == 4, stats["readers"]   # 2 statsd, 2 SSF
    assert sum(r["lines"] for r in stats["readers"]) > 0, stats["readers"]
    print(f"tsan stress ok: {stats['lines']} lines through "
          f"{len(threads)} threads")
    return 0


if __name__ == "__main__":
    sys.exit(main())

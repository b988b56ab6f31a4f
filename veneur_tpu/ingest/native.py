"""ctypes bindings + pump for the C++ ingest bridge (native/vtpu_ingest.cpp).

The bridge is the TPU build's native analogue of veneur's ingest front half
(server.go sym: Server.ReadMetricSocket ×num_readers on SO_REUSEPORT
sockets; samplers/parser.go sym: ParseMetric; the digest-sharded dispatch of
worker.go): C++ reader threads parse DogStatsD lines, intern MetricKeys to
device-bank slots, and stage (slot, value, weight) samples in per-bank
rings. Python's job shrinks to polling device-ready batches.

Pieces here:
  * build()/load(): compile (once) and dlopen the shared library.
  * NativeBridge: the raw C API, numpy-typed.
  * BridgeKeyView: presents a bridge bank through the KeyInterner interface
    (active_items / scope_of / key_of / advance_interval / dropped_no_slot)
    so AggregationEngine.flush works unchanged on top of C++ interning.
  * NativePump: the polling thread — drains sample rings into the engine's
    batch-ingest kernels, keeps the slot→key mirrors fresh, and routes
    slow-path lines (events, service checks, CPython-float oddities,
    invalid UTF-8) through the Python parser.
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import threading
import time

import numpy as np

from ..ingest.parser import MetricKey
from ..models.worker import SlotInfo

_BANKS = {"histo": 0, "counter": 1, "gauge": 2, "set": 3}
# `stats()`'s per-bank key counts, each as `<name>_<bank>`: keys holding
# a slot now, and running totals of keys minted into a slot and of keys
# the idle TTL evicted
KEY_STATS = ("keys_live", "keys_interned", "keys_evicted")
# a bank's `ring_high_<bank>`: the most samples its fullest sub-ring
# has held since the flush last took the mark (`take_ring_high`)
RING_HIGH = "ring_high"
# what `reader_stats()` gives of each UDP reader thread, running totals
READER_STATS = ("packets", "lines", "busy_ns")
# the length of the array `vtpu_stats` fills (native/vtpu_ingest.cpp:
# kStatsFields; vlint NA04 holds the two equal) and its fields' names,
# by position. A tuple built once: senders pace on `stats()`
STATS_FIELDS = 36
_STATS_KEYS = ("packets", "lines", "samples", "parse_errors",
               "slow_routed", "drops_no_slot", "ring_drops",
               "other_drops", "pending_other", "ssf_spans",
               "ssf_fallbacks", "ssf_errors", "ssf_other_drops",
               "pending_ssf_other", "ssf_stream_frames",
               "ssf_stream_conns", "ssf_stream_conn_errors",
               "ssf_stream_read_ns", "ssf_stream_wait_ns", "intern_ns",
               *(f"{name}_{bank}" for name in (*KEY_STATS, RING_HIGH)
                 for bank in _BANKS))
assert len(_STATS_KEYS) == STATS_FIELDS
_StatsArray = ctypes.c_uint64 * STATS_FIELDS
_MTYPE_NAMES = ["counter", "gauge", "timer", "histogram", "set"]

P_METRIC, P_ERROR, P_OTHER = 0, 1, 2

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_lib = None
_lib_lock = threading.Lock()


class NativeUnavailable(RuntimeError):
    pass


def build(force: bool = False, name: str = "vtpu_ingest") -> str:
    """Compile native/<name>.cpp into native/build/lib<name>.so if the
    library is missing or older than its source (the bridge by default;
    cluster/wire.py builds `vtpu_wire` through here too); `force`
    rebuilds it regardless (make -B) — a copied tree's mtimes say
    nothing about which source a library on disk was built from.
    Returns its path."""
    src = os.path.join(_NATIVE_DIR, name + ".cpp")
    lib = os.path.join(_NATIVE_DIR, "build", f"lib{name}.so")
    if not os.path.exists(src):
        raise NativeUnavailable(f"source missing: {src}")
    if force or not os.path.exists(lib) or (
            os.path.getmtime(lib) < os.path.getmtime(src)):
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR, f"build/lib{name}.so"]
            + (["-B"] if force else []),
            capture_output=True, text=True)
        if proc.returncode != 0:
            raise NativeUnavailable(
                f"native build failed:\n{proc.stdout}\n{proc.stderr}")
    return lib


def load() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        # Env override: point the whole process at an alternate build of
        # the bridge — how CI runs the native tests under TSAN
        # (`make -C native tsan`, then VENEUR_TPU_NATIVE_LIB=
        # native/build/libvtpu_ingest_tsan.so with libtsan LD_PRELOADed).
        path = os.environ.get("VENEUR_TPU_NATIVE_LIB") or build()
        lib = ctypes.CDLL(path)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.vtpu_create.restype = ctypes.c_void_p
        lib.vtpu_create.argtypes = [ctypes.c_int32] * 8
        lib.vtpu_destroy.argtypes = [ctypes.c_void_p]
        lib.vtpu_handle_packet.argtypes = [ctypes.c_void_p, u8p,
                                           ctypes.c_int32]
        # c_char_p: ctypes passes the bytes object's buffer directly
        # (read-only, zero-copy) — this call is per-datagram on the SSF
        # hot path, where a bytearray+frombuffer wrap costs ~10us/call
        lib.vtpu_handle_ssf.restype = ctypes.c_int32
        lib.vtpu_handle_ssf.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int32]
        lib.vtpu_set_indicator_timer.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_char_p]
        lib.vtpu_start_udp.restype = ctypes.c_int32
        lib.vtpu_start_udp.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_int32, ctypes.c_int32,
                                       ctypes.c_int32]
        lib.vtpu_start_ssf_udp.restype = ctypes.c_int32
        lib.vtpu_start_ssf_udp.argtypes = [ctypes.c_void_p,
                                           ctypes.c_char_p,
                                           ctypes.c_int32, ctypes.c_int32,
                                           ctypes.c_int32, ctypes.c_int32]
        lib.vtpu_start_ssf_stream.restype = ctypes.c_int32
        lib.vtpu_start_ssf_stream.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int32]
        lib.vtpu_drain_ssf_other.restype = ctypes.c_int32
        lib.vtpu_drain_ssf_other.argtypes = [ctypes.c_void_p, u8p,
                                             ctypes.c_int32]
        lib.vtpu_ssf_bound_port.restype = ctypes.c_int32
        lib.vtpu_ssf_bound_port.argtypes = [ctypes.c_void_p]
        lib.vtpu_stop.argtypes = [ctypes.c_void_p]
        lib.vtpu_poll.restype = ctypes.c_int32
        lib.vtpu_poll.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                  ctypes.c_int32, i32p, f32p, f32p, i32p]
        lib.vtpu_drain_new_keys.restype = ctypes.c_int32
        lib.vtpu_drain_new_keys.argtypes = [ctypes.c_void_p, u8p,
                                            ctypes.c_int32]
        lib.vtpu_drain_other.restype = ctypes.c_int32
        lib.vtpu_drain_other.argtypes = [ctypes.c_void_p, u8p,
                                         ctypes.c_int32]
        lib.vtpu_slot_scopes.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                         u8p, ctypes.c_int32]
        lib.vtpu_advance_interval.restype = ctypes.c_int32
        lib.vtpu_advance_interval.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int32]
        lib.vtpu_key_count.restype = ctypes.c_int64
        lib.vtpu_key_count.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.vtpu_intern.restype = ctypes.c_int32
        lib.vtpu_intern.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                    ctypes.c_int32, u8p, ctypes.c_int32,
                                    u8p, ctypes.c_int32]
        lib.vtpu_stats.argtypes = [ctypes.c_void_p, u64p]
        lib.vtpu_take_ring_high.argtypes = [ctypes.c_void_p, u64p]
        lib.vtpu_reader_stats.restype = ctypes.c_int32
        lib.vtpu_reader_stats.argtypes = [ctypes.c_void_p, u64p,
                                          ctypes.c_int32]
        lib.vtpu_next_arrival.restype = ctypes.c_int32
        lib.vtpu_next_arrival.argtypes = [ctypes.c_void_p]
        lib.vtpu_ring_way_capacity.restype = ctypes.c_int32
        lib.vtpu_ring_way_capacity.argtypes = [ctypes.c_void_p]
        lib.vtpu_set_tags_exclude.argtypes = [ctypes.c_void_p, u8p,
                                              ctypes.c_int32]
        lib.vtpu_parse_one.restype = ctypes.c_int32
        lib.vtpu_parse_one.argtypes = [u8p, ctypes.c_int32, u8p,
                                       ctypes.c_int32, i32p]
        lib.vtpu_bench_parse.restype = ctypes.c_double
        lib.vtpu_bench_parse.argtypes = [u8p, ctypes.c_int32,
                                         ctypes.c_int32]
        lib.vtpu_bound_port.restype = ctypes.c_int32
        lib.vtpu_bound_port.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _f32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def parse_one(line: bytes):
    """Stateless conformance parse via the C++ parser.

    Returns (verdict, fields|None) where fields mirror
    parser.parse_metric's result: dict(name, type, joined_tags, digest,
    value, sample_rate, scope)."""
    lib = load()
    buf = np.zeros(4 + len(line) * 2 + 256, np.uint8)
    out_len = ctypes.c_int32(0)
    arr = np.frombuffer(bytearray(line), np.uint8) if line else \
        np.zeros(1, np.uint8)
    v = lib.vtpu_parse_one(_u8(arr), len(line), _u8(buf), len(buf),
                           ctypes.byref(out_len))
    if v != P_METRIC:
        return v, None
    b = buf.tobytes()[:out_len.value]
    mtype, scope = b[0], b[1]
    rate, value = struct.unpack_from("<dd", b, 2)
    (digest,) = struct.unpack_from("<I", b, 18)
    off = 22
    (nl,) = struct.unpack_from("<H", b, off)
    off += 2
    name = b[off:off + nl].decode()
    off += nl
    (tl,) = struct.unpack_from("<H", b, off)
    off += 2
    tags = b[off:off + tl].decode()
    off += tl
    (ml,) = struct.unpack_from("<H", b, off)
    off += 2
    member = b[off:off + ml].decode()
    return v, {
        "name": name, "type": _MTYPE_NAMES[mtype], "joined_tags": tags,
        "digest": digest, "value": member if _MTYPE_NAMES[mtype] == "set"
        else value, "sample_rate": rate, "scope": scope,
    }


class NativeBridge:
    """Owning wrapper over one C++ bridge instance."""

    def __init__(self, histo_slots: int, counter_slots: int,
                 gauge_slots: int, set_slots: int, hll_precision: int = 14,
                 idle_ttl: int = 16, ring_capacity: int = 1 << 20,
                 max_packet: int = 8192):
        self._lib = load()
        self._h = self._lib.vtpu_create(
            histo_slots, counter_slots, gauge_slots, set_slots,
            hll_precision, idle_ttl, ring_capacity, max_packet)
        self.capacities = {"histo": histo_slots, "counter": counter_slots,
                           "gauge": gauge_slots, "set": set_slots}
        # samples one sub-ring holds
        self.ring_way_capacity = int(
            self._lib.vtpu_ring_way_capacity(self._h))
        self._n_readers = 0     # UDP readers started so far
        self._key_buf = np.zeros(1 << 20, np.uint8)
        self._other_buf = np.zeros(1 << 20, np.uint8)
        self._closed = False

    def close(self):
        if not self._closed:
            self._closed = True
            self._lib.vtpu_destroy(self._h)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -------- ingest --------

    def handle_packet(self, data: bytes):
        arr = np.frombuffer(bytearray(data), np.uint8) if data else \
            np.zeros(1, np.uint8)
        self._lib.vtpu_handle_packet(self._h, _u8(arr), len(data))

    def handle_ssf(self, data: bytes) -> int:
        """Decode one SSF span datagram and stage its embedded samples
        natively (sinks/ssfmetrics.py's C++ twin). Returns 1 = handled,
        0 = caller must run the Python span path for this datagram
        (STATUS samples present), -1 = malformed protobuf."""
        return int(self._lib.vtpu_handle_ssf(self._h, data, len(data)))

    def set_indicator_timer(self, name: str) -> None:
        """Enable the indicator-span duration timer
        (indicator_span_timer_name). Call before readers start."""
        self._lib.vtpu_set_indicator_timer(self._h, name.encode())

    def set_tags_exclude(self, names) -> None:
        """Install tags_exclude (config.go sym: Config.TagsExclude) in
        the C++ parser. Must be called BEFORE start_udp — the list is
        read lock-free by the reader threads."""
        packed = "\n".join(names).encode()
        arr = np.frombuffer(bytearray(packed), np.uint8) if packed else \
            np.zeros(1, np.uint8)
        self._lib.vtpu_set_tags_exclude(self._h, _u8(arr), len(packed))

    def start_udp(self, host: str, port: int, n_readers: int,
                  rcvbuf: int = 0) -> int:
        rc = self._lib.vtpu_start_udp(
            self._h, host.encode(), port, n_readers, rcvbuf)
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        self._n_readers += n_readers
        return rc

    def start_ssf_udp(self, host: str, port: int, n_readers: int,
                      rcvbuf: int = 0, max_dgram: int = 16384) -> int:
        """Start native SSF span readers (one datagram = one SSFSpan):
        recvmmsg + decode + ring staging in C++; fallback datagrams
        queue for drain_ssf_other. Returns the bound port."""
        rc = self._lib.vtpu_start_ssf_udp(
            self._h, host.encode(), port, n_readers, rcvbuf, max_dgram)
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        self._n_readers += n_readers
        return rc

    def start_ssf_stream(self, listen_fd: int) -> None:
        """Start the native reader of framed SSF streams on a listening
        stream socket the caller bound (unix:// or tcp://): accept, read,
        frame cutting (ssf/framing.py's layout), decode and ring staging
        in C++, one thread a connection; fallback frames queue for
        drain_ssf_other. The bridge accepts on its own dup of the
        descriptor and closes what it accepted in stop()."""
        rc = self._lib.vtpu_start_ssf_stream(self._h, listen_fd)
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))

    def drain_ssf_other(self) -> list:
        """Fallback SSF datagrams (STATUS-carrying spans) for the
        Python span pipeline, as raw protobuf bytes."""
        out = []
        while True:
            n = self._lib.vtpu_drain_ssf_other(
                self._h, _u8(self._other_buf), len(self._other_buf))
            if n <= 0:
                break
            b = self._other_buf[:n].tobytes()
            off = 0
            while off < n:
                (ln,) = struct.unpack_from("<I", b, off)
                off += 4
                out.append(b[off:off + ln])
                off += ln
        return out

    def stop(self):
        self._lib.vtpu_stop(self._h)

    def bound_port(self) -> int:
        return self._lib.vtpu_bound_port(self._h)

    # -------- draining --------

    def poll(self, bank: str, out_slots, out_a, out_b, out_c) -> int:
        return self._lib.vtpu_poll(
            self._h, _BANKS[bank], len(out_slots), _i32(out_slots),
            _f32(out_a), _f32(out_b), _i32(out_c))

    def drain_new_keys(self):
        """Yield (bank, mtype, scope, slot, name, joined_tags)."""
        out = []
        while True:
            n = self._lib.vtpu_drain_new_keys(
                self._h, _u8(self._key_buf), len(self._key_buf))
            if n <= 0:
                break
            b = self._key_buf.tobytes()[:n]
            off = 0
            while off < n:
                bank, mtype, scope = b[off], b[off + 1], b[off + 2]
                (slot,) = struct.unpack_from("<i", b, off + 3)
                off += 7
                (nl,) = struct.unpack_from("<H", b, off)
                off += 2
                name = b[off:off + nl].decode()
                off += nl
                (tl,) = struct.unpack_from("<H", b, off)
                off += 2
                tags = b[off:off + tl].decode()
                off += tl
                out.append((bank, mtype, scope, slot, name, tags))
            if n < len(self._key_buf) // 2:
                break
        return out

    def drain_other(self):
        """Yield raw slow-path lines (bytes)."""
        out = []
        while True:
            n = self._lib.vtpu_drain_other(
                self._h, _u8(self._other_buf), len(self._other_buf))
            if n <= 0:
                break
            b = self._other_buf.tobytes()[:n]
            off = 0
            while off < n:
                (sl,) = struct.unpack_from("<H", b, off)
                off += 2
                out.append(b[off:off + sl])
                off += sl
            if n < len(self._other_buf) // 2:
                break
        return out

    def slot_scopes(self, bank: str) -> np.ndarray:
        out = np.zeros(self.capacities[bank], np.uint8)
        self._lib.vtpu_slot_scopes(self._h, _BANKS[bank], _u8(out),
                                   len(out))
        return out

    def advance_interval(self, bank: str) -> int:
        return self._lib.vtpu_advance_interval(self._h, _BANKS[bank])

    def key_count(self, bank: str) -> int:
        return self._lib.vtpu_key_count(self._h, _BANKS[bank])

    def intern(self, mtype: str, scope: int, name: str,
               joined_tags: str) -> int:
        """Intern one key through the C++ table (slow path, ssfmetrics
        bridge, global-tier Combine). Returns slot or -1."""
        nb = name.encode()
        tb = joined_tags.encode()
        na = np.frombuffer(bytearray(nb), np.uint8) if nb else \
            np.zeros(1, np.uint8)
        ta = np.frombuffer(bytearray(tb), np.uint8) if tb else \
            np.zeros(1, np.uint8)
        return self._lib.vtpu_intern(
            self._h, _MTYPE_NAMES.index(mtype), scope, _u8(na), len(nb),
            _u8(ta), len(tb))

    def stats(self) -> dict:
        """The bridge's totals by name, and under `"readers"` what
        each UDP reader thread did (`reader_stats`)."""
        # a ctypes array made a call (any thread may ask) and read by
        # one slice: senders pace on this, and a numpy array with its
        # pointer costs twice as much as the call itself
        out = _StatsArray()
        self._lib.vtpu_stats(self._h, out)
        st = dict(zip(_STATS_KEYS, out[:]))
        st["readers"] = self.reader_stats()
        return st

    def reader_stats(self) -> list:
        """[{packets, lines, busy_ns}] of every UDP reader thread in
        the order they were started (the statsd listeners' first):
        running totals of datagrams received, lines parsed and ns from
        a receive's return to its burst staged in the rings. Which
        flows a reader serves is the kernel's choice (SO_REUSEPORT
        hashes the flow), so these say how the load fell."""
        if not self._n_readers:
            return []
        out = (ctypes.c_uint64 * (len(READER_STATS) * self._n_readers))()
        n = self._lib.vtpu_reader_stats(self._h, out, self._n_readers)
        flat, k = out[:], len(READER_STATS)
        return [dict(zip(READER_STATS, flat[k * r:k * r + k]))
                for r in range(n)]

    def take_ring_high(self) -> dict:
        """bank -> the most samples its fullest sub-ring has held since
        the last take (of `ring_way_capacity`), and start again: the
        flush's read, once a tick. `stats()["ring_high_<bank>"]` reads
        the same mark without resetting it."""
        out = (ctypes.c_uint64 * len(_BANKS))()
        self._lib.vtpu_take_ring_high(self._h, out)
        return dict(zip(_BANKS, out[:]))

    def next_arrival(self) -> int:
        """The next number of the bridge-wide arrival order (an int32
        that wraps), for a gauge that reaches the engine by the Python
        path: ordered among the datagrams by when it is processed."""
        return int(self._lib.vtpu_next_arrival(self._h))


class BridgeKeyView:
    """KeyInterner-shaped facade over one bridge bank.

    AggregationEngine.flush consumes active_items()/scope_of()/key_of()/
    advance_interval()/len()/dropped_no_slot; here those are backed by the
    C++ interner plus a host mirror:
      * slot→MetricKey mirror, updated from drain_new_keys()
      * touched mask, updated by the pump from each polled batch (exact
        w.r.t. bank contents — no interval race with the readers)
      * scope snapshot, refreshed at flush time.
    """

    def __init__(self, bridge: NativeBridge, bank: str):
        self.bridge = bridge
        self.bank = bank
        self.capacity = bridge.capacities[bank]
        self.mirror: dict[int, MetricKey] = {}
        self.touched = np.zeros(self.capacity, bool)
        self._scopes = np.zeros(self.capacity, np.uint8)
        # Per-slot SlotInfo holders carrying the engine's flush
        # presentation cache; replaced whenever the C++ interner
        # reassigns a slot to a new key (register()).
        self._holders: dict[int, SlotInfo] = {}
        self.dropped_no_slot = 0
        # keys the idle TTL evicted at the newest advance_interval
        self.evicted = 0

    def __len__(self):
        return self.bridge.key_count(self.bank)

    @property
    def interned(self) -> int:
        """Running total of keys the C++ table minted into a slot."""
        return int(self.bridge.stats()["keys_interned_" + self.bank])

    def lookup(self, key: MetricKey, scope: int) -> int:
        """KeyInterner.lookup parity for the engine's Python entry points
        (engine.process on slow-path lines, import_* Combine staging):
        interns through the C++ table, mirrors, and marks touched.
        Caller holds the engine lock, so mark+dispatch is atomic w.r.t.
        flush."""
        slot = self.bridge.intern(key.type, scope, key.name,
                                  key.joined_tags)
        if slot < 0:
            self.dropped_no_slot += 1
            return -1
        if self.mirror.get(slot) != key:
            self._holders[slot] = SlotInfo(slot, 0, scope)
        self.mirror[slot] = key
        self.touched[slot] = True
        return slot

    def register(self, slot: int, key: MetricKey):
        if self.mirror.get(slot) != key:
            self._holders[slot] = SlotInfo(slot, 0, 0)
        self.mirror[slot] = key

    def mark(self, slots: np.ndarray):
        self.touched[slots] = True

    def refresh_scopes(self):
        self._scopes = self.bridge.slot_scopes(self.bank)

    def key_of(self, slot: int):
        return self.mirror.get(slot)

    def scope_of(self, slot: int) -> int:
        return int(self._scopes[slot])

    def _items(self, slots):
        self.refresh_scopes()
        out = []
        scopes = self._scopes
        for slot in slots:
            key = self.mirror.get(slot)
            if key is not None:
                holder = self._holders.get(slot)
                if holder is None:
                    holder = self._holders[slot] = SlotInfo(slot, 0, 0)
                out.append((key, slot, int(scopes[slot]), holder))
        return out

    def active_items(self):
        return self._items(np.nonzero(self.touched)[0].tolist())

    def all_items(self):
        """Every key the mirror holds, touched or idle — what a FULL
        forward resync ships (KeyInterner.all_items parity). The C++
        interner evicts idle keys without telling the mirror, so a key
        whose slot was freed and not yet reassigned still rides along:
        its bank row is fresh-init, one more zero/empty liveness row."""
        return self._items(list(self.mirror))

    def advance_interval(self):
        self.touched[:] = False
        self.evicted = self.bridge.advance_interval(self.bank)


class NativePump:
    """Polls the bridge and feeds the engine's batch-ingest kernels.

    One pump thread replaces the per-packet Python parse path: it moves
    staged samples bank-by-bank into the XLA scatter programs in
    `batch`-sized chunks (fixed shapes — no recompiles), mirrors new key
    registrations, and hands slow-path lines to `slow_path` (the Python
    parser + engine.process round trip).
    """

    def __init__(self, bridge: NativeBridge, engine, views: dict,
                 slow_path, batch: int = 8192, idle_sleep: float = 0.002,
                 ssf_slow_path=None, stamps=None):
        self.bridge = bridge
        self.engine = engine
        self.views = views
        self.slow_path = slow_path
        # raw SSF datagrams the native listener could not express
        # (STATUS samples); routed to the Python span pipeline
        self.ssf_slow_path = ssf_slow_path
        self.batch = batch
        self.idle_sleep = idle_sleep
        # flight recorder (an observe.StampLog, or None): one
        # `ingest.pump.batch` row per dispatch — poll returned ->
        # ingest_*_batch returned, i.e. copy, engine-lock wait,
        # dispatch — which the server's next flush tick grafts under
        # its `ingest` root. Preallocated; past its budget a tick's
        # later dispatches lengthen the last row, so seconds stay exact
        self.stamps = stamps
        # the bridge's tallies at the last take: the stream readers' ns
        # and the ns inside intern_key's slow path, then each UDP
        # reader's busy ns
        self._taken_ns = {"ssf_stream_read_ns": 0, "intern_ns": 0}
        self._taken_busy: list = []
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # pump_once may be called by both the pump thread and
        # Server.drain(); they share the poll buffers, so cycles are
        # serialized
        self._pump_lock = threading.Lock()
        self._bufs = {
            b: (np.zeros(batch, np.int32), np.zeros(batch, np.float32),
                np.zeros(batch, np.float32), np.zeros(batch, np.int32))
            for b in _BANKS
        }

    def take_stamps(self) -> list:
        """The interval's flight recorder rows, for the flush tick that
        grafts them under its `ingest` root: the pump's batches and,
        where framed SSF streams were read, one `ingest.ssf.read` row
        of the stream readers' seconds inside handle_ssf + staging
        since the last take; where keys were minted, one
        `ingest.intern` row of the seconds inside intern_key's slow
        path; and one `ingest.reader.busy` row for each UDP reader that
        received a datagram, of its seconds from a receive's return to
        the burst staged (rows of one name, one a reader, in no order:
        the longest is the busiest reader's). The readers keep a tally
        and no edges, so each row is laid to end where the pump's last
        batch did."""
        if self.stamps is None:
            return []
        rows = self.stamps.take()
        st = self.bridge.stats()
        end = max((r[2] for r in rows), default=time.monotonic_ns())
        # keys minted while no batch was pumped (the server's own
        # timers, through the Python path) wait for an interval that
        # pumped: an idle interval grafts no `ingest` root
        tallies = [("ingest.ssf.read", "ssf_stream_read_ns")]
        if rows:
            tallies.append(("ingest.intern", "intern_ns"))
        for name, key in tallies:
            total = int(st[key])
            took, self._taken_ns[key] = total - self._taken_ns[key], total
            if took > 0:
                rows.append((name, end - took, end))
        busy = [int(r["busy_ns"]) for r in st["readers"]]
        last = self._taken_busy + [0] * (len(busy) - len(self._taken_busy))
        self._taken_busy = busy
        rows += [("ingest.reader.busy", end - (now - was), end)
                 for now, was in zip(busy, last) if now > was]
        return rows

    def start(self):
        self._thread = threading.Thread(target=self._run, name="native-pump",
                                        daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _run(self):
        import logging
        while not self._stop.is_set():
            try:
                moved = self.pump_once()
            except Exception:
                # a dead pump silently halts ALL aggregation (rings
                # fill, every sample drops); degrade loudly instead
                logging.getLogger(__name__).exception(
                    "pump cycle failed; retrying")
                time.sleep(0.1)
                continue
            if moved == 0:
                time.sleep(self.idle_sleep)

    def pump_once(self) -> int:
        """One poll cycle across all banks; returns items moved."""
        with self._pump_lock:
            moved = 0
            for bank in _BANKS:
                moved += self._pump_bank(bank)
            for line in self.bridge.drain_other():
                self.slow_path(line)
                moved += 1
            if self.ssf_slow_path is not None:
                for payload in self.bridge.drain_ssf_other():
                    self.ssf_slow_path(payload)
                    moved += 1
            return moved

    def drain(self, timeout: float = 10.0) -> bool:
        """Pump until the bridge is empty (deterministic test settling:
        the analogue of Server.drain's queue accounting)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            moved = self.pump_once()
            st = self.bridge.stats()
            if moved == 0 and st["pending_other"] == 0 \
                    and st["pending_ssf_other"] == 0:
                return True
        return False

    def _sync_keys(self):
        for bank_i, mtype, scope, slot, name, tags in \
                self.bridge.drain_new_keys():
            bank = ("histo", "counter", "gauge", "set")[bank_i]
            key = MetricKey(name=name, type=_MTYPE_NAMES[mtype],
                            joined_tags=tags)
            self.views[bank].register(slot, key)
            del scope

    def _pump_bank(self, bank: str) -> int:
        slots, a, b, c = self._bufs[bank]
        stamps = self.stamps
        total = 0
        while True:
            n = self.bridge.poll(bank, slots, a, b, c)
            if n <= 0:
                break
            t0 = time.monotonic_ns()
            if n < self.batch:
                slots[n:] = -1  # pad rows are dropped by the kernels
            # Sync key records BEFORE marking/dispatching this batch: the
            # bridge enqueues a new-key record before the first sample for
            # that key reaches a ring, so every slot in this batch has its
            # mirror entry drainable now — a flush interleaving after
            # dispatch can always resolve slot→key.
            self._sync_keys()
            # COPY before dispatch — the kernels must never see the
            # pump's reused poll buffers. jax's CPU client ZERO-COPIES
            # page-aligned numpy arrays into executable arguments, so an
            # async dispatch still holds the buffer when the next poll
            # overwrites it (observed as both over- and under-counted
            # banks at batch>=32768, where numpy's allocation becomes
            # mmap'd/page-aligned; 8192-wide buffers happened to be
            # heap-allocated, which the runtime copies). The Python
            # staging path has the same contract — _Stage.drain()
            # copies. A fresh copy is ~30us at 32k width vs the ~30ms
            # scatter program it feeds.
            sl = slots.copy()
            view = self.views[bank]
            mark = lambda s_: view.mark(s_)  # runs under the engine lock
            eng = self.engine
            if bank == "histo":
                eng.ingest_histo_batch(sl, a.copy(), b.copy(), count=n,
                                       mark=mark)
            elif bank == "counter":
                eng.ingest_counter_batch(sl, a.copy(), b.copy(), count=n,
                                         mark=mark)
            elif bank == "gauge":
                # `c` is each sample's place in the bridge-wide arrival
                # order: the ways were polled one after another, so
                # the batch's own order says nothing across readers
                eng.ingest_gauge_batch(sl, a.copy(), count=n, mark=mark,
                                       order=c.copy())
            else:
                # astype allocates fresh storage, which satisfies the
                # aliasing contract for the rho column by itself
                eng.ingest_set_batch(sl, c.copy(), a.astype(np.uint8),
                                     count=n, mark=mark)
            if stamps is not None:
                stamps.add("ingest.pump.batch", t0, time.monotonic_ns())
            total += n
            if n < self.batch:
                break
        return total

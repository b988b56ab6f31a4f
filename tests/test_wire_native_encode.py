"""The sender's native pass over an export's columns (ISSUE 47): the
bytes `GrpcForwarder.__call__` hands gRPC must be, chunk by chunk and
byte for byte, `MetricList(metrics=export_to_metrics(export)[i:end],
...).SerializeToString()`, with no `metricpb.Metric` built on the way
(`export_to_metrics` raises inside the forwarder while it sends), and
the receiving half must read every one of those requests natively to
the records `decode_metric_batch` gives.
`wire.export_to_metrics` with protobuf's serializer is the reference;
`native/vtpu_wire.cpp:vtpu_wire_encode` writes the same plain shape
from `wire.export_columns`' arrays. Host code only: nothing here
touches a device.
"""

import numpy as np
import pytest

from tests.test_wire_native_decode import FALLBACK, _agree, _digest
from veneur_tpu import sketches
from veneur_tpu.cluster import forward, wire
from veneur_tpu.cluster.forward import GrpcForwarder, _export_tail
from veneur_tpu.cluster.protos import forward_pb2
from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.models.pipeline import ForwardExport
from veneur_tpu.observe.registry import TelemetryRegistry
from veneur_tpu.resilience import (Egress, ForwardEnvelope,
                                   PartialDeliveryError,
                                   TerminalEgressError)

pytestmark = pytest.mark.skipif(
    wire.native_encode_fn() is None or wire.native_decode_fn() is None,
    reason="native/vtpu_wire.cpp cannot be built here")

STAMP = sketches.DEFAULT_STAMP
ENVELOPE = dict(trace_id=0xABCDEF0123, span_id=77, close_ns=1_700_000_000)


# ---- exports, as a flush, a re-merge or a journal's replay hands them ----

def _f32(entry):
    """A digest as the flush exports it: float32 centroids."""
    key, means, weights, *five = entry
    return (key, np.asarray(means, np.float32),
            np.asarray(weights, np.float32), *five)


def _timers(n, k, seed=3, **kw):
    rng = np.random.default_rng(seed)
    return [_f32(_digest(rng, f"e.lat.t{i:05d}", k, **kw))
            for i in range(n)]


def _four_centroid_timers():
    ex = ForwardExport()
    ex.histograms = _timers(300, 4)
    return ex, {}


def _full_digests():
    """Bodies whose lengths need a two- and a three-byte varint."""
    ex = ForwardExport()
    ex.histograms = [_f32(_digest(np.random.default_rng(n), f"e.full.n{n}",
                                  n)) for n in (64, 5, 512, 1024, 6, 7)]
    return ex, {}


def _histogram_and_timer_keys():
    ex = ForwardExport()
    ex.histograms = (_timers(3, 4, kind="timer")
                     + _timers(3, 4, seed=4, kind="histogram")
                     + _timers(2, 4, seed=5, kind="distribution"))
    return ex, {}


def _empty_and_dead_digests():
    none = np.empty(0, np.float32)
    ex = ForwardExport()
    ex.histograms = [
        (MetricKey("e.empty", "timer", "a:b"), none, none,
         0.0, 0.0, 0.0, 0.0, 0.0),
        # weights at and under zero leave no centroid, as today
        (MetricKey("e.dead", "timer", ""),
         np.array([1.0, 2.0, 3.0], np.float32),
         np.array([0.0, -1.0, np.nan], np.float32),
         1.0, 3.0, 0.0, 0.0, 0.0),
        (MetricKey("e.half", "histogram", "a:b"),
         np.array([1.0, 2.0, 3.0], np.float32),
         np.array([2.0, 0.0, 1.0], np.float32),
         1.0, 3.0, 5.0, 3.0, 7.0 / 3.0)]
    return ex, {}


def _zero_statistics():
    """0.0 is left out, -0.0 is written: proto3 looks at the bits."""
    one = np.ones(1, np.float32)
    ex = ForwardExport()
    for i, five in enumerate((
            (0.0, 0.0, 0.0, 1.0, 0.0), (-0.0, -0.0, -0.0, 1.0, -0.0),
            (0.0, -0.0, 0.0, -0.0, 0.0), (-1.5, 0.0, float("nan"), 2.0,
                                          float("inf")))):
        ex.histograms.append((MetricKey(f"e.zero.{i}", "timer", "k:v"),
                              np.array([0.0, -0.0][i % 2] * one), one,
                              *five))
    return ex, {}


def _counters():
    ex = ForwardExport()
    ex.counters = [(MetricKey(f"e.c{i}", "counter", "a:1"), v)
                   for i, v in enumerate((
                       0.0, -0.0, 1.0, -1.0, 0.5, 1.5, 2.5, -0.5, -1.5,
                       -2.5, 0.49999999999999994, 7.0, 127.0, 128.0,
                       float(2 ** 40), -float(2 ** 40), float(2 ** 62),
                       -float(2 ** 63), 4503599627370497.0))]
    return ex, {}


def _gauges():
    ex = ForwardExport()
    ex.gauges = [(MetricKey(f"e.g{i}", "gauge", ""), v)
                 for i, v in enumerate((
                     0.0, -0.0, 1.5, float("nan"), float("inf"),
                     float("-inf"), 5e-324, 1.7976931348623157e308))]
    return ex, {}


def _sets():
    rng = np.random.default_rng(9)
    ex = ForwardExport()
    ex.sets = [(MetricKey("e.users", "set", "env:prod"),
                rng.integers(0, 30, 1 << 14).astype(np.uint8)),   # 16 KiB
               (MetricKey("e.few", "set", ""),
                rng.integers(0, 7, 1 << 4).astype(np.uint8))]
    return ex, {}


def _ull_sets():
    ex, _ = _sets()
    ex.set_engine = "ull"
    return ex, {}


def _names_and_tags():
    rng = np.random.default_rng(11)
    ex = ForwardExport()
    for name, tags in (
            ("e.none", ""), ("e.one", "env:prod"),
            ("e.three", "a:1,b:2,c:3"), ("e.hole", "a:1,,b:2"),
            ("e.ends", ",a:1,"), ("e.comma", ","),
            ("e.µs.latência.時間", "région:eu-west,名前:値"), ("", "a:b"),
            ("e." + "n" * 200, "t:" + "v" * 300),          # two-byte lengths
            ("e." + "N" * 20_000, "t:" + "V" * 17_000)):   # three-byte
        ex.histograms.append(_f32(_digest(rng, name, 4, tags=tags)))
        ex.counters.append((MetricKey(name, "counter", tags), 3.0))
        ex.gauges.append((MetricKey(name, "gauge", tags), 2.5))
    return ex, {}


def _every_kind():
    """All four kinds, an envelope, the stamp and the advisory rows,
    in chunks of seven: the rows ride the first chunk only."""
    rng = np.random.default_rng(13)
    ex = ForwardExport()
    ex.histograms = _timers(12, 4) + _timers(3, 64, seed=6)
    ex.sets = [(MetricKey(f"e.s{i}", "set", "env:prod"),
                rng.integers(0, 7, 1 << 10).astype(np.uint8))
               for i in range(4)]
    ex.counters = [(MetricKey(f"e.c{i}", "counter", "a:1"), float(i * 17))
                   for i in range(9)]
    ex.gauges = [(MetricKey(f"e.g{i}", "gauge", ""), i / 4)
                 for i in range(5)]
    ex.prefix_sketches = [("e", b"\x01\x02\x03"), ("f.g", bytes(64))]
    ex.kind = "delta"
    return ex, dict(max_per_batch=7, engine_stamp=STAMP,
                    envelope=ForwardEnvelope("sender-e", 41, kind="delta",
                                             **ENVELOPE))


def _wider_than_the_bank():
    """A re-merged or hand-built export: float64 arrays and lists,
    which protobuf takes as they are; one float32 digest among them
    widens with the rest."""
    rng = np.random.default_rng(17)
    ex = ForwardExport()
    ex.histograms = [_digest(rng, f"e.wide.{i}", 4) for i in range(5)]
    key, means, weights, *five = _digest(rng, "e.lists", 6)
    ex.histograms.append((key, means.tolist(), weights.tolist(), *five))
    ex.histograms.append((key, [1, 2], [3, 4], 1, 2, 11, 7, 3.5))
    ex.histograms += _timers(2, 4)
    return ex, {}


def _nothing():
    return ForwardExport(), {}


EXPORTS = {f.__name__.lstrip("_"): f for f in (
    _four_centroid_timers, _full_digests, _histogram_and_timer_keys,
    _empty_and_dead_digests, _zero_statistics, _counters, _gauges, _sets,
    _ull_sets, _names_and_tags, _every_kind, _wider_than_the_bank,
    _nothing)}


# ---- the forwarder, and the reference ----

def _forwarder(send=None, **kw):
    """(forwarder, the requests it handed gRPC, its registry)."""
    sent, reg = [], TelemetryRegistry()
    fwd = GrpcForwarder("127.0.0.1:1", egress=Egress(
        "g", registry=reg, transport=lambda *a, **k: None), **kw)
    fwd._send = send or (lambda req, timeout=None: sent.append(req))
    return fwd, sent, reg


def _reference(export, envelope=None, max_per_batch=10_000,
               engine_stamp=None, centroid_codec="lossless"):
    """The requests as the parent wrote them: protobuf objects, one a
    sketch and one a centroid, `_chunk_bounds` over them, and
    protobuf's serializer."""
    metrics = wire.export_to_metrics(export, codec=centroid_codec)
    bounds = forward._chunk_bounds(metrics, max_per_batch)
    out = []
    for j, (i, end) in enumerate(bounds):
        ml = forward_pb2.MetricList(metrics=metrics[i:end])
        if engine_stamp:
            ml.sketch_engines = engine_stamp
        if j == 0 and export.prefix_sketches:
            wire.prefix_sketches_to_pb(ml, export.prefix_sketches)
        if envelope is not None:
            ml.envelope.CopyFrom(wire.envelope_pb(
                envelope.sender_id, envelope.interval_seq,
                envelope.chunk_offset + j,
                envelope.chunk_count or envelope.chunk_offset + len(bounds),
                trace_id=envelope.trace_id, span_id=envelope.span_id,
                close_ns=envelope.close_ns, kind=envelope.kind))
        out.append(ml.SerializeToString())
    return bounds, out


def _tally(reg):
    return (reg.total("g", "forward.encode_native"),
            reg.total("g", "forward.encode_fallback"))


def _no_objects(monkeypatch):
    def refuse(*_a, **_kw):
        raise AssertionError("a protobuf object a sketch on the native arm")

    monkeypatch.setattr(forward.wire, "export_to_metrics", refuse)


@pytest.mark.parametrize("name", EXPORTS)
def test_the_forwarder_sends_protobufs_bytes_and_builds_no_metric(
        name, monkeypatch):
    export, how = EXPORTS[name]()
    envelope = how.pop("envelope", None)
    bounds, want = _reference(export, envelope, **how)
    fwd, sent, reg = _forwarder(**how)
    n = sum(end - i for i, end in bounds)
    with monkeypatch.context() as m:
        _no_objects(m)
        fwd(export, envelope=envelope)
    assert len(sent) == len(want)
    for got, ref in zip(sent, want):
        assert got == ref
    assert _tally(reg) == (n, 0)
    assert reg.total("g", "forward.bytes") == sum(map(len, want))
    # ... and the receiving half takes every request natively, to the
    # records the reference decode gives
    for raw in sent:
        assert _agree(raw)[FALLBACK] == 0
    # the columns are bounded by what the export holds: no memo a key
    cols = wire.export_columns(export)
    assert cols.counts.tolist() == [len(export.histograms),
                                    len(export.sets),
                                    len(export.counters),
                                    len(export.gauges)]
    assert cols.means.dtype == cols.weights.dtype
    assert cols.means.dtype == (np.float64 if name == "wider_than_the_bank"
                                else np.float32)


_ODD = (0.0, -0.0, 1.0, -1.0, float("nan"), float("inf"), float("-inf"),
        5e-324, 1e308, 0.5, 2.5)


def _random_export(rng):
    """A few of each kind: odd doubles, weights of either sign, keys
    of any letters, empty names and tags among them."""
    def value():
        if rng.random() < 0.4:
            return float(rng.choice(_ODD))
        return float(rng.normal() * 10.0 ** rng.integers(-3, 12))

    def key(kind):
        name = "".join(rng.choice(list("ab.c_é時"), rng.integers(0, 12)))
        tags = ",".join("".join(rng.choice(list("k:v,é"), rng.integers(0, 6)))
                        for _ in range(rng.integers(0, 4)))
        return MetricKey(name, kind, tags)

    ex = ForwardExport()
    dtype = rng.choice([np.float32, np.float64])
    for _ in range(rng.integers(0, 5)):
        k = int(rng.integers(0, 9))
        ex.histograms.append((
            key(str(rng.choice(["timer", "histogram", "other"]))),
            np.array([value() for _ in range(k)], dtype),
            np.array([value() for _ in range(k)], dtype),
            value(), value(), value(), value(), value()))
    for _ in range(rng.integers(0, 3)):
        ex.sets.append((key("set"), rng.integers(
            0, 9, 1 << int(rng.integers(4, 8))).astype(np.uint8)))
    for _ in range(rng.integers(0, 5)):
        v = value()
        ex.counters.append((key("counter"),
                            v if abs(v) < 2.0 ** 63 else 3.5))
    for _ in range(rng.integers(0, 5)):
        ex.gauges.append((key("gauge"), value()))
    return ex


@pytest.mark.parametrize("seed", range(4))
def test_random_exports_are_protobufs_bytes(seed):
    rng = np.random.default_rng(seed)
    fn = wire.native_encode_fn()
    with np.errstate(all="ignore"):
        for _ in range(250):
            export = _random_export(rng)
            metrics = wire.export_to_metrics(export)
            encoded = wire.encode_export(export, fn)
            assert bytes(encoded.data[:encoded.off[-1]]) \
                == forward_pb2.MetricList(metrics=metrics).SerializeToString()
            assert encoded.sizes.tolist() \
                == [m.ByteSize() for m in metrics]


# ---- chunks ----

def _parent_bounds(sizes, max_count, max_bytes):
    """`_chunk_bounds` as the parent had it, over sizes."""
    bounds, start, size = [], 0, 0
    for i, b in enumerate(sizes):
        if i > start and (i - start >= max_count
                          or size + b > max_bytes):
            bounds.append((start, i))
            start, size = i, 0
        size += b
    if start < len(sizes):
        bounds.append((start, len(sizes)))
    return bounds


@pytest.mark.parametrize("seed", range(6))
def test_the_bounds_are_the_greedy_rules(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(0, 400))
    sizes = rng.integers(1, 60, n)
    sizes[rng.random(n) < 0.05] = rng.integers(300, 900)   # ride alone
    for max_count, max_bytes in ((7, 10 ** 9), (10 ** 9, 256), (5, 128),
                                 (1, 1), (10 ** 9, 10 ** 9)):
        assert forward._size_bounds(sizes, max_count, max_bytes) \
            == _parent_bounds(sizes.tolist(), max_count, max_bytes)


def _by_count():
    ex = ForwardExport()
    ex.histograms = _timers(23, 4)
    ex.counters = [(MetricKey(f"e.c{i}", "counter", "a:1"), float(i))
                   for i in range(10)]
    return ex, dict(max_per_batch=8), 5


def _by_bytes():
    """Sets of 16 KiB against the chunk's 3 MiB, and a digest past it
    (a length of four bytes), which rides alone."""
    rng = np.random.default_rng(21)
    ex = ForwardExport()
    ex.histograms = _timers(5, 64) + [
        _f32(_digest(rng, "e.huge", 170_000))] + _timers(2, 4)
    ex.sets = [(MetricKey(f"e.s{i}", "set", "env:prod"),
                rng.integers(0, 30, 1 << 14).astype(np.uint8))
               for i in range(250)]
    ex.gauges = [(MetricKey("e.g", "gauge", ""), 1.0)]
    return ex, {}, 4


@pytest.mark.parametrize("split", (_by_count, _by_bytes),
                         ids=("by_count", "by_bytes"))
def test_chunks_are_the_parents_and_a_tail_resends_their_bytes(
        split, monkeypatch):
    export, how, n_chunks = split()
    envelope = ForwardEnvelope("sender-c", 5, **ENVELOPE)
    bounds, want = _reference(export, envelope, **how)
    assert len(bounds) == n_chunks
    if split is _by_bytes:
        assert bounds[:2] == [(0, 5), (5, 6)]     # the digest, alone
        assert max(map(len, want[2:])) <= forward.MAX_CHUNK_BYTES + 256
    # the whole send: the parent's bounds, the parent's bytes
    fwd, sent, _reg = _forwarder(**how)
    fwd(export, envelope=envelope)
    assert sent == want
    assert [len(forward_pb2.MetricList.FromString(r).metrics)
            for r in sent] == [end - i for i, end in bounds]
    # a failure at chunk j: the tail, and under the envelope's offset
    # chunks j.. with the bytes they had the first time
    for j in (1, n_chunks - 1):
        first = []

        def send(req, timeout=None):
            if len(first) == j:
                raise TerminalEgressError("boom")
            first.append(req)

        fwd, _sent, _reg = _forwarder(send, **how)
        with pytest.raises(PartialDeliveryError) as ei:
            fwd(export, envelope=envelope)
        assert first == want[:j]
        err = ei.value
        assert (err.delivered_chunks, err.chunk_count) == (j, n_chunks)
        tail = _export_tail(export, bounds[j][0])
        assert wire.export_to_metrics(err.undelivered) \
            == wire.export_to_metrics(tail)
        fwd, again, _reg = _forwarder(**how)
        with monkeypatch.context() as m:
            _no_objects(m)
            fwd(err.undelivered, envelope=ForwardEnvelope(
                "sender-c", 5, chunk_offset=j, chunk_count=n_chunks,
                **ENVELOPE))
        assert again == want[j:]


# ---- what falls back, and the count of it ----

def _q16(fwd):
    fwd.centroid_codec = "q16"


def _no_library(fwd):
    fwd._encode = None


@pytest.mark.parametrize("arm", (_q16, _no_library),
                         ids=lambda f: f.__name__.lstrip("_"))
def test_what_the_pass_does_not_write_export_to_metrics_does(arm):
    export, how = _every_kind()
    envelope = how.pop("envelope")
    fwd, sent, reg = _forwarder(**how)
    arm(fwd)
    _bounds, want = _reference(export, envelope,
                               centroid_codec=fwd.centroid_codec, **how)
    fwd(export, envelope=envelope)
    assert sent == want
    assert _tally(reg) == (0, 33)
    packed = [bool(m.histogram.t_digest.packed_centroids)
              for r in sent for m in
              forward_pb2.MetricList.FromString(r).metrics
              if m.WhichOneof("value") == "histogram"]
    assert all(packed) if arm is _q16 else not any(packed)


def test_a_counter_that_is_no_int64_raises_as_it_did():
    """The pass refuses the export; protobuf's setter raises on it, as
    it did before there was a pass, and nothing is sent or counted."""
    export, how = _every_kind()
    envelope = how.pop("envelope")
    fwd, sent, reg = _forwarder(**how)
    key = export.counters[3][0]
    for bad, error in ((float("nan"), ValueError),
                       (float("inf"), OverflowError),
                       (float(2 ** 63), ValueError)):
        export.counters[3] = (key, bad)
        with pytest.raises(error):
            fwd(export, envelope=envelope)
        with pytest.raises(error):
            wire.export_to_metrics(export)
    assert not sent and _tally(reg) == (0, 0)
    export.counters[3] = (key, -float(2 ** 63))
    fwd(export, envelope=envelope)
    assert _tally(reg) == (33, 0)


def test_a_digest_of_uneven_columns_is_export_to_metrics():
    """More means than weights: protobuf's loop cuts to the shorter,
    and the columns hand the export over rather than guess."""
    export, _ = _four_centroid_timers()
    key, means, weights, *five = export.histograms[7]
    export.histograms[7] = (key, means, weights[:3], *five)
    assert wire.export_columns(export) is None
    _bounds, want = _reference(export)
    fwd, sent, reg = _forwarder()
    fwd(export)
    assert sent == want and _tally(reg) == (0, 300)


def test_the_sends_phase_says_who_wrote_the_sketches():
    from veneur_tpu.observe import FlightRecorder
    from veneur_tpu.observe import recorder as rec

    export, how = _every_kind()
    how.pop("envelope")
    fwd, sent, _reg = _forwarder(**how)
    flight = FlightRecorder(capacity=2)
    tick = flight.begin_tick(1)
    token = rec.set_current_tick(tick, -1)
    try:
        fwd(export)
    finally:
        rec.reset_current_tick(token)
    flight.end_tick(tick)
    rows = {}
    for slot in tick._slots[:tick.n]:
        rows.setdefault(slot.name, []).append(slot)
    assert [len(rows[name]) for name in (
        "forward.export", "forward.chunk.plan", "forward.chunk.build",
        "forward.chunk.serialize", "forward.release")] == [
            1, 1, len(sent), len(sent), 1]
    # a hand-built export: its columns were read from its tuples
    assert rows["forward.export"][0].meta == {
        "n_metrics": 33, "encode_native": 33, "encode_fallback": 0,
        "export_direct": 0, "export_tuples": 33}
    assert [s.meta["nbytes"] for s in rows["forward.chunk.serialize"]] \
        == [len(r) for r in sent]
    # none of them of no length: a phase whose ends are one instant is
    # dropped by the benchmark's reader
    assert all(s.t1 > s.t0 for group in rows.values() for s in group)

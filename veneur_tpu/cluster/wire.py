"""Conversions between engine exports and the metricpb wire format.

Parity: the samplers' Metric()/Export() (local side, producing
metricpb.Metric) and Combine() (global side, consuming it) —
samplers/samplers.go, worker.go (sym: Worker.ImportMetricGRPC).
"""

from __future__ import annotations

import base64
import ctypes
import json
import logging
import struct
import threading
from itertools import chain, repeat
from operator import attrgetter, itemgetter
from typing import NamedTuple

import numpy as np

from .. import sketches
from ..ingest.parser import (GLOBAL_ONLY, LOCAL_ONLY, MIXED_SCOPE,
                             MetricKey)
from ..models.pipeline import FlushColumns, ForwardExport
from ..utils.hashing import metric_digest
from .protos import forward_pb2, metric_pb2

log = logging.getLogger("veneur_tpu.cluster.wire")

HLL_VERSION = 1

# ---- sketch-engine/wire-format stamp (ISSUE 10 mixed-fleet safety) --
#
# Every forward request declares which sketch engines produced its
# payloads: "h=<engine>/<wire_ver>,s=<engine>/<wire_ver>" (strings
# minted by sketches.engine_stamp). Carriers: MetricList.sketch_engines
# (field 4) on the forwardrpc arm, the metadata key below on the
# SendMetricsV2 stream, and the header below on jsonmetric-v1. An
# ABSENT stamp means a legacy peer running the default pair; a PRESENT
# stamp that does not match the receiver's engines is rejected loudly
# (counted + per-sender at /debug/fleet) — incompatible register banks
# must never merge silently. Like the envelope/trace codecs, the
# field<->header mapping lives ONLY here (TR01 precedent); the stamp
# string format itself lives in sketches/ (SK01).

SKETCH_HEADER = "X-Veneur-Sketch-Engines"
SKETCH_METADATA_KEY = "veneur-sketch-engines"

# per-prefix Huffman-Bucket cardinality sketches riding to the global
# tier (overload-defense satellite): MetricList.prefix_sketches rows on
# the forwardrpc arm, one base64(json) header on jsonmetric-v1 (capped
# by the SENDER to its top prefixes — headers have practical size
# limits; the pb arm carries the full set)
PREFIX_SKETCH_HEADER = "X-Veneur-Prefix-Sketches"


def sketch_stamp_from_headers(headers) -> str | None:
    v = _header_get(headers, SKETCH_HEADER)
    return str(v) if v else None


def sketch_stamp_from_metric_list(ml) -> str | None:
    return ml.sketch_engines or None


def sketch_stamp_from_metadata(metadata) -> str | None:
    for key, value in metadata or ():
        if key == SKETCH_METADATA_KEY:
            v = value.decode() if isinstance(value, bytes) else value
            return v or None
    return None


def encode_prefix_sketches_header(items) -> str:
    """[(prefix, registers bytes)] -> one base64(json) header value."""
    payload = [[p, base64.b64encode(bytes(r)).decode("ascii")]
               for p, r in items]
    return base64.b64encode(
        json.dumps(payload, separators=(",", ":")).encode()).decode(
        "ascii")


def decode_prefix_sketches_header(value) -> list:
    """Inverse of encode_prefix_sketches_header; tolerant — a malformed
    advisory header decodes to [] (cardinality telemetry must never
    cost an interval), like the trace-context decoders."""
    try:
        payload = json.loads(base64.b64decode(value))
        return [(str(p), base64.b64decode(r)) for p, r in payload]
    except Exception:
        return []


def prefix_sketches_to_pb(ml, items) -> None:
    """Attach [(prefix, registers bytes)] rows to a MetricList."""
    for p, r in items:
        ml.prefix_sketches.add(prefix=str(p), registers=bytes(r))


def prefix_sketches_from_pb(ml) -> list:
    return [(ps.prefix, bytes(ps.registers))
            for ps in ml.prefix_sketches]

# ---- idempotency envelope (exactly-once forward) ----
#
# Every forwarded chunk carries (sender_id, interval_seq, chunk_index,
# chunk_count) so the receiving global tier can drop replays: the
# forwardrpc contract embeds a forwardrpc.Envelope (SendMetrics) or a
# binary metadata header (SendMetricsV2, streaming — there is no
# request message to hang it on); the jsonmetric-v1 contract carries
# the same four fields as HTTP headers. The encode helpers here are
# the ONLY place the field<->header mapping lives; the import server
# and the HTTP /import handler decode through the matching helpers so
# the two directions cannot drift (mirrored-arm parity:
# tests/test_exactly_once.py TestEnvelopeEncodeDecodeParity; pinned
# bytes/headers: tests/test_wire_golden.py).

ENVELOPE_METADATA_KEY = "veneur-envelope-bin"   # gRPC metadata, serialized Envelope
ENVELOPE_SENDER_HEADER = "X-Veneur-Sender-Id"
ENVELOPE_SEQ_HEADER = "X-Veneur-Interval-Seq"
ENVELOPE_CHUNK_HEADER = "X-Veneur-Chunk"        # "<index>/<count>"

# ---- fleet-tracing context (cross-tier span propagation) ----
#
# The sender's flush-tick trace identity (trace_id + root span id) and
# interval-close wall time ride ALONGSIDE the envelope on both forward
# contracts: as Envelope fields 5-7 on the forwardrpc arm (and inside
# the serialized `veneur-envelope-bin` metadata of SendMetricsV2), as
# the two headers below on jsonmetric-v1. Observability only — the
# dedupe/apply path never reads them, a legacy peer ignores them, and
# decode is TOLERANT (malformed trace context degrades to None; it
# must never 400 a request whose envelope is fine). Like the envelope
# codecs, the field<->header mapping lives ONLY here (vlint TR01).

TRACE_HEADER = "X-Veneur-Trace-Id"              # "<trace_id>:<span_id>"
TRACE_CLOSE_HEADER = "X-Veneur-Interval-Close-Ns"


def envelope_pb(sender_id: str, interval_seq: int, chunk_index: int,
                chunk_count: int, trace_id: int = 0, span_id: int = 0,
                close_ns: int = 0, kind: str = "full"):
    return forward_pb2.Envelope(
        sender_id=sender_id, interval_seq=int(interval_seq),
        chunk_index=int(chunk_index), chunk_count=int(chunk_count),
        trace_id=int(trace_id), span_id=int(span_id),
        interval_close_ns=int(close_ns),
        forward_kind=_KIND_TO_PB.get(kind, 0))


def envelope_headers(sender_id: str, interval_seq: int, chunk_index: int,
                     chunk_count: int, trace_id: int = 0,
                     span_id: int = 0, close_ns: int = 0,
                     kind: str = "full") -> dict:
    """The jsonmetric-v1 header encoding of one chunk's envelope (plus
    its trace context, when the sender has one — zero trace_id emits
    no trace headers, and a full-kind chunk emits no kind header,
    keeping legacy header sets byte-identical)."""
    out = {ENVELOPE_SENDER_HEADER: sender_id,
           ENVELOPE_SEQ_HEADER: str(int(interval_seq)),
           ENVELOPE_CHUNK_HEADER:
               f"{int(chunk_index)}/{int(chunk_count)}"}
    if kind == KIND_DELTA:
        out[FORWARD_KIND_HEADER] = KIND_DELTA
    if trace_id:
        out[TRACE_HEADER] = f"{int(trace_id)}:{int(span_id)}"
        if close_ns:
            out[TRACE_CLOSE_HEADER] = str(int(close_ns))
    return out


def _header_get(headers, name):
    v = headers.get(name)
    # urllib's Request stores header keys str.capitalize()d;
    # http.server's Message is case-insensitive already
    return v if v is not None else headers.get(name.capitalize())


def trace_from_headers(headers) -> tuple | None:
    """(trace_id, span_id, close_ns) from jsonmetric-v1 headers, or
    None. Tolerant: a malformed trace context is dropped (None), never
    an error — trace loss must not cost an interval."""
    raw = _header_get(headers, TRACE_HEADER)
    if not raw:
        return None
    try:
        tid, _, sid = str(raw).partition(":")
        if not int(tid):
            # zero trace_id means "no context" on every arm (the pb
            # and metadata decoders skip it the same way) — a peer
            # that stamps headers unconditionally must not produce a
            # dangling-parent span tree here
            return None
        close = _header_get(headers, TRACE_CLOSE_HEADER)
        return (int(tid), int(sid or 0), int(close or 0))
    except ValueError:
        return None


def trace_from_metric_list(ml) -> tuple | None:
    """Trace context of a forwardrpc.MetricList's envelope, or None."""
    if not ml.HasField("envelope") or not ml.envelope.trace_id:
        return None
    e = ml.envelope
    return (e.trace_id, e.span_id, e.interval_close_ns)


def trace_from_metadata(metadata) -> tuple | None:
    """Trace context of a SendMetricsV2 stream's invocation metadata,
    or None (shares the envelope's serialized-Envelope carrier)."""
    for key, value in metadata or ():
        if key == ENVELOPE_METADATA_KEY:
            try:
                e = forward_pb2.Envelope.FromString(value)
            except Exception:
                return None
            if e.trace_id:
                return (e.trace_id, e.span_id, e.interval_close_ns)
            return None
    return None


def envelope_from_headers(headers) -> tuple | None:
    """Decode (sender_id, interval_seq, chunk_index, chunk_count) from a
    mapping with .get (http.server headers, a plain dict). Returns None
    when no envelope was sent (legacy senders — dedupe is skipped);
    raises ValueError on a malformed one (the receiver 400s rather than
    mis-applying it)."""
    sender = _header_get(headers, ENVELOPE_SENDER_HEADER)
    seq = _header_get(headers, ENVELOPE_SEQ_HEADER)
    chunk = _header_get(headers, ENVELOPE_CHUNK_HEADER)
    if sender is None and seq is None and chunk is None:
        return None
    if not sender or seq is None:
        raise ValueError("incomplete forward envelope headers")
    try:
        idx, _, cnt = (chunk or "0/1").partition("/")
        return (sender, int(seq), int(idx), int(cnt or 1))
    except ValueError:
        raise ValueError(f"malformed forward envelope: seq={seq!r} "
                         f"chunk={chunk!r}") from None


def envelope_from_metric_list(ml) -> tuple | None:
    """Envelope of a forwardrpc.MetricList, or None (legacy sender)."""
    if not ml.HasField("envelope"):
        return None
    e = ml.envelope
    return (e.sender_id, e.interval_seq, e.chunk_index, e.chunk_count)


def envelope_from_metadata(metadata) -> tuple | None:
    """Envelope of a SendMetricsV2 stream's invocation metadata
    (an iterable of (key, value) pairs), or None."""
    for key, value in metadata or ():
        if key == ENVELOPE_METADATA_KEY:
            e = forward_pb2.Envelope.FromString(value)
            return (e.sender_id, e.interval_seq, e.chunk_index,
                    e.chunk_count)
    return None

# ---- forward kind: full | delta (ISSUE 13 delta forwarding) ----
#
# Every enveloped chunk declares whether its payload is a FULL export
# (the sender's complete active sketch set — and the gap-baseline
# reset) or a DELTA (only the sketches the dirty-slot bitmap saw
# touched this interval). Carriers: Envelope.forward_kind (field 8;
# 0 = full and every legacy chunk, 1 = delta) on the forwardrpc arm
# and inside the serialized `veneur-envelope-bin` SendMetricsV2
# metadata, and the header below on jsonmetric-v1 — emitted ONLY for
# deltas, so full/legacy header sets stay byte-identical. Decode is
# tolerant: an unknown kind reads as "full" (full skips the gap check
# and merge-applies, which is always sound; a delta misread as full
# can never corrupt state, only skip a belt-check). The field<->header
# mapping lives ONLY here (vlint TR01, same single home as the
# envelope/trace codecs).

FORWARD_KIND_HEADER = "X-Veneur-Forward-Kind"
KIND_FULL = "full"
KIND_DELTA = "delta"
_KIND_TO_PB = {KIND_FULL: 0, KIND_DELTA: 1}

# the wire marker of a delta-over-gap refusal — the receiver puts it
# in the FAILED_PRECONDITION details (gRPC) and the 409 body's
# "error" field (HTTP); the sender-side leaf forwarders match on it
# to translate the refusal into DeltaGapRefusedError. One spelling,
# here, like every other wire literal in this module.
DELTA_GAP_DETAIL = "delta-over-gap"


def forward_kind_from_headers(headers) -> str:
    v = _header_get(headers, FORWARD_KIND_HEADER)
    return KIND_DELTA if v == KIND_DELTA else KIND_FULL


def forward_kind_from_metric_list(ml) -> str:
    if ml.HasField("envelope") and ml.envelope.forward_kind == 1:
        return KIND_DELTA
    return KIND_FULL


def forward_kind_from_metadata(metadata) -> str:
    for key, value in metadata or ():
        if key == ENVELOPE_METADATA_KEY:
            try:
                e = forward_pb2.Envelope.FromString(value)
            except Exception:
                return KIND_FULL
            return KIND_DELTA if e.forward_kind == 1 else KIND_FULL
    return KIND_FULL


_TYPE_TO_PB = {
    "counter": metric_pb2.Counter,
    "gauge": metric_pb2.Gauge,
    "histogram": metric_pb2.Histogram,
    "timer": metric_pb2.Timer,
    "set": metric_pb2.Set,
}
_PB_TO_TYPE = {v: k for k, v in _TYPE_TO_PB.items()}
_PB_TO_TYPE[metric_pb2.Timer] = "timer"


def encode_hll(registers: np.ndarray) -> bytes:
    """The HLL register wire row (code byte 1 — unchanged since the
    pre-registry tree). The engine-tagged codec lives in sketches/;
    this name is kept for the HLL arm's callers and golden tests."""
    return sketches.encode_set_registers("hll", registers)


def decode_hll(data: bytes) -> np.ndarray:
    engine_id, regs = sketches.decode_set_registers(data)
    if engine_id != "hll":
        raise ValueError("bad HLL payload")
    return regs


def encode_set_payload(engine_id: str, registers) -> bytes:
    """Engine-tagged set-register wire row (byte 0 selects the engine:
    1 = HLL, 2 = ULL — see sketches.encode_set_registers)."""
    return sketches.encode_set_registers(engine_id, registers)


def decode_set_payload(data: bytes) -> tuple:
    """-> (engine_id, registers u8[m]); ValueError on unknown codes."""
    return sketches.decode_set_registers(data)


# ---- quantized-centroid wire row (ISSUE 13, vlint WC01) ----
#
# The q16 codec: one histogram's centroid list packed as
#
#     u32 n | f32 lo | f32 hi | n x u16 q_mean | n x varint q_weight
#
# (little-endian). Means are affine-quantized onto a per-list 16-bit
# grid between lo = min(means) and hi = max(means): the endpoints are
# exact, interior points carry <= (hi-lo)/65535/2 absolute error — the
# bounded mean-perturbation t-digest quantile bounds tolerate (arxiv
# 1902.04023; the exact count/sum/min/max ride the untouched TDigest
# scalar fields either way). Weights are 1/8-fixed-point varints,
# floored at 1/8 so a live centroid can never quantize to dead:
# q_w = max(1, round(w * 8)). -0.0 canonicalizes to +0.0 (the affine
# grid has one zero); non-finite means REFUSE (ValueError) and the
# caller falls back to the lossless row for that metric — quantization
# is a bytes optimization, never a correctness gamble. The math lives
# ONLY here (vlint WC01 flags the wire-key literals elsewhere), and
# the JSON carrier key is "centroids_q16" (base64 of this row).

Q16_JSON_KEY = "centroids_q16"
_Q16_GRID = 65535
_Q16_WSCALE = 8.0
_Q16_HEAD = struct.Struct("<Iff")


def _varint(n: int) -> bytes:
    """Scalar reference encoder — kept as the golden twin the
    vectorized block below is regression-pinned against."""
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


# varint byte-length thresholds: a value v needs 1 + #(thresholds <= v)
# bytes; 9 thresholds (2^7 .. 2^63) cover the full u64 range (10 bytes
# max — the q16 encoder refuses weights >= 2^63 anyway)
_VARINT_THRESHOLDS = (np.uint64(1) << (np.uint64(7) * np.arange(
    1, 10, dtype=np.uint64)))


def _varint_block(vals: np.ndarray) -> bytes:
    """Varint-encode a u64 vector in one numpy pass — BYTE-IDENTICAL
    to b"".join(_varint(int(v)) for v in vals), regression-pinned by
    tests/test_wire_golden.py. The scalar join was the q16 encoder's
    Python-loop floor at 100k sketches (ISSUE 13 follow-up: the bytes
    were won, this wins the CPU back): per element it paid a Python
    loop iteration, an int() unbox, and a bytearray grow; here the
    byte count, the 7-bit chunks, and the continuation bits all
    compute columnwise and the row materializes with one tobytes()."""
    v = np.ascontiguousarray(vals, np.uint64)
    if v.size == 0:
        return b""
    nbytes = 1 + (v[:, None] >= _VARINT_THRESHOLDS[None, :]).sum(
        axis=1)
    total = int(nbytes.sum())
    ends = np.cumsum(nbytes)
    idx = np.repeat(np.arange(v.size), nbytes)        # value per byte
    pos = (np.arange(total)
           - np.repeat(ends - nbytes, nbytes)).astype(np.uint64)
    chunk = (v[idx] >> (np.uint64(7) * pos)) & np.uint64(0x7F)
    cont = (np.arange(total) + 1) != np.repeat(ends, nbytes)
    out = (chunk | (cont.astype(np.uint64) << np.uint64(7))) \
        .astype(np.uint8)
    # vlint: disable=DR02 reason=the q16 varint WIRE block (weight
    # fixed-point bytes, not a bank leaf); single-homed here per WC01
    return out.tobytes()


def _read_varint(data: bytes, off: int):
    shift = result = 0
    while True:
        if off >= len(data):
            raise ValueError("truncated q16 varint")
        b = data[off]
        off += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            return result, off
        shift += 7
        if shift > 63:
            raise ValueError("oversized q16 varint")


def encode_q16_centroids(means, weights) -> bytes:
    """Pack (means, weights) into the q16 row. Zero/negative-weight
    entries are dropped (mirroring the lossless row); non-finite means
    raise ValueError (caller falls back to lossless for that metric)."""
    means = np.asarray(means, np.float64)
    weights = np.asarray(weights, np.float64)
    live = weights > 0
    means, weights = means[live], weights[live]
    if means.size and not np.isfinite(means).all():
        raise ValueError("non-finite centroid mean refuses q16")
    if weights.size and (not np.isfinite(weights).all()
                         or float(weights.max()) * _Q16_WSCALE >= 2**63):
        # an inf/NaN (or varint-overflowing) weight would cast to 0 in
        # the fixed-point step and silently DELETE a live centroid —
        # refuse instead, like non-finite means (caller falls back to
        # the lossless row for this metric)
        raise ValueError("non-finite/oversized centroid weight "
                         "refuses q16")
    n = int(means.size)
    if n == 0:
        return _Q16_HEAD.pack(0, 0.0, 0.0)
    # + 0.0 canonicalizes -0.0 endpoints (one zero on the grid)
    lo = float(means.min()) + 0.0
    hi = float(means.max()) + 0.0
    span = hi - lo
    if span > 0:
        q = np.rint((means - lo) * (_Q16_GRID / span))
        q = np.clip(q, 0, _Q16_GRID).astype(np.uint16)
    else:
        q = np.zeros(n, np.uint16)
    qw = np.maximum(1, np.rint(weights * _Q16_WSCALE)).astype(np.uint64)
    return (_Q16_HEAD.pack(n, lo, hi)
            # vlint: disable=DR02 reason=the q16 centroid WIRE row
            # (deliberately lossy quantized means, not a bank leaf);
            # single-homed here per WC01
            + q.astype("<u2").tobytes()
            + _varint_block(qw))


def decode_q16_centroids(data: bytes):
    """Inverse of encode_q16_centroids -> (means f32[n], weights
    f32[n]); ValueError on truncation (poison-pill reject path)."""
    if len(data) < _Q16_HEAD.size:
        raise ValueError("truncated q16 centroid row")
    n, lo, hi = _Q16_HEAD.unpack_from(data, 0)
    off = _Q16_HEAD.size
    if len(data) < off + 2 * n:
        raise ValueError("truncated q16 mean block")
    # vlint: disable=DR02 reason=inverse of the q16 wire row above —
    # same single-homed wire codec, not a bank-leaf byte move
    q = np.frombuffer(data, "<u2", n, off).astype(np.float64)
    off += 2 * n
    weights = np.empty(n, np.float64)
    for i in range(n):
        w, off = _read_varint(data, off)
        weights[i] = w / _Q16_WSCALE
    span = float(hi) - float(lo)
    if span > 0:
        means = lo + q * (span / _Q16_GRID)
    else:
        means = np.full(n, float(lo), np.float64)
    return means.astype(np.float32), weights.astype(np.float32)


def histogram_wire_fragment(means, weights, codec: str = "lossless"):
    """The jsonmetric-v1 centroid carrier for one histogram: the
    lossless [[mean, weight], ...] list under "centroids", or the q16
    row base64'd under "centroids_q16" (falling back to lossless for a
    list the codec refuses). Single home of both JSON spellings."""
    if codec == "q16":
        try:
            return {Q16_JSON_KEY: base64.b64encode(
                encode_q16_centroids(means, weights)).decode("ascii")}
        except ValueError:
            pass
    return {"centroids": [[float(m), float(w)]
                          for m, w in zip(means, weights)]}


def histogram_centroids_from_json(h: dict):
    """-> (means, weights) from a jsonmetric-v1 histogram dict,
    whichever carrier it used. The q16 arm raises ValueError on a
    malformed row (the import path 400s the body, like any other
    decode failure)."""
    packed = h.get(Q16_JSON_KEY)
    if packed is not None:
        return decode_q16_centroids(base64.b64decode(packed))
    cents = h.get("centroids", [])
    means = np.array([c[0] for c in cents], np.float32)
    weights = np.array([c[1] for c in cents], np.float32)
    return means, weights


def td_centroids(td):
    """-> (means f32, weights f32) of a metricpb TDigest, whichever
    row it carries — the ONE decode point for both representations
    (import apply, export inversion, journal recovery)."""
    if len(td.packed_centroids):
        return decode_q16_centroids(td.packed_centroids)
    return (np.array([c.mean for c in td.centroids], np.float32),
            np.array([c.weight for c in td.centroids], np.float32))


def export_to_metrics(export: ForwardExport,
                      codec: str = "lossless") -> list:
    """ForwardExport -> [metricpb.Metric] (the flush-side
    serialization). `codec` selects the centroid row: "lossless" (the
    default — repeated Centroid messages, bit-exact) or "q16" (the
    packed quantized row above; per-metric fallback to lossless when a
    list refuses quantization)."""
    out = []
    for key, means, weights, vmin, vmax, vsum, count, recip in (
            export.histograms):
        m = metric_pb2.Metric(
            name=key.name, tags=_split_tags(key.joined_tags),
            type=_TYPE_TO_PB.get(key.type, metric_pb2.Histogram),
            scope=metric_pb2.Global)
        td = m.histogram.t_digest
        td.min, td.max, td.sum = float(vmin), float(vmax), float(vsum)
        td.count, td.reciprocal_sum = float(count), float(recip)
        packed = None
        if codec == "q16":
            try:
                packed = encode_q16_centroids(means, weights)
            except ValueError:
                packed = None
        if packed is not None:
            td.packed_centroids = packed
        else:
            for mean, w in zip(np.asarray(means), np.asarray(weights)):
                if w > 0:
                    td.centroids.add(mean=float(mean), weight=float(w))
        out.append(m)
    for key, regs in export.sets:
        m = metric_pb2.Metric(name=key.name,
                              tags=_split_tags(key.joined_tags),
                              type=metric_pb2.Set, scope=metric_pb2.Global)
        m.set.hyper_log_log = encode_set_payload(export.set_engine, regs)
        out.append(m)
    for key, value in export.counters:
        m = metric_pb2.Metric(name=key.name,
                              tags=_split_tags(key.joined_tags),
                              type=metric_pb2.Counter,
                              scope=metric_pb2.Global)
        m.counter.value = int(round(value))
        out.append(m)
    for key, value in export.gauges:
        m = metric_pb2.Metric(name=key.name,
                              tags=_split_tags(key.joined_tags),
                              type=metric_pb2.Gauge,
                              scope=metric_pb2.Global)
        m.gauge.value = float(value)
        out.append(m)
    return out


def export_from_metrics(metrics) -> ForwardExport:
    """[metricpb.Metric] -> ForwardExport — the exact inverse of
    export_to_metrics over its image (entry order preserved per type,
    so the concatenated wire order survives a roundtrip and replayed
    chunk indices keep lining up). Counter values come back as the
    wire's int64; callers that need exact floats (the durability
    journal) carry them in a side channel."""
    export = ForwardExport()
    for m in metrics:
        key = metric_key_of(m)
        which = m.WhichOneof("value")
        if which == "histogram":
            td = m.histogram.t_digest
            means, weights = td_centroids(td)
            export.histograms.append(
                (key, means, weights, td.min, td.max, td.sum, td.count,
                 td.reciprocal_sum))
        elif which == "set":
            eng_id, regs = decode_set_payload(m.set.hyper_log_log)
            export.sets.append((key, regs))
            export.set_engine = eng_id
        elif which == "counter":
            export.counters.append((key, float(m.counter.value)))
        elif which == "gauge":
            export.gauges.append((key, float(m.gauge.value)))
    return export


def metric_key_of(m) -> MetricKey:
    mtype = _PB_TO_TYPE.get(m.type, "histogram")
    return MetricKey(name=m.name, type=mtype,
                     joined_tags=",".join(sorted(m.tags)))


def metric_digest_of(m) -> int:
    """The worker-sharding digest of a metricpb.Metric: FNV-1a over
    its key's name, type and joined tags, as the packet path's."""
    key = metric_key_of(m)
    return metric_digest(key.name, key.type, key.joined_tags)


def apply_metric_to_engine(engine, m) -> None:
    """metricpb.Metric -> engine.import_* (the Combine dispatch)."""
    key = metric_key_of(m)
    which = m.WhichOneof("value")
    if which == "histogram":
        td = m.histogram.t_digest
        means, weights = td_centroids(td)
        engine.import_histogram(key, means, weights, td.min, td.max,
                                td.sum, td.count, td.reciprocal_sum)
    elif which == "set":
        eng_id, regs = decode_set_payload(m.set.hyper_log_log)
        engine.import_set(key, regs, eng_id)
    elif which == "counter":
        engine.import_counter(key, float(m.counter.value))
    elif which == "gauge":
        engine.import_gauge(key, m.gauge.value)


# kinds of a decoded import record (decode_metric_batch)
IMPORT_HISTOGRAM, IMPORT_SET, IMPORT_COUNTER, IMPORT_GAUGE = range(4)


def decode_metric_batch(pbs) -> tuple:
    """One request's metricpb.Metrics, decoded in one pass for
    AggregationEngine.import_list -> (records, means, weights,
    rejected). A record is `(kind, key, at, ...)` in wire order, `at`
    the metric's position in `pbs` (whoever re-routes or rejects a
    record reads the object there):

      IMPORT_HISTOGRAM  (.., start, stop, min, max, sum, count, recip)
                        its centroids are means[start:stop] /
                        weights[start:stop] of the batch's two flat
                        f32 columns (one numpy conversion a batch,
                        whichever row each digest carried)
      IMPORT_SET        (.., registers u8[m], engine_id)
      IMPORT_COUNTER    (.., value as float)
      IMPORT_GAUGE      (.., value)

    Decoding is identical to apply_metric_to_engine's, metric by
    metric: a payload that one rejects the other rejects, as
    `(pb, exception)` in `rejected`, and the rest of the batch
    decodes. A metric with no value set yields no record.

    This is the reference: BatchDecoder reads the same records from a
    request's bytes in one native pass, and comes here for every
    metric and every batch that pass does not take."""
    records, rejected = [], []
    fm: list = []
    fw: list = []
    for at, m in enumerate(pbs):
        start = len(fm)
        try:
            key = metric_key_of(m)
            which = m.WhichOneof("value")
            if which == "histogram":
                td = m.histogram.t_digest
                if len(td.packed_centroids):
                    means, weights = decode_q16_centroids(
                        td.packed_centroids)
                    fm.extend(means.tolist())
                    fw.extend(weights.tolist())
                else:
                    for c in td.centroids:
                        fm.append(c.mean)
                        fw.append(c.weight)
                records.append((IMPORT_HISTOGRAM, key, at, start, len(fm),
                                td.min, td.max, td.sum, td.count,
                                td.reciprocal_sum))
            elif which == "set":
                eng_id, regs = decode_set_payload(m.set.hyper_log_log)
                records.append((IMPORT_SET, key, at, regs, eng_id))
            elif which == "counter":
                records.append((IMPORT_COUNTER, key, at,
                                float(m.counter.value)))
            elif which == "gauge":
                records.append((IMPORT_GAUGE, key, at, m.gauge.value))
        except Exception as e:
            del fm[start:], fw[start:]
            rejected.append((m, e))
    return (records, np.array(fm, np.float32), np.array(fw, np.float32),
            rejected)


class DigestBlock(NamedTuple):
    """A request's histograms as columns, a row a digest in wire order:
    what the import stage takes (AggregationEngine._stage_import_records),
    so that no object is built a sketch between the request's bytes and
    the landing's device matrix. Digest `j` is the metric `pbs[at[j]]`
    under `keys[j]`, its centroids `means[start[j]:stop[j]]` and
    `weights[start[j]:stop[j]]` of the two flat f32 columns (which may
    hold stretches no digest names), its exact statistics the column
    `stats[:, j]`: min, max, sum, count, reciprocal sum."""
    at: np.ndarray          # int64[n]
    keys: list              # MetricKey a digest
    start: np.ndarray       # int64[n]
    stop: np.ndarray        # int64[n]
    stats: np.ndarray       # float64[5, n]
    means: np.ndarray       # float32[...]
    weights: np.ndarray     # float32[...]


def digest_block(records, means, weights) -> tuple:
    """decode_metric_batch's records as the stage takes them ->
    (DigestBlock of the histograms, the other records in their order):
    the one way from the reference's records into the stage."""
    digests = [r for r in records if r[0] == IMPORT_HISTOGRAM]
    others = [r for r in records if r[0] != IMPORT_HISTOGRAM]
    n = len(digests)
    table = np.array([r[2:] for r in digests], np.float64).reshape(n, 8)
    spans = table[:, :3].astype(np.int64)
    return DigestBlock(spans[:, 0], [r[1] for r in digests], spans[:, 1],
                       spans[:, 2], np.ascontiguousarray(table[:, 3:].T),
                       means, weights), others


# ---- the native pass over a request's bytes (native/vtpu_wire.cpp) ----

# a row's kind beyond the four of a record: no member of the `value`
# oneof set (no record), and a metric the pass is not sure of (read
# from the parsed message by decode_metric_batch)
_ROW_NONE, _ROW_FALLBACK = 4, 5
# the least a Centroid with a weight takes on the wire (tag, length,
# tag, fixed64), which sizes the columns the pass fills: a request
# whose centroids all have one fits, and a metric that would overrun
# them falls back like any other the pass is not sure of
_CENTROID_WIRE_BYTES = 11

_native_lock = threading.Lock()
_native_fn = None       # vtpu_wire_decode; False where it cannot be had
_native_encode = None   # vtpu_wire_encode, the same way

_DECODE_ARGS = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_int64] + [ctypes.c_void_p] * 4 + [ctypes.c_int64]
_ENCODE_ARGS = [ctypes.c_void_p] * 14 + [ctypes.c_int64, ctypes.c_void_p,
                                         ctypes.c_int64] + [ctypes.c_void_p] * 2


def native_decode_fn():
    """libvtpu_wire's decoder, built (at most once a checkout, like
    the ingest bridge and through the same `make`) and loaded at most
    once a process; None where the library cannot be built or loaded,
    said once in the log: every batch is then decoded in Python. The
    call keeps the interpreter's lock (ctypes.PyDLL): the walk is a
    tenth of a request's decode, 0.1 ms of a 1,110-sketch request,
    and a worker that let the lock go for it would wait behind the
    handler threads, a switch interval at a time, to have it back."""
    global _native_fn
    if _native_fn is None:
        with _native_lock:
            if _native_fn is None:
                _native_fn = _load_native(
                    ctypes.PyDLL, "vtpu_wire_decode", _DECODE_ARGS,
                    "imports decode in Python") or False
    return _native_fn or None


def native_encode_fn():
    """libvtpu_wire's encoder (encode_export), had as the decoder is;
    None where it cannot be, or where the library on disk is one from
    before it had the entry point: every forward is then written by
    export_to_metrics. The call lets the interpreter's lock go
    (ctypes.CDLL): it is one call a send and writes the whole export,
    tens of milliseconds of a 100,000-sketch tick, in which the sink
    threads and an import worker of the same process go on."""
    global _native_encode
    if _native_encode is None:
        with _native_lock:
            if _native_encode is None:
                _native_encode = _load_native(
                    ctypes.CDLL, "vtpu_wire_encode", _ENCODE_ARGS,
                    "forwards are written in Python") or False
    return _native_encode or None


def _load_native(dll, entry, argtypes, instead):
    from ..ingest import native
    try:
        fn = getattr(dll(native.build(name="vtpu_wire")), entry)
    except (native.NativeUnavailable, OSError, AttributeError) as e:
        log.warning("libvtpu_wire unavailable, %s: %s", instead, e)
        return None
    fn.restype = ctypes.c_int64
    fn.argtypes = argtypes
    return fn


class BatchDecoder:
    """decode_metric_batch for an engine's import worker: the same
    digests, records and rejects, value for value and in wire order,
    read from the request's serialized bytes in one native pass where
    the batch still has them (gRPC SendMetrics) and the library loaded.
    The request's histograms leave as the pass's own columns (a
    DigestBlock), never as an object a sketch; its sets, counters and
    gauges as decode_metric_batch's records.
    A metric's MetricKey is found by the raw bytes of its name,
    tags and type in a dictionary, filled on a miss by metric_key_of of
    the parsed metric (sorting, joining and UTF-8 stay the parser's),
    emptied whole when it passes `max_keys` entries: the one loop over
    a request's sketches that is left. A metric the pass
    is not sure of is decoded by decode_metric_batch from its parsed
    message and takes its place by its position; a batch without bytes,
    or a list the pass cannot walk, goes there whole (digest_block)."""

    def __init__(self, max_keys: int):
        self.max_keys = max(1, int(max_keys))
        self._keys: dict = {}

    def decode(self, pbs, raw=None, at=None) -> tuple:
        """-> (block, records, rejected, counts): the histograms as a
        DigestBlock, the other metrics as decode_metric_batch's
        records. `raw` is the
        serialized forwardrpc.MetricList that `pbs` was parsed from and
        `at` the positions of `pbs` in its `metrics` (ascending; None
        for all of them); `counts` is (native, fallback, key hits, key
        misses), sketches by the path that decoded them."""
        if isinstance(raw, bytes) and len(pbs):
            fn = native_decode_fn()
            out = fn and self._decode_native(fn, pbs, raw, at)
            if out:
                return out
        records, means, weights, rejected = decode_metric_batch(pbs)
        return (*digest_block(records, means, weights), rejected,
                (0, len(pbs), 0, 0))

    def _decode_native(self, fn, pbs, raw, at):
        n = len(pbs)
        cap = len(raw) // _CENTROID_WIRE_BYTES + 1
        # a column each (C order): rows are picked out of them by kind
        ints = np.empty((5, n), np.int64)
        floats = np.empty((5, n), np.float64)
        means = np.empty(cap, np.float32)
        weights = np.empty(cap, np.float32)
        if at is not None:
            at = np.ascontiguousarray(at, np.int64)
            if at.shape != (n,):
                return None
        total = fn(raw, len(raw), None if at is None else at.ctypes.data,
                   n, ints.ctypes.data, floats.ctypes.data,
                   means.ctypes.data, weights.ctypes.data, cap)
        if total < 0:
            return None
        kinds = ints[0]
        # a key a sketch the pass read, in wire order
        keyed = np.flatnonzero(kinds < _ROW_NONE)
        keys, found = self._keys, []
        misses = 0
        for i, ko, kl in zip(keyed.tolist(), ints[1, keyed].tolist(),
                             ints[2, keyed].tolist()):
            kb = raw[ko:ko + kl]
            key = keys.get(kb)
            if key is None:
                misses += 1
                if len(keys) >= self.max_keys:
                    keys.clear()
                key = keys[kb] = metric_key_of(pbs[i])
            found.append(key)
        # the histograms: rows of the pass's columns, the columns at
        # their own size (the stage keeps views of them)
        digests = kinds[keyed] == IMPORT_HISTOGRAM
        which = np.flatnonzero(digests)
        rows = keyed[which]
        block = DigestBlock(
            rows, list(map(found.__getitem__, which.tolist())),
            ints[3, rows], ints[4, rows], floats[:, rows],
            means[:total].copy(), weights[:total].copy())
        # the others: a record each
        records, rejected = [], []
        which = np.flatnonzero(~digests)
        rows = keyed[which]
        for j, i, kind, a, b, f0 in zip(
                which.tolist(), rows.tolist(), kinds[rows].tolist(),
                ints[3, rows].tolist(), ints[4, rows].tolist(),
                floats[0, rows].tolist()):
            if kind == IMPORT_COUNTER:
                records.append((IMPORT_COUNTER, found[j], i, float(a)))
            elif kind == IMPORT_GAUGE:
                records.append((IMPORT_GAUGE, found[j], i, f0))
            else:
                try:
                    eng_id, regs = decode_set_payload(raw[a:a + b])
                except Exception as e:
                    rejected.append((i, pbs[i], e))
                else:
                    records.append((IMPORT_SET, found[j], i, regs, eng_id))
        fallback = np.flatnonzero(kinds == _ROW_FALLBACK).tolist()
        if fallback:
            block = self._with_fallback(pbs, fallback, block, records,
                                        rejected)
        return (block, records, [(pb, e) for _i, pb, e in rejected],
                (n - len(fallback), len(fallback), len(keyed) - misses,
                 misses))

    @staticmethod
    def _with_fallback(pbs, fallback, block, records, rejected):
        """The metrics at positions `fallback`, which the pass left to
        decode_metric_batch, put where they lay: their records and
        rejects (by position) into the two lists, their digests into
        `block`'s rows, the centroids behind the pass's own. Returns
        the block."""
        recs, means, weights, rej = decode_metric_batch(
            [pbs[i] for i in fallback])
        where = {id(pbs[i]): i for i in fallback}
        rejected += [(where[id(pb)], pb, e) for pb, e in rej]
        rejected.sort(key=itemgetter(0))
        more, others = digest_block(
            [r[:2] + (fallback[r[2]],) + r[3:] for r in recs], means, weights)
        records += others
        records.sort(key=itemgetter(2))
        if not more.keys:
            return block
        at = np.concatenate([block.at, more.at])
        order = np.argsort(at, kind="stable")
        keys, behind = block.keys + more.keys, len(block.means)
        return DigestBlock(
            at[order],
            list(map(keys.__getitem__, order.tolist())),
            np.concatenate([block.start, more.start + behind])[order],
            np.concatenate([block.stop, more.stop + behind])[order],
            np.concatenate([block.stats, more.stats], axis=1)[:, order],
            np.concatenate([block.means, means]),
            np.concatenate([block.weights, weights]))


# ---- the sender's native pass: an export's columns to its bytes ----

class ExportColumns(NamedTuple):
    """A ForwardExport as flat arrays in wire order (histograms, sets,
    counters, gauges: metric i of export_to_metrics is entry i here):
    what vtpu_wire_encode takes. Every `*_off` is int64, starts at 0
    and has one entry more than the things it bounds. The seam at
    which a flush hands over its own columns (ForwardExport.columns, a
    FlushColumns) instead of a tuple a key: the value arrays are then
    the flush's as they are, and only the keys' names and tags are
    joined here."""
    counts: np.ndarray      # int64[4]: histograms, sets, counters, gauges
    names: bytes            # every key's name, UTF-8, end to end
    name_off: np.ndarray
    tags: bytes             # every key's joined_tags, the same way
    tag_off: np.ndarray
    types: np.ndarray       # uint8[n_h]: metricpb.Histogram or .Timer
    cent_off: np.ndarray    # [n_h + 1] into means / weights
    means: np.ndarray       # float32 (float64 where an export held wider)
    weights: np.ndarray     # the same dtype, weights <= 0 still among them
    stats: np.ndarray       # float64[n_h, 5]: min max sum count recip
    sets: bytes             # encode_set_payload's rows, end to end
    set_off: np.ndarray
    counters: np.ndarray    # float64[n_c], rounded as round() rounds
    gauges: np.ndarray      # float64[n_g]


def _offsets(lengths, n: int) -> np.ndarray:
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.fromiter(lengths, np.int64, n), out=off[1:])
    return off


def _utf8_column(strings: list) -> tuple:
    """-> (the strings' UTF-8 end to end, int64 offsets[n + 1]). ASCII,
    which is nearly every key, is encoded once, whole."""
    joined = "".join(strings)
    if joined.isascii():
        return joined.encode("ascii"), _offsets(map(len, strings),
                                                len(strings))
    encoded = [s.encode("utf-8") for s in strings]
    return b"".join(encoded), _offsets(map(len, encoded), len(encoded))


_KEY_NAME = attrgetter("name")
_KEY_TYPE = attrgetter("type")
_KEY_TAGS = attrgetter("joined_tags")


def _columns_from_tuples(export: ForwardExport):
    """The FlushColumns of an export that has only its entry lists (one
    built by hand, re-merged from a spill, read back from a journal, or
    changed since its flush), or None for one whose centroid lists the
    columns cannot hold as they are (a digest with more means than
    weights or the reverse, which export_to_metrics cuts to the
    shorter). No loop over centroids and none that builds an object a
    sketch: a few passes over the tuples' fields."""
    hs = export.histograms
    n_h = len(hs)
    means, weights = [e[1] for e in hs], [e[2] for e in hs]
    if list(map(len, means)) != list(map(len, weights)):
        return None
    return FlushColumns(
        tuple([e[0] for e in entries] for entries in (
            hs, export.sets, export.counters, export.gauges)),
        _offsets(map(len, means), n_h),
        np.concatenate(means) if n_h else np.empty(0, np.float32),
        np.concatenate(weights) if n_h else np.empty(0, np.float32),
        np.fromiter(chain.from_iterable(e[3:8] for e in hs), np.float64,
                    5 * n_h).reshape(n_h, 5),
        [regs for _key, regs in export.sets],
        np.array([v for _key, v in export.counters], np.float64),
        np.array([v for _key, v in export.gauges], np.float64))


def export_columns(export: ForwardExport):
    """ForwardExport -> ExportColumns, or None for an export the
    columns cannot hold (_columns_from_tuples). An export that still
    has its flush's columns hands them over untouched, and no entry
    list is read or built; any other is read from its tuples."""
    cols = export.columns
    if cols is None:
        cols = _columns_from_tuples(export)
        if cols is None:
            return None
    keys = list(chain.from_iterable(cols.keys))
    names, name_off = _utf8_column(list(map(_KEY_NAME, keys)))
    tags, tag_off = _utf8_column(list(map(_KEY_TAGS, keys)))
    n_h = len(cols.keys[0])
    means, weights = cols.means, cols.weights
    if not (means.dtype == weights.dtype == np.float32):
        # a re-merged or hand-built export: float() of each, as the
        # protobuf setter takes it
        means = means.astype(np.float64)
        weights = weights.astype(np.float64)
    payloads = [encode_set_payload(export.set_engine, regs)
                for regs in cols.regs]
    return ExportColumns(
        np.array(list(map(len, cols.keys)), np.int64),
        names, name_off, tags, tag_off,
        np.fromiter(map(_TYPE_TO_PB.get, map(_KEY_TYPE, cols.keys[0]),
                        repeat(metric_pb2.Histogram)), np.uint8, n_h),
        cols.cent_off, means, weights, cols.stats,
        b"".join(payloads), _offsets(map(len, payloads), len(payloads)),
        np.rint(cols.counters), cols.gauges)


# what a metric can take on the wire beyond its key's, its set's and its
# centroids' bytes: the lengths and tags of every level, type, scope, a
# digest's five statistics (45) or a counter's varint (11)
_METRIC_WIRE_SLACK = 96
_CENTROID_WIRE_MAX = 20     # tag, length, two tagged doubles


class EncodedMetrics(NamedTuple):
    """An export's sketches as the bytes of a MetricList's `metrics`
    (encode_export): metric i of export_to_metrics(export) lies at
    data[off[i]:off[i + 1]], a length-delimited field 1, and
    `sizes[i]` is what its ByteSize() would say."""
    data: memoryview
    off: list               # n + 1 offsets into `data`
    sizes: np.ndarray       # int64[n]


def encode_export(export: ForwardExport, fn):
    """ForwardExport -> EncodedMetrics through `fn` (native_encode_fn's
    entry point), without a protobuf object a sketch or a centroid;
    None for an export the columns or the pass will not take, which
    the caller then hands to export_to_metrics: the pass refuses a
    counter that is no int64, on which that raises as it always has.
    Lossless centroids only: the q16 row is export_to_metrics'."""
    cols = export_columns(export)
    if cols is None:
        return None
    n = int(cols.counts.sum())
    cap = (_METRIC_WIRE_SLACK * n + len(cols.names) + len(cols.tags)
           + 6 * cols.tags.count(b",") + len(cols.sets)
           + _CENTROID_WIRE_MAX * len(cols.means))
    buf = np.empty(cap, np.uint8)
    off = np.empty(n + 1, np.int64)
    sizes = np.empty(n, np.int64)
    if fn(*(a if isinstance(a, bytes) else a.ctypes.data for a in cols),
          int(cols.means.dtype == np.float64), buf.ctypes.data, cap,
          off.ctypes.data, sizes.ctypes.data) < 0:
        return None
    return EncodedMetrics(memoryview(buf), off.tolist(), sizes)


def _split_tags(joined: str) -> list[str]:
    return joined.split(",") if joined else []

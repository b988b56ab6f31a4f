"""Golden wire-byte fixtures for the cross-process wire formats.

The fixtures are HAND-CONSTRUCTED from the .proto field numbers with a
minimal protobuf encoder (below) — independent of the protobuf runtime —
and pinned in both directions:

  encode: message built through the public helpers serializes to exactly
          these bytes;
  decode: these bytes parse back to the expected values.

This is the strongest byte-level conformance we can assert while the
reference mount is empty (SURVEY.md): the field numbers match the
reference's samplers/metricpb/metric.proto (sym: metricpb.Metric),
forwardrpc/forward.proto (sym: MetricList) and ssf/sample.proto
(sym: SSFSpan) as recorded in our .proto files; when the mount is
populated, re-verifying reduces to diffing the .proto files, and any
field-number fix will fail these tests loudly instead of silently
changing the wire.
"""

import struct

import numpy as np

from veneur_tpu.cluster import wire
from veneur_tpu.cluster.protos import forward_pb2, metric_pb2
from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.models.pipeline import ForwardExport
from veneur_tpu.ssf import framing
from veneur_tpu.ssf.protos import ssf_pb2


# --- minimal hand encoder (protobuf wire spec, nothing else) ---

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(field: int, wt: int) -> bytes:
    return _varint((field << 3) | wt)


def _ld(field: int, payload: bytes) -> bytes:      # length-delimited
    return _tag(field, 2) + _varint(len(payload)) + payload


def _s(field: int, text: str) -> bytes:
    return _ld(field, text.encode())


def _vi(field: int, n: int) -> bytes:              # varint scalar
    return _tag(field, 0) + _varint(n)


def _d(field: int, x: float) -> bytes:             # 64-bit double
    return _tag(field, 1) + struct.pack("<d", x)


def _f(field: int, x: float) -> bytes:             # 32-bit float
    return _tag(field, 5) + struct.pack("<f", x)


# --- metricpb.Metric: all four value arms + status_check ---

def test_metric_counter_golden_bytes():
    export = ForwardExport()
    export.counters.append((MetricKey("c.x", "counter", "a:b,c:d"), 42.0))
    (m,) = wire.export_to_metrics(export)
    golden = (
        _s(1, "c.x")                    # name = 1
        + _s(2, "a:b") + _s(2, "c:d")   # tags = 2 (repeated)
        # type = 3 is Counter = 0 -> omitted (proto3 default)
        + _ld(4, _vi(1, 42))            # counter = 4 { value = 1 }
        + _vi(8, 2)                     # scope = 8 (Global = 2)
    )
    assert m.SerializeToString() == golden
    back = metric_pb2.Metric.FromString(golden)
    assert back.name == "c.x" and list(back.tags) == ["a:b", "c:d"]
    assert back.WhichOneof("value") == "counter"
    assert back.counter.value == 42
    assert back.scope == metric_pb2.Global


def test_metric_gauge_golden_bytes():
    export = ForwardExport()
    export.gauges.append((MetricKey("g", "gauge", ""), -1.5))
    (m,) = wire.export_to_metrics(export)
    golden = (
        _s(1, "g")
        + _vi(3, 1)                     # type = 3 (Gauge = 1)
        + _ld(5, _d(1, -1.5))           # gauge = 5 { value = 1 (double) }
        + _vi(8, 2)
    )
    assert m.SerializeToString() == golden
    back = metric_pb2.Metric.FromString(golden)
    assert back.gauge.value == -1.5


def test_metric_histogram_golden_bytes():
    export = ForwardExport()
    export.histograms.append(
        (MetricKey("h", "histogram", "k:v"),
         np.array([1.0, 3.0]), np.array([2.0, 1.0]),
         1.0, 3.0, 5.0, 3.0, 7.0 / 6.0))
    (m,) = wire.export_to_metrics(export)
    centroids = (_ld(1, _d(1, 1.0) + _d(2, 2.0))    # centroid{mean,weight}
                 + _ld(1, _d(1, 3.0) + _d(2, 1.0)))
    tdigest = (centroids
               + _d(2, 1.0)            # min = 2
               + _d(3, 3.0)            # max = 3
               + _d(4, 5.0)            # sum = 4
               + _d(5, 3.0)            # count = 5
               + _d(6, 7.0 / 6.0))     # reciprocal_sum = 6
    golden = (
        _s(1, "h") + _s(2, "k:v")
        + _vi(3, 2)                    # type = Histogram = 2
        + _ld(6, _ld(1, tdigest))      # histogram = 6 { t_digest = 1 }
        + _vi(8, 2)
    )
    assert m.SerializeToString() == golden
    back = metric_pb2.Metric.FromString(golden)
    td = back.histogram.t_digest
    assert [c.mean for c in td.centroids] == [1.0, 3.0]
    assert td.count == 3.0 and td.reciprocal_sum == 7.0 / 6.0


def test_metric_set_golden_bytes():
    regs = np.zeros(16, np.uint8)      # precision 4
    regs[3] = 9
    export = ForwardExport()
    export.sets.append((MetricKey("s", "set", ""), regs))
    (m,) = wire.export_to_metrics(export)
    payload = bytes([wire.HLL_VERSION, 4]) + regs.tobytes()
    golden = (
        _s(1, "s")
        + _vi(3, 3)                    # type = Set = 3
        + _ld(7, _ld(1, payload))      # set = 7 { hyper_log_log = 1 }
        + _vi(8, 2)
    )
    assert m.SerializeToString() == golden
    back = metric_pb2.Metric.FromString(golden)
    assert np.array_equal(wire.decode_hll(back.set.hyper_log_log), regs)


def test_metric_status_check_golden_bytes():
    # built directly (exports never carry checks; importsrv can)
    m = metric_pb2.Metric(name="ck", type=metric_pb2.StatusCheck)
    m.status_check.status = 2.0
    m.status_check.message = "crit"
    golden = (
        _s(1, "ck")
        + _vi(3, 4)                    # type = StatusCheck = 4
        + _ld(9, _d(1, 2.0) + _s(2, "crit"))   # status_check = 9
    )
    assert m.SerializeToString() == golden
    assert metric_pb2.Metric.FromString(golden).status_check.message == \
        "crit"


def test_forwardrpc_metric_list_golden_bytes():
    export = ForwardExport()
    export.counters.append((MetricKey("c", "counter", ""), 7.0))
    metrics = wire.export_to_metrics(export)
    ml = forward_pb2.MetricList(metrics=metrics)
    inner = _s(1, "c") + _ld(4, _vi(1, 7)) + _vi(8, 2)
    golden = _ld(1, inner)             # metrics = 1 (repeated Metric)
    assert ml.SerializeToString() == golden
    assert forward_pb2.MetricList.FromString(
        golden).metrics[0].counter.value == 7


# --- idempotency envelope: both forward arms ---

def _golden_envelope_bytes():
    # forwardrpc.Envelope{sender_id="s1", interval_seq=7,
    #                     chunk_index=1, chunk_count=3}
    return (_s(1, "s1")                # sender_id = 1
            + _vi(2, 7)                # interval_seq = 2 (uint64)
            + _vi(3, 1)                # chunk_index = 3 (uint32)
            + _vi(4, 3))               # chunk_count = 4 (uint32)


def test_envelope_golden_bytes():
    env = wire.envelope_pb("s1", 7, 1, 3)
    golden = _golden_envelope_bytes()
    assert env.SerializeToString() == golden
    back = forward_pb2.Envelope.FromString(golden)
    assert (back.sender_id, back.interval_seq, back.chunk_index,
            back.chunk_count) == ("s1", 7, 1, 3)


def test_send_metrics_envelope_bearing_metric_list_golden_bytes():
    """The SendMetrics arm: MetricList grew `envelope = 2`; an
    envelope-bearing payload produced by the ACTUAL forwarder stamping
    path must serialize to exactly these bytes — and a pre-envelope
    payload must still parse (HasField false)."""
    from veneur_tpu.cluster.forward import GrpcForwarder
    from veneur_tpu.resilience import Egress, ForwardEnvelope

    export = ForwardExport()
    export.counters.append((MetricKey("c", "counter", ""), 7.0))
    sent = []
    fwd = GrpcForwarder("127.0.0.1:1",
                        egress=Egress("g", transport=lambda *a, **k: None))
    fwd._send = lambda req, timeout=None: sent.append(req)
    fwd(export, envelope=ForwardEnvelope("s1", 7, chunk_offset=1,
                                         chunk_count=3))
    (data,) = sent          # the forwarder hands gRPC serialized bytes
    inner = _s(1, "c") + _ld(4, _vi(1, 7)) + _vi(8, 2)
    golden = (_ld(1, inner)                       # metrics = 1
              + _ld(2, _golden_envelope_bytes()))  # envelope = 2
    assert data == golden
    back = forward_pb2.MetricList.FromString(golden)
    assert back.HasField("envelope")
    assert back.envelope.sender_id == "s1"
    assert back.envelope.interval_seq == 7
    # legacy payload (no envelope) still parses with HasField false
    legacy = _ld(1, inner)
    assert not forward_pb2.MetricList.FromString(
        legacy).HasField("envelope")


def test_send_metrics_v2_envelope_metadata_golden():
    """The SendMetricsV2 arm is a client stream of bare Metrics — the
    envelope rides as binary gRPC metadata. Pin the key and the value
    bytes so neither side can drift."""
    assert wire.ENVELOPE_METADATA_KEY == "veneur-envelope-bin"
    value = wire.envelope_pb("s1", 7, 1, 3).SerializeToString()
    assert value == _golden_envelope_bytes()
    md = [(wire.ENVELOPE_METADATA_KEY, value)]
    assert wire.envelope_from_metadata(md) == ("s1", 7, 1, 3)


def test_jsonmetric_v1_envelope_headers_golden():
    """The jsonmetric-v1 arm: envelope fields ride as pinned X-Veneur-*
    headers in a pinned format."""
    headers = wire.envelope_headers("s1", 7, 1, 3)
    assert headers == {"X-Veneur-Sender-Id": "s1",
                       "X-Veneur-Interval-Seq": "7",
                       "X-Veneur-Chunk": "1/3"}
    assert wire.envelope_from_headers(headers) == ("s1", 7, 1, 3)
    # absent chunk header defaults to the single-chunk interval
    assert wire.envelope_from_headers(
        {"X-Veneur-Sender-Id": "s1",
         "X-Veneur-Interval-Seq": "7"}) == ("s1", 7, 0, 1)


# --- quantized-centroid wire row (q16, ISSUE 13) ---

def _golden_q16_row():
    # means [1.0, 3.0] weights [2.0, 1.0]: lo=1.0 hi=3.0, grid points
    # 0 and 65535 (endpoints are exact), weights 1/8-fixed -> 16, 8
    return (struct.pack("<Iff", 2, 1.0, 3.0)
            + struct.pack("<HH", 0, 65535)
            + bytes([16]) + bytes([8]))


def test_q16_row_golden_bytes():
    row = wire.encode_q16_centroids(np.array([1.0, 3.0]),
                                    np.array([2.0, 1.0]))
    assert row == _golden_q16_row()
    means, weights = wire.decode_q16_centroids(row)
    np.testing.assert_array_equal(means, np.float32([1.0, 3.0]))
    np.testing.assert_array_equal(weights, np.float32([2.0, 1.0]))


def test_q16_metric_golden_bytes():
    """The pb carrier: TDigest.packed_centroids = 7 replaces the
    repeated Centroid list when the sender's codec is q16, and
    td_centroids decodes either representation."""
    export = ForwardExport()
    export.histograms.append(
        (MetricKey("h", "histogram", "k:v"),
         np.array([1.0, 3.0]), np.array([2.0, 1.0]),
         1.0, 3.0, 5.0, 3.0, 7.0 / 6.0))
    (m,) = wire.export_to_metrics(export, codec="q16")
    tdigest = (_d(2, 1.0) + _d(3, 3.0) + _d(4, 5.0) + _d(5, 3.0)
               + _d(6, 7.0 / 6.0)
               + _ld(7, _golden_q16_row()))   # packed_centroids = 7
    golden = (
        _s(1, "h") + _s(2, "k:v")
        + _vi(3, 2)
        + _ld(6, _ld(1, tdigest))
        + _vi(8, 2)
    )
    assert m.SerializeToString() == golden
    back = metric_pb2.Metric.FromString(golden)
    means, weights = wire.td_centroids(back.histogram.t_digest)
    np.testing.assert_array_equal(means, np.float32([1.0, 3.0]))
    np.testing.assert_array_equal(weights, np.float32([2.0, 1.0]))
    # a lossless metric still decodes through the same entry point
    (m_ll,) = wire.export_to_metrics(export)
    assert len(m_ll.histogram.t_digest.packed_centroids) == 0
    means2, _w2 = wire.td_centroids(m_ll.histogram.t_digest)
    np.testing.assert_array_equal(means2, np.float32([1.0, 3.0]))


def test_q16_roundtrip_within_quantization_bound():
    import random
    rng = random.Random(17)
    for _trial in range(100):
        n = rng.randrange(1, 80)
        means = np.float32([rng.uniform(-1e6, 1e6) for _ in range(n)])
        weights = np.float32(
            [rng.choice([1.0, 0.5, 3.25, 2.0, 1e5]) for _ in range(n)])
        m2, w2 = wire.decode_q16_centroids(
            wire.encode_q16_centroids(means, weights))
        span = float(means.max() - means.min())
        # mean error <= half a grid step (+ f32 rounding headroom)
        assert np.abs(m2 - means).max() <= span / 65535 / 2 + abs(
            span) * 1e-6 + 1e-3
        # weight error <= half a 1/8 step
        assert np.abs(w2 - weights).max() <= 1 / 16 + 1e-6
        # endpoints land exactly on the grid
        assert np.float32(m2.min()) == np.float32(means.min())
        assert np.float32(m2.max()) == np.float32(means.max())


def test_q16_edges_nan_negzero_empty():
    # empty list -> 12-byte header, decodes to empty arrays
    row = wire.encode_q16_centroids([], [])
    assert row == struct.pack("<Iff", 0, 0.0, 0.0)
    m, w = wire.decode_q16_centroids(row)
    assert m.size == 0 and w.size == 0
    # -0.0 canonicalizes to +0.0 (the affine grid has one zero)
    m, w = wire.decode_q16_centroids(
        wire.encode_q16_centroids([-0.0, -0.0], [1.0, 1.0]))
    assert not np.signbit(m).any() and (m == 0.0).all()
    # NaN/inf means REFUSE (caller falls back to the lossless row) —
    # and export_to_metrics actually does fall back per metric
    import pytest
    with pytest.raises(ValueError):
        wire.encode_q16_centroids([np.nan], [1.0])
    with pytest.raises(ValueError):
        wire.encode_q16_centroids([np.inf, 1.0], [1.0, 1.0])
    # a non-finite (or varint-overflowing) WEIGHT refuses too — the
    # fixed-point cast would silently delete the centroid otherwise
    with pytest.raises(ValueError):
        wire.encode_q16_centroids([1.0, 2.0], [np.inf, 2.0])
    with pytest.raises(ValueError):
        wire.encode_q16_centroids([1.0], [1e19])
    export = ForwardExport()
    export.histograms.append(
        (MetricKey("h", "histogram", ""),
         np.array([np.inf, 1.0]), np.array([1.0, 2.0]),
         1.0, 1.0, 1.0, 3.0, 0.0))
    (m_pb,) = wire.export_to_metrics(export, codec="q16")
    td = m_pb.histogram.t_digest
    assert len(td.packed_centroids) == 0 and len(td.centroids) == 2
    # zero-weight entries drop, like the lossless row
    m, w = wire.decode_q16_centroids(
        wire.encode_q16_centroids([5.0, 6.0], [0.0, 2.0]))
    np.testing.assert_array_equal(m, np.float32([6.0]))
    # truncated rows refuse loudly
    with pytest.raises(ValueError):
        wire.decode_q16_centroids(_golden_q16_row()[:-3])


def test_q16_json_carrier_roundtrip():
    """The jsonmetric-v1 carrier: "centroids_q16" = base64(row); both
    spellings decode through histogram_centroids_from_json."""
    import base64
    frag = wire.histogram_wire_fragment(
        np.array([1.0, 3.0]), np.array([2.0, 1.0]), codec="q16")
    assert frag == {"centroids_q16": base64.b64encode(
        _golden_q16_row()).decode("ascii")}
    m, w = wire.histogram_centroids_from_json(frag)
    np.testing.assert_array_equal(m, np.float32([1.0, 3.0]))
    lossless = wire.histogram_wire_fragment(
        np.array([1.0, 3.0]), np.array([2.0, 1.0]))
    assert lossless == {"centroids": [[1.0, 2.0], [3.0, 1.0]]}
    m, w = wire.histogram_centroids_from_json(lossless)
    np.testing.assert_array_equal(w, np.float32([2.0, 1.0]))


# --- forward kind (delta marker): both arms ---

def test_envelope_forward_kind_golden_bytes():
    """Envelope.forward_kind = 8: emitted only for deltas — a full
    envelope serializes byte-identically to the pre-delta format."""
    env = wire.envelope_pb("s1", 7, 1, 3, kind="delta")
    golden = _golden_envelope_bytes() + _vi(8, 1)
    assert env.SerializeToString() == golden
    back = forward_pb2.Envelope.FromString(golden)
    assert back.forward_kind == 1
    ml = forward_pb2.MetricList()
    ml.envelope.CopyFrom(back)
    assert wire.forward_kind_from_metric_list(ml) == "delta"
    # full == legacy bytes
    assert wire.envelope_pb("s1", 7, 1, 3).SerializeToString() == \
        _golden_envelope_bytes()
    assert wire.envelope_pb(
        "s1", 7, 1, 3, kind="full").SerializeToString() == \
        _golden_envelope_bytes()


def test_jsonmetric_v1_forward_kind_headers_golden():
    headers = wire.envelope_headers("s1", 7, 1, 3, kind="delta")
    assert headers == {"X-Veneur-Sender-Id": "s1",
                       "X-Veneur-Interval-Seq": "7",
                       "X-Veneur-Chunk": "1/3",
                       "X-Veneur-Forward-Kind": "delta"}
    assert wire.forward_kind_from_headers(headers) == "delta"
    # full emits NO kind header (legacy header sets byte-identical)
    full = wire.envelope_headers("s1", 7, 1, 3)
    assert wire.FORWARD_KIND_HEADER not in full
    assert wire.forward_kind_from_headers(full) == "full"
    # unknown kind values degrade to full (tolerant decode)
    assert wire.forward_kind_from_headers(
        {"X-Veneur-Forward-Kind": "banana"}) == "full"


# --- SSF: span protobuf + stream frame ---

def _golden_span():
    span = ssf_pb2.SSFSpan(
        trace_id=100, id=200, parent_id=50,
        start_timestamp=1_000_000, end_timestamp=2_000_000,
        error=True, service="svc", name="op")
    span.tags["env"] = "prod"          # exactly one entry: map order
    sample = span.metrics.add(
        metric=ssf_pb2.SSFSample.GAUGE, name="m", value=1.5,
        timestamp=3, sample_rate=0.5, scope=ssf_pb2.SSFSample.GLOBAL)
    del sample
    golden = (
        # version = 1 is 0 -> omitted
        _vi(2, 100)                    # trace_id
        + _vi(3, 200)                  # id
        + _vi(4, 50)                   # parent_id
        + _vi(5, 1_000_000)            # start_timestamp
        + _vi(6, 2_000_000)            # end_timestamp
        + _vi(7, 1)                    # error = true
        + _s(8, "svc")                 # service
        + _ld(9, _s(1, "env") + _s(2, "prod"))   # tags map entry
        + _s(11, "op")                 # name
        + _ld(12,                      # metrics = 12 (SSFSample)
              _vi(1, 1)                #   metric = GAUGE = 1
              + _s(2, "m")             #   name
              + _f(3, 1.5)             #   value (float32)
              + _vi(4, 3)              #   timestamp
              + _f(7, 0.5)             #   sample_rate
              + _vi(10, 2))            #   scope = GLOBAL = 2
    )
    return span, golden


def test_ssf_span_golden_bytes():
    span, golden = _golden_span()
    assert span.SerializeToString() == golden
    back = framing.parse_ssf_datagram(golden)
    assert back.trace_id == 100 and back.tags["env"] == "prod"
    assert back.metrics[0].value == 1.5
    assert back.metrics[0].scope == ssf_pb2.SSFSample.GLOBAL


def test_ssf_stream_frame_golden_bytes():
    """protocol/wire.go framing: version byte 0x00, little-endian uint32
    length, then the span protobuf."""
    span, golden_payload = _golden_span()
    frame = framing.write_ssf(span)
    assert frame == (b"\x00" + struct.pack("<I", len(golden_payload))
                     + golden_payload)
    import io
    back = framing.read_ssf(io.BytesIO(frame))
    assert back.id == 200 and back.name == "op"


class TestRandomizedRoundtrip:
    """Randomized encode->bytes->decode roundtrips over the forward wire
    (golden tests above pin fixed bytes; these harden the rest of the
    value space: random centroids, unicode/odd tags, extreme floats —
    protocol/wire_test.go's roundtrip property, widened)."""

    def test_export_metrics_roundtrip(self):
        import random
        rng = random.Random(5)
        from veneur_tpu.cluster import wire
        from veneur_tpu.cluster.protos import metric_pb2
        from veneur_tpu.ingest.parser import MetricKey
        from veneur_tpu.models.pipeline import ForwardExport

        tag_pool = ["env:prod", "høst:ünicøde",
                    "emoji:\U0001f600", "empty:", "k:v:w", "plain"]
        for trial in range(200):
            n_cent = rng.randrange(0, 60)
            means = np.sort(np.float32(
                [rng.uniform(-1e30, 1e30) for _ in range(n_cent)]))
            weights = np.float32(
                [rng.choice([1.0, 0.5, 3.25, 1e-3, 1e7])
                 for _ in range(n_cent)])
            tags = ",".join(sorted(rng.sample(tag_pool,
                                              rng.randrange(0, 4))))
            key = MetricKey(f"m.{trial}", "timer", tags)
            vmin = float(means.min()) if n_cent else 0.0
            vmax = float(means.max()) if n_cent else 0.0
            exp = ForwardExport(histograms=[
                (key, means, weights, vmin, vmax,
                 float(np.float32(means.sum())), float(weights.sum()),
                 0.25)])
            pbs = wire.export_to_metrics(exp)
            data = [m.SerializeToString() for m in pbs]
            back = [metric_pb2.Metric.FromString(d) for d in data]
            assert len(back) == 1
            m = back[0]
            assert wire.metric_key_of(m) == key  # type survives (Timer)
            td = m.histogram.t_digest
            got_means = np.float32([c.mean for c in td.centroids])
            got_w = np.float32([c.weight for c in td.centroids])
            live = weights > 0
            np.testing.assert_array_equal(got_means, means[live])
            np.testing.assert_array_equal(got_w, weights[live])
            assert np.float32(td.min) == np.float32(vmin)
            assert np.float32(td.max) == np.float32(vmax)
            assert np.float32(td.count) == np.float32(weights.sum())

    def test_hll_roundtrip_random(self):
        import random
        rng = random.Random(9)
        from veneur_tpu.cluster import wire
        for p in (4, 10, 14):
            for _ in range(20):
                regs = np.array([rng.randrange(0, 64)
                                 for _ in range(1 << p)], np.uint8)
                np.testing.assert_array_equal(
                    wire.decode_hll(wire.encode_hll(regs)), regs)

    def test_ssf_frame_roundtrip_random(self):
        import io
        import random
        rng = random.Random(13)
        from veneur_tpu.ssf import framing
        from veneur_tpu.ssf.protos import ssf_pb2
        for trial in range(100):
            sp = ssf_pb2.SSFSpan()
            sp.version = 1
            sp.trace_id = rng.randrange(1, 1 << 63)
            sp.id = rng.randrange(1, 1 << 63)
            sp.name = "op-é" * rng.randrange(1, 20)
            sp.service = "svc"
            sp.indicator = bool(rng.randrange(2))
            for i in range(rng.randrange(0, 5)):
                sp.tags[f"k{i}"] = "v" * rng.randrange(0, 50)
            buf = io.BytesIO(framing.write_ssf(sp))
            back = framing.read_ssf(buf)
            # message equality, not byte equality: proto3 map fields
            # serialize in unspecified order, so re-encoded bytes can
            # legally differ while the messages are identical
            assert back is not None and back == sp


# ---- vectorized varint weight block (ISSUE 14 satellite) ----
#
# The q16 weight encoder's Python varint join was loop-bound at 100k
# sketches; the numpy block must stay BYTE-IDENTICAL to the scalar
# reference across the whole value range it can see (the encoder
# refuses weights >= 2^63, so 9 varint bytes is the ceiling).

def test_varint_block_bit_identical_to_scalar_reference():
    from veneur_tpu.cluster.wire import _varint as scalar
    from veneur_tpu.cluster.wire import _varint_block
    edges = [0, 1, 127, 128, 255, 16383, 16384, 2**21 - 1, 2**21,
             2**28 - 1, 2**28, 2**35, 2**49, 2**62, 2**63 - 1]
    rng = np.random.default_rng(23)
    vals = np.array(
        edges + list(rng.integers(0, 2**63, 4096, dtype=np.uint64)),
        np.uint64)
    assert _varint_block(vals) == b"".join(
        scalar(int(v)) for v in vals)
    assert _varint_block(np.array([], np.uint64)) == b""
    assert _varint_block(np.array([300], np.uint64)) == scalar(300)


def test_q16_weight_bytes_unchanged_by_vectorization():
    # the full-row regression: encode_q16_centroids output is pinned
    # against a scalar-join re-encode of the same weights (the golden
    # row tests above already pin the absolute bytes)
    from veneur_tpu.cluster import wire
    rng = np.random.default_rng(29)
    means = rng.normal(50, 20, 300)
    weights = np.round(rng.uniform(0.1, 9000, 300), 3)
    row = wire.encode_q16_centroids(means, weights)
    n, lo, hi = wire._Q16_HEAD.unpack_from(row, 0)
    off = wire._Q16_HEAD.size + 2 * n
    qw = np.maximum(1, np.rint(
        np.asarray(weights, np.float64) * 8.0)).astype(np.uint64)
    assert row[off:] == b"".join(wire._varint(int(w)) for w in qw)
    got_m, got_w = wire.decode_q16_centroids(row)
    np.testing.assert_allclose(got_w, weights, atol=1 / 16)

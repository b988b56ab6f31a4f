"""The import worker's native pass over a request's bytes (ISSUE 42):
`wire.BatchDecoder.decode(pbs, raw)` must be `wire.decode_metric_batch(
FromString(raw).metrics)` in everything `import_list` stages from:
the metrics in wire order, every digest's centroids and exact
statistics bit for bit, the rejects. The decoder hands the histograms
over as columns (a `wire.DigestBlock`, ISSUE 44) and the reference as
records, which `wire.digest_block`, the one helper between the two
forms, turns into the same block.
The Python function is the reference; `native/vtpu_wire.cpp` reads the
plain shape `export_to_metrics` writes and marks every other metric
for the reference to decode, so the two agree on any bytes the protobuf
parser accepts. Host code only: nothing here touches a device.
"""

import logging
import struct

import numpy as np
import pytest

from tests.test_wire_golden import (_d, _golden_envelope_bytes,
                                    _golden_q16_row, _ld, _s, _tag, _vi)
from veneur_tpu import sketches
from veneur_tpu.cluster import wire
from veneur_tpu.cluster.protos import forward_pb2
from veneur_tpu.ingest import native
from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.models.pipeline import DECODE_TALLY, ForwardExport

pytestmark = pytest.mark.skipif(
    wire.native_decode_fn() is None,
    reason="native/vtpu_wire.cpp cannot be built here")

HLL_P = 10
NATIVE, FALLBACK, HITS, MISSES = range(4)


# ---- requests ----

def _request(export=None, extra=(), envelope=True, codec="lossless"):
    """Serialized MetricList as a local's forward writes it: the
    export's metrics, the stamp, then the envelope appended as the
    benchmark's driver appends it; `extra` are hand-encoded Metric
    payloads put behind the export's."""
    ml = forward_pb2.MetricList(
        metrics=wire.export_to_metrics(export or ForwardExport(),
                                       codec=codec),
        sketch_engines=sketches.DEFAULT_STAMP)
    raw = ml.SerializeToString() + b"".join(_ld(1, m) for m in extra)
    return raw + (_ld(2, _golden_envelope_bytes()) if envelope else b"")


def _digest(rng, name, n, tags="env:prod,svc:api", kind="timer"):
    x = np.sort(rng.lognormal(4.6, 0.4, n))
    if not n:
        return (MetricKey(name, kind, tags), x, x, 0.0, 0.0, 0.0, 0.0, 0.0)
    w = rng.integers(1, 9, n).astype(np.float64)
    return (MetricKey(name, kind, tags), x, w, x[0], x[-1],
            float((x * w).sum()), float(w.sum()), float((w / x).sum()))


def _fleet_shaped(seed=42):
    """What `forward_payloads` sends: digests of 4 centroids and a few
    of 64, two tags a key, then sets, then counters."""
    rng = np.random.default_rng(seed)
    ex = ForwardExport()
    for i in range(60):
        ex.histograms.append(_digest(rng, f"smoke.timer.t{i:04d}",
                                     4 if i < 54 else 64))
    ex.sets = [(MetricKey(f"smoke.set.s{i:04d}", "set", "env:prod"),
                rng.integers(0, 7, 1 << HLL_P).astype(np.uint8))
               for i in range(5)]
    ex.counters = [(MetricKey(f"smoke.counter.c{i:04d}", "counter",
                              "env:prod"), float(i * 17))
                   for i in range(20)]
    return _request(ex)


def _every_width(seed=7):
    rng = np.random.default_rng(seed)
    ex = ForwardExport()
    for n in (0, 1, 4, 64, 512, 4, 0, 1):
        ex.histograms.append(_digest(rng, f"w.lat.n{n}", n))
    ex.histograms.append(_digest(rng, "w.h", 4, tags="", kind="histogram"))
    return _request(ex)


def _scalars():
    ex = ForwardExport()
    ex.counters = [(MetricKey(f"c{i}", "counter", "a:1"), float(v))
                   for i, v in enumerate((0, 1, -1, 7, -(2 ** 40)))]
    ex.gauges = [(MetricKey(f"g{i}", "gauge", ""), v) for i, v in enumerate(
        (0.0, -0.0, 1.5, float("nan"), float("inf"), float("-inf"),
         5e-324, 1.7976931348623157e308))]
    # int64's ends, which no float names: by hand
    extra = [_s(1, "c.min") + _ld(4, _vi(1, 1 << 63)) + _vi(8, 2),
             _s(1, "c.max") + _ld(4, _vi(1, (1 << 63) - 1)) + _vi(8, 2),
             _s(1, "c.neg1") + _ld(4, _vi(1, (1 << 64) - 1)) + _vi(8, 2),
             # no member of the oneof: no record, a sketch all the same
             _s(1, "bare") + _s(2, "k:v") + _vi(3, 1) + _vi(8, 2),
             b""]
    return _request(ex, extra)


def _centroid(mean, weight):
    return _ld(1, _d(1, mean) + _d(2, weight))


def _td(centroids=b"", tail=None):
    tail = (_d(2, 1.0) + _d(3, 3.0) + _d(4, 5.0) + _d(5, 3.0)
            + _d(6, 7.0 / 6.0)) if tail is None else tail
    return _ld(6, _ld(1, centroids + tail))


def _goldens():
    """The byte strings tests/test_wire_golden.py pins, in one list."""
    regs = np.zeros(16, np.uint8)
    regs[3] = 9
    return [
        _s(1, "c.x") + _s(2, "a:b") + _s(2, "c:d") + _ld(4, _vi(1, 42))
        + _vi(8, 2),
        _s(1, "g") + _vi(3, 1) + _ld(5, _d(1, -1.5)) + _vi(8, 2),
        _s(1, "h") + _s(2, "k:v") + _vi(3, 2)
        + _td(_centroid(1.0, 2.0) + _centroid(3.0, 1.0)) + _vi(8, 2),
        _s(1, "s") + _vi(3, 3)
        + _ld(7, _ld(1, bytes([wire.HLL_VERSION, 4]) + regs.tobytes()))
        + _vi(8, 2),
        _s(1, "ck") + _vi(3, 4) + _ld(9, _d(1, 2.0) + _s(2, "crit")),
        _s(1, "c") + _ld(4, _vi(1, 7)) + _vi(8, 2),
        _s(1, "h") + _s(2, "k:v") + _vi(3, 2)
        + _td(tail=_d(2, 1.0) + _d(3, 3.0) + _d(4, 5.0) + _d(5, 3.0)
              + _d(6, 7.0 / 6.0) + _ld(7, _golden_q16_row()))
        + _vi(8, 2),
    ]


def _golden_list():
    return _request(extra=_goldens())


def _fall_back():
    """Metrics the pass must hand to Python, each between two it reads
    itself, so a fallback digest's centroids land between theirs."""
    plain = (_s(1, "p") + _s(2, "k:v") + _vi(3, 5)
             + _td(_centroid(2.0, 1.0) + _centroid(4.0, 3.0)) + _vi(8, 2))
    name, td = _s(1, "odd") + _vi(3, 5), _td(_centroid(9.0, 2.0))
    odd = [
        _goldens()[-1],                                 # q16 centroids
        td + name + _vi(8, 2),                          # value first
        name + _vi(8, 2) + td,                          # scope first
        _vi(3, 5) + _s(1, "odd") + td,                  # type, then name
        name + td + _ld(11, b"new") + _vi(8, 2),        # an unknown field
        name + td + _tag(8, 5) + b"\0\0\0\0",           # scope as fixed32
        name + td + td,                                 # the oneof twice
        name + _ld(5, _d(1, 2.5)) + td,                 # two members
        name + _s(1, "again") + td,                     # name twice
        name + _ld(6, _ld(1, _d(2, 1.0) + _centroid(1.0, 1.0))),
        name + _ld(6, _ld(1, _d(2, 1.0) + _d(2, 2.0))),     # min twice
        name + _ld(6, _ld(1, _ld(1, _d(2, 1.0) + _d(1, 2.0)))),
        name + _ld(6, _ld(1, _ld(1, _d(1, 2.0) + _vi(3, 1)))),
        name + _ld(6, _ld(1, b"") + _ld(1, b"")),       # t_digest twice
        name + _ld(4, _vi(1, 3) + _vi(1, 4)),           # value twice
        name + _ld(4, _d(1, 3.0)),                      # a double counter
        _goldens()[4],                                  # a status_check
        name + _ld(9, _d(1, 1.0)) + _ld(5, _d(1, 2.5)),
        name + _s(10, "host") + _vi(8, 2) + td,         # hostname first
    ]
    out = [plain]
    for m in odd:
        out += [m, plain]
    return _request(extra=out)


def _still_plain():
    """Shapes a serializer may write that the pass does read: members
    left at their defaults, a hostname, a type the enum does not have."""
    name = _s(1, "dflt") + _s(2, "a:1") + _s(2, "a:1")
    return _request(extra=[
        name + _ld(6, b""),                             # no t_digest
        name + _ld(6, _ld(1, b"")),                     # an empty one
        name + _td(_ld(1, b"") + _ld(1, _d(2, 2.0)) + _ld(1, _d(1, 3.0))),
        name + _ld(4, b""), name + _ld(5, b""), name + _ld(7, b""),
        name + _ld(7, _ld(1, b"")),
        _s(1, "x") + _vi(3, 99) + _ld(5, _d(1, 1.0)) + _s(10, "host"),
        _s(1, "x") + _tag(3, 0) + b"\x82\x80\x00" + _ld(5, _d(1, 1.0)),
        _ld(5, _d(1, 1.0)),                             # no name at all
        _s(1, "café") + _s(2, "é:ü") + _ld(5, _d(1, 2.0)),
    ])


def _poisoned_set():
    rng = np.random.default_rng(3)
    ex = ForwardExport()
    ex.sets = [(MetricKey(f"u{i}", "set", ""),
                rng.integers(0, 7, 1 << HLL_P).astype(np.uint8))
               for i in range(3)]
    bad = _s(1, "evil") + _vi(3, 3) + _ld(7, _ld(1, b"\xff\x00garbage"))
    raw = _request(ex, envelope=False)
    cut = raw.index(_ld(1, wire.export_to_metrics(ex)[1]
                        .SerializeToString()))
    return raw[:cut] + _ld(1, bad) + raw[cut:]


def _beside_the_metrics():
    """Everything forward.proto puts beside field 1, between metrics."""
    m = _goldens()
    sketch = _ld(3, _s(1, "svc.") + _ld(2, bytes(64)))
    return (_ld(2, _golden_envelope_bytes()) + _ld(1, m[0]) + sketch
            + _s(4, sketches.DEFAULT_STAMP) + _ld(1, m[2]) + sketch
            + _vi(1, 5) + _ld(1, m[1]) + _ld(9, b"later") + _vi(12, 1))


REQUESTS = {
    "golden_list": _golden_list,
    "fleet_shaped": _fleet_shaped,
    "every_width": _every_width,
    "scalars": _scalars,
    "q16_codec": lambda: _request(_export_of(_fleet_shaped()), codec="q16"),
    "fall_back": _fall_back,
    "still_plain": _still_plain,
    "poisoned_set": _poisoned_set,
    "beside_the_metrics": _beside_the_metrics,
    "empty": lambda: b"",
    "envelope_alone": lambda: _ld(2, _golden_envelope_bytes()),
}
# what the pass itself reads of each: (native, fallback) sketches
PATHS = {"golden_list": (5, 2), "fleet_shaped": (85, 0),
         "every_width": (9, 0), "scalars": (18, 0), "q16_codec": (25, 60),
         "fall_back": (20, 19), "still_plain": (11, 0),
         "poisoned_set": (4, 0), "beside_the_metrics": (3, 0),
         "empty": (0, 0), "envelope_alone": (0, 0)}
MUTATION_SEEDS = tuple(range(4200, 4206))


def _export_of(raw):
    return wire.export_from_metrics(
        forward_pb2.MetricList.FromString(raw).metrics)


# ---- the comparison ----

def _bits(v):
    """A record's field, so that NaN equals NaN and -0.0 is not 0.0."""
    if isinstance(v, float):
        return struct.pack("<d", v)
    if isinstance(v, np.ndarray):
        return (str(v.dtype), v.shape, v.tobytes())
    return v


def _canon(out, pbs):
    """The decoder's (block, records, rejected, ...) or the
    reference's (records, means, weights, rejected), value for value:
    a digest as its position, key, centroids and five statistics,
    whichever stretch of the columns it names."""
    if isinstance(out[0], wire.DigestBlock):
        block, records, rejected = out[:3]
    else:
        (block, records), rejected = wire.digest_block(*out[:3]), out[3]
    assert block.means.dtype == block.weights.dtype == np.float32
    assert block.means.ndim == block.weights.ndim == 1
    assert block.at.dtype == block.start.dtype == block.stop.dtype \
        == np.int64
    assert block.stats.dtype == np.float64
    assert block.stats.shape == (5, len(block.keys))
    assert all(rec[0] != wire.IMPORT_HISTOGRAM for rec in records)
    digests = [(at, key, block.means[a:b].tobytes(),
                block.weights[a:b].tobytes(), five.tobytes())
               for at, key, a, b, five in zip(
                   block.at.tolist(), block.keys, block.start.tolist(),
                   block.stop.tolist(), block.stats.T)]
    return (digests, [tuple(_bits(v) for v in rec) for rec in records],
            [(pb.SerializeToString(), type(e), str(e))
             for pb, e in rejected])


def _agree(raw, decoder=None, at=None):
    """Both decoders over `raw` (the share at positions `at` of it);
    returns the native side's counts."""
    pbs = forward_pb2.MetricList.FromString(raw).metrics
    if at is not None:
        pbs = [pbs[i] for i in at]
    decoder = decoder or wire.BatchDecoder(1 << 16)
    got = decoder.decode(pbs, raw, at)
    want = wire.decode_metric_batch(pbs)
    assert _canon(got, pbs) == _canon(want, pbs)
    # a digest and a record name their metric by position, in wire
    # order, and no position twice
    ats = got[0].at.tolist(), [rec[2] for rec in got[1]]
    for kind in ats:
        assert kind == sorted(kind) and all(0 <= i < len(pbs) for i in kind)
    assert len(set(ats[0] + ats[1])) == len(ats[0]) + len(ats[1])
    counts = got[3]
    assert counts[NATIVE] + counts[FALLBACK] == len(pbs)
    return counts


def _mutants(raw, seed, n):
    """`n` damaged copies of `raw`: bytes flipped, a tail cut off, a
    stretch of it copied over another place."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        b = bytearray(raw)
        how = rng.integers(0, 4)
        if how == 0:
            for at in rng.integers(0, len(b), rng.integers(1, 4)):
                b[at] ^= 1 << rng.integers(0, 8)
        elif how == 1:
            b[rng.integers(0, len(b))] = rng.integers(0, 256)
        elif how == 2:
            del b[rng.integers(1, len(b)):]
        else:
            n_copy = int(rng.integers(1, 40))
            src, dst = rng.integers(0, len(b) - n_copy, 2)
            b[dst:dst + n_copy] = b[src:src + n_copy]
        yield bytes(b)


def _mutation_base(seed):
    rng = np.random.default_rng(seed)
    ex = ForwardExport()
    for i in range(6):
        ex.histograms.append(_digest(rng, f"m.lat.k{i}", (4, 1, 20)[i % 3]))
    ex.sets = [(MetricKey("m.users", "set", "env:prod"),
                rng.integers(0, 7, 1 << 4).astype(np.uint8))]
    ex.counters = [(MetricKey("m.hits", "counter", "a:1,b:2"), -3.0)]
    ex.gauges = [(MetricKey("m.level", "gauge", ""), 2.5)]
    return _request(ex, extra=_goldens()[4:])


CASES = ([("request", name) for name in REQUESTS]
         + [("mutations", seed) for seed in MUTATION_SEEDS])


@pytest.mark.parametrize("what,which", CASES,
                         ids=[f"{w}-{x}" for w, x in CASES])
def test_the_native_pass_is_the_python_decode(what, which):
    if what == "request":
        raw = REQUESTS[which]()
        counts = _agree(raw)
        assert counts[:2] == PATHS[which], counts
        # a second decoder call finds every key it looked up
        decoder = wire.BatchDecoder(1 << 16)
        first = _agree(raw, decoder)
        again = _agree(raw, decoder)
        assert again[MISSES] == 0
        assert again[HITS] == first[HITS] + first[MISSES]
        return
    raw = _mutation_base(which)
    decoder = wire.BatchDecoder(64)
    accepted = native_read = 0
    with np.errstate(all="ignore"):
        for mutant in _mutants(raw, which, 400):
            try:
                forward_pb2.MetricList.FromString(mutant)
            except Exception:
                continue
            accepted += 1
            native_read += _agree(mutant, decoder)[NATIVE]
    # the loop compared something: the parser takes most flips of a
    # double or a register, and the pass still reads most metrics
    assert accepted >= 100 and native_read >= 5 * accepted


@pytest.mark.parametrize("seed", MUTATION_SEEDS[:3])
def test_the_pass_reads_nothing_past_the_length_it_is_given(seed):
    """The entry point over the first `n` bytes of a longer buffer fills
    what it fills over those bytes alone, whatever lies behind them: a
    walk that read past `n` would see the two tails differ."""
    fn = wire.native_decode_fn()
    raw = _mutation_base(seed)
    rng = np.random.default_rng(seed)

    def walk(buf, n, rows):
        ints = np.full((5, rows), -7, np.int64)
        floats = np.full((5, rows), -7.0)
        means = np.full(len(raw), -7, np.float32)
        weights = np.full(len(raw), -7, np.float32)
        got = fn(buf, n, None, rows, ints.ctypes.data, floats.ctypes.data,
                 means.ctypes.data, weights.ctypes.data, len(means))
        # a fallback row holds nothing but its kind
        ints[1:, ints[0] == 5] = 0
        floats[:, ints[0] == 5] = 0
        return (got, ints.tobytes(), floats.tobytes(), means.tobytes(),
                weights.tobytes())

    # where each of the list's fields ends, and the metrics before it
    ends, at, metrics = [], 0, 0
    while at < len(raw):
        tag, at = wire._read_varint(raw, at)
        size, at = wire._read_varint(raw, at)   # all length-delimited
        at += size
        metrics += tag == (1 << 3 | 2)
        ends.append((at, metrics))
    assert at == len(raw) and metrics >= 10
    whole = 0
    for n in [e for e, _m in ends] + rng.integers(0, len(raw), 200).tolist():
        # a cut inside a metric: the pass is asked for that one too,
        # so it walks up to the cut before it refuses the list
        rows = max([m for e, m in ends if e <= n], default=0) \
            + (n not in dict(ends))
        noise = bytes(rng.integers(0, 256, len(raw) - n, dtype=np.uint8))
        a, b, c = (walk(raw[:n], n, rows), walk(raw, n, rows),
                   walk(raw[:n] + noise, n, rows))
        assert a == b == c, n
        assert (a[0] >= 0) == (n in dict(ends)), n
        whole += a[0] >= 0
    assert whole >= len(ends)


def test_a_share_of_the_request_by_its_positions():
    """More than one engine: each decodes its own metrics of the one
    request, named by their positions in it."""
    raw = _fall_back()
    n = len(forward_pb2.MetricList.FromString(raw).metrics)
    for at in ([0], [n - 1], list(range(0, n, 3)), list(range(1, n, 2)),
               list(range(n))):
        # the odd positions hold the metrics that fall back
        assert _agree(raw, at=at)[FALLBACK] == sum(i % 2 for i in at)
    # positions the list does not have, or out of order: Python's
    for at in ([n], [3, 2], [-1]):
        pbs = list(forward_pb2.MetricList.FromString(raw).metrics)[:len(at)]
        out = wire.BatchDecoder(64).decode(pbs, raw, at)
        assert out[3] == (0, len(at), 0, 0)
        assert _canon(out, pbs) == _canon(wire.decode_metric_batch(pbs),
                                          pbs)


def test_a_key_is_found_by_its_bytes_and_the_dictionary_is_bounded():
    rng = np.random.default_rng(1)
    ex = ForwardExport()
    for i in range(10):
        ex.histograms.append(_digest(rng, f"k{i}", 2, tags="b:2,a:1"))
    raw = _request(ex)
    roomy = wire.BatchDecoder(10)
    assert _agree(raw, roomy) == (10, 0, 0, 10)
    assert _agree(raw, roomy) == (10, 0, 10, 0)
    assert len(roomy._keys) == 10
    # the same key under another tag order is other bytes, one more
    # entry, and the same MetricKey: sorting stayed the parser's
    ex.histograms = [(MetricKey(k.name, k.type, "a:1,b:2"), *rest)
                     for k, *rest in ex.histograms[:3]]
    other = _request(ex)
    pbs = forward_pb2.MetricList.FromString(other).metrics
    block, *_rest, counts = roomy.decode(pbs, other)
    assert counts == (3, 0, 0, 3)
    assert block.keys == [
        MetricKey(f"k{i}", "timer", "a:1,b:2") for i in range(3)]
    # past its bound the dictionary is emptied whole, never grown
    assert len(roomy._keys) == 3
    tight = wire.BatchDecoder(4)
    for _ in range(3):
        counts = _agree(raw, tight)
        assert counts[HITS] + counts[MISSES] == 10 and counts[MISSES] >= 6
        assert len(tight._keys) <= 4
    assert wire.BatchDecoder(0).max_keys == 1


def test_without_bytes_or_without_the_library_python_decodes(
        monkeypatch, caplog):
    raw = _fleet_shaped()
    pbs = forward_pb2.MetricList.FromString(raw).metrics
    want = _canon(wire.decode_metric_batch(pbs), pbs)
    decoder = wire.BatchDecoder(256)
    for no_bytes in (None, bytearray(raw), memoryview(raw)):
        out = decoder.decode(pbs, no_bytes)
        assert out[3] == (0, 85, 0, 0) and _canon(out, pbs) == want
    assert decoder.decode([], raw)[3] == (0, 0, 0, 0)
    # bytes that are another request's: the list's count differs
    out = decoder.decode(pbs, _every_width())
    assert out[3] == (0, 85, 0, 0) and _canon(out, pbs) == want

    def no_compiler(**_kw):
        raise native.NativeUnavailable("no compiler in this test")

    monkeypatch.setattr(wire, "_native_fn", None)
    monkeypatch.setattr(native, "build", no_compiler)
    with caplog.at_level(logging.WARNING, logger="veneur_tpu.cluster.wire"):
        for _ in range(3):
            out = decoder.decode(pbs, raw)
            assert out[3] == (0, 85, 0, 0) and _canon(out, pbs) == want
    said = [r for r in caplog.records if "libvtpu_wire" in r.getMessage()]
    assert len(said) == 1 and "no compiler" in said[0].getMessage()
    assert wire.native_decode_fn() is None


def test_the_tallys_names_follow_the_counts_order():
    assert DECODE_TALLY == ("import_decode_native", "import_decode_fallback",
                            "import_decode_key_hits",
                            "import_decode_key_misses")
    assert (wire.IMPORT_HISTOGRAM, wire.IMPORT_SET, wire.IMPORT_COUNTER,
            wire.IMPORT_GAUGE, wire._ROW_NONE, wire._ROW_FALLBACK) == tuple(
                range(6))

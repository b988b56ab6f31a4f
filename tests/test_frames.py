"""Frame-native egress: sinks serialize straight from MetricFrame blocks.

VERDICT r2 weak #3: the lazy MetricFrame only deferred the 1.7s
InterMetric materialization because every sink consumed the materialized
list. These tests pin the contract that the frame-native paths produce
BYTE-IDENTICAL output to the legacy list paths (sinks/sinks.go sym:
MetricSink.Flush), so the server can hand sinks the columnar FrameSet.
"""

import dataclasses
import gc
import threading

import numpy as np
import pytest

from veneur_tpu import metrics as metrics_mod
from veneur_tpu.metrics import FrameSet, InterMetric, MetricFrame, MetricType
from veneur_tpu.sinks.basic import (BlackholeMetricSink, tsv_from_frames,
                                    tsv_line)
from veneur_tpu.sinks.datadog import DatadogMetricSink


def build_frameset():
    """A frameset exercising every block shape: multi-column histogram
    blocks (shared tags, mixed gauge/counter columns), single-column
    scalar blocks, host:/device: magic tags, and loose self-metrics."""
    fr = MetricFrame(1234, "host-a")
    tags_web = ["env:prod", "svc:web"]
    tags_magic = ["device:sda", "env:prod", "host:other-host"]
    fr.add_block(
        [("api.ms.50percentile", "api.ms.99percentile", "api.ms.count"),
         ("db.ms.50percentile", "db.ms.99percentile", "db.ms.count")],
        [tags_web, tags_magic],
        np.array([[10.5, 99.25, 400.0], [1.5, 9.75, 20.0]]),
        (MetricType.GAUGE, MetricType.GAUGE, MetricType.COUNTER))
    fr.add_block(["hits", "misses"], [tags_web, []],
                 np.array([30.0, 7.0]),
                 (MetricType.COUNTER,))
    fr.add_block(["load"], [["role:db"]], np.array([0.75]),
                 (MetricType.GAUGE,))
    extra = [InterMetric(name="veneur.flush.total_duration_ns",
                         timestamp=1234, value=5e6, tags=[],
                         type=MetricType.GAUGE, hostname="host-a")]
    return FrameSet([fr], extra)


def test_tsv_from_frames_byte_identical():
    fs = build_frameset()
    legacy = "".join(tsv_line(m, "host-a", 10) for m in fs.to_list())
    native = "".join(tsv_from_frames(fs, "host-a", 10))
    assert native == legacy


def test_datadog_frame_flush_byte_identical():
    def make(bodies):
        sink = DatadogMetricSink(api_key="k", api_url="http://x",
                                 hostname="fallback", tags=["base:tag"],
                                 interval_s=10)
        sink._post = lambda path, body, deadline=None: bodies.append((path, body))
        return sink

    fs = build_frameset()
    legacy_bodies, native_bodies = [], []
    make(legacy_bodies).flush(fs.to_list())
    make(native_bodies).flush_frames(fs)
    assert native_bodies == legacy_bodies
    # sanity on the content itself
    series = native_bodies[0][1]["series"]
    by_name = {}
    for s in series:
        by_name.setdefault(s["metric"], s)
    assert by_name["api.ms.count"]["type"] == "rate"
    assert by_name["api.ms.count"]["points"][0][1] == 40.0
    assert by_name["db.ms.50percentile"]["host"] == "other-host"
    assert by_name["db.ms.50percentile"]["device_name"] == "sda"
    assert by_name["load"]["tags"] == ["base:tag", "role:db"]
    assert by_name["hits"]["host"] == "host-a"


def test_datadog_chunking_matches():
    fs = build_frameset()

    def make(bodies):
        sink = DatadogMetricSink(api_key="k", api_url="http://x",
                                 hostname="h", interval_s=10,
                                 flush_max_per_body=4)
        sink._post = lambda path, body, deadline=None: bodies.append(
            len(body["series"]))
        return sink

    a, b = [], []
    make(a).flush(fs.to_list())
    make(b).flush_frames(fs)
    assert a == b and sum(a) == len(fs)


def test_blackhole_counts_without_materializing():
    fs = build_frameset()
    sink = BlackholeMetricSink()
    sink.flush_frames(fs)
    assert sink.flushed_total == len(fs) == 10
    # the frame must not have been materialized by the blackhole
    assert fs.frames[0]._list is None


def test_frameset_iteration_matches_to_list():
    fs = build_frameset()
    assert [m.name for m in fs] == [m.name for m in fs.to_list()]
    assert len(fs) == len(fs.to_list())


# ---- rows a block at a time, the collector held off (ISSUE 50) ----

G, C = MetricType.GAUGE, MetricType.COUNTER


def per_row(frame):
    """The per-row construction every block went through before the
    column path: one keyword call a row, block by block, key by key,
    column by column."""
    out = []
    for names, tags, values, types in frame.blocks:
        for nm, tg, row in zip(names, tags, values.tolist()):
            for j, v in enumerate(row):
                out.append(InterMetric(
                    name=nm if isinstance(nm, str) else nm[j],
                    timestamp=frame.timestamp, value=v, tags=tg,
                    type=types[j], hostname=frame.hostname))
    return out


def _plain():
    fr = MetricFrame(7, "h")
    fr.add_block(["hits", "misses", "drops"], [["a:1"], [], ["b:2", "c"]],
                 np.array([30.0, 7.0, -0.0]), (C,))
    return FrameSet([fr])


def _one_name_sequences():
    fr = MetricFrame(7, "h")
    fr.add_block([("p50",), ["p99"], "mixed"], [["a:1"], ["b"], []],
                 np.array([[1.5], [2.5], [3.5]]), (G,))
    return FrameSet([fr])


def _three_columns():
    fr = MetricFrame(9, "")
    fr.add_block([("t.50", "t.99", "t.count"), ["u.50", "u.99", "u.count"]],
                 [["x:1", "y:2"], []],
                 np.array([[1.0, 2.0, 3.0], [4.0, np.inf, 6.0]]), (G, G, C))
    return FrameSet([fr])


def _empty():
    return FrameSet([MetricFrame(1, "h")])


def _empty_block():
    fr = MetricFrame(1, "h")
    fr.add_block([], [], np.zeros((0, 3)), (G, G, C))
    fr.add_block(["one"], [["t"]], np.array([1.0]), (G,))
    return FrameSet([fr])


def _two_frames():
    a = build_frameset().frames[0]
    b = _three_columns().frames[0]
    return FrameSet([a, b], build_frameset().extra)


@pytest.mark.parametrize("make,rows", [
    (_plain, 3), (_one_name_sequences, 3), (_three_columns, 6),
    (build_frameset, 10), (_empty, 0), (_empty_block, 1),
    (_two_frames, 16)],
    ids=["m1_plain_names", "m1_one_name_sequences", "m3",
         "several_blocks_and_extra", "empty_frame", "empty_block",
         "two_frames_and_extra"])
def test_rows_built_by_block_equal_the_per_row_rows(make, rows):
    fs = make()
    want = [m for fr in fs.frames for m in per_row(fr)] + list(fs.extra)
    lazy = list(fs)                     # before anything is cached
    assert all(fr._list is None for fr in fs.frames)
    got = fs.to_list()
    assert len(got) == rows == len(fs)
    for g, w in zip(got, want, strict=True):
        # field for field (inf == inf; -0.0 == 0.0 is told apart below)
        assert dataclasses.asdict(g) == dataclasses.asdict(w)
        assert type(g.value) is float and repr(g.value) == repr(w.value)
        assert type(g.type) is MetricType
    assert lazy == got and list(fs) == got      # cached now, still equal
    for fr in fs.frames:
        assert list(fr) == fr.to_list() == per_row(fr)
        it = iter(fr.to_list())
        for _names, tags, values, _types in fr.blocks:
            for tg, row in zip(tags, values):
                for _ in row:
                    # the frame's own shared list, never a copy
                    assert next(it).tags is tg
        assert fr.rows_built == len(fr) and fr.rows_fallback == 0
        assert fr.build_ns > 0
    sinks = [m.sinks for fr in fs.frames for m in fr.to_list()]
    assert all(s == [] and type(s) is list for s in sinks)
    assert len({id(s) for s in sinks}) == len(sinks)    # one of its own


def test_a_ragged_block_takes_the_per_row_fallback_and_is_counted():
    fr = MetricFrame(3, "h")
    # names[1] is longer than the block is wide: the per-row loop reads
    # its first m names, the column path cannot flatten it
    fr.add_block([("a.50", "a.99"), ("b.50", "b.99", "b.spare")],
                 [["t"], []], np.array([[1.0, 2.0], [3.0, 4.0]]), (G, C))
    fr.add_block(["plain"], [["t"]], np.array([5.0]), (G,))
    assert list(fr) == per_row(fr)
    rows = fr.to_list()
    assert rows == per_row(fr)
    assert [m.name for m in rows] == ["a.50", "a.99", "b.50", "b.99",
                                      "plain"]
    assert (fr.rows_built, fr.rows_fallback) == (5, 4)
    fs = FrameSet([fr])
    assert fs.claim_build() == {"rows_built": 5, "rows_fallback": 4,
                                "build_ns": fr.build_ns}
    # handed out once, and only to the thread that built
    assert fs.claim_build() == {"rows_built": 0, "rows_fallback": 0,
                                "build_ns": 0}


def test_only_the_building_thread_claims_the_build():
    fs = build_frameset()
    got = {}
    t = threading.Thread(target=lambda: (fs.to_list(), got.update(
        fs.claim_build())))
    fs2 = build_frameset()
    fs2.to_list()
    t.start()
    t.join()
    assert got["rows_built"] == 9           # the frame's; extra is no build
    assert fs.claim_build()["rows_built"] == 0      # not this thread's
    assert fs2.claim_build()["rows_built"] == 9


# ---- the guard ----

@pytest.fixture
def collector_on():
    was = gc.isenabled()
    gc.enable()
    yield
    (gc.enable if was else gc.disable)()    # the TEST restores, not code


@pytest.mark.parametrize("first_out", [0, 1], ids=["fifo", "lifo"])
def test_collection_is_off_exactly_while_either_thread_is_inside(
        collector_on, first_out):
    hold = metrics_mod._CollectorHold()
    inside = [threading.Event(), threading.Event()]
    leave = [threading.Event(), threading.Event()]
    left = [threading.Event(), threading.Event()]

    def worker(i):
        with hold:
            inside[i].set()
            leave[i].wait(10)
        left[i].set()

    ts = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
    assert gc.isenabled()
    ts[0].start()
    assert inside[0].wait(10) and not gc.isenabled()
    ts[1].start()
    assert inside[1].wait(10) and not gc.isenabled()
    leave[first_out].set()
    assert left[first_out].wait(10)
    assert not gc.isenabled()       # the other is still inside
    leave[1 - first_out].set()
    assert left[1 - first_out].wait(10)
    assert gc.isenabled()
    for t in ts:
        t.join()


def test_a_process_with_collection_disabled_leaves_disabled(collector_on):
    gc.disable()
    hold = metrics_mod._CollectorHold()
    with hold:
        with hold:                  # re-entrant on one thread too
            assert not gc.isenabled()
        assert not gc.isenabled()
    assert not gc.isenabled()
    gc.enable()
    with hold:
        assert not gc.isenabled()
    assert gc.isenabled()


def test_an_exception_inside_the_guard_restores(collector_on):
    hold = metrics_mod._CollectorHold()
    with pytest.raises(KeyError):
        with hold:
            assert not gc.isenabled()
            raise KeyError("boom")
    assert gc.isenabled() and hold._depth == 0

    # and through the frame: a block whose names raise mid-build
    class Boom:
        def __len__(self):
            return 2

        def __iter__(self):
            yield "a"
            raise KeyError("names")

    fr = MetricFrame(1, "h")
    fr.add_block(Boom(), [[], []], np.array([1.0, 2.0]), (G,))
    with pytest.raises(KeyError):
        fr.to_list()
    assert gc.isenabled() and metrics_mod._collector_held._depth == 0
    assert fr._list is None and not fr._mat_lock.locked()


def test_the_guard_is_not_held_while_a_sink_flushes(collector_on):
    from veneur_tpu.sinks.basic import CaptureMetricSink

    seen = []

    class AssertingSink(CaptureMetricSink):
        def flush(self, metrics):
            seen.append((gc.isenabled(),
                         metrics_mod._collector_held._depth))
            super().flush(metrics)

    fs = build_frameset()
    sink = AssertingSink()
    assert sink.flush_frames(fs) == 10
    assert seen == [(True, 0)]
    assert fs.claim_build()["rows_built"] == 9
    # inside the build it IS held: a names column that looks
    calls = []

    class Looking(list):
        def __iter__(self):
            calls.append((gc.isenabled(),
                          metrics_mod._collector_held._depth))
            return super().__iter__()

    fr = MetricFrame(1, "h")
    fr.add_block(Looking(["a", "b"]), [[], []], np.array([1.0, 2.0]), (G,))
    fr.to_list()
    assert calls == [(False, 1)]


def test_many_threads_building_at_once_leave_the_collector_as_found(
        collector_on):
    """More builders than cores under a short switch interval: inside
    every build collection is off, the count never goes negative, and
    when the last one leaves it is on again."""
    import sys

    seen_on, errors = [], []

    class Looking(list):
        def __iter__(self):
            if gc.isenabled() or metrics_mod._collector_held._depth < 1:
                seen_on.append(1)
            return super().__iter__()

    def build():
        try:
            for _ in range(40):
                fr = MetricFrame(1, "h")
                fr.add_block(Looking(f"n{i}" for i in range(64)),
                             [[]] * 64, np.arange(64.0), (G,))
                assert len(fr.to_list()) == 64
        except Exception as e:      # surfaced below, not lost in a thread
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=build) for _ in range(32)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(was)
    assert not errors and not seen_on
    assert metrics_mod._collector_held._depth == 0 and gc.isenabled()

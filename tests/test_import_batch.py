"""The import's hand-over (ISSUE 30): a forwarded request reaches the
engine as ONE batch. `AggregationEngine.import_list` must be the
per-metric Combine (`wire.apply_metric_to_engine`, metric by metric)
in everything a flush, a checkpoint or a landing can see: the same
staged state, the same landings holding the same rows in the same
order, the same rejects, a bit-identical flush. And on the way there:
one queue item an engine a request, a queue bounded in sketches, a
shed counted in sketches, and a drain() that waits for the staging.
"""

import functools
import json
import queue
import threading
import urllib.request

import numpy as np
import pytest

from veneur_tpu import observe
from veneur_tpu.cluster import wire
from veneur_tpu.cluster.importsrv import (ForwardHandler, ImportedBatch,
                                          start_import_server)
from veneur_tpu.cluster.protos import forward_pb2, metric_pb2
from veneur_tpu.config import read_config
from veneur_tpu.ingest import parser
from veneur_tpu.ingest.admission import AdmissionController
from veneur_tpu.models import pipeline
from veneur_tpu.models.pipeline import AggregationEngine, EngineConfig
from veneur_tpu.server import Server, _WorkerQueue
from veneur_tpu.sinks.basic import CaptureMetricSink

HLL_P = 10


def _timer(ml, name, means, weights=None, tags=(), packed=False):
    m = ml.metrics.add(name=name, type=metric_pb2.Timer, tags=list(tags))
    td = m.histogram.t_digest
    means = np.asarray(means, np.float64)
    weights = np.ones(len(means)) if weights is None else weights
    if packed:
        td.packed_centroids = wire.encode_q16_centroids(means, weights)
    else:
        for mean, w in zip(means, weights):
            td.centroids.add(mean=float(mean), weight=float(w))
    td.min, td.max = float(means.min()), float(means.max())
    td.sum = float((means * weights).sum())
    td.count = float(np.sum(weights))
    td.reciprocal_sum = float((weights / means).sum())


def _set(ml, name, rng):
    m = ml.metrics.add(name=name, type=metric_pb2.Set)
    regs = rng.integers(0, 6, 1 << HLL_P).astype(np.uint8)
    m.set.hyper_log_log = wire.encode_set_payload("hll", regs)


def _counter(ml, name, value, tags=()):
    m = ml.metrics.add(name=name, type=metric_pb2.Counter, tags=list(tags))
    m.counter.value = value


def _gauge(ml, name, value):
    m = ml.metrics.add(name=name, type=metric_pb2.Gauge)
    m.gauge.value = value


def _request(n_timers, n_keys, prefix="svc"):
    """One request of every kind the wire carries: 4-centroid timers
    (`n_timers` digests over `n_keys` keys, so a key arrives more than
    once), 130-centroid timers, a q16-packed digest, sets, a counter
    hit twice, a gauge written twice, and one malformed metric in the
    middle, which rejects itself."""
    rng = np.random.default_rng(30)
    ml = forward_pb2.MetricList()
    half = n_timers // 2
    for i in range(half):
        _timer(ml, f"{prefix}.lat.k{i % n_keys}",
               rng.lognormal(4.6, 0.1, 4), tags=["b:2", "a:1"])
    for i in range(3):
        _timer(ml, f"{prefix}.wide.k{i}", rng.lognormal(4.6, 0.1, 130),
               weights=rng.integers(1, 9, 130).astype(np.float64))
    _timer(ml, f"{prefix}.q16", rng.lognormal(4.6, 0.1, 40), packed=True)
    _counter(ml, f"{prefix}.hits", 7)
    _gauge(ml, f"{prefix}.level", 1.5)
    bad = ml.metrics.add(name=f"{prefix}.evil", type=metric_pb2.Set)
    bad.set.hyper_log_log = b"\xff\x00garbage"
    for i in range(4):
        _set(ml, f"{prefix}.users.s{i}", rng)
    _counter(ml, f"{prefix}.hits", 35, tags=["z:9"])
    _counter(ml, f"{prefix}.hits", 35)
    _gauge(ml, f"{prefix}.level", -2.25)
    for i in range(half, n_timers):
        _timer(ml, f"{prefix}.lat.k{i % n_keys}",
               rng.lognormal(4.6, 0.1, 4), tags=["a:1", "b:2"])
    return forward_pb2.MetricList.FromString(ml.SerializeToString())


# case -> (request arguments, staging threshold in digests or None for
# the engine's own 8,192, keys a prefix may hold or None for no
# admission control)
CASES = {
    "mixed": ((200, 120), None, None),
    "fold": ((60, 40), None, 8),
    "landing_inside_a_batch": ((500, 90), 64, None),
    "landing_at_the_8192nd_digest": ((8300, 200), None, None),
}
MESH_CASES = ("mixed", "fold")


def _engine(kind, budget):
    cfg = EngineConfig(histogram_slots=512, counter_slots=64,
                       gauge_slots=64, set_slots=32, buffer_depth=256,
                       batch_size=256, hll_precision=HLL_P,
                       percentiles=(0.5, 0.99),
                       aggregates=("min", "max", "count", "sum"),
                       is_global=True)
    if kind == "mesh":
        from veneur_tpu.parallel.engine import MeshAggregationEngine
        eng = MeshAggregationEngine(cfg, n_devices=8)
    else:
        eng = AggregationEngine(cfg)
    if budget is not None:
        eng.attach_admission(AdmissionController(
            registry=observe.TelemetryRegistry(),
            max_keys_per_prefix=budget))
    return eng


def _bytes(x):
    return x.tobytes() if isinstance(x, np.ndarray) else x


def _plain(items):
    return [tuple(_bytes(f) for f in it) for it in items]


def _mesh_stage(eng):
    """The mesh engine's columnar stage as plain values: the staged
    points, the slot and point count of each staged digest, and the
    interval's exact-stats deltas of every slot that has one."""
    n, nd = eng._h_n, eng._h_nd
    touched = np.flatnonzero(eng._h_deltas.any(axis=0))
    return {"points": [c[:n].tobytes() for c in
                       (eng._h_slots, eng._h_vals, eng._h_wts)],
            "digests": [eng._h_dslots[:nd].tolist(),
                        eng._h_dpoints[:nd].tolist()],
            "deltas": [touched.tolist(),
                       eng._h_deltas[:, touched].tobytes()]}


def _watch_landings(eng, kind):
    """Every histogram landing's rows, in the order it was handed
    them, whichever thread or path lands."""
    seen = []
    if kind == "mesh":
        orig = eng._stage_landing

        def land():
            if eng._h_n:
                # the deltas wait for the flush: a landing inside a
                # batch finds the whole batch's there already
                stage = _mesh_stage(eng)
                seen.append((stage["points"], stage["digests"]))
            return orig()
        eng._stage_landing = land
    else:
        orig = eng._land_import_centroids

        def land(bank, stage, dirty):
            if stage.digests:
                seen.append(_plain(stage.items()))
            return orig(bank, stage, dirty)
        eng._land_import_centroids = land
    return seen


def _staged(eng, kind):
    state = {"centroids": _plain(eng._import_centroids.items()),
             "sets": _plain(eng._import_sets),
             "counters": list(eng._import_counter_acc.items()),
             "gauges": list(eng._import_gauge_acc.items())}
    if kind == "mesh":
        state["stage"] = _mesh_stage(eng)
    else:
        state["centroid_total"] = eng._import_centroids.centroids
    return state


def _shape(landing):
    """(S, W) of the `[S, W]` matrix `_land_imports_clustered` fills
    from a landing's rows: distinct slots, and the widest pile rounded
    up to 128 lanes."""
    piles = {}
    for slot, means, *_ in landing:
        piles[slot] = piles.get(slot, 0) + len(means) // 4
    return len(piles), max(128, -(-max(piles.values()) // 128) * 128)


@functools.lru_cache(maxsize=None)
def _both_ways(kind, case):
    """The case's request applied metric by metric (`one`), as one
    batch of parsed messages (`batch`) and as one batch with the
    request's bytes (`bytes`, ISSUE 42) into three fresh engines ->
    what each staged, landed, rejected and flushed."""
    args, stage_digests, budget = CASES[case]
    request = _request(*args)
    out = {}
    old = pipeline._IMPORT_STAGE_DIGESTS
    if stage_digests is not None:
        pipeline._IMPORT_STAGE_DIGESTS = stage_digests
    try:
        for way in ("one", "batch", "bytes"):
            eng = _engine(kind, budget)
            landings = _watch_landings(eng, kind)
            if way == "one":
                rejected = 0
                for pb in request.metrics:
                    try:
                        wire.apply_metric_to_engine(eng, pb)
                    except Exception:
                        rejected += 1
            else:
                raw = request.SerializeToString() if way == "bytes" \
                    else None
                rerouted, bad = eng.import_list(7, request.metrics, raw)
                assert rerouted == []
                rejected = len(bad)
                assert [pb.name for pb, _e in bad] == ["svc.evil"]
                assert eng.last_import_op == 7
            staged = _staged(eng, kind)
            decoded = [getattr(eng, "_" + k) for k in pipeline.DECODE_TALLY]
            mid = len(landings)
            rows = sorted(
                (m.name, tuple(m.tags), repr(m.value), m.type)
                for m in eng.flush(timestamp=30).metrics)
            out[way] = {"staged": staged, "landings": landings,
                        "decoded": decoded,
                        "mid_interval_landings": mid,
                        "rejected": rejected, "rows": rows,
                        "folded": (None if budget is None else
                                   eng._adm._tel.total(
                                       observe.SERVER_SCOPE,
                                       "overload.folded_samples"))}
    finally:
        pipeline._IMPORT_STAGE_DIGESTS = old
    return out


ENGINE_CASES = ([("single", c) for c in CASES]
                + [("mesh", c) for c in MESH_CASES])
each_case = pytest.mark.parametrize(
    "kind, case", ENGINE_CASES, ids=[f"{k}-{c}" for k, c in ENGINE_CASES])


@each_case
def test_batch_stages_what_metric_by_metric_stages(kind, case):
    got = _both_ways(kind, case)
    assert got["batch"]["staged"] == got["one"]["staged"]
    staged = got["one"]["staged"]
    if case != "fold":
        # the counter hit twice is one f64 sum beside its tagged twin,
        # the gauge written twice its last value
        assert [v for _s, v in staged["counters"]] == [42.0, 35.0]
        assert [v for _s, v in staged["gauges"]] == [-2.25]
        assert len(staged["sets"]) == 4


@each_case
def test_batch_lands_the_same_rows_in_the_same_landings(kind, case):
    got = _both_ways(kind, case)
    one, batch = got["one"], got["batch"]
    assert batch["landings"] == one["landings"]
    assert batch["mid_interval_landings"] == one["mid_interval_landings"]
    if kind == "single":
        assert [_shape(x) for x in batch["landings"]] \
            == [_shape(x) for x in one["landings"]]
    if case == "landing_inside_a_batch":
        # 504 digests against a stage of 64: seven landings fall inside
        # the batch, each at exactly the 64th staged digest
        assert one["mid_interval_landings"] == 7
        assert [len(x) for x in one["landings"][:7]] == [64] * 7
    if case == "landing_at_the_8192nd_digest":
        assert one["mid_interval_landings"] == 1
        assert len(one["landings"][0]) == pipeline._IMPORT_STAGE_DIGESTS
        # 200 keys of four centroids 41 times over, three of 130 and
        # the q16 digest: the landing's matrix is what it is today
        assert _shape(one["landings"][0]) == (204, 256)


@each_case
def test_batch_rejects_the_malformed_metric_and_only_it(kind, case):
    got = _both_ways(kind, case)
    assert got["batch"]["rejected"] == got["one"]["rejected"] == 1
    assert got["batch"]["folded"] == got["one"]["folded"]
    if case == "fold":
        assert got["one"]["folded"] > 0


@each_case
def test_batch_flushes_bit_identically(kind, case):
    got = _both_ways(kind, case)
    assert got["batch"]["rows"] == got["one"]["rows"]
    names = {r[0] for r in got["one"]["rows"]}
    if case == "fold":
        assert "svc.__other__.count" in names
    else:
        assert {"svc.lat.k0.count", "svc.wide.k2.99percentile",
                "svc.q16.50percentile", "svc.users.s3", "svc.hits",
                "svc.level"} <= names
    assert "svc.evil" not in names


@each_case
def test_batch_from_its_bytes_is_the_batch_from_its_messages(kind, case):
    """The worker reads a gRPC request's sketches from its bytes in one
    native pass (ISSUE 42): everything a flush, a checkpoint or a
    landing can see is what the parsed messages gave, the q16 digest
    (which the pass leaves to Python) in its place among the others
    and the malformed set rejecting itself alone."""
    got = _both_ways(kind, case)
    native, parsed = got["bytes"], got["batch"]
    for what in ("staged", "landings", "mid_interval_landings",
                 "rejected", "rows", "folded"):
        assert native[what] == parsed[what], what
    n = len(_request(*CASES[case][0]).metrics)
    n_native, n_fallback, hits, misses = native["decoded"]
    assert (n_native, n_fallback) == (n - 1, 1)
    # a key of either tag order is minted once and found afterwards
    assert hits + misses == n - 1 and misses <= CASES[case][0][1] * 2 + 20
    assert parsed["decoded"] == [0, n, 0, 0]
    assert got["one"]["decoded"] == [0, 0, 0, 0]


def test_decode_rolls_back_a_digest_that_fails_half_way():
    """A histogram whose centroids raise after some were read leaves
    nothing in the batch's flat columns; its neighbours keep theirs."""
    ml = forward_pb2.MetricList()
    _timer(ml, "a", [1.0, 2.0])
    _timer(ml, "b", [5.0, 6.0, 7.0])

    class Torn:
        name, type, tags = "torn", metric_pb2.Timer, ()

        def WhichOneof(self, _):
            return "histogram"

        @property
        def histogram(self):
            def cents():
                yield metric_pb2.Centroid(mean=9.0, weight=1.0)
                raise ValueError("torn centroid list")

            class TD:
                packed_centroids = b""
                centroids = cents()
            return type("H", (), {"t_digest": TD})
    pbs = [ml.metrics[0], Torn(), ml.metrics[1]]
    records, means, weights, rejected = wire.decode_metric_batch(pbs)
    assert [r[1].name for r in records] == ["a", "b"]
    assert means.tolist() == [1.0, 2.0, 5.0, 6.0, 7.0]
    assert weights.tolist() == [1.0] * 5
    assert [(r[3], r[4]) for r in records] == [(0, 2), (2, 5)]
    assert len(rejected) == 1 and rejected[0][0] is pbs[1]


# ---------------------------------------------------------------- the queue

_CFG = """
interval: "3600s"
statsd_listen_addresses: []
tpu_histogram_slots: 256
tpu_counter_slots: 256
tpu_gauge_slots: 64
tpu_set_slots: 32
tpu_hll_precision: 10
"""


def _server(extra=""):
    return Server(read_config(text=_CFG + extra),
                  sinks=[CaptureMetricSink()], plugins=[], span_sinks=[])


class _Ctx:
    def invocation_metadata(self):
        return ()


def _queued(srv):
    return [list(q.queue) for q in srv.worker_queues]


@pytest.mark.parametrize("workers", [1, 2])
@pytest.mark.parametrize("transport", ["grpc", "grpc-stream", "http"])
def test_one_request_is_one_queue_item_an_engine(transport, workers):
    """Whatever the transport, an admitted request puts ONE
    ImportedBatch on each engine's queue that has a share (the server
    is not started, so the items stay where the handler put them), the
    shares partition the request in wire order, and one engine takes
    the request's own list with no digest computed."""
    srv = _server(f"num_workers: {workers}\n")
    request = _request(40, 25)
    want = [pb.name for pb in request.metrics]
    if transport == "http":
        from veneur_tpu.http_api import HttpApi
        body = [{"name": pb.name, "type": "counter", "value": 3,
                 "tags": list(pb.tags)} for pb in request.metrics]
        api = HttpApi("127.0.0.1:0",
                      submit_batch=srv._submit_import_batch)
        api.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{api.port}/import",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert json.loads(resp.read())["imported"] == len(want)
        finally:
            api.stop()
    else:
        handler = ForwardHandler(srv._submit_import_batch)
        if transport == "grpc":
            raw = request.SerializeToString()
            handler._send_metrics(request, _Ctx(), raw=raw)
        else:
            handler._send_metrics_v2(iter(request.metrics), _Ctx())
    queued = _queued(srv)
    assert all(len(items) <= 1 for items in queued)
    batches = [items[0] for items in queued if items]
    assert all(isinstance(b, ImportedBatch) for b in batches)
    assert len({b.op_id for b in batches}) == 1
    assert len(batches) == workers
    shares = [[pb.name for pb in b.pbs] for b in batches]
    assert sorted(n for s in shares for n in s) == sorted(want)
    for share in shares:        # wire order kept inside a share
        it = iter(want)
        assert all(name in it for name in share)
    # SendMetrics alone has the request's bytes: they ride in every
    # share, with the share's positions in the request (ISSUE 42)
    for b, share in zip(batches, shares):
        if transport != "grpc":
            assert b.raw is None
        else:
            assert b.raw is raw
            assert (b.at is None if workers == 1
                    else [want[i] for i in b.at] == share)
    if workers == 1:
        assert shares == [want]
    if workers == 2:
        from veneur_tpu.utils.hashing import metric_digest
        for qi, b in enumerate(batches):
            for pb in b.pbs:
                key = wire.metric_key_of(pb)
                assert metric_digest(key.name, key.type,
                                     key.joined_tags) % 2 == qi


def test_forward_handler_needs_submit_batch():
    """submit_batch is the one routing callback: no handler or import
    server without it, and none around a per-metric `submit`."""
    with pytest.raises(TypeError):
        ForwardHandler()
    with pytest.raises(TypeError):
        ForwardHandler(submit=lambda digest, item: None)
    with pytest.raises(TypeError):
        start_import_server("127.0.0.1:0")


def _record_puts(srv):
    """Every item put on a worker queue of `srv`, as (queue, item), in
    order — the worker threads may take them off at once."""
    puts = []
    for qi, q in enumerate(srv.worker_queues):
        def put(item, qi=qi, orig=q._put):
            puts.append((qi, item))
            orig(item)
        q._put = put
    return puts


@pytest.mark.parametrize("transport", ["grpc", "grpc_v2_stream", "http"])
def test_every_import_transport_hands_over_one_batch(transport):
    """One request through each front end of a STARTED server — its
    own listeners, real sockets — puts exactly one ImportedBatch an
    engine that has a share on the worker queues, and nothing else."""
    import grpc

    from veneur_tpu.cluster.forward import SEND_METRICS, SEND_METRICS_V2

    srv = _server('num_workers: 2\nis_global: true\n'
                  'grpc_listen_addresses: ["127.0.0.1:0"]\n'
                  'http_address: "127.0.0.1:0"\n')
    request = forward_pb2.MetricList()
    for i in range(24):
        _counter(request, f"svc.hits.k{i}", 3)
    want = sorted(pb.name for pb in request.metrics)
    srv.start()
    try:
        puts = _record_puts(srv)
        if transport == "http":
            body = [{"name": pb.name, "type": "counter", "value": 3,
                     "tags": []} for pb in request.metrics]
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.http_api.port}/import",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert json.loads(resp.read())["imported"] == len(want)
        else:
            with grpc.insecure_channel(
                    f"127.0.0.1:{srv.grpc_port}") as ch:
                empty = forward_pb2.Empty.FromString
                if transport == "grpc":
                    ch.unary_unary(
                        SEND_METRICS,
                        request_serializer=forward_pb2.MetricList
                        .SerializeToString,
                        response_deserializer=empty)(request, timeout=10)
                else:
                    ch.stream_unary(
                        SEND_METRICS_V2,
                        request_serializer=metric_pb2.Metric
                        .SerializeToString,
                        response_deserializer=empty)(
                        iter(request.metrics), timeout=10)
        assert srv.drain(10.0)
        assert all(isinstance(item, ImportedBatch) for _, item in puts)
        assert sorted(qi for qi, _ in puts) == [0, 1]
        assert len({item.op_id for _, item in puts}) == 1
        assert sorted(pb.name for _, item in puts
                      for pb in item.pbs) == want
    finally:
        srv.stop()


def test_fold_rerouted_from_inside_a_batch_keeps_its_op_id():
    """Two engines, a prefix budget of two keys: an over-budget
    forwarded counter whose fold key is homed on the OTHER engine
    leaves the batch it came in and arrives there as an ImportedBatch
    of one, rewritten onto the fold key, under the request's op id —
    and the fold row counts every folded value once."""
    srv = _server("num_workers: 2\noverload_defense_enabled: true\n"
                  "overload_max_keys_per_prefix: 2\n")
    request = forward_pb2.MetricList()
    for k in range(12):
        _counter(request, f"imp.c{k}", k + 1)
    srv.start()
    try:
        puts = _record_puts(srv)
        assert srv._submit_import_batch(list(request.metrics)) == 12
        assert srv.drain(10.0)
        first, rerouted = puts[:2], puts[2:]
        assert sorted(qi for qi, _ in first) == [0, 1]
        op_id = first[0][1].op_id
        home = [qi for qi, eng in enumerate(srv.engines)
                if any(k.name == "imp.__other__"
                       for k in eng.counter_keys._map)]
        assert len(home) == 1
        assert rerouted                  # some fold came from the other
        for qi, item in rerouted:
            assert isinstance(item, ImportedBatch)
            assert (qi, item.op_id) == (home[0], op_id)
            assert [pb.name for pb in item.pbs] == ["imp.__other__"]
        by = {m.name: m.value for m in srv.flush_once(timestamp=10)}
        kept = [v for n, v in by.items() if n.startswith("imp.c")]
        assert len(kept) == 2
        assert sum(kept) + by["imp.__other__"] == 78.0
        assert sum(eng.last_import_op == op_id
                   for eng in srv.engines) == 2
    finally:
        srv.stop()


@pytest.mark.parametrize("items, weight", [
    ([ImportedBatch(1, [object()] * 5)], 5),
    ([ImportedBatch(1, [])], 1),
    ([ImportedBatch(1, [object()] * 3), parser.parse_packet(b"x:1|c"),
      ImportedBatch(2, [object()] * 9)], 13),
], ids=["batch", "empty_batch", "mixed"])
def test_worker_queue_counts_sketches_waiting(items, weight):
    q = _WorkerQueue(maxsize=100)
    for item in items:
        q.put_nowait(item)
    assert q.qsize() == weight and not q.empty()
    assert q.unfinished_tasks == len(items)
    assert [q.get_nowait() for _ in items] == items
    assert q.qsize() == 0 and q.empty()


def test_queue_bounds_sketches_and_shed_counts_sketches():
    """The bound is on forwarded sketches waiting: two batches of 8
    fill a queue of 10 (a put is admitted while under the bound), the
    third waits out flush_timeout and is shed, counted by its five
    sketches; what the queue holds stays what was admitted."""
    srv = _server('flush_timeout: "50ms"\n')
    srv.worker_queues[0] = _WorkerQueue(maxsize=10)

    def mk(n):
        return [metric_pb2.Metric(name=f"c{i}", type=metric_pb2.Counter)
                for i in range(n)]
    assert srv._submit_import_batch(mk(8)) == 8
    assert srv._submit_import_batch(mk(8)) == 8
    q = srv.worker_queues[0]
    assert q.qsize() == 16 and q.full()
    assert srv._peek("worker.dropped") == 0
    srv._submit_import_batch(mk(5))
    assert srv._peek("worker.dropped") == 5
    # inside the shed window the next one does not wait
    srv._submit_import_batch(mk(3))
    assert srv._peek("worker.dropped") == 8
    assert [len(b.pbs) for b in q.queue] == [8, 8]
    with pytest.raises(queue.Full):
        q.put_nowait(parser.parse_packet(b"x:1|c"))


def test_drain_waits_until_the_batch_is_staged():
    srv = _server()
    srv.start()
    try:
        eng = srv.engines[0]
        gate, entered = threading.Event(), threading.Event()
        orig = eng.import_list

        def slow(*batch):
            entered.set()
            assert gate.wait(30)
            return orig(*batch)
        eng.import_list = slow
        request = _request(20, 10)
        srv._submit_import_batch(request.metrics)
        assert entered.wait(10)
        # popped off the queue, not yet staged: still unfinished
        assert srv.worker_queues[0].qsize() == 0
        assert not srv.drain(0.2)
        assert not eng._import_counter_acc
        gate.set()
        assert srv.drain(10.0)
        assert list(eng._import_counter_acc.values()) == [42.0, 35.0]
        assert srv._peek("import.rejected") == 1
        out = {m.name: m.value for m in srv.flush_once(timestamp=5)}
        assert out["svc.hits"] == 35.0 or out["svc.hits"] == 42.0
        assert out["veneur.import.rejected_total"] == 1.0
    finally:
        srv.stop()

"""The Server: listeners + sharded engines + flush loop + watchdog.

Parity: server.go (sym: Server, NewFromConfig, Server.Start,
Server.HandleMetricPacket, Server.ReadMetricSocket, Server.Shutdown),
flusher.go (sym: Server.Flush, Server.FlushWatchdog), networking.go.

Threading model (the Go goroutine topology, reshaped):
  * `num_readers` UDP reader threads per listen address (SO_REUSEPORT
    sockets — same kernel-level fan-in as the reference).
  * Readers parse inline and route each sample by digest to one of
    `num_workers` worker queues (`Workers[Digest % len(Workers)]`).
  * Each worker thread owns one AggregationEngine feeding the device —
    engines own disjoint hash-space shards, so flush is a union, never a
    merge. Device calls release the GIL, so workers overlap.
  * One flush thread ticks every `interval`, drains all engines, fans out
    to sinks (thread per sink, timed), hands exports to the forwarder.
  * A watchdog thread kills the process if flushes stop completing
    (crash-only design: Server.FlushWatchdog panics for the supervisor
    to restart).
"""

from __future__ import annotations

import logging
import os
import queue
import socket
import threading
import time

from . import observe, resilience
from .config import Config, _parse_interval
from .ingest import parser
from .metrics import FrameSet, InterMetric, MetricType
from .models.pipeline import (APPLY_CPU_TALLY, APPLY_PHASES, DECODE_TALLY,
                              LAND_PHASES, AggregationEngine, EngineConfig,
                              ForwardExport)
from .sinks import MetricSink
from .sinks.basic import (BlackholeMetricSink, DebugMetricSink,
                          LocalFilePlugin)

log = logging.getLogger("veneur_tpu.server")

_STOP = object()


def _fold_rewrite(pb, fr) -> int:
    """Apply an ImportFoldReroute's rewrite to the pb IN PLACE (the
    fold key is tagless by construction) and return the fold key's
    digest — the single-homed fold routing basis. One definition for
    both sites that re-route a fold (the worker loop, recovery
    replay): the rewrite diverging between live and replay would
    silently break the kill-restart bit-identity."""
    pb.name = fr.key.name
    del pb.tags[:]
    return fr.digest


class _WorkerQueue(queue.Queue):
    """A worker queue bounded in metrics waiting, not in items: an
    ImportedBatch weighs the forwarded sketches it carries, anything
    else one. A request travels as one item an engine, and 65,536
    requests of 6,500 sketches would be no bound (queue.Queue's
    documented _init/_qsize/_put/_get override points, as
    PriorityQueue uses them). A put is admitted while the weight
    waiting is under `maxsize`, so the bound overshoots by at most one
    batch; `unfinished_tasks` still counts items, which is what
    drain() settles on."""

    def _init(self, maxsize):
        super()._init(maxsize)
        self._weight = 0

    def _qsize(self):
        return self._weight

    def _put(self, item):
        self.queue.append(item)
        self._weight += self._weigh(item)

    def _get(self):
        item = self.queue.popleft()
        self._weight -= self._weigh(item)
        return item

    @staticmethod
    def _weigh(item) -> int:
        return len(getattr(item, "pbs", ())) or 1


# veneur.ssf.* self-metrics a native local drains from `bridge.stats()`,
# beside ssf.received / ssf.error: name -> the bridge's counter
SSF_BRIDGE_TELEMETRY = {
    "ssf.fallback": "ssf_fallbacks",
    "ssf.stream.frames": "ssf_stream_frames",
    "ssf.stream.connections": "ssf_stream_conns",
    "ssf.stream.connection_errors": "ssf_stream_conn_errors",
    "ssf.stream.read_ns": "ssf_stream_read_ns",
    "ssf.stream.ring_wait_ns": "ssf_stream_wait_ns",
}


class Server:
    # Flight-recorder rows ONE flush tick keeps of each kind of work
    # done between ticks (observe.StampLog budgets: past them a kind's
    # rows coalesce and its seconds stay exact). The import kinds are
    # sized for 100k keys a tick (~16 requests x 3 phases, ~13 landings
    # x 3) next to the tick's own 16-35 phases, `import.apply.request`
    # (the three children an engine stamps a request, APPLY_PHASES) for
    # a fleet's 32 requests a tick: 209 rows behind 32 senders of
    # 10,000 keys, inside the default flight_recorder_max_phases
    # (256); the pump's for a local, whose
    # tick has the forward's 3 phases a chunk besides — TickRecord.graft
    # folds what the tick has no slots left for, so none is dropped.
    GRAFT_BUDGET = {"import.request": 16, "import.apply": 12,
                    "import.apply.request": 32,
                    "import.land": 16, "ingest.pump.batch": 256}
    # a worker's busy runs closer than this are one `import.apply` run
    APPLY_MERGE_GAP_NS = 1_000_000

    def __init__(self, cfg: Config, sinks: list[MetricSink] | None = None,
                 plugins=None, forwarder=None, span_sinks=None):
        self.cfg = cfg
        self.hostname = cfg.hostname or (
            "" if cfg.omit_empty_hostname else socket.gethostname())
        # Native ingest: the C++ bridge owns interning over ONE engine's
        # slot space; its reader threads are the parallelism. A mesh
        # engine likewise owns the whole slot space (sharded over chips).
        self._mesh_mode = cfg.tpu_num_devices > 1
        n_workers = (1 if cfg.native_ingest or self._mesh_mode
                     else max(1, cfg.num_workers))
        ecfg_kw = dict(
            histogram_slots=max(256, cfg.tpu_histogram_slots // n_workers),
            counter_slots=max(128, cfg.tpu_counter_slots // n_workers),
            gauge_slots=max(128, cfg.tpu_gauge_slots // n_workers),
            set_slots=max(64, cfg.tpu_set_slots // n_workers),
            batch_size=cfg.tpu_batch_size,
            buffer_depth=cfg.tpu_buffer_depth,
            compression=cfg.tpu_compression,
            hll_precision=cfg.tpu_hll_precision,
            histogram_backend=cfg.histogram_backend,
            set_backend=cfg.set_backend,
            ull_precision=cfg.tpu_ull_precision,
            req_levels=cfg.tpu_req_levels,
            req_capacity=cfg.tpu_req_capacity,
            percentiles=tuple(cfg.percentiles),
            aggregates=tuple(cfg.aggregates),
            idle_ttl_intervals=cfg.tpu_slot_idle_ttl_intervals,
            forward_enabled=bool(cfg.forward_address
                                 or cfg.consul_forward_service_name),
            # a server with a gRPC import listener is (also) a global tier
            is_global=cfg.is_global or bool(cfg.grpc_listen_addresses),
            hostname=self.hostname,
        )
        if self._mesh_mode:
            # multi-chip serving: ONE engine whose banks are sharded
            # over a device mesh; slot routing replaces worker sharding
            # (SURVEY §7 step 7). Forward/import stay on the cluster
            # tier — the engine constructor enforces it.
            from .parallel.engine import (MESH_TELEMETRY,
                                          MeshAggregationEngine)
            self.engines = [MeshAggregationEngine(
                EngineConfig(**ecfg_kw),
                n_devices=cfg.tpu_num_devices)]
            self._mesh_telemetry = MESH_TELEMETRY
        else:
            self._mesh_telemetry = {}
            self.engines = [AggregationEngine(EngineConfig(**ecfg_kw))
                            for _ in range(n_workers)]
        if cfg.flight_recorder:
            # import landings stamp `import.land` (+ children) here,
            # and import_list a request's three `import.apply.*`; the
            # engine's flush hands them to the tick
            for eng in self.engines:
                eng.land_stamps = observe.StampLog({
                    **dict.fromkeys(LAND_PHASES,
                                    self.GRAFT_BUDGET["import.land"]),
                    **dict.fromkeys(
                        APPLY_PHASES,
                        self.GRAFT_BUDGET["import.apply.request"])})
        self.worker_queues: list[queue.Queue] = [
            _WorkerQueue(maxsize=65536) for _ in range(n_workers)]
        # per queue: until when a full queue sheds imports without
        # waiting (see _enqueue_import)
        self._import_shed_until = [0.0] * n_workers
        # Sketch-engine/wire stamp (ISSUE 10): declared on every
        # forwarded chunk and enforced on every import request — a
        # mixed fleet (peer running different sketch backends) is
        # refused loudly, never silently merged. One stamp per server:
        # all engines share the config's backends. The forward
        # centroid codec folds in (ISSUE 13, "h=tdigest/1q"): a
        # quantized-centroid fleet is a DIFFERENT wire format, and a
        # lossless peer must be refused before decode, not fed packed
        # rows it would misread as empty centroid lists.
        from . import sketches as _sketches
        self.engine_stamp = _sketches.stamp_with_codec(
            self.engines[0].engine_stamp, cfg.forward_centroid_codec)
        # Fleet-wide per-prefix cardinality (overload-defense
        # satellite): received Huffman-Bucket sketches merge-by-max
        # here, keyed by prefix; /debug/fleet serves the estimates.
        self._fleet_sketch_lock = threading.Lock()
        self._fleet_sketches: dict[str, bytearray] = {}
        self.native_bridge = None
        self.native_pump = None
        if cfg.native_ingest:
            self._setup_native_ingest()
        # Unified telemetry spine (observe/registry.py): every counter
        # this server owns — ingest, span pipeline, flush, sinks —
        # lives in a PER-SERVER registry (two servers in one process,
        # the chaos-harness topology, must never cross-count), while
        # egress/durability objects keep counting into the process
        # DEFAULT_REGISTRY; _self_metrics drains both. The historical
        # counter attributes (packets_received, ...) remain as
        # read-only properties over the registry. Built before the
        # sinks: the Prometheus scrape surface captures it.
        self.telemetry = observe.TelemetryRegistry()
        # Overload defense (ingest/admission.py): ONE controller shared
        # by every engine's KeyInterners (per-prefix key budgets +
        # fold-to-other) and by handle_packet (adaptive shed governor).
        # None = defense off, the regression-pinned pre-defense path.
        self.admission = None
        self._rate_corrected_types = None
        if cfg.overload_defense_enabled:
            if self.native_bridge is not None:
                log.warning(
                    "overload_defense_enabled has no effect with "
                    "native_ingest (the C++ bridge owns interning); "
                    "defense disabled")
            else:
                from .ingest import admission as _admission
                self.admission = _admission.from_config(cfg,
                                                        self.telemetry)
                self._rate_corrected_types = \
                    _admission.RATE_CORRECTED_TYPES
                # index/n/reroute single-home each fold key on the
                # engine its digest routes to — one flush, one row
                # per `__other__` series, however many workers
                for i, eng in enumerate(self.engines):
                    eng.attach_admission(
                        self.admission, index=i, n=len(self.engines),
                        reroute=self._route_metric)
        # one shared egress policy (retry/breaker knobs) for every
        # config-built sink and forwarder; per-destination breakers are
        # created inside each Egress
        self._egress_policy = resilience.policy_from_config(cfg)
        self.sinks = sinks if sinks is not None else self._sinks_from_config()
        if plugins is not None:
            self.plugins = plugins
        else:
            self.plugins = []
            if cfg.flush_file:
                self.plugins.append(LocalFilePlugin(
                    cfg.flush_file, max(1, round(cfg.interval_seconds))))
            if cfg.aws_s3_bucket:
                from .sinks.s3 import S3Plugin
                self.plugins.append(S3Plugin(
                    bucket=cfg.aws_s3_bucket, region=cfg.aws_region,
                    access_key=cfg.aws_access_key_id,
                    secret_key=cfg.aws_secret_access_key,
                    interval_s=max(1, round(cfg.interval_seconds)),
                    egress_policy=self._egress_policy))
        if forwarder is None and cfg.forward_address:
            if cfg.forward_use_grpc:
                from .cluster.forward import GrpcForwarder
                forwarder = GrpcForwarder(
                    cfg.forward_address,
                    timeout_s=cfg.flush_timeout_seconds,
                    egress_policy=self._egress_policy,
                    engine_stamp=self.engine_stamp,
                    centroid_codec=cfg.forward_centroid_codec)
            else:
                from .cluster.forward import HttpJsonForwarder
                forwarder = HttpJsonForwarder(
                    cfg.forward_address,
                    timeout_s=cfg.flush_timeout_seconds,
                    max_per_body=cfg.flush_max_per_body,
                    egress_policy=self._egress_policy,
                    engine_stamp=self.engine_stamp,
                    centroid_codec=cfg.forward_centroid_codec)
        elif forwarder is None and cfg.consul_forward_service_name:
            # discover the global tier via Consul and re-resolve on the
            # refresh interval (consul.go; Server.RefreshDestinations)
            from .cluster.discovery import ConsulDiscoverer
            from .cluster.forward import DiscoveringForwarder
            forwarder = DiscoveringForwarder(
                ConsulDiscoverer(),
                cfg.consul_forward_service_name,
                refresh_interval_s=_parse_interval(
                    cfg.consul_refresh_interval),
                use_grpc=cfg.forward_use_grpc,
                timeout_s=cfg.flush_timeout_seconds,
                max_per_body=cfg.flush_max_per_body,
                egress_policy=self._egress_policy,
                engine_stamp=self.engine_stamp,
                centroid_codec=cfg.forward_centroid_codec)
        # Durable state (off by default): crash-safe journals for the
        # sender's replay ladder + spill tier and the receiver's dedupe
        # watermarks. Recovery runs HERE, in the constructor — before
        # start() binds any listener — so a restarted process resumes
        # its ladder under the original envelopes and a restarted
        # global refuses ancient replays before the first RPC arrives.
        self._forward_journal = None
        self._dedupe_journal = None
        # (by here a configured forward_address/consul service has
        # already produced a concrete forwarder, so "will wrap" is
        # simply "a forwarder exists and is not already resilient")
        will_wrap = forwarder is not None and not isinstance(
            forwarder, resilience.ResilientForwarder)
        if cfg.durability_enabled and will_wrap:
            from .durability import ForwardJournal
            self._forward_journal = ForwardJournal(
                cfg.durability_dir,
                fsync=cfg.durability_fsync,
                fsync_interval_s=_parse_interval(
                    cfg.durability_fsync_interval),
                snapshot_journal_bytes=(
                    cfg.durability_snapshot_journal_bytes))
        if will_wrap:
            # lossless-forward contract: terminal failures spill the
            # interval's sketches for re-merge into the next flush
            # instead of dropping them (resilience.SpillBuffer)
            forwarder = resilience.ResilientForwarder(
                forwarder,
                destination=(cfg.forward_address
                             or cfg.consul_forward_service_name
                             or "forward"),
                max_spill_sketches=cfg.spill_max_sketches,
                gauge_max_age_intervals=(
                    cfg.spill_gauge_max_age_intervals),
                max_spill_intervals=cfg.spill_max_intervals,
                sender_id=(cfg.forward_sender_id or
                           resilience.new_sender_id(self.hostname)),
                # one wall budget for the whole replay ladder (plus the
                # current send's own retry_deadline): a flush tick can
                # stall at most ~3x retry_deadline, not
                # spill_max_intervals x retry_deadline
                replay_budget_s=2 * _parse_interval(cfg.retry_deadline),
                # delta forwarding (ISSUE 13): the flush loop asks
                # next_forward_kind() what to build each tick
                delta_enabled=cfg.forward_delta,
                full_resync_intervals=cfg.forward_full_resync_intervals,
                # recovery happens inside the constructor: parked
                # intervals come back with their original envelopes
                journal=self._forward_journal)
        self.forwarder = forwarder   # callable(ForwardExport) or None
        # Receiver side of the exactly-once contract: one dedupe ledger
        # shared by the gRPC importsrv and the HTTP /import path, so a
        # sender that fails over between contracts still dedupes.
        self.dedupe_ledger = None
        if cfg.forward_dedupe_enabled and (
                cfg.grpc_listen_addresses or cfg.http_address
                or cfg.is_global):
            from .cluster.importsrv import DedupeLedger
            self.dedupe_ledger = DedupeLedger(
                max_seqs_per_sender=(
                    cfg.forward_dedupe_max_seqs_per_sender),
                max_senders=cfg.forward_dedupe_max_senders,
                ttl_s=_parse_interval(cfg.forward_dedupe_ttl))
            if cfg.durability_enabled:
                # recovery-before-listen: restore the per-sender
                # watermarks the last incarnation flushed under, so an
                # ancient replay (already flushed downstream before the
                # crash) is dropped, not double-counted
                from .durability import WatermarkJournal
                self._dedupe_journal = WatermarkJournal(
                    cfg.durability_dir,
                    fsync=cfg.durability_fsync,
                    fsync_interval_s=_parse_interval(
                        cfg.durability_fsync_interval))
                marks = self._dedupe_journal.load()
                if marks:
                    n = self.dedupe_ledger.restore_watermarks(marks)
                    resilience.DEFAULT_REGISTRY.incr(
                        "import", "durability.recovered_watermarks", n)
                # watermarks are journaled ONE TICK BEHIND (see
                # flush_once): a seq admitted mid-tick may still be
                # sitting in a worker queue when this tick's engines
                # drain, so only the PREVIOUS tick's snapshot — whose
                # data has had a full interval to land and flush — is
                # safe to make a durable hard-drop floor
                self._pending_watermarks: dict = {}
        # Global-tier engine checkpointing (durability/ ISSUE 9): the
        # piece the watermark journal alone cannot give — an interval
        # the global ADMITTED AND ACKED is never replayed by its
        # sender, so its merged sketch state used to die with the
        # process. When armed, every admitted import op is write-ahead
        # journaled (inside _submit_import_batch, before the worker
        # queues and therefore before the ack), and each flush
        # boundary appends a self-contained per-engine delta
        # checkpoint (dirty piles + interner tables + staged imports +
        # the applied-op watermark). Recovery runs HERE, before any
        # listener binds: restore the latest checkpoint group per
        # engine, then replay ops above each engine's watermark
        # through the normal digest routing — the restarted global
        # flushes BIT-IDENTICAL state (chaos-gated in
        # tests/test_exactly_once_chaos.py).
        self._engine_journal = None
        self._engine_journal_armed = False
        self._recovery = None            # restore stats for /debug, health
        self._recovering = False         # True until start() completes
        self._next_import_op = 0
        self._recent_import_ops: list = []   # (op_id, bytes), 2-tick window
        self._import_ops_evicted = False     # cap evicted since last seal
        self._ops_at_last_checkpoint = 0
        self._last_checkpoint_sig = None
        self._last_checkpoint_t = None
        self._last_checkpoint_stats = (0, 0)   # (dirty, total) piles
        self._import_submit_lock = threading.Lock()
        # Time-travel query tier (durability/history.py, ISSUE 14):
        # retained window of committed checkpoint generations + the
        # GET /query read path. Armed below, with the engine journal.
        self._history = None
        self._query_tier = None
        self._history_baseline = None      # (recs, marks, empty) of
        #                                    the prev boundary — the
        #                                    next generation's baseline
        self._history_prev_close_ns = 0
        # Arming keys on the IMPORT tiers (a gRPC import listener or a
        # declared global), NOT on http_address alone: http_address is
        # also just the ops/healthcheck listener on sending-tier
        # servers, which would otherwise pay dirty-bitmap marking on
        # the UDP hot path plus a per-tick checkpoint+fsync for state
        # that is never write-aheaded (UDP is lossy by contract). A
        # global that receives ONLY over HTTP /import must set
        # `is_global: true` to get checkpointing.
        if cfg.durability_enabled and cfg.durability_engine_snapshot \
                and (cfg.grpc_listen_addresses or cfg.is_global):
            if self._mesh_mode or self.native_bridge is not None:
                log.warning(
                    "durability_engine_snapshot has no effect with a "
                    "mesh engine or native_ingest (the %s owns the "
                    "banks/interner); engine checkpointing disabled",
                    "mesh" if self._mesh_mode else "native bridge")
            else:
                from .durability import EngineJournal
                self._engine_journal_armed = True
                self._recovering = True
                self._engine_journal = EngineJournal(
                    cfg.durability_dir,
                    fsync=cfg.durability_fsync,
                    fsync_interval_s=_parse_interval(
                        cfg.durability_fsync_interval),
                    snapshot_journal_bytes=(
                        cfg.durability_snapshot_journal_bytes))
                for eng in self.engines:
                    eng.enable_dirty_tracking(
                        cfg.durability_engine_delta_threshold)
                self._recover_engine_state()
        # Fleet-scope tracing, receiver half (observe/fleet.py): the
        # per-sender e2e/freshness view plus the import observer that
        # phase-attributes each import request and parents its spans on
        # the remote sender's flush span. Built for the same servers
        # that can receive forwards; observability only — admission and
        # apply behavior is identical with it on or off.
        self.fleet = None
        self.import_observer = None
        self._import_stamps = None
        if cfg.grpc_listen_addresses or cfg.http_address or cfg.is_global:
            self.fleet = observe.FleetView(
                max_senders=cfg.fleet_max_senders,
                window=cfg.fleet_e2e_window)
            import_ring = None
            if cfg.flight_recorder:
                import_ring = observe.FlightRecorder(
                    capacity=cfg.flight_recorder_ticks, max_phases=16)
                # import work done between flushes — per request on
                # handler threads, per busy run on worker threads —
                # leaves its edges here for the next flush tick
                self._import_stamps = observe.StampLog({
                    "import." + n: self.GRAFT_BUDGET["import.request"]
                    for n in observe.REQUEST_PHASES}
                    | {"import.apply": self.GRAFT_BUDGET["import.apply"]})
            self.import_observer = observe.ImportObserver(
                fleet=self.fleet, flight=import_ring,
                client=lambda: self.trace_client,
                stamps=self._import_stamps)
        self._grpc_servers = []
        # tags_exclude strips tag names BEFORE key construction (metrics
        # differing only in an excluded tag aggregate together), in both
        # the Python parser and the C++ bridge's.
        self._exclude_tags = frozenset(cfg.tags_exclude) or None
        # parser hardening bounds (counted rejection, never an
        # unbounded interned key)
        self._max_name_len = cfg.metric_max_name_length
        self._max_tag_len = cfg.metric_max_tag_length
        if self.native_bridge is not None and (
                self._max_name_len != parser.MAX_NAME_LENGTH
                or self._max_tag_len != parser.MAX_TAG_LENGTH):
            log.warning(
                "metric_max_name_length/metric_max_tag_length have no "
                "effect with native_ingest (the C++ bridge parses and "
                "interns without the bounds)")
        if self._exclude_tags and self.native_bridge is not None:
            self.native_bridge.set_tags_exclude(sorted(
                self._exclude_tags))
        # stats_address: ship veneur.* self-metrics there as DogStatsD
        # over UDP (the reference's scopedstatsd client, usually pointed
        # at the local veneur itself); unset = inject into our own flush.
        self._stats_sock = None
        if cfg.stats_address:
            host, _, port = cfg.stats_address.rpartition(":")
            fam = (socket.AF_INET6 if ":" in host.strip("[]")
                   else socket.AF_INET)
            self._stats_sock = socket.socket(fam, socket.SOCK_DGRAM)
            self._stats_dest = (host.strip("[]") or "127.0.0.1",
                                int(port))
        # Self-tracing (flusher.go: spans around flush/forward): when an
        # SSF UDP listener exists, point a trace client back at it so
        # the server traces itself through its own ingest path.
        self.trace_client = None
        self._ssf_udp_sock = None
        self.ssf_native_port = None   # set by the native SSF listener
        self._sentry = None
        if cfg.sentry_dsn:
            from .utils.sentry import SentryClient
            self._sentry = SentryClient(cfg.sentry_dsn)
        # in-flight fan-out threads (flusher-thread-only): a sink whose
        # previous flush is still running skips the interval instead of
        # delaying the tick (flusher.go's per-sink goroutines never
        # block the ticker). Per-sink flush stats/skips now ride the
        # telemetry registry (scope "sink:<name>") and drain next
        # interval like every other counter.
        self._sink_inflight: dict[tuple, threading.Thread] = {}

        self._threads: list[threading.Thread] = []
        self._sockets: list[socket.socket] = []
        self._listen_socks: list[socket.socket] = []  # stream accept socks
        self._stream_conns: set[socket.socket] = set()
        self._conns_lock = threading.Lock()
        self._stop = threading.Event()
        self._started = False        # flipped at the end of start()
        self._last_flush_ok = time.monotonic()
        # Flight recorder: the bounded ring of per-tick phase trees
        # behind /debug/flush, SSF self-tracing, and the
        # veneur.flush.phase.* dogfood timers. Strictly process-local.
        self.flight = None
        if cfg.flight_recorder:
            self.flight = observe.FlightRecorder(
                capacity=cfg.flight_recorder_ticks,
                max_phases=cfg.flight_recorder_max_phases)
        # Time-travel query tier (ISSUE 14): armed with the engine
        # journal (recovery already ran above), built HERE because its
        # query ticks adopt into the flight ring just created
        if self._engine_journal_armed \
                and cfg.history_retention_generations > 0:
            self._setup_history()
        # on-demand jax.profiler capture around flush ticks (see
        # _maybe_profile); written under _stats_lock
        self._profile_ticks = 0
        self._profile_active = False
        self._last_forward_err = None   # sentry dedupe, under _stats_lock
        # last interval's forward bytes by destination/kind (sampled
        # around the forward call each tick; under _stats_lock)
        self._last_forward_bytes = None
        self._stats_lock = threading.Lock()
        # SSF span pipeline (SpanWorker + SpanSinks)
        self.span_queue: queue.Queue = queue.Queue(
            maxsize=max(1, cfg.ssf_buffer_size))
        self.span_sinks = (span_sinks if span_sinks is not None
                           else self._span_sinks_from_config())
        # Native SSF fast path: when the only span consumer is the
        # ssfmetrics bridge, the C++ bridge decodes span datagrams and
        # stages their embedded samples straight into the rings — the
        # Python span pipeline (decode -> queue -> worker -> extract ->
        # re-submit -> per-sample process) costs ~75us/span where the
        # native path is a few us. Spans the fast path can't express
        # (STATUS samples -> service checks) fall back per-datagram.
        from .sinks.ssfmetrics import SSFMetricsSink
        self._native_ssf = (
            self.native_bridge is not None
            and len(self.span_sinks) == 1
            and type(self.span_sinks[0]) is SSFMetricsSink)
        if self._native_ssf:
            # the sink's configured timer name, not cfg's: a caller may
            # construct the sink directly with its own name, and the
            # fallback (Python) path would use that — both paths must
            # derive the same indicator timer
            timer_name = self.span_sinks[0]._timer_name
            if timer_name:
                self.native_bridge.set_indicator_timer(timer_name)

    # ------------- telemetry accessors (registry-backed) -------------
    # The historical counter attributes, preserved as read-only views
    # over the unified registry: interval-delta (reset at each flush's
    # drain), exactly like the attribute counters they replace.

    def _peek(self, name: str) -> int:
        return self.telemetry.peek(observe.SERVER_SCOPE, name)

    def _count(self, name: str, n: int = 1):
        self.telemetry.incr(observe.SERVER_SCOPE, name, n)

    @property
    def packets_received(self) -> int:
        return self._peek("packet.received")

    @property
    def parse_errors(self) -> int:
        return self._peek("packet.error")

    @property
    def queue_drops(self) -> int:
        return self._peek("worker.dropped")

    @property
    def spans_received(self) -> int:
        return self._peek("ssf.received")

    @property
    def ssf_errors(self) -> int:
        return self._peek("ssf.error")

    @property
    def flush_errors(self) -> int:
        return self._peek("flush.error")

    @property
    def import_rejected(self) -> int:
        return self._peek("import.rejected")

    @property
    def flush_count(self) -> int:
        """Completed flush ticks since start (a level: never drained)."""
        return self.telemetry.level(observe.SERVER_SCOPE, "flush.count")

    # ------------- construction helpers -------------

    def _setup_native_ingest(self):
        """Swap the single engine's KeyInterners for views over the C++
        interning bridge, and build the pump that drains its sample
        rings into the engine's batch kernels."""
        from .ingest.native import BridgeKeyView, NativeBridge, NativePump

        eng = self.engines[0]
        ecfg = eng.cfg
        self.native_bridge = NativeBridge(
            histo_slots=ecfg.histogram_slots,
            counter_slots=ecfg.counter_slots,
            gauge_slots=ecfg.gauge_slots,
            set_slots=ecfg.set_slots,
            hll_precision=ecfg.hll_precision,
            idle_ttl=ecfg.idle_ttl_intervals,
            ring_capacity=self.cfg.native_ring_capacity,
            max_packet=self.cfg.metric_max_length)
        views = {b: BridgeKeyView(self.native_bridge, b)
                 for b in ("histo", "counter", "gauge", "set")}
        eng.histo_keys = views["histo"]
        eng.counter_keys = views["counter"]
        eng.gauge_keys = views["gauge"]
        eng.set_keys = views["set"]
        # a gauge that reaches the engine by the Python path (a
        # slow-path line, a fallback span) is ordered among the
        # bridge's datagrams by the bridge's own arrival count
        eng.gauge_clock = self.native_bridge.next_arrival

        def slow_path(line: bytes):
            """Lines the C++ parser routes to Python: events, service
            checks, CPython-float oddities, invalid UTF-8. Must apply
            the same tags_exclude as the fast path or one logical
            metric splits into two series."""
            try:
                item = parser.parse_packet(line, self._exclude_tags,
                                           self._max_name_len,
                                           self._max_tag_len)
            except parser.ParseError:
                self._count("packet.error")
                return
            self._route_metric(item)

        def ssf_slow_path(payload: bytes):
            """SSF datagrams the native listener routed back (STATUS
            samples -> service checks need Python semantics)."""
            from .ssf import framing
            try:
                span = framing.parse_ssf_datagram(payload)
            except framing.FramingError:
                self._count("ssf.error")
                return
            self.handle_ssf_span(span)

        self.native_pump = NativePump(
            self.native_bridge, eng, views, slow_path,
            batch=self.cfg.native_pump_batch,
            ssf_slow_path=ssf_slow_path,
            stamps=(observe.StampLog(
                {"ingest.pump.batch": self.GRAFT_BUDGET["ingest.pump.batch"]})
                if self.cfg.flight_recorder else None))

    def _sinks_from_config(self) -> list[MetricSink]:
        out: list[MetricSink] = []
        cfg = self.cfg
        # every network sink gets the configured per-attempt timeout
        # (flush_timeout) and the shared retry/breaker policy — the
        # CF01-class bug was each constructor keeping its hardcoded 10s
        pol = self._egress_policy
        to = cfg.flush_timeout_seconds
        if cfg.datadog_api_key:
            from .sinks.datadog import DatadogMetricSink
            out.append(DatadogMetricSink(
                api_key=cfg.datadog_api_key,
                api_url=cfg.datadog_api_hostname,
                hostname=self.hostname,
                tags=list(cfg.tags),
                interval_s=max(1, round(cfg.interval_seconds)),
                flush_max_per_body=cfg.datadog_flush_max_per_body,
                timeout_s=to, egress_policy=pol))
        if cfg.signalfx_api_key:
            from .sinks.signalfx import SignalFxMetricSink
            out.append(SignalFxMetricSink(
                api_key=cfg.signalfx_api_key,
                endpoint=cfg.signalfx_endpoint_base,
                hostname=self.hostname, tags=list(cfg.tags),
                vary_key_by=cfg.signalfx_vary_key_by,
                timeout_s=to, egress_policy=pol))
        if cfg.kafka_broker and (cfg.kafka_metric_topic or cfg.kafka_topic):
            from .sinks.kafka import KafkaMetricSink
            out.append(KafkaMetricSink(
                broker=cfg.kafka_broker,
                metric_topic=cfg.kafka_metric_topic or cfg.kafka_topic,
                egress_policy=pol))
        if cfg.newrelic_insert_key:
            from .sinks.newrelic import NewRelicMetricSink
            out.append(NewRelicMetricSink(
                insert_key=cfg.newrelic_insert_key,
                account_id=cfg.newrelic_account_id,
                tags=list(cfg.tags),
                interval_s=cfg.interval_seconds,
                timeout_s=to, egress_policy=pol))
        if cfg.prometheus_repeater_address:
            from .sinks.prometheus import PrometheusMetricSink
            out.append(PrometheusMetricSink(
                listen_address=cfg.prometheus_repeater_address,
                # one scrape surface for ALL veneur.* self-metrics:
                # this server's telemetry spine + the process-default
                # egress/durability registry
                registries=(self.telemetry,
                            resilience.DEFAULT_REGISTRY)))
        if cfg.debug:
            out.append(DebugMetricSink())
        if not out:
            out.append(BlackholeMetricSink())
        return out

    def _span_sinks_from_config(self):
        """Span egress: always include the ssfmetrics bridge so embedded
        samples reach the metric pipeline (sinks/ssfmetrics)."""
        from .sinks.ssfmetrics import SSFMetricsSink

        pol = self._egress_policy
        to = self.cfg.flush_timeout_seconds
        out = [SSFMetricsSink(
            self._route_metric,
            indicator_span_timer_name=self.cfg.indicator_span_timer_name)]
        if self.cfg.datadog_trace_api_address:
            from .sinks.datadog import DatadogSpanSink
            out.append(DatadogSpanSink(
                trace_api_address=self.cfg.datadog_trace_api_address,
                buffer_size=self.cfg.ssf_buffer_size,
                timeout_s=to, egress_policy=pol))
        if self.cfg.splunk_hec_address:
            from .sinks.splunk import SplunkSpanSink
            out.append(SplunkSpanSink(
                hec_address=self.cfg.splunk_hec_address,
                token=self.cfg.splunk_hec_token,
                hostname=self.hostname,
                timeout_s=to, egress_policy=pol))
        if self.cfg.xray_address:
            from .sinks.xray import XRaySpanSink
            out.append(XRaySpanSink(daemon_address=self.cfg.xray_address))
        if self.cfg.falconer_address:
            from .sinks.grpsink import GrpcSpanSink
            out.append(GrpcSpanSink(self.cfg.falconer_address,
                                    timeout_s=to, egress_policy=pol))
        if self.cfg.kafka_broker and self.cfg.kafka_span_topic:
            from .sinks.kafka import KafkaSpanSink
            out.append(KafkaSpanSink(
                broker=self.cfg.kafka_broker,
                span_topic=self.cfg.kafka_span_topic,
                egress_policy=pol))
        if self.cfg.lightstep_access_token:
            from .sinks.lightstep import LightStepSpanSink
            out.append(LightStepSpanSink(
                access_token=self.cfg.lightstep_access_token,
                collector_url=self.cfg.lightstep_collector_host,
                hostname=self.hostname,
                timeout_s=to, egress_policy=pol))
        if self.cfg.debug:
            from .sinks.basic import BlackholeSpanSink
            out.append(BlackholeSpanSink())
        return out

    # ------------- lifecycle -------------

    def start(self):
        # Precompile the device programs BEFORE any listener or the
        # watchdog exists: a cold backend pays the whole compile bill
        # here, not inside flush 0 where it would overrun
        # watchdog_missed_flushes intervals.
        # Engines with identical shapes share executables, so this
        # compiles once and executes cheaply n_workers times.
        t0 = time.monotonic()
        for eng in self.engines:
            eng.warmup()
        if self.native_pump is not None and \
                self.native_pump.batch != self.engines[0].cfg.batch_size:
            # the pump dispatches at its own width; compile those
            # executables now, not inline under the ingest lock
            self.engines[0].warm_ingest_kernels(self.native_pump.batch)
        warm_s = time.monotonic() - t0
        if warm_s > 1.0:
            log.info("engine warmup (device program compile): %.1fs",
                     warm_s)
        for s in self.sinks:
            try:
                s.start()
            except Exception as e:
                log.error("sink %s failed to start: %s", s.name(), e)
        for i, q in enumerate(self.worker_queues):
            t = threading.Thread(target=self._worker_loop, args=(i, q),
                                 name=f"worker-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        for addr in self.cfg.statsd_listen_addresses:
            self._start_statsd_listener(addr)
        for addr in self.cfg.ssf_listen_addresses:
            self._start_ssf_listener(addr)
        if self.trace_client is None:
            trace_port = None
            if self._ssf_udp_sock is not None:
                trace_port = self._ssf_udp_sock.getsockname()[1]
            elif getattr(self, "ssf_native_port", None):
                trace_port = self.ssf_native_port  # native SSF listener
            if trace_port is not None:
                from . import trace
                self.trace_client = trace.Client(
                    f"udp://127.0.0.1:{trace_port}")
        if self.cfg.enable_profiling:
            self._start_profiling()
        for addr in self.cfg.grpc_listen_addresses:
            self._start_import_listener(addr)
        for ss in self.span_sinks:
            try:
                ss.start()
            except Exception as e:
                log.error("span sink %s failed to start: %s",
                          ss.name(), e)
        t = threading.Thread(target=self._span_worker, name="span-worker",
                             daemon=True)
        t.start()
        self._threads.append(t)
        if self.cfg.http_address:
            self._start_http_api(self.cfg.http_address)
        if self.native_pump is not None:
            self.native_pump.start()
        # watchdog epoch starts after warmup — compile time is not lag
        self._last_flush_ok = time.monotonic()
        t = threading.Thread(target=self._flush_loop, name="flusher",
                             daemon=True)
        t.start()
        self._threads.append(t)
        # the watchdog thread ALWAYS runs: it counts overdue ticks
        # (veneur.watchdog.stalled_ticks_total — the outside-visible
        # stall signal behind /healthz) every interval; the crash-only
        # exit stays gated on flush_watchdog_missed_flushes > 0
        t = threading.Thread(target=self._watchdog, name="watchdog",
                             daemon=True)
        t.start()
        self._threads.append(t)
        # vlint: disable=TH01 reason=monotonic one-way flag; readers
        # (health probes) tolerate either order around startup
        self._recovering = False
        # vlint: disable=TH01 reason=monotonic one-way flag; readers
        # (health probes) tolerate either order around startup
        self._started = True

    def stop(self, *, grace: float | None = None, clock=time.monotonic,
             sleep=time.sleep):
        self._stop.set()
        if getattr(self, "http_api", None) is not None:
            try:
                self.http_api.stop()
            except Exception:
                pass
        # graceful importsrv shutdown: reject new RPCs immediately but
        # let in-flight SendMetrics finish routing onto the worker
        # queues — their chunks are already recorded in the dedupe
        # ledger, so killing them mid-stream would strand entries the
        # sender will never replay. clock/sleep are injectable (fault
        # harness) so the grace-expiry path is testable without real
        # waiting.
        from .cluster.importsrv import stop_import_server
        if grace is None:
            grace = min(2.0, self.cfg.interval_seconds)
        for g in self._grpc_servers:
            try:
                stop_import_server(g, grace, clock=clock, sleep=sleep)
            except Exception:
                pass
        if self.dedupe_ledger is not None:
            self.dedupe_ledger.clear()   # torn down only after drain
        for q in self.worker_queues:
            try:
                q.put_nowait(_STOP)
            except queue.Full:
                pass
        try:
            self.span_queue.put_nowait(_STOP)
        except queue.Full:
            pass
        with self._conns_lock:
            conns = list(self._stream_conns)
        for c in conns:
            # shutdown (not just close) so reader threads blocked in
            # recv() wake up immediately
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        for s in self._sockets + self._listen_socks + conns:
            try:
                s.close()
            except OSError:
                pass
        if self.native_pump is not None:
            self.native_pump.stop()
        if self.native_bridge is not None:
            self.native_bridge.stop()
        # the fan-out never joins sink threads; drain them here (bounded)
        # so the final interval's data isn't killed mid-POST at exit and
        # sinks aren't stop()ed under an in-flight flush
        deadline = time.monotonic() + min(
            10.0, self.cfg.interval_seconds)
        for t in list(self._sink_inflight.values()):
            while True:
                try:
                    t.join(max(0.0, deadline - time.monotonic()))
                    break
                except RuntimeError:   # registered but not yet started
                    if time.monotonic() >= deadline:
                        break
                    time.sleep(0.005)
        for s in self.sinks + self.span_sinks:
            try:
                s.stop()
            except Exception:
                pass
        # durable shutdown: push every journal record to disk and
        # release the file handles, so a restart from the same
        # durability_dir starts clean (the crash path skips this — the
        # journal's torn-write tolerance covers it)
        for j in (self._forward_journal, self._dedupe_journal,
                  self._engine_journal):
            if j is not None:
                try:
                    j.close()
                except Exception:
                    log.exception("durability journal close failed")
        if self._query_tier is not None:
            # the history store itself holds no file handles (segments
            # and the manifest publish atomically per boundary); only
            # the query executor needs shutting down
            try:
                self._query_tier.close()
            except Exception:
                pass
        if self.trace_client is not None:
            try:
                self.trace_client.close()
            except Exception:
                pass
        if self._stats_sock is not None:
            try:
                self._stats_sock.close()
            except OSError:
                pass

    # ------------- ingest -------------

    @staticmethod
    def _resolve_inet(scheme: str, rest: str):
        """'host:port' (+scheme suffix 4/6, brackets allowed) → (family,
        bind_addr). udp6://[::1]:8126 must bind an AF_INET6 socket."""
        host, _, port = rest.rpartition(":")
        host = host.strip("[]")
        if scheme.endswith("6"):
            family = socket.AF_INET6
            host = host or "::"
        elif scheme.endswith("4"):
            family = socket.AF_INET
            host = host or "0.0.0.0"
        else:
            family = socket.AF_INET6 if ":" in host else socket.AF_INET
            host = host or "0.0.0.0"
        return family, (host, int(port))

    def _start_statsd_listener(self, addr: str):
        scheme, _, rest = addr.partition("://")
        if scheme in ("udp", "udp4", "udp6"):
            family, bind_addr = self._resolve_inet(scheme, rest)
            if self.native_bridge is not None:
                # the bridge only accepts numeric addresses; resolve
                # hostnames here (the Python path's bind() would too)
                host = socket.getaddrinfo(
                    bind_addr[0], bind_addr[1], family,
                    socket.SOCK_DGRAM)[0][4][0]
                self.native_bridge.start_udp(
                    host, bind_addr[1], max(1, self.cfg.num_readers),
                    rcvbuf=self.cfg.read_buffer_size_bytes)
                return
            for ri in range(max(1, self.cfg.num_readers)):
                sock = socket.socket(family, socket.SOCK_DGRAM)
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                if hasattr(socket, "SO_REUSEPORT"):
                    sock.setsockopt(socket.SOL_SOCKET,
                                    socket.SO_REUSEPORT, 1)
                try:
                    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                                    self.cfg.read_buffer_size_bytes)
                except OSError:
                    pass
                sock.bind(bind_addr)
                self._sockets.append(sock)
                t = threading.Thread(
                    target=self._read_metric_socket, args=(sock,),
                    name=f"udp-reader-{ri}", daemon=True)
                t.start()
                self._threads.append(t)
        elif scheme in ("tcp", "tcp4", "tcp6", "unix"):
            # statsd over streams (networking.go: StartStatsd's TCP/UNIX
            # arms), newline-delimited; TLS (incl. mutual) when the
            # config's tls_* triple is set
            if scheme != "unix":
                family, bind_addr = self._resolve_inet(scheme, rest)
                lsock = socket.socket(family, socket.SOCK_STREAM)
                lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                lsock.bind(bind_addr)
            else:
                if os.path.exists(rest):
                    os.unlink(rest)
                lsock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                lsock.bind(rest)
            lsock.listen(128)
            self._listen_socks.append(lsock)
            ssl_ctx = self._tls_context() if scheme != "unix" else None
            t = threading.Thread(
                target=self._accept_statsd_streams, args=(lsock, ssl_ctx),
                name=f"statsd-{scheme}-accept", daemon=True)
            t.start()
            self._threads.append(t)
        else:
            raise ValueError(f"unsupported statsd listener {addr!r}")

    def _tls_context(self):
        """Server-side TLS from the config triple (networking.go: the
        tls_key / tls_certificate pair enables TLS on TCP statsd;
        tls_authority_certificate additionally demands client certs —
        mutual TLS)."""
        if not (self.cfg.tls_key and self.cfg.tls_certificate):
            return None
        import ssl
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(certfile=self.cfg.tls_certificate,
                            keyfile=self.cfg.tls_key)
        if self.cfg.tls_authority_certificate:
            ctx.load_verify_locations(
                cafile=self.cfg.tls_authority_certificate)
            ctx.verify_mode = ssl.CERT_REQUIRED
        return ctx

    def _accept_statsd_streams(self, lsock: socket.socket, ssl_ctx):
        while not self._stop.is_set():
            try:
                conn, _ = lsock.accept()
            except OSError:
                break
            if ssl_ctx is not None:
                try:
                    conn = ssl_ctx.wrap_socket(conn, server_side=True)
                except Exception:
                    self._count("packet.error")
                    try:
                        conn.close()
                    except OSError:
                        pass
                    continue
            with self._conns_lock:
                self._stream_conns.add(conn)
            threading.Thread(target=self._read_statsd_stream, args=(conn,),
                             name="statsd-stream", daemon=True).start()

    def _read_statsd_stream(self, conn: socket.socket):
        """Newline-delimited metric lines over a stream connection; a
        line split across reads is reassembled. An oversized line is
        dropped IN FULL: after the drop the reader stays in discard
        mode until the line's terminating newline arrives, so the
        line's later bytes can never be parsed as a fresh metric."""
        max_len = self.cfg.metric_max_length
        tail = b""
        discarding = False
        try:
            with conn:
                while not self._stop.is_set():
                    try:
                        data = conn.recv(65536)
                    except OSError:
                        return
                    if not data:
                        if tail and not discarding:
                            self.handle_packet(tail)
                        return
                    if discarding:
                        nl = data.find(b"\n")
                        if nl < 0:
                            continue
                        data = data[nl + 1:]
                        discarding = False
                        if not data:
                            continue
                    buf = tail + data
                    nl = buf.rfind(b"\n")
                    if nl < 0:
                        tail = buf
                        if len(tail) > max_len:
                            # oversized garbage line: drop, count, and
                            # swallow the rest of it
                            self._count("packet.error")
                            tail = b""
                            discarding = True
                        continue
                    self.handle_packet(buf[:nl])
                    tail = buf[nl + 1:]
                    if len(tail) > max_len:
                        self._count("packet.error")
                        tail = b""
                        discarding = True
        finally:
            with self._conns_lock:
                self._stream_conns.discard(conn)

    def _start_ssf_listener(self, addr: str):
        """SSF ingest (Server.StartSSF): udp:// datagrams carry bare
        SSFSpan protobufs; tcp:// and unix:// carry framed streams
        (protocol.ReadSSF)."""
        scheme, _, rest = addr.partition("://")
        if scheme in ("udp", "udp4", "udp6"):
            family, bind_addr = self._resolve_inet(scheme, rest)
            if self._native_ssf and family != socket.AF_INET6:
                # C++ SSF readers: recvmmsg + native decode + ring
                # staging; no Python thread owns this socket. Fallback
                # datagrams come back through the pump's ssf_slow_path.
                self.ssf_native_port = self.native_bridge.start_ssf_udp(
                    bind_addr[0], bind_addr[1],
                    n_readers=max(1, self.cfg.num_readers),
                    rcvbuf=self.cfg.read_buffer_size_bytes,
                    max_dgram=self.cfg.trace_max_length_bytes)
                log.info("native SSF listener on udp://%s:%d",
                         bind_addr[0], self.ssf_native_port)
                return
            sock = socket.socket(family, socket.SOCK_DGRAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind(bind_addr)
            self._sockets.append(sock)
            if self._ssf_udp_sock is None:
                self._ssf_udp_sock = sock  # self-trace target
            t = threading.Thread(target=self._read_ssf_packet_socket,
                                 args=(sock,), name="ssf-udp-reader",
                                 daemon=True)
        elif scheme in ("tcp", "tcp4", "tcp6", "unix"):
            if scheme != "unix":
                family, bind_addr = self._resolve_inet(scheme, rest)
                lsock = socket.socket(family, socket.SOCK_STREAM)
                lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                lsock.bind(bind_addr)
            else:
                if os.path.exists(rest):
                    os.unlink(rest)
                lsock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                lsock.bind(rest)
            lsock.listen(128)
            self._listen_socks.append(lsock)
            if self._native_ssf:
                # C++ stream readers: the bridge accepts on this socket
                # and reads, cuts and decodes the frames itself, one
                # thread a connection and no Python thread on the path;
                # fallback frames come back through the pump's
                # ssf_slow_path, and stop() closes what it accepted.
                # Every other case (a span sink besides the ssfmetrics
                # bridge, no bridge) keeps the Python loop below.
                self.native_bridge.start_ssf_stream(lsock.fileno())
                log.info("native SSF stream listener on %s", addr)
                return
            t = threading.Thread(target=self._accept_ssf_streams,
                                 args=(lsock,), name=f"ssf-{scheme}-accept",
                                 daemon=True)
        else:
            raise ValueError(f"unsupported SSF listener {addr!r}")
        t.start()
        self._threads.append(t)

    def _read_ssf_packet_socket(self, sock: socket.socket):
        """Server.ReadSSFPacketSocket: one datagram = one SSFSpan."""
        from .ssf import framing

        max_len = self.cfg.trace_max_length_bytes
        native_ssf = self._native_ssf
        while not self._stop.is_set():
            try:
                data, _ = sock.recvfrom(max_len)
            except OSError:
                break
            if native_ssf:
                rc = self.native_bridge.handle_ssf(data)
                if rc == 1:
                    # samples staged in the rings; the pump lands them.
                    # Counted by the bridge's ssf_spans (folded into
                    # telemetry) — NOT spans_received, which would
                    # double-report the same span.
                    continue
                if rc < 0:
                    self._count("ssf.error")
                    continue
                # rc == 0: STATUS samples present — Python path below
            try:
                span = framing.parse_ssf_datagram(data)
            except framing.FramingError:
                self._count("ssf.error")
                continue
            self.handle_ssf_span(span)

    def _accept_ssf_streams(self, lsock: socket.socket):
        while not self._stop.is_set():
            try:
                conn, _ = lsock.accept()
            except OSError:
                break
            with self._conns_lock:
                self._stream_conns.add(conn)
            threading.Thread(target=self._read_ssf_stream, args=(conn,),
                             name="ssf-stream", daemon=True).start()

    def _read_ssf_stream(self, conn: socket.socket):
        """Server.HandleTracePacket over a framed stream; a corrupt
        frame poisons only its own connection. The loop of a listener
        the bridge does not read itself (`_start_ssf_listener`): every
        span goes through the span pipeline."""
        from .ssf import framing

        try:
            with conn:
                while not self._stop.is_set():
                    try:
                        span = framing.read_ssf(conn)
                        if span is None:
                            return
                    except (framing.FramingError, EOFError, OSError):
                        self._count("ssf.error")
                        return
                    self.handle_ssf_span(span)
        finally:
            with self._conns_lock:
                self._stream_conns.discard(conn)

    def handle_ssf_span(self, span):
        """Route one ingested span to the SpanWorker (drop-on-full,
        counted, like the reference's SpanChan)."""
        try:
            self.span_queue.put_nowait(span)
        except queue.Full:
            self._count("worker.dropped")
        # counted after the enqueue so a waiter that observes the count
        # and then drain()s cannot race ahead of the item
        self._count("ssf.received")

    def _span_worker(self):
        """SpanWorker: fan each span out to every span sink."""
        while True:
            span = self.span_queue.get()
            try:
                if span is _STOP:
                    break
                for ss in self.span_sinks:
                    try:
                        ss.ingest(span)
                    except Exception:
                        log.exception("span sink %s ingest failed",
                                      ss.name())
            finally:
                self.span_queue.task_done()

    def _route_metric(self, item):
        """Digest-route one item onto a worker queue — the single
        dispatch point shared by the packet path and the ssfmetrics
        bridge. Events/service checks have no digest and ride on
        queue 0. Drop-on-full is deliberate lossiness under
        backpressure, counted, like veneur's full worker channels."""
        qi = item.digest % len(self.worker_queues) \
            if hasattr(item, "digest") else 0
        try:
            self.worker_queues[qi].put_nowait(item)
        except queue.Full:
            self._count("worker.dropped")

    def _enqueue_import(self, qi: int, item, n: int = 1):
        """Hand one import item (`n` forwarded metrics) to worker `qi`,
        WAITING while its queue is full — the reference's blocking
        ImportMetricChan send: backpressure lands on the sender's RPC,
        which it retries under the same envelope, instead of on the
        data. One local's flush of 100k sketches is a burst of 100k
        items against a 65,536-deep queue; dropping on full lost a
        fifth of it. The wait is bounded by flush_timeout (the sender's
        own per-attempt patience); after one expired wait the queue
        sheds without waiting until that long has passed, so a wedged
        worker costs its senders one timeout, not one per metric. Shed
        items are counted (veneur.worker.dropped_total). Import entry
        points only — never a worker thread, which could deadlock
        against its peer."""
        q = self.worker_queues[qi]
        patience = self.cfg.flush_timeout_seconds
        try:
            if time.monotonic() < self._import_shed_until[qi]:
                q.put_nowait(item)
            else:
                q.put(item, timeout=patience)
        except queue.Full:
            # vlint: disable=TH01 reason=a monotonic hint, not an
            # invariant: racing writers all store a time about
            # flush_timeout ahead, and a stale read only decides
            # whether one more put waits
            self._import_shed_until[qi] = time.monotonic() + patience
            self._count("worker.dropped", n)

    # -------- engine checkpoint/restore (durability, ISSUE 9) --------

    # in-memory write-ahead retention cap: ops kept for snapshot
    # compaction (a two-checkpoint window normally holds a handful;
    # thousands means compaction stopped running — bound it anyway)
    MAX_RETAINED_IMPORT_OPS = 65536

    def _engine_journal_failed(self, what: str):
        """A failing disk must not fail imports or the flush tick: the
        process degrades to the pre-durability in-memory contract,
        counted and loud (same policy as the watermark journal)."""
        resilience.DEFAULT_REGISTRY.incr("import",
                                         "durability.journal_errors")
        log.exception(
            "engine %s journal op failed; DISABLING engine "
            "checkpointing for this process (in-memory aggregation "
            "unaffected; crash-restart recovery degrades)", what)
        j, self._engine_journal = self._engine_journal, None
        if j is not None:
            try:
                j.close()
            except Exception:
                pass

    def _group_imports(self, pbs) -> dict:
        """One import request's metrics by target engine (= worker
        queue), wire order kept inside each share, as (share, the
        share's positions in the request or None for all of it): the
        worker-sharding digest (FNV-1a over name, type and tags, as
        the packet path's) where there is more than one engine to
        choose between, and no work a metric where there is one. An
        unroutable metric (bad key bytes) rejects itself, counted and
        logged."""
        from .cluster import wire
        n = len(self.engines)
        if n == 1:
            return {0: (pbs, None)} if len(pbs) else {}
        groups: dict[int, tuple] = {}
        for at, pb in enumerate(pbs):
            try:
                digest = wire.metric_digest_of(pb)
            except Exception as e:
                self._count("import.rejected")
                log.warning("rejected unroutable imported metric: %s", e)
                continue
            share, ats = groups.setdefault(digest % n, ([], []))
            share.append(pb)
            ats.append(at)
        return groups

    def _submit_import_batch(self, pbs, envelope=None, raw=None) -> int:
        """The import submit path (importsrv and the HTTP /import
        handler): one admitted request = one op, grouped per target
        engine so each engine's share travels as ONE ImportedBatch and
        the worker applies it as a unit under the op id (the
        watermark's consistent cut). With engine checkpointing armed
        the op is write-ahead journaled BEFORE any worker queue — and
        therefore before the sender's ack — and the submit lock makes
        journal order == queue order, so recovery's replay reproduces
        the original per-engine application order exactly. `envelope`
        (the request's already-admitted idempotency envelope) rides in
        the op record so recovery can re-seed the dedupe ledger —
        recovered state plus a forgotten envelope would double-count
        the sender's replay. `raw` (the serialized MetricList `pbs` was
        parsed from, where the request came as one: gRPC SendMetrics)
        rides in each ImportedBatch, for the worker to read the
        sketches from. Returns the count routed."""
        from .cluster.importsrv import ImportedBatch
        from .durability import records as drecords
        groups = self._group_imports(pbs)
        with self._import_submit_lock:
            op_id = self._next_import_op = self._next_import_op + 1
            if self._engine_journal is not None:
                try:
                    payload = drecords.encode_engine_import(
                        op_id, pbs, envelope)
                    self._engine_journal.append_import(payload)
                    self._recent_import_ops.append((op_id, payload))
                    if len(self._recent_import_ops) > \
                            self.MAX_RETAINED_IMPORT_OPS:
                        self._recent_import_ops.pop(0)
                        # the history tier seals generations from this
                        # list; an eviction means the next generation
                        # would silently under-count — flag it so the
                        # seal degrades LOUDLY (crash recovery is
                        # unaffected: it reads the full journal)
                        self._import_ops_evicted = True
                except Exception:
                    self._engine_journal_failed("import write-ahead")
            for qi, (share, at) in groups.items():
                # a shed batch is journaled all the same: recovery
                # replays it, only live processing loses it
                self._enqueue_import(
                    qi, ImportedBatch(op_id, share, raw, at), len(share))
        return sum(len(share) for share, _at in groups.values())

    def _recover_engine_state(self):
        """Recovery-before-listen: rebuild the engines from the engine
        journal — the LATEST self-contained checkpoint group per
        engine, then every import op above that engine's applied-op
        watermark, replayed in journal order through the same digest
        routing and grouped apply the live path uses (what makes the
        next flush bit-identical to a zero-crash oracle). Never raises
        on corrupt state: a shape-fingerprint mismatch or undecodable
        group drops the WHOLE recovery loudly (fresh start) rather
        than scattering rows into wrong slots."""
        from .durability import records as drecords
        from .durability.history import collect_checkpoint_groups
        tel, S = self.telemetry, observe.SERVER_SCOPE
        t0 = time.monotonic_ns()
        recs = self._engine_journal.load_records()
        # ONE committed-group walk (durability/history.py owns it —
        # the time-travel tier reconstructs generations through the
        # SAME state machine, so the COMMIT discipline cannot drift
        # between what recovery restores and what queries serve):
        # a group counts only once its COMMIT arrived — a crash
        # mid-append leaves META (whose watermark would suppress op
        # replay) without the KEYS/BANK rows that back it, and
        # restoring that would be silent data loss. BANK payloads come
        # back ENCODED (their leaf order is engine-aware) and decode
        # below against the engines this server runs — a journal
        # written by DIFFERENT backends is refused at the fingerprint
        # check before any decoded rows can land.
        latest, op_payloads, torn, errors = \
            collect_checkpoint_groups(recs)
        ops: list = []
        for payload in op_payloads:
            try:
                ops.append(drecords.decode_engine_import(payload))
            except Exception:
                errors += 1
                log.exception(
                    "engine recovery: undecodable import op skipped")
        if errors:
            tel.incr(S, "durability.engine_recovery_errors", errors)
            log.warning("engine recovery: %d undecodable record(s) "
                        "skipped", errors)
        if torn:
            tel.incr(S, "durability.engine_recovery_errors", torn)
            log.warning(
                "engine recovery: %d torn (uncommitted) checkpoint "
                "group(s) dropped — falling back to the previous "
                "complete group(s); ops above their watermark replay",
                torn)
        n = len(self.engines)
        for idx, g in latest.items():
            n_eng = g["meta"][0]
            if idx >= n or n_eng != n:
                log.error(
                    "engine recovery REFUSED: checkpoint was taken "
                    "under %d engine(s), this server runs %d — "
                    "starting fresh (replaying ops against a "
                    "different shard map would double/misplace data)",
                    n_eng, n)
                tel.incr(S, "durability.engine_recovery_errors")
                self._recovery = {"refused": "engine count mismatch"}
                return
        restored = 0
        try:
            for idx, g in latest.items():
                _n_eng, wm, gseq, fpr = g["meta"]
                banks: dict = {}
                for payload in g["banks"]:
                    _i, kind, ids, leaves = \
                        drecords.decode_engine_bank(
                            payload,
                            leaf_names_of=self.engines[idx]
                            .bank_leaf_names)
                    banks[kind] = (ids, leaves)
                self.engines[idx].restore_checkpoint(
                    fpr, gseq, wm, g["keys"], banks, g["staged"])
                restored += 1
        except Exception as e:
            # fingerprint mismatch (ValueError) or an undecodable bank
            # row: refuse the WHOLE recovery loudly — a partial
            # restore would flush silently-wrong state
            log.error("engine recovery REFUSED: %s — starting fresh", e)
            tel.incr(S, "durability.engine_recovery_errors")
            self._recovery = {"refused": str(e)}
            return
        replayed = metrics_replayed = 0
        for op_id, pbs, env in ops:
            if op_id > self._next_import_op:
                self._next_import_op = op_id
            if env is not None and self.dedupe_ledger is not None:
                # re-seed the ledger with the envelope this op was
                # admitted under: its merged state is being recovered,
                # so the sender's ambiguous-failure replay of the same
                # chunk must dedupe, not double-count (ops the
                # retention window compacted away are covered by the
                # durable watermark journal instead — the two windows
                # interlock)
                self.dedupe_ledger.admit(*env)
            applied = False
            reroutes: list = []
            for ei, (epbs, _at) in self._group_imports(pbs).items():
                eng = self.engines[ei]
                if op_id <= eng.last_import_op:
                    continue   # inside the restored checkpoint already
                rerouted, rejected = eng.import_list(op_id, epbs)
                reroutes.extend(rerouted)
                for _pb, e in rejected:
                    self._count("import.rejected")
                    log.warning("engine recovery: rejected corrupted "
                                "journaled metric: %s", e)
                applied = True
                metrics_replayed += len(epbs)
            # overload-defense folds homed on other engines replay
            # AFTER every direct share: a reroute stamps the target's
            # watermark to op_id, and doing that before the target's
            # own direct share would make the loop above skip it
            for fr, pb in reroutes:
                digest = _fold_rewrite(pb, fr)
                self.engines[digest % n].import_list(op_id, [pb])
            if applied:
                replayed += 1
            # retain for the next compaction (recovery's conservative
            # window: everything not provably inside every checkpoint)
            self._recent_import_ops.append(
                (op_id, drecords.encode_engine_import(op_id, pbs, env)))
        restore_ns = time.monotonic_ns() - t0
        tel.incr(S, "durability.engine_recovered_ops", replayed)
        tel.incr(S, "durability.engine_recovered_metrics",
                 metrics_replayed)
        tel.set_gauge(S, "durability.engine_restore_ns", restore_ns)
        self._recovery = {
            "engines_restored": restored,
            "ops_replayed": replayed,
            "metrics_replayed": metrics_replayed,
            "restore_ns": restore_ns,
            "generation": self._engine_journal.generation(),
        }
        if restored or replayed:
            log.info("engine recovery: %d engine checkpoint(s) "
                     "restored, %d import op(s) (%d metrics) replayed "
                     "in %.1fms", restored, replayed, metrics_replayed,
                     restore_ns / 1e6)

    def _engine_checkpoint(self, ts: int | None = None,
                           retired_wms: list | None = None):
        """The flush-boundary hook: append one self-contained delta
        checkpoint group per engine (dirty piles only — the swap
        re-zeroed everything else), skip entirely when nothing changed
        (an idle global must not grow the journal), and compact when
        the journal outgrew its budget — the snapshot is the latest
        groups plus the ops the two-checkpoint retention window still
        holds (an op admitted longer ago has had a full interval to
        drain into an engine and be covered by a watermark; the same
        one-interval fuzz the watermark journal documents).

        With the history tier armed (ISSUE 14), the boundary ALSO
        seals the closing interval as a query generation: `ts` is the
        interval-close wall time and `retired_wms` the per-engine
        swap-time watermarks the flush results reported — the
        interval's exact per-engine replay cut."""
        from .durability import records as drecords
        tel, S = self.telemetry, observe.SERVER_SCOPE
        recs: list = []
        dirty = total = 0
        staged_any = interned_any = False
        marks = []
        n = len(self.engines)
        for i, eng in enumerate(self.engines):
            snap = eng.checkpoint_state()
            recs.extend(drecords.encode_engine_checkpoint(i, n, snap))
            dirty += snap["piles_dirty"]
            total += snap["piles_total"]
            staged_any = staged_any or any(
                snap["staged"][f] for f in ("centroids", "sets",
                                            "counters", "gauges"))
            interned_any = interned_any or any(
                entries for _iv, entries in snap["interner"].values())
            marks.append(snap["last_import_op"])
        # a baseline with no bank rows, nothing staged, and no interned
        # keys reconstructs to NOTHING — the next interval can seal as
        # a zero-cost empty generation if it also gets no ops (the
        # history tier's idle path; interner idle-TTL eviction makes a
        # quiet server converge here)
        empty_next = not dirty and not staged_any and not interned_any
        if self._history is not None and ts is not None:
            self._history_seal(ts, retired_wms or [0] * n, recs, marks,
                               empty_next)
        sig = (tuple(marks),
               tuple(len(ki) for eng in self.engines
                     for _k, _a, ki in eng._bank_table()))
        # vlint: disable=TH01 reason=flush-path-only state; flushes are
        # serialized (one flusher thread, tests call flush_once
        # synchronously) and readers (debug/health) tolerate staleness
        self._last_checkpoint_stats = (dirty, total)
        if not dirty and not staged_any \
                and sig == self._last_checkpoint_sig:
            # nothing to persist: every pile is fresh, nothing staged,
            # no new ops, no interner churn — the delta encoding's
            # degenerate (and steady-state idle) case
            tel.incr(S, "durability.engine_delta_skipped_piles", total)
            return
        nbytes = self._engine_journal.append_checkpoint(recs)
        self._engine_journal.sync()
        tel.set_gauge(S, "durability.engine_snapshot_bytes", nbytes)
        tel.incr(S, "durability.engine_delta_skipped_piles",
                 total - dirty)
        # vlint: disable=TH01 reason=flush-path-only state; flushes are
        # serialized (one flusher thread, tests call flush_once
        # synchronously)
        self._last_checkpoint_sig = sig
        # vlint: disable=TH01 reason=flush-path-only state; debug-page
        # readers tolerate staleness
        self._last_checkpoint_t = time.monotonic()
        with self._import_submit_lock:
            cut = self._ops_at_last_checkpoint
            self._recent_import_ops = [
                o for o in self._recent_import_ops if o[0] > cut]
            self._ops_at_last_checkpoint = self._next_import_op
            retained = [(drecords.REC_ENGINE_IMPORT, p)
                        for _id, p in self._recent_import_ops]
            # compaction must run under the submit lock: an op
            # appended between the retention snapshot and the journal
            # truncate would be lost from both
            self._engine_journal.maybe_compact(recs + retained)

    # ---------- time-travel history + query tier (ISSUE 14) ----------

    def _setup_history(self):
        """Arm the retention store + query tier (called from __init__,
        inside the engine-journal-armed branch, AFTER recovery): the
        post-recovery consistent cut becomes the FIRST generation's
        baseline, and the query tier gets a factory minting SCRATCH
        engines from a copy of the live engine shape — it never holds
        a reference to the live pipeline (read-path isolation, vlint
        QT01)."""
        import dataclasses

        from .durability import HistoryStore, QueryTier
        cfg = self.cfg
        self._history = HistoryStore(
            cfg.durability_dir,
            retention_generations=cfg.history_retention_generations,
            retention_seconds=_parse_interval(
                cfg.history_retention_seconds),
            fsync=cfg.durability_fsync != "never",
            registry=self.telemetry)
        self._history_baseline = self._capture_history_baseline()
        # the next generation's open edge: the newest RETAINED close
        # stamp (a restart continues the timeline where it left off —
        # the first post-restart interval absorbs the crash window),
        # else 0 — NOT wall-now, because flush timestamps may be
        # scripted (tests, replay rigs) and an epoch open edge would
        # postdate the first scripted close; a fresh store's first
        # generation simply claims everything before its close
        retained = self._history.entries()
        self._history_prev_close_ns = (retained[-1].close_ns
                                       if retained else 0)
        ecfg = self.engines[0].cfg

        def scratch_factory(percentiles=None, aggregates=None,
                            merge=False):
            # merge=False: a per-generation reconstruction engine —
            # forward-enabled so its flush builds the export rows the
            # merge stage consumes. merge=True: the cross-interval
            # merge engine — global-tier presentation so its frame
            # carries percentiles (the requested quantiles) for every
            # live key. Neither flag is part of the checkpoint
            # fingerprint, so restores match the live shape exactly.
            kw = dict(forward_enabled=not merge, is_global=merge)
            if percentiles is not None:
                kw["percentiles"] = tuple(percentiles)
            if aggregates is not None:
                kw["aggregates"] = tuple(aggregates)
            return AggregationEngine(dataclasses.replace(ecfg, **kw))

        self._query_tier = QueryTier(
            self._history, scratch_factory, len(self.engines),
            flight=self.flight, registry=self.telemetry,
            scope=observe.SERVER_SCOPE,
            engines_describe=self.engines[0].engines_describe(),
            max_concurrent=cfg.query_max_concurrent,
            cache_entries=cfg.query_cache_entries,
            timeout_s=_parse_interval(cfg.query_timeout))

    def _capture_history_baseline(self):
        """(records, per-engine watermarks, provably-empty flag) of a
        consistent cut across every engine — the baseline the NEXT
        closed interval reconstructs on top of."""
        from .durability import records as drecords
        recs: list = []
        marks: list = []
        empty = True
        n = len(self.engines)
        for i, eng in enumerate(self.engines):
            snap = eng.checkpoint_state()
            recs.extend(drecords.encode_engine_checkpoint(i, n, snap))
            marks.append(snap["last_import_op"])
            if snap["piles_dirty"] or any(
                    snap["staged"][f] for f in ("centroids", "sets",
                                                "counters", "gauges")) \
                    or any(entries for _iv, entries
                           in snap["interner"].values()):
                empty = False
        return recs, marks, empty

    def _history_seal(self, ts: int, retired_wms: list, recs: list,
                      marks: list, empty_next: bool = False):
        """Seal the interval that just flushed as one query
        generation: its baseline is the PREVIOUS boundary's checkpoint
        groups, its ops everything write-aheaded above the baseline's
        lowest watermark (the per-engine exact cut — baseline wm <
        op_id <= retire wm — is applied at query time, exactly like
        recovery's replay filter), its close stamp the flush's wall
        timestamp (scripted clocks stay scripted end to end). Runs on
        the flusher thread; a failing disk degrades history loudly
        without failing the tick (the journal-error policy)."""
        tel, S = self.telemetry, observe.SERVER_SCOPE
        try:
            base_recs, base_marks, base_empty = self._history_baseline
            min_wm = min(base_marks) if base_marks else 0
            with self._import_submit_lock:
                op_recs = [(i, p) for i, p in self._recent_import_ops
                           if i > min_wm]
                evicted, self._import_ops_evicted = \
                    self._import_ops_evicted, False
            if evicted:
                # the in-memory retention cap dropped ops this
                # interval: the generation seals INCOMPLETE. Loud +
                # counted — a silent under-count would violate the
                # tier's exactness contract (crash recovery still has
                # the full journal; only history is lossy here)
                tel.incr(S, "durability.history_truncated")
                log.warning(
                    "history: MAX_RETAINED_IMPORT_OPS (%d) evicted "
                    "import ops this interval — the sealed generation "
                    "under-counts; raise the cap or shorten the flush "
                    "interval", self.MAX_RETAINED_IMPORT_OPS)
            close_ns = int(ts) * 1_000_000_000
            if base_empty and not op_recs:
                # provably-empty interval: a manifest row, not a
                # segment (consecutive ones coalesce — an idle tier
                # must not write a segment + fsyncs per tick)
                self._history.append_empty(
                    close_ns, self._history_prev_close_ns)
            else:
                self._history.append(close_ns,
                                     self._history_prev_close_ns,
                                     retired_wms, base_recs, op_recs)
            # vlint: disable=TH01 reason=flush-path-only state; flushes
            # are serialized (one flusher thread, tests call flush_once
            # synchronously)
            self._history_baseline = (recs, marks, empty_next)
            # vlint: disable=TH01 reason=flush-path-only state (above)
            self._history_prev_close_ns = close_ns
            hs = self._history.debug_state()
            tel.set_gauge(S, "history.generations", hs["generations"])
            tel.set_gauge(S, "history.bytes", hs["bytes"])
        except Exception:
            tel.incr(S, "durability.journal_errors")
            log.exception(
                "history generation seal failed; DISABLING the "
                "time-travel tier for this process (aggregation and "
                "crash recovery unaffected)")
            # vlint: disable=TH01 reason=monotone one-way degrade on
            # the flusher thread; readers (query path, debug page)
            # tolerate either value across the flip
            self._history = None
            if self._query_tier is not None:
                self._query_tier.close()
                # vlint: disable=TH01 reason=same one-way degrade; the
                # http wiring null-checks per request
                self._query_tier = None

    def _serve_query(self, params: dict) -> dict:
        """GET /query backend (http_api wires it when the tier is
        armed): runs on the query tier's dedicated executor, never on
        the ingest/flush path."""
        from .durability import QueryError
        tier = self._query_tier
        if tier is None:    # disk-error degrade after the listener bound
            raise QueryError(
                503, "time-travel tier disabled after a disk error "
                     "(see veneur.durability.journal_errors_total)")
        return tier.query(params)

    def _start_import_listener(self, addr: str):
        """Global-mode gRPC receive path (importsrv): forwarded metrics
        are re-hashed onto the worker queues and merged via Combine."""
        from .cluster import wire
        from .cluster.importsrv import start_import_server

        # its requests come with their bytes: have the library that
        # reads them built and loaded before the first one (a process
        # that cannot says so once and decodes in Python)
        wire.native_decode_fn()
        server, port = start_import_server(
            addr, self._submit_import_batch, ledger=self.dedupe_ledger,
            observer=self.import_observer,
            engine_stamp=self.engine_stamp,
            note_stamp=self._note_sketch_stamp,
            merge_sketches=self.merge_prefix_sketches)
        self._grpc_servers.append(server)
        self.grpc_port = port

    def _start_http_api(self, addr: str):
        """Ops HTTP listener (handlers.go): healthchecks + the legacy
        POST /import path, which feeds the same Combine machinery as
        gRPC import."""
        from .http_api import HttpApi

        self.http_api = HttpApi(
            addr, ledger=self.dedupe_ledger,
            debug_state=self._debug_flush_state,
            observer=self.import_observer,
            fleet_state=self._debug_fleet_state,
            health=self.health_state,
            submit_batch=self._submit_import_batch,
            engine_stamp=self.engine_stamp,
            note_stamp=self._note_sketch_stamp,
            merge_sketches=self.merge_prefix_sketches,
            # time-travel query tier (ISSUE 14): absent = 404, so an
            # operator can tell "not armed" from "bad query"
            query=(self._serve_query
                   if self._query_tier is not None else None),
            # the profiler trigger only exists when the operator opted
            # in via debug_flush_profile (a capture is a debug action)
            profile=(self.request_profile_capture
                     if self.cfg.debug_flush_profile else None))
        self.http_api.start()

    def bound_port(self) -> int:
        """Port of the first UDP socket (for tests binding port 0)."""
        if self.native_bridge is not None and not self._sockets:
            return self.native_bridge.bound_port()
        return self._sockets[0].getsockname()[1]

    def _read_metric_socket(self, sock: socket.socket):
        """[HOT LOOP 1] recvfrom -> split -> parse -> route
        (Server.ReadMetricSocket + HandleMetricPacket)."""
        max_len = self.cfg.metric_max_length
        while not self._stop.is_set():
            try:
                data, _ = sock.recvfrom(max_len)
            except OSError:
                break
            self.handle_packet(data)

    def handle_packet(self, data: bytes):
        if self.native_bridge is not None:
            # the bridge counts packets/errors itself; folded into
            # self-metrics at flush
            self.native_bridge.handle_packet(data)
            return
        # Overload backpressure (ingest/admission.py): when the
        # governor is engaged, shed WHOLE datagrams pre-parse at the
        # adaptive rate (the cheapest possible drop — no parse, no
        # queue; counted as veneur.overload.shed_packets_total) and
        # rate-correct the surviving counter/timer/histogram samples
        # so flushed totals stay unbiased. Disengaged (the steady
        # state, and always when the defense is off) this costs one
        # attribute load + None check per datagram.
        adm = self.admission
        shed_rate = 1.0
        if adm is not None and adm.shed_rate < 1.0:
            if adm.admit_packet() is None:
                # the datagram WAS received; its loss is the counted
                # degradation (received == applied + counted_degraded)
                self._count("packet.received")
                return
            shed_rate = adm.shed_rate
        for line in data.split(b"\n"):
            if not line:
                continue
            try:
                item = parser.parse_packet(line, self._exclude_tags,
                                           self._max_name_len,
                                           self._max_tag_len)
            except parser.ParseError:
                self._count("packet.error")
                continue
            if shed_rate < 1.0 and isinstance(item, parser.UDPMetric) \
                    and item.key.type in self._rate_corrected_types:
                # survivor of the shed lottery: weight it up so
                # counter totals / histogram weights stay unbiased
                item.sample_rate = max(item.sample_rate * shed_rate,
                                       1e-9)
            self._route_metric(item)
        # counted after routing so a waiter that observes the count and
        # then drain()s cannot race ahead of the lines
        self._count("packet.received")

    def _worker_loop(self, idx: int, q: queue.Queue):
        """[HOT LOOP 2] queue -> engine (Worker.Work +
        Worker.ImportMetricGRPC for forwarded metrics)."""
        from .cluster.importsrv import ImportedBatch

        eng = self.engines[idx]
        # flight recorder: one `import.apply` stamp per busy run of
        # imported items (first one dequeued -> nothing left unfinished
        # on the queue), closed BEFORE the last task_done so a drain()
        # that returns finds the run stamped
        stamps = self._import_stamps
        run_t0 = 0
        while True:
            item = q.get()
            try:
                if item is _STOP:
                    break
                if isinstance(item, parser.UDPMetric):
                    eng.process(item)
                elif isinstance(item, ImportedBatch):
                    if stamps is not None and not run_t0:
                        run_t0 = time.monotonic_ns()
                    # one import request's share for this engine,
                    # applied as a unit so the engine's applied-op
                    # watermark is an exact replay cut
                    rerouted, rejected = eng.import_list(
                        item.op_id, item.pbs, item.raw, item.at)
                    for fr, pb in rerouted:
                        # overload defense: the fold key is homed on
                        # another engine — rewrite the aggregate onto
                        # it and re-route under the SAME op id
                        # (single-homed folds; the home engine admits
                        # it as an ordinary import)
                        digest = _fold_rewrite(pb, fr)
                        try:
                            self.worker_queues[
                                digest
                                % len(self.worker_queues)].put_nowait(
                                ImportedBatch(item.op_id, [pb]))
                        except queue.Full:
                            self._count("worker.dropped")
                    for pb, e in rejected:
                        self._count("import.rejected")
                        log.warning(
                            "rejected corrupted imported metric "
                            "%r: %s", getattr(pb, "name", "?"), e)
                elif isinstance(item, parser.Event):
                    eng.process_event(item)
                else:
                    eng.process_service_check(item)
            finally:
                if run_t0 and q.unfinished_tasks <= 1:
                    stamps.add("import.apply", run_t0,
                               time.monotonic_ns(),
                               self.APPLY_MERGE_GAP_NS)
                    run_t0 = 0
                q.task_done()

    def drain(self, timeout: float = 10.0, *, clock=time.monotonic,
              sleep=time.sleep) -> bool:
        """Block until every enqueued span and metric has been fully
        processed by its worker (not merely popped). Deterministic
        replacement for sleep-based settling in tests: uses the queues'
        unfinished-task accounting, so an item mid-`eng.process` still
        counts as in flight. `clock`/`sleep` are injectable (the fault
        harness's FakeClock) so the deadline-expiry path is testable
        without real waiting."""
        deadline = clock() + timeout
        if self.native_pump is not None:
            # bridge rings + slow path first; slow-path items land on the
            # worker queues, which the loop below then settles
            if not self.native_pump.drain(timeout):
                return False
        queues = [self.span_queue] + self.worker_queues
        while True:
            if all(q.unfinished_tasks == 0 for q in queues):
                return True
            if clock() >= deadline:
                return False
            sleep(0.005)

    # ------------- flush -------------

    def _flush_loop(self):
        interval = self.cfg.interval_seconds
        next_t = time.monotonic() + interval
        if self.cfg.synchronize_with_interval:
            # align ticks to wall-clock multiples of the interval
            now = time.time()
            next_t = time.monotonic() + (interval - now % interval)
        while not self._stop.wait(max(0.0, next_t - time.monotonic())):
            next_t += interval
            try:
                self.flush_once()
                self._last_flush_ok = time.monotonic()
            except Exception as e:
                log.exception("flush failed")
                self._count("flush.error")
                if self._sentry is not None:
                    self._sentry.capture(e, "flush failed")

    def flush_once(self, timestamp: int | None = None):
        """One flush tick: drain engines, fan out, forward
        (Server.Flush). Returns the flush's FrameSet — iterable of
        InterMetrics; frame-native consumers read .frames directly and
        InterMetric objects are only ever built lazily, inside whichever
        sink thread first needs them.

        With the flight recorder on, the tick's phase tree (engine
        drain / device dispatch / device exec / materialize / per-sink
        fan-out / forward ladder / durability ops) lands in the ring
        behind /debug/flush, replays as an SSF span tree through the
        server's own trace client (flusher.go self-tracing parity), and
        its top-level durations are re-ingested as LOCAL-ONLY
        veneur.flush.phase.* timers — the engine serving percentiles of
        its own flush."""
        t0 = time.monotonic()
        ts = int(timestamp if timestamp is not None else time.time())
        tick = token = None
        grafts: dict = {}
        if self.flight is not None:
            tick = self.flight.begin_tick(ts)
            # the cut: import and pump work stamped up to here is this
            # tick's (taken now, grafted when the tick ends, each kind
            # under a root with the meta beside its rows); the
            # engines' flushes add the stamps they hold
            grafts = {
                "import": ([] if self._import_stamps is None
                           else self._import_stamps.take(), {}),
                "ingest": ([] if self.native_pump is None
                           else self.native_pump.take_stamps(), {})}
            if timestamp is not None:
                # scripted/explicit timestamps stay scripted all the
                # way through the e2e accounting: the interval-close
                # stamp the forward envelopes carry (and the fleet
                # view's merge clock) derives from the SAME value, so
                # close->merged latency is deterministic under the
                # fault harness's pinned clocks
                tick.close_ns = int(timestamp * 1_000_000_000)
            token = observe.set_current_tick(tick)
        self._maybe_profile_start()
        try:
            if tick is None and self.trace_client is not None:
                # flight_recorder: false must not silence the flush
                # self-trace entirely — emit the root veneur.flush
                # span the pre-recorder wrapper always produced (the
                # per-phase children do require the recorder)
                from . import trace as trace_mod
                from .observe.registry import flush_span_name
                with trace_mod.start_span(self.trace_client,
                                          flush_span_name(),
                                          service="veneur"):
                    frameset = self._flush_tick(ts, t0, tick, grafts)
            else:
                frameset = self._flush_tick(ts, t0, tick, grafts)
        finally:
            # a failing (or killed — SimulatedKill/SIGKILL chaos) tick
            # still closes its record: the ring is process-local state
            # with no journal interaction, so a crash can never leave
            # it half-written for the next incarnation
            if token is not None:
                observe.reset_current_tick(token)
            if tick is not None:
                # grafted last, so a tick short of slots drops these
                # rows and never its own phases
                for root, (rows, meta) in grafts.items():
                    tick.graft(rows, root=root, **meta)
                self.flight.end_tick(tick)
                if self.trace_client is not None:
                    self.flight.emit_spans(tick, self.trace_client)
            self._maybe_profile_stop()
        if tick is not None and self.cfg.flush_phase_timers:
            # dogfood loop: the NEXT tick's flush serves percentiles of
            # THIS tick's phases, flushed like any tenant metric
            for m in observe.phase_timer_samples(tick):
                self._route_metric(m)
        if tick is not None and tick.dropped:
            # ring-overflow export: phases the slot budget dropped are
            # counted in the tick AND surfaced as a self-metric
            # (veneur.observe.phases_dropped_total, drained next
            # interval) so attribution gaps are visible in dashboards,
            # not only to a /debug/flush reader
            self._count("observe.phases_dropped", tick.dropped)
        self.telemetry.incr_level(observe.SERVER_SCOPE, "flush.count")
        return frameset

    def _flush_tick(self, ts: int, t0: float, tick, grafts: dict):
        """The tick body (split from flush_once so recorder lifecycle
        wraps it exactly once). `tick` is the TickRecord or None;
        `grafts` the (rows, root's meta) of each kind that flush_once
        grafts when the tick ends."""
        frames = []
        events, checks = [], []
        status_metrics = []
        eng_stats = {"samples": 0, "dropped_no_slot": 0,
                     "overflow_rows": 0, "overflow_bank": 0,
                     "sidestep_rows": 0, "sidestep_bank": 0,
                     "import_batches": 0, "import_metrics": 0,
                     "import_land_rows": 0, "import_land_bank": 0,
                     "keys_interned": 0, "keys_evicted": 0, "keys_live": 0,
                     **dict.fromkeys(DECODE_TALLY, 0)}
        # Engines flush concurrently so their device programs and
        # device→host transfers overlap instead of queueing behind
        # one another's host assembly. Single engine = no thread.
        results: list = [None] * len(self.engines)
        eng_ph: list = [-1] * len(self.engines)
        # Delta forwarding (ISSUE 13): ask the forwarder what THIS
        # interval's export build should be — "delta" (dirty-bitmap
        # subset) unless a full resync is due/forced or deltas are off.
        # Engines that cannot honor it (mesh, tracking off) degrade to
        # full and say so in export.kind.
        fkind = "full"
        if self.forwarder is not None:
            nfk = getattr(self.forwarder, "next_forward_kind", None)
            if nfk is not None:
                fkind = nfk()
        ep = -1 if tick is None else tick.start("engine")
        if len(self.engines) == 1:
            eng_ph[0] = -1 if tick is None else \
                tick.start("engine.flush", ep)
            results[0] = self.engines[0].flush(timestamp=ts,
                                               forward_kind=fkind)
            if tick is not None:
                tick.finish(eng_ph[0], engine=0)
        else:
            def _one(i, eng):
                ph = -1 if tick is None else \
                    tick.start("engine.flush", ep)
                eng_ph[i] = ph
                try:
                    results[i] = eng.flush(timestamp=ts,
                                           forward_kind=fkind)
                except BaseException as e:
                    results[i] = e
                finally:
                    if tick is not None:
                        tick.finish(ph, engine=i)
            ths = [threading.Thread(target=_one, args=(i, eng),
                                    daemon=True,
                                    name=f"engine-flush-{i}")
                   for i, eng in enumerate(self.engines)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
        for i, (eng, res) in enumerate(zip(self.engines, results)):
            if isinstance(res, BaseException):
                raise res
            if res is None:   # a flush thread died; surface it
                raise RuntimeError("engine flush failed")
            for k in eng_stats:
                eng_stats[k] += res.stats.get(k, 0)
            # the mesh engine's own tally of the interval's imports,
            # from its note on the flush
            for k in self._mesh_telemetry:
                eng_stats[k] = res.stats["flush_path"].get(k, 0)
            if tick is not None:
                # graft the engine's own stamps (drain / device
                # dispatch / device exec / fetch / materialize) under
                # its engine.flush phase, with their real edges
                drain = (eng_ph[i], tick.mono_start)
                added = {}
                for nm, p0, p1, *under in res.stats.get("phases", ()):
                    # a stamp that names one before it nests there
                    idx = added[nm] = tick.add(
                        "engine." + nm, p0, p1,
                        parent=added[under[0]] if under else eng_ph[i])
                    if nm == "drain":
                        drain = (idx, p0)
                # the engine's import stamps since the previous flush:
                # the flush-time landing ran inside engine.drain and
                # nests there; mid-interval landings and the requests'
                # `import.apply.*` join the `import` root, where the
                # worker's CPU time over the latter is the root's meta
                own, (between, meta) = [], grafts["import"]
                for r in res.stats.get("import_phases", ()):
                    (own if r[1] >= drain[1] and r[0] in LAND_PHASES
                     else between).append(r)
                tick.graft(own, parent=drain[0])
                for k in APPLY_CPU_TALLY:
                    ns = res.stats.get("flush_path", {}).get(k, 0)
                    if ns:
                        meta[k] = meta.get(k, 0) + ns
            frames.append(res.frame)
            status_metrics.extend(res.status_metrics)
            ev, ch = eng.drain_events()
            events.extend(ev)
            checks.extend(ch)
        # one engine's export goes on as it is, columns and all; more
        # are joined kind by kind, engine after engine
        merged_export = ForwardExport.joined([r.export for r in results])
        # the merged interval is a FULL resync only if EVERY engine
        # actually built one; any delta share makes the whole payload
        # incomplete, so stamp it delta (which claims less — a safe
        # under-claim; in practice engines share one config and agree).
        # The forwarder's resync bookkeeping keys off this.
        merged_export.kind = ("delta" if any(
            r.export.kind == "delta" for r in results) else "full")
        if tick is not None:
            tick.finish(ep)

        if self.fleet is not None:
            # e2e boundary: every interval admitted before this drain
            # is now merged into flushed state — turn the pending close
            # stamps into close->merged latency samples. The timers
            # dogfood through the engine NEXT tick (like phase timers)
            # and are LOCAL-ONLY; the freshness watermark rides the
            # registry as a per-sender gauge. One-interval fuzz for
            # chunks still in a worker queue at drain time — the same
            # documented fuzz as the dedupe watermark journal.
            fp = -1 if tick is None else tick.start("fleet")
            now_ns = (tick.close_ns if tick is not None
                      else int(ts) * 1_000_000_000)
            e2e = self.fleet.on_flush(now_ns)
            for sid, age in self.fleet.freshness(now_ns).items():
                self.telemetry.set_gauge(f"sender:{sid}",
                                         "e2e.freshness_age_ns", age)
            for m in observe.e2e_timer_samples(e2e):
                self._route_metric(m)
            if tick is not None:
                tick.finish(fp, senders=len(e2e),
                            intervals=sum(len(v) for v in e2e.values()))

        tp = -1 if tick is None else tick.start("telemetry")
        frameset = FrameSet(
            frames,
            status_metrics + self._self_metrics(ts, t0, eng_stats))
        if tick is not None:
            tick.finish(tp)
        fo = -1 if tick is None else tick.start("fanout")
        self._fan_out(frameset, events, checks, tick=tick, parent=fo)
        if tick is not None:
            tick.finish(fo)

        # per-prefix cardinality sketches ride to the global tier when
        # the defense is on (merge-by-max there; advisory, excluded
        # from the replay journal — a lost interval's rows are
        # strictly dominated by the next interval's)
        if self.admission is not None and self.forwarder is not None:
            merged_export.prefix_sketches = \
                self.admission.export_sketches()

        # forward when the interval produced exports OR earlier spilled
        # sketches await re-merge — an idle interval must still retry a
        # recovered endpoint, or spilled data strands in the buffer
        if self.forwarder is not None and (
                any(merged_export.counts())
                or getattr(self.forwarder, "pending_spill", 0)):
            fw = -1 if tick is None else tick.start("forward")
            # re-scope the contextvar so the ladder's attempt/replay/
            # journal phases nest under `forward`, not beside it
            ftok = observe.set_current_tick(tick, fw) \
                if tick is not None else None
            # bytes-on-the-wire accounting (ISSUE 13): the leaf
            # forwarders count veneur.forward.bytes* per delivered
            # chunk; sample the cumulative totals around the call so
            # /debug/fleet can show THIS interval's bytes next to e2e
            bytes_before = resilience.DEFAULT_REGISTRY \
                .totals_by_name_prefix("forward.bytes")
            try:
                self.forwarder(merged_export)
                with self._stats_lock:
                    self._last_forward_err = None
            except Exception as e:
                log.exception("forward failed")
                # a sustained outage (breaker open / no destinations)
                # fails every tick with the same error; capture each
                # DISTINCT failure once, not one event per interval —
                # the resilience counters carry the per-tick signal
                sig = f"{type(e).__name__}: {e}"
                with self._stats_lock:
                    repeat = sig == self._last_forward_err
                    self._last_forward_err = sig
                if self._sentry is not None and not repeat:
                    self._sentry.capture(e, "forward failed")
            finally:
                if ftok is not None:
                    observe.reset_current_tick(ftok)
                if tick is not None:
                    tick.finish(fw)
                bytes_after = resilience.DEFAULT_REGISTRY \
                    .totals_by_name_prefix("forward.bytes")
                sample = {}
                for (scope, name), v in bytes_after.items():
                    d = v - bytes_before.get((scope, name), 0)
                    if d:
                        sample.setdefault(scope, {})[name] = d
                with self._stats_lock:
                    self._last_forward_bytes = {
                        "kind": merged_export.kind,
                        "by_destination": sample,
                    }
        # durability flush boundary: fsync + compact the forward
        # journal, and record the dedupe ledger's per-sender admitted
        # watermarks (everything admitted up to here rides in flushed
        # state no later than the NEXT tick — the one-interval fuzz is
        # documented in README "Durable state")
        dp = -1
        dtok = None
        if tick is not None and (
                self._forward_journal is not None
                or self._engine_journal is not None
                or (self._dedupe_journal is not None
                    and self.dedupe_ledger is not None)):
            dp = tick.start("durability")
            dtok = observe.set_current_tick(tick, dp)
        try:
            if self._engine_journal is not None:
                try:
                    # engine delta checkpoint: the banks were just
                    # swapped, so `fresh + dirty rows` is the whole
                    # post-flush state; everything admitted since rides
                    # the write-ahead import ops. The per-engine
                    # swap-time watermarks seal the closed interval as
                    # a time-travel generation (ISSUE 14).
                    self._engine_checkpoint(
                        ts, [r.stats.get("retired_import_op", 0)
                             for r in results])
                except Exception:
                    self._engine_journal_failed("checkpoint")
            if self._forward_journal is not None:
                jt = getattr(self.forwarder, "journal_tick", None)
                if jt is not None:
                    jt()  # journal failures degrade inside the forwarder
            if self._dedupe_journal is not None and \
                    self.dedupe_ledger is not None:
                try:
                    # record LAST tick's snapshot, capture this tick's:
                    # a seq admitted during this tick may not be in the
                    # state this tick flushed (worker-queue residency),
                    # so it only becomes a durable floor once a full
                    # interval has carried it into a flush. A crash
                    # loses at most the watermark advance of the last
                    # two ticks — replays of those seqs re-admit, which
                    # the receiver-side dedupe ledger bounds exactly as
                    # before durability existed.
                    marks = self._pending_watermarks
                    # vlint: disable=TH01 reason=flush-path-only state;
                    # flushes are serialized (one flusher thread, tests
                    # call flush_once synchronously)
                    self._pending_watermarks = \
                        self.dedupe_ledger.max_admitted()
                    self._dedupe_journal.record(marks)
                    self._dedupe_journal.sync()
                except Exception:
                    # a failing disk must not fail the flush tick; the
                    # in-memory ledger keeps deduping, only crash-restart
                    # watermark durability degrades (counted, loud)
                    resilience.DEFAULT_REGISTRY.incr(
                        "import", "durability.journal_errors")
                    log.exception(
                        "dedupe watermark journal failed; DISABLING it "
                        "for this process (in-memory dedupe unaffected)")
                    try:
                        self._dedupe_journal.close()
                    except Exception:
                        pass
                    # vlint: disable=TH01 reason=flush-path-only state;
                    # flushes are serialized (one flusher thread, tests
                    # call flush_once synchronously) and stop() reads it
                    # only after the last tick ended
                    self._dedupe_journal = None
        finally:
            if dtok is not None:
                observe.reset_current_tick(dtok)
            if dp != -1:
                tick.finish(dp)

        # Overload governor boundary: adapt the shed rate from this
        # tick's wall duration (overrun = the flush can't keep up with
        # ingest) and the worst worker-queue fill, then record the
        # interval's degradation as phases — a storm tick shows its
        # fold/shed volume in the flight-recorder ring, next to the
        # phases explaining WHY the tick overran.
        adm = self.admission
        if adm is not None:
            op = -1 if tick is None else tick.start("overload")
            qfill = max((q.qsize() / q.maxsize
                         for q in self.worker_queues), default=0.0)
            delta = adm.on_tick(time.monotonic() - t0,
                                self.cfg.interval_seconds, qfill)
            if tick is not None:
                if delta["folded"] or delta["sampled_out"] \
                        or delta["over_budget"]:
                    tick.finish(
                        tick.start("overload.fold", op),
                        folded=delta["folded"],
                        sampled_out=delta["sampled_out"],
                        keys_over_budget=delta["over_budget"])
                if delta["shed"]:
                    tick.finish(tick.start("overload.shed", op),
                                shed=delta["shed"])
                tick.finish(op, rate=delta["rate"],
                            overloaded=delta["overloaded"])
        return frameset

    # ------------- on-demand jax.profiler capture -------------
    # GET /debug/flush/profile?ticks=N schedules a capture (gated by
    # debug_flush_profile); the flusher starts the trace before the
    # next tick and stops it after N ticks.

    def request_profile_capture(self, ticks: int = 1) -> dict:
        ticks = max(1, int(ticks))
        with self._stats_lock:
            self._profile_ticks = max(self._profile_ticks, ticks)
            pending = self._profile_ticks
        return {"capture_ticks": pending,
                "dir": self.cfg.debug_flush_profile_dir}

    def _maybe_profile_start(self):
        with self._stats_lock:
            want = self._profile_ticks > 0 and not self._profile_active
            if want:
                self._profile_active = True
        if not want:
            return
        try:
            import jax
            jax.profiler.start_trace(self.cfg.debug_flush_profile_dir)
            log.info("debug/flush: jax profiler capture started -> %s",
                     self.cfg.debug_flush_profile_dir)
        except Exception as e:
            log.warning("debug/flush: jax profiler unavailable: %s", e)
            with self._stats_lock:
                self._profile_active = False
                self._profile_ticks = 0

    def _maybe_profile_stop(self):
        with self._stats_lock:
            if not self._profile_active:
                return
            self._profile_ticks -= 1
            done = self._profile_ticks <= 0
        if not done:
            return
        try:
            import jax
            jax.profiler.stop_trace()
            log.info("debug/flush: jax profiler capture complete")
        except Exception as e:
            log.warning("debug/flush: profiler stop failed: %s", e)
        with self._stats_lock:
            self._profile_active = False

    def _debug_flush_state(self) -> dict:
        """GET /debug/flush payload: the flight-recorder ring plus the
        breaker/ladder/journal/dedupe-ledger state a flush-latency
        investigation needs next (schema in README 'Observability')."""
        fwd = self.forwarder
        state = {
            "flush_count": self.flush_count,
            # active sketch engines + wire stamp (ISSUE 10): what this
            # server merges and declares on every forwarded chunk
            "sketch_engines": self.engines[0].engines_describe(),
            "flight_recorder": (None if self.flight is None
                                else self.flight.debug_state()),
            "forward": (fwd.debug_state()
                        if hasattr(fwd, "debug_state") else None),
            # overload defense: budgets, per-prefix cardinality
            # estimates, governor rate, fold/shed counters
            "admission": (self.admission.debug_state()
                          if self.admission is not None
                          else {"enabled": False}),
            "dedupe_ledger": None,
            "durability": {
                "forward_journal_bytes": (
                    self._forward_journal.size_bytes()
                    if self._forward_journal is not None else None),
                "watermark_journal_bytes": (
                    self._dedupe_journal.size_bytes()
                    if self._dedupe_journal is not None else None),
                "engine_checkpoint": self._engine_checkpoint_state(),
                # time-travel history tier (ISSUE 14): retained
                # generations + query-path counters/cache
                "history": (self._history.debug_state()
                            if self._history is not None else None),
            },
            "query": (self._query_tier.debug_state()
                      if self._query_tier is not None else None),
            "registry": {
                "server": self.telemetry.debug_state(),
                "process": resilience.DEFAULT_REGISTRY.debug_state(),
            },
        }
        if self.dedupe_ledger is not None:
            state["dedupe_ledger"] = {
                "size": self.dedupe_ledger.size(),
                "senders": self.dedupe_ledger.sender_count(),
                "watermarks": self.dedupe_ledger.max_admitted(),
            }
        return state

    def _engine_checkpoint_state(self) -> dict | None:
        """The /debug/flush checkpoint block: generation, journal and
        last-delta bytes, the dirty/total pile ratio of the last
        boundary, the last-checkpoint age, and the restore stats of
        this incarnation's recovery (None when the feature is off)."""
        if not self._engine_journal_armed:
            return None
        j = self._engine_journal
        dirty, total = self._last_checkpoint_stats
        return {
            "enabled": j is not None,   # False = degraded (disk error)
            "generation": j.generation() if j is not None else None,
            "journal_bytes": j.size_bytes() if j is not None else None,
            "last_snapshot_bytes": (j.last_checkpoint_bytes
                                    if j is not None else None),
            "piles_dirty": dirty,
            "piles_total": total,
            "dirty_ratio": round(dirty / total, 6) if total else 0.0,
            "last_checkpoint_age_s": (
                round(time.monotonic() - self._last_checkpoint_t, 3)
                if self._last_checkpoint_t is not None else None),
            "pending_import_ops": len(self._recent_import_ops),
            "restore": self._recovery,
        }

    # health verdict threshold: a flush is STALLED once its lag exceeds
    # this many intervals (1.5 = the check flips within one interval of
    # the first missed tick, without flapping on ordinary jitter)
    HEALTH_STALL_INTERVALS = 1.5

    def health_state(self, now: float | None = None,
                     fwd_state: dict | None = None) -> dict:
        """Structured verdicts for GET /healthz and /ready. `healthy`
        is the hard bit — false ONLY when the flush loop is stalled
        (the crash-only failure mode made observable from outside);
        the remaining checks are degradation signals (breaker open,
        journal degraded, governor shedding, queue fill) that flag
        `status: degraded` without failing the probe — supervisors
        must not restart a server that is correctly load-shedding.
        `now` is injectable (fault harness); `fwd_state` lets a caller
        that already computed the forwarder's debug_state (the
        /debug/fleet page embeds this verdict) pass it in instead of
        rebuilding the per-entry ladder list."""
        now = time.monotonic() if now is None else now
        interval = self.cfg.interval_seconds
        lag = now - self._last_flush_ok
        started = self._started
        stalled = started and lag > self.HEALTH_STALL_INTERVALS * interval
        checks = {
            "flush": {"ok": not stalled, "lag_s": round(lag, 3),
                      "interval_s": interval,
                      "stalled_ticks_total": self.telemetry.total(
                          observe.SERVER_SCOPE, "watchdog.stalled_ticks")},
        }
        fwd = self.forwarder
        if fwd_state is not None or hasattr(fwd, "debug_state"):
            # same introspection path /debug/flush and /debug/fleet
            # consume — ONE owner of the forwarder-internals dig
            st = fwd_state if fwd_state is not None else fwd.debug_state()
            bstate = st["breaker_state"]
            pending = st["pending_spill"]
            checks["forward"] = {
                "ok": bstate != "open" and not pending,
                "breaker_state": bstate,
                "pending_spill": pending,
                "ladder_depth": len(st["ladder"]),
            }
        degraded_journals = []
        if self.cfg.durability_enabled:
            if (self._forward_journal is not None
                    and getattr(self.forwarder, "_journal", None) is None
                    and isinstance(self.forwarder,
                                   resilience.ResilientForwarder)):
                degraded_journals.append("forward")
            if self.dedupe_ledger is not None \
                    and self._dedupe_journal is None:
                degraded_journals.append("dedupe_watermarks")
            if self._engine_journal_armed and self._engine_journal is None:
                degraded_journals.append("engine")
            checks["journal"] = {"ok": not degraded_journals,
                                 "degraded": degraded_journals}
        if self._engine_journal_armed or self._recovery is not None:
            # recovery-before-listen verdict: in_progress until start()
            # completes (the /ready "recovering" window), then the
            # restore stats — what was restored/replayed and how long
            # it took — stay on the page. A REFUSED recovery (shape
            # fingerprint / engine-count mismatch: journaled state was
            # discarded, fresh start) keeps ok=false so a monitor
            # keying on status sees the data-loss condition, like the
            # disk-failure path does via the journal check.
            refused = bool((self._recovery or {}).get("refused"))
            checks["recovery"] = {
                "ok": not self._recovering and not refused,
                "in_progress": self._recovering,
                **(self._recovery or {}),
            }
        if self.admission is not None:
            rate = self.admission.shed_rate
            checks["overload"] = {"ok": rate >= 1.0, "shed_rate": rate}
        qfill = max((q.qsize() / q.maxsize for q in self.worker_queues),
                    default=0.0)
        checks["queues"] = {"ok": qfill < 0.9, "fill": round(qfill, 4)}
        degraded = any(not c["ok"] for c in checks.values())
        recovering = self._recovering
        return {
            "healthy": not stalled,
            "ready": started and not recovering
                     and not self._stop.is_set(),
            "status": ("recovering" if recovering
                       else "stalled" if stalled
                       else "degraded" if degraded else "ok"),
            "checks": checks,
        }

    def _note_sketch_stamp(self, sender_id: str, stamp, ok: bool):
        """Record one import request's engine-stamp verdict (both the
        gRPC and HTTP paths route here): per-sender row in the fleet
        view + the veneur.import.engine_mismatch_total counter on
        reject — the loud half of the mixed-fleet contract."""
        if self.fleet is not None:
            self.fleet.note_stamp(sender_id, stamp, ok)
        if not ok:
            resilience.DEFAULT_REGISTRY.incr("import",
                                             "import.engine_mismatch")

    # distinct prefixes the fleet cardinality map will hold — the same
    # bounded-memory posture as the admission controller's own
    # max_prefixes (a network-facing receiver must stay bounded however
    # many prefixes senders churn through); overflow rows are dropped
    # and counted
    MAX_FLEET_SKETCH_PREFIXES = 4096

    def merge_prefix_sketches(self, items):
        """Merge received per-prefix Huffman-Bucket cardinality rows
        (merge-by-max — idempotent under replays) into the fleet map
        served at /debug/fleet, so fleet-wide cardinality is ONE
        estimate, not per-shard guesses. Bounded: prefixes past
        MAX_FLEET_SKETCH_PREFIXES are dropped (counted), never grown."""
        dropped = 0
        with self._fleet_sketch_lock:
            for prefix, regs in items:
                cur = self._fleet_sketches.get(prefix)
                if cur is None:
                    if len(self._fleet_sketches) \
                            >= self.MAX_FLEET_SKETCH_PREFIXES:
                        dropped += 1
                        continue
                    self._fleet_sketches[prefix] = bytearray(regs)
                elif len(cur) != len(regs):
                    # senders configured with different sketch_buckets
                    # cannot merge: DROP the row (counted) rather than
                    # replace — a replace would flip-flop the prefix's
                    # estimate between single-sender views per request
                    dropped += 1
                else:
                    for i, r in enumerate(regs):
                        if r > cur[i]:
                            cur[i] = r
        if dropped:
            resilience.DEFAULT_REGISTRY.incr(
                "import", "fleet.sketch_prefixes_dropped", dropped)

    def _fleet_cardinality(self, top: int = 50) -> dict:
        """JSON-ready fleet-wide per-prefix cardinality estimates:
        received sketches merged (at read time) with this server's own
        admission-controller sketches, so a global that also ingests
        locally reports one number per prefix."""
        from .ingest.admission import estimate_registers
        with self._fleet_sketch_lock:
            merged = {p: bytes(r) for p, r in self._fleet_sketches.items()}
        if self.admission is not None:
            for prefix, regs in self.admission.export_sketches():
                cur = merged.get(prefix)
                if cur is None:
                    merged[prefix] = bytes(regs)
                elif len(cur) == len(regs):
                    merged[prefix] = bytes(
                        max(a, b) for a, b in zip(cur, regs))
                # width mismatch: keep the fleet row (local estimate
                # is a subset of it anyway), never replace
        rows = sorted(
            ((p, round(estimate_registers(r), 1))
             for p, r in merged.items()),
            key=lambda kv: -kv[1])
        return dict(rows[:top])

    def _debug_fleet_state(self) -> dict:
        """GET /debug/fleet payload: the per-sender fleet view (e2e
        p50/p99, freshness, last-seen, dedupe watermark) on a receiving
        tier, this server's OWN forward ladder summary (depth, replay
        ages, spill, breaker) on a sending tier, the bounded import
        ring, and the health verdict — the one page that answers
        'which sender is stalled, which interval is stuck in a replay
        ladder, how stale is the global's view'."""
        now_ns = time.time_ns()
        senders: dict = {}
        if self.fleet is not None:
            fleet = self.fleet.debug_state(now_ns)
            senders = fleet["senders"]
        if self.dedupe_ledger is not None:
            for sid, mark in self.dedupe_ledger.max_admitted().items():
                # a sender known only from restored watermarks (journal
                # recovery, no forward yet this incarnation) still gets
                # the FULL documented row shape — a dashboard indexing
                # row["e2e_ms"] must not crash on a restarted fleet
                senders.setdefault(sid, {
                    "last_seen_age_s": None,
                    "newest_close_ns": 0,
                    "freshness_age_ms": None,
                    "intervals_merged": 0,
                    "pending": 0,
                    "e2e_ms": {"count": 0, "p50": 0.0, "p99": 0.0},
                })["dedupe_watermark"] = mark
        forward = None
        fwd_state = None
        fwd = self.forwarder
        if hasattr(fwd, "debug_state"):
            fwd_state = fwd.debug_state()
            ages = [e["age"] for e in fwd_state["ladder"]]
            forward = {
                "sender_id": fwd_state["sender_id"],
                "ladder_depth": len(fwd_state["ladder"]),
                "replay_ages": ages,
                "oldest_replay_age": max(ages, default=0),
                "pending_spill": fwd_state["pending_spill"],
                "breaker_state": fwd_state["breaker_state"],
            }
        obs = self.import_observer
        # forward bytes (ISSUE 13): cumulative per destination per
        # kind from the process registry, plus the last interval's
        # sample — the bytes/interval row an operator reads next to
        # e2e latency to see what delta/quantized forwarding saves
        fbytes: dict = {}
        for (scope, name), v in resilience.DEFAULT_REGISTRY \
                .totals_by_name_prefix("forward.bytes").items():
            fbytes.setdefault(scope, {})[name] = v
        with self._stats_lock:
            last_bytes = self._last_forward_bytes
        return {
            "now_ns": now_ns,
            "flush_count": self.flush_count,
            "senders": senders,
            "forward": forward,
            "forward_bytes": {
                "cumulative": fbytes,
                "last_interval": last_bytes,
            },
            # mixed-fleet visibility (ISSUE 10): this server's engine
            # stamp next to each sender's declared stamp above, plus
            # the mismatch-reject total
            "sketch_engines": {
                "local": self.engine_stamp,
                "mismatch_rejects": resilience.DEFAULT_REGISTRY.total(
                    "import", "import.engine_mismatch"),
            },
            # fleet-wide per-prefix cardinality (merged received +
            # local Huffman-Bucket sketches)
            "fleet_cardinality": self._fleet_cardinality(),
            "import_recorder": (obs.debug_state() if obs is not None
                                else None),
            "health": self.health_state(fwd_state=fwd_state),
        }

    def _self_metrics(self, ts: int, t0: float,
                      eng_stats: dict | None = None) -> list[InterMetric]:
        """veneur.* self-telemetry: stage the per-tick gauges/deltas
        into the unified registry, then drain BOTH registries — this
        server's spine and the process-default egress/durability one —
        through the single name mapping in observe/registry.py (the
        internal statsd client's names, unchanged)."""
        tel, S = self.telemetry, observe.SERVER_SCOPE
        # the core counters report every interval, zeros included, as
        # the pre-unification attribute drain always did
        for name in ("packet.received", "packet.error", "worker.dropped",
                     "ssf.received", "ssf.error", "flush.error",
                     "import.rejected", "watchdog.stalled_ticks"):
            tel.mark(S, name, 0)
        if self.flight is not None:
            # ring-overflow accounting reports every interval; its
            # steady-state ZERO is the signal that phase attribution
            # is complete (no phases dropped to the slot budget)
            tel.mark(S, "observe.phases_dropped", 0)
        if self.native_bridge is not None:
            # UDP in native mode is counted in the bridge; fold in the
            # per-interval deltas. Drop classes: ring/backpressure
            # drops -> worker.dropped_total; bank-full drops -> the
            # dropped_no_slot metric, REPLACING the engine's own count
            # (the BridgeKeyView only sees the slow-path subset, which
            # the bridge counter already includes — adding both would
            # double-report).
            st = self.native_bridge.stats()
            last = getattr(self, "_last_bridge_stats", None) or {}
            tel.incr(S, "packet.received",
                     int(st["packets"]) - int(last.get("packets", 0)))
            tel.incr(S, "packet.error",
                     int(st["parse_errors"])
                     - int(last.get("parse_errors", 0)))
            tel.incr(S, "worker.dropped",
                     int(st["ring_drops"])
                     - int(last.get("ring_drops", 0)))
            # natively-decoded spans + their decode errors (fallback
            # datagrams re-enter the Python path and are counted there)
            tel.incr(S, "ssf.received",
                     int(st["ssf_spans"]) - int(last.get("ssf_spans", 0)))
            tel.incr(S, "ssf.error",
                     int(st["ssf_errors"])
                     - int(last.get("ssf_errors", 0)))
            # spans the fast path handed back whole, and what the
            # framed-stream readers tallied (veneur.ssf.fallback_total,
            # veneur.ssf.stream.*_total; ring_wait_ns is 0 while a full
            # ring drops and counts instead of making the reader wait)
            for name, key in SSF_BRIDGE_TELEMETRY.items():
                tel.incr(S, name, int(st[key]) - int(last.get(key, 0)))
            # what each UDP reader did in the interval
            # (veneur.ingest.reader.*_total tagged reader:<i>: with
            # SO_REUSEPORT the kernel picks a flow's reader, so these
            # say how the flows fell), and how full each bank's fullest
            # sub-ring got since the last flush, in samples of
            # native_ring_capacity / 8 (veneur.ingest.ring_high_water
            # tagged bank:<name>; the take starts the mark again)
            was = last.get("readers", [])
            for i, now in enumerate(st["readers"]):
                old = was[i] if i < len(was) else {}
                for key, n in now.items():
                    tel.incr(f"reader:{i}", "ingest.reader." + key,
                             int(n) - int(old.get(key, 0)))
            for bank, high in self.native_bridge.take_ring_high().items():
                tel.set_gauge("bank:" + bank, "ingest.ring_high_water",
                              high)
            if eng_stats is not None:
                eng_stats["dropped_no_slot"] = (
                    int(st["drops_no_slot"])
                    - int(last.get("drops_no_slot", 0)))
            # vlint: disable=TH01 reason=flush-path-only state; flushes
            # are serialized (one flusher thread, tests call flush_once
            # synchronously), so no concurrent writer exists
            self._last_bridge_stats = st
        if self.admission is not None:
            # overload counters report every interval, zeros included
            # (a zero IS the steady-state signal: the defense is armed
            # and degrading nothing), plus the live governor rate
            for name in ("overload.folded_samples",
                         "overload.fold_sampled_out",
                         "overload.keys_over_budget",
                         "overload.shed_packets"):
                tel.mark(S, name, 0)
            tel.set_gauge(S, "overload.adaptive_sample_rate",
                          self.admission.shed_rate)
        tel.set_gauge(S, "flush.total_duration_ns",
                      (time.monotonic() - t0) * 1e9)
        if self.dedupe_ledger is not None:
            tel.set_gauge(S, "forward.dedupe_ledger_size",
                          self.dedupe_ledger.size())
        if self._engine_journal is not None:
            # engine-checkpoint self-metrics, present-at-zero while the
            # feature is armed (a zero delta-skip/dirty tick IS the
            # steady-state signal); the recovered_* counters were
            # incr'd during recovery-before-listen and drain here
            for name in ("durability.engine_delta_skipped_piles",
                         "durability.engine_recovered_ops",
                         "durability.engine_recovered_metrics",
                         "durability.engine_recovery_errors"):
                tel.mark(S, name, 0)
            dirty, total = self._last_checkpoint_stats
            tel.set_gauge(S, "durability.engine_snapshot_piles_dirty",
                          dirty)
            tel.set_gauge(S, "durability.engine_snapshot_piles_total",
                          total)
            tel.set_gauge(S, "durability.engine_snapshot_bytes",
                          self._engine_journal.last_checkpoint_bytes)
            tel.set_gauge(S, "durability.engine_restore_ns",
                          (self._recovery or {}).get("restore_ns", 0))
        journals = [j for j in (self._forward_journal,
                                self._dedupe_journal,
                                self._engine_journal) if j is not None]
        if journals:
            # counters (journal_appends/truncated_frames/recovered_*)
            # ride the process registry's drain below; the level-style
            # metrics are gauges and come straight from the journals
            tel.set_gauge(S, "durability.journal_bytes",
                          sum(j.size_bytes() for j in journals))
            tel.set_gauge(S, "durability.snapshot_duration_ns",
                          max(j.journal.last_snapshot_ns
                              for j in journals))
        if eng_stats is not None:
            tel.mark(S, "samples.processed", eng_stats["samples"])
            tel.mark(S, "samples.dropped_no_slot",
                     eng_stats["dropped_no_slot"])
            # the ingest's overflow compress: rows compressed one by
            # one, and passes over a whole histogram bank (the dear arm)
            tel.mark(S, "ingest.overflow_rows", eng_stats["overflow_rows"])
            tel.mark(S, "ingest.overflow_bank", eng_stats["overflow_bank"])
            # the hot-slot sidestep: hot rows landed through a work
            # set, and passes it made over a whole histogram bank
            tel.mark(S, "ingest.sidestep_rows", eng_stats["sidestep_rows"])
            tel.mark(S, "ingest.sidestep_bank", eng_stats["sidestep_bank"])
            # the key tables, all banks and engines summed: keys minted
            # into a slot in the interval, keys the idle TTL evicted at
            # this flush, keys holding a slot after it
            # (veneur.keys.interned_total / .evicted_total / .live)
            tel.mark(S, "keys.interned", eng_stats["keys_interned"])
            tel.mark(S, "keys.evicted", eng_stats["keys_evicted"])
            tel.set_gauge(S, "keys.live", eng_stats["keys_live"])
            # the import's hand-over: batches the engines applied and
            # the forwarded metrics in them (one batch a request and
            # engine; a ratio near 1 means requests of one metric)
            tel.mark(S, "import.batches", eng_stats["import_batches"])
            tel.mark(S, "import.batch_metrics",
                     eng_stats["import_metrics"])
            # the import's landings: rows that went through a work set,
            # and landings that compressed the whole bank (the dear arm)
            tel.mark(S, "import.land_rows", eng_stats["import_land_rows"])
            tel.mark(S, "import.land_bank", eng_stats["import_land_bank"])
            # the import's decode: sketches the native pass read from a
            # request's bytes, sketches read from parsed messages in
            # Python, and the hits and misses of the dictionary that
            # finds a natively read sketch's key
            # (veneur.import.decode_native_total / _fallback_total /
            # _key_hits_total / _key_misses_total)
            for k in DECODE_TALLY:
                tel.mark(S, k.replace("_", ".", 1), eng_stats[k])
            # the mesh engine's landings (veneur.import.mesh.*):
            # points staged, programs dispatched, scatter rounds, hot
            # slots pre-clustered on the host, keys a full shard
            # spilled onto another
            for k, name in self._mesh_telemetry.items():
                tel.mark(S, name, eng_stats.get(k, 0))
        # ---- drop classes ----
        # Losses are counted exactly once, at the layer that owns them:
        #   veneur.worker.dropped_total          ingest backpressure —
        #     full worker queues / native rings (queue_drops). Data is
        #     GONE; it never reached a bank.
        #   veneur.samples.dropped_no_slot_total bank capacity — key
        #     churn beyond the slot budget. Also gone.
        #   veneur.sink.flush_errors_total       a sink's delivery
        #     failed AFTER the resilience layer's retries; that sink's
        #     copy of the interval is gone (other sinks unaffected).
        #   veneur.resilience.*                  the egress layer's own
        #     accounting (per destination:) — attempts/retries/
        #     failures/breaker_* describe delivery effort;
        #     spilled/remerged_total are NOT drops OR deliveries: a
        #     failed forward's sketches are spilled, then re-merged
        #     into the next interval's forward (lossless), and only
        #     spill_evicted_total (budget/gauge-age eviction) is loss.
        #
        # Per-sink counts/durations drain from the PREVIOUS interval's
        # fan-out (this interval's sinks haven't run yet) — the sink
        # threads recorded them into scope "sink:<name>" as they
        # finished. Dotted counter names carry their own namespace;
        # plain names are the egress layer's veneur.resilience.* — the
        # mapping lives in observe/registry.py.
        out = (tel.drain(ts, self.hostname)
               + resilience.DEFAULT_REGISTRY.drain(ts, self.hostname))
        if self._stats_sock is not None:
            # scopedstatsd mode: ship veneur.* over the wire to
            # stats_address (usually this server's own statsd port)
            # instead of injecting into this flush.
            lines = []
            for m in out:
                kind = "c" if m.type == MetricType.COUNTER else "g"
                tags = ("|#" + ",".join(m.tags)) if m.tags else ""
                lines.append(f"{m.name}:{m.value:g}|{kind}{tags}")
            try:
                self._stats_sock.sendto("\n".join(lines).encode(),
                                        self._stats_dest)
            except OSError:
                pass
            return []
        return out

    def _fan_out(self, frameset, events, checks, tick=None, parent=-1):
        """Per-sink parallel flush, decoupled from the tick (one
        independent goroutine per sink in Server.Flush — the flusher
        NEVER joins them). Sinks receive the columnar FrameSet; legacy
        sinks materialize InterMetrics lazily in their own thread
        (cached once, shared), frame-native sinks never do. A sink whose
        previous flush is still in flight skips this interval — counted
        as veneur.sink.flush_skipped_total — so one wedged vendor can't
        push the next tick late or starve the other sinks.

        With a tick active, every sink/plugin/span-sink flush gets its
        own phase under `fanout` (the sink threads hold explicit
        handles); a sink still running when the flush tick ends shows
        `in_flight` in /debug/flush — the wedged-vendor signature."""
        tel = self.telemetry
        phase_timers = self.cfg.flush_phase_timers

        def spawn(key, target):
            prev = self._sink_inflight.get(key)
            if prev is not None and prev.is_alive():
                # tagged by component kind so a wedged plugin named
                # like a sink doesn't masquerade as that sink
                tel.incr(f"{key[0]}:{key[1]}", "sink.flush_skipped")
                if tick is not None:
                    tick.finish(tick.start("sink.skip", parent),
                                kind=key[0], name=key[1])
                return
            t = threading.Thread(target=target, daemon=True,
                                 name=f"{key[0]}-{key[1]}")
            # register BEFORE start so stop()'s drain can never miss an
            # in-flight sink; stop() tolerates the not-yet-started window
            # vlint: disable=TH01 reason=flusher-thread-only map; stop()
            # only reads it after _stop is set and the last tick ended
            self._sink_inflight[key] = t
            t.start()

        for s in self.sinks:
            def run(sink=s):
                ph = -1 if tick is None else \
                    tick.start("sink.flush", parent)
                t0 = time.monotonic()
                ok = False
                n = None
                try:
                    n = sink.flush_frames(frameset)
                    if events or checks:
                        sink.flush_other(events, checks)
                    ok = True
                except Exception:
                    log.exception("sink %s flush failed", sink.name())
                finally:
                    # drained in the NEXT interval's veneur.sink.*
                    # self-metrics (flusher.go per-sink spans); a failed
                    # flush reports 0 flushed + an error count, so a
                    # down vendor is visible, not masked. flush_frames
                    # returns the count actually serialized (after sink
                    # routing / STATUS drops); None = everything.
                    count = 0
                    if ok:
                        count = n if isinstance(n, int) else len(frameset)
                    dur_s = time.monotonic() - t0
                    scope = f"sink:{sink.name()}"
                    tel.mark(scope, "sink.metrics_flushed", count)
                    tel.set_gauge(scope, "sink.flush_duration_ns",
                                  dur_s * 1e9)
                    tel.mark(scope, "sink.flush_errors", 0 if ok else 1)
                    # the rows a legacy sink's flush_frames made the
                    # frames build: on the sink whose thread did the
                    # build, zeros on one that found the list cached
                    # or reads blocks (veneur.sink.rows_built_total /
                    # rows_fallback_total)
                    built = frameset.claim_build()
                    tel.mark(scope, "sink.rows_built",
                             built["rows_built"])
                    tel.mark(scope, "sink.rows_fallback",
                             built["rows_fallback"])
                    if tick is not None:
                        tick.finish(ph, sink=sink.name(), ok=ok,
                                    flushed=count, **built)
                        if phase_timers:
                            # per-sink fan-out child timer
                            # (veneur.flush.phase.fanout.<sink>):
                            # emitted HERE, by the sink's own thread,
                            # because the tick-end dogfood sampler
                            # would race sinks still in flight — a
                            # slow vendor is exactly the one a
                            # tick-end sample would miss
                            self._route_metric(observe.fanout_timer_sample(
                                sink.name(), dur_s * 1e3))
            spawn(("sink", s.name()), run)
        for p in self.plugins:
            def runp(plugin=p):
                ph = -1 if tick is None else \
                    tick.start("plugin.flush", parent)
                t0 = time.monotonic()
                ok = True
                try:
                    plugin.flush_frames(frameset, self.hostname)
                except Exception:
                    ok = False
                    log.exception("plugin %s flush failed", plugin.name())
                finally:
                    if tick is not None:
                        tick.finish(ph, plugin=plugin.name(), ok=ok)
                        if phase_timers:
                            self._route_metric(observe.fanout_timer_sample(
                                plugin.name(),
                                (time.monotonic() - t0) * 1e3))
            spawn(("plugin", p.name()), runp)
        for ss in self.span_sinks:
            def runs(sink=ss):
                ph = -1 if tick is None else \
                    tick.start("spansink.flush", parent)
                t0 = time.monotonic()
                ok = True
                try:
                    sink.flush()
                except Exception:
                    ok = False
                    log.exception("span sink %s flush failed",
                                  sink.name())
                finally:
                    if tick is not None:
                        tick.finish(ph, sink=sink.name(), ok=ok)
                        if phase_timers:
                            self._route_metric(observe.fanout_timer_sample(
                                sink.name(),
                                (time.monotonic() - t0) * 1e3))
            spawn(("spansink", ss.name()), runs)

    def _start_profiling(self):
        """enable_profiling: expose the JAX/XLA profiler (xprof) — the
        TPU build's analogue of the reference's net/http/pprof wiring
        (server.go). mutex_profile_fraction / block_profile_rate are
        Go-runtime knobs with no XLA equivalent; they are accepted for
        YAML compatibility and warned about, not silently eaten."""
        if self.cfg.mutex_profile_fraction or self.cfg.block_profile_rate:
            log.warning("mutex_profile_fraction/block_profile_rate are "
                        "Go-runtime profiling knobs with no effect in "
                        "the TPU build; use enable_profiling (JAX "
                        "profiler) instead")
        try:
            import jax
            port = self.cfg.profile_port
            jax.profiler.start_server(port)
            log.info("JAX profiler server on :%d", port)
        except Exception as e:
            log.warning("enable_profiling: JAX profiler unavailable: %s",
                        e)

    # ------------- watchdog -------------

    def _watchdog(self):
        """Stall accounting + crash-only supervision. Every interval
        the watchdog compares now against the last COMPLETED flush;
        an overdue tick increments veneur.watchdog.stalled_ticks_total
        (a wedged flusher is detectable from outside the process —
        /healthz and the counter — instead of only by absence of
        data). The hard exit (Server.FlushWatchdog panics after
        watchdog_max_ticks) stays opt-in via
        flush_watchdog_missed_flushes."""
        interval = self.cfg.interval_seconds
        max_lag = (self.cfg.flush_watchdog_missed_flushes * interval)
        while not self._stop.wait(interval):
            lag = time.monotonic() - self._last_flush_ok
            if lag > interval:
                self._count("watchdog.stalled_ticks")
            if max_lag > 0 and lag > max_lag:
                log.critical(
                    "flush watchdog: no completed flush in %.1fs "
                    "(max %.1fs) — exiting for supervisor restart",
                    lag, max_lag)
                if self._sentry is not None:
                    # ConsumePanic: the event must escape the dying
                    # process, so this send blocks (bounded)
                    self._sentry.capture(
                        None, "flush watchdog expired; crash-only exit",
                        wait=True)
                os._exit(2)

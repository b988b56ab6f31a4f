"""Debug/ops HTTP server: healthchecks, version, the legacy JSON
import path, and the flush introspection surface.

Parity: handlers.go (sym: Server.Serve / HTTPServe — /healthcheck,
/healthcheck/tcp, /version, /builddate) and handlers_global.go (sym:
Server.handleImport — POST /import with a []JSONMetric body; the Go gob
digest blobs are JSON centroid arrays here, matching what
cluster.forward.HttpJsonForwarder emits). The reference also exposes
net/http/pprof; the Python analogues are GET /debug/threads (a stack
dump of every thread) and GET /debug/flush — the flight recorder's
ring of phase-attributed flush ticks plus breaker/ladder/journal/
dedupe-ledger state (schema in README "Observability"), with
GET /debug/flush/profile?ticks=N triggering an on-demand jax.profiler
capture when the server was configured with debug_flush_profile.
"""

from __future__ import annotations

import json
import logging
import sys
import threading
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from . import __version__
from .cluster import wire
from .cluster.protos import metric_pb2
from .ingest.parser import MetricKey

log = logging.getLogger("veneur_tpu.http")

BUILD_DATE = "dev"

_TYPE_TO_PB = {
    "counter": metric_pb2.Counter,
    "gauge": metric_pb2.Gauge,
    "histogram": metric_pb2.Histogram,
    "timer": metric_pb2.Timer,
    "set": metric_pb2.Set,
}


def json_metric_to_pb(d: dict) -> metric_pb2.Metric:
    """One JSONMetric dict → metricpb.Metric, so the HTTP import path
    reuses the gRPC path's merge machinery (handleImport →
    Worker.ImportMetric equivalence)."""
    mtype = d.get("type", "")
    if mtype not in _TYPE_TO_PB:
        raise ValueError(f"unknown metric type {mtype!r}")
    m = metric_pb2.Metric(name=d["name"], type=_TYPE_TO_PB[mtype],
                          tags=list(d.get("tags", [])))
    if mtype in ("histogram", "timer"):
        h = d["histogram"]
        td = m.histogram.t_digest
        # both centroid carriers decode through wire.py (WC01): the
        # lossless [[mean, weight]] list or the q16 packed row
        means, weights = wire.histogram_centroids_from_json(h)
        for mean, w in zip(means, weights):
            if float(w) > 0:
                td.centroids.add(mean=float(mean), weight=float(w))
        td.min = float(h.get("min", 0.0))
        td.max = float(h.get("max", 0.0))
        td.sum = float(h.get("sum", 0.0))
        td.count = float(h.get("count", 0.0))
        td.reciprocal_sum = float(h.get("reciprocal_sum", 0.0))
    elif mtype == "set":
        m.set.hyper_log_log = bytes.fromhex(d["set"])
    elif mtype == "counter":
        m.counter.value = int(d["value"])
    elif mtype == "gauge":
        m.gauge.value = float(d["value"])
    return m


class HttpApi:
    """The ops HTTP listener; `submit_batch(metrics, envelope)` routes
    a POST /import's metrics onto the worker queues (the Server
    provides it)."""

    def __init__(self, address: str, healthy=None,
                 ledger=None, debug_state=None, profile=None,
                 observer=None, fleet_state=None, health=None,
                 submit_batch=None, engine_stamp=None, note_stamp=None,
                 merge_sketches=None, query=None):
        """`debug_state()` (optional) returns the JSON-ready dict for
        GET /debug/flush; `profile(ticks)` (optional) schedules an
        on-demand jax.profiler capture — absent means the knob is off
        and the endpoint answers 403, so an operator can tell "not
        enabled" from "not a server with an engine" (404).

        `observer` (optional, observe.ImportObserver) phase-attributes
        each POST /import and parents its spans on the remote sender's
        flush span. `fleet_state()` serves GET /debug/fleet (the
        per-sender e2e/freshness view). `health()` serves GET /healthz
        and /ready with STRUCTURED verdicts (schema in README
        "Observability"): a dict with `healthy`/`ready` booleans and a
        per-check breakdown — unhealthy answers 503, so a wedged
        flusher is detectable from OUTSIDE the process, not only by
        absence of data. Without `health`, /healthz degrades to the
        legacy boolean `healthy` callback.

        `query` (optional, ISSUE 14): the time-travel query tier —
        GET /query?metric=&q=&t0=&t1= serves historical percentiles /
        counts / cardinalities reconstructed from the durability
        journal's retained checkpoint generations. Absent means the
        tier is not armed on this server (history retention off, or
        not an import tier) and the endpoint answers 404. The callback
        runs the query on the tier's OWN executor — never this handler
        thread beyond the wait, never the ingest/flush path.

        `submit_batch` (optional, `submit_batch(metrics, envelope) ->
        routed count`) routes one request's decoded metrics as a unit
        — the Server's implementation puts one ImportedBatch an engine
        on the worker queues, after write-aheading the request to the
        engine journal where that is armed (before the 200 ack either
        way). Without it the listener is ops-only and answers POST
        /import with 503.

        `engine_stamp` (ISSUE 10): the server's sketch-engine/wire
        stamp; a POST /import whose declared stamp (or implied legacy
        default) does not match is 400'd BEFORE any decode work —
        incompatible sketch payloads must never merge. Verdicts are
        recorded via `note_stamp(sender, stamp, ok)`; advisory
        per-prefix cardinality rows (X-Veneur-Prefix-Sketches) feed
        `merge_sketches(items)`."""
        host, _, port = address.rpartition(":")
        host = host.strip("[]") or "0.0.0.0"
        self._submit_batch = submit_batch
        self._healthy = healthy or (lambda: True)
        self._ledger = ledger   # cluster.importsrv.DedupeLedger or None
        self._debug_state = debug_state
        self._profile = profile
        self._observer = observer
        self._fleet_state = fleet_state
        self._health = health
        self._engine_stamp = engine_stamp
        self._note_stamp = note_stamp
        self._merge_sketches = merge_sketches
        self._query = query
        api = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):   # quiet; logrus-style app logs
                pass

            def _reply(self, code: int, body: bytes,
                       ctype: str = "text/plain"):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path in ("/healthcheck", "/healthcheck/tcp"):
                    if api._healthy():
                        self._reply(200, b"ok\n")
                    else:
                        self._reply(503, b"unhealthy\n")
                elif self.path in ("/healthz", "/ready"):
                    self._health_verdict(self.path == "/ready")
                elif self.path.rstrip("/") == "/debug/fleet":
                    if api._fleet_state is None:
                        self._reply(404, b"no fleet state on this "
                                         b"listener\n")
                        return
                    self._reply(200, json.dumps(
                        api._fleet_state(), default=str).encode(),
                        "application/json")
                elif self.path == "/version":
                    self._reply(200, __version__.encode() + b"\n")
                elif self.path == "/builddate":
                    self._reply(200, BUILD_DATE.encode() + b"\n")
                elif self.path == "/debug/threads":
                    frames = sys._current_frames()
                    out = []
                    for t in threading.enumerate():
                        out.append(f"--- {t.name} ({t.ident}) ---")
                        f = frames.get(t.ident)
                        if f is not None:
                            out.extend(traceback.format_stack(f))
                    self._reply(200, "\n".join(out).encode())
                elif self.path.startswith("/debug/flush"):
                    self._debug_flush()
                elif urlparse(self.path).path.rstrip("/") == "/query":
                    self._serve_query()
                else:
                    self._reply(404, b"not found\n")

            def _serve_query(self):
                """GET /query (ISSUE 14): time-travel reads from the
                durability journal's retained generations. Schema in
                README 'Time-travel queries'."""
                if api._query is None:
                    self._reply(404, b"no time-travel query tier on "
                                     b"this server (set "
                                     b"history_retention_generations "
                                     b"with durability enabled)\n")
                    return
                # keep_blank_values: `tags=` (empty) means "untagged
                # keys only", distinct from no tags filter at all
                qs = parse_qs(urlparse(self.path).query,
                              keep_blank_values=True)
                params = {k: v[0] for k, v in qs.items() if v}
                try:
                    body = api._query(params)
                except Exception as e:
                    status = getattr(e, "status", 500)
                    detail = getattr(e, "detail", f"query failed: {e}")
                    self._reply(status, json.dumps(
                        {"error": detail}).encode(),
                        "application/json")
                    return
                self._reply(200, json.dumps(
                    body, default=str).encode(), "application/json")

            def _health_verdict(self, readiness: bool):
                """GET /healthz | /ready: structured verdicts, 503 on
                a failing verdict so supervisors/probes need no JSON
                parsing — the body carries the why."""
                if api._health is None:
                    ok = bool(api._healthy())
                    body = {"healthy": ok, "ready": ok, "checks": {}}
                else:
                    body = api._health()
                ok = body.get("ready" if readiness else "healthy", False)
                self._reply(200 if ok else 503,
                            json.dumps(body, default=str).encode(),
                            "application/json")

            def _debug_flush(self):
                u = urlparse(self.path)
                if u.path.rstrip("/") == "/debug/flush/profile":
                    if api._profile is None:
                        self._reply(403, b"profiler capture disabled "
                                         b"(set debug_flush_profile)\n")
                        return
                    try:
                        ticks = int(parse_qs(u.query).get(
                            "ticks", ["1"])[0])
                    except ValueError:
                        self._reply(400, b"ticks must be an integer\n")
                        return
                    self._reply(200, json.dumps(
                        api._profile(ticks)).encode(),
                        "application/json")
                    return
                if u.path.rstrip("/") != "/debug/flush":
                    self._reply(404, b"not found\n")
                    return
                if api._debug_state is None:
                    self._reply(404, b"no flush state on this "
                                     b"listener\n")
                    return
                state = api._debug_state()
                self._reply(200, json.dumps(
                    state, default=str).encode(), "application/json")

            def do_POST(self):
                if self.path != "/import":
                    self._reply(404, b"not found\n")
                    return
                if api._submit_batch is None:
                    self._reply(503, b"not a global veneur\n")
                    return
                # jsonmetric-v1 contract: reject a declared format we
                # don't speak rather than misparse it; absent header =
                # v1 (curl/operator tooling)
                ver = self.headers.get("X-Veneur-Forward-Version")
                if ver is not None and ver != "jsonmetric-v1":
                    self._reply(400, f"unsupported forward format "
                                     f"{ver!r}\n".encode())
                    return
                # idempotency envelope (exactly-once forward): decoded
                # up front so a malformed one 400s before any work, but
                # NOT admitted to the ledger until the body has fully
                # decoded — admitting first would record a chunk whose
                # read/parse then failed as "applied", and the sender's
                # safe re-send (a 400 promises nothing was imported)
                # would be dropped as a duplicate.
                try:
                    env = wire.envelope_from_headers(self.headers)
                except ValueError as e:
                    self._reply(400, f"bad forward envelope: "
                                     f"{e}\n".encode())
                    return
                # sketch-engine/wire stamp (ISSUE 10): a mismatched
                # fleet degrades LOUDLY — 400 before any decode work,
                # verdict counted + recorded per sender
                obs_kw = {}
                if api._engine_stamp is not None:
                    from . import sketches
                    remote = wire.sketch_stamp_from_headers(self.headers)
                    ok = sketches.stamp_compatible(api._engine_stamp,
                                                   remote)
                    if not ok:
                        # mismatch: counted + the sender's row marked
                        # (it IS alive, just misconfigured); accepted
                        # stamps annotate via the observer scope only
                        # after the body proves decodable
                        if api._note_stamp is not None:
                            api._note_stamp(
                                env[0] if env else "(unknown)",
                                remote, False)
                        self._reply(400, b"sketch engine/wire-format "
                                         b"mismatch\n")
                        return
                    obs_kw["stamp"] = remote
                # delta-over-gap refusal (ISSUE 13): a delta chunk may
                # only apply over an unbroken per-sender seq chain —
                # checked from the HEADERS, before any body decode,
                # like the stamp gate. 409 + the marker body is the
                # wire shape the sender's fallback recognizes (spill
                # the payload, force a full resync); the refused delta
                # was never applied so nothing is lost or doubled.
                if (env is not None and api._ledger is not None
                        and wire.forward_kind_from_headers(self.headers)
                        == "delta"
                        and not api._ledger.check_delta(env[0], env[1])):
                    self._reply(409, json.dumps(
                        {"error": wire.DELTA_GAP_DETAIL,
                         "sender": env[0], "seq": env[1]}).encode(),
                        "application/json")
                    return
                if api._merge_sketches is not None:
                    raw = self.headers.get(wire.PREFIX_SKETCH_HEADER)
                    if raw:
                        items = wire.decode_prefix_sketches_header(raw)
                        if items:
                            api._merge_sketches(items)
                if api._observer is not None:
                    # tolerant trace decode (None on malformed) + the
                    # import ring / span-tree / fleet observation scope
                    trace = wire.trace_from_headers(self.headers)
                    with api._observer.request(env, trace, "http",
                                               **obs_kw) as scope:
                        self._import_body(env, scope)
                else:
                    self._import_body(env, None)

            def _import_body(self, env, scope):
                ph = -1 if scope is None else scope.start("decode")
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n))
                    if not isinstance(body, list):
                        raise ValueError("body must be a JSON array "
                                         "of metrics")
                    # decode the whole batch before submitting any of it
                    # (atomic like handleImport: a 400 means nothing was
                    # imported, so clients may safely re-send)
                    decoded = [json_metric_to_pb(d) for d in body]
                except (ValueError, KeyError, TypeError) as e:
                    if scope is not None:
                        scope.finish(ph, outcome="error")
                        scope.rejected = True
                    self._reply(400, f"bad import body: {e}\n".encode())
                    return
                if scope is not None:
                    scope.finish(ph, n_metrics=len(decoded))
                # payload fully in hand: NOW consult the ledger — a
                # chunk it has already admitted is dropped WHOLE, with
                # a 200 (the sender delivered it, it just can't know
                # that yet: the ambiguous-failure replay path)
                ph = -1 if scope is None else scope.start("dedupe")
                admitted = not (env is not None
                                and api._ledger is not None
                                and not api._ledger.admit(*env))
                if scope is not None:
                    scope.finish(ph, admitted=admitted)
                    scope.admitted = admitted
                if not admitted:
                    self._reply(200, json.dumps(
                        {"imported": 0, "deduped": True}).encode(),
                        "application/json")
                    return
                ph = -1 if scope is None else scope.start("route")
                count = api._submit_batch(decoded, env)
                if scope is not None:
                    scope.finish(ph, n_metrics=count)
                    scope.n_metrics = count
                self._reply(200, json.dumps({"imported": count}).encode(),
                            "application/json")

        self._httpd = ThreadingHTTPServer((host, int(port or 0)), Handler)
        self.port = self._httpd.server_port
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="http-api", daemon=True)

    def start(self):
        self._thread.start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()

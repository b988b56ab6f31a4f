"""The system under test and the tick that drives it.

Two `veneur_tpu.server.Server`s built from a deployment file
(`perfbench/configs/<name>.json`: the two servers' config keys as an
operator would write them) in the one process that holds the chip: a
local tier on the native bridge forwarding over gRPC to a global tier.
One tick is one flush interval under a scripted clock:

  bench.send          every datagram of the payload into the local's
                      UDP socket, paced on the bridge's counters (one
                      sender, closed loop)
  bench.settle        last datagram -> every line parsed, pumped,
                      landed on the device (`Server.drain`,
                      `block_until_ready`)
  bench.local_flush   `flush_once` of the local: device, fetch,
                      materialize, export, gRPC forward
  bench.global_drain  forward acknowledged -> every import applied
  bench.global_flush  `flush_once` of the global
  bench.sink_wait     -> the global's sink holds the flush

From the program the harness takes the servers, their flight recorder
phases, `bridge.stats()` and the telemetry registry's counters; every
clock reading, span and reduction is its own. Pacing is `send_window`
of `chip_smoke.py`, copied.
"""

from __future__ import annotations

import contextlib
import gc
import os
import socket
import threading
import time

from perfbench.traffic import HERE, load_data


def log(msg: str = "") -> None:
    print(msg, flush=True)


def load_config(name: str, rehearsal: bool = False,
                root: str = HERE) -> dict:
    return load_data("configs", name, rehearsal, root)


# ------------------------------------------------------------------ meters

class CompileMeter:
    """Counts what JAX compiles (or fetches from the persistent cache)
    through jax.monitoring. Copied from chip_smoke.py."""

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GcMeter:
    """Every garbage collection of the process with its edges on the
    monotonic clock (`gc.callbacks`), so a tick can say how many fell
    inside it and how long they took."""

    def __init__(self):
        self.spans: list = []          # (t0_ns, t1_ns, generation)
        self._t0 = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.monotonic_ns()
        else:
            self.spans.append((self._t0, time.monotonic_ns(),
                               info.get("generation", -1)))

    def close(self):
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(self._cb)

    def inside(self, t0_ns: int, t1_ns: int):
        hit = [(a, b) for a, b, _g in self.spans if a < t1_ns and b > t0_ns]
        return len(hit), sum(min(b, t1_ns) - max(a, t0_ns)
                             for a, b in hit) / 1e9


class Spans:
    """The benchmark's own spans: (name, t0_ns, t1_ns) on the monotonic
    clock, each also written into the profiler's trace when one is being
    taken (`jax.profiler.TraceAnnotation`), so the trace reduction can
    lay host spans and device operations on one clock."""

    def __init__(self):
        self.rows: list = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.monotonic_ns()))


# ------------------------------------------------------------------- sinks

def make_sink():
    """A sink that keeps only the newest flush and stamps its arrival:
    `emit_latency_s` ends here."""
    from veneur_tpu.sinks.basic import CaptureMetricSink

    class StampSink(CaptureMetricSink):
        def __init__(self):
            super().__init__()
            self.count = 0
            self.arrived_ns = 0

        def flush(self, metrics):
            now = time.monotonic_ns()
            with self._cv:
                self.flushes = [list(metrics)]
                self.count += 1
                self.arrived_ns = now
                self._cv.notify_all()

        def wait_count(self, n, timeout):
            with self._cv:
                return self._cv.wait_for(lambda: self.count >= n, timeout)

    return StampSink()


# ------------------------------------------------------------------- tiers

class Tiers:
    def __init__(self, cfg: dict, rehearsal: bool):
        import yaml

        from veneur_tpu.config import read_config
        from veneur_tpu.server import Server
        self.cfg = cfg
        self.gsink, self.lsink = make_sink(), make_sink()
        self.lsrv = self.gsrv = None
        common = dict(cfg["common"])
        if rehearsal:
            common["aggregation_backend"] = "cpu"

        def build(tier, extra, sink):
            text = yaml.safe_dump({**common, **cfg[tier], **extra})
            return Server(read_config(text=text, env={}), sinks=[sink])

        try:
            self.gsrv = build("global", {}, self.gsink)
            self.gsrv.start()
            self.lsrv = build(
                "local",
                {"forward_address": f"127.0.0.1:{self.gsrv.grpc_port}"},
                self.lsink)
            self.lsrv.start()
        except BaseException:
            self.stop()
            raise
        self.leng, self.geng = self.lsrv.engines[0], self.gsrv.engines[0]
        self.bridge = self.lsrv.native_bridge
        if self.bridge is None:
            self.stop()
            raise RuntimeError("the local tier is not on the native bridge")
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.dest = ("127.0.0.1", self.lsrv.bound_port())
        self.flushes = 0

    def stop(self):
        with contextlib.suppress(Exception):
            self.sock.close()
        for srv in (self.lsrv, self.gsrv):
            if srv is not None:
                srv.stop()

    # -- what the guarantees are judged by, besides the sinks

    def mesh_devices(self) -> int:
        """Distinct devices under the least-spread leaf of the global's
        banks (1 for a one-chip engine)."""
        import jax
        me = getattr(self.geng, "me", None)
        if me is None:
            return 1
        return min(len({s.device for s in leaf.addressable_shards})
                   for leaf in jax.tree_util.tree_leaves(me.banks))

    def drop_counters(self) -> dict:
        from veneur_tpu import kernels
        from veneur_tpu.observe import SERVER_SCOPE
        st = self.bridge.stats()
        out = {"local.parse_errors": int(st["parse_errors"]),
               "local.ring_drops": int(st["ring_drops"]),
               "local.other_drops": int(st["other_drops"]),
               "local.drops_no_slot": int(st["drops_no_slot"]),
               "forwarder.pending_spill": int(
                   getattr(self.lsrv.forwarder, "pending_spill", 0)),
               "kernels.fallback_total": int(kernels.fallback_total())}
        for tier, srv in (("local", self.lsrv), ("global", self.gsrv)):
            for name in ("flush.error", "packet.error", "worker.dropped",
                         "samples.dropped_no_slot", "import.rejected"):
                out[f"{tier}.{name}"] = int(
                    srv.telemetry.total(SERVER_SCOPE, name))
        return out

    # -- the import landing's lane widths

    def watch_landing(self):
        """The one-chip global lands a batch of imported digests through
        `cluster_rows` on a [digests, lanes] matrix whose lanes are the
        widest digest's centroids rounded up to 128. A hot key's digest
        holds 118-130 centroids at compression 100, and which side of
        128 it falls on follows how the local's pump happened to batch
        its samples: a width the warm-up ticks did not meet can turn up
        in a timed tick. So the warm-up ticks record every shape the
        landing clusters, and `warm_landing_widths` then runs each at
        the other widths. An engine that lands another way (the mesh
        engine's fixed batches) records nothing."""
        self._landing, self._landing_seen = None, set()
        heng = getattr(self.geng, "_heng", None)
        inner = getattr(heng, "cluster_rows", None)
        if inner is None:
            return

        def recording(values, weights, **kw):
            self._landing_seen.add((values.shape, tuple(sorted(kw.items()))))
            return inner(values, weights, **kw)

        # the engine's sketch adapter is a frozen dataclass
        object.__setattr__(heng, "cluster_rows", recording)
        self._landing = (heng, inner)

    def warm_landing_widths(self) -> list:
        """Stop recording; cluster zeros at every multiple of 128 lanes,
        up to the widest met and at least 256, that a recorded landing
        did not use. Returns the shapes it warmed."""
        import jax
        import numpy as np
        if self._landing is None:
            return []
        heng, inner = self._landing
        object.__delattr__(heng, "cluster_rows")    # the class's own again
        self._landing = None
        seen = self._landing_seen
        widest = max([256] + [shape[1] for shape, _kw in seen])
        warmed = []
        for (rows, _lanes), kw in sorted(seen):
            for lanes in range(128, widest + 1, 128):
                if ((rows, lanes), kw) in seen:
                    continue
                seen.add(((rows, lanes), kw))
                zeros = np.zeros((rows, lanes), np.float32)
                jax.block_until_ready(inner(zeros, zeros, **dict(kw)))
                warmed.append((rows, lanes))
        return warmed

    def forward_bytes(self) -> int:
        from veneur_tpu import resilience
        regs = {id(r): r for r in (self.lsrv.telemetry,
                                   resilience.DEFAULT_REGISTRY)}
        return int(sum(
            v for r in regs.values()
            for (_s, n), v in r.totals_by_name_prefix("forward.bytes").items()
            if n == "forward.bytes"))

    # -- one tick

    def send(self, dgrams: list, n_lines: int, timeout_s: float) -> dict:
        """All of a payload's datagrams, paced so that neither the
        socket buffer (16 datagrams in flight) nor the bridge's sample
        rings can drop one: a reader thread stages into ONE of a bank's
        8 sub-rings, so with the single reader a bank holds
        native_ring_capacity / 8 samples; the sender keeps the samples
        parsed but not yet pumped under half of that. Returns the clock
        readings of the send phase and the time spent waiting."""
        bridge, eng, sock, dest = self.bridge, self.leng, self.sock, self.dest
        ring_room = self.lsrv.cfg.native_ring_capacity // 16
        st = bridge.stats()
        base, base_lines = int(st["packets"]), int(st["lines"])
        waited = 0
        t_first = time.monotonic_ns()
        deadline = time.monotonic() + timeout_s
        for i, d in enumerate(dgrams):
            sock.sendto(d, dest)
            if i % 8 == 7:
                w0 = 0
                while True:
                    st = bridge.stats()
                    in_flight = base + i + 1 - int(st["packets"])
                    unpumped = (int(st["lines"]) - base_lines
                                - eng.samples_processed)
                    if in_flight <= 16 and unpumped <= ring_room:
                        break
                    if not w0:
                        w0 = time.monotonic_ns()
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"pacing stalled: {in_flight} datagrams in "
                            f"flight, {unpumped} samples unpumped")
                    time.sleep(0.0002)
                if w0:
                    waited += time.monotonic_ns() - w0
        t_last = time.monotonic_ns()
        return {"t_first": t_first, "t_last": t_last, "wait_ns": waited,
                "base_lines": base_lines, "n_lines": n_lines,
                "deadline": deadline}

    def settle(self, sent: dict):
        """Every line parsed, staged, pumped and landed. A reader counts
        a burst's lines while parsing and pushes its samples to the
        rings right after, so the bridge's line count runs a few
        microseconds ahead of what the pump can see, and a sample that
        straggled into the next tick would fail that key's exact count.
        The engine's own count of samples taken in this interval closes
        the gap: every line of the payload is one sample, so the tick
        has landed when the engine has counted them all."""
        import jax
        eng = self.leng
        want = sent["base_lines"] + sent["n_lines"]
        while (int(self.bridge.stats()["lines"]) < want
               or eng.samples_processed < sent["n_lines"]):
            if time.monotonic() > sent["deadline"]:
                got = int(self.bridge.stats()["lines"]) - sent["base_lines"]
                raise TimeoutError(
                    f"datagrams lost: bridge parsed {got} and the engine "
                    f"took {eng.samples_processed} of {sent['n_lines']} "
                    f"lines")
            time.sleep(0.0002)
        # the pump cycle that moved the last samples holds the pump's
        # lock until its ingest program is dispatched; drain waits on it
        if not self.lsrv.drain(timeout=600.0):
            raise TimeoutError("local tier did not drain its rings")
        jax.block_until_ready((eng.histo_bank, eng.counter_bank,
                               eng.gauge_bank, eng.set_bank))

    @staticmethod
    def _phases(srv, tier: str) -> list:
        tick = srv.flight.last_tick() if srv.flight is not None else None
        if tick is None:
            return []
        return [(f"{tier}:{name}", t0, t1)
                for name, t0, t1, _p in tick.phases() if t1 > t0]

    def tick(self, payload: dict, ts: int, spans: Spans, gcm: GcMeter,
             meter: CompileMeter) -> dict:
        """One whole tick; returns its record (clock readings in ns on
        the monotonic clock, flight recorder phases of both tiers,
        counter deltas, what the process did meanwhile)."""
        lost0 = self.bridge.stats()
        bytes0 = self.forward_bytes()
        compiles0 = meter.requests       # == len(meter.names)
        cpu0, n0 = time.process_time(), len(spans.rows)
        with spans.span("bench.send"):
            sent = self.send(payload["datagrams"], payload["n_lines"], 600.0)
        with spans.span("bench.settle"):
            self.settle(sent)
        t_landed = time.monotonic_ns()
        with spans.span("bench.local_flush"):
            self.lsrv.flush_once(timestamp=ts)
        # the forward is acknowledged once its metrics sit on the
        # global's worker queues; flush when they are applied
        with spans.span("bench.global_drain"):
            if not self.gsrv.drain(timeout=600.0):
                raise TimeoutError("global tier did not drain its imports")
        with spans.span("bench.global_flush"):
            self.gsrv.flush_once(timestamp=ts + 5)
        self.flushes += 1
        with spans.span("bench.sink_wait"):
            ok = (self.gsink.wait_count(self.flushes, 120.0)
                  and self.lsink.wait_count(self.flushes, 120.0))
        if not ok:
            raise TimeoutError("a sink missed its flush")
        t_end = self.gsink.arrived_ns
        st = self.bridge.stats()
        lost = (sent["n_lines"] - (int(st["lines"]) - int(lost0["lines"]))
                + sum(int(st[k]) - int(lost0[k]) for k in
                      ("ring_drops", "drops_no_slot", "other_drops")))
        gc_n, gc_s = gcm.inside(sent["t_last"], t_end)
        own = {name: (t1 - t0) / 1e9 for name, t0, t1 in spans.rows[n0:]}
        phases = self._phases(self.lsrv, "local") + self._phases(
            self.gsrv, "global")
        return {
            "lines": sent["n_lines"],
            "t_first_ns": sent["t_first"], "t_last_ns": sent["t_last"],
            "t_landed_ns": t_landed, "t_end_ns": t_end,
            "ingest_s": (t_landed - sent["t_first"]) / 1e9,
            "emit_latency_s": (t_end - sent["t_last"]) / 1e9,
            "gen_wait_s": sent["wait_ns"] / 1e9,
            "spans": own,
            "phase_rows": phases,
            "counters": {"forward.bytes": self.forward_bytes() - bytes0,
                         "bridge.lost_lines": lost,
                         "compile.programs": meter.requests - compiles0},
            "compiled": meter.names[compiles0:],
            "cpu_s": time.process_time() - cpu0,
            "wall_s": (t_end - sent["t_first"]) / 1e9,
            "gc_n": gc_n, "gc_s": gc_s,
            "threads": threading.active_count(),
            "loadavg": os.getloadavg()[0],
            "flush_path": {"local": dict(self.leng._last_flush_info),
                           "global": dict(getattr(
                               self.geng, "_last_flush_info", {}))},
        }


def phase_seconds(rows: list) -> dict:
    """{name: summed seconds} of one tick's flight recorder phases."""
    out: dict = {}
    for name, t0, t1 in rows:
        out[name] = out.get(name, 0.0) + (t1 - t0) / 1e9
    return out

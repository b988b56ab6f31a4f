"""The driver `two_tier`: a local tier forwarding to a global tier.

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

From the program the driver takes the servers, their flight recorder
phases, `bridge.stats()` and the telemetry registry's counters; every
clock reading, span and reduction is the benchmark's own
(`perfbench/harness.py`). Pacing is `send_window` of `chip_smoke.py`,
copied. Payloads are the generator `dogstatsd_lines`'s: datagrams of
DogStatsD lines.
"""

from __future__ import annotations

import contextlib
import socket
import time

from perfbench import harness, reference
from perfbench.harness import CompileMeter, GcMeter, Spans


class Driver:
    TAKES = "datagrams"
    OPS = "lines"

    def __init__(self, cfg: dict, rehearsal: bool):
        from veneur_tpu.ingest import native
        native.build()
        self.cfg = cfg
        self.gsink, self.lsink = harness.make_sink(), harness.make_sink()
        self.lsrv = self.gsrv = None
        try:
            self.gsrv = harness.build_server(cfg, "global", {}, self.gsink,
                                             rehearsal)
            self.gsrv.start()
            self.lsrv = harness.build_server(
                cfg, "local",
                {"forward_address": f"127.0.0.1:{self.gsrv.grpc_port}"},
                self.lsink, rehearsal)
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
        self._landing = None

    def stop(self):
        with contextlib.suppress(Exception):
            self.sock.close()
        for srv in (self.lsrv, self.gsrv):
            if srv is not None:
                srv.stop()

    # -- what the guarantees are judged by, besides the sinks

    def mesh_devices(self) -> int:
        return harness.mesh_devices(self.geng)

    def drop_counters(self) -> dict:
        from veneur_tpu import kernels
        st = self.bridge.stats()
        out = {"local.parse_errors": int(st["parse_errors"]),
               "local.ring_drops": int(st["ring_drops"]),
               "local.other_drops": int(st["other_drops"]),
               "local.drops_no_slot": int(st["drops_no_slot"]),
               "forwarder.pending_spill": int(
                   getattr(self.lsrv.forwarder, "pending_spill", 0)),
               "kernels.fallback_total": int(kernels.fallback_total())}
        out.update(harness.server_counters(self.lsrv, "local"))
        out.update(harness.server_counters(self.gsrv, "global"))
        return out

    # -- the import landing's lane widths (harness.LandingWatch)

    def watch_warmup(self):
        self._landing = harness.LandingWatch(self.geng)

    def finish_warmup(self) -> list:
        return self._landing.warm_other_widths()

    def forward_bytes(self) -> int:
        return harness.registry_total(self.lsrv, "forward.bytes")

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

    def tick(self, payload: dict, ts: int, spans: Spans, gcm: GcMeter,
             meter: CompileMeter) -> dict:
        """One whole tick; returns its record (clock readings in ns on
        the monotonic clock, flight recorder phases of both tiers,
        counter deltas, what the process did meanwhile)."""
        lost0 = self.bridge.stats()
        bytes0 = self.forward_bytes()
        books = harness.TickBooks(spans, meter)
        with spans.span("bench.send"):
            sent = self.send(payload["datagrams"], payload["n_lines"], 600.0)
        with spans.span("bench.settle"):
            self.settle(sent)
        t_landed = time.monotonic_ns()
        with spans.span("bench.local_flush"):
            self.lsrv.flush_once(timestamp=ts)
        harness.flush_global(self.gsrv, ts + 5, spans, 600.0)
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
        rec = books.close(gcm, sent["t_first"], sent["t_last"], t_end)
        rec["counters"].update({
            "forward.bytes": self.forward_bytes() - bytes0,
            "bridge.lost_lines": lost})
        rec.update({
            "attempted": sent["n_lines"], "lines": sent["n_lines"],
            "t_landed_ns": t_landed,
            "ingest_s": (t_landed - sent["t_first"]) / 1e9,
            "gen_wait_s": sent["wait_ns"] / 1e9,
            "phase_rows": (harness.server_phases(self.lsrv, "local")
                           + harness.server_phases(self.gsrv, "global")),
            "flush_path": {"local": dict(self.leng._last_flush_info),
                           "global": dict(getattr(
                               self.geng, "_last_flush_info", {}))},
        })
        return rec

    # -- the tick just driven against its reference, between ticks

    def check(self, payload: dict, rec: dict, tol: dict) -> dict:
        """Both sinks' newest flush and the tick's lost lines against
        the payload's reference. `failed` counts the timer lines the
        emitted counts do not account for and the lines the bridge
        lost."""
        local = reference.sink_values(self.lsink.take())
        glob = reference.sink_values(self.gsink.take())
        how = (self.cfg.get("control") or {}).get("answers")
        if how:
            local = reference.degrade(local, how)
            glob = reference.degrade(glob, how)
        lost = rec["counters"]["bridge.lost_lines"]
        v = reference.check_tick(payload["ref"], local, glob, tol)
        v["numbers"]["bridge.lost_lines"] = (float(abs(lost)), 0.0)
        v["attempted"] = payload["n_lines"]
        v["failed"] = (payload["timer_lines"] - v.pop("accounted_lines")
                       + max(0, lost))
        return v

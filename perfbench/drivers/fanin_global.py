"""The driver `fanin_global`: one global tier behind a fleet of senders.

One `veneur_tpu.server.Server` built from the deployment file's `common`
and `global` keys, alone in the process that holds the chip. The locals
are not servers: `fan_in_locals` sender threads, each with a gRPC channel
and a seq chain of its own, ship requests the generator
`forward_payloads` built in set-up. They are outside the timed path
except for their calls. One tick is one flush boundary of the fleet
under a scripted clock:

  bench.forwards      all senders released at once -> the last
                      acknowledgement. First tick `full`, later ticks
                      `delta` on the sender's unbroken chain; the
                      payload's `replayed` senders send their request a
                      second time after its acknowledgement (a retry
                      after a lost one): the global must drop it
  bench.global_drain  -> every import applied
  bench.global_flush  `flush_once` of the global
  bench.sink_wait     -> the global's sink holds the flush

`emit_latency_s` runs from the release to the sink: `t_first_ns` and
`t_last_ns` are both the release. Which senders' requests share a
landing follows thread scheduling, and the `[S, W]` each landing
clusters is what the probe is there to show: a run that writes its
ticks' records (`--ticks-out`, the study's runs) keeps the warm-up's
recorder on the program's `cluster_rows` and gives every tick's
`landing_shapes`, timed ticks too. A benchmark run takes the recorder
off after the warm-up, as the `two_tier` driver does: its window times
the program as it is, and its records carry no shapes. Set-up warms
every lane width up to the widest pile the fleet can make. A tick has
120 s.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time

from perfbench import harness, reference
from perfbench.harness import CompileMeter, GcMeter, Spans

TICK_TIMEOUT_S = 120.0
SEND_METRICS = "/forwardrpc.Forward/SendMetrics"


class Driver:
    TAKES = "forward_requests"
    OPS = "sketches"

    def __init__(self, cfg: dict, rehearsal: bool):
        import grpc
        self.cfg = cfg
        self.gsink = harness.make_sink()
        self.gsrv = None
        self.channels: list = []
        self.gsrv = harness.build_server(cfg, "global", {}, self.gsink,
                                         rehearsal)
        try:
            self.gsrv.start()
            self.geng = self.gsrv.engines[0]
            address = f"127.0.0.1:{self.gsrv.grpc_port}"
            for _ in range(int(cfg["fan_in_locals"])):
                ch = grpc.insecure_channel(address)
                self.channels.append(ch)
            self.calls = [ch.unary_unary(SEND_METRICS,
                                         request_serializer=None,
                                         response_deserializer=None)
                          for ch in self.channels]
            for ch in self.channels:
                grpc.channel_ready_future(ch).result(timeout=30.0)
        except BaseException:
            self.stop()
            raise
        self.flushes = 0
        self._landing = None
        self._widest_pile = 0

    def stop(self):
        for ch in self.channels:
            with contextlib.suppress(Exception):
                ch.close()
        if self.gsrv is not None:
            self.gsrv.stop()

    def mesh_devices(self) -> int:
        return harness.mesh_devices(self.geng)

    def watch_warmup(self):
        self._landing = harness.LandingWatch(self.geng)

    def finish_warmup(self) -> list:
        """Every lane width up to the widest pile the fleet can make,
        every sender's digest of a hot key in one stage (the payloads
        say how wide): the first probe met a width in its 21st tick that
        no warm-up tick had met (PERF.md, PR 28). Only a study's run
        keeps recording behind it."""
        keep = bool(self.cfg.get("study"))
        warmed = self._landing.warm_other_widths(keep=keep,
                                                 upto=self._widest_pile)
        if not keep:
            self._landing = None
        return warmed

    def drop_counters(self) -> dict:
        from veneur_tpu import kernels
        out = {"kernels.fallback_total": int(kernels.fallback_total())}
        out.update(harness.server_counters(self.gsrv, "global"))
        for name in ("forward.chunk_overflow", "forward.delta_gap_refused",
                     "import.engine_mismatch"):
            out[f"global.{name}"] = harness.registry_total(self.gsrv, name)
        led = self.gsrv.dedupe_ledger
        if led is not None:
            # a sender the ledger forgot would make its next replay an
            # apply: every sender must still be known at the end
            out["global.dedupe.senders_forgotten"] = max(
                0, len(self.channels) - led.sender_count())
            harness.log(f"dedupe ledger: {led.size()} chunk entries of "
                        f"{led.sender_count()} senders")
        return out

    def duplicates_dropped(self) -> int:
        """Chunks the dedupe ledger has dropped as replays."""
        return harness.registry_total(self.gsrv,
                                      "forward.duplicates_dropped")

    # -- one tick

    def _envelopes(self, kind: str) -> list:
        from veneur_tpu.cluster import wire
        from veneur_tpu.cluster.protos import forward_pb2
        seq = self.flushes + 1
        return [forward_pb2.MetricList(envelope=wire.envelope_pb(
            f"bench-local-{s:02d}", seq, 0, 1, kind=kind)
        ).SerializeToString() for s in range(len(self.calls))]

    def forwards(self, payload: dict, spans: Spans) -> dict:
        """Every sender ships its request on its own channel and, where
        the payload says so, ships it again. The threads are started and
        each tick's envelope is put behind its request before the span
        opens: `bench.forwards` is release -> last acknowledgement."""
        envs = self._envelopes("full" if self.flushes == 0 else "delta")
        bodies = [b + e for b, e in zip(payload["requests"], envs)]
        twice = set(payload["replayed"])
        gate = threading.Event()
        acks = [0] * len(bodies)
        errors: list = []

        def sender(s):
            gate.wait()
            try:
                for _ in range(2 if s in twice else 1):
                    self.calls[s](bodies[s], timeout=TICK_TIMEOUT_S)
                acks[s] = time.monotonic_ns()
            except Exception as e:      # raised by the tick, below
                errors.append((s, e))

        threads = [threading.Thread(target=sender, args=(s,), daemon=True)
                   for s in range(len(bodies))]
        for th in threads:
            th.start()
        with spans.span("bench.forwards"):
            t0 = time.monotonic_ns()
            gate.set()
            deadline = time.monotonic() + TICK_TIMEOUT_S
            for th in threads:
                th.join(max(0.0, deadline - time.monotonic()))
        if any(th.is_alive() for th in threads):
            raise TimeoutError(f"a sender had no acknowledgement in "
                               f"{TICK_TIMEOUT_S:.0f} s")
        if errors:
            s, e = errors[0]
            raise RuntimeError(f"{len(errors)} sender(s) failed; sender "
                               f"{s}: {e}") from e
        return {"t_release": t0,
                "acks_s": sorted((a - t0) / 1e9 for a in acks),
                "bytes": sum(len(bodies[s]) * (2 if s in twice else 1)
                             for s in range(len(bodies)))}

    def tick(self, payload: dict, ts: int, spans: Spans, gcm: GcMeter,
             meter: CompileMeter) -> dict:
        self._widest_pile = max(self._widest_pile, payload["widest_pile"])
        dup0 = self.duplicates_dropped()
        books = harness.TickBooks(spans, meter)
        sent = self.forwards(payload, spans)
        harness.flush_global(self.gsrv, ts + 5, spans, TICK_TIMEOUT_S)
        self.flushes += 1
        with spans.span("bench.sink_wait"):
            if not self.gsink.wait_count(self.flushes, TICK_TIMEOUT_S):
                raise TimeoutError("the global's sink missed its flush")
        t0, t_end = sent["t_release"], self.gsink.arrived_ns
        dropped = self.duplicates_dropped() - dup0
        acks = sent["acks_s"]
        rec = books.close(gcm, t0, t0, t_end)
        rec["counters"].update({"fleet.bytes": sent["bytes"],
                                "import.duplicates_dropped": dropped})
        rec.update({
            "attempted": payload["n_sketches"],
            "acks_s": {"first": acks[0], "median": acks[len(acks) // 2],
                       "last": acks[-1]},
            "phase_rows": harness.server_phases(self.gsrv, "global"),
            "flush_path": {"global": dict(getattr(
                self.geng, "_last_flush_info", {}))},
        })
        if self._landing is not None:
            rec["landing_shapes"] = self._landing.taken()
        harness.log(f"  landings [S, W]: {rec.get('landing_shapes', '-')}  "
                    f"acks {acks[0]:.3f} / {acks[len(acks) // 2]:.3f} / "
                    f"{acks[-1]:.3f}s  duplicates dropped {dropped}")
        return rec

    # -- the tick just driven against its reference, between ticks

    def check(self, payload: dict, rec: dict, tol: dict) -> dict:
        """The global's newest flush against the fleet's reference, and
        the replays the tick's record says the ledger dropped against
        those the payload holds. `failed` counts the timer sketches
        whose samples the emitted counts do not account for."""
        glob = reference.sink_values(self.gsink.take())
        how = (self.cfg.get("control") or {}).get("answers")
        if how:
            glob = reference.degrade(glob, how)
        ref = payload["ref"]
        v = reference.check_tick(ref, None, glob, tol)
        kept = (len(payload["replayed"])
                - rec["counters"]["import.duplicates_dropped"])
        v["numbers"]["replays_not_dropped"] = (float(abs(kept)), 0.0)
        want = sum(count for count, _lo, _hi in ref["timer"].values())
        short = max(0.0, 1.0 - v.pop("accounted_lines") / want)
        v["attempted"] = payload["n_sketches"]
        v["failed"] = math.ceil(
            short * len(ref["timer"]) * int(self.cfg["fan_in_locals"]))
        return v

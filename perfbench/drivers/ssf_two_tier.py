"""The driver `ssf_two_tier`: `two_tier`'s two servers and tick, with the
local tier fed SSF spans on a framed UNIX stream and nothing else.

The local listens on `unix://<temp dir>/ssf.sock` (the deployment file's
`ssf_listen_addresses`, the path made here), on the native bridge, with
`indicator_span_timer_name` set; no statsd listener takes traffic. One
tick is `two_tier`'s with the send phase replaced:

  bench.send   every chunk of the payload's frames down ONE connection
               (`sendall`), closed loop: the sender writes as fast as
               the socket takes. A stream reader that finds a sub-ring
               full drops and counts, as every transport of the bridge
               does, so between chunks the sender keeps the samples
               staged and not yet pumped under half a sub-ring, on the
               bridge's `samples` less the engine's `samples_processed`,
               as `two_tier.send` does on `lines`.

Everything after it (`settle`, both flushes, the sinks) is `two_tier`'s
code. The tick record has `two_tier`'s keys: `lines` is the samples the
tier must stage, the indicator spans' timers among them (a staged sample
is what a line is), `bridge.lost_lines` those samples less what the
bridge staged (`samples`) and what went through the fallback, plus the
bridge's drop counters. Beside them, for the `ssf.*` readers,
`counters["ssf.*"]`: the bridge's tallies where it keeps them (a program
without the stream reader keeps none, and the readers find nothing).

`check` holds the tick against the generator's reference with
`reference.check_tick`, as `two_tier` does. The indicator timer is one
name under many tag sets, so its rows are named with their tags
(`ssf_spans.indicator_key`); the STATUS spans' service checks are
counted at the local's sink, which holds each as a row, and the bridge's `ssf_fallbacks` must be
the tick's STATUS spans and no other.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import socket
import tempfile
import time

from perfbench import harness, reference
from perfbench.drivers import two_tier

# the counters of `bridge.stats()` the stream readers add to (PR 43)
TALLIES = {"ssf.frames": "ssf_stream_frames",
           "ssf.read_ns": "ssf_stream_read_ns",
           "ssf.ring_wait_ns": "ssf_stream_wait_ns"}


class Driver(two_tier.Driver):
    TAKES = "ssf_frames"
    OPS = "samples"

    def __init__(self, cfg: dict, rehearsal: bool):
        from veneur_tpu.ingest import native
        native.build()
        self.cfg = cfg
        self.gsink, self.lsink = harness.make_sink(), harness.make_sink()
        self.lsrv = self.gsrv = self.sock = None
        self.tmp = tempfile.mkdtemp(prefix="ssf")
        path = os.path.join(self.tmp, "ssf.sock")
        try:
            self.gsrv = harness.build_server(cfg, "global", {}, self.gsink,
                                             rehearsal)
            self.gsrv.start()
            self.lsrv = harness.build_server(
                cfg, "local",
                {"forward_address": f"127.0.0.1:{self.gsrv.grpc_port}",
                 "ssf_listen_addresses": ["unix://" + path]},
                self.lsink, rehearsal)
            self.lsrv.start()
            self.leng = self.lsrv.engines[0]
            self.geng = self.gsrv.engines[0]
            self.bridge = self.lsrv.native_bridge
            if self.bridge is None:
                raise RuntimeError(
                    "the local tier is not on the native bridge")
            self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            self.sock.connect(path)
        except BaseException:
            self.stop()
            raise
        self.indicator = cfg["common"].get("indicator_span_timer_name", "")
        self.flushes = 0
        self._landing = None

    def stop(self):
        if self.sock is not None:
            with contextlib.suppress(Exception):
                self.sock.close()
        for srv in (self.lsrv, self.gsrv):
            if srv is not None:
                srv.stop()
        shutil.rmtree(self.tmp, ignore_errors=True)

    def drop_counters(self) -> dict:
        st = self.bridge.stats()
        out = super().drop_counters()
        out.update({"local.ssf_errors": int(st["ssf_errors"]),
                    "local.ssf_other_drops": int(st["ssf_other_drops"])})
        return out

    # -- one tick

    def send(self, chunks: list, n_lines: int, timeout_s: float) -> dict:
        """All of a payload's chunks down the one connection. The
        kernel's socket buffer and the reader's read buffer hold what
        was sent and not yet parsed (some 300 KB, a few thousand
        samples), which the pacing's half a sub-ring of room covers."""
        bridge, eng, sock = self.bridge, self.leng, self.sock
        ring_room = self.lsrv.cfg.native_ring_capacity // 16
        base = int(bridge.stats()["samples"])
        waited = 0
        t_first = time.monotonic_ns()
        deadline = time.monotonic() + timeout_s
        for data, _staged in chunks:
            sock.sendall(data)
            w0 = 0
            while (int(bridge.stats()["samples"]) - base
                   - eng.samples_processed) > ring_room:
                if not w0:
                    w0 = time.monotonic_ns()
                if time.monotonic() > deadline:
                    raise TimeoutError("pacing stalled: the pump is not "
                                       "taking the staged samples")
                time.sleep(0.0002)
            if w0:
                waited += time.monotonic_ns() - w0
        t_last = time.monotonic_ns()
        return {"t_first": t_first, "t_last": t_last, "wait_ns": waited,
                "base_samples": base, "n_lines": n_lines,
                "deadline": deadline}

    def settle(self, sent: dict, fallback_lines: int):
        """Every sample parsed, staged, pumped and landed, as
        `two_tier.settle` has it for lines: the bridge has staged every
        sample of the fast path (its reader hands a STATUS span to the
        fallback's queue in passing, so those are queued by then), and
        the engine has counted every sample of the tick, the fallback's
        among them; then the drain and `block_until_ready`."""
        import jax
        eng = self.leng
        want = sent["base_samples"] + sent["n_lines"] - fallback_lines
        while (int(self.bridge.stats()["samples"]) < want
               or eng.samples_processed < sent["n_lines"]):
            if time.monotonic() > sent["deadline"]:
                got = (int(self.bridge.stats()["samples"])
                       - sent["base_samples"])
                raise TimeoutError(
                    f"samples lost: the bridge staged {got} and the "
                    f"engine took {eng.samples_processed} of "
                    f"{sent['n_lines']} ({fallback_lines} of them "
                    f"through the fallback)")
            time.sleep(0.0002)
        if not self.lsrv.drain(timeout=600.0):
            raise TimeoutError("local tier did not drain its rings")
        jax.block_until_ready((eng.histo_bank, eng.counter_bank,
                               eng.gauge_bank, eng.set_bank))

    def tick(self, payload: dict, ts: int, spans, gcm, meter) -> dict:
        st0 = self.bridge.stats()
        bytes0 = self.forward_bytes()
        books = harness.TickBooks(spans, meter)
        # what the program as it is configured will stage: a control may
        # have taken the indicator timer away, and the tick must end
        n_lines, fallback = payload["n_lines"], payload["fallback_lines"]
        if not self.indicator:
            n_lines -= payload["indicator_lines"]
            fallback -= payload["fallback_indicator_lines"]
        with spans.span("bench.send"):
            sent = self.send(payload["chunks"], n_lines, 600.0)
        with spans.span("bench.settle"):
            self.settle(sent, fallback)
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

        def delta(key):
            return int(st[key]) - int(st0[key])

        lost = (n_lines - fallback - delta("samples")
                + sum(delta(k) for k in ("ring_drops", "drops_no_slot",
                                         "other_drops", "ssf_other_drops")))
        rec = books.close(gcm, sent["t_first"], sent["t_last"], t_end)
        rec["counters"].update({
            "forward.bytes": self.forward_bytes() - bytes0,
            "bridge.lost_lines": lost,
            "ssf.spans": delta("ssf_spans") + delta("ssf_fallbacks"),
            "ssf.fallbacks": delta("ssf_fallbacks")})
        rec["counters"].update({name: delta(key)
                                for name, key in TALLIES.items()
                                if key in st})
        rec.update({
            "attempted": payload["n_lines"], "lines": payload["n_lines"],
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

    def sink_values(self, sink) -> dict:
        """`reference.sink_values` of a sink's newest flush, the
        indicator timer's rows named with their tags."""
        rows, ind = sink.take(), self.indicator
        if ind:
            for m in rows:
                if m.name.startswith(ind + "."):
                    m.name = (f"{ind}|{','.join(sorted(m.tags))}"
                              f"{m.name[len(ind):]}")
        return reference.sink_values(rows)

    def check(self, payload: dict, rec: dict, tol: dict) -> dict:
        local, glob = self.sink_values(self.lsink), self.sink_values(
            self.gsink)
        how = (self.cfg.get("control") or {}).get("answers")
        if how:
            local = reference.degrade(local, how)
            glob = reference.degrade(glob, how)
        lost = rec["counters"]["bridge.lost_lines"]
        # a service check reaches the local's sink as a row of its own
        checks = [local.pop(k) for k in list(local)
                  if k.startswith(payload["check_prefix"])]
        v = reference.check_tick(payload["ref"], local, glob, tol)
        want = payload["fallback_spans"]
        v["numbers"]["bridge.lost_lines"] = (float(abs(lost)), 0.0)
        v["numbers"]["service_checks_off"] = (float(abs(len(checks) - want)),
                                              0.0)
        v["numbers"]["ssf_fallbacks_off"] = (
            float(abs(rec["counters"]["ssf.fallbacks"] - want)), 0.0)
        v["attempted"] = payload["n_lines"]
        v["failed"] = (payload["timer_lines"] - v.pop("accounted_lines")
                       + max(0, lost))
        return v

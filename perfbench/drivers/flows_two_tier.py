"""The driver `flows_two_tier`: `zipf_two_tier`'s two servers, settle,
tick and check, behind a host of client processes: the local tier reads
with `num_readers` `SO_REUSEPORT` sockets (the deployment file's
`local.num_readers`) and the payload (`generators/dogstatsd_flows.py`)
arrives as UDP flows, each a client socket of its own opened in set-up
and kept for the run, connected to the local's port from an ephemeral
source port. Which reader a flow lands on is the kernel's choice by the
flow's hash.

`bench.send` is the mix's `flows.threads` sender threads, each sending
its flows' datagrams by turns, first half then second half, closed loop
on `two_tier.send`'s pacing with the bridge's totals shared by all the
threads: at most `flows.in_flight` datagrams sent and not yet received
(a sixth of one socket's 2 MiB buffer if every flow hashed to one
reader) and parsed samples not yet pumped at most
`native_ring_capacity / 16` (half of one sub-ring, so no spread of flows
over readers can drop). Between the halves every thread waits until the
bridge has received every datagram of the first (`packets`: a barrier
on the readers, not on the pump), so a second-half write is later than
a first-half one whichever reader took it. A thread's waiting, barrier
included, is its `wait_ns`; the tick's `gen_wait_s` is the threads'
mean.

What it adds to the tick record, from `bridge.stats()` read before the
send and after the settle (a program without the fields adds nothing):
`readers`, what each reader thread did in the tick (`packets`, `lines`,
`busy_ns`), and `ring`, the high water of each bank's fullest sub-ring
since the last flush with a sub-ring's capacity.

`check` is `zipf_two_tier`'s, whose exact fields hold every gauge to
the reference's last write by flow; beside them
`handover_gauge_mismatches` counts the handed-over gauges alone (limit
0). Under the control `handover_first_writer` the local's answers for
them are replaced by their first flows' last writes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import socket
import threading
import time

from perfbench import reference
from perfbench.drivers import zipf_two_tier


class Driver(zipf_two_tier.Driver):
    TAKES = "flow_datagrams"
    OPS = "lines"

    def __init__(self, cfg: dict, rehearsal: bool):
        self.flows: list = []
        super().__init__(cfg, rehearsal)
        self._in_flight = 0         # the mix's, from the payload
        self._before: dict = {}     # bridge.stats() before the send
        self._settled: dict = {}    # and after the settle

    def stop(self):
        for s in self.flows:
            with contextlib.suppress(Exception):
                s.close()
        super().stop()

    def _open_flows(self, count: int):
        for _ in range(count - len(self.flows)):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.connect(self.dest)
            self.flows.append(s)

    # -- one tick

    def send(self, halves: list, n_lines: int, timeout_s: float) -> dict:
        bridge, eng = self.bridge, self.leng
        flows = self.flows
        in_flight_max = self._in_flight
        ring_room = self.lsrv.cfg.native_ring_capacity // 16
        st = bridge.stats()
        self._before = st
        base, base_lines = int(st["packets"]), int(st["lines"])
        first_half = sum(len(t) for t in halves[0])
        sent = [0] * len(halves[0])         # a thread's own count
        waited = [0] * len(halves[0])
        errors: list = []
        deadline = time.monotonic() + timeout_s

        def wait_until(ok, who):
            """Sleep until `ok(stats)`; the ns waited."""
            w0 = 0
            while not ok(bridge.stats()):
                w0 = w0 or time.monotonic_ns()
                if errors or time.monotonic() > deadline:
                    raise TimeoutError(f"sender {who} stalled")
                time.sleep(0.0002)
            return time.monotonic_ns() - w0 if w0 else 0

        def paced(st):
            in_flight = base + sum(sent) - int(st["packets"])
            unpumped = (int(st["lines"]) - base_lines
                        - eng.samples_processed)
            return in_flight <= in_flight_max and unpumped <= ring_room

        def sender(t):
            try:
                for h, half in enumerate(halves):
                    for i, (f, d) in enumerate(half[t]):
                        flows[f].send(d)
                        sent[t] += 1
                        if i % 8 == 7:
                            waited[t] += wait_until(paced, t)
                    if h == 0:
                        # the barrier: every first-half datagram of
                        # every thread received
                        waited[t] += wait_until(
                            lambda st: int(st["packets"]) - base
                            >= first_half, t)
            except Exception as e:      # re-raised by the tick's thread
                errors.append(e)

        t_first = time.monotonic_ns()
        threads = [threading.Thread(target=sender, args=(t,),
                                    name=f"bench-sender-{t}")
                   for t in range(len(sent))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        t_last = time.monotonic_ns()
        if errors:
            raise errors[0]
        return {"t_first": t_first, "t_last": t_last,
                "wait_ns": sum(waited) // len(waited),
                "base_lines": base_lines, "n_lines": n_lines,
                "deadline": deadline}

    def settle(self, sent: dict):
        super().settle(sent)
        self._settled = self.bridge.stats()

    def tick(self, payload: dict, ts: int, spans, gcm, meter) -> dict:
        self._in_flight = int(payload["in_flight"])
        self._open_flows(int(payload["flows"]))     # the first tick's
        rec = super().tick(payload, ts, spans, gcm, meter)
        st, st0 = self._settled, self._before
        if "readers" in st:
            rec["readers"] = [
                {k: int(now[k]) - int(was[k]) for k in now}
                for now, was in zip(st["readers"], st0["readers"])]
        high = {k[len("ring_high_"):]: int(v) for k, v in st.items()
                if k.startswith("ring_high_")}
        if high:
            rec["ring"] = {"high": high, "way_capacity": int(
                self.bridge.ring_way_capacity)}
        return rec

    # -- the tick just driven against its reference, between ticks

    def check(self, payload: dict, rec: dict, tol: dict) -> dict:
        hand = payload["handover"]
        control = self.cfg.get("control") or {}
        if control.get("handover") == "first_writer":
            rows = self.lsink.flushes[-1]
            self.lsink.flushes[-1] = [
                dataclasses.replace(m, value=hand["first"][m.name])
                if m.name in hand["first"] else m for m in rows]
        local = reference.sink_values(self.lsink.flushes[-1])
        off = [n for n, want in hand["second"].items()
               if local.get(n) is None or float(local[n]) != float(want)]
        v = super().check(payload, rec, tol)
        v["numbers"]["handover_gauge_mismatches"] = (float(len(off)), 0.0)
        for n in off[:3]:
            v["mismatches"].append(
                f"local: handed-over gauge {n} = {local.get(n)!r}, its "
                f"second flow's last write is {hand['second'][n]!r} (its "
                f"first flow's {hand['first'][n]!r})")
        return v

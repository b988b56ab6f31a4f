"""chip_smoke.py — the quickest proof that veneur-tpu still starts, and
answers right, on the chip.

One process (the only one to touch JAX) drives the deployment veneur
exists for through the entry points a user calls — config text through
`veneur_tpu.config.read_config`, `Server(cfg).start()`, real loopback
sockets, the production forwarder, `flush_once(timestamp=...)` under
`interval: "3600s"` (scripted clock), a CaptureMetricSink — at the
repo's own north-star size (BASELINE.json `north_star`, `configs[]`
2-5), none of it cut:

  local tier   one Server, native_ingest (C++ readers -> pump -> device
               batches), t-digest + HLL p=14, forwarding to the global
               over gRPC forwardrpc (the `forward_use_grpc` default),
               delta forwarding on (the default);
  global tier  one Server importing on a gRPC listener, emitting
               percentiles;
  size         100,000 distinct timer keys in 131,072 histogram slots
               (compression 100, buffer depth 256: [131072, 256] x4 f32
               ~ 0.5 GB per histogram bank, two live under the double
               buffer, on each tier's engine), 1,000 sets receiving
               1,000,000 distinct members, 1,000 counters, 1,000
               gauges, tags on every line;
  traffic      three flush windows generated from --seed, DogStatsD
               datagrams of <= 80 lines / 4000 bytes, paced on the
               bridge's packet count so nothing is lost to the socket
               buffer. Window 1 touches every key (>= 4 samples per
               timer key; 1,000 hot timer keys take 2,000 samples each
               and overflow their 256-deep buffers into the in-ingest
               compress): the full flush program. Window 2 touches a
               seeded 10%: an incremental bucket (its hot keys now fill
               whole pump batches and take the host pre-cluster
               sidestep). Window 3 sends window 2 again: it must
               compile nothing.

After each window a plain-numpy reference — independent of veneur_tpu,
over the same generated samples — is held against what the two tiers'
sinks received, each series where veneur's scoping emits it:

  * timer count/min/max EXACT for every key (f32 for min/max) at the
    local tier and again, after the forward, at the global tier;
  * counter totals EXACT for every name (mixed scope at the local,
    `veneurglobalonly` at the global);
  * gauges: the last value written;
  * set estimates within 3% of the true distinct count (HLL p=14);
  * for the hot keys p50 within 1% and p99 within 2% of
    numpy.quantile. A t-digest bounds RANK error; what that is worth
    in value depends on the density. Through both tiers, over 1,000
    keys of 2,000 samples each, the worst p99 sat 6.5 order statistics
    off numpy's (0.33% of rank, the k1 contract at compression 100)
    and the worst p50 about 30. The latencies are lognormal(100 ms,
    sigma 0.1): near p99 neighbouring order statistics are then ~0.19%
    of the value apart, so 2% is ~10 of them, and near the median
    ~0.013%, so 1% is ~80. A heavier tail (sigma 0.5: ~0.94% per order
    statistic at p99) puts the same rank error 6.5% off in value.

At the end the counters must read: no flush, parse or import error, no
worker/ring/bank drop, nothing parked in the forwarder, and
`veneur.kernels.fallback_total == 0`. `flush_once` catches forward
errors, so the smoke judges by what ARRIVED and by the counters, never
by the absence of an exception.

Two more legs: the KERNEL leg builds the one Pallas kernel (hll_stats)
at its serving shape and checks it against the jnp reduction on the
chip, jit against jit, both timed; the MESH leg (>= 4 devices) sends the same
traffic into a global whose banks are sharded over four chips and
requires its answers to equal the one-chip global's.

No chip, no run: unless JAX's first device is a TPU the default
invocation exits non-zero naming the platform it found and prints no
result. `--cpu-dryrun` is the one explicit way to run elsewhere: tiny
sizes, the kernel leg under the Pallas interpreter, output marked
"dryrun": true.

The last line of standard output is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import socket
import sys
import threading
import time

import numpy as np

LEGS = ("kernels", "tiers", "mesh")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How big the deployment is. FULL is BASELINE.json's north star;
    DRYRUN keeps every shape of the flow (hot keys that overflow their
    buffers, a window above and two below the incremental threshold)
    at a size the CPU interpreter finishes in a minute."""
    timer_keys: int
    histogram_slots: int
    hot_keys: int
    hot_samples: int
    cold_samples: int
    set_keys: int
    set_members: int          # distinct members per set per window
    counters: int
    gauges: int
    buffer_depth: int
    pump_batch: int
    batch_size: int
    set_slots: int
    hll_rows: int             # kernel leg: register-file rows


FULL = Sizes(timer_keys=100_000, histogram_slots=131_072, hot_keys=1_000,
             hot_samples=2_000, cold_samples=4, set_keys=1_000,
             set_members=1_000, counters=1_000, gauges=1_000,
             buffer_depth=256, pump_batch=1 << 15, batch_size=8192,
             set_slots=4096, hll_rows=4096)
DRYRUN = Sizes(timer_keys=400, histogram_slots=512, hot_keys=40,
               hot_samples=600, cold_samples=4, set_keys=16,
               set_members=300, counters=20, gauges=20,
               buffer_depth=256, pump_batch=2048, batch_size=512,
               set_slots=64, hll_rows=64)

PERCENTILES = (0.5, 0.75, 0.99)
P50_TOL, P99_TOL, SET_TOL = 0.01, 0.02, 0.03
TOUCH_FRACTION = 0.10        # windows 2 and 3
TIMER_MEDIAN_MS, TIMER_SIGMA = 100.0, 0.1    # lognormal latencies


def log(msg: str = "") -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ compile meter

class CompileMeter:
    """Counts what JAX compiles (or fetches from the persistent cache)
    and how long it took, through jax.monitoring."""

    def __init__(self):
        import jax
        self.requests = 0          # programs built or fetched
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

    def snapshot(self):
        return (self.requests, self.seconds, self.cache_hits)

    def since(self, snap) -> dict:
        return {"programs": self.requests - snap[0],
                "seconds": round(self.seconds - snap[1], 2),
                "cache_hits": self.cache_hits - snap[2]}


# ----------------------------------------------------------------- traffic

def _timer_name(i):
    return f"smoke.timer.k{i:06d}"


def _timer_tags(i):
    return f"env:prod,shard:{i % 64}"


class Window:
    """One flush window's traffic, generated from the seed, together
    with everything the numpy reference needs: which keys it touches,
    every sample's value, send order."""

    def __init__(self, sizes: Sizes, seed: int, index: int,
                 touched: dict):
        rng = np.random.default_rng([seed, index])
        self.sizes = sizes
        self.touched = touched
        s = sizes

        # ---- timers: value in integer thousandths, so the text on the
        # wire ("123.456"), the f64 the parser makes of it and the f32
        # the bank keeps are all exactly reproducible from `milli`
        tk = touched["timers"]
        per_key = np.where(tk < s.hot_keys, s.hot_samples, s.cold_samples)
        self.t_key = np.repeat(tk, per_key)
        self.t_milli = np.maximum(1, np.rint(rng.lognormal(
            np.log(TIMER_MEDIAN_MS), TIMER_SIGMA, self.t_key.size)
            * 1000.0)).astype(np.int64)
        order = rng.permutation(self.t_key.size)
        self.t_key, self.t_milli = self.t_key[order], self.t_milli[order]

        # ---- sets: distinct members per set, plus 5% resent
        sk = touched["sets"]
        base = index * 10_000_000
        member = (base + np.arange(sk.size * s.set_members,
                                   dtype=np.int64))
        self.s_key = np.repeat(sk, s.set_members)
        dup = rng.choice(member.size, member.size // 20, replace=False)
        self.s_key = np.concatenate([self.s_key, self.s_key[dup]])
        self.s_member = np.concatenate([member, member[dup]])
        order = rng.permutation(self.s_key.size)
        self.s_key, self.s_member = self.s_key[order], self.s_member[order]

        # ---- counters (even names mixed scope, odd names global-only)
        # and gauges: a few integer samples each
        ck = touched["counters"]
        self.c_key = np.repeat(ck, 3)
        self.c_val = rng.integers(1, 1000, self.c_key.size)
        gk = touched["gauges"]
        self.g_key = np.repeat(gk, 3)
        self.g_milli = rng.integers(0, 10_000_000, self.g_key.size)
        order = rng.permutation(self.g_key.size)
        self.g_key, self.g_milli = self.g_key[order], self.g_milli[order]

    # the DogStatsD text, in send order: gauges and counters first, then
    # timers and sets interleaved
    def lines(self) -> list:
        def dec(m):
            return f"{m // 1000}.{m % 1000:03d}"

        out = [f"smoke.gauge.g{k:04d}:{dec(m)}|g|#env:prod,kind:gauge"
               for k, m in zip(self.g_key.tolist(),
                               self.g_milli.tolist())]
        out += [f"smoke.counter.c{k:04d}:{v}|c|#env:prod"
                + (",veneurglobalonly" if k % 2 else "")
                for k, v in zip(self.c_key.tolist(), self.c_val.tolist())]
        names = {int(k): (_timer_name(int(k)), _timer_tags(int(k)))
                 for k in self.touched["timers"]}
        timers = [f"{names[k][0]}:{dec(m)}|ms|#{names[k][1]}"
                  for k, m in zip(self.t_key.tolist(),
                                  self.t_milli.tolist())]
        sets = [f"smoke.set.s{k:04d}:m{m}|s|#env:prod"
                for k, m in zip(self.s_key.tolist(),
                                self.s_member.tolist())]
        # spread the sets evenly through the timers
        step = max(1, len(timers) // max(1, len(sets)))
        merged, si = [], 0
        for i in range(0, len(timers), step):
            merged.extend(timers[i:i + step])
            if si < len(sets):
                merged.append(sets[si])
                si += 1
        merged.extend(sets[si:])
        return out + merged


def touched_keys(sizes: Sizes, seed: int, index: int) -> dict:
    """Window 1 touches everything; windows 2 and 3 the same seeded
    10% of every kind (always including a tenth of the hot keys)."""
    s = sizes
    if index == 1:
        return {"timers": np.arange(s.timer_keys),
                "sets": np.arange(s.set_keys),
                "counters": np.arange(s.counters),
                "gauges": np.arange(s.gauges)}
    rng = np.random.default_rng([seed, 99])

    def tenth(n):
        k = max(1, int(round(n * TOUCH_FRACTION)))
        return np.sort(rng.choice(n, k, replace=False))

    hot = tenth(s.hot_keys)
    cold = tenth(s.timer_keys - s.hot_keys) + s.hot_keys
    timers = np.concatenate([hot, cold])[
        :max(1, int(round(s.timer_keys * TOUCH_FRACTION)))]
    return {"timers": np.sort(timers), "sets": tenth(s.set_keys),
            "counters": tenth(s.counters), "gauges": tenth(s.gauges)}


def datagrams(lines: list, max_lines: int = 80, max_bytes: int = 4000):
    """Pack lines into datagrams under `metric_max_length` (4096 is the
    UDP read size: a longer datagram is silently truncated)."""
    out, cur, size = [], [], 0
    for ln in lines:
        b = ln.encode()
        if cur and (len(cur) >= max_lines or size + len(b) + 1 > max_bytes):
            out.append(b"\n".join(cur))
            cur, size = [], 0
        cur.append(b)
        size += len(b) + 1
    if cur:
        out.append(b"\n".join(cur))
    return out


# --------------------------------------------------------------- reference

def reference(w: Window) -> dict:
    """What the two tiers must emit for this window — plain numpy over
    the generated samples, nothing of veneur_tpu."""
    ref = {"timer": {}, "hot": {}, "counter_local": {},
           "counter_global": {}, "gauge": {}, "set": {}}
    val64 = w.t_milli / 1000.0                 # == strtod("123.456")
    val32 = val64.astype(np.float32)
    order = np.argsort(w.t_key, kind="stable")
    keys, starts = np.unique(w.t_key[order], return_index=True)
    ends = np.append(starts[1:], order.size)
    v32, v64 = val32[order], val64[order]
    mins = np.minimum.reduceat(v32, starts)
    maxs = np.maximum.reduceat(v32, starts)
    for k, a, b, lo, hi in zip(keys.tolist(), starts.tolist(),
                               ends.tolist(), mins.tolist(),
                               maxs.tolist()):
        ref["timer"][_timer_name(k)] = (float(b - a), lo, hi)
        if k < w.sizes.hot_keys:
            ref["hot"][_timer_name(k)] = np.quantile(
                v64[a:b], PERCENTILES)
    for k in np.unique(w.c_key).tolist():
        total = float(w.c_val[w.c_key == k].sum())
        side = "counter_global" if k % 2 else "counter_local"
        ref[side][f"smoke.counter.c{k:04d}"] = total
    for k in np.unique(w.g_key).tolist():
        last = w.g_milli[np.nonzero(w.g_key == k)[0][-1]]
        ref["gauge"][f"smoke.gauge.g{k:04d}"] = float(
            np.float32(last / 1000.0))
    for k in np.unique(w.s_key).tolist():
        ref["set"][f"smoke.set.s{k:04d}"] = float(
            np.unique(w.s_member[w.s_key == k]).size)
    return ref


class Checker:
    """Collects failed assertions instead of stopping at the first."""

    def __init__(self):
        self.failures: list = []
        self.checked = 0

    def that(self, ok: bool, what: str):
        self.checked += 1
        if not ok:
            self.failures.append(what)
            if len(self.failures) <= 40:
                log(f"  FAIL {what}")

    def exact(self, got: dict, name: str, want: float, where: str):
        v = got.get(name)
        self.that(v is not None and float(v) == float(want),
                  f"{where}: {name} = {v!r}, want exactly {want!r}")

    def close(self, got: dict, name: str, want: float, tol: float,
              where: str):
        v = got.get(name)
        self.that(v is not None
                  and abs(float(v) - want) <= tol * abs(want),
                  f"{where}: {name} = {v!r}, want {want!r} within "
                  f"{tol:.0%}")


def sink_values(metrics) -> dict:
    """name -> value for one flush's smoke.* rows; a duplicated name
    would be a scoping bug, so it is kept visible."""
    out = {}
    for m in metrics:
        if m.name.startswith("smoke."):
            if m.name in out:
                out[m.name + "#dup"] = m.value
            out[m.name] = m.value
    return out


def check_window(chk: Checker, ref: dict, local: dict, glob: dict,
                 tag: str):
    n0 = len(chk.failures)
    chk.that(not any(k.endswith("#dup") for k in list(local) + list(glob)),
             f"{tag}: a series was emitted twice by one tier")
    for name, (count, lo, hi) in ref["timer"].items():
        for tier, got in (("local", local), ("global", glob)):
            chk.exact(got, name + ".count", count, f"{tag} {tier}")
            chk.exact(got, name + ".min", lo, f"{tag} {tier}")
            chk.exact(got, name + ".max", hi, f"{tag} {tier}")
        # mixed-scope timers: percentiles are the global tier's
        chk.that(name + ".50percentile" not in local,
                 f"{tag} local: {name} emitted a percentile")
    worst = {"p50": 0.0, "p99": 0.0}
    for name, qs in ref["hot"].items():
        chk.close(glob, name + ".50percentile", qs[0], P50_TOL,
                  f"{tag} global")
        chk.close(glob, name + ".99percentile", qs[2], P99_TOL,
                  f"{tag} global")
        for label, q, suf in (("p50", qs[0], ".50percentile"),
                              ("p99", qs[2], ".99percentile")):
            v = glob.get(name + suf)
            if v is not None:
                worst[label] = max(worst[label], abs(v - q) / q)
    for name, total in ref["counter_local"].items():
        chk.exact(local, name, total, f"{tag} local")
        chk.that(name not in glob, f"{tag} global: local counter {name}")
    for name, total in ref["counter_global"].items():
        chk.exact(glob, name, total, f"{tag} global")
        chk.that(name not in local,
                 f"{tag} local: global-only counter {name}")
    for name, last in ref["gauge"].items():
        chk.exact(local, name, last, f"{tag} local")
    worst_set = 0.0
    for name, distinct in ref["set"].items():
        chk.close(glob, name, distinct, SET_TOL, f"{tag} global")
        if name in glob:
            worst_set = max(worst_set, abs(glob[name] - distinct) / distinct)
    # nothing of another window may leak into this one
    expect_local = (3 * len(ref["timer"]) + len(ref["counter_local"])
                    + len(ref["gauge"]))
    chk.that(len(local) == expect_local,
             f"{tag} local: {len(local)} smoke.* rows, want {expect_local}")
    expect_glob = ((3 + len(PERCENTILES)) * len(ref["timer"])
                   + len(ref["counter_global"]) + len(ref["set"]))
    chk.that(len(glob) == expect_glob,
             f"{tag} global: {len(glob)} smoke.* rows, want {expect_glob}")
    return {"failures": len(chk.failures) - n0,
            "worst_p50_rel": round(worst["p50"], 5),
            "worst_p99_rel": round(worst["p99"], 5),
            "worst_set_rel": round(worst_set, 5)}


# ------------------------------------------------------------------ tiers

def tier_configs(sizes: Sizes, backend: str, global_devices: int,
                 grpc_port: int | None):
    """The two servers' config text — what an operator would put in
    the YAML files, sizes aside."""
    # flush_timeout / retry_deadline: the forward of 100k sketches is
    # one burst against the global's 65,536-deep worker queue, paced by
    # its import backpressure; the defaults (10 s an attempt, 8 s for a
    # whole interval's chunks) are sized for 10 s intervals of far
    # fewer keys, and an interval that outlives them is parked and
    # arrives one flush late
    common = f"""
interval: "3600s"
flush_timeout: "60s"
retry_deadline: "120s"
aggregation_backend: {backend}
tpu_histogram_slots: {sizes.histogram_slots}
tpu_set_slots: {sizes.set_slots}
tpu_buffer_depth: {sizes.buffer_depth}
tpu_batch_size: {sizes.batch_size}
percentiles: [{", ".join(str(p) for p in PERCENTILES)}]
aggregates: ["min", "max", "count"]
"""
    if grpc_port is None:
        return common + f"""
hostname: smoke-global
grpc_listen_addresses: ["127.0.0.1:0"]
tpu_num_devices: {global_devices}
"""
    return common + f"""
hostname: smoke-local
statsd_listen_addresses: ["udp://127.0.0.1:0"]
native_ingest: true
native_pump_batch: {sizes.pump_batch}
forward_address: "127.0.0.1:{grpc_port}"
"""


def send_window(srv, dgrams: list, n_lines: int, timeout_s: float):
    """All of a window's datagrams into the local tier's UDP socket,
    paced so that neither the socket buffer (16 datagrams in flight)
    nor the bridge's sample rings can drop one: a reader thread stages
    into ONE of a bank's 8 sub-rings, so with the default single reader
    a bank holds native_ring_capacity / 8 samples; the sender keeps the
    samples parsed but not yet pumped under half of that."""
    bridge, eng = srv.native_bridge, srv.engines[0]
    ring_room = srv.cfg.native_ring_capacity // 16
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    dest = ("127.0.0.1", srv.bound_port())
    base = int(bridge.stats()["packets"])
    base_lines = int(bridge.stats()["lines"])
    deadline = time.monotonic() + timeout_s
    try:
        for i, d in enumerate(dgrams):
            sock.sendto(d, dest)
            if i % 8 == 7:
                while True:
                    st = bridge.stats()
                    in_flight = base + i + 1 - int(st["packets"])
                    unpumped = (int(st["lines"]) - base_lines
                                - eng.samples_processed)
                    if in_flight <= 16 and unpumped <= ring_room:
                        break
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"pacing stalled: {in_flight} datagrams in "
                            f"flight, {unpumped} samples unpumped")
                    time.sleep(0.0002)
        # settle: every line parsed ...
        while int(bridge.stats()["lines"]) < base_lines + n_lines:
            if time.monotonic() > deadline:
                st = bridge.stats()
                raise TimeoutError(
                    f"datagrams lost: bridge saw "
                    f"{int(st['packets']) - base} of {len(dgrams)} "
                    f"datagrams, {int(st['lines']) - base_lines} of "
                    f"{n_lines} lines")
            time.sleep(0.001)
    finally:
        sock.close()
    # ... and staged: a reader counts a burst's lines while parsing and
    # pushes its samples to the rings right after, so for a few
    # microseconds the count is ahead of what the pump can see. No
    # counter closes that gap; a pause far longer than it does, and a
    # sample that still straggled into the next window would fail that
    # key's exact count.
    time.sleep(0.05)
    if not srv.drain(timeout=timeout_s):
        raise TimeoutError("local tier did not drain its rings")


def run_tiers(sizes: Sizes, seed: int, backend: str, global_devices: int,
              meter: CompileMeter, chk: Checker, label: str) -> dict:
    """Two tiers, three windows; returns the leg's report, including
    what the global emitted per window (for the mesh comparison)."""
    import jax

    from veneur_tpu import kernels
    from veneur_tpu.config import read_config
    from veneur_tpu.observe import SERVER_SCOPE
    from veneur_tpu.server import Server
    from veneur_tpu.sinks.basic import CaptureMetricSink

    report = {"label": label, "windows": []}
    t0 = time.monotonic()
    snap = meter.snapshot()
    gcap, lcap = CaptureMetricSink(), CaptureMetricSink()
    gsrv = Server(read_config(text=tier_configs(
        sizes, backend, global_devices, None), env={}),
        sinks=[gcap])
    lsrv = None
    try:
        gsrv.start()
        lsrv = Server(read_config(text=tier_configs(
            sizes, backend, 1, gsrv.grpc_port), env={}),
            sinks=[lcap])
        lsrv.start()
        leng, geng = lsrv.engines[0], gsrv.engines[0]
        report["setup_s"] = round(time.monotonic() - t0, 2)
        report["setup_compile"] = meter.since(snap)
        report["forward_wire"] = type(
            getattr(lsrv.forwarder, "inner", lsrv.forwarder)).__name__
        report["global_engine"] = type(geng).__name__
        log(f"[{label}] set-up {report['setup_s']}s "
            f"(compile: {report['setup_compile']}); forward wire "
            f"{report['forward_wire']}")
        chk.that(lsrv.native_bridge is not None,
                 f"{label}: local tier is not on the native bridge")
        if global_devices > 1:
            devs = set()
            for leaf in jax.tree_util.tree_leaves(geng.me.banks):
                devs |= set(leaf.sharding.device_set)
                chk.that(len({s.device for s in leaf.addressable_shards})
                         == global_devices,
                         f"{label}: a bank leaf is not sharded over "
                         f"{global_devices} distinct devices")
            report["mesh_devices"] = sorted(str(d) for d in devs)
            log(f"[{label}] mesh banks on {report['mesh_devices']}; "
                f"hll_stats inside shard_map: {geng.me.pallas_estimate}")

        sent = None
        for index in (1, 2, 3):
            if index != 3:
                w = Window(sizes, seed, index,
                           touched_keys(sizes, seed, index))
                lines = w.lines()
                sent = (lines, datagrams(lines), reference(w))
            # window 3 is window 2 again, sample for sample: the
            # steady state the window stands for is that the same work
            # compiles nothing. (Fresh values on the same keys no
            # longer could either: the import landing's programs take
            # their shapes from the configuration and are compiled in
            # warmup(); a dirty set in another incremental bucket
            # still would.)
            lines, dg, ref = sent
            ts = 1_000 + 10 * index
            snap = meter.snapshot()
            tw = time.monotonic()
            send_window(lsrv, dg, len(lines), timeout_s=600.0)
            t_sent = time.monotonic()
            lsrv.flush_once(timestamp=ts)
            t_local = time.monotonic()
            # the forward is acknowledged once its metrics sit on the
            # global's worker queues; flush when they are applied
            if not gsrv.drain(timeout=600.0):
                raise TimeoutError(
                    "global tier did not drain its import queue")
            gsrv.flush_once(timestamp=ts + 5)
            t_glob = time.monotonic()
            lcap.wait_for_flush(index, timeout=60.0)
            gcap.wait_for_flush(index, timeout=60.0)
            chk.that(len(lcap.flushes) == index
                     and len(gcap.flushes) == index,
                     f"{label} window {index}: a sink missed its flush")
            local = sink_values(lcap.flushes[-1])
            glob = sink_values(gcap.flushes[-1])
            verdict = check_window(chk, ref, local, glob,
                                   f"{label} window {index}")
            row = {
                "window": index, "lines": len(lines),
                "datagrams": len(dg),
                "ingest_s": round(t_sent - tw, 2),
                "local_flush_s": round(t_local - t_sent, 2),
                "global_import_flush_s": round(t_glob - t_local, 2),
                "wall_s": round(t_glob - tw, 2),
                "compile": meter.since(snap),
                "local_flush_path": dict(leng._last_flush_info),
                "global_flush_path": dict(geng._last_flush_info),
                **verdict,
            }
            report["windows"].append(row)
            report.setdefault("global_out", []).append(glob)
            log(f"[{label}] window {index}: {json.dumps(row)}")
        n3 = report["windows"][2]["compile"]["programs"]
        chk.that(n3 == 0, f"{label}: window 3 compiled {n3} programs: "
                          f"{meter.names[len(meter.names) - n3:]}")
        chk.that(report["windows"][0]["local_flush_path"]["path"] == "full",
                 f"{label}: window 1 did not take the full flush program")
        chk.that(report["windows"][1]["local_flush_path"]["path"]
                 == "incremental",
                 f"{label}: window 2 did not take an incremental bucket")

        # ---- the counters: what was lost or demoted along the way
        st = lsrv.native_bridge.stats()
        counters = {
            "local.parse_errors": int(st["parse_errors"]),
            "local.ring_drops": int(st["ring_drops"]),
            "local.other_drops": int(st["other_drops"]),
            "local.drops_no_slot": int(st["drops_no_slot"]),
            "forwarder.pending_spill": int(
                getattr(lsrv.forwarder, "pending_spill", 0)),
            "kernels.fallback_total": int(kernels.fallback_total()),
        }
        for tier, srv in (("local", lsrv), ("global", gsrv)):
            for name in ("flush.error", "packet.error", "worker.dropped",
                         "samples.dropped_no_slot", "import.rejected"):
                counters[f"{tier}.{name}"] = int(
                    srv.telemetry.total(SERVER_SCOPE, name))
        report["counters"] = counters
        log(f"[{label}] counters: {json.dumps(counters)}")
        for name, v in counters.items():
            chk.that(v == 0, f"{label}: {name} = {v}, want 0")
    finally:
        for srv in (lsrv, gsrv):
            if srv is not None:
                srv.stop()
    return report


def compare_globals(chk: Checker, one: list, mesh: list):
    """The mesh-backed global against the one-chip global, window by
    window: exact fields exact, sketched fields within contract."""
    for i, (a, b) in enumerate(zip(one, mesh), 1):
        chk.that(set(a) == set(b),
                 f"mesh window {i}: series differ from the one-chip leg "
                 f"({len(set(a) ^ set(b))} names)")
        worst = 0.0
        for name, v in a.items():
            m = b.get(name)
            if m is None:
                continue
            if name.endswith("percentile") or name.startswith("smoke.set."):
                tol = P99_TOL if name.endswith("percentile") else 1e-3
                ok = abs(m - v) <= tol * max(abs(v), 1e-9)
                worst = max(worst, abs(m - v) / max(abs(v), 1e-9))
            else:
                ok = m == v
            chk.that(ok, f"mesh window {i}: {name} = {m!r}, one chip "
                         f"{v!r}")
        log(f"[mesh] window {i}: {len(a)} series held against the "
            f"one-chip leg (sketched fields within {worst:.2e} relative)")


# ----------------------------------------------------------------- kernels

def kernel_leg(sizes: Sizes, dryrun: bool, chk: Checker) -> dict:
    """The one Pallas kernel, hll_stats, built at its serving shape
    (under the interpreter at a tiny shape in a dry run) and held
    against the jnp reduction on the same device, jit against jit,
    both timed. It must build and agree; a refusal prints the
    compiler's message."""
    import functools

    import jax
    import jax.numpy as jnp

    from veneur_tpu.kernels import hll_stats
    from veneur_tpu.ops import hll

    rng = np.random.default_rng(7)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.monotonic()
        jax.block_until_ready(fn(*args))
        return round((time.monotonic() - t0) * 1e3, 3)

    t0 = time.monotonic()
    try:
        # [4096, 16384] u8, adversarial rows included
        K, m = sizes.hll_rows, 1 << 14
        regs = rng.integers(0, 52, (K, m)).astype(np.uint8)
        regs[0] = 0
        regs[1] = 1
        regs[2] = 51
        regs[3] = (rng.random(m) < 0.01).astype(np.uint8) * 30
        regs[4, : m // 2] = 0
        bank = hll.HLLBank(jnp.asarray(regs))
        kern = jax.jit(functools.partial(hll_stats.hll_stats,
                                         interpret=dryrun))
        twin = jax.jit(hll_stats._stats_jnp)
        ez, zs = jax.device_get(kern(bank.registers))
        ez_j, zs_j = jax.device_get(twin(bank.registers))
        est_k = jax.device_get(jax.jit(hll._estimate_from_stats)(
            bank, jnp.asarray(ez), jnp.asarray(zs)))
        est_j = jax.device_get(hll._estimate_jnp(bank))
        zerr = float(np.max(np.abs(zs - zs_j) / np.maximum(zs_j, 1e-9)))
        eerr = float(np.max(np.abs(est_k - est_j)
                            / np.maximum(np.abs(est_j), 1.0)))
        ez_equal = bool(np.array_equal(ez, ez_j))
        verdict = {"compiled": True, "shape": [K, m],
                   "ez_equal": ez_equal, "zsum_rel_err": zerr,
                   "estimate_rel_err": eerr,
                   "agrees": ez_equal and zerr <= 1e-4 and eerr <= 1e-4,
                   "kernel_ms": timed(kern, bank.registers),
                   "xla_ms": timed(twin, bank.registers)}
    except Exception as e:      # noqa: BLE001 — the message IS the result
        verdict = {"compiled": False,
                   "message": f"{type(e).__name__}: {e}"[:1200]}
    verdict["seconds"] = round(time.monotonic() - t0, 2)
    log(f"[kernels] hll_stats: {json.dumps(verdict)}")
    chk.that(verdict["compiled"] and verdict.get("agrees", False),
             "kernel hll_stats "
             + ("disagrees with the jnp reduction"
                if verdict["compiled"] else "was refused"))
    return {"hll_stats": verdict}


# -------------------------------------------------------------------- main

def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib
    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        out["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        out["libtpu"] = "not installed"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cpu-dryrun", action="store_true",
                    help="run off-chip: tiny sizes, interpret-mode "
                         "kernels, output marked dryrun")
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of "
                         f"{','.join(LEGS)} (default: all; mesh needs "
                         ">= 4 devices)")
    ap.add_argument("--deadline-s", type=float, default=1150.0,
                    help="hard stop: exit 124 if still running")
    args = ap.parse_args(argv)
    legs = [x for x in args.legs.split(",") if x]
    if set(legs) - set(LEGS):
        ap.error(f"unknown leg in {legs}")

    t_start = time.monotonic()

    def expire():
        print(f"chip_smoke: still running after {args.deadline_s:.0f}s, "
              "giving up", file=sys.stderr, flush=True)
        os._exit(124)

    guard = threading.Timer(args.deadline_s, expire)
    guard.daemon = True
    guard.start()

    # first contact with JAX — and the only process that has any
    from veneur_tpu.utils import platform
    if args.cpu_dryrun:
        platform.pin_cpu(4)
    import jax
    dev = jax.devices()[0]
    if not args.cpu_dryrun and not platform.is_tpu(dev):
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"platform {dev.platform!r} ({dev.device_kind}); "
              "--cpu-dryrun is the explicit off-chip run",
              file=sys.stderr, flush=True)
        return 2
    cache_dir = platform.setup_compile_cache()
    meter = CompileMeter()
    sizes = DRYRUN if args.cpu_dryrun else FULL
    backend = "cpu" if args.cpu_dryrun else "tpu"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"platform: {dev.platform}")
    log(f"device_kind: {dev.device_kind}")
    log(f"device_count: {len(jax.devices())}")
    vers = versions()
    log(f"versions: {json.dumps(vers)}")
    log(f"compile_cache: {cache_dir} (placed by "
        + ("JAX_COMPILATION_CACHE_DIR" if os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") else "the checkout") + ")")
    log(f"sizes: {json.dumps(dataclasses.asdict(sizes))}")
    log(f"seed: {args.seed}  dryrun: {args.cpu_dryrun}")

    # built from what git would commit: the bridge is compiled from
    # native/vtpu_ingest.cpp in this run, and a failed build is fatal —
    # the Python parser is not a substitute
    from veneur_tpu.ingest import native
    t0 = time.monotonic()
    lib = native.build(force=True)
    log(f"native bridge: built {os.path.relpath(lib)} from source in "
        f"{time.monotonic() - t0:.1f}s")

    chk = Checker()
    report = {"device": device, "versions": vers, "legs": {},
              "dryrun": args.cpu_dryrun, "seed": args.seed}
    ran = []
    if "kernels" in legs:
        report["legs"]["kernels"] = kernel_leg(sizes, args.cpu_dryrun, chk)
        ran.append("kernels")
    one_chip = None
    if "tiers" in legs:
        one_chip = run_tiers(sizes, args.seed, backend, 1, meter,
                             chk, "tiers")
        ran.append("tiers")
    if "mesh" in legs:
        if len(jax.devices()) >= 4:
            mesh = run_tiers(sizes, args.seed, backend, 4, meter,
                             chk, "mesh")
            chk.that(mesh["global_engine"] == "MeshAggregationEngine",
                     "mesh leg: the global is not a mesh engine")
            if one_chip is not None:
                compare_globals(chk, one_chip["global_out"],
                                mesh["global_out"])
            report["legs"]["mesh"] = mesh
            ran.append("mesh")
        else:
            log(f"mesh: not run ({len(jax.devices())} devices)")
    if one_chip is not None:
        report["legs"]["tiers"] = one_chip
    for leg in report["legs"].values():
        leg.pop("global_out", None)

    stats = dev.memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    report["compile_total"] = meter.since((0, 0.0, 0))
    report["legs_ran"] = ran
    report["wall_s"] = round(time.monotonic() - t_start, 1)
    report["assertions"] = chk.checked
    report["failures"] = chk.failures[:200]
    log(f"peak_bytes_in_use: {report['peak_bytes_in_use']}")
    log(f"compile_total: {json.dumps(report['compile_total'])}")
    log(f"legs_ran: {ran}")
    log(f"assertions: {chk.checked} checked, {len(chk.failures)} failed; "
        f"wall {report['wall_s']}s")
    try:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "chip_smoke_report.json"),
                  "w") as f:
            json.dump(report, f, indent=1, default=str)
    except OSError as e:
        log(f"(report not written: {e})")

    guard.cancel()
    ok = not chk.failures and bool(ran)
    last = {"ok": ok, "device": device}
    if args.cpu_dryrun:
        last["dryrun"] = True
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

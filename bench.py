"""Benchmark: p99 flush-merge latency @100k distinct histograms.

The BASELINE.json north-star number: p99 flush-merge < 50 ms on TPU for
100k distinct histogram keys (the reference's Server.Flush merge/quantile
loop at the same cardinality — flusher.go sym: Server.Flush — which it
performs in Go over per-key MergingDigests). Prints ONE JSON line:

  {"metric": ..., "value": p99_ms, "unit": "ms", "vs_baseline": 50/p99, ...}

vs_baseline > 1 means the target is beaten by that factor.

One process, one chip: it refuses to start unless JAX's first device is
a TPU (`--cpu` is the explicit host-only run, and its record says
platform cpu), every phase is time-boxed against `--budget-s`, and a
run that produced no record exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

COMPRESSION = 100.0
BUF = 256
TARGET_MS = 50.0
MAX_TIMED_ITERS = 10


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------- worker

def run(k: int, budget_s: float, cpu: bool) -> int:
    """Run the flush-merge bench at cardinality k; print one JSON line.

    Exec and fetch are timed SEPARATELY (exec-only loop bounded by
    block_until_ready, then dispatch+fetch rounds), then the same bank
    goes through the real engine flush end to end."""
    deadline = time.monotonic() + budget_s
    import numpy as np

    import jax
    import jax.numpy as jnp

    from veneur_tpu.utils import platform

    if cpu:
        platform.pin_cpu()
        dev = jax.devices()[0]
    else:
        dev = platform.require_tpu("bench.py (pass --cpu for a host run)")
    platform.setup_compile_cache()
    plat = dev.platform
    _log(f"k={k} platform={plat} ({dev.device_kind}) "
         f"budget={budget_s:.0f}s")

    from veneur_tpu.models import pipeline
    from veneur_tpu.ops import hll, scalar, tdigest

    # Build the pre-flush state host-side and ship it once. Steady-state
    # worst case (r2 verdict weak #9): a warm digest enters the flush
    # with ~C merged centroids AND a full sample buffer — ~40% more data
    # per compress row than buffers alone — so seed buffers, compress
    # once on device, then refill the buffers with a second batch.
    rng = np.random.default_rng(0)
    proto = tdigest.init(1, compression=COMPRESSION, buf_size=BUF)
    c = proto.num_centroids
    bv1 = rng.gamma(2.0, 20.0, (k, BUF)).astype(np.float32)
    bv2 = rng.gamma(2.0, 20.0, (k, BUF)).astype(np.float32)
    both = np.concatenate([bv1, bv2], axis=1)
    bank = tdigest.TDigestBank(
        mean=np.zeros((k, c), np.float32),
        weight=np.zeros((k, c), np.float32),
        buf_value=bv1,
        buf_weight=np.ones((k, BUF), np.float32),
        buf_n=np.full((k,), BUF, np.int32),
        vmin=both.min(axis=1),
        vmax=both.max(axis=1),
        vsum=both.sum(axis=1, dtype=np.float64).astype(np.float32),
        count=np.full((k,), 2.0 * BUF, np.float32),
        recip=(1.0 / both).sum(axis=1, dtype=np.float64).astype(
            np.float32),
        vsum_lo=np.zeros((k,), np.float32),
        count_lo=np.zeros((k,), np.float32),
        recip_lo=np.zeros((k,), np.float32),
    )
    bank = jax.device_put(bank, dev)
    bank = tdigest.compress(bank, compression=COMPRESSION)
    bank = bank._replace(
        buf_value=jax.device_put(bv2, dev),
        buf_weight=jax.device_put(np.ones((k, BUF), np.float32), dev),
        buf_n=jax.device_put(np.full((k,), BUF, np.int32), dev))
    # compress() is a plain jit whose outputs are UNCOMMITTED; recommit,
    # so the flush executable is built for the arrays the engine's own
    # (committed) bank lineage would hand it
    bank = jax.device_put(bank, dev)
    jax.block_until_ready(bank.mean)
    _log(f"state on device at "
         f"{time.monotonic() - (deadline - budget_s):.1f}s")

    # ---- compress-only A/B microbench (ISSUE 3): the merge-path
    # compress (sorted-prefix rank-merge, the serving default) vs the
    # legacy full-row comparator sort, on the same warm worst-case bank.
    # Emitted machine-readably as compress_merge_path_ms /
    # compress_row_sort_ms so the artifact pins the speedup; the
    # full-sort arm stays dispatchable via full_sort=True (or
    # VENEUR_TPU_TDIGEST_FULL_SORT=1 process-wide) until a chip run of
    # this A/B has decided it (ROADMAP).
    compress_ab = {}
    # Sub-budget, not the raw deadline: each arm pays its own program
    # compile (~10-20s @100k CPU) plus timed iters (~12-20s each
    # there), and an unbounded A/B would starve the headline phases
    # below of their budget (observed: the 100k worker died after the
    # A/B without ever printing its record). The pair gets a bounded
    # slice, drops to 1 iteration per arm when tight, and skips an arm
    # it cannot at least compile+run once.
    ab_reserve = 80.0 if k >= 50_000 else 30.0   # tail phases' budget
    ab_deadline = min(deadline - ab_reserve, time.monotonic() + 110.0)
    for label, flag in (("compress_merge_path_ms", False),
                        ("compress_row_sort_ms", True)):
        need = 20.0 if k >= 50_000 else 3.0      # compile + 1 iter floor
        if time.monotonic() >= ab_deadline - need:
            _log(f"compress A/B skipped at {label} (sub-budget)")
            break
        try:
            fn = jax.jit(lambda b, f=flag: tdigest._compress_impl(
                b, COMPRESSION, full_sort=f))
            jax.block_until_ready(fn(bank))  # compile (bank not donated)
            arm = []
            while len(arm) < 3:
                t0 = time.monotonic()
                jax.block_until_ready(fn(bank))
                arm.append((time.monotonic() - t0) * 1000.0)
                if time.monotonic() >= ab_deadline:
                    break
            compress_ab[label] = round(sorted(arm)[len(arm) // 2], 1)
            _log(f"{label} = {compress_ab[label]:.0f}ms "
                 f"({len(arm)} iters)")
        except Exception as exc:
            _log(f"compress A/B {label} failed: {exc!r}")
    if len(compress_ab) == 2:
        compress_ab["compress_speedup"] = round(
            compress_ab["compress_row_sort_ms"]
            / max(compress_ab["compress_merge_path_ms"], 1e-3), 2)
        _log(f"compress merge-path speedup "
             f"{compress_ab['compress_speedup']}x")

    # The benched program is the ENGINE's real fused flush executable
    # (compress + quantiles + aggregates + counter/gauge/set
    # finalization in one XLA call) — not a bench-only kernel.
    qs = np.asarray([0.5, 0.75, 0.99], np.float32)
    agg_emit = ("min", "max", "count")
    from veneur_tpu.sketches.hll_engine import HLLEngine
    from veneur_tpu.sketches.tdigest_engine import TDigestEngine
    heng = TDigestEngine(compression=COMPRESSION, buffer_depth=BUF)
    seng = HLLEngine(precision=14)
    # built under the arms a default-config engine serves on this device
    from veneur_tpu import kernels
    arms = kernels.engine_arms("auto", plat, heng, seng)
    prog = pipeline._flush_executable(
        dev, heng, seng, False, agg_emit, arms["estimate"] == "fused",
        kernel_arm=arms["histogram"])
    small = jax.device_put(
        (scalar.init_counters(16), scalar.init_gauges(16),
         hll.init(16, 14)), dev)

    def run_prog(b, fetch):
        """One flush-program run on a throwaway copy (the program
        donates its inputs). Returns (exec_ms, fetch_ms)."""
        copy = jax.tree_util.tree_map(jnp.copy, (b,) + small)
        jax.block_until_ready(copy)
        t0 = time.monotonic()
        out = prog(*copy, qs)
        jax.block_until_ready(out)
        t1 = time.monotonic()
        if fetch:
            jax.device_get(out)
        return (t1 - t0) * 1000.0, (time.monotonic() - t1) * 1000.0

    t0 = time.monotonic()
    run_prog(bank, fetch=True)
    compile_s = time.monotonic() - t0
    _log(f"compile+first-run {compile_s:.1f}s")

    # Steady-state EXEC-ONLY loop, each dispatch bounded by
    # block_until_ready: the program's on-device latency. The first
    # dispatch after the warm-up round's fetch is timed on its own.
    post_fetch_ms, _ = run_prog(bank, fetch=False)
    times = []
    for i in range(MAX_TIMED_ITERS):
        # 10s margin: the fetch/transport phases after this loop are
        # what make the record parseable — never exec-iterate into them
        if times and time.monotonic() >= deadline - 10.0:
            _log(f"deadline hit after {len(times)} iters")
            break
        exec_ms, _ = run_prog(bank, fetch=False)
        times.append(exec_ms)
    times.sort()
    p99 = times[min(len(times) - 1, int(len(times) * 0.99))]
    _log(f"exec-only p99 {p99:.2f}ms over {len(times)} iters "
         f"(first post-fetch dispatch: {post_fetch_ms:.1f}ms)")

    # Fetch cost, measured on 3 dispatch+fetch rounds.
    fetches = []
    for i in range(3):
        if fetches and time.monotonic() >= deadline:
            break
        e_ms, f_ms = run_prog(bank, fetch=True)
        fetches.append(f_ms)
        _log(f"fetch round {i}: exec {e_ms:.1f}ms "
             f"fetch {f_ms:.1f}ms")
    fetches.sort()
    fetch_med = fetches[len(fetches) // 2]

    # Transport probe: the device->host rate for a FRESH array of the
    # flush payload's size, measured on the same backend — how much of
    # e2e is pure transfer (q[K,3] + aggcols[K,3] + lo_count[K] f32 =
    # 28 bytes/slot).
    payload_mb = 28.0 * k / 1e6
    n_probe = int(payload_mb * 1e6 / 4)
    probe_times = []
    for i in range(3):
        # a fresh buffer each probe — transfers of already-fetched
        # buffers are cached by the backend and would read as 0ms
        fresh = jnp.full((n_probe,), float(i + 1), jnp.float32)
        jax.block_until_ready(fresh)
        t0 = time.monotonic()
        jax.device_get(fresh)
        probe_times.append(time.monotonic() - t0)
    probe_times.sort()
    probe_mbps = payload_mb / probe_times[len(probe_times) // 2]
    _log(f"transport probe {probe_mbps:.1f} MB/s for "
         f"{payload_mb:.1f} MB payload; program fetch median "
         f"{fetch_med:.1f}ms")

    # ---- end-to-end phase: the same worst-case bank through the real
    # engine flush (lock+swap, merge program, fetch, columnar
    # InterMetric assembly for k interned keys).
    e2e = {}
    if time.monotonic() < deadline - 2.5 * (times[0] / 1000.0) - 10.0:
        from veneur_tpu.ingest.parser import MetricKey
        from veneur_tpu.models.pipeline import (
            AggregationEngine, EngineConfig)
        eng = AggregationEngine(EngineConfig(
            histogram_slots=k, counter_slots=16, gauge_slots=16,
            set_slots=16, buffer_depth=BUF))
        eng.warmup()  # what Server.start() does before its flush loop
        for i in range(k):
            eng.histo_keys.lookup(
                MetricKey(f"svc.latency.{i}", "timer", "env:prod"), 0)
        e2e_times, stats = [], None
        for i in range(5):
            if e2e_times and time.monotonic() >= deadline:
                break
            # the flush program donates its inputs, so hand the engine a
            # device-side copy of the prefilled bank each round (untimed)
            copy = jax.tree_util.tree_map(jnp.copy, bank)
            jax.block_until_ready(copy.mean)
            eng.histo_bank = copy
            # every slot is warm in this worst-case bank: mark the
            # whole dirty bitmap so the injected state is visible to
            # the serving flush (above the incremental threshold it
            # takes the full program — the honest 100%-dirty e2e;
            # config18 of bench_suite.py carries the dirty-fraction
            # A/B rows)
            if eng._dirty is not None:
                eng._dirty[0][:] = True
            cur = eng.histo_keys.interval
            for info in eng.histo_keys._map.values():
                info.last_interval = cur
            t0 = time.monotonic()
            res = eng.flush()
            dt = (time.monotonic() - t0) * 1000.0
            # Frame-native sink cost: what the serving fan-out pays per
            # sink that consumes blocks (blackhole counts; heavier sinks
            # serialize in their own thread, off this critical path).
            from veneur_tpu.metrics import FrameSet
            from veneur_tpu.sinks.basic import BlackholeMetricSink
            t0 = time.monotonic()
            bh = BlackholeMetricSink()
            bh.flush_frames(FrameSet([res.frame]))
            sink_ms = (time.monotonic() - t0) * 1000.0
            # Legacy comparison: materializing the InterMetric list (the
            # cost a non-frame-native sink pays once, in its thread).
            t0 = time.monotonic()
            n_metrics = len(res.metrics)
            mat_ms = (time.monotonic() - t0) * 1000.0
            e2e_times.append(dt)
            stats = res.stats
            stats["materialize_ms"] = mat_ms
            stats["sink_frame_ms"] = sink_ms
            _log(f"e2e flush {i}: {dt:.1f}ms + frame-sink "
                 f"{sink_ms:.2f}ms + materialize {mat_ms:.1f}ms "
                 f"(n_metrics={n_metrics}, bh={bh.flushed_total})")
        timed = sorted(e2e_times[1:] or e2e_times)  # [0] warms transfers
        e2e_p99 = timed[min(len(timed) - 1, int(len(timed) * 0.99))]
        e2e = {
            "e2e_p99_ms": round(e2e_p99, 3),
            "e2e_iters": len(timed),
            "e2e_swap_ms": round(stats["swap_ns"] / 1e6, 2),
            "e2e_merge_ms": round(stats["merge_ns"] / 1e6, 2),
            "e2e_assembly_ms": round(stats["assembly_ns"] / 1e6, 2),
            "e2e_materialize_ms": round(stats["materialize_ms"], 2),
            "e2e_sink_frame_ms": round(stats["sink_frame_ms"], 2),
            # transport accounting: merge_ns = program exec + the
            # device->host fetch; exec_p99_ms is the program-only cost,
            # so the residual over it is wire time, cross-checked
            # against the measured probe rate
            "fetch_mb": round(payload_mb, 2),
            "probe_mbps": round(probe_mbps, 1),
            "transport_floor_ms": round(
                payload_mb / probe_mbps * 1000.0, 1),
            "e2e_minus_transport_ms": round(
                e2e_p99 - payload_mb / probe_mbps * 1000.0, 1),
        }

    # Headline value: the served-engine e2e p99 when measured, else the
    # program's exec-only p99 (`headline_source` says which).
    # vs_baseline is only meaningful at the north-star cardinality.
    if "e2e_p99_ms" in e2e:
        headline, headline_src = e2e["e2e_p99_ms"], "e2e"
    else:
        headline, headline_src = p99, "exec_only"
    vs = round(TARGET_MS / headline, 3) if k >= 100_000 else 0.0
    out_rec = {
        "metric": f"flush_merge_p99_ms_{k // 1000}k_histos_{plat}",
        "value": round(headline, 3),
        "unit": "ms",
        "vs_baseline": vs,
        "k": k,
        "platform": plat,
        "device_kind": dev.device_kind,
        "headline_source": headline_src,
        "exec_p99_ms": round(p99, 3),
        "exec_iters": len(times),
        "post_fetch_dispatch_ms": round(post_fetch_ms, 1),
        "compile_s": round(compile_s, 1),
        "prog_fetch_med_ms": round(fetch_med, 1),
        "kernel_arms": arms,
        **compress_ab,
        **e2e,
    }
    print(json.dumps(out_rec), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--k", type=int, default=100_000,
                    help="distinct histogram keys (north star: 100000)")
    ap.add_argument("--budget-s", type=float, default=330.0,
                    help="wall budget the phases time-box against")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the host CPU (the record says so); "
                         "without it anything but a TPU is refused")
    args = ap.parse_args(argv)
    return run(args.k, args.budget_s, args.cpu)


if __name__ == "__main__":
    sys.exit(main())

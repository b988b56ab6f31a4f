"""One run of one cell of BENCHMARK.json.

  python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a deployment (`perfbench/configs/`) and a traffic mix
(`perfbench/mixes/`); the deployment's file names the driver that builds
and drives its servers (`perfbench/drivers/`), the mix's file the
generator that makes its payloads and their plain reference
(`perfbench/generators/`); BENCHMARK.json says which end-to-end metrics
(`--trace 0`) and which per-layer metrics (`--trace 1`, each read by
`perfbench/metrics/<name>`) the cell reports. `perfbench/harness.py` has
what a driver and a generator must give. Set-up starts the servers,
builds every payload and its reference from the seed, and runs the
cell's own tick untimed until a whole cycle of payloads compiles
nothing. The window then runs whole ticks until `--seconds` of ticks
have passed: a tick that starts inside the window is finished and timed.
A traced window (`--trace 1`) is shorter: at most `TRACED_TICKS` timed
ticks, so that what a traced run costs (the ticks, the comparisons
between them under the profiler, the trace's events and their
reduction) is fixed by the benchmark and not by how fast the tree under
test ticks; `breakdown`'s seconds are sums over that window. A trace
whose device lines end before the last timed tick starts is refused
(`tracered.refuse_cut_short`), and a traced run's last log line says
where its seconds went. Every tick, timed or not, traced or not, is
checked against the reference between ticks, outside every timed
interval and off the window's clock. The last line of standard output
is the result.

Off the chip the run fails and prints no result. `--rehearsal` is the
explicit CPU run for tests: tiny sizes, virtual devices, the result
marked `"rehearsal": true`, no time and no device metric in it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

_T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MAX_WARMUP_TICKS = 8
# The per-layer metrics are medians over the timed ticks, and sixteen are
# ample for a median (the cells with the longest ticks have had 4-7 all
# along). Without the cap the number of traced ticks is `--seconds` over
# the tick's length, so a tree that ticks twice as fast pays for twice
# the comparisons, events and reduction, and ran into the driver's time
# limit for being faster (PR 40); and the profiler's device line holds
# some 25 ticks of the cell with the most device events a tick.
TRACED_TICKS = 16


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(manifest: dict, cell: str, group: str) -> list:
    """The metrics of `end_to_end` or `per_layer` this cell reports: an
    end-to-end metric without a `workloads` key is every cell's; a
    per-layer one is reported wherever the metric it moves is."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def reports(m):
        if "workloads" in m:
            return cell in m["workloads"]
        return reports(e2e[m["moves"]]) if "moves" in m else True

    return [m for m in manifest[group] if reports(m)]


def window_open(k: int, measured_s: float, seconds: float,
                traced: bool) -> bool:
    """Whether the window takes one more tick after `k` timed ticks of
    `measured_s` seconds together: always a first; then until `seconds`
    of ticks have passed, and under the profiler until `TRACED_TICKS`
    ticks have, whichever comes first."""
    if traced and k >= TRACED_TICKS:
        return False
    return k == 0 or measured_s < seconds


def reexec_with_process_env(want: dict):
    """The deployment file states the environment the process runs in
    (`assumed.process.env`, e.g. a fixed PYTHONHASHSEED: 100k interned
    key strings hash differently in every process otherwise). Variables
    the interpreter reads at start need a fresh start: re-execute once,
    before anything has touched JAX."""
    missing = {k: str(v) for k, v in want.items()
               if os.environ.get(k) != str(v)}
    if not missing:
        return
    if os.environ.get("PERFBENCH_REEXEC"):
        raise SystemExit(f"perfbench: could not set {sorted(missing)}")
    env = {**os.environ, **missing, "PERFBENCH_REEXEC": "1",
           "PERFBENCH_T0": repr(_T0)}
    sys.stdout.flush()
    os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)]
              + sys.argv[1:], env)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny sizes, virtual devices; for tests")
    ap.add_argument("--control", default=None,
                    help="run one of the deployment file's `controls` in "
                         "the program's place: `correct` must come out "
                         "false (never used by a benchmark run)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under chiprun_out/ "
                         "(perfbench/study/dump_trace.py reads them)")
    ap.add_argument("--ticks-out", default=None,
                    help="append every tick's record to this .jsonl file "
                         "(the noise study reads it)")
    args = ap.parse_args(argv)

    manifest = load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"perfbench: no cell {args.workload!r} in "
                         f"BENCHMARK.json ({sorted(cells)})")
    cell = cells[args.workload]

    from perfbench import harness, layers, reference
    log = harness.log
    cfg = harness.load_config(cell["config"], args.rehearsal)
    mix = harness.load_mix(cell["traffic"], args.rehearsal)
    cfg["control"], cfg["study"] = None, bool(args.ticks_out)
    if args.control:
        cfg["control"] = control = cfg["controls"][args.control]
        cfg["common"] = {**cfg["common"], **control.get("common", {})}
    process = cfg.get("assumed", {}).get("process", {})
    reexec_with_process_env(process.get("env", {}))
    t_start = float(os.environ.get("PERFBENCH_T0", _T0))
    driver, generator = harness.load_driver(cfg), harness.load_generator(mix)
    if driver.Driver.TAKES != generator.MAKES:
        raise SystemExit(
            f"perfbench: the driver {cfg['driver']!r} sends "
            f"{driver.Driver.TAKES!r} and the generator "
            f"{mix['generator']!r} makes {generator.MAKES!r}")

    # first contact with JAX — and the only process that has any
    from veneur_tpu.utils import platform
    chips = int(cell["chips"])
    if args.rehearsal:
        platform.pin_cpu(max(chips, 1))
    import jax
    devs = jax.devices()
    if not args.rehearsal and (not platform.is_tpu(devs[0])
                               or len(devs) < chips):
        print(f"perfbench: cell {cell['name']} needs {chips} TPU chip(s), "
              f"but JAX reports {len(devs)} device(s) of platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr, flush=True)
        return 2
    peaks = None
    if not args.rehearsal:
        with open(os.path.join(HERE, "peaks", "peaks.json")) as f:
            table = json.load(f)
        if devs[0].device_kind not in table:
            print(f"perfbench: no peaks for device kind "
                  f"{devs[0].device_kind!r} in perfbench/peaks/peaks.json",
                  file=sys.stderr, flush=True)
            return 2
        peaks = table[devs[0].device_kind]
    cache_dir = platform.setup_compile_cache()
    log(f"cell {cell['name']}  seed {args.seed}  seconds {args.seconds}  "
        f"trace {args.trace}  rehearsal {args.rehearsal}")
    log(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache_dir}; PYTHONHASHSEED "
        f"{os.environ.get('PYTHONHASHSEED')}")

    meter = harness.CompileMeter()
    gcm = harness.GcMeter()
    spans = harness.Spans()

    payloads, ref_s = generator.build(cfg, mix, args.seed, log)
    tol = cfg["guarantees"]["tolerances"]
    t = driver.Driver(cfg, args.rehearsal)
    verdicts, records = [], []
    took_s = {"comparisons": 0.0}   # a traced run's stages, for its log
    try:
        want_devices = int(cfg["global"].get("tpu_num_devices", 1))
        got_devices = t.mesh_devices()
        log(f"guarantee: every bank leaf of the global on {want_devices} "
            f"distinct device(s): found {got_devices}")

        def one_tick(i, timed):
            p = payloads[i % len(payloads)]
            rec = t.tick(p, 1_000 + 10 * i, spans, gcm, meter)
            if not records and layers.missing_keys(rec):
                raise RuntimeError(
                    f"the driver {cfg['driver']!r} gave a tick record "
                    f"without {layers.missing_keys(rec)}")
            c0 = time.monotonic()
            v = t.check(p, rec, tol)
            if timed:
                took_s["comparisons"] += time.monotonic() - c0
            rec.update(index=i, timed=timed, payload=i % len(payloads) + 1,
                       compared={k: val for k, (val, _lim)
                                 in v["numbers"].items()})
            verdicts.append(v)
            records.append(rec)
            nums = "  ".join(f"{k} {val:.6g} (limit {lim:g})"
                             for k, (val, lim) in v["numbers"].items())
            took = "  ".join(f"{k.removeprefix('bench.')} {s:.3f}s"
                             for k, s in rec["spans"].items())
            log(f"tick {i} {'timed' if timed else 'warm-up'}: "
                f"{rec['attempted']} {t.OPS}  {took}  "
                f"emit {rec['emit_latency_s']:.3f}s  compiled "
                f"{rec['counters']['compile.programs']}  | {nums}")
            for m in v["mismatches"]:
                log(f"  MISMATCH {m}")
            if timed and rec["compiled"]:
                log(f"  COMPILED in the window: {rec['compiled']}")
            return rec

        # warm-up: the cell's own ticks, untimed, until a whole cycle of
        # payloads after the first tick (the first forward is a full
        # resync, later ones deltas) has compiled nothing; then what the
        # driver warms besides (the import landing's other lane widths)
        t.watch_warmup()
        n, quiet = 0, 0
        while quiet < len(payloads):
            if n >= MAX_WARMUP_TICKS:
                raise RuntimeError(
                    f"still compiling after {n} warm-up ticks: "
                    f"{records[-1]['compiled']}")
            rec = one_tick(n, False)
            quiet = quiet + 1 if (n > 0 and not rec["counters"][
                "compile.programs"]) else 0
            n += 1
        warmed = t.finish_warmup()
        log(f"import landing: warmed {warmed} besides what the warm-up "
            f"ticks met")
        setup_s = time.monotonic() - t_start - ref_s
        log(f"set-up {setup_s:.3f}s ({n} warm-up ticks; {meter.requests} "
            f"programs built or fetched, {meter.cache_hits} cache hits, "
            f"{meter.seconds:.1f}s compiling); reference {ref_s:.3f}s "
            f"not counted")

        trace_dir = os.path.join(ROOT, "chiprun_out", "perfbench_trace",
                                 f"{cell['name']}.{args.seed}")
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            from perfbench.tracered import SYNC
            with spans.span(SYNC):
                time.sleep(0.002)
        # the window's clock runs during ticks only: the comparison
        # with the reference between two ticks is not the system's time
        w0 = time.monotonic()
        k, measured_s = 0, 0.0
        try:
            while window_open(k, measured_s, args.seconds, bool(args.trace)):
                measured_s += one_tick(n + k, True)["wall_s"]
                k += 1
        finally:
            w1 = time.monotonic()
            if args.trace:
                jax.profiler.stop_trace()
                took_s["stop_trace"] = time.monotonic() - w1
        counters = t.drop_counters()
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devs[:max(chips, 1)]), default=0)
    finally:
        t.stop()
        gcm.close()

    timed = [r for r in records if r["timed"]]
    log(f"window: {len(timed)} timed ticks, {measured_s:.1f}s of ticks in "
        f"{w1 - w0:.1f}s with the comparisons between them")
    log(f"counters: {json.dumps(counters)}")

    # ---- correct: every tick of the run, warm-up included
    numbers: dict = {}
    for v in verdicts:
        for name, (val, lim) in v["numbers"].items():
            worst = numbers.get(name, (0.0, lim))[0]
            numbers[name] = (reference.worse(val, worst), lim)
    in_window = sum(r["counters"]["compile.programs"] for r in timed)
    numbers["compile.in_window"] = (float(in_window), 0.0)
    numbers["drop_and_error_counters"] = (float(sum(counters.values())), 0.0)
    numbers["mesh_devices_missing"] = (
        float(abs(want_devices - got_devices)), 0.0)
    compared = [
        f"compared: {name} = {val:.6g}  limit {lim:g}  "
        f"{'ok' if reference.within({name: (val, lim)}) else 'FAIL'}"
        for name, (val, lim) in numbers.items()]
    for line in compared:
        log(line)
    correct = reference.within(numbers)
    attempted = int(sum(v["attempted"] for v in verdicts))
    failed = int(sum(v["failed"] for v in verdicts))

    if args.ticks_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.ticks_out)),
                    exist_ok=True)
        with open(args.ticks_out, "a") as f:
            for r in records:
                row = {k2: v for k2, v in r.items() if k2 != "phase_rows"}
                row["phases"] = harness.phase_seconds(r["phase_rows"])
                row.update(cell=cell["name"], seed=args.seed,
                           pid=os.getpid(), setup_s=setup_s)
                f.write(json.dumps(row) + "\n")

    # ---- metrics
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    ctx = {"ticks": timed, "trace": None, "config": cfg, "mix": mix,
           "peaks": peaks, "run": {"setup_s": setup_s},
           "device": {} if args.rehearsal else {
               "peak_hbm_bytes": float(peak)}}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": {}, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    if args.control:
        result["control"] = args.control
    group = "per_layer" if args.trace else "end_to_end"
    if args.trace and not args.rehearsal:
        from perfbench import tracered
        r0 = time.monotonic()
        trace = tracered.load_xplane(tracered.find_xplane(trace_dir))
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        r1 = time.monotonic()
        windows = [(r["t_first_ns"], r["t_end_ns"]) for r in timed]
        ends, (tick0, tick1) = tracered.refuse_cut_short(
            trace, spans.rows, windows)
        log(f"trace: the last timed tick ran {tick0:.3f}s-{tick1:.3f}s on "
            f"the trace's clock; its device lines end at {ends}")
        tr = tracered.reduce_trace(
            trace, spans.rows,
            [row for r in timed for row in r["phase_rows"]], windows)
        took_s.update(load_xplane=r1 - r0,
                      reduce_trace=time.monotonic() - r1)
        ctx["trace"] = tr
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
        log(f"trace: busy by device {tr['busy_s_by_device']} of "
            f"{tr['window_s']:.3f}s in timed ticks")
    for m in cell_metrics(manifest, cell["name"], group):
        # a rehearsal is a CPU run: it prints counts, never a time, a
        # rate or a device metric
        if args.rehearsal and m["source"] != "program_counter":
            continue
        v = layers.read_metric(m["name"], ctx)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    # every number compared beside its limit: the last lines of standard
    # error, and the last key of the result
    result["compared"] = {
        name: {"value": val if math.isfinite(val) else repr(val),
               "limit": lim} for name, (val, lim) in numbers.items()}
    if args.trace:
        took_s = {"set-up": w0 - t_start, "ticks": measured_s,
                  "between ticks": w1 - w0 - measured_s, **took_s}
        log(f"traced run, {len(timed)} timed ticks: "
            + "  ".join(f"{k} {v:.1f}s" for k, v in took_s.items())
            + f"  (the comparisons are inside `between ticks`);  "
              f"{time.monotonic() - t_start:.1f}s since the process started")
    print("\n".join(compared), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

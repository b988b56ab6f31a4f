"""The noise study of `emit_latency_s`: where the host-clock spread
comes from.

  python3 perfbench/study/noise_study.py run --cells two_tier_1chip.steady_10k,two_tier_1chip.wide_100k \
      --runs 6 --seconds 51 --out perfbench/study/ticks.jsonl
  python3 perfbench/study/noise_study.py reduce perfbench/study/ticks.jsonl

`run` starts `--runs` fresh processes of each cell, one after another
(this parent never touches JAX, so each child holds the chip alone),
and has every tick of every process appended to the file: the tick's
`emit_latency_s`, each of its spans and flight recorder phases, the
process's CPU seconds against wall seconds, the garbage collections
that fell inside it, the thread count and the load average. `reduce`
prints the tables PERF.md keeps: the spread of the per-run estimate
between processes against the spread of ticks within a process, which
phase carries the variance, and whether it follows garbage collection,
CPU share or nothing measured. A spread is the distance between the
first and third quartile (statistics.quantiles, n=4) over the median.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")


def iqr(values):
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def spread(values):
    return iqr(values) / statistics.median(values)


def pearson(xs, ys):
    if len(xs) < 3 or len(set(xs)) < 2 or len(set(ys)) < 2:
        return float("nan")
    return statistics.correlation(xs, ys)


def run(args):
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for cell in args.cells.split(","):
        for i in range(args.runs):
            cmd = [sys.executable, RUN, "--workload", cell,
                   "--seed", str(args.seed0 + i), "--seconds",
                   str(args.seconds), "--trace", "0",
                   "--ticks-out", args.out]
            p = subprocess.run(cmd, capture_output=True, text=True)
            last = p.stdout.strip().splitlines()[-1:] or ["(no output)"]
            print(f"{cell} run {i} rc={p.returncode} {last[0][:400]}",
                  flush=True)
            if p.returncode:
                print(p.stderr[-2000:], flush=True)
    return 0


def per_tick_table(t: dict) -> dict:
    """One tick's seconds by span and by phase, flat."""
    out = dict(t["spans"])
    out.update(t["phases"])
    return out


def reduce(args):
    rows = [json.loads(ln) for ln in open(args.file)]
    cells = sorted({r["cell"] for r in rows})
    for cell in cells:
        runs: dict = {}
        for r in rows:
            if r["cell"] == cell and r["timed"]:
                runs.setdefault((r["pid"], r["seed"]), []).append(r)
        runs = {k: sorted(v, key=lambda r: r["index"])
                for k, v in runs.items()}
        if not runs:
            continue
        print(f"\n### {cell}: {len(runs)} processes, "
              f"{[len(v) for v in runs.values()]} timed ticks each\n")
        est = {
            "median of all timed ticks": [statistics.median(
                t["emit_latency_s"] for t in v) for v in runs.values()],
            "median, first timed tick left out": [statistics.median(
                t["emit_latency_s"] for t in (v[1:] or v))
                for v in runs.values()],
            "mean of all timed ticks": [statistics.fmean(
                t["emit_latency_s"] for t in v) for v in runs.values()],
            "first timed tick alone": [v[0]["emit_latency_s"]
                                       for v in runs.values()],
        }
        print("| estimator of a run | median over runs (s) | "
              "spread between processes |")
        print("|---|---|---|")
        for name, vals in est.items():
            print(f"| {name} | {statistics.median(vals):.4f} | "
                  f"{100 * spread(vals):.2f}% |")
        within = [spread([t["emit_latency_s"] for t in v])
                  for v in runs.values() if len(v) >= 2]
        if within:
            print(f"| ticks within one process (median of the processes' "
                  f"own spreads) | | {100 * statistics.median(within):.2f}% "
                  f"(worst {100 * max(within):.2f}%) |")
        first = [v[0]["emit_latency_s"] / statistics.median(
            t["emit_latency_s"] for t in (v[1:] or v)) for v in runs.values()]
        print(f"\nfirst timed tick over the median of the later ones: "
              f"{', '.join(f'{x:.3f}' for x in first)}")

        # which phase carries it: per-run median seconds of each span
        # and phase; its spread between processes in seconds, against
        # the spread of emit_latency_s in seconds
        names = sorted({k for v in runs.values() for t in v
                        for k in per_tick_table(t)})
        emit_runs = est["median of all timed ticks"]
        print(f"\n| span or phase | median (s) | between-process IQR (s) | "
              f"within-process IQR (s) | r with the run's emit_latency_s |")
        print("|---|---|---|---|---|")
        table = []
        for name in names:
            per_run = [statistics.median(per_tick_table(t).get(name, 0.0)
                                         for t in v) for v in runs.values()]
            inner = [iqr([per_tick_table(t).get(name, 0.0) for t in v])
                     for v in runs.values() if len(v) >= 2]
            table.append((iqr(per_run), name, statistics.median(per_run),
                          statistics.median(inner) if inner else 0.0,
                          pearson(per_run, emit_runs)))
        print(f"| emit_latency_s | {statistics.median(emit_runs):.4f} | "
              f"{iqr(emit_runs):.4f} | | 1 |")
        for b, name, med, inner, r in sorted(table, reverse=True)[:14]:
            print(f"| {name} | {med:.4f} | {b:.4f} | {inner:.4f} | {r:.2f} |")

        # does it follow anything measured
        ticks = [t for v in runs.values() for t in v]
        emit = [t["emit_latency_s"] for t in ticks]
        print("\n| per tick, against emit_latency_s | median | max | r |")
        print("|---|---|---|---|")
        for label, f in (
                ("gc seconds inside the emit interval", lambda t: t["gc_s"]),
                ("gc count inside the emit interval", lambda t: t["gc_n"]),
                ("process CPU seconds / wall seconds of the tick",
                 lambda t: t["cpu_s"] / t["wall_s"]),
                ("threads", lambda t: t["threads"]),
                ("1-minute load average", lambda t: t["loadavg"])):
            vals = [f(t) for t in ticks]
            print(f"| {label} | {statistics.median(vals):.4g} | "
                  f"{max(vals):.4g} | {pearson(vals, emit):.2f} |")
        cpu_runs = [statistics.median(t["cpu_s"] / t["wall_s"] for t in v)
                    for v in runs.values()]
        print(f"\nper-run CPU share against the run's emit_latency_s: r = "
              f"{pearson(cpu_runs, emit_runs):.2f}; per-run values "
              f"{', '.join(f'{e:.3f}' for e in emit_runs)}")
        if any("ingest_s" in t for t in ticks):
            rate = [sum(t["lines"] for t in v) / sum(t["ingest_s"] for t in v)
                    for v in runs.values()]
            print(f"ingest_rate per run: median {statistics.median(rate):.0f}"
                  f" lines/s, spread {100 * spread(rate):.2f}%")
        setups = [v[0]["setup_s"] for v in runs.values()]
        print(f"setup_s per run: {', '.join(f'{s:.1f}' for s in setups)}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--cells", required=True)
    r.add_argument("--runs", type=int, default=6)
    r.add_argument("--seconds", type=int, default=51)
    r.add_argument("--seed0", type=int, default=2_500_000_001)
    r.add_argument("--out", required=True)
    d = sub.add_parser("reduce")
    d.add_argument("file")
    args = ap.parse_args(argv)
    return run(args) if args.cmd == "run" else reduce(args)


if __name__ == "__main__":
    sys.exit(main())

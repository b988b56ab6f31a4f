"""The fan-in probe: `fanin32_global_1chip.fleet_1k` through `run.py`'s
own `main`, under a manifest that is BENCHMARK.json plus the entries of
`perfbench/study/fanin32.entries.json` — the `configs` and `workloads`
entries a later PR adds, letter for letter. The cell is in no
BENCHMARK.json yet: it waits for a PR that may change the program
(PERF.md §7).

  python3 perfbench/study/fanin_probe.py run --workload <cell> --seed n \\
      --seconds s --trace 0|1 [--rehearsal] [--control name] ...
      one run in this process, `run.py`'s arguments after `run`; the
      tier-1 rehearsals call this

  python3 perfbench/study/fanin_probe.py study [--seeds a,b,c]
      [--seconds 51] [--out chiprun_out/pr28/fanin]
      the study on the chip: a run a seed and a traced run of the first
      seed, each a process of its own (one process holds the chip), each
      with its log, its result line and every tick's record
      (`--ticks-out`) under --out; then a summary of what PERF.md asks
      for. A run that dies or is killed at its limit is recorded as that.

  python3 perfbench/study/fanin_probe.py limits [--seeds a,b,...]
      [--controls bf16_sums,f32_running_sums,...] [--control-seeds a,b,c]
      [--seconds 20] [--control-seconds 10] [--out chiprun_out/pr28/fanin3]
      [--rehearsal]
      the readings a limit is set from: sound runs on --seeds as the
      benchmark would make them (no `--ticks-out`, so nothing wraps the
      program in the window), then every control on --control-seeds in
      a short window; a line a run with every number compared, and at
      the end each number's largest sound reading beside each control's
      smallest.

Every per-layer metric of BENCHMARK.json that lists no cells is reported
in every cell that reports `emit_latency_s`, this one too; the metrics of
the local tier and of the forward list theirs since PR 28.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "fanin32_global_1chip.fleet_1k"
RUN_LIMIT_S = 900


def merged_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(HERE, "fanin32.entries.json")) as f:
        entries = json.load(f)
    for group in ("configs", "workloads"):
        have = {e["name"] for e in manifest[group]}
        manifest[group] += [e for e in entries[group]
                            if e["name"] not in have]
    return manifest


def run_one(argv: list) -> int:
    """`run.main` under the merged manifest. The deployment's process
    environment is set here, before `run.py` would re-execute itself
    into a process that has lost the manifest."""
    from perfbench import harness, run
    manifest = merged_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--rehearsal", action="store_true")
    known, _rest = ap.parse_known_args(argv)
    env = harness.load_config(cells[known.workload]["config"]).get(
        "assumed", {}).get("process", {}).get("env", {})
    missing = {k: str(v) for k, v in env.items()
               if os.environ.get(k) != str(v)}
    if missing:
        os.execve(sys.executable, [sys.executable, os.path.abspath(__file__),
                                   "run", *argv],
                  {**os.environ, **missing})
    run.load_manifest = lambda root=ROOT: manifest
    return run.main(argv)


def one_process(out: str, tag: str, seed: int, seconds, extra: list):
    """One run of the cell in a process of its own (one process holds
    the chip), its output under `out`; the exit code, or how it died."""
    cmd = [sys.executable, os.path.abspath(__file__), "run",
           "--workload", CELL, "--seed", str(seed), "--seconds",
           str(seconds), *extra]
    with open(os.path.join(out, tag + ".out"), "w") as fo, \
            open(os.path.join(out, tag + ".err"), "w") as fe:
        try:
            return subprocess.run(cmd, cwd=ROOT, stdout=fo, stderr=fe,
                                  timeout=RUN_LIMIT_S).returncode
        except subprocess.TimeoutExpired:
            return f"killed at {RUN_LIMIT_S} s"


def study(args) -> int:
    out = os.path.join(ROOT, args.out)
    os.makedirs(out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = [(s, 0) for s in seeds] + [(seeds[0], 1)]
    rows = []
    for seed, trace in plan:
        tag = f"{CELL}_{seed}_t{trace}"
        ticks = os.path.join(out, tag + ".ticks.jsonl")
        rc = one_process(out, tag, seed, args.seconds,
                         ["--trace", str(trace), "--ticks-out", ticks])
        rows.append(summarize(out, tag, seed, trace, rc, ticks))
        print(json.dumps(rows[-1]), flush=True)
    emits = [r["metrics"]["emit_latency_s"] for r in rows
             if r.get("metrics", {}).get("emit_latency_s")]
    if len(emits) >= 3:
        q = statistics.quantiles(emits, n=4)
        print(json.dumps({"emit_latency_s": emits, "spread":
                          (q[2] - q[0]) / statistics.median(emits)}),
              flush=True)
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


def limits(args) -> int:
    from perfbench.study.pairs import read_result
    out = os.path.join(ROOT, args.out)
    os.makedirs(out, exist_ok=True)
    plan = [("sound", int(s), args.seconds)
            for s in args.seeds.split(",") if s]
    plan += [(c, int(s), args.control_seconds)
             for c in args.controls.split(",") if c
             for s in args.control_seeds.split(",")]
    read: dict = {}                 # side -> number -> [a run's reading]
    for side, seed, seconds in plan:
        tag = f"{CELL}_{side}_{seed}"
        extra = (["--trace", "0"]
                 + ([] if side == "sound" else ["--control", side])
                 + (["--rehearsal"] if args.rehearsal else []))
        rc = one_process(out, tag, seed, seconds, extra)
        row = read_result(os.path.join(out, tag + ".out"))
        print(json.dumps({"run": tag, "rc": rc, **{
            k: row.get(k) for k in ("correct", "compared", "metrics",
                                    "set_up", "tail")}}), flush=True)
        for name, (value, _ok) in row["compared"].items():
            read.setdefault(side, {}).setdefault(name, []).append(value)
    table = {name: {"sound_largest": max(values), "sound_runs": len(values),
                    **{side: min(numbers[name]) for side, numbers
                       in read.items() if side != "sound"
                       and name in numbers}}
             for name, values in read.get("sound", {}).items()}
    print(json.dumps({"limits_from": table}), flush=True)
    with open(os.path.join(out, "limits.json"), "w") as f:
        json.dump({"read": read, "table": table}, f, indent=1)
    return 0


def summarize(out: str, tag: str, seed: int, trace: int, rc, ticks: str):
    from perfbench.study.pairs import read_result
    row = {"run": tag, "seed": seed, "trace": trace, "rc": rc}
    row.update(read_result(os.path.join(out, tag + ".out")))
    if os.path.exists(ticks):
        with open(ticks) as f:
            recs = [json.loads(ln) for ln in f]
        timed = [r for r in recs if r["timed"]]
        row["ticks"] = {"warm_up": len(recs) - len(timed),
                        "timed": len(timed)}
        row["shapes_by_tick"] = [
            [r["index"], r["timed"], r["landing_shapes"], r["compiled"]]
            for r in recs]
        row["shapes"] = sorted({tuple(s) for r in recs
                                for s in r["landing_shapes"]})
        if timed:
            def med(f):
                return statistics.median(f(r) for r in timed)
            row["per_tick"] = {
                "wall_s": med(lambda r: r["wall_s"]),
                "forwards_s": med(lambda r: r["spans"]["bench.forwards"]),
                "acks_s": med(lambda r: r["acks_s"]["last"]),
                **{name: med(lambda r, n=name: r["phases"].get(n, 0.0))
                   for name in ("global:import.route",
                                "global:import.dedupe",
                                "global:import.apply", "global:import.land",
                                "global:import.land.stage",
                                "global:import.land.cluster")}}
    return row


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "run":
        sys.exit(run_one(sys.argv[2:]))
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("study", "limits"))
    ap.add_argument("--seeds", default="2800000001,2800000002,2800000003")
    ap.add_argument("--seconds", type=int, default=51)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-seconds", type=int, default=10)
    ap.add_argument("--rehearsal", action="store_true",
                    help="`limits` on the CPU at rehearsal size: the "
                         "wiring, never a reading")
    ap.add_argument("--out", default="chiprun_out/pr28/fanin")
    args = ap.parse_args()
    sys.exit(study(args) if args.mode == "study" else limits(args))

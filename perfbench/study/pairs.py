"""Runs of one cell on two trees in one chip call, and their table.

  python3 perfbench/study/pairs.py --cell <cell> --out chiprun_out/pr28 \\
      --parent archive_check/parent --plan P:1,C:1,C:2,P:2,...,C:1:t

A plan entry is `<tree>:<seed>[:t][:<set>]`: `P` the tree unpacked at
--parent (`git archive <commit> | tar -x -C <dir>`, a directory
`.gitignore` lists), `C` this tree; `t` a traced run; `<set>` a label
that keeps a second run of one seed apart (`C:1::2`). Each run is
`<command of the tree's BENCHMARK.json> --workload <cell> --seed <n>
--seconds <run_seconds> --trace <0|1>` from the tree's root, a process of
its own (one process holds the chip), its output under --out as
`<cell>_<P|C>_<seed>_t<0|1>[_s<set>].out` / `.err`. The first run of a
tree in a call is marked: it is the one that compiled.

The table: every run's metrics and compared numbers; for each tree and
set the median of each end-to-end metric and its spread (quartile
distance over the median, `statistics.quantiles(n=4)`, the driver's
rule); the change's median over the parent's.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one_run(tree: str, cell: str, seed: int, trace: int, base: str) -> dict:
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cmd = manifest["command"] + [
        "--workload", cell, "--seed", str(seed), "--seconds",
        str(manifest["run_seconds"]), "--trace", str(trace)]
    t0 = time.monotonic()
    with open(base + ".out", "w") as fo, open(base + ".err", "w") as fe:
        try:
            rc = subprocess.run(cmd, cwd=tree, stdout=fo, stderr=fe,
                                timeout=1500).returncode
        except subprocess.TimeoutExpired:
            rc = "killed at 1500 s"
    row = {"rc": rc, "seconds": round(time.monotonic() - t0, 1)}
    row.update(read_result(base + ".out"))
    return row


def read_result(path: str) -> dict:
    """What a run's standard output says: the result line's fields, the
    numbers compared, the set-up line; the last lines where there is no
    result."""
    with open(path) as f:
        lines = f.read().splitlines()
    row = {}
    if lines and lines[-1].startswith("{"):
        res = json.loads(lines[-1])
        row.update(correct=res["correct"], attempted=res["attempted"],
                   failed=res["failed"], device=res["device"],
                   metrics={k: v["value"]
                            for k, v in res["metrics"].items()},
                   breakdown=res.get("breakdown"))
    row["compared"] = {}
    for ln in lines:
        m = re.match(r"compared: (\S+) = (\S+)  limit (\S+)  (ok|FAIL)", ln)
        if m:
            row["compared"][m.group(1)] = [float(m.group(2)), m.group(4)]
    row["set_up"] = next((ln for ln in lines if ln.startswith("set-up ")),
                         None)
    if "correct" not in row:
        row["tail"] = lines[-5:]
    return row


def spread(values: list) -> float | None:
    if len(values) < 3:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--parent", default="archive_check/parent")
    ap.add_argument("--out", default="chiprun_out/pairs")
    args = ap.parse_args()
    out = os.path.join(ROOT, args.out)
    os.makedirs(out, exist_ok=True)
    trees = {"P": os.path.join(ROOT, args.parent), "C": ROOT}
    rows, first = [], set()
    for entry in args.plan.split(","):
        side, seed, *rest = entry.split(":")
        trace = int(bool(rest and rest[0] == "t"))
        label = rest[1] if len(rest) > 1 else ""
        tag = f"{args.cell}_{side}_{seed}_t{trace}" + (
            f"_s{label}" if label else "")
        row = {"run": tag, "side": side, "seed": int(seed), "trace": trace,
               "set": label, "first_of_tree": side not in first}
        first.add(side)
        row.update(one_run(trees[side], args.cell, int(seed), trace,
                           os.path.join(out, tag)))
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items()
                          if k != "breakdown"}), flush=True)
    table = {}
    for side in ("P", "C"):
        for label in sorted({r["set"] for r in rows if r["side"] == side}):
            runs = [r for r in rows if r["side"] == side
                    and r["set"] == label and not r["trace"]
                    and "metrics" in r]
            names = sorted({k for r in runs for k in r["metrics"]})
            table[f"{side}{label}"] = {
                name: {"n": len(v), "median": statistics.median(v),
                       "spread": spread(v),
                       "median_warm": statistics.median(w) if w else None}
                for name in names
                for v in [[r["metrics"][name] for r in runs]]
                for w in [[r["metrics"][name] for r in runs
                           if not r["first_of_tree"]]]}
    print("TABLE " + json.dumps(table), flush=True)
    with open(os.path.join(out, f"{args.cell}.pairs.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    bad = [r["run"] for r in rows if r.get("correct") is not True]
    print(f"runs {len(rows)}, not correct or without a result: {bad}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

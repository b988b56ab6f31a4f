"""`import.apply` from inside, in one run of any cell: the five metrics
of `perfbench/apply_split.py`, how much of `import.apply` its three
children cover tick by tick, and what the rows cost the flight
recorder's budget.

  PYTHONHASHSEED=0 python3 perfbench/study/apply_budget.py \
      --workload fanin32_global_1chip.fleet_1k --seed 7 --seconds 51 \
      --trace 0 [--recorder-off]

The arguments are run.py's (`--ticks-out` and `--trace 1` among them)
and the result line is printed as ever; the table follows it, over the
run's timed ticks: per tier the most slots a tick used of
`flight_recorder_max_phases` and the phases dropped; for the global
the rows of each `import.*` kind in its fullest tick, the rows the
engine's log handed over of each `import.apply.*` name against those
the tick kept (`TickRecord.graft` folds the last rows of the most
numerous name where the tick is short of slots: a name's seconds stay
exact, the folded rows' edges go) and the share of those names' seconds
that sat in folded rows. It works under either driver (`phase_budget.py`
knows the two-tier one alone). `--recorder-off` runs the cell with
`flight_recorder: false` on every server, for the cost of the
recorder: no phase, no table, the end-to-end line only. The
environment variable is the one the deployment files state: with it
set run.py does not re-execute itself, and this wrapper stays.
"""

from __future__ import annotations

import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

READ = ("import.apply_ms", "import.land_ms", "import.apply_decode_ms",
        "import.apply_lock_wait_ms", "import.apply_stage_ms",
        "import.apply_cpu_share", "import.sketch_us")


def spread(values):
    return (f"{min(values):.4g} / {statistics.median(values):.4g} / "
            f"{max(values):.4g}")


def main(argv):
    if os.environ.get("PYTHONHASHSEED") != "0":
        raise SystemExit("apply_budget: run with PYTHONHASHSEED=0")
    from perfbench import harness, layers, run
    from perfbench.apply_split import split
    from veneur_tpu.models.pipeline import APPLY_PHASES
    from veneur_tpu.observe import StampLog
    argv = list(argv)
    recorder_off = "--recorder-off" in argv
    if recorder_off:
        argv.remove("--recorder-off")
        build = harness.build_server
        harness.build_server = lambda cfg, tier, extra, sink, rehearsal: \
            build(cfg, tier, {**extra, "flight_recorder": False}, sink,
                  rehearsal)
    seen, taken = [], []
    take = StampLog.take

    def taking(self):
        rows = take(self)
        taken.extend(rows)
        return rows

    StampLog.take = taking
    load_driver = harness.load_driver

    def wrapped_driver(cfg, root=harness.HERE):
        mod = load_driver(cfg, root)
        inner = mod.Driver.tick

        def tick(self, *a, **kw):
            del taken[:]
            rec = inner(self, *a, **kw)
            row = {"rec": rec}
            for tier in ("local", "global"):
                srv = getattr(self, tier[0] + "srv", None)
                t = None if srv is None or srv.flight is None \
                    else srv.flight.last_tick()
                if t is not None:
                    names = [n for n, _t0, _t1, _p in t.phases()]
                    row[tier] = (t.n, t.dropped, srv.flight.max_phases,
                                 {k: names.count(k) for k in sorted(
                                     set(names)) if k.startswith("import.")})
            handed = {n: sorted((t0, t1) for m, t0, t1 in taken if m == n)
                      for n in APPLY_PHASES}
            kept = row.get("global", (0, 0, 0, {}))[3]
            folded = sum(t1 - t0 for n, rows in handed.items()
                         for t0, t1 in rows[kept.get(n, 0):])
            total = sum(t1 - t0 for rows in handed.values()
                        for t0, t1 in rows)
            row["handed"] = {n: len(rows) for n, rows in handed.items()}
            row["folded_share"] = folded / total if total else 0.0
            seen.append(row)
            return rec

        mod.Driver.tick = tick
        return mod

    harness.load_driver = wrapped_driver
    rc = run.main(argv)
    timed = [r for r in seen if r["rec"].get("timed")]
    if recorder_off or not timed:
        return rc
    for tier in ("local", "global"):
        rows = [r[tier] for r in timed if tier in r]
        if rows:
            full = max(rows, key=lambda r: r[0])
            print(f"apply_budget {tier}: {len(rows)} timed ticks, most "
                  f"slots used {full[0]} of {full[2]}, dropped "
                  f"{sum(r[1] for r in rows)}; fullest tick {full[3]}",
                  flush=True)
    fullest = max(timed, key=lambda r: r.get("global", (0,))[0])
    print(f"apply_budget rows of import.apply.* the engine's log handed "
          f"over in the fullest tick {fullest['handed']}; share of their "
          f"seconds in rows the tick folded, min / median / max over the "
          f"ticks: {spread([r['folded_share'] for r in timed])}",
          flush=True)
    ticks = [r["rec"] for r in timed]
    cover = []
    for t in ticks:
        s = split(t)
        run_s = harness.phase_seconds(t["phase_rows"]).get(
            "global:import.apply")
        if s and run_s:
            cover.append(100.0 * sum(s.values()) / run_s)
    if cover:
        print(f"apply_budget decode + lock_wait + stage over import.apply, "
              f"%, min / median / max of {len(cover)} ticks: "
              f"{spread(cover)}", flush=True)
    ctx = {"ticks": ticks, "trace": None, "device": {}, "run": {}}
    print("apply_budget metrics: " + "  ".join(
        f"{name} {layers.read_metric(name, ctx)}" for name in READ),
        flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

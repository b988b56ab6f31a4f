"""How full the flight recorder's ticks are: one run of a cell, as
`perfbench/run.py` makes it, with the phase slots each tier's flush tick
used and dropped read off the servers after every tick.

  PYTHONHASHSEED=0 python3 perfbench/study/phase_budget.py \
      --workload two_tier_1chip.wide_100k --seed 7 --seconds 51 --trace 0

The arguments are run.py's. The result line is printed as ever; the
table follows it: per tier the most slots a timed tick used of the
recorder's budget (`flight_recorder_max_phases`), the phases dropped,
the rows of each per-chunk, per-request or per-dispatch kind in the
fullest tick, the pump's dispatches before the tick folds them into its
free slots, and how much of
`forward.send` no child phase covers. The environment
variable is the one the deployment files state (`assumed.process.env`):
with it set run.py does not re-execute itself, and this wrapper stays.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

COUNTED = ("forward.chunk.build", "egress.attempt", "ingest.pump.batch",
           "import.decode", "import.apply", "import.land")


def unstamped(phases):
    """Milliseconds of `forward.send` no child phase covers, by where
    they lie: {"(start)>forward.export": ms, "egress.attempt>(end)":
    ms, "<child>><next child>": ms summed over the chunks}."""
    send = [(t0, t1) for n, t0, t1, _p in phases
            if n == "forward.send" and t1 > t0]
    if not send:
        return None
    s0, s1 = send[0]
    kids = sorted((t0, t1, n) for n, t0, t1, _p in phases
                  if (n.startswith("forward.") or n == "egress.attempt")
                  and n != "forward.send" and s0 <= t0 and 0 < t1 <= s1)
    edges = [(s0, s0, "(start)")] + kids + [(s1, s1, "(end)")]
    out = {"forward.send": round((s1 - s0) / 1e6, 3)}
    for a, b in zip(edges, edges[1:]):
        key = f"{a[2]}>{b[2]}"
        out[key] = round(out.get(key, 0.0) + max(0, b[0] - a[1]) / 1e6, 3)
    return out


def main(argv):
    if os.environ.get("PYTHONHASHSEED") != "0":
        raise SystemExit("phase_budget: run with PYTHONHASHSEED=0")
    from perfbench import harness, run
    from veneur_tpu.observe import StampLog
    from veneur_tpu.server import Server
    seen, taken = [], []
    two_tier = harness.load_code("drivers", "two_tier")
    inner = two_tier.Driver.tick
    take = StampLog.take

    def counting_take(self):
        rows = take(self)
        taken.append((self, len(rows)))
        return rows

    StampLog.take = counting_take

    def tick(self, *a, **kw):
        del taken[:]
        rec = inner(self, *a, **kw)
        # the pump's own log, before the tick folds it into its slots
        row = {"pump": sum(n for log, n in taken
                           if log is self.lsrv.native_pump.stamps)}
        for tier, srv in (("local", self.lsrv), ("global", self.gsrv)):
            t = srv.flight.last_tick()
            names = [n for n, _t0, _t1, _p in t.phases()]
            row[tier] = (t.n, t.dropped, srv.flight.max_phases,
                         {k: names.count(k) for k in COUNTED
                          if k in names})
            if tier == "local":
                row["send"] = unstamped(t.phases())
        seen.append(row)
        return rec

    two_tier.Driver.tick = tick
    harness.load_driver = lambda cfg, root=harness.HERE: two_tier
    rc = run.main(argv)
    for tier in ("local", "global"):
        rows = [r[tier] for r in seen]
        if not rows:
            continue
        full = max(rows)
        print(f"phase_budget {tier}: {len(rows)} ticks, most slots used "
              f"{full[0]} of {full[2]}, dropped "
              f"{sum(r[1] for r in rows)}; fullest tick {full[3]}",
              flush=True)
    print(f"phase_budget pump dispatches stamped a tick, before the tick "
          f"folds them into its free slots (the log holds "
          f"{Server.GRAFT_BUDGET['ingest.pump.batch']}): "
          f"{[r['pump'] for r in seen]}", flush=True)
    sends = [r["send"] for r in seen if r.get("send")]
    if sends:
        print("phase_budget forward.send, ms no child covers, last "
              f"three ticks: {sends[-3:]}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

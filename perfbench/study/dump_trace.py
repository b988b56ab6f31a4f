"""Look at one trace by hand: planes, lines, the names on each line with
their total time. `python3 perfbench/study/dump_trace.py <trace dir> <out.json>`
also writes a few hundred of the flattened rows
(perfbench.tracered.load_xplane) of the first tick: the form in which
the reduction's test keeps a recorded trace."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(log_dir, out):
    import jax

    from perfbench import tracered
    path = tracered.find_xplane(log_dir)
    print(path, os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            tot, n, stats = {}, 0, None
            for ev in line.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns / 1e9
                if stats is None:
                    stats = {k: str(v)[:200] for k, v in ev.stats}
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {line.name!r}: {n} events, {len(tot)} names")
            print(f"    first event stats: {stats}")
            for name, s in top:
                print(f"    {s:9.4f}s  {name[:150]}")
    rows = tracered.load_xplane(path)
    sync = [r for r in rows["host"] if r[0] == tracered.SYNC]
    t0 = sync[0][1] if sync else 0.0
    flush = [r for r in rows["host"] if r[0] == "bench.local_flush"]
    f0 = flush[0][1] if flush else t0
    end = max((r[1] + r[2] for r in rows["host"][:12]), default=t0)

    def keep(ev):
        head = [r for r in ev if r[1] >= t0][:300]
        in_flush = [r for r in ev if r[1] >= f0][:300]
        kernel = [r for r in ev if "stats" in r[0] and r[1] < end][:8]
        return sorted(head + in_flush + kernel, key=lambda r: r[1])

    small = {"host": rows["host"][:12],
             "device": {str(d): keep(ev) for d, ev in rows["device"].items()},
             "modules": {str(d): [r for r in ev if t0 <= r[1] < end][:200]
                         for d, ev in rows["modules"].items()}}
    with open(out, "w") as f:
        json.dump(small, f)
    print("rows written:", {d: len(v) for d, v in small["device"].items()},
          len(small["host"]))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])

"""import.sketch_us: what the global's import costs a forwarded sketch,
from inside.

1e6 x the seconds of the phases `global:import.apply.decode` and
`.stage` of a tick over the sketches its engine applied
(`import_metrics` of the tick's `flush_path.global`): decode, key
lookup, staging and the landings that fall inside a batch, without the
worker's queue and the runs' edges. The unit the records argue in
(20.6 us a sketch behind a fleet, 22 on the mesh), comparable across
cells. The median over the timed ticks that applied a batch; a program
without the phases gives nothing to read (`perfbench/apply_split.py`).
"""

import statistics

from perfbench.apply_split import global_info, split


def read(ctx):
    costs = []
    for t in ctx["ticks"]:
        s, sketches = split(t), global_info(t).get("import_metrics")
        if s and sketches:
            costs.append(1e6 * (s["decode"] + s["stage"]) / sketches)
    return float(statistics.median(costs)) if costs else None

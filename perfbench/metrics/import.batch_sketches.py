"""import.batch_sketches: forwarded sketches per import batch the
global's engine applied in a tick.

`AggregationEngine._last_flush_info["import_metrics"]` over
`["import_batches"]`, which the tick record copies into
`flush_path.global`: the engine counts, under the lock hold that
stages a batch, the batches `import_list` applied since its last flush
and the metrics in them. A request travels to an engine as one batch,
so this is the requests' size (thousands); a program that hands
sketches over one by one reads 1. The median over the timed ticks that
applied a batch; a program without the counter leaves the metric out.
"""

import statistics


def read(ctx):
    sizes = []
    for t in ctx["ticks"]:
        info = t.get("flush_path", {}).get("global", {})
        batches = info.get("import_batches")
        if batches:
            sizes.append(info.get("import_metrics", 0) / batches)
    return float(statistics.median(sizes)) if sizes else None

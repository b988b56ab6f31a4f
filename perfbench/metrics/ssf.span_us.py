"""ssf.span_us: what the bridge's stream reader spends on a span.

1e6 x the seconds of the phase `local:ingest.ssf.read` of a tick (the
stream readers' time inside `handle_ssf` and staging, which the pump
stamps once a tick from the bridge's tally) over the spans the bridge
was given in it (`counters["ssf.spans"]`: those it staged and those it
handed to the fallback). Decode, tag formatting, interning and the
push into the rings; not the socket read, not the pump. The median over
the timed ticks; a program that reads the stream in Python stamps no
such phase, and there is nothing to read.
"""

import statistics

from perfbench.harness import phase_seconds

PHASE = "local:ingest.ssf.read"


def read(ctx):
    costs = []
    for t in ctx["ticks"]:
        took = phase_seconds(t["phase_rows"]).get(PHASE)
        spans = t["counters"].get("ssf.spans")
        if took is not None and spans:
            costs.append(1e6 * took / spans)
    return float(statistics.median(costs)) if costs else None

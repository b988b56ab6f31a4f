"""keys.slot_fill: how full the local tier's fullest bank of slots gets
within a tick.

A bank's table only grows while an interval runs and gives slots back
at its flush, so its high-water mark is what it held when the flush
began: the keys it holds after the flush plus the keys the flush
evicted (`keys_live` + `keys_evicted` of the engine's flush info, by
bank, in the tick record's `flush_path.local`). 100 x that over the
bank's slots (the deployment file's `tpu_<bank>_slots`), the largest of
the four banks; the median over the timed ticks. At 100 the next new
key is dropped and counted (`drops_no_slot`); a shorter
`tpu_slot_idle_ttl_intervals` lowers it and interns the keys that come
back again. A program that keeps no such counts leaves the metric out.
"""

import statistics

SLOTS = ("tpu_histogram_slots", "tpu_counter_slots", "tpu_gauge_slots",
         "tpu_set_slots")       # in the flush info's order of banks


def read(ctx):
    common = ctx["config"]["common"]
    fills = []
    for t in ctx["ticks"]:
        info = t.get("flush_path", {}).get("local", {})
        live, evicted = info.get("keys_live"), info.get("keys_evicted")
        if live and evicted and all(k in common for k in SLOTS):
            fills.append(max(100.0 * (n + e) / common[k]
                             for n, e, k in zip(live, evicted, SLOTS)))
    return float(statistics.median(fills)) if fills else None

"""The driver `zipf_two_tier`: `two_tier`'s two servers, send, settle and
tick, for a mix whose keys come and go (`generators/dogstatsd_zipf.py`).

What it adds to the tick record, from the program's own counts
(`AggregationEngine._last_flush_info`, which `two_tier.tick` copies into
`flush_path`: `keys_interned`, `keys_evicted`, `keys_live`, each `[histo,
counter, gauge, set]`: the keys a bank's table minted in the interval,
evicted at its flush and holds after it): their sums over a tier's
banks, `counters["keys.interned.<tier>"]`, `["keys.evicted.<tier>"]` and
`["keys.live.<tier>"]`. The by-bank counts stay where the program put
them. A program that keeps no such counts cannot run the deployment,
whose guarantees name them: the driver says so and the run ends before
any server starts.

`check` replaces `two_tier`'s:

  * `reference.check_tick` for everything exact (weighted timer counts,
    f32 extremes, counter totals with `@rate`, last-written gauges, row
    counts) and `pct_outside`; the reference's `hot` is empty, so no
    percentile is compared by value;
  * the set estimates in two parts, because the mix's sets run from 4
    members to 4,008: a set of at least `SMALL_SET` members in the
    relative gap `check_tick` computes (`worst_set_rel`, here over
    those sets alone), a smaller one in members
    (`worst_small_set_off`): a dense HLL loses a member where two
    share a register, one member of nine is 11%, and a relative limit
    wide enough for that would hold no large set to anything;
  * p50 and p99 **in rank**: the global's answer for a timer key of at
    least `RANKED_P50` lines a tick is placed among that key's sorted
    samples (`place`), and the distance of its rank from 0.50 is the
    number compared (`worst_p50_rank`); p99 likewise for a key of at
    least `RANKED_P99` (`worst_p99_rank`). At lognormal sigma 0.5 the
    same rank error is five times the value error it is at 0.1, so a
    limit in value would measure the mix and not the digest;
  * the key tables against `dogstatsd_zipf.KeyLedger`, one ledger a
    tier, every tick of the run, the first included:
    `keys_interned_mismatch`, `keys_evicted_mismatch` (keys off, summed
    over banks and tiers; limit 0; what a table holds is what it minted
    less what it evicted, so two counts held from the first tick hold
    the third). A ledger is fed the keys the tick touched at its tier:
    the payload's, and the tier's own `veneur.*` timers (its flush
    tick's phases, the fleet's latency), which a server feeds itself
    and the seed cannot know. Their names are data of the deployment
    file (`guarantees.own_timers`, by tier), never read off the program
    under test: a flush feeds them into the interval after it, so every
    tick of a run but the first touches them all. The names a tier's
    sink emits (a timer key emits one `.count` row) are held to the
    list beside the counts (`own_timers_mismatch`, names off, limit
    0). What a tick should mint and a flush evict follows from those
    keys, the deployment's idle TTL and the interners' documented rule,
    never from a count the program gives. The ledgers live in the
    driver from tick to tick: `check` is called once a tick, in order.

`failed` counts the weighted timer lines the emitted counts do not
account for, and the lines the bridge lost.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference
from perfbench.drivers import two_tier
from perfbench.generators.dogstatsd_zipf import KeyLedger

BANKS = ("histo", "counter", "gauge", "set")   # `_last_flush_info`'s order
KINDS = ("interned", "evicted")      # compared; `live` follows from them
SMALL_SET = 100     # members: below it an estimate is held in members
OWN_TIMERS = "veneur."     # the self-metrics' prefix (observe/registry.py)


def place(samples: np.ndarray, v: float) -> float:
    """The rank of `v` among sorted `samples`, in [0, 1]: the share of
    samples below it, a sample equal to it counted half."""
    lo = np.searchsorted(samples, v, side="left")
    hi = np.searchsorted(samples, v, side="right")
    return float(lo + hi) / (2.0 * samples.size)


def rank_gaps(ranked: dict, glob: dict) -> dict:
    """The worst distance of an emitted p50 / p99 from its rank, over
    the compared keys; a missing or non-finite answer misses whole."""
    worst = {}
    for name, (samples, p99_too) in ranked.items():
        for label, q in (("p50", 0.5), ("p99", 0.99)):
            if label == "p99" and not p99_too:
                continue
            v = reference.finite(glob.get(name + reference.pct_suffix(q)))
            gap = 1.0 if v is None else abs(place(samples, v) - q)
            worst[label] = max(worst.get(label, 0.0), gap)
    return worst


def set_gaps(sets: dict, glob: dict) -> dict:
    """The worst relative gap of an estimate over the sets of at least
    `SMALL_SET` members, and the worst gap in members over the smaller
    ones; a missing or non-finite answer misses whole."""
    worst = {}
    for name, distinct in sets.items():
        if distinct >= SMALL_SET:
            label, gap = "rel", reference.rel_gap(glob.get(name), distinct)
        else:
            v = reference.finite(glob.get(name))
            label, gap = "small", (distinct if v is None
                                   else abs(v - distinct))
        worst[label] = max(worst.get(label, 0.0), gap)
    return worst


def own_timer_keys(rows) -> set:
    """The tier's own timers a sink's rows name."""
    return {m.name[:-len(".count")] for m in rows
            if m.name.startswith(OWN_TIMERS) and m.name.endswith(".count")}


class Driver(two_tier.Driver):
    TAKES = "datagrams"
    OPS = "lines"

    def __init__(self, cfg: dict, rehearsal: bool):
        from veneur_tpu.ingest import native
        if not hasattr(native, "KEY_STATS"):
            raise SystemExit(
                "perfbench: this program keeps no count of the keys its "
                "tables mint and evict (ingest/native.py:KEY_STATS, "
                "`keys_interned` / `keys_evicted` / `keys_live` in the "
                f"engines' flush info); {cfg['name']!r} guarantees them "
                "tick by tick and cannot run on it")
        super().__init__(cfg, rehearsal)
        ttl = cfg["common"]["tpu_slot_idle_ttl_intervals"]
        self.ledgers = {"local": KeyLedger(ttl), "global": KeyLedger(ttl)}

    def tick(self, payload: dict, ts: int, spans, gcm, meter) -> dict:
        rec = super().tick(payload, ts, spans, gcm, meter)
        rec["counters"].update({
            f"keys.{kind}.{tier}": int(sum(info.get("keys_" + kind, ())))
            for tier, info in rec["flush_path"].items()
            for kind in (*KINDS, "live")})
        return rec

    def check(self, payload: dict, rec: dict, tol: dict) -> dict:
        rows = {"local": self.lsink.take(), "global": self.gsink.take()}
        local = reference.sink_values(rows["local"])
        glob = reference.sink_values(rows["global"])
        how = (self.cfg.get("control") or {}).get("answers")
        if how:
            local = reference.degrade(local, how)
            glob = reference.degrade(glob, how)
        lost = rec["counters"]["bridge.lost_lines"]
        ref = payload["ref"]
        v = reference.check_tick(ref, local, glob, tol)
        numbers = v["numbers"]
        numbers["bridge.lost_lines"] = (float(abs(lost)), 0.0)
        sets = set_gaps(ref["set"], glob)
        del numbers["worst_set_rel"]
        if "rel" in sets:
            numbers["worst_set_rel"] = (sets["rel"], tol["set"])
        if "small" in sets:
            numbers["worst_small_set_off"] = (sets["small"],
                                              tol["set_small"])
        gaps = rank_gaps(ref["ranked"], glob)
        for label in ("p50", "p99"):
            if label in gaps:
                numbers[f"worst_{label}_rank"] = (gaps[label],
                                                  tol[label + "_rank"])
        off = dict.fromkeys(KINDS, 0)
        own, own_off = self.cfg["guarantees"]["own_timers"], 0
        for tier, ledger in self.ledgers.items():
            # a flush feeds its own timers into the interval after it
            listed = own[tier] if ledger.interval else []
            emitted = own_timer_keys(rows[tier])
            own_off += len(emitted ^ set(listed))
            for name in sorted(emitted ^ set(listed))[:3]:
                v["mismatches"].append(
                    f"{tier}: own timer {name} "
                    + ("not listed" if name in emitted else "not emitted"))
            touched = dict(payload["keys"][tier])
            touched["histo"] = touched["histo"].tolist() + listed
            want = ledger.tick(touched)
            info = rec["flush_path"][tier]
            for kind in KINDS:
                got = info.get("keys_" + kind) or [None] * len(BANKS)
                for bank, n in zip(BANKS, got):
                    if n != want[kind][bank]:
                        off[kind] += (abs(n - want[kind][bank])
                                      if n is not None else 1)
                        if len(v["mismatches"]) < 12:
                            v["mismatches"].append(
                                f"{tier}: keys {kind} in the {bank} bank "
                                f"{n!r}, want {want[kind][bank]}")
        for kind in KINDS:
            numbers[f"keys_{kind}_mismatch"] = (float(off[kind]), 0.0)
        numbers["own_timers_mismatch"] = (float(own_off), 0.0)
        v["attempted"] = payload["n_lines"]
        v["failed"] = (payload["timer_lines"] - v.pop("accounted_lines")
                       + max(0, lost))
        return v

"""The generator `dogstatsd_zipf`: a production DogStatsD mix for a tier
that listens on UDP. Mostly counters and gauges, keys that follow a Zipf
law, a hot set that moves from tick to tick, `@rate` on the hot names.

A mix (`perfbench/mixes/<name>.json`) gives, per kind, the lines a tick
sends (`lines`), the law's exponent (`zipf_s`, 1.0), the share of a
kind's touched ranks whose key moves (`moving_share`) and how many
payloads a run cycles through (`distinct_ticks`); a deployment
(`perfbench/configs/<name>.json`) the population the keys are drawn
from. Within a kind, rank r of the kind's ranks takes

    n_r = floor(N_kind * r^-s / H + 0.5)        H = sum of r^-s

lines; a rank of no line is not touched. The ranks are the kind's
population, except the sets', which are the mix's `sets.touched`. Sizes
and counts follow from the mix and the deployment alone; the seed picks
which key stands on which rank, every value and the send order, never
an amount of work. The same seed gives the same datagrams byte for byte.

**The hot set moves.** Of a kind's touched ranks every tenth (rank
index 9, 19, ...: hot and cold alike) takes a key that only this
payload has; the other nine tenths keep one key in every payload. Sets
do not move.

**Kinds.** Counters: integers 1-999; even names mixed scope, odd names
`veneurglobalonly`; every third name carries one of `counters.rates`
(`@0.5`, `@0.25`, `@0.125`: `1 / rate` is exact in f32 and a total is an
integer). Gauges: thousandths, no `@`. Timers: even names `|ms`, odd
names `|h`; lognormal latencies in integer thousandths; the first
`timers.rated_ranks` ranks carry one of `timers.rates`, one rate a key,
so a key's count is its lines times a power of two and its quantiles
are its samples'. Sets: distinct members, a share of each set's lines
resent.

`reference(payload)` is numpy over the generated samples and imports
nothing of the program: what the two tiers must emit for one tick, each
series where veneur's scoping emits it, in the form
`perfbench/reference.py:check_tick` holds a tick's answers against
(`hot` left empty: no percentile is compared by value), and beside it
under `ranked` the sorted samples of every timer key of at least
`RANKED_P50` lines (and which of them have `RANKED_P99`), for a
comparison in rank. `KeyLedger` is the plain reference of the key
tables: which keys a tick mints into a slot and which a flush evicts,
from the keys a tick touches, the idle TTL and the rule both interners
document (`models/worker.py:KeyInterner.advance_interval`,
`native/vtpu_ingest.cpp:vtpu_advance_interval`): at a flush the
interval number goes up by one, and a key last touched before
`interval - ttl` gives its slot back.

Line packing is `dogstatsd_lines.datagrams`; timer names and tags are
`dogstatsd_lines`'s.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench.generators import dogstatsd_lines as lines

MAKES = "datagrams"

KINDS = ("counters", "timers", "gauges", "sets")
RANKED_P50 = 200      # a timer key of this many lines: p50 compared in rank
RANKED_P99 = 1000     # and of this many: p99 too


def ladder(n_lines: int, ranks: int, s: float) -> np.ndarray:
    """`n_r` for the touched ranks of a kind, in rank order."""
    r = np.arange(1, ranks + 1, dtype=np.float64) ** -float(s)
    n = np.floor(n_lines * r / r.sum() + 0.5).astype(np.int64)
    return n[n > 0]


def moving_ranks(touched: int, share: float) -> np.ndarray:
    """The rank indices whose key moves: the last of every `1 / share`."""
    if not share:
        return np.zeros(0, np.int64)
    step = int(round(1.0 / share))
    return np.arange(touched)[step - 1::step]


def key_plan(mix: dict, population: dict, seed: int) -> dict:
    """kind -> {"n": lines a rank, "keys": [payload][rank] -> key id,
    "moving": rank indices}. A kind's keys are a seeded draw without
    replacement from its population: the touched ranks' first, then each
    later payload's own keys for the moving ranks. A name's parity says
    where a counter is scoped and what type a timer is, so a rank takes
    an even or an odd name by its index (`(i + i // 10) % 2`: the moving
    ranks alternate too) and how many keys reach the global is the same
    for every seed."""
    rng = np.random.default_rng([seed, 99])
    pop = {"counters": population["counters"],
           "timers": population["timer_keys"],
           "gauges": population["gauges"],
           "sets": population["set_keys"]}
    plan = {}
    for kind in KINDS:
        ranks = mix["sets"]["touched"] if kind == "sets" else pop[kind]
        n = ladder(mix["lines"][kind], ranks, mix["zipf_s"])
        share = 0.0 if kind == "sets" else mix["moving_share"]
        moving = moving_ranks(len(n), share)
        odd = (np.arange(len(n)) + np.arange(len(n)) // 10) % 2 == 1
        keys = [np.empty(len(n), np.int64)
                for _ in range(mix["distinct_ticks"])]
        for parity in (0, 1):
            of = np.flatnonzero(odd == bool(parity))
            mov = np.flatnonzero(np.isin(of, moving))
            need = len(of) + (len(keys) - 1) * len(mov)
            names = np.arange(parity, pop[kind], 2)
            if need > len(names):
                raise ValueError(f"mix draws {need} {kind} of {len(names)} "
                                 f"names of that parity")
            draw = names[rng.permutation(len(names))[:need]]
            for k, mine in enumerate(keys):
                mine[of] = draw[:len(of)]
                if k:
                    at = len(of) + (k - 1) * len(mov)
                    mine[of[mov]] = draw[at:at + len(mov)]
        plan[kind] = {"n": n, "keys": keys, "moving": moving}
    return plan


def counter_name(k: int) -> str:
    return f"smoke.counter.c{k:06d}"


def gauge_name(k: int) -> str:
    return f"smoke.gauge.g{k:06d}"


def set_name(k: int) -> str:
    return f"smoke.set.s{k:04d}"


def counter_weights(keys: np.ndarray, rates: list) -> np.ndarray:
    """`1 / rate` of each counter name: every third name carries one of
    `rates`, by turns; the others none."""
    w = np.ones(keys.size, np.float64)
    rated = keys % 3 == 0
    w[rated] = 1.0 / np.asarray(rates, np.float64)[
        (keys[rated] // 3) % len(rates)]
    return w


class Payload:
    """One tick's traffic with everything the numpy reference needs:
    every line's key and value, its weight, and where the seeded
    shuffle sends it."""

    def __init__(self, mix: dict, plan: dict, seed: int, index: int):
        rng = np.random.default_rng([seed, index])
        self.mix, self.index = mix, index
        k = index - 1

        c = plan["counters"]
        self.c_key = np.repeat(c["keys"][k], c["n"])
        self.c_val = rng.integers(1, 1000, self.c_key.size)
        self.c_wt = counter_weights(self.c_key, mix["counters"]["rates"])

        t = plan["timers"]
        self.t_key = np.repeat(t["keys"][k], t["n"])
        dist = mix["timers"]["distribution"]
        if dist["kind"] != "lognormal":
            raise ValueError(f"unknown distribution {dist['kind']!r}")
        self.t_milli = np.maximum(1, np.rint(rng.lognormal(
            np.log(dist["median_ms"]), dist["sigma"], self.t_key.size)
            * 1000.0)).astype(np.int64)
        rates = np.asarray(mix["timers"]["rates"], np.float64)
        by_rank = np.ones(len(t["n"]), np.float64)
        rated = min(mix["timers"]["rated_ranks"], len(by_rank))
        by_rank[:rated] = 1.0 / rates[np.arange(rated) % len(rates)]
        self.t_wt = np.repeat(by_rank, t["n"])

        g = plan["gauges"]
        self.g_key = np.repeat(g["keys"][k], g["n"])
        self.g_milli = rng.integers(0, 10_000_000, self.g_key.size)

        # sets: a set's lines are distinct members and, of a share of
        # them, one more sending of a member it already has
        s = plan["sets"]
        n_dup = (s["n"] * mix["sets"]["resent_share"]).astype(np.int64)
        n_new = s["n"] - n_dup
        first = np.cumsum(n_new) - n_new          # a set's first member
        member = index * 10_000_000 + np.arange(int(n_new.sum()),
                                                dtype=np.int64)
        again = (np.repeat(first, n_dup)
                 + (rng.random(int(n_dup.sum()))
                    * np.repeat(n_new, n_dup)).astype(np.int64))
        self.s_key = np.concatenate([np.repeat(s["keys"][k], n_new),
                                     np.repeat(s["keys"][k], n_dup)])
        self.s_member = np.concatenate([member, member[again]])

        # the send order: one seeded shuffle of the whole tick; a
        # line's place says which write of a gauge is the last
        sizes = [self.c_key.size, self.t_key.size, self.g_key.size,
                 self.s_key.size]
        self.n_lines = int(sum(sizes))
        place = np.empty(self.n_lines, np.int64)
        place[rng.permutation(self.n_lines)] = np.arange(self.n_lines)
        edges = np.cumsum([0] + sizes)
        (self.c_at, self.t_at, self.g_at,
         self.s_at) = (place[a:b] for a, b in zip(edges, edges[1:]))

    def lines(self) -> list:
        """The DogStatsD text in send order."""
        def dec(m):
            return f"{m // 1000}.{m % 1000:03d}"

        def rate(w):
            return "" if w == 1.0 else f"|@{1.0 / w:g}"

        out = [None] * self.n_lines
        for at, k, v, w in zip(self.c_at.tolist(), self.c_key.tolist(),
                               self.c_val.tolist(), self.c_wt.tolist()):
            out[at] = (f"{counter_name(k)}:{v}|c{rate(w)}|#env:prod"
                       + (",veneurglobalonly" if k % 2 else ""))
        head = {int(k): f"{lines.timer_name(int(k))}:%s|"
                        f"{'h' if k % 2 else 'ms'}%s|#"
                        f"{lines.timer_tags(int(k))}"
                for k in np.unique(self.t_key)}
        for at, k, m, w in zip(self.t_at.tolist(), self.t_key.tolist(),
                               self.t_milli.tolist(), self.t_wt.tolist()):
            out[at] = head[k] % (dec(m), rate(w))
        for at, k, m in zip(self.g_at.tolist(), self.g_key.tolist(),
                            self.g_milli.tolist()):
            out[at] = f"{gauge_name(k)}:{dec(m)}|g|#env:prod,kind:gauge"
        for at, k, m in zip(self.s_at.tolist(), self.s_key.tolist(),
                            self.s_member.tolist()):
            out[at] = f"{set_name(k)}:m{m}|s|#env:prod"
        return out

    def keys(self) -> dict:
        """The key ids the tick touches, by kind."""
        return {"counters": np.unique(self.c_key),
                "timers": np.unique(self.t_key),
                "gauges": np.unique(self.g_key),
                "sets": np.unique(self.s_key)}


def reference(p: Payload, percentiles) -> dict:
    ref = {"timer": {}, "hot": {}, "ranked": {}, "counter_local": {},
           "counter_global": {}, "gauge": {}, "set": {},
           "percentiles": tuple(percentiles)}
    val64 = p.t_milli / 1000.0                 # == strtod("123.456")
    order = np.argsort(p.t_key, kind="stable")
    keys, starts = np.unique(p.t_key[order], return_index=True)
    ends = np.append(starts[1:], order.size)
    v32, v64 = val64.astype(np.float32)[order], val64[order]
    counts = np.add.reduceat(p.t_wt[order], starts)
    mins = np.minimum.reduceat(v32, starts)
    maxs = np.maximum.reduceat(v32, starts)
    for k, a, b, n, lo, hi in zip(keys.tolist(), starts.tolist(),
                                  ends.tolist(), counts.tolist(),
                                  mins.tolist(), maxs.tolist()):
        name = lines.timer_name(k)
        ref["timer"][name] = (float(n), lo, hi)
        if b - a >= RANKED_P50:
            ref["ranked"][name] = (np.sort(v64[a:b]), b - a >= RANKED_P99)
    c_tot = np.bincount(p.c_key, weights=p.c_val * p.c_wt)
    for k in np.unique(p.c_key).tolist():
        side = "counter_global" if k % 2 else "counter_local"
        ref[side][counter_name(k)] = float(c_tot[k])
    # a gauge is its last write in send order
    by_place = np.argsort(p.g_at)
    g_key, g_milli = p.g_key[by_place][::-1], p.g_milli[by_place][::-1]
    last_keys, last_at = np.unique(g_key, return_index=True)
    for k, m in zip(last_keys.tolist(), g_milli[last_at].tolist()):
        ref["gauge"][gauge_name(k)] = float(np.float32(m / 1000.0))
    pairs = np.unique((p.s_key.astype(np.int64) << 40) | p.s_member)
    sk, n = np.unique(pairs >> 40, return_counts=True)
    for k, c in zip(sk.tolist(), n.tolist()):
        ref["set"][set_name(k)] = float(c)
    return ref


# which bank of a tier's engine a kind's keys live in, and which of a
# kind's keys reach the global: timers and sets are forwarded whole,
# counters where the name is global-only (odd), gauges never
BANKS = {"timers": "histo", "counters": "counter", "gauges": "gauge",
         "sets": "set"}


def tier_keys(keys: dict, tier: str) -> dict:
    """bank -> the key ids of `Payload.keys()` that tier's bank sees."""
    out = {BANKS[kind]: ids for kind, ids in keys.items()}
    if tier == "global":
        out["counter"] = out["counter"][out["counter"] % 2 == 1]
        out["gauge"] = out["gauge"][:0]
    return out


class KeyLedger:
    """The plain reference of one tier's four key tables. `tick` is
    handed the keys an interval touched, by bank (any hashable: ids of
    the payload, names of keys the tier feeds itself), and returns what
    the interval minted, what its flush evicts and what holds a slot
    after it, by bank."""

    def __init__(self, ttl: int):
        self.ttl = int(ttl)
        self.interval = 0
        self.last: dict = {}      # bank -> {key: interval last touched}

    def tick(self, touched: dict) -> dict:
        out = {"interned": {}, "evicted": {}, "live": {}}
        for bank, keys in touched.items():
            last = self.last.setdefault(bank, {})
            keys = keys.tolist() if hasattr(keys, "tolist") else list(keys)
            out["interned"][bank] = sum(1 for k in keys if k not in last)
            last.update(dict.fromkeys(keys, self.interval))
        self.interval += 1
        horizon = self.interval - self.ttl
        for bank, last in self.last.items():
            dead = ([k for k, at in last.items() if at < horizon]
                    if self.ttl > 0 and horizon >= 0 else [])
            for k in dead:
                del last[k]
            out["evicted"][bank] = len(dead)
            out["live"][bank] = len(last)
            out["interned"].setdefault(bank, 0)
        return out


def build(cfg: dict, mix: dict, seed: int, log) -> tuple:
    """Every datagram the run will send and what the tiers must answer,
    built during set-up: `distinct_ticks` payloads, cycled through by
    the window. The reference's seconds are kept apart: they are not
    set-up."""
    plan = key_plan(mix, cfg["population"], seed)
    dg = mix["datagram"]
    payloads, ref_s = [], 0.0
    for k in range(mix["distinct_ticks"]):
        p = Payload(mix, plan, seed, k + 1)
        grams = lines.datagrams(p.lines(), dg["max_lines"], dg["max_bytes"])
        r0 = time.monotonic()
        ref = reference(p, cfg["percentiles"])
        keys = p.keys()
        ref_s += time.monotonic() - r0
        payloads.append({
            "datagrams": grams, "n_lines": p.n_lines,
            # weighted: a timer line at @0.5 counts two
            "timer_lines": float(p.t_wt.sum()),
            "keys": {tier: tier_keys(keys, tier)
                     for tier in ("local", "global")},
            "ref": ref})
        log(f"payload {k + 1}: {p.n_lines} lines in {len(grams)} datagrams; "
            + ", ".join(f"{len(ids)} {kind}" for kind, ids in keys.items()))
    return payloads, ref_s

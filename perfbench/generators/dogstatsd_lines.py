"""The generator `dogstatsd_lines`: one general reader of the mix files
whose traffic is DogStatsD text for a tier that listens on UDP.

A mix (`perfbench/mixes/<name>.json`) is data: how many keys of each
kind a tick touches, how many samples each takes, the latency
distribution, how many distinct payloads a run cycles through. A
deployment (`perfbench/configs/<name>.json`) gives the population the
keys are drawn from. Everything else follows from `--seed`: which keys,
every value, the send order. The same seed gives the same datagrams,
byte for byte; another seed gives the same sizes and counts over other
keys and values, so the seed never changes the amount of work.

`reference(payload)` is numpy over the generated samples and imports
nothing of the program: what the two tiers must emit for one tick, each
series where veneur's scoping emits it (`perfbench/reference.py` holds
a tick's answers against it).

Copied out of `chip_smoke.py` (Window / touched_keys / datagrams /
reference) so that a later change to the smoke cannot move the
yardstick.
"""

from __future__ import annotations

import time

import numpy as np

MAKES = "datagrams"


def build(cfg: dict, mix: dict, seed: int, log) -> tuple:
    """Every datagram the run will send and what the tiers must answer,
    built during set-up: `distinct_ticks` payloads over the same keys
    with values of their own, cycled through by the window. The
    reference's seconds are kept apart: they are not set-up."""
    touched = touched_keys(mix, cfg["population"], seed)
    dg = mix["datagram"]
    payloads, ref_s = [], 0.0
    for k in range(mix["distinct_ticks"]):
        p = Payload(mix, touched, seed, k + 1)
        lines = p.lines()
        grams = datagrams(lines, dg["max_lines"], dg["max_bytes"])
        r0 = time.monotonic()
        ref = reference(p, cfg["percentiles"])
        ref_s += time.monotonic() - r0
        payloads.append({"datagrams": grams, "n_lines": len(lines),
                         "timer_lines": int(p.t_key.size), "ref": ref})
        log(f"payload {k + 1}: {len(lines)} lines in {len(grams)} datagrams")
    return payloads, ref_s


def timer_name(i: int) -> str:
    return f"smoke.timer.k{i:06d}"


def timer_tags(i: int) -> str:
    return f"env:prod,shard:{i % 64}"


def touched_keys(mix: dict, population: dict, seed: int) -> dict:
    """The keys every tick of the run touches: a seeded draw of the
    mix's count from the deployment's population, per kind. Hot timer
    keys are the first `hot_keys` of the draw's own seeded order."""
    rng = np.random.default_rng([seed, 99])

    def draw(n, of):
        if n > of:
            raise ValueError(f"mix touches {n} keys of a population of {of}")
        return rng.permutation(of)[:n]

    timers = draw(mix["timers"]["keys"], population["timer_keys"])
    return {"timers": np.sort(timers),
            "hot": np.sort(timers[:mix["timers"]["hot_keys"]]),
            "sets": np.sort(draw(mix["sets"]["keys"],
                                 population["set_keys"])),
            "counters": np.sort(draw(mix["counters"]["keys"],
                                     population["counters"])),
            "gauges": np.sort(draw(mix["gauges"]["keys"],
                                   population["gauges"]))}


class Payload:
    """One tick's traffic with everything the numpy reference needs:
    which keys it touches, every sample's value, the send order."""

    def __init__(self, mix: dict, touched: dict, seed: int, index: int):
        rng = np.random.default_rng([seed, index])
        self.mix, self.touched, self.index = mix, touched, index
        t = mix["timers"]

        # timers: value in integer thousandths, so the text on the wire
        # ("123.456"), the f64 the parser makes of it and the f32 the
        # bank keeps are all exactly reproducible from `milli`
        tk = touched["timers"]
        per_key = np.where(np.isin(tk, touched["hot"]),
                           t["hot_samples"], t["cold_samples"])
        self.t_key = np.repeat(tk, per_key)
        dist = t["distribution"]
        if dist["kind"] != "lognormal":
            raise ValueError(f"unknown distribution {dist['kind']!r}")
        self.t_milli = np.maximum(1, np.rint(rng.lognormal(
            np.log(dist["median_ms"]), dist["sigma"], self.t_key.size)
            * 1000.0)).astype(np.int64)
        order = rng.permutation(self.t_key.size)
        self.t_key, self.t_milli = self.t_key[order], self.t_milli[order]

        # sets: distinct members per set, plus a share resent
        s = mix["sets"]
        sk = touched["sets"]
        member = (index * 10_000_000
                  + np.arange(sk.size * s["members"], dtype=np.int64))
        self.s_key = np.repeat(sk, s["members"])
        n_dup = int(member.size * s["resent_share"])
        dup = rng.choice(member.size, n_dup, replace=False) if n_dup \
            else np.zeros(0, np.int64)
        self.s_key = np.concatenate([self.s_key, self.s_key[dup]])
        self.s_member = np.concatenate([member, member[dup]])
        order = rng.permutation(self.s_key.size)
        self.s_key, self.s_member = self.s_key[order], self.s_member[order]

        # counters (even names mixed scope, odd names global-only) and
        # gauges: a few integer samples each
        self.c_key = np.repeat(touched["counters"], mix["counters"]["samples"])
        self.c_val = rng.integers(1, 1000, self.c_key.size)
        self.g_key = np.repeat(touched["gauges"], mix["gauges"]["samples"])
        self.g_milli = rng.integers(0, 10_000_000, self.g_key.size)
        order = rng.permutation(self.g_key.size)
        self.g_key, self.g_milli = self.g_key[order], self.g_milli[order]

    def lines(self) -> list:
        """The DogStatsD text in send order: gauges and counters first,
        then timers with the sets spread evenly through them."""
        def dec(m):
            return f"{m // 1000}.{m % 1000:03d}"

        out = [f"smoke.gauge.g{k:04d}:{dec(m)}|g|#env:prod,kind:gauge"
               for k, m in zip(self.g_key.tolist(), self.g_milli.tolist())]
        out += [f"smoke.counter.c{k:04d}:{v}|c|#env:prod"
                + (",veneurglobalonly" if k % 2 else "")
                for k, v in zip(self.c_key.tolist(), self.c_val.tolist())]
        names = {int(k): f"{timer_name(int(k))}:%s|ms|#{timer_tags(int(k))}"
                 for k in self.touched["timers"]}
        timers = [names[k] % dec(m)
                  for k, m in zip(self.t_key.tolist(), self.t_milli.tolist())]
        sets = [f"smoke.set.s{k:04d}:m{m}|s|#env:prod"
                for k, m in zip(self.s_key.tolist(), self.s_member.tolist())]
        if not sets:
            return out + timers
        step = max(1, len(timers) // len(sets))
        merged, si = [], 0
        for i in range(0, len(timers), step):
            merged.extend(timers[i:i + step])
            if si < len(sets):
                merged.append(sets[si])
                si += 1
        merged.extend(sets[si:])
        return out + merged


def datagrams(lines: list, max_lines: int, max_bytes: int) -> list:
    """Pack lines into datagrams under `metric_max_length` (4096 is the
    UDP read size: a longer datagram is silently truncated)."""
    out, cur, size = [], [], 0
    for ln in lines:
        b = ln.encode()
        if cur and (len(cur) >= max_lines or size + len(b) + 1 > max_bytes):
            out.append(b"\n".join(cur))
            cur, size = [], 0
        cur.append(b)
        size += len(b) + 1
    if cur:
        out.append(b"\n".join(cur))
    return out


def reference(p, percentiles) -> dict:
    ref = {"timer": {}, "hot": {}, "counter_local": {},
           "counter_global": {}, "gauge": {}, "set": {},
           "percentiles": tuple(percentiles)}
    val64 = p.t_milli / 1000.0                 # == strtod("123.456")
    val32 = val64.astype(np.float32)
    order = np.argsort(p.t_key, kind="stable")
    keys, starts = np.unique(p.t_key[order], return_index=True)
    ends = np.append(starts[1:], order.size)
    v32, v64 = val32[order], val64[order]
    mins = np.minimum.reduceat(v32, starts)
    maxs = np.maximum.reduceat(v32, starts)
    hot = set(p.touched["hot"].tolist())
    for k, a, b, lo, hi in zip(keys.tolist(), starts.tolist(),
                               ends.tolist(), mins.tolist(), maxs.tolist()):
        ref["timer"][timer_name(k)] = (float(b - a), lo, hi)
        if k in hot:
            ref["hot"][timer_name(k)] = np.quantile(v64[a:b], percentiles)
    c_tot = np.bincount(p.c_key, weights=p.c_val.astype(np.float64))
    for k in np.unique(p.c_key).tolist():
        side = "counter_global" if k % 2 else "counter_local"
        ref[side][f"smoke.counter.c{k:04d}"] = float(c_tot[k])
    for k in np.unique(p.g_key).tolist():
        last = p.g_milli[np.nonzero(p.g_key == k)[0][-1]]
        ref["gauge"][f"smoke.gauge.g{k:04d}"] = float(
            np.float32(last / 1000.0))
    if p.s_key.size:
        pairs = np.unique((p.s_key.astype(np.int64) << 40) | p.s_member)
        sk, n = np.unique(pairs >> 40, return_counts=True)
        for k, c in zip(sk.tolist(), n.tolist()):
            ref["set"][f"smoke.set.s{k:04d}"] = float(c)
    return ref

"""The load generator: one general reader of traffic-mix files.

A mix (`perfbench/mixes/<name>.json`) is data: how many keys of each
kind a tick touches, how many samples each takes, the latency
distribution, how many distinct payloads a run cycles through. A
deployment (`perfbench/configs/<name>.json`) gives the population the
keys are drawn from. Everything else follows from `--seed`: which keys,
every value, the send order. The same seed gives the same datagrams,
byte for byte; another seed gives the same sizes and counts over other
keys and values, so the seed never changes the amount of work.

Copied out of `chip_smoke.py` (Window / touched_keys / datagrams) so
that a later change to the smoke cannot move the yardstick.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_data(kind: str, name: str, rehearsal: bool = False,
              root: str = HERE) -> dict:
    """`<root>/<kind>/<name>.json`; in a rehearsal the file's own
    `rehearsal` block overrides its groups key by key (tiny sizes)."""
    with open(os.path.join(root, kind, name + ".json")) as f:
        data = json.load(f)
    if rehearsal:
        for group, over in data.get("rehearsal", {}).items():
            data[group] = ({**data.get(group, {}), **over}
                           if isinstance(over, dict) else over)
    return data


def load_mix(name: str, rehearsal: bool = False, root: str = HERE) -> dict:
    return load_data("mixes", name, rehearsal, root)


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

"""The generator `dogstatsd_flows`: `dogstatsd_zipf`'s tick as a host of
many client processes sends it, each process a UDP flow of its own, into
a tier that reads with several `SO_REUSEPORT` sockets.

The lines are `dogstatsd_zipf`'s, letter for letter: the same mix keys
(`lines`, `zipf_s`, `moving_share`, `distinct_ticks`, the rates, the
sets), the same key plan, ladder, values and seeded shuffle, from the
same seed. What this generator adds is who sends which line and when,
from the mix's `flows` block:

  * `count` flows (client sockets, each with a source port of its own)
    over `threads` sender threads, flow f on thread `f // (count /
    threads)`;
  * a counter's, timer's and set's line goes to a flow by a seeded draw
    a line, so one key arrives from many processes and a new key
    reaches several readers at once; **a gauge key belongs to one
    flow**, the process that reports it, drawn a key;
  * **handover**: of the tick's gauge keys with at least two lines, in
    key order, every `handover_every`-th is handed over: a second,
    different flow writes the later half of its lines (a process
    replaced in mid-interval by a deploy: a new source port, so as like
    as not another reader);
  * the tick has two halves with a barrier between them (the driver
    waits until the tier has received every datagram of the first): a
    line is in the first half if its place in the seeded shuffle is in
    the first half of the tick, but a handed-over key's own lines are
    split in two by their places, the earlier `ceil(n / 2)` to the
    first half and its first flow, the rest to the second half and its
    second flow. So "later" is a fact the reference can hold;
  * within a half a flow sends its lines in their places' order, packed
    by `dogstatsd_lines.datagrams`; a thread sends its flows' datagrams
    by turns, the flows in a seeded order.

`reference` is `dogstatsd_zipf.reference` (numpy over the samples,
nothing of the program) with the gauges answered from the flows: a
gauge is the last write **in its flow's order**, and a handed-over
gauge the last write of its second flow. `first_writer` beside it is
what a handed-over gauge would read had the first flow's last write
won (the control `handover_first_writer` answers with it).
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from perfbench.generators import dogstatsd_lines as lines
from perfbench.generators import dogstatsd_zipf as zipf

MAKES = "flow_datagrams"


class Flows:
    """Who sends which line of a `dogstatsd_zipf.Payload`, and in which
    half of the tick: `flow` and `half` by the line's place in the
    seeded shuffle, and for the gauges `g_flow` / `g_half` by sample,
    `handed` (key id -> (first flow, second flow))."""

    def __init__(self, p: zipf.Payload, spec: dict, seed: int):
        rng = np.random.default_rng([seed, p.index, 7])
        n, count = p.n_lines, int(spec["count"])
        self.count, self.threads = count, int(spec["threads"])
        if count % self.threads:
            raise ValueError(f"{count} flows over {self.threads} threads")
        self.flow = rng.integers(0, count, n)
        self.half = (np.arange(n) >= (n + 1) // 2).astype(np.int64)

        # a gauge key's flow, and the handed-over keys' second flows
        keys, inverse, per_key = np.unique(
            p.g_key, return_inverse=True, return_counts=True)
        first = rng.integers(0, count, keys.size)
        twice = np.flatnonzero(per_key >= 2)
        handed = twice[int(spec["handover_every"]) - 1::
                       int(spec["handover_every"])]
        second = (first[handed] + 1
                  + rng.integers(0, count - 1, handed.size)) % count
        self.g_flow = first[inverse]
        self.g_half = self.half[p.g_at].copy()
        # a handed-over key's lines in their places' order: the earlier
        # ceil(n / 2) stay with the first flow in the first half
        order = np.lexsort((p.g_at, inverse))
        rank = np.arange(order.size) - np.repeat(
            np.cumsum(per_key) - per_key, per_key)
        late = np.zeros(p.g_key.size, bool)
        late[order] = rank >= np.repeat((per_key + 1) // 2, per_key)
        is_handed = np.zeros(keys.size, bool)
        is_handed[handed] = True
        moved = is_handed[inverse]
        self.g_half[moved] = late[moved]
        to_second = np.zeros(keys.size, np.int64)
        to_second[handed] = second
        self.g_flow = np.where(moved & late, to_second[inverse],
                               self.g_flow)
        self.flow[p.g_at] = self.g_flow
        self.half[p.g_at] = self.g_half
        self.handed = {int(k): (int(a), int(b)) for k, a, b in zip(
            keys[handed].tolist(), first[handed].tolist(), second.tolist())}
        # the order a thread's flows take their turns in
        per = count // self.threads
        self.turns = [(t * per + rng.permutation(per)).tolist()
                      for t in range(self.threads)]


def gauge_answers(p: zipf.Payload, fl: Flows) -> tuple:
    """({name: the gauge's last write}, {name: a handed-over gauge's
    first flow's last write}). A flow sends its first-half lines, then
    its second-half lines, each in their places' order: `when` is a
    line's place in its own flow's sending. The last write of a key is
    its latest line on the flow that holds the key at the tick's end."""
    when = fl.g_half * p.n_lines + p.g_at
    by_when = np.argsort(when)[::-1]
    g_key, g_flow, g_milli = (p.g_key[by_when], fl.g_flow[by_when],
                              p.g_milli[by_when])
    final = {k: b for k, (_a, b) in fl.handed.items()}
    last, first_writer = {}, {}
    for k, f, m in zip(g_key.tolist(), g_flow.tolist(), g_milli.tolist()):
        value = float(np.float32(m / 1000.0))
        if f == final.get(k, f):
            last.setdefault(zipf.gauge_name(k), value)
        else:
            first_writer.setdefault(zipf.gauge_name(k), value)
    return last, first_writer


def sendings(text: list, fl: Flows, dg: dict) -> list:
    """[half][thread] -> [(flow, datagram)] in the order the thread
    sends them: each flow's lines of the half in their places' order,
    packed into datagrams, the thread's flows taking turns."""
    by = np.lexsort((np.arange(len(text)), fl.flow, fl.half))
    group = fl.half[by] * fl.count + fl.flow[by]
    edges = np.flatnonzero(np.diff(group)) + 1
    grams = {}
    for idx in np.split(by, edges):
        at = int(idx[0])
        grams[int(fl.half[at]), int(fl.flow[at])] = lines.datagrams(
            [text[i] for i in idx.tolist()], dg["max_lines"],
            dg["max_bytes"])
    out = []
    for half in (0, 1):
        threads = []
        for turns in fl.turns:
            queues = [[(f, d) for d in grams.get((half, f), [])]
                      for f in turns]
            threads.append([x for row in itertools.zip_longest(*queues)
                            for x in row if x is not None])
        out.append(threads)
    return out


def build(cfg: dict, mix: dict, seed: int, log) -> tuple:
    """Every datagram the run will send, by half and sender thread, and
    what the tiers must answer, built during set-up: `distinct_ticks`
    payloads, cycled through by the window. The reference's seconds
    are kept apart: they are not set-up."""
    plan = zipf.key_plan(mix, cfg["population"], seed)
    dg, spec = mix["datagram"], mix["flows"]
    payloads, ref_s = [], 0.0
    for k in range(mix["distinct_ticks"]):
        p = zipf.Payload(mix, plan, seed, k + 1)
        fl = Flows(p, spec, seed)
        halves = sendings(p.lines(), fl, dg)
        r0 = time.monotonic()
        ref = zipf.reference(p, cfg["percentiles"])
        ref["gauge"], first_writer = gauge_answers(p, fl)
        keys = p.keys()
        ref_s += time.monotonic() - r0
        n_grams = [sum(len(t) for t in half) for half in halves]
        payloads.append({
            "datagrams": halves, "n_datagrams": n_grams,
            "flows": fl.count, "in_flight": int(spec["in_flight"]),
            "n_lines": p.n_lines,
            "timer_lines": float(p.t_wt.sum()),
            "keys": {tier: zipf.tier_keys(keys, tier)
                     for tier in ("local", "global")},
            "handover": {"second": {n: ref["gauge"][n]
                                    for n in first_writer},
                         "first": first_writer},
            "ref": ref})
        log(f"payload {k + 1}: {p.n_lines} lines in {n_grams[0]} + "
            f"{n_grams[1]} datagrams over {fl.count} flows; "
            + ", ".join(f"{len(ids)} {kind}" for kind, ids in keys.items())
            + f"; {len(fl.handed)} gauges handed over")
    return payloads, ref_s

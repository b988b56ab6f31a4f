"""The generator `forward_payloads`: what a fleet of identical locals
forwards to one global at a flush boundary, pre-built from the seed.

A mix (`perfbench/mixes/<name>.json`) gives the keys a tick touches and
what EVERY sender reports for each: timer samples a sender (hot and cold
keys), set members a sender (a share of them the same fleet-wide, the
rest the sender's own), counter samples a sender. The deployment gives
the population the keys are drawn from and `fan_in_locals`, the number
of senders. Everything else follows from `--seed`: which keys and every
value. Another seed gives the same sizes over other keys and values.
The set members are the mix's (`sets.member_salt`), the same from seed
to seed as the other mixes' are: an HLL estimate's error is a draw of
the members' hashes, one in 4,500 such draws at p=14 lies past the
deployment's 3% (3.7 standard errors), and a run's `correct` must not
hang on that draw (PERF.md, PR 28).

`Fleet` holds the raw samples. From them, separately:

  * `reference(fleet, ...)`: plain numpy over the union of all senders'
    raw samples, members and counts: what the global must answer. It
    never sees a digest or a register, and imports nothing of the
    program. A request sent twice counts once. A control may ask it
    for less (`leave_out`, `sum_dtype`), so that it must not agree with
    a sound program.
  * `encode(fleet, ...)`: each sender's request as a local would ship
    it. A sender's digest for a key is its samples as sorted unit-weight
    centroids with their exact min, max, sum, count and reciprocal sum:
    a valid t-digest that owes nothing to the code under test. Its set
    is HLL registers under the wire's member hash (`member_hashes`, the
    program's `set_member_hash` in numpy; a test holds the two equal)
    and the wire's split into register and rho, a numpy maximum a
    register: the registers are the format, the truth they are judged by
    is the distinct count. The bytes are `wire.ForwardExport` through
    `wire.export_to_metrics`, serialized once in set-up without the
    envelope: the driver appends each tick's envelope (a protobuf
    message is the concatenation of its fields), because the seq chain
    is the sender's and runs on through the warm-up.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench.generators.dogstatsd_lines import timer_name, timer_tags

MAKES = "forward_requests"

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)
MEMBER_WIDTH = 18         # "m" + 6 digits of the mix's salt + 11 of number


def touched_keys(mix: dict, population: dict, seed: int) -> dict:
    """The keys every tick of the run touches, a seeded draw from the
    deployment's population; hot timer keys are the first `hot_keys` of
    the draw's own order; counters are the odd, global-only names; the set
    members' salt is the mix's, not the seed's."""
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
            "counters": np.sort(2 * draw(mix["counters"]["keys"],
                                         population["counters"] // 2) + 1),
            "salt": int(mix["sets"]["member_salt"])}


class Fleet:
    """One tick's raw samples of every sender: the same keys from each,
    values and own members of its own."""

    def __init__(self, mix: dict, touched: dict, senders: int, seed: int,
                 index: int):
        rng = np.random.default_rng([seed, index])
        self.touched, self.senders, self.index = touched, senders, index
        t = mix["timers"]
        tk = touched["timers"]
        per_key = np.where(np.isin(tk, touched["hot"]),
                           t["hot_samples"], t["cold_samples"])
        # the same key column for every sender; values in integer
        # thousandths, as the other generator's: the f64 on the wire and
        # the f32 the bank keeps are exactly reproducible from `milli`
        self.t_key = np.repeat(tk, per_key)
        self.t_bounds = np.concatenate([[0], np.cumsum(per_key)])
        dist = t["distribution"]
        if dist["kind"] != "lognormal":
            raise ValueError(f"unknown distribution {dist['kind']!r}")
        self.t_milli = np.maximum(1, np.rint(rng.lognormal(
            np.log(dist["median_ms"]), dist["sigma"],
            (senders, self.t_key.size)) * 1000.0)).astype(np.int64)

        # sets: member numbers; group 0 is the fleet's, 1 + s sender s's
        s = mix["sets"]
        sk = touched["sets"]
        shared = int(s["members"] * s["shared_share"])
        own = s["members"] - shared
        pos = np.arange(sk.size, dtype=np.int64)[:, None]

        def numbers(group, n):
            return (index * 10**9 + group * 10**6 + pos * 10**3
                    + np.arange(n, dtype=np.int64)[None, :])

        if s["members"] > 1000 or senders + 1 >= 1000 or sk.size > 1000:
            raise ValueError("set member numbers would collide")
        self.s_shared = numbers(0, shared)                  # [sets, shared]
        self.s_own = np.stack([numbers(1 + i, own)          # [senders, sets,
                               for i in range(senders)])    #  own]
        # counters: a few integer samples a sender
        c = mix["counters"]
        self.c_val = rng.integers(
            1, 1000, (senders, touched["counters"].size, c["samples"]))

    def members(self, sender: int) -> np.ndarray:
        """[sets, members] member numbers sender `sender` reports."""
        return np.concatenate([self.s_shared, self.s_own[sender]], axis=1)

    def n_sketches(self) -> int:
        """Sketches one sender ships a tick."""
        return (self.touched["timers"].size + self.touched["sets"].size
                + self.touched["counters"].size)


# -------------------------------------------------------- plain reference

def reference(fleet: Fleet, percentiles, leave_out=(),
              sum_dtype="float64") -> dict:
    """What the global must emit for the tick: numpy over the union of
    the senders' raw samples. `leave_out` (a control's) drops senders'
    samples from it, and `sum_dtype` "float32" (a control's) adds a
    key's samples up one by one in a single float32, as a bank without
    the f32 pair would: either must NOT agree with a sound program."""
    keep = [s for s in range(fleet.senders) if s not in set(leave_out)]
    ref = {"timer": {}, "timer_sum": {}, "hot": {}, "counter_local": {},
           "counter_global": {}, "gauge": {}, "set": {},
           "percentiles": tuple(percentiles)}
    val64 = fleet.t_milli[keep] / 1000.0          # [senders, samples]
    val32 = val64.astype(np.float32)
    hot = set(fleet.touched["hot"].tolist())
    b = fleet.t_bounds
    for i, k in enumerate(fleet.touched["timers"].tolist()):
        v64 = val64[:, b[i]:b[i + 1]].ravel()
        v32 = val32[:, b[i]:b[i + 1]].ravel()
        name = timer_name(k)
        ref["timer"][name] = (float(v64.size), float(v32.min()),
                              float(v32.max()))
        ref["timer_sum"][name] = (
            float(v64.sum()) if sum_dtype == "float64" else
            float(np.cumsum(v32, dtype=np.dtype(sum_dtype))[-1]))
        if k in hot:
            ref["hot"][name] = np.quantile(v64, percentiles)
    totals = fleet.c_val[keep].sum(axis=(0, 2))
    for k, total in zip(fleet.touched["counters"].tolist(),
                        totals.tolist()):
        ref["counter_global"][f"smoke.counter.c{k:04d}"] = float(total)
    for row, k in enumerate(fleet.touched["sets"].tolist()):
        everyone = np.concatenate([fleet.members(s)[row] for s in keep])
        ref["set"][f"smoke.set.s{k:04d}"] = float(np.unique(everyone).size)
    return ref


# ------------------------------------------------------------ wire format

def member_text(numbers: np.ndarray, salt: int) -> np.ndarray:
    """The member strings as a [n, MEMBER_WIDTH] matrix of ASCII bytes:
    `m<salt, 6 digits><number, 11 digits>`."""
    n = np.asarray(numbers, np.int64).ravel()
    full = np.int64(salt) * 10**11 + n
    out = np.empty((n.size, MEMBER_WIDTH), np.uint8)
    out[:, 0] = ord("m")
    for col in range(MEMBER_WIDTH - 1, 0, -1):
        out[:, col] = ord("0") + full % 10
        full = full // 10
    return out


def member_hashes(text: np.ndarray) -> np.ndarray:
    """`utils/hashing.py:set_member_hash` of every row: FNV-1a 64 over
    the bytes, then murmur3's 64-bit finalizer, in wrapping uint64."""
    with np.errstate(over="ignore"):
        h = np.full(text.shape[0], 0xCBF29CE484222325, np.uint64)
        for col in range(text.shape[1]):
            h = (h ^ text[:, col].astype(np.uint64)) \
                * np.uint64(0x00000100000001B3)
        h ^= h >> np.uint64(33)
        h *= np.uint64(0xFF51AFD7ED558CCD)
        h ^= h >> np.uint64(33)
        h *= np.uint64(0xC4CEB9FE1A85EC53)
        h ^= h >> np.uint64(33)
    return h & _M64


def sender_registers(fleet: Fleet, sender: int, precision: int) -> np.ndarray:
    """u8[sets, 2**precision]: the sender's HLL registers of each set."""
    from veneur_tpu.ops.hll import host_hash_to_updates
    members = fleet.members(sender)
    idx, rho = host_hash_to_updates(
        member_hashes(member_text(members, fleet.touched["salt"])),
        precision)
    regs = np.zeros((members.shape[0], 1 << precision), np.uint8)
    rows = np.repeat(np.arange(members.shape[0]), members.shape[1])
    np.maximum.at(regs, (rows, idx), rho)
    return regs


def encode(fleet: Fleet, precision: int) -> list:
    """Each sender's request without its envelope: serialized
    `forwardrpc.MetricList` bytes, histograms, then sets, then counters,
    stamped with the default sketch engines as a current local's is."""
    from veneur_tpu import sketches
    from veneur_tpu.cluster import wire
    from veneur_tpu.cluster.protos import forward_pb2
    from veneur_tpu.ingest.parser import MetricKey
    t = fleet.touched
    tkeys = [MetricKey(timer_name(k), "timer", timer_tags(k))
             for k in t["timers"].tolist()]
    skeys = [MetricKey(f"smoke.set.s{k:04d}", "set", "env:prod")
             for k in t["sets"].tolist()]
    ckeys = [MetricKey(f"smoke.counter.c{k:04d}", "counter", "env:prod")
             for k in t["counters"].tolist()]
    b = fleet.t_bounds
    out = []
    for s in range(fleet.senders):
        v = fleet.t_milli[s] / 1000.0
        ex = wire.ForwardExport()
        for i, key in enumerate(tkeys):
            x = np.sort(v[b[i]:b[i + 1]])
            ex.histograms.append((key, x, np.ones(x.size), x[0], x[-1],
                                  x.sum(), x.size, (1.0 / x).sum()))
        regs = sender_registers(fleet, s, precision)
        ex.sets = [(key, regs[row]) for row, key in enumerate(skeys)]
        ex.counters = [(key, float(c)) for key, c in zip(
            ckeys, fleet.c_val[s].sum(axis=1).tolist())]
        out.append(forward_pb2.MetricList(
            metrics=wire.export_to_metrics(ex),
            sketch_engines=sketches.DEFAULT_STAMP).SerializeToString())
    return out


def build(cfg: dict, mix: dict, seed: int, log) -> tuple:
    """`distinct_ticks` payloads over the same keys with values and
    members of their own: every sender's request bytes, which senders
    send theirs twice (`replay_every`: a retry after a lost
    acknowledgement), the centroids one key can bring the global in one
    tick (`widest_pile`), and the reference, whose seconds are kept
    apart."""
    touched = touched_keys(mix, cfg["population"], seed)
    senders = int(cfg["fan_in_locals"])
    control = cfg.get("control") or {}
    less = control.get("reference", {})
    precision = {**cfg["sketches"],
                 **control.get("sketches", {})}["hll_precision"]
    replayed = list(range(0, senders, mix["replay_every"]))
    payloads, ref_s = [], 0.0
    for k in range(mix["distinct_ticks"]):
        fleet = Fleet(mix, touched, senders, seed, k + 1)
        requests = encode(fleet, precision)
        r0 = time.monotonic()
        ref = reference(fleet, cfg["percentiles"],
                        less.get("leave_out_senders", ()),
                        less.get("sum_dtype", "float64"))
        ref_s += time.monotonic() - r0
        payloads.append({"requests": requests, "replayed": replayed,
                         "n_sketches": senders * fleet.n_sketches(),
                         "widest_pile": senders * int(np.diff(
                             fleet.t_bounds).max()),
                         "ref": ref})
        log(f"payload {k + 1}: {senders} senders x {fleet.n_sketches()} "
            f"sketches, {sum(map(len, requests))} bytes; senders "
            f"{replayed} send twice")
    return payloads, ref_s

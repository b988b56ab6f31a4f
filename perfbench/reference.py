"""The plain reference and the comparison that decides `correct`.

`reference(payload)` is numpy over the generated samples and imports
nothing of the program: what the two tiers must emit for one tick,
each series where veneur's scoping emits it. `check_tick` holds a
tick's two sink captures against it and returns every number compared
beside its limit:

  * exact fields — timer count/min/max (f32 extremes) for every key at
    BOTH tiers, counter totals where scoped, gauges last-write, the row
    counts of both sinks (nothing of another tick may leak in), no
    series emitted twice: the number compared is how many disagree,
    limit 0;
  * hot-key p50 / p99 against numpy.quantile and set estimates against
    the true distinct count: the worst relative gap, limits from the
    deployment's stated guarantees (1% / 2% / 3%);
  * every percentile of EVERY timer key at the global (p75 and the keys
    of a few samples too, where numpy's interpolation and the digest's
    differ by definition): min <= p50 <= p75 <= p99 <= max, the worst
    overshoot as a share of the key's max, limit a few f32 roundings.

An answer that is missing or not a finite number is a whole miss (gap
1.0, or one exact mismatch): `max(0.0, nan)` is 0.0 in Python, so a NaN
must never reach a comparison.

The exact f32 extremes and exact counts are what a lower-precision
bank cannot pass (a bfloat16 min/max keeps 8 bits of mantissa), and a
digest of lower compression fails the percentile limits; both controls
are kept as tests beside this file.

Copied out of `chip_smoke.py` (reference / Checker / check_window).
"""

from __future__ import annotations

import math

import numpy as np

from perfbench.traffic import timer_name


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


def sink_values(metrics) -> dict:
    """name -> value for one flush's smoke.* rows; a duplicated name
    would be a scoping bug, so it is kept visible."""
    out = {}
    for m in metrics:
        name = m.name
        if name.startswith("smoke."):
            if name in out:
                out[name + "#dup"] = m.value
            out[name] = m.value
    return out


def pct_suffix(q: float) -> str:
    return f".{int(round(q * 100))}percentile"


def finite(v):
    """`v` as a float, or None where it is missing or not finite."""
    if v is None:
        return None
    v = float(v)
    return v if math.isfinite(v) else None


def rel_gap(v, want: float) -> float:
    """|v - want| / want; a missing or non-finite answer misses whole."""
    v = finite(v)
    return 1.0 if v is None else abs(v - want) / abs(want)


def check_tick(ref: dict, local: dict, glob: dict, tol: dict) -> dict:
    """Hold one tick's sink captures against the reference. Returns
    {"numbers": {name: (value, limit)}, "mismatches": [...first few...],
    "accounted_lines": lines the emitted counts account for}."""
    bad: list = []
    n_bad = 0

    def miss(what):
        nonlocal n_bad
        n_bad += 1
        if len(bad) < 12:
            bad.append(what)

    def exact(got, name, want, where):
        v = got.get(name)
        if v is None or float(v) != float(want):
            miss(f"{where}: {name} = {v!r}, want exactly {want!r}")

    if any(k.endswith("#dup") for k in local) or any(
            k.endswith("#dup") for k in glob):
        miss("a series was emitted twice by one tier")
    for tier, got in (("local", local), ("global", glob)):
        for name, v in got.items():
            if finite(v) is None:
                miss(f"{tier}: {name} = {v!r} is not a finite number")
    accounted = 0.0
    pcts = [pct_suffix(q) for q in ref["percentiles"]]
    worst = {"p50": 0.0, "p99": 0.0, "set": 0.0, "outside": 0.0}
    for name, (count, lo, hi) in ref["timer"].items():
        for tier, got in (("local", local), ("global", glob)):
            exact(got, name + ".count", count, tier)
            exact(got, name + ".min", lo, tier)
            exact(got, name + ".max", hi, tier)
        accounted += min(finite(local.get(name + ".count")) or 0.0,
                         finite(glob.get(name + ".count")) or 0.0)
        # mixed-scope timers: percentiles are the global tier's
        if name + pcts[0] in local:
            miss(f"local: {name} emitted a percentile")
        # min <= p50 <= p75 <= p99 <= max, for every key
        ladder = [lo] + [finite(glob.get(name + s)) for s in pcts] + [hi]
        if None in ladder:
            worst["outside"] = 1.0
        else:
            over = max(a - b for a, b in zip(ladder, ladder[1:]))
            worst["outside"] = max(worst["outside"], over / abs(hi))
    qs_i = {q: i for i, q in enumerate(ref["percentiles"])}
    for name, qs in ref["hot"].items():
        for label, q in (("p50", 0.5), ("p99", 0.99)):
            gap = rel_gap(glob.get(name + pct_suffix(q)), float(qs[qs_i[q]]))
            worst[label] = max(worst[label], gap)
    for name, total in ref["counter_local"].items():
        exact(local, name, total, "local")
        if name in glob:
            miss(f"global: local counter {name}")
    for name, total in ref["counter_global"].items():
        exact(glob, name, total, "global")
        if name in local:
            miss(f"local: global-only counter {name}")
    for name, last in ref["gauge"].items():
        exact(local, name, last, "local")
    for name, distinct in ref["set"].items():
        worst["set"] = max(worst["set"], rel_gap(glob.get(name), distinct))
    n_pct = len(ref["percentiles"])
    want_local = (3 * len(ref["timer"]) + len(ref["counter_local"])
                  + len(ref["gauge"]))
    if len(local) != want_local:
        miss(f"local: {len(local)} smoke.* rows, want {want_local}")
    want_glob = ((3 + n_pct) * len(ref["timer"])
                 + len(ref["counter_global"]) + len(ref["set"]))
    if len(glob) != want_glob:
        miss(f"global: {len(glob)} smoke.* rows, want {want_glob}")
    numbers = {"exact_mismatches": (float(n_bad), 0.0)}
    if ref["timer"]:
        numbers["worst_pct_outside_rel"] = (worst["outside"],
                                            tol["pct_outside"])
    if ref["hot"]:
        numbers["worst_p50_rel"] = (worst["p50"], tol["p50"])
        numbers["worst_p99_rel"] = (worst["p99"], tol["p99"])
    if ref["set"]:
        numbers["worst_set_rel"] = (worst["set"], tol["set"])
    return {"numbers": numbers, "mismatches": bad,
            "accounted_lines": accounted}


def worse(a: float, b: float) -> float:
    """The larger of two compared numbers, a NaN larger than any."""
    return a if (math.isnan(a) or a >= b) else b


def within(numbers: dict) -> bool:
    return all(math.isfinite(v) and v <= limit
               for v, limit in numbers.values())

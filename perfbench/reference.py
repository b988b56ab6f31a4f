"""The comparison that decides `correct`.

A generator's plain reference (numpy over the generated samples, beside
the generator) says what the tiers must emit for one tick. `check_tick`
holds a tick's sink captures against it (the global's, and the local's
where the topology has a local tier) and returns every number compared
beside its limit:

  * exact fields — timer count/min/max (f32 extremes) for every key at
    every tier, counter totals where scoped, gauges last-write, the row
    counts of the sinks (nothing of another tick may leak in), no
    series emitted twice: the number compared is how many disagree,
    limit 0;
  * hot-key p50 / p99 against numpy.quantile and set estimates against
    the true distinct count: the worst relative gap, limits from the
    deployment's stated guarantees (1% / 2% / 3%);
  * every percentile of EVERY timer key at the global (p75 and the keys
    of a few samples too, where numpy's interpolation and the digest's
    differ by definition): min <= p50 <= p75 <= p99 <= max, the worst
    overshoot as a share of the key's max, limit a few f32 roundings;
  * where the reference gives sums (`timer_sum`, a deployment that
    emits them): the worst relative gap of a key's sum, which the bank
    carries in f32 pairs and cannot give exactly.

An answer that is missing or not a finite number is a whole miss (gap
1.0, or one exact mismatch): `max(0.0, nan)` is 0.0 in Python, so a NaN
must never reach a comparison.

The exact f32 extremes and exact counts are what a lower-precision
bank cannot pass (a bfloat16 min/max keeps 8 bits of mantissa), and a
digest of lower compression fails the percentile limits; both controls
are kept as tests beside this file.

Copied out of `chip_smoke.py` (Checker / check_window).
"""

from __future__ import annotations

import math

import numpy as np


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


def degrade(answers: dict, how: dict) -> dict:
    """A control's lower precision applied to a tier's answers: the
    named series rounded through a narrower float type."""
    import ml_dtypes
    dt = np.dtype(getattr(ml_dtypes, how["round_through"]))
    return {k: (float(np.float32(v).astype(dt).astype(np.float32))
                if k.endswith(tuple(how["suffixes"])) else v)
            for k, v in answers.items()}


def check_tick(ref: dict, local: dict | None, glob: dict,
               tol: dict) -> dict:
    """Hold one tick's sink captures against the reference; `local` is
    None where the topology has no local tier. Returns {"numbers":
    {name: (value, limit)}, "mismatches": [...first few...],
    "accounted_lines": lines the emitted counts account for}."""
    tiers = [("global", glob)]
    if local is not None:
        tiers.insert(0, ("local", local))
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

    if any(k.endswith("#dup") for _t, got in tiers for k in got):
        miss("a series was emitted twice by one tier")
    for tier, got in tiers:
        for name, v in got.items():
            if finite(v) is None:
                miss(f"{tier}: {name} = {v!r} is not a finite number")
    accounted = 0.0
    pcts = [pct_suffix(q) for q in ref["percentiles"]]
    worst = {"p50": 0.0, "p99": 0.0, "set": 0.0, "outside": 0.0,
             "sum": 0.0}
    for name, (count, lo, hi) in ref["timer"].items():
        for tier, got in tiers:
            exact(got, name + ".count", count, tier)
            exact(got, name + ".min", lo, tier)
            exact(got, name + ".max", hi, tier)
        accounted += min(finite(got.get(name + ".count")) or 0.0
                         for _t, got in tiers)
        # mixed-scope timers: percentiles are the global tier's
        if local is not None and name + pcts[0] in local:
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
    sums = ref.get("timer_sum", {})
    for name, total in sums.items():
        worst["sum"] = max(worst["sum"],
                           rel_gap(glob.get(name + ".sum"), total))
    for name, total in ref["counter_global"].items():
        exact(glob, name, total, "global")
        if local is not None and name in local:
            miss(f"local: global-only counter {name}")
    for name, distinct in ref["set"].items():
        worst["set"] = max(worst["set"], rel_gap(glob.get(name), distinct))
    n_pct = len(ref["percentiles"])
    if local is not None:
        for name, total in ref["counter_local"].items():
            exact(local, name, total, "local")
            if name in glob:
                miss(f"global: local counter {name}")
        for name, last in ref["gauge"].items():
            exact(local, name, last, "local")
        want_local = (3 * len(ref["timer"]) + len(ref["counter_local"])
                      + len(ref["gauge"]))
        if len(local) != want_local:
            miss(f"local: {len(local)} smoke.* rows, want {want_local}")
    want_glob = ((3 + n_pct) * len(ref["timer"]) + len(sums)
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
    if sums:
        numbers["worst_sum_rel"] = (worst["sum"], tol["sum"])
    return {"numbers": numbers, "mismatches": bad,
            "accounted_lines": accounted}


def worse(a: float, b: float) -> float:
    """The larger of two compared numbers, a NaN larger than any."""
    return a if (math.isnan(a) or a >= b) else b


def within(numbers: dict) -> bool:
    return all(math.isfinite(v) and v <= limit
               for v, limit in numbers.values())

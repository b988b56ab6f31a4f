"""`correct` can fail: the copied numpy reference agrees with itself
served back, and disagrees with deliberately degraded answers (extremes
through bfloat16; a lost sample; a stale gauge; a leaked row; a digest
too coarse), each through the number that is there to catch it. And one
whole run with the timed path broken underneath comes out not correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import harness, reference  # noqa: E402
from perfbench.generators import dogstatsd_lines as traffic  # noqa: E402

TOL = {"p50": 0.01, "p99": 0.02, "set": 0.03, "pct_outside": 1e-6}
PCT = (0.5, 0.75, 0.99)


@pytest.fixture(scope="module")
def served():
    """A payload, its reference, and the answers a faultless pair of
    tiers would give (built from the reference itself)."""
    cfg = harness.load_config("two_tier_1chip", rehearsal=True)
    mix = harness.load_mix("wide_100k", rehearsal=True)
    p = traffic.Payload(mix, traffic.touched_keys(
        mix, cfg["population"], 11), 11, 1)
    ref = traffic.reference(p, PCT)
    local, glob = {}, {}
    for name, (count, lo, hi) in ref["timer"].items():
        for tier in (local, glob):
            tier.update({name + ".count": count, name + ".min": lo,
                         name + ".max": hi})
        qs = ref["hot"].get(name, (lo, lo, hi))
        for q, v in zip(PCT, qs):
            glob[name + reference.pct_suffix(q)] = float(v)
    local.update(ref["counter_local"])
    glob.update(ref["counter_global"])
    local.update(ref["gauge"])
    glob.update(ref["set"])
    return p, ref, local, glob


def numbers(ref, local, glob):
    return {k: v for k, (v, _lim) in reference.check_tick(
        ref, local, glob, TOL)["numbers"].items()}


def test_faultless_answers_pass(served):
    p, ref, local, glob = served
    out = reference.check_tick(ref, local, glob, TOL)
    assert reference.within(out["numbers"]) and not out["mismatches"]
    assert out["numbers"]["exact_mismatches"] == (0.0, 0.0)
    assert out["accounted_lines"] == p.t_key.size
    assert len(ref["hot"]) == 4 and len(ref["set"]) == 16


def test_reference_is_plain_numpy_over_the_samples(served):
    p, ref, _local, _glob = served
    k = int(p.touched["hot"][0])
    vals = p.t_milli[p.t_key == k] / 1000.0
    name = traffic.timer_name(k)
    assert ref["timer"][name] == (float(vals.size),
                                  float(np.float32(vals.min())),
                                  float(np.float32(vals.max())))
    assert np.allclose(ref["hot"][name], np.quantile(vals, PCT))
    s = int(p.touched["sets"][0])
    assert ref["set"][f"smoke.set.s{s:04d}"] == np.unique(
        p.s_member[p.s_key == s]).size == 300
    c = int(p.touched["counters"][1])
    side = "counter_global" if c % 2 else "counter_local"
    assert ref[side][f"smoke.counter.c{c:04d}"] == p.c_val[p.c_key == c].sum()


def test_bfloat16_extremes_fail_the_exact_fields(served):
    _p, ref, local, glob = served
    how = {"suffixes": [".min", ".max"], "round_through": "bfloat16"}
    n = numbers(ref, reference.degrade(local, how),
                reference.degrade(glob, how))
    # 8 bits of mantissa: nearly every extreme of nearly every key moves
    assert n["exact_mismatches"] > len(ref["timer"])
    assert n["worst_p99_rel"] <= TOL["p99"]


@pytest.mark.parametrize("fault, number", [
    ("lost_sample", "exact_mismatches"), ("stale_gauge", "exact_mismatches"),
    ("leaked_row", "exact_mismatches"), ("twice", "exact_mismatches"),
    ("coarse_p99", "worst_p99_rel"), ("coarse_p50", "worst_p50_rel"),
    ("set_off", "worst_set_rel"), ("missing_percentile", "worst_p99_rel"),
    ("nan_p50", "worst_p50_rel"), ("nan_p99", "worst_p99_rel"),
    ("nan_set", "worst_set_rel"), ("inf_set", "worst_set_rel"),
    ("nan_p75", "exact_mismatches"), ("nan_count", "exact_mismatches"),
    ("nan_p75", "worst_pct_outside_rel"),
    ("p75_over_p99", "worst_pct_outside_rel"),
    ("cold_key_p99_over_max", "worst_pct_outside_rel"),
    ("cold_key_p50_under_min", "worst_pct_outside_rel")])
def test_each_fault_fails_the_number_that_is_there_for_it(served, fault,
                                                          number):
    _p, ref, local, glob = served
    local, glob = dict(local), dict(glob)
    hot = next(iter(ref["hot"]))
    if fault == "lost_sample":
        glob[hot + ".count"] -= 1
    elif fault == "stale_gauge":
        local[next(iter(ref["gauge"]))] += 0.001
    elif fault == "leaked_row":
        glob["smoke.timer.k999999.count"] = 4.0
    elif fault == "twice":
        local[hot + ".count#dup"] = 1.0
    elif fault == "coarse_p99":
        glob[hot + ".99percentile"] *= 1.025
    elif fault == "coarse_p50":
        glob[hot + ".50percentile"] *= 0.988
    elif fault == "set_off":
        glob[next(iter(ref["set"]))] *= 1.04
    elif fault == "missing_percentile":
        del glob[hot + ".99percentile"]
    elif fault in ("nan_p50", "nan_p75", "nan_p99"):
        glob[f"{hot}.{fault[-2:]}percentile"] = float("nan")
    elif fault == "nan_set":
        glob[next(iter(ref["set"]))] = float("nan")
    elif fault == "inf_set":
        glob[next(iter(ref["set"]))] = float("inf")
    elif fault == "nan_count":
        local[hot + ".count"] = float("nan")
    elif fault == "p75_over_p99":
        glob[hot + ".75percentile"] = glob[hot + ".99percentile"] * 1.001
    elif fault.startswith("cold_key"):
        cold = next(k for k in ref["timer"] if k not in ref["hot"])
        if fault.endswith("over_max"):
            glob[cold + ".99percentile"] = glob[cold + ".max"] * 1.00001
        else:
            glob[cold + ".50percentile"] = glob[cold + ".min"] * 0.99999
    out = reference.check_tick(ref, local, glob, TOL)
    failing = [k for k, (v, lim) in out["numbers"].items() if v > lim]
    assert number in failing and not reference.within(out["numbers"])


@pytest.mark.parametrize("value, ok", [
    (0.0, True), (0.02, True), (0.020001, False), (float("nan"), False),
    (float("inf"), False)])
def test_a_number_that_is_not_finite_is_never_within_its_limit(value, ok):
    """`max(0.0, nan)` is 0.0 in Python: the run-level worst of a number
    must keep a NaN, and `within` must refuse it."""
    assert reference.within({"n": (value, 0.02)}) is ok
    worst = reference.worse(value, reference.worse(0.01, 0.0))
    assert reference.within({"n": (worst, 0.02)}) is ok
    assert reference.within(
        {"n": (reference.worse(0.01, worst), 0.02)}) is ok


BROKEN_RUN = r"""
import dataclasses, sys
import numpy as np
sys.path.insert(0, sys.argv.pop(1))
from perfbench import harness, run

make, made = harness.make_sink, []


def broken_sink():
    sink = make()
    made.append(sink)
    if len(made) == 1:                          # the global's sink
        flush = sink.flush

        def altered(metrics):
            metrics = list(metrics)
            for i, m in enumerate(metrics):
                if (m.name.startswith("smoke.timer.")
                        and m.name.endswith(".max")):
                    v = float(np.nextafter(np.float32(m.value),
                                           np.float32(np.inf)))
                    metrics[i] = dataclasses.replace(m, value=v)
                    break
            flush(metrics)
        sink.flush = altered
    return sink


harness.make_sink = broken_sink
sys.exit(run.main(sys.argv[1:]))
"""


def test_a_run_with_an_answer_altered_where_it_is_produced_is_not_correct(
        tmp_path):
    """The rest of a run, driven past the harness's look for a chip
    (`--rehearsal`) with the timed path broken underneath: the global's
    sink is handed one timer's maximum a single f32 ulp high, in every
    tick. Every tick is compared, so the run comes out not correct. (A
    process of its own: a run sets JAX's compile cache and listeners
    for the life of its process.)"""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONHASHSEED="0",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "-c", BROKEN_RUN, REPO, "--workload",
         "two_tier_1chip.steady_10k", "--seed", "5", "--seconds", "0.5",
         "--trace", "0", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False and out["rehearsal"] is True
    fails = {ln.split()[1] for ln in p.stdout.splitlines()
             if ln.startswith("compared:") and ln.endswith("FAIL")}
    assert fails - {"compile.in_window"} == {"exact_mismatches"}
    assert "MISMATCH global: smoke.timer." in p.stdout


@pytest.mark.parametrize("met, warmed", [
    ([(8192, 128), (1808, 128)], [(8192, 256), (1808, 256)]),
    ([(8192, 256), (1808, 128)], [(8192, 128), (1808, 256)]),
    ([(1000, 128), (1000, 256)], []),
    ([(40, 384)], [(40, 128), (40, 256)]),
    ([], [])])
def test_set_up_warms_the_landing_widths_the_warm_up_ticks_did_not_meet(
        met, warmed):
    """The global's import landing clusters a [digests, lanes] matrix,
    lanes from its widest digest in steps of 128; which width a tick
    meets follows thread timing, so every one is warmed before the
    window (a compile in a timed tick is not correct)."""
    import dataclasses

    calls = []

    @dataclasses.dataclass(frozen=True)
    class Adapter:                  # the engine's sketch adapter is frozen
        compression: float = 100.0

        def cluster_rows(self, values, weights, num_centroids,
                         sorted_prefix=0):
            calls.append((values.shape, num_centroids, sorted_prefix))
            return values, weights

    class Engine:
        _heng = Adapter()

    t = harness.LandingWatch(Engine())
    for shape in met:
        z = np.zeros(shape, np.float32)
        Engine._heng.cluster_rows(z, z, num_centroids=256)
    assert calls == [(s, 256, 0) for s in met]
    assert sorted(t.warm_other_widths()) == sorted(warmed)
    assert sorted(calls[len(met):]) == sorted((s, 256, 0) for s in warmed)
    # the adapter is the program's own again, and nothing is recorded
    assert "cluster_rows" not in vars(Engine._heng)
    assert t.warm_other_widths() == []


def test_an_engine_without_a_lane_width_ladder_has_nothing_to_warm():
    class MeshEngine:
        pass

    t = harness.LandingWatch(MeshEngine())
    assert t.warm_other_widths() == []

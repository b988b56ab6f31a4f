"""The deployment `dogstatsd_zipf_two_tier_1chip` at its rehearsal's
sizes, in this process, on the CPU: the two tiers against the
generator's plain reference over nine ticks, so that the run passes
through a cold start, the first evictions (the fourth flush) and the
steady state in which every tick mints keys into slots that evicted
keys gave back.

Exact counts, extremes, totals and gauges with `@rate`; a reclaimed
slot read by the key that took it, at both tiers; the program's key
counts against the ledger tick by tick; the new phases, marks and
bridge fields; and what a counter total's exactness rests on: at the
global nothing (a forwarded total past 2^24 lands as two f32 columns),
at the local one pump batch's delta for one key under 2^24, which the
full-size mix is shown to keep.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import harness, layers, reference  # noqa: E402
from perfbench.generators import dogstatsd_zipf as zipf  # noqa: E402

CONFIG, MIX = "dogstatsd_zipf_two_tier_1chip", "zipf_churn_600k"
TICKS = 9
BANKS = ("histo", "counter", "gauge", "set")


def _slot_keys(eng) -> dict:
    """bank -> {slot: key name} of an engine's tables, whichever kind."""
    out = {}
    for bank in BANKS:
        table = getattr(eng, bank + "_keys")
        if hasattr(table, "mirror"):        # the bridge's view
            live = set(np.flatnonzero(table.touched).tolist())
            out[bank] = {s: k.name for s, k in table.mirror.items()
                         if s in live}
        else:
            out[bank] = {info.slot: k.name
                         for k, info in table._map.items()
                         if info.last_interval == table.interval}
    return out


@pytest.fixture(scope="module")
def driven():
    import jax  # noqa: F401  (conftest pins cpu)
    cfg = harness.load_config(CONFIG, True)
    mix = harness.load_mix(MIX, True)
    cfg["control"], cfg["study"] = None, False
    payloads, _s = zipf.build(cfg, mix, 2**31 + 45, lambda _m: None)
    # process-wide, and a test file that shares this worker may have
    # driven the counted fallback on purpose: held as a delta
    from veneur_tpu import kernels
    fallbacks = kernels.fallback_total()
    driver = harness.load_driver(cfg).Driver(cfg, True)
    spans, gcm = harness.Spans(), harness.GcMeter()
    meter = harness.CompileMeter()
    tol = cfg["guarantees"]["tolerances"]
    out = []
    try:
        for i in range(TICKS):
            p = payloads[i % len(payloads)]
            # which key sits on which slot while the tick's samples land
            # is read just before the flush resets the touched marks
            seen = {}
            flush = driver.lsrv.flush_once

            def spying(*a, _seen=seen, _flush=flush, **kw):
                _seen["local"] = _slot_keys(driver.leng)
                return _flush(*a, **kw)

            driver.lsrv.flush_once = spying
            gflush = driver.gsrv.flush_once

            def gspying(*a, _seen=seen, _flush=gflush, **kw):
                _seen["global"] = _slot_keys(driver.geng)
                return _flush(*a, **kw)

            driver.gsrv.flush_once = gspying
            try:
                rec = driver.tick(p, 1_000 + 10 * i, spans, gcm, meter)
            finally:
                driver.lsrv.flush_once, driver.gsrv.flush_once = flush, gflush
            local = reference.sink_values(driver.lsink.flushes[-1])
            glob = reference.sink_values(driver.gsink.flushes[-1])
            out.append((p, rec, driver.check(p, rec, tol), seen,
                        local, glob))
        stats = driver.bridge.stats()
        drops = driver.drop_counters()
        drops["kernels.fallback_total"] -= fallbacks
        from veneur_tpu.observe import SERVER_SCOPE
        marks = {tier: {name: srv.telemetry.total(SERVER_SCOPE, name)
                        for name in ("keys.interned", "keys.evicted")}
                 for tier, srv in (("local", driver.lsrv),
                                   ("global", driver.gsrv))}
    finally:
        driver.stop()
        gcm.close()
    return cfg, out, stats, drops, marks


def test_every_tick_holds_the_guarantees_with_rates(driven):
    _cfg, ticks, _stats, drops, _marks = driven
    for p, rec, v, _seen, local, glob in ticks:
        assert v["mismatches"] == []
        assert reference.within(v["numbers"]), v["numbers"]
        assert set(v["numbers"]) >= {
            "exact_mismatches", "worst_set_rel", "worst_small_set_off",
            "worst_pct_outside_rel",
            "bridge.lost_lines", "worst_p50_rank", "worst_p99_rank",
            "keys_interned_mismatch", "keys_evicted_mismatch",
            "own_timers_mismatch"}
        assert v["failed"] == 0 and v["attempted"] == p["n_lines"]
        ref = p["ref"]
        # a sampled timer's count is its lines times 2 or 4, at both tiers
        heavy = [k for k, (n, _lo, _hi) in ref["timer"].items()
                 if n >= 2 * zipf.RANKED_P50]
        assert heavy
        for name in heavy:
            assert local[name + ".count"] == glob[name + ".count"] \
                == ref["timer"][name][0]
        assert p["timer_lines"] > sum(len(s) for s, _ in
                                      ref["ranked"].values())
    assert not any(drops.values()), drops


def test_the_key_counts_are_the_ledgers_tick_by_tick(driven):
    cfg, ticks, stats, _drops, marks = driven
    ledgers = {tier: zipf.KeyLedger(3) for tier in ("local", "global")}
    assert cfg["common"]["tpu_slot_idle_ttl_intervals"] == 3
    total = {tier: {"interned": 0, "evicted": 0} for tier in ledgers}
    own = cfg["guarantees"]["own_timers"]
    for i, (p, rec, _v, _seen, _local, _glob) in enumerate(ticks):
        for tier, ledger in ledgers.items():
            # the histo bank also holds the tier's own timers, by the
            # deployment file's list, from the second interval on
            touched = dict(p["keys"][tier])
            touched["histo"] = touched["histo"].tolist() + (
                own[tier] if i else [])
            want = ledger.tick(touched)
            got = {kind: rec["flush_path"][tier]["keys_" + kind]
                   for kind in ("interned", "evicted", "live")}
            for kind, by_bank in got.items():
                assert dict(zip(BANKS, by_bank)) == want[kind], (
                    i, tier, kind)
                if kind != "live":
                    total[tier][kind] += sum(by_bank)
                assert rec["counters"][f"keys.{kind}.{tier}"] == sum(
                    by_bank)
        if i >= 5:      # the steady state: as many in as out
            assert got["interned"] == got["evicted"]
            assert sum(rec["flush_path"]["local"]["keys_interned"][1:3]) > 0
        elif i < 3:
            assert sum(rec["flush_path"]["local"]["keys_evicted"]) == 0
    # the same counts three ways: the engines' flush info (above), the
    # bridge's running totals, the servers' telemetry marks
    for at, bank in enumerate(BANKS):
        assert stats["keys_interned_" + bank] == sum(
            rec["flush_path"]["local"]["keys_interned"][at]
            for _p, rec, *_ in ticks)
        assert stats["keys_evicted_" + bank] == sum(
            rec["flush_path"]["local"]["keys_evicted"][at]
            for _p, rec, *_ in ticks)
        assert stats["keys_live_" + bank] == \
            ticks[-1][1]["flush_path"]["local"]["keys_live"][at]
    assert stats["intern_ns"] > 0
    for tier in ledgers:
        assert marks[tier]["keys.interned"] == total[tier]["interned"] > 0
        assert marks[tier]["keys.evicted"] == total[tier]["evicted"] > 0


def test_a_reclaimed_slot_reads_the_new_keys_samples_alone(driven):
    _cfg, ticks, _stats, _drops, _marks = driven
    for tier, banks in (("local", ("histo", "counter", "gauge")),
                        ("global", ("histo", "counter"))):
        for bank in banks:
            holder, reused = {}, 0
            for p, _rec, v, seen, local, glob in ticks:
                got = local if tier == "local" else glob
                ref = p["ref"]
                for slot, name in seen[tier][bank].items():
                    if not name.startswith("smoke."):
                        continue
                    was = holder.get(slot)
                    holder[slot] = name
                    if was is None or was == name:
                        continue
                    # another key held this slot in an earlier tick:
                    # what the tier emits for the new key is the new
                    # key's samples and nothing the old one left
                    reused += 1
                    assert was not in seen[tier][bank].values()
                    if bank == "histo":
                        n, lo, hi = ref["timer"][name]
                        assert (got[name + ".count"], got[name + ".min"],
                                got[name + ".max"]) == (n, lo, hi)
                    elif bank == "counter":
                        side = ("counter_local" if tier == "local"
                                and name in ref["counter_local"]
                                else "counter_global")
                        if tier == "local" and side == "counter_global":
                            assert name not in got   # forwarded, not emitted
                        else:
                            assert got[name] == ref[side][name]
                    else:
                        assert got[name] == ref["gauge"][name]
                assert v["numbers"]["exact_mismatches"] == (0.0, 0.0)
            assert reused > 0, (tier, bank)


def test_the_new_phases_reach_the_tick_record_and_the_readers(driven):
    cfg, ticks, _stats, _drops, _marks = driven
    recs = [rec for _p, rec, *_ in ticks]
    assert not layers.missing_keys(recs[0])
    for rec in recs:
        names = [row[0] for row in rec["phase_rows"]]
        assert names.count("local:engine.advance") == 1
        assert names.count("global:engine.advance") == 1
        if rec["counters"]["keys.interned.local"] > 24:
            assert names.count("local:ingest.intern") == 1
        rows = {name: (t0, t1) for name, t0, t1 in rec["phase_rows"]}
        a0, a1 = rows["local:engine.advance"]
        s0, s1 = rows["local:engine.swap"]
        assert s0 <= a0 <= a1 <= s1         # under the ingest lock
    ctx = {"ticks": recs, "trace": None, "device": {}, "run": {},
           "config": cfg}
    for name in ("ingest.intern_us", "local.advance_ms", "global.advance_ms",
                 "keys.slot_fill", "ingest.overflow_rows",
                 "ingest.pump_batches", "local.scatter_ms",
                 "import.batch_sketches", "import.land_rows",
                 "forward.tick_bytes"):
        assert layers.read_metric(name, ctx) is not None, name
    fill = layers.read_metric("keys.slot_fill", ctx)
    assert 40.0 < fill < 100.0


# ------------------------------------- what a counter's exactness rests on

def test_a_forwarded_total_past_2_24_lands_exact_at_the_global():
    from veneur_tpu.ingest.parser import MetricKey
    from veneur_tpu.models.pipeline import (AggregationEngine,
                                            EngineConfig, _f32_pair)
    hi, lo = _f32_pair(np.array([109_000_003.0, 5.0, 2.0**24 + 1]))
    assert lo.tolist() == [3.0, 0.0, 1.0]
    assert (hi.astype(np.float64) + lo).tolist() == [109_000_003.0, 5.0,
                                                     2.0**24 + 1]
    eng = AggregationEngine(EngineConfig(
        histogram_slots=256, counter_slots=256, gauge_slots=256,
        set_slots=64, batch_size=256, is_global=True, hostname="g"))
    big, small = MetricKey("t.big", "counter", ""), MetricKey(
        "t.small", "counter", "")
    eng.import_counter(big, 100_000_001.0)
    eng.import_counter(big, 9_000_002.0)
    eng.import_counter(small, 7.0)
    got = {m.name: m.value for m in eng.flush(timestamp=1).frame}
    assert got["t.big"] == 109_000_003.0 and got["t.small"] == 7.0


def test_the_local_total_is_exact_under_the_batch_bound_the_mix_keeps():
    """`ops/scalar.py:counter_add` sums one pump batch's samples of a
    key in f32: exact while that sum stays under 2^24 (the 2Sum pair
    then carries any number of batches exactly). Over it, it is not:
    the guarantee rests on the bound. The full-size mix keeps it: the
    worst window of `native_pump_batch` consecutive counter lines of a
    tick holds under 2^24 of any one key."""
    import jax.numpy as jnp
    from veneur_tpu.ops import scalar
    under = np.full(2_000, 8_001.0, np.float32)        # 16,002,000 < 2^24
    bank = scalar.init_counters(128)
    slots = jnp.zeros(under.size, jnp.int32)
    for _ in range(8):
        bank = scalar.counter_add(bank, slots, jnp.asarray(under),
                                  jnp.ones(under.size, jnp.float32))
    assert float(bank.hi[0]) + float(bank.lo[0]) == 8 * 2_000 * 8_001.0
    over = np.full(4_000, 8_001.0, np.float32)         # 32,004,000 > 2^24
    once = scalar.counter_add(scalar.init_counters(128),
                              jnp.zeros(over.size, jnp.int32),
                              jnp.asarray(over),
                              jnp.ones(over.size, jnp.float32))
    assert float(once.hi[0]) + float(once.lo[0]) != 4_000 * 8_001.0

    cfg, mix = harness.load_config(CONFIG), harness.load_mix(MIX)
    batch = cfg["local"]["native_pump_batch"]
    plan = zipf.key_plan(mix, cfg["population"], 45)
    worst = 0.0
    for index in (1, 2):
        p = zipf.Payload(mix, plan, 45, index)
        order = np.argsort(p.c_at)
        key, amount = p.c_key[order], (p.c_val * p.c_wt)[order]
        for hot in plan["counters"]["keys"][index - 1][:12]:
            run_sum = np.concatenate([[0.0], np.cumsum(
                np.where(key == hot, amount, 0.0))])
            worst = max(worst, float(
                (run_sum[batch:] - run_sum[:-batch]).max()))
    assert 2.0**21 < worst < 2.0**24 * 0.8, worst

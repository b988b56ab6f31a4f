"""The deployment `ssf_two_tier_1chip` at rehearsal size on the CPU: a
seeded payload of the generator `ssf_spans` through a local `Server` on
a framed UNIX stream, forwarded to a global, both flushed, every series
held against the generator's plain reference; and the reference itself
held against the repo's Python twin of upstream's extraction rules
(`sinks/ssfmetrics.py`) on the generator's own frames, decoded with the
generated protobuf module the generator does not import.
"""

import io
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import harness, reference  # noqa: E402

SEED = 2**31 + 4343
CELL = "ssf_two_tier_1chip.spans_10k"


@pytest.fixture(scope="module")
def parts():
    cfg = harness.load_config("ssf_two_tier_1chip", rehearsal=True)
    mix = harness.load_mix("spans_10k", rehearsal=True)
    cfg["control"], cfg["study"] = None, False
    gen = harness.load_generator(mix)
    payloads, _ref_s = gen.build(cfg, mix, SEED, lambda _m: None)
    return cfg, mix, gen, payloads


def frames_of(payload) -> list:
    """The payload's spans, decoded by the protobuf runtime."""
    from veneur_tpu.ssf import framing
    stream = io.BytesIO(b"".join(data for data, _n in payload["chunks"]))
    out = []
    while True:
        sp = framing.read_ssf(stream)
        if sp is None:
            return out
        out.append(sp)


# ------------------------------------------------------------- the generator

def test_the_mix_spreads_timers_over_every_unit_the_program_knows():
    from veneur_tpu.ssf import TIME_UNITS
    mix = harness.load_mix("spans_10k")
    gen = harness.load_generator(mix)
    assert sorted(mix["timers"]["units"]) == sorted(TIME_UNITS)
    assert gen.UNIT_MS == pytest.approx(
        {u: s * 1e3 for u, s in TIME_UNITS.items()})


def test_the_same_seed_gives_the_same_bytes_and_another_the_same_sizes(parts):
    cfg, mix, gen, payloads = parts
    again, _ = gen.build(cfg, mix, SEED, lambda _m: None)
    other, _ = gen.build(cfg, mix, SEED + 1, lambda _m: None)
    for a, b, c in zip(payloads, again, other):
        assert [d for d, _n in a["chunks"]] == [d for d, _n in b["chunks"]]
        assert [d for d, _n in a["chunks"]] != [d for d, _n in c["chunks"]]
        for k in ("n_frames", "n_lines", "timer_lines", "fallback_spans"):
            assert a[k] == c[k], k
    assert "veneur_tpu" not in open(gen.__file__).read().split('"""', 2)[2]


def test_the_frames_are_the_traffic_the_mix_describes(parts):
    cfg, mix, _gen, payloads = parts
    from veneur_tpu.ssf.protos import ssf_pb2
    sp_mix = mix["spans"]
    for p in payloads:
        spans = frames_of(p)
        assert len(spans) == p["n_frames"]
        trace = [s for s in spans if s.id]
        batch = [s for s in spans if not s.id]
        assert len(trace) == sp_mix["trace_spans"]
        assert sum(s.indicator for s in trace) == sp_mix["indicator_spans"]
        status = [s for s in trace if any(
            m.metric == ssf_pb2.SSFSample.STATUS for m in s.metrics)]
        assert len(status) == sp_mix["status_spans"] == p["fallback_spans"]
        for s in trace:
            assert s.service and s.name and s.trace_id and s.parent_id
            assert s.end_timestamp > s.start_timestamp > 0
            assert len(s.tags) == sp_mix["span_tags"]
            kinds = [m.metric for m in s.metrics
                     if m.metric != ssf_pb2.SSFSample.STATUS]
            assert len(kinds) == 2 and kinds[0] == ssf_pb2.SSFSample.HISTOGRAM
            assert kinds[1] != ssf_pb2.SSFSample.HISTOGRAM
        assert all(len(s.metrics) <= sp_mix["batch_samples"]
                   and not (s.service or s.tags or s.indicator)
                   for s in batch)
        staged = (sum(len(s.metrics) for s in spans) - len(status)
                  + sp_mix["indicator_spans"])
        assert staged == p["n_lines"] == sum(n for _d, n in p["chunks"])
        samples = [m for s in spans for m in s.metrics]
        timers = [m for m in samples
                  if m.metric == ssf_pb2.SSFSample.HISTOGRAM]
        assert {m.unit for m in timers} == set(mix["timers"]["units"])
        # a rate of 0 (left out) beside a rate of 1; all three scopes
        assert {m.sample_rate for m in timers} == {0.0, 1.0}
        assert {m.scope for m in samples} == {0, 1, 2}
        # duplicate tag keys are on the wire, the last of them wins
        raw = b"".join(d for d, _n in p["chunks"])
        assert raw.count(b"stale") >= len(timers) // mix[
            "duplicate_tag_every"]
        assert all(m.tags["env"] == "prod" for m in samples)


def test_the_reference_states_the_python_twins_extraction_rules(parts):
    """`sinks/ssfmetrics.py` over the decoded frames, aggregated the
    plain way, gives the generator's reference: the reference imports
    nothing of the program, so this is where the two meet."""
    cfg, _mix, _gen, payloads = parts
    from veneur_tpu.sinks.ssfmetrics import (indicator_timer,
                                             sample_to_metric)
    name = cfg["guarantees"]["indicator_timer"]
    assert name == cfg["common"]["indicator_span_timer_name"]
    for p in payloads:
        timers, counters, gauges, sets = {}, {}, {}, {}
        for sp in frames_of(p):
            items = [sample_to_metric(m) for m in sp.metrics]
            items.append(indicator_timer(sp, name))
            for it in items:
                if it is None:
                    continue
                key = it.key.name
                if key == name:
                    key += "|" + it.key.joined_tags
                assert it.sample_rate == 1.0
                if it.key.type == "timer":
                    timers.setdefault(key, []).append(np.float32(it.value))
                elif it.key.type == "counter":
                    counters[key] = counters.get(key, 0.0) + it.value
                elif it.key.type == "gauge":
                    gauges[key] = float(np.float32(it.value))
                elif it.key.type == "set":
                    sets.setdefault(key, set()).add(it.value)
                else:
                    raise AssertionError(it.key.type)
        ref = p["ref"]
        assert {k: (float(len(v)), float(min(v)), float(max(v)))
                for k, v in timers.items()} == ref["timer"]
        assert counters == {**ref["counter_local"], **ref["counter_global"]}
        assert gauges == ref["gauge"]
        assert {k: float(len(v)) for k, v in sets.items()} == ref["set"]
        assert sum(1 for k in ref["timer"] if k.startswith(name + "|")) > 1


# ---------------------------------------------- the deployment, end to end

@pytest.fixture(scope="module")
def driven(parts):
    """Three ticks of the cell's driver, in this process."""
    import jax  # noqa: F401  (conftest pins cpu)
    cfg, mix, _gen, payloads = parts
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
        threads = None
        for i in range(3):
            p = payloads[i % len(payloads)]
            rec = driver.tick(p, 1_000 + 10 * i, spans, gcm, meter)
            threads = sorted(t.name for t in threading.enumerate())
            out.append((p, rec, driver.check(p, rec, tol)))
        stats = driver.bridge.stats()
        drops = driver.drop_counters()
        drops["kernels.fallback_total"] -= fallbacks
    finally:
        driver.stop()
        gcm.close()
    return out, threads, stats, drops


def test_every_series_against_the_reference(driven):
    ticks, _threads, _stats, drops = driven
    for p, rec, v in ticks:
        assert v["mismatches"] == []
        assert reference.within(v["numbers"]), v["numbers"]
        assert set(v["numbers"]) >= {
            "exact_mismatches", "worst_p50_rel", "worst_p99_rel",
            "worst_set_rel", "worst_pct_outside_rel", "bridge.lost_lines",
            "service_checks_off", "ssf_fallbacks_off"}
        assert v["failed"] == 0 and v["attempted"] == p["n_lines"]
        assert rec["lines"] == rec["attempted"] == p["n_lines"]
        # the indicator keys are among the timers held exactly
        assert any("|error:" in k for k in p["ref"]["timer"])
    assert not any(drops.values()), drops


def test_a_span_reaches_the_rings_without_the_interpreter(driven):
    ticks, threads, stats, _drops = driven
    # the Python accept and frame loops do not exist in the run
    assert not [n for n in threads if n.startswith(("ssf-unix-accept",
                                                    "ssf-stream"))]
    assert stats["ssf_stream_conns"] == 1
    assert stats["ssf_stream_conn_errors"] == 0 == stats["ssf_errors"]
    assert stats["ssf_stream_frames"] == sum(p["n_frames"]
                                             for p, _r, _v in ticks)
    for p, rec, _v in ticks:
        c = rec["counters"]
        assert c["ssf.frames"] == c["ssf.spans"] == p["n_frames"]
        assert c["ssf.fallbacks"] == p["fallback_spans"]
        assert c["ssf.read_ns"] > 0 and c["ssf.ring_wait_ns"] == 0
        assert c["bridge.lost_lines"] == 0
        names = [row[0] for row in rec["phase_rows"]]
        assert names.count("local:ingest.ssf.read") == 1
        assert "local:ingest.pump.batch" in names
        assert {"local", "global"} == set(rec["flush_path"])


def test_the_tick_record_feeds_the_accepted_readers(driven):
    from perfbench import layers
    ticks, _threads, _stats, _drops = driven
    recs = [r for _p, r, _v in ticks]
    assert not layers.missing_keys(recs[0])
    ctx = {"ticks": recs, "trace": None, "device": {}, "run": {}}
    for name in ("ingest_rate", "gen.wait_share", "bridge.lost_lines",
                 "ingest.settle_s", "ingest.pump_dispatch_ms",
                 "ingest.pump_batches", "ingest.overflow_rows",
                 "local.flush_s", "forward.tick_bytes", "global.flush_s",
                 "import.batch_sketches", "tick.median_emit_s",
                 "ssf.span_us", "ssf.fallback_share", "ssf.ring_wait_ms"):
        assert layers.read_metric(name, ctx) is not None, name
    assert layers.read_metric("bridge.lost_lines", ctx) == 0.0
    assert layers.read_metric("ssf.ring_wait_ms", ctx) == 0.0
    mix = harness.load_mix("spans_10k", rehearsal=True)["spans"]
    assert layers.read_metric("ssf.fallback_share", ctx) == pytest.approx(
        100.0 * mix["status_spans"] / recs[0]["counters"]["ssf.spans"])


def test_without_the_indicator_timer_the_cell_is_not_correct(tmp_path):
    """The control the deployment brings: `indicator_span_timer_name`
    unset breaks the exact counts of the indicator keys, and the tick
    still ends."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "1",
         "--trace", "0", "--rehearsal", "--control", "no_indicator_timer"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["control"] == "no_indicator_timer"
    assert out["correct"] is False
    failing = {ln.split()[1] for ln in p.stdout.splitlines()
               if ln.startswith("compared:") and ln.endswith("FAIL")}
    # the indicator keys' rows are missing: their exact fields, and the
    # ladder min <= p50 <= ... <= max that every timer key is held to
    assert failing - {"compile.in_window"} == {"exact_mismatches",
                                               "worst_pct_outside_rel"}
    assert out["failed"] > 0

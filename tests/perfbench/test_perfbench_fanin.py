"""The seam's first user: the fan-in deployment's files
(`fanin32_global_1chip`, `fleet_1k`, the driver `fanin_global`, the
generator `forward_payloads`), whose two manifest entries wait in
`perfbench/study/fanin32.entries.json` for the PR that may change the
program's landing, and are held to the manifest's own rules whether they
are still absent from BENCHMARK.json or in it letter for letter.

The generator's wire side is held to the program's hash and its
reference to plain numpy; the cell runs through `run.py`'s own `main`
in rehearsal (`perfbench/study/fanin_probe.py run`) and comes out
`correct`, and its controls (the dedupe ledger off, extremes or sums
through bfloat16, a sender left out of the reference, a reference that
adds up in one float32, registers at p=12, percentiles through
bfloat16) each come out not `correct`, by the number each is there for.
Both drivers' tick records are held to the contract `perfbench/layers.py`
writes down. What shapes the program's landing takes is the program's:
only the form of a record's `landing_shapes` is held, where a record
carries any."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import harness, layers, run  # noqa: E402

PROBE = os.path.join(REPO, "perfbench", "study", "fanin_probe.py")
RUN = os.path.join(REPO, "perfbench", "run.py")
CELL = checks.FANIN_CELL
LOCAL_KEYS = {"lines", "ingest_s", "gen_wait_s", "t_landed_ns"}
LOCAL_COUNTERS = {"bridge.lost_lines", "forward.bytes"}


# ------------------------------------------------------- the waiting entries

def test_the_waiting_entries_keep_the_manifests_rules():
    checks.check_waiting_entries(run.load_manifest())


def test_the_cell_is_asked_for_no_metric_of_a_tier_it_does_not_have():
    """Under the manifest plus the waiting entries, merged the way the
    probe merges them, the cell reports `emit_latency_s` and `setup_s`,
    and of the per-layer metrics at least those of the import, the
    global flush, the host and the device: the eight of the local tier
    and the forward list their cells since PR 28."""
    checks.check_the_fan_in_cell_is_asked_for_no_metric_of_an_absent_tier(
        run.load_manifest())


# ------------------------------------------- the generator, off the servers

@pytest.fixture(scope="module")
def generator():
    return harness.load_generator(harness.load_mix("fleet_1k"))


def test_the_member_hash_is_the_programs(generator):
    from veneur_tpu.utils.hashing import set_member_hash
    numbers = np.array([0, 1, 999, 2 * 10**9 + 33 * 10**6 + 9 * 10**3 + 499,
                        99 * 10**9 + 999_999_999], np.int64)
    for salt in (0, 7, 999_999):
        text = generator.member_text(numbers, salt)
        assert text.shape == (numbers.size, generator.MEMBER_WIDTH)
        strings = [bytes(row).decode() for row in text]
        assert strings[1] == f"m{salt:06d}{1:011d}"
        assert generator.member_hashes(text).tolist() == [
            set_member_hash(s) for s in strings]


def test_a_fleet_at_the_cells_own_size(generator):
    """The sizes the issue states, from the files as committed: 32
    senders x 1,110 sketches, 2,048 samples a hot key a tick and 128 a
    cold one, 16,500 distinct members a set, every fourth-of-32 sender
    twice; and the reference is numpy over the raw samples."""
    cfg = harness.load_config("fanin32_global_1chip")
    mix = harness.load_mix("fleet_1k")
    touched = generator.touched_keys(mix, cfg["population"], 2**31 + 5)
    assert touched["timers"].size == 1000 and touched["hot"].size == 100
    assert touched["sets"].size == 10 and touched["counters"].size == 100
    assert (touched["counters"] % 2 == 1).all()         # global-only names
    fleet = generator.Fleet(mix, touched, 32, 2**31 + 5, 1)
    assert 32 * fleet.n_sketches() == 35_520
    ref = generator.reference(fleet, cfg["percentiles"])
    counts = sorted({c for c, _lo, _hi in ref["timer"].values()})
    assert counts == [128.0, 2048.0] and len(ref["hot"]) == 100
    assert set(ref["set"].values()) == {16_500.0}
    k = int(touched["hot"][0])
    col = np.nonzero(fleet.t_key == k)[0]
    vals = fleet.t_milli[:, col].ravel() / 1000.0
    name = f"smoke.timer.k{k:06d}"
    assert ref["timer"][name] == (2048.0, float(np.float32(vals.min())),
                                  float(np.float32(vals.max())))
    assert ref["timer_sum"][name] == pytest.approx(vals.sum(), rel=1e-12)
    assert np.allclose(ref["hot"][name], np.quantile(vals, [.5, .75, .99]))
    c = int(touched["counters"][3])
    assert ref["counter_global"][f"smoke.counter.c{c:04d}"] \
        == fleet.c_val[:, 3, :].sum()
    assert not ref["counter_local"] and not ref["gauge"]
    # a sender left out (the `missing_sender` control) changes it
    less = generator.reference(fleet, cfg["percentiles"], leave_out=[3])
    assert less["timer"][name][0] == 2048.0 - 64
    assert set(less["set"].values()) == {16_000.0}
    # one running float32 (the `f32_running_sums` control) is off by
    # more than the sum's limit in the worst key, and by nothing else
    f32 = generator.reference(fleet, cfg["percentiles"], sum_dtype="float32")
    gaps = [abs(f32["timer_sum"][n] - t) / t
            for n, t in ref["timer_sum"].items()]
    assert 3 * cfg["guarantees"]["tolerances"]["sum"] < max(gaps) < 1e-5
    assert f32["timer"] == ref["timer"] and f32["set"] == ref["set"]
    assert mix["replay_every"] == 8


def test_the_seed_changes_keys_and_values_never_sizes_or_members(generator):
    cfg = harness.load_config("fanin32_global_1chip", rehearsal=True)
    mix = harness.load_mix("fleet_1k", rehearsal=True)
    cfg["control"] = None
    sizes, bodies, registers = set(), [], set()
    for seed in (1, 2, 2**31 + 12345):
        payloads, _ = generator.build(cfg, mix, seed, lambda _m: None)
        again, _ = generator.build(cfg, mix, seed, lambda _m: None)
        assert [p["requests"] for p in again] \
            == [p["requests"] for p in payloads]
        sizes.add(tuple((p["n_sketches"], len(p["requests"]),
                         tuple(p["replayed"]), len(p["ref"]["timer"]),
                         len(p["ref"]["set"])) for p in payloads))
        bodies.append(payloads[0]["requests"][0])
        touched = generator.touched_keys(mix, cfg["population"], seed)
        fleet = generator.Fleet(mix, touched, 8, seed, 1)
        registers.add(generator.sender_registers(fleet, 5, 14).tobytes())
    # an HLL estimate's error is a draw of the members' hashes: the
    # members are the mix's, as the other mixes' are, not the seed's
    assert len(sizes) == 1 and len(set(bodies)) == 3 and len(registers) == 1
    (shape,) = sizes
    assert shape[0][:3] == (8 * 52, 8, (0,))


def test_a_request_decodes_to_the_senders_digests(generator):
    """A sender's request is a valid forward: it parses as a
    MetricList whose digests are the sender's sorted samples at unit
    weight with exact scalars, and whose registers estimate its own
    member count; the envelope appended behind it parses too."""
    from veneur_tpu.cluster import wire
    from veneur_tpu.cluster.protos import forward_pb2
    cfg = harness.load_config("fanin32_global_1chip", rehearsal=True)
    mix = harness.load_mix("fleet_1k", rehearsal=True)
    touched = generator.touched_keys(mix, cfg["population"], 9)
    fleet = generator.Fleet(mix, touched, 8, 9, 1)
    body = generator.encode(fleet, 14)[5]
    env = forward_pb2.MetricList(envelope=wire.envelope_pb(
        "bench-local-05", 3, 0, 1, kind="delta")).SerializeToString()
    ml = forward_pb2.MetricList.FromString(body + env)
    assert wire.envelope_from_metric_list(ml) == ("bench-local-05", 3, 0, 1)
    assert wire.forward_kind_from_metric_list(ml) == "delta"
    assert len(ml.metrics) == fleet.n_sketches() == 52
    ex = wire.export_from_metrics(ml.metrics)
    key, means, weights, vmin, vmax, vsum, count, _recip = ex.histograms[0]
    k = int(touched["timers"][0])
    vals = np.sort(fleet.t_milli[5, fleet.t_key == k] / 1000.0)
    assert key.name == f"smoke.timer.k{k:06d}" and key.type == "timer"
    assert (weights == 1).all() and count == vals.size
    assert np.array_equal(means, vals.astype(np.float32))
    assert (vmin, vmax) == (vals[0], vals[-1])
    assert vsum == pytest.approx(vals.sum(), rel=1e-12)
    regs = ex.sets[0][1]
    assert regs.shape == (1 << 14,) and 0 < np.count_nonzero(regs) <= 300
    assert len(ex.counters) == 10 and not ex.gauges


# ------------------------------------ the cell through run.py's own main

@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perfbench_fanin_cache"))


def rehearse(cmd, cache_dir, *extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir)
    p = subprocess.run(
        [sys.executable, *cmd, "--seed", str(2**31 + 28), "--seconds", "1",
         "--rehearsal", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    fails = {ln.split()[1] for ln in p.stdout.splitlines()
             if ln.startswith("compared:") and ln.endswith("FAIL")}
    # a rehearsal leaves compile.in_window to the chip run
    # (test_perfbench_rehearsal.failing)
    return p, json.loads(p.stdout.strip().splitlines()[-1]), \
        fails - {"compile.in_window"}


@pytest.fixture(scope="module")
def fanin_run(cache_dir, tmp_path_factory):
    ticks = str(tmp_path_factory.mktemp("fanin_ticks") / "ticks.jsonl")
    p, out, fails = rehearse([PROBE, "run", "--workload", CELL], cache_dir,
                             "--trace", "1", "--ticks-out", ticks)
    with open(ticks) as f:
        return p, out, fails, [json.loads(ln) for ln in f]


def test_the_fan_in_cell_rehearses_correct(fanin_run):
    p, out, fails, records = fanin_run
    assert not fails and out["rehearsal"] is True
    assert out["failed"] == 0 and out["attempted"] % (8 * 52) == 0
    assert "PYTHONHASHSEED 0" in p.stdout and "timed ticks" in p.stdout
    for name in ("exact_mismatches", "replays_not_dropped"):
        assert f"compared: {name} = 0 " in p.stdout
    assert "compared: worst_sum_rel" in p.stdout
    assert "bridge.lost_lines" not in p.stdout     # no tier that has one
    # counts only from a CPU, and none of a tier the cell does not have
    counts = checks.counts_of(checks.merged(
        run.load_manifest(), checks.waiting_entries()), CELL)
    assert "compile.in_window" in out["metrics"]
    assert set(out["metrics"]) <= counts
    # first tick full, later deltas on each sender's chain; one sender
    # of the rehearsal's 8 sends twice and is dropped every tick
    assert all(r["counters"]["import.duplicates_dropped"] == 1
               for r in records)
    assert all(r["attempted"] == 8 * 52 for r in records)
    # a study's run says what the landing clustered, where the engine
    # lands through `cluster_rows`: pairs of positive whole numbers,
    # whose values are the program's (PERF.md 5c has the finding)
    checks.check_landing_shapes(records)


@pytest.mark.parametrize("control, number", [
    ("dedupe_off", "exact_mismatches"),
    ("bf16_extremes", "exact_mismatches"),
    ("missing_sender", "exact_mismatches"),
    ("bf16_sums", "worst_sum_rel"),
    ("f32_running_sums", "worst_sum_rel"),
    ("hll12", "worst_set_rel"),
    ("bf16_percentiles", "worst_pct_outside_rel")])
def test_a_fan_in_control_comes_out_not_correct(control, number, cache_dir):
    """`dedupe_off` is the planted double apply: the replayed request
    lands twice and the exact counts see it (and `replays_not_dropped`
    reads 1). `bf16_extremes` moves nothing but min and max, `bf16_sums`
    and `f32_running_sums` nothing but the sums, `hll12` nothing but the
    set estimates; `bf16_percentiles` stays inside the p50 and p99
    tolerances and puts some key's p99 past its max. With `missing_sender` the program is sound and the
    reference is short of one sender: the comparison must see all of
    them. `compression20` separates only at the cell's own 2,048 samples
    a key and is a chip reading (PERF.md, PR 28). None of these runs
    writes its ticks' records, so the recorder on the program's
    `cluster_rows` comes off with the warm-up."""
    p, out, fails = rehearse([PROBE, "run", "--workload", CELL], cache_dir,
                             "--trace", "0", "--control", control)
    assert out["correct"] is False and out["control"] == control
    assert number in fails
    if control == "dedupe_off":
        assert "replays_not_dropped" in fails
        assert re.search(r"MISMATCH global: smoke\.timer\.\S+\.count",
                         p.stdout)
    if control in ("bf16_extremes", "bf16_sums", "f32_running_sums",
                   "hll12", "bf16_percentiles"):
        assert fails == {number}
    lines = p.stdout.splitlines()
    shapes = {("timed" if " timed: " in lines[i + 1] else "warm-up"):
              ln.split("landings [S, W]: ")[1].split("  acks")[0]
              for i, ln in enumerate(lines[:-1])
              if ln.startswith("  landings [S, W]: ")}
    # the recorder is the benchmark's and comes off with the warm-up;
    # what it saw before that, if anything, is the program's
    assert shapes["timed"] == "-"
    assert shapes["warm-up"] == "-" or shapes["warm-up"].startswith("[[")


# ----------------------------------------------- the tick record's contract

@pytest.fixture(scope="module")
def two_tier_records(cache_dir, tmp_path_factory):
    ticks = str(tmp_path_factory.mktemp("two_tier_ticks") / "ticks.jsonl")
    _p, _out, fails = rehearse(
        [RUN, "--workload", "two_tier_1chip.steady_10k"], cache_dir,
        "--trace", "0", "--ticks-out", ticks)
    assert not fails
    with open(ticks) as f:
        return [json.loads(ln) for ln in f]


def held(rec):
    rec = dict(rec, phase_rows=[])    # --ticks-out folds them to `phases`
    return layers.missing_keys(rec)


def test_both_drivers_keep_the_tick_records_contract(fanin_run,
                                                     two_tier_records):
    fanin = fanin_run[3]
    assert fanin and two_tier_records
    for rec in fanin + two_tier_records:
        assert held(rec) == [], rec
        assert rec["t_first_ns"] <= rec["t_last_ns"] < rec["t_end_ns"]
        assert rec["emit_latency_s"] == pytest.approx(
            (rec["t_end_ns"] - rec["t_last_ns"]) / 1e9)
        assert rec["wall_s"] == pytest.approx(
            (rec["t_end_ns"] - rec["t_first_ns"]) / 1e9)
        known = set(layers.TICK_KEYS) | set(layers.OPTIONAL_TICK_KEYS) | {
            "index", "timed", "payload", "compared", "phases", "cell",
            "seed", "pid", "setup_s"}
        assert set(rec) <= known, set(rec) - known
        assert all(k.startswith("bench.") for k in rec["spans"])
        assert any(k.startswith("global:") for k in rec["phases"])
    for rec in two_tier_records:
        assert LOCAL_KEYS <= set(rec) and LOCAL_COUNTERS <= set(
            rec["counters"])
        assert set(rec["flush_path"]) == {"local", "global"}
        assert set(rec["spans"]) == {
            "bench.send", "bench.settle", "bench.local_flush",
            "bench.global_drain", "bench.global_flush", "bench.sink_wait"}
    for rec in fanin:
        assert not LOCAL_KEYS & set(rec)
        assert not LOCAL_COUNTERS & set(rec["counters"])
        assert rec["t_first_ns"] == rec["t_last_ns"]      # the release
        assert set(rec["spans"]) == {
            "bench.forwards", "bench.global_drain", "bench.global_flush",
            "bench.sink_wait"}
        assert set(rec["acks_s"]) == {"first", "median", "last"}
        assert rec["acks_s"]["last"] <= rec["spans"]["bench.forwards"]
        assert not any(k.startswith("local:") for k in rec["phases"])


def test_a_record_short_of_the_contract_is_named():
    assert layers.missing_keys({}) == list(layers.TICK_KEYS) + [
        "counters[compile.programs]"]
    whole = dict.fromkeys(layers.TICK_KEYS, 0)
    whole["counters"] = {"compile.programs": 0}
    assert layers.missing_keys(whole) == []
    del whole["attempted"]
    assert layers.missing_keys(whole) == ["attempted"]


def test_run_py_names_no_topology_and_no_traffic_kind():
    with open(RUN) as f:
        text = f.read()
    for word in ("Tiers", "lsink", "gsink", "bridge", "timer_lines",
                 "datagram", "two_tier", "fanin", "dogstatsd", "native"):
        assert word not in text, word

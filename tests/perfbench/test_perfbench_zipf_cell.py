"""PR 45's cell, `dogstatsd_zipf_two_tier_1chip.zipf_churn_600k`: the
deployment file against `two_tier_1chip`'s, the mix's ladder against
what its `tick` states, the moving keys, the plain reference's key
ledger and rank placement, the entries in the manifest with the five
readers on hand-made tick records and traces (on records as the parent
commit's program gives them they find nothing and raise nothing), and
the cell rehearsed through `run.py` with each control.

It also holds what two tests of `test_perfbench_ssf_cells.py` assert
with the cells and entries found by name (they pin PR 43's two cells to
the end of `workloads` and its `ssf.*` entries to the end of
`per_layer`), and the four lists `test_perfbench_fixed_landing.py` pins
to PR 33's cells, as appended to: those files are not a cell PR's to
edit, and `tests/conftest.py` marks their tests expected failures while
outgrown."""

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
from perfbench.generators import dogstatsd_zipf as zipf  # noqa: E402

MANIFEST = run.load_manifest()
RUN = os.path.join(REPO, "perfbench", "run.py")
MS = 1_000_000
CONFIG = "dogstatsd_zipf_two_tier_1chip"
MIX = "zipf_churn_600k"
CELL = CONFIG + "." + MIX
SSF = "ssf_two_tier_1chip.spans_10k"
FLEET_10K = "fanin32_mesh_global_4chip.fleet_10k"
ENTRIES = {   # name -> (unit, source, layer, moves)
    "ingest.intern_us": ("us", "program_span", "sockets + C++ bridge",
                         "ingest_rate"),
    "local.advance_ms": ("ms", "program_span", "local flush",
                         "emit_latency_s"),
    "global.advance_ms": ("ms", "program_span", "global flush",
                          "emit_latency_s"),
    "keys.slot_fill": ("%", "program_counter", "sockets + C++ bridge",
                       "ingest_rate"),
    "ingest.sidestep_device_ms": ("ms", "device_trace",
                                  "pump + engine ingest programs",
                                  "ingest_rate"),
}
# accepted metrics of the global's flush and import landing, which run
# in the cell as in `steady_10k`: name -> (unit, better, source, layer)
# and the list each had, to which the cell is appended
TWO_TIER = ["two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
            "two_tier_1chip.hot_1k"]
FANIN = ["fanin32_global_1chip.fleet_1k", "fanin32_global_1chip.fleet_10k"]
APPENDED = {
    "global.flush_device_ms": (("ms", "lower", "program_span",
                                "global flush"), TWO_TIER + FANIN),
    "import.compress_device_ms": (("ms", "lower", "device_trace",
                                   "import"), TWO_TIER + FANIN),
    "import.land_pad_share": (("%", "lower", "program_counter", "import"),
                              TWO_TIER + FANIN),
    "import.cluster_roofline": (("%", "higher", "device_trace", "kernels"),
                                FANIN + TWO_TIER[1:2]),
}
# what the mix file's `tick` states, kind by kind: lines, touched keys,
# the first rank's lines, keys of one line, moving keys
TICK = {"counters": (320_848, 54_589, 27_295, 36_393, 5_458),
        "timers": (106_635, 19_850, 9_925, 13_234, 1_985),
        "gauges": (113_737, 21_058, 10_529, 14_039, 2_105),
        "sets": (29_988, 1_000, 4_008, 0, 0)}


@pytest.fixture(scope="module")
def full():
    cfg, mix = harness.load_config(CONFIG), harness.load_mix(MIX)
    return cfg, mix, zipf.key_plan(mix, cfg["population"], 45)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perfbench_jax_cache"))


def run_cell(args, cache_dir):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir,
               BENCH_RUN="7")
    p = subprocess.run([sys.executable, RUN, "--workload", CELL,
                        "--seconds", "1", "--rehearsal", *args],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    failing = {ln.split()[1] for ln in p.stdout.splitlines()
               if ln.startswith("compared:") and ln.endswith("FAIL")}
    return json.loads(p.stdout.strip().splitlines()[-1]), failing


# ---------------------------------------------------------- the deployment

def test_the_deployment_is_two_tier_1chips_but_for_what_the_issue_lists():
    cfg, two = harness.load_config(CONFIG), harness.load_config(
        "two_tier_1chip")
    assert set(cfg) == set(two)
    for same in ("chips", "fan_in_locals", "sketches", "percentiles",
                 "local", "global", "reduced"):
        assert cfg[same] == two[same], same
    # the two controls as they stand, and one that moves the set limits
    assert list(cfg["controls"]) == [*two["controls"], "hll_precision13"]
    for kept, control in two["controls"].items():
        assert cfg["controls"][kept] == control
    assert cfg["controls"]["hll_precision13"]["common"] == {
        "tpu_hll_precision": cfg["sketches"]["hll_precision"] - 1}
    assert cfg["driver"] == "zipf_two_tier"
    assert cfg["population"] == {"timer_keys": 100_000, "set_keys": 2_000,
                                 "counters": 100_000, "gauges": 50_000}
    ours = dict(cfg["common"])
    assert (ours.pop("tpu_counter_slots"), ours.pop("tpu_gauge_slots"),
            ours.pop("tpu_slot_idle_ttl_intervals")) == (131_072, 65_536, 3)
    assert ours == two["common"]
    g, g2 = cfg["guarantees"], two["guarantees"]
    for kept in ("lines_lost", "drop_and_error_counters", "forward",
                 "compile_in_window"):
        assert g[kept] == g2[kept]
    assert len(g["exact"]) > len(g2["exact"])
    assert set(g) - set(g2) == {"own_timers"}
    own = g["own_timers"]
    assert (len(own["local"]), len(own["global"])) == (8, 9)
    assert all(n.startswith("veneur.") for n in own["local"] + own["global"])
    assert set(g["tolerances"]) == {"p50_rank", "p99_rank", "set",
                                    "set_small", "pct_outside"}
    assert (g["tolerances"]["set"], g["tolerances"]["pct_outside"]) == (
        g2["tolerances"]["set"], g2["tolerances"]["pct_outside"])
    for kept, said in two["assumed"].items():
        assert cfg["assumed"][kept] == said
    for said in ("type_shares", "zipf_s", "moving_share", "rates", "sigma",
                 "tpu_slot_idle_ttl_intervals"):
        assert cfg["assumed"][said]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == ["fan_in_locals"]
    sources = [c["source"] for c in MANIFEST["configs"]]
    assert len(set(sources)) == len(sources)


# ------------------------------------------------------------------ the mix

def test_the_mix_is_the_issues_letter_for_letter():
    mix = harness.load_mix(MIX)
    assert mix["generator"] == "dogstatsd_zipf" and zipf.MAKES == "datagrams"
    assert mix["lines"] == {"counters": 330_000, "timers": 120_000,
                            "gauges": 120_000, "sets": 30_000}
    assert (mix["zipf_s"], mix["moving_share"], mix["distinct_ticks"]) == (
        1.0, 0.1, 5)
    assert mix["counters"]["rates"] == [0.5, 0.25, 0.125]
    assert mix["timers"]["rates"] == [0.5, 0.25]
    assert mix["timers"]["rated_ranks"] == 1_000
    assert mix["timers"]["distribution"] == {
        "kind": "lognormal", "median_ms": 100.0, "sigma": 0.5}
    assert mix["sets"] == {"touched": 1_000, "resent_share": 0.05}
    assert mix["datagram"] == {"max_lines": 80, "max_bytes": 4000}
    assert mix["keys_repeat"] is False


@pytest.mark.parametrize("kind", list(TICK))
def test_the_ladder_is_what_the_mix_file_states(full, kind):
    _cfg, mix, plan = full
    lines, touched, first, ones, moving = TICK[kind]
    n = plan[kind]["n"]
    assert (int(n.sum()), len(n), int(n[0]), int((n == 1).sum()),
            len(plan[kind]["moving"])) == TICK[kind]
    assert np.all(np.diff(n) <= 0) and n[-1] >= 1
    stated = mix["tick"].replace(",", "")
    for number in (lines, touched, first) + ((ones, moving) if moving
                                             else ()):
        assert re.search(rf"\b{number}\b", stated), (kind, number)


def test_a_tenth_of_the_touched_ranks_move_and_nothing_else(full):
    cfg, mix, plan = full
    for kind in zipf.KINDS:
        keys, moving = plan[kind]["keys"], plan[kind]["moving"]
        assert len(keys) == mix["distinct_ticks"] == 5
        stay = np.setdiff1d(np.arange(len(keys[0])), moving)
        own = []
        for k, mine in enumerate(keys):
            assert len(np.unique(mine)) == len(mine)
            assert np.array_equal(mine[stay], keys[0][stay])
            own.append(set(mine[moving].tolist()))
            for other in own[:k]:
                assert not own[k] & other
        if kind == "sets":
            assert len(moving) == 0
        else:
            assert np.array_equal(moving, np.arange(9, len(keys[0]), 10))
    drawn = {kind: len(set(np.concatenate(plan[kind]["keys"]).tolist()))
             for kind in zipf.KINDS}
    assert drawn == {"counters": 76_421, "timers": 27_790,
                     "gauges": 29_478, "sets": 1_000}
    stated = mix["tick"].replace(",", "")
    assert "76421 / 27790 / 29478" in stated
    assert "interns 9548 keys" in stated      # 5458 + 1985 + 2105


def test_two_seeds_give_the_same_counts_and_other_keys():
    cfg, mix = (harness.load_config(CONFIG, True),
                harness.load_mix(MIX, True))

    def built(seed):
        payloads, _s = zipf.build(cfg, mix, seed, lambda _m: None)
        ledger, ticks = zipf.KeyLedger(3), []
        for i in range(8):
            ticks.append(ledger.tick(payloads[i % 5]["keys"]["local"]))
        return payloads, ticks

    a, ticks_a = built(2**31 + 7)
    b, ticks_b = built(45)
    again, _t = built(45)
    assert ticks_a == ticks_b
    for pa, pb, pc in zip(a, b, again):
        assert pb["datagrams"] == pc["datagrams"]
        assert pa["datagrams"] != pb["datagrams"]
        assert pa["n_lines"] == pb["n_lines"]
        assert pa["timer_lines"] == pb["timer_lines"]
        for tier in ("local", "global"):
            assert ({bank: len(ids) for bank, ids in pa["keys"][tier].items()}
                    == {bank: len(ids)
                        for bank, ids in pb["keys"][tier].items()})
        assert len(pa["ref"]["ranked"]) == len(pb["ref"]["ranked"]) > 0
    # the steady state of interning and evicting, from the sixth tick
    assert ticks_a[5] == ticks_a[6] == ticks_a[7]
    assert ticks_a[7]["interned"] == ticks_a[7]["evicted"]
    assert sum(ticks_a[7]["interned"].values()) > 0


def test_a_payload_is_what_the_reference_says_it_is():
    cfg, mix = (harness.load_config(CONFIG, True),
                harness.load_mix(MIX, True))
    (p, *_rest), _s = zipf.build(cfg, mix, 45, lambda _m: None)
    text = b"\n".join(p["datagrams"]).decode().split("\n")
    assert len(text) == p["n_lines"]
    ref = p["ref"]
    count, total, last, members = {}, {}, {}, {}
    for ln in text:
        head, kind, *rest = ln.split("|")
        name, value = head.split(":")
        w = 1.0 / float(rest[0][1:]) if rest[0].startswith("@") else 1.0
        assert w in (1.0, 2.0, 4.0, 8.0)
        if kind in ("ms", "h"):
            count[name] = count.get(name, 0.0) + w
        elif kind == "c":
            total[name] = total.get(name, 0.0) + w * float(value)
            assert ("veneurglobalonly" in ln) == (int(name[-6:]) % 2 == 1)
        elif kind == "g":
            assert w == 1.0
            last[name] = float(np.float32(float(value)))
        else:
            assert kind == "s" and w == 1.0
            members.setdefault(name, set()).add(value)
    assert count == {k: v[0] for k, v in ref["timer"].items()}
    assert total == {**ref["counter_local"], **ref["counter_global"]}
    assert last == ref["gauge"]
    assert {k: float(len(v)) for k, v in members.items()} == ref["set"]
    assert any("|@0.5|" in ln or "|@0.25|" in ln for ln in text
               if "|ms|" in ln or "|h|" in ln)
    assert ref["hot"] == {}
    for name, (samples, _p99) in ref["ranked"].items():
        assert np.all(np.diff(samples) >= 0)
        assert len(samples) >= zipf.RANKED_P50
        assert ref["timer"][name][0] >= len(samples)


def test_the_ledger_evicts_at_the_fourth_flush_and_mints_again():
    ledger = zipf.KeyLedger(3)
    seen = [ledger.tick({"counter": ["stays", "moves"] if i in (0, 5)
                         else ["stays"]}) for i in range(7)]
    assert [t["interned"]["counter"] for t in seen] == [2, 0, 0, 0, 0, 1, 0]
    assert [t["evicted"]["counter"] for t in seen] == [0, 0, 0, 1, 0, 0, 0]
    assert [t["live"]["counter"] for t in seen] == [2, 2, 2, 1, 1, 2, 2]
    never = zipf.KeyLedger(0)
    assert never.tick({"set": [1]})["evicted"] == {"set": 0}


def test_a_percentile_is_placed_among_the_samples():
    drv = harness.load_driver({"name": CONFIG, "driver": "zipf_two_tier"})
    samples = np.arange(1.0, 1001.0)
    assert drv.place(samples, 500.5) == 0.5
    assert drv.place(samples, 500.0) == pytest.approx(0.4995)
    assert drv.place(samples, 0.0) == 0.0 and drv.place(samples, 2e3) == 1.0
    ranked = {"smoke.t": (samples, True), "smoke.u": (samples[:300], False)}
    glob = {"smoke.t.50percentile": 510.5, "smoke.t.99percentile": 990.5,
            "smoke.u.50percentile": 150.5, "smoke.u.99percentile": 1.0}
    gaps = drv.rank_gaps(ranked, glob)
    assert gaps == {"p50": pytest.approx(0.01), "p99": pytest.approx(0.0)}
    del glob["smoke.t.99percentile"]
    assert drv.rank_gaps(ranked, glob)["p99"] == 1.0
    assert drv.Driver.TAKES == zipf.MAKES and drv.Driver.OPS == "lines"
    # a set under SMALL_SET members is held in members, a larger one in
    # the relative gap; a missing answer misses whole
    sets = {"smoke.a": 9.0, "smoke.b": 99.0, "smoke.c": 100.0,
            "smoke.d": 4000.0}
    glob = {"smoke.a": 8.0, "smoke.b": 101.0, "smoke.c": 101.0,
            "smoke.d": 3900.0}
    assert drv.set_gaps(sets, glob) == {"small": 2.0,
                                        "rel": pytest.approx(0.025)}
    del glob["smoke.a"], glob["smoke.d"]
    assert drv.set_gaps(sets, glob) == {"small": 9.0, "rel": 1.0}


# -------------------------------------------------------------- the entries

def test_the_cell_and_its_entries_keep_the_contract():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert {k: v for k, v in cell.items() if k != "why"} == {
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert checks.line_ok(cell["why"])
    assert checks.cell_names(MANIFEST)[-1] == CELL
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, CELL, "end_to_end")] == ["ingest_rate", "emit_latency_s",
                                          "setup_s"]
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "ingest_rate")
    assert rate["workloads"][-2:] == [SSF, CELL] and rate["bound"] == 0.14
    every = [m["name"] for m in MANIFEST["per_layer"]]
    assert every[-len(ENTRIES):] == list(ENTRIES)
    # what the SSF cell reports of `steady_10k`'s, the four lists the
    # cell was appended to, and its own
    mine = [m["name"] for m in run.cell_metrics(MANIFEST, CELL, "per_layer")]
    ssf = {m["name"] for m in run.cell_metrics(MANIFEST, SSF, "per_layer")
           if not m["name"].startswith("ssf.")}
    assert mine == [n for n in every
                    if n in ssf | set(APPENDED) | set(ENTRIES)]
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) == 4 and len(MANIFEST["workloads"]) == 11
    checks.check_top_level(MANIFEST)
    checks.check_names_units_and_entries(MANIFEST)
    checks.check_every_cell_reports_what_the_contract_asks(MANIFEST)
    checks.check_every_entry_has_its_files(MANIFEST)
    checks.check_drivers_and_generators_fit(MANIFEST)
    checks.check_waiting_entries(MANIFEST)


def _tick(counters=None, phases=(), live=None):
    rec = {"flush_path": {}, "spans": {}, "counters": counters or {},
           "phase_rows": [(name, a * MS, b * MS) for name, a, b in phases]}
    if live is not None:
        rec["flush_path"]["local"] = {
            "keys_live": [live, 2 * live, live, 8],
            "keys_evicted": [0, live, 0, 0]}
    return rec


def _ctx(ticks, trace=None):
    return {"ticks": ticks, "trace": trace, "device": {}, "run": {},
            "config": harness.load_config(CONFIG)}


@pytest.mark.parametrize("name", list(ENTRIES))
def test_entry_and_reader(name):
    unit, source, layer, moves = ENTRIES[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [CELL]
    # a record of the parent commit's program: nothing to read, no raise
    assert layers.read_metric(name, _ctx([])) is None
    assert layers.read_metric(name, _ctx([_tick({"keys.interned.local": 0},
                                                [])])) is None
    ticks = [_tick({"keys.interned.local": keys},
                   [("local:ingest.intern", 0, ms),
                    ("local:engine.advance", 0, ms),
                    ("global:engine.advance", 0, 2 * ms)],
                   live=live)
             for keys, ms, live in ((1_000, 2, 100), (4_000, 4, 32_768),
                                    (2_000, 6, 200))]
    # the fullest bank at its high-water mark: the counters' 400 held
    # and 200 evicted of 131,072 in the median tick
    want = {"ingest.intern_us": 2.0, "local.advance_ms": 4.0,
            "global.advance_ms": 8.0, "keys.slot_fill": 100.0 * 600 / 131_072,
            "ingest.sidestep_device_ms": 300.0}
    # the sidestep's compress by its program's name, not the landing's
    trace = {"module_seconds": {"jit_compress_impl": 0.9,
                                "jit__compress_impl": 5.0}}
    assert layers.read_metric(name, _ctx(ticks, trace)) == pytest.approx(
        want[name])
    if source == "device_trace":
        assert layers.read_metric(name, _ctx(ticks)) is None
        assert layers.read_metric(name, _ctx(ticks, {
            "module_seconds": {"jit__compress_impl": 5.0}})) is None


@pytest.mark.parametrize("name", list(APPENDED))
def test_an_accepted_metric_of_the_globals_layers_lists_the_cell(name):
    """What `test_perfbench_fixed_landing.py` holds of the entry, with
    the cell appended to the list it pins and nothing else changed."""
    (unit, better, source, layer), cells = APPENDED[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "emit_latency_s", "workloads": cells + [CELL]}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [
        c for c in checks.cell_names(MANIFEST) if c in cells + [CELL]]
    assert (name in checks.counts_of(MANIFEST, CELL)) == (
        source == "program_counter")


# -------------- what two outgrown tests of test_perfbench_ssf_cells.py held

def test_pr33s_pr36s_and_pr43s_cells_found_by_name():
    one_k, ten_k = ("fanin32_global_1chip.fleet_1k",
                    "fanin32_global_1chip.fleet_10k")
    fleet_1k = "fanin32_mesh_global_4chip.fleet_1k"
    waiting = checks.waiting_entries()
    by_name = {w["name"]: w for w in MANIFEST["workloads"]}
    assert waiting["configs"][0] in MANIFEST["configs"]
    assert by_name[one_k] == waiting["workloads"][0]
    big = by_name[ten_k]
    assert {k: v for k, v in big.items() if k != "why"} == {
        "name": ten_k, "config": "fanin32_global_1chip",
        "traffic": "fleet_10k", "chips": 1}
    assert checks.line_ok(big["why"])
    # in the order their PRs appended them, this PR's one behind them
    names = checks.cell_names(MANIFEST)
    at = [names.index(c) for c in (one_k, ten_k, fleet_1k,
                                   "mesh_global_4chip.wide_100k", SSF,
                                   FLEET_10K, CELL)]
    assert at == sorted(at) and at[-3:] == list(range(len(names) - 3,
                                                      len(names)))
    small, mix = harness.load_mix("fleet_1k"), harness.load_mix("fleet_10k")
    assert mix["timers"].pop("keys") == 10 * small["timers"].pop("keys")
    told = ("name", "why", "scale", "rehearsal")
    assert {k: v for k, v in mix.items() if k not in told} == \
        {k: v for k, v in small.items() if k not in told}
    assert all(mix[k] != small[k] for k in told)
    for cell in (one_k, ten_k, fleet_1k, "mesh_global_4chip.wide_100k",
                 FLEET_10K):
        assert [m["name"] for m in run.cell_metrics(
            MANIFEST, cell, "end_to_end")] == ["emit_latency_s", "setup_s"]
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, one_k, "per_layer")] == [m["name"] for m in run.cell_metrics(
            MANIFEST, ten_k, "per_layer")]


def test_the_ssf_cell_still_reports_steady_10ks_metrics_and_its_own():
    ssf_entries = {"ssf.span_us": ("us", "program_span"),
                   "ssf.fallback_share": ("%", "program_counter"),
                   "ssf.ring_wait_ms": ("ms", "program_span")}
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, SSF, "end_to_end")] == ["ingest_rate", "emit_latency_s",
                                          "setup_s"]
    mine = [m["name"] for m in run.cell_metrics(MANIFEST, SSF, "per_layer")]
    steady = [m["name"] for m in run.cell_metrics(
        MANIFEST, "two_tier_1chip.steady_10k", "per_layer")]
    pinned = {"global.flush_device_ms", "import.compress_device_ms",
              "import.land_pad_share"}
    assert [n for n in mine if not n.startswith("ssf.")] == [
        n for n in steady if n not in pinned]
    assert mine[-3:] == list(ssf_entries)
    # behind them only what PR 45 appended
    every = [m["name"] for m in MANIFEST["per_layer"]]
    at = every.index("ssf.span_us")
    assert every[at:] == list(ssf_entries) + list(ENTRIES)
    for name, (unit, source) in ssf_entries.items():
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": "sockets + C++ bridge",
                         "moves": "ingest_rate", "workloads": [SSF]}
        assert checks.check_reported_where_it_says(MANIFEST, name) == [SSF]
    mix = harness.load_mix("spans_10k")
    steady_mix = harness.load_mix("steady_10k")
    for same in ("sets", "counters", "gauges", "distinct_ticks"):
        assert mix[same] == steady_mix[same]
    assert {k: v for k, v in mix["timers"].items() if k != "units"} \
        == steady_mix["timers"]


# ------------------------------------------------- the cell through run.py

def test_the_cell_rehearsed_comes_out_correct(cache_dir):
    out, failing = run_cell(["--seed", str(2**31 + 45), "--trace", "1"],
                            cache_dir)
    assert not failing - {"compile.in_window"}
    assert out["rehearsal"] is True and out["failed"] == 0
    for number in ("exact_mismatches", "keys_interned_mismatch",
                   "keys_evicted_mismatch", "own_timers_mismatch",
                   "bridge.lost_lines", "drop_and_error_counters"):
        assert out["compared"][number] == {"value": 0.0, "limit": 0.0}
    assert {"worst_p50_rank", "worst_p99_rank", "worst_set_rel",
            "worst_small_set_off",
            "worst_pct_outside_rel"} <= set(out["compared"])
    # counts only in a rehearsal: of the five new metrics the fill,
    # of the four lists the cell joined the landing's padding
    assert out["metrics"]["keys.slot_fill"]["value"] > 0
    assert 0 < out["metrics"]["import.land_pad_share"]["value"] < 100
    assert not {"ingest.intern_us", "local.advance_ms", "global.advance_ms",
                "ingest.sidestep_device_ms", "global.flush_device_ms",
                "import.compress_device_ms",
                "import.cluster_roofline"} & set(out["metrics"])


@pytest.mark.parametrize("control, fails", [
    ("bf16_extremes", "exact_mismatches"),
    ("compression20", "worst_p99_rank"),
    ("hll_precision13", "worst_small_set_off")])
def test_a_control_comes_out_not_correct(control, fails, cache_dir):
    out, failing = run_cell(["--seed", "45", "--trace", "0", "--control",
                             control], cache_dir)
    assert out["correct"] is False and out["control"] == control
    assert fails in failing
    assert not failing & {"keys_interned_mismatch", "keys_evicted_mismatch",
                          "own_timers_mismatch", "bridge.lost_lines"}

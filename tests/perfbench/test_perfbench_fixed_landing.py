"""The readers PR 33 added with `fanin32_global_1chip.fleet_1k` and its
sibling at size, `fanin32_global_1chip.fleet_10k` (the same deployment
under the mix `fleet_10k`, a new file: ten times the timer keys):
`fanin.land_stage_ms`, `fanin.land_cluster_ms`, `fanin.ack_last_s`,
`import.land_pad_share` and `import.cluster_roofline`. Each takes
hand-made tick records (present, absent, in some ticks only), the
roofline a hand-made trace and the one recorded on the v5e
(`recorded_trace_fleet_1k.json`, written by
`perfbench/study/dump_trace.py` from a traced run of the cell); each
agrees with its BENCHMARK.json entry; a rehearsal of each new cell
prints the one count among them. The accepted metrics of layers the
fan-in cells run and whose tests take a cell without a local tier
(`global.flush_device_ms`, `import.compress_device_ms`) have the two
cells appended to their lists, and nothing else about them changed. A
rehearsal is a CPU run: it proves names and counts, never a time."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import harness, layers, run, tracered  # noqa: E402

CELL = "fanin32_global_1chip.fleet_1k"
CELL_10K = "fanin32_global_1chip.fleet_10k"
FANIN = [CELL, CELL_10K]
TWO_TIER = ["two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
            "two_tier_1chip.hot_1k"]
MANIFEST = run.load_manifest()
MS = 1_000_000
PEAKS = {"hbm_bytes_per_s": 819e9}
CONFIG = harness.load_config("fanin32_global_1chip")

# name -> what its entry must be, `workloads` apart, and the cells
ENTRIES = {
    "fanin.land_stage_ms": (("ms", "lower", "program_span", "import"),
                            FANIN),
    "fanin.land_cluster_ms": (("ms", "lower", "program_span", "import"),
                              FANIN),
    "fanin.ack_last_s": (("s", "lower", "host_clock", "import"), FANIN),
    "import.land_pad_share": (("%", "lower", "program_counter", "import"),
                              TWO_TIER + FANIN),
    "import.cluster_roofline": (("%", "higher", "device_trace", "kernels"),
                                FANIN + ["two_tier_1chip.wide_100k"]),
}

# accepted metrics of layers the fan-in cells run: the list each had
# at the parent commit, to which the two cells are appended. The two
# counters beside them (`import.batch_sketches`, `import.land_rows`)
# keep their lists: their own tests run every listed cell's rehearsal
# and read its `flush_path.local`, which a cell without a local tier
# has not (PERF.md 7)
WIDENED = {
    "global.flush_device_ms": TWO_TIER,
    "import.compress_device_ms": TWO_TIER,
}


def _ctx(ticks, trace=None):
    return {"ticks": ticks, "trace": trace, "device": {}, "run": {},
            "peaks": PEAKS, "config": CONFIG}


def _tick(glob=None, phases=(), acks=None):
    t = {"flush_path": {"global": glob or {}}, "spans": {}, "counters": {},
         "phase_rows": [(name, a * MS, b * MS) for name, a, b in phases]}
    if acks is not None:
        t["acks_s"] = acks
    return t


# ------------------------------------------------------------- the entries

@pytest.mark.parametrize("name", list(ENTRIES))
def test_entry_is_what_the_issue_named(name):
    (unit, better, source, layer), cells = ENTRIES[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "emit_latency_s", "workloads": cells}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [
        c for c in checks.cell_names(MANIFEST) if c in cells]
    # its reader is a file of its own, found by the metric's name
    assert any(os.path.exists(os.path.join(
        REPO, "perfbench", "metrics", name + ext))
        for ext in (".json", ".py"))
    # only a count may be printed by a CPU rehearsal
    for cell in FANIN:
        assert (name in checks.counts_of(MANIFEST, cell)) == \
            (source == "program_counter")


@pytest.mark.parametrize("name", list(WIDENED))
def test_an_accepted_metric_of_a_layer_the_cells_run_lists_them(name):
    """Appended to the list, and nothing else of the entry changed: its
    reader finds the fan-in global's compress program, flush phases
    and counters as it finds the two-tier global's."""
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == WIDENED[name] + FANIN
    assert entry["moves"] == "emit_latency_s"
    assert checks.check_reported_where_it_says(MANIFEST, name) == [
        c for c in checks.cell_names(MANIFEST) if c in entry["workloads"]]


def test_the_cells_and_their_configuration():
    """`fleet_1k` is the waiting entry letter for letter; `fleet_10k` is
    the same deployment under a mix that differs from `fleet_1k`'s in
    the timer keys a sender reports, and in nothing the limits were
    read on (hot keys, samples, the ten fixed sets, counters)."""
    waiting = checks.waiting_entries()
    assert waiting["configs"][0] in MANIFEST["configs"]
    assert MANIFEST["workloads"][-2] == waiting["workloads"][0]
    big = MANIFEST["workloads"][-1]
    assert {k: v for k, v in big.items() if k != "why"} == {
        "name": CELL_10K, "config": "fanin32_global_1chip",
        "traffic": "fleet_10k", "chips": 1}
    assert checks.line_ok(big["why"])
    small, mix = harness.load_mix("fleet_1k"), harness.load_mix("fleet_10k")
    assert mix["timers"].pop("keys") == 10 * small["timers"].pop("keys")
    told = ("name", "why", "scale", "rehearsal")
    assert {k: v for k, v in mix.items() if k not in told} == \
        {k: v for k, v in small.items() if k not in told}
    assert all(mix[k] != small[k] for k in told)
    for cell in FANIN:
        assert [m["name"] for m in run.cell_metrics(
            MANIFEST, cell, "end_to_end")] == ["emit_latency_s", "setup_s"]
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, CELL, "per_layer")] == [m["name"] for m in run.cell_metrics(
            MANIFEST, CELL_10K, "per_layer")]
    checks.check_waiting_entries(MANIFEST)
    checks.check_every_cell_reports_what_the_contract_asks(MANIFEST)
    checks.check_every_entry_has_its_files(MANIFEST)
    # it reports the two end-to-end metrics without a list, and no
    # metric of a tier it lacks
    checks.check_the_fan_in_cell_is_asked_for_no_metric_of_an_absent_tier(
        MANIFEST)


# ---------------------------------------------- the landing's two phases

@pytest.mark.parametrize("kid", ["stage", "cluster"])
def test_the_fanin_phase_readers_read_what_the_two_tier_ones_read(kid):
    phase = f"global:import.land.{kid}"
    other = "global:import.land." + ("cluster" if kid == "stage"
                                     else "stage")
    # four landings a tick, summed; the median over the ticks, in ms
    ticks = [_tick(phases=[(phase, 0, 10), (phase, 20, 30),
                           (phase, 40, 55), (phase, 60, 65),
                           (other, 10, 12)]),
             _tick(phases=[(phase, 0, 50)]),
             _tick(phases=[(phase, 0, 20), (other, 30, 90)])]
    got = layers.read_metric(f"fanin.land_{kid}_ms", _ctx(ticks))
    assert got == pytest.approx(40.0)
    assert got == layers.read_metric(f"import.land_{kid}_ms", _ctx(ticks))
    # an engine that stamps no child phase gives nothing to read
    none = [_tick(phases=[("global:import.land", 0, 9)])] * 3
    assert layers.read_metric(f"fanin.land_{kid}_ms", _ctx(none)) is None
    assert layers.read_metric(f"fanin.land_{kid}_ms", _ctx([])) is None


# ------------------------------------------------ the last acknowledgement

@pytest.mark.parametrize("acks, want", [
    ([{"first": 0.1, "median": 1.6, "last": 2.3},
      {"first": 0.2, "median": 1.7, "last": 2.5},
      {"first": 0.1, "median": 1.5, "last": 2.2}], 2.3),
    # a two-tier tick has no senders of the benchmark's own
    ([None, None], None),
    ([None, {"first": 0.1, "median": 0.2, "last": 0.4}], 0.4),
    ([{}], None),
    ([], None),
], ids=["median_of_the_last", "no_senders", "some_ticks", "empty_record",
        "no_ticks"])
def test_ack_last_is_the_median_of_each_ticks_last(acks, want):
    ticks = [_tick(acks=a) for a in acks]
    assert layers.read_metric("fanin.ack_last_s", _ctx(ticks)) == want


# ------------------------------------------------------- the padding share

@pytest.mark.parametrize("ticks, want", [
    # fleet_1k's: four landings of [1024, 1024] holding 90k centroids
    ([{"import_land_lanes": 4 << 20, "import_land_lanes_filled": 360000,
       "import_land_prechunked": 0}] * 3,
     100.0 * (1 - 360000 / (4 << 20))),
    # steady_10k's: [8192, 256] filled to 124 lanes a row, then the tail
    ([{"import_land_lanes": 2 * 8192 * 256,
       "import_land_lanes_filled": 10000 * 124},
      {"import_land_lanes": 2 * 8192 * 128,
       "import_land_lanes_filled": 10000 * 120},
      {"import_land_lanes": 2 * 8192 * 256,
       "import_land_lanes_filled": 10000 * 124}],
     100.0 * (1 - 10000 * 124 / (2 * 8192 * 256))),
    ([{"import_land_lanes": 1024, "import_land_lanes_filled": 1024}], 0.0),
    # a tick that landed nothing says nothing about padding
    ([{"import_land_lanes": 0, "import_land_lanes_filled": 0},
      {"import_land_lanes": 1000, "import_land_lanes_filled": 250}], 75.0),
    # the parent commit's ticks: a program without the counters
    ([{"path": "incremental", "import_land_rows": 1000,
       "import_land_bank": 0}] * 3, None),
    ([], None),
], ids=["fleet", "median", "no_padding", "idle_tick", "no_counters",
        "no_ticks"])
def test_pad_share_is_the_lanes_no_centroid_filled(ticks, want):
    got = layers.read_metric("import.land_pad_share",
                             _ctx([_tick(t) for t in ticks]))
    assert got == (None if want is None else pytest.approx(want))


def test_pad_share_does_not_raise_on_a_tick_without_a_flush_path():
    ticks = [{"phase_rows": [], "spans": {}, "counters": {}},
             {"flush_path": {"local": {}}}]
    assert layers.read_metric("import.land_pad_share", _ctx(ticks)) is None


# ----------------------------------------------------------- the roofline

def _reduced(modules, window_ms=100):
    """A trace with the given module rows on device 0, one tick wide."""
    trace = {"device": {0: [["%sort.1 = f32[8192,4096]{1,0} sort(...)",
                             0, 1 * MS]]},
             "modules": {0: [[n, a * MS, d * MS] for n, a, d in modules]},
             "host": [[tracered.SYNC, -2 * MS, 1 * MS]]}
    bench = [(tracered.SYNC, -2 * MS, -1 * MS)]
    return tracered.reduce_trace(trace, bench, [], [(0, window_ms * MS)])


def _roofline():
    return harness.load_code("metrics", "import.cluster_roofline")


@pytest.mark.parametrize("compression, C", [(20.0, 128), (100.0, 256),
                                            (1000.0, 2048)])
def test_roofline_counts_centroids_a_row_as_the_bank_holds_them(
        compression, C):
    from veneur_tpu.ops import tdigest
    assert _roofline().centroids_per_row(compression) == C
    assert tdigest.init(1, compression).num_centroids == C


def test_roofline_is_useful_bytes_over_bandwidth_over_device_time():
    mod = _roofline()
    assert mod.least_bytes(90000, 1000, 100.0) == \
        8 * 90000 + 8 * 256 * 1000
    tr = _reduced([("jit_cluster_rows(123)", 10, 2),
                   ("jit_cluster_rows(123)", 50, 2),
                   ("jit__compress_impl(9)", 20, 5)])
    ticks = [_tick({"import_land_lanes_filled": 180000,
                    "import_land_rows": 2000,
                    "import_land_lanes": 2 << 20})]
    least_s = (8 * 180000 + 8 * 256 * 2000) / 819e9
    want = 100.0 * least_s / 0.004
    assert layers.read_metric("import.cluster_roofline",
                              _ctx(ticks, tr)) == pytest.approx(want)
    assert 0 < want < 100


def test_roofline_counts_filled_lanes_not_operand_shapes():
    """The same piles through a wider padded shape are the same work:
    the lanes handed over (`import_land_lanes`) and the shapes in the
    trace's text do not enter the byte count."""
    tr = _reduced([("jit_cluster_rows(1)", 0, 4)])
    narrow = _tick({"import_land_lanes_filled": 5000,
                    "import_land_rows": 40,
                    "import_land_lanes": 1024 * 128})
    wide = _tick({"import_land_lanes_filled": 5000,
                  "import_land_rows": 40,
                  "import_land_lanes": 8192 * 4096})
    a = layers.read_metric("import.cluster_roofline", _ctx([narrow], tr))
    b = layers.read_metric("import.cluster_roofline", _ctx([wide], tr))
    assert a == b == pytest.approx(
        100.0 * (8 * 5000 + 8 * 256 * 40) / 819e9 / 0.004)
    # the operands of [8192, 4096] alone would be 268 MB: 8,200% here
    assert b < 100.0 * (8 * 8192 * 4096) / 819e9 / 0.004 / 1000


@pytest.mark.parametrize("glob, modules, traced", [
    # the parent commit: the program runs, the counters are not there
    ({"import_land_rows": 1000, "import_land_bank": 0},
     [("jit_cluster_rows(1)", 0, 4)], True),
    # an engine that lands another way: counters at 0, no such program
    ({"import_land_lanes_filled": 0, "import_land_rows": 0},
     [("jit_merge(7)", 0, 4)], True),
    # an untraced run
    ({"import_land_lanes_filled": 5000, "import_land_rows": 40},
     [("jit_cluster_rows(1)", 0, 4)], False),
], ids=["no_counters", "no_program", "no_trace"])
def test_roofline_leaves_the_metric_out(glob, modules, traced):
    tr = _reduced(modules) if traced else None
    assert layers.read_metric("import.cluster_roofline",
                              _ctx([_tick(glob)], tr)) is None


def test_roofline_without_peaks_or_ticks_reads_nothing():
    tr = _reduced([("jit_cluster_rows(1)", 0, 4)])
    ctx = _ctx([_tick({"import_land_lanes_filled": 1})], tr)
    ctx["peaks"] = None
    assert layers.read_metric("import.cluster_roofline", ctx) is None
    assert layers.read_metric("import.cluster_roofline",
                              _ctx([], tr)) is None


@pytest.fixture(scope="module")
def recorded():
    """The first two ticks of a traced run of the cell on the v5e (my
    chip run, PR 33, seed 3300000106), reduced over those ticks: four
    landings a tick, `flush_path.global` as the run's records had it."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "recorded_trace_fleet_1k.json")) as f:
        rows = json.load(f)
    trace = {"host": rows["host"],
             "device": {int(d): ev for d, ev in rows["device"].items()},
             "modules": {int(d): ev for d, ev in rows["modules"].items()}}
    off = 7_000 * MS            # monotonic clock = trace clock + off
    bench = [(n, a + off, a + dur + off) for n, a, dur in rows["host"]]
    first = [r for r in bench if r[0] == "bench.forwards"][:2]
    last = [r for r in bench if r[0] == "bench.sink_wait"][:2]
    windows = [(a[1], b[2]) for a, b in zip(first, last)]
    ticks = [_tick({"import_land_rows": 4000, "import_land_bank": 0,
                    "import_land_lanes": 3 * (1 << 20) + (1 << 19),
                    "import_land_lanes_filled": 320000,
                    "import_land_prechunked": 0})] * 2
    return tracered.reduce_trace(trace, bench, [], windows), ticks


def test_roofline_on_the_recorded_trace(recorded):
    tr, ticks = recorded
    # one cluster program a landing, under the name the reader sums
    assert tr["module_seconds"]["jit_cluster_rows"] == pytest.approx(
        0.012023, rel=1e-3)
    calls = [r for r in tr["module_seconds"] if "cluster" in r]
    assert calls == ["jit_cluster_rows"]
    got = layers.read_metric("import.cluster_roofline", _ctx(ticks, tr))
    least_s = 2 * (8 * 320000 + 8 * 256 * 4000) / 819e9
    assert got == pytest.approx(100.0 * least_s / 0.012023, rel=1e-3)
    assert 0.1 < got < 1.0       # 0.22%: 26 us of bytes in 12 ms
    # the same ticks on the parent commit's program: nothing to read
    bare = [_tick({"import_land_rows": 4000, "import_land_bank": 0})] * 2
    assert layers.read_metric("import.cluster_roofline",
                              _ctx(bare, tr)) is None
    assert layers.read_metric("import.land_pad_share",
                              _ctx(ticks)) == pytest.approx(
        100.0 * (1 - 320000 / 3670016))


# ----------------------------------------------------- a rehearsal prints it

@pytest.fixture(scope="module")
def jax_cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.mark.parametrize("cell", FANIN)
def test_a_rehearsal_of_the_cell_prints_the_count_and_carries_the_tally(
        cell, tmp_path, jax_cache):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=jax_cache)
    ticks = tmp_path / "ticks.jsonl"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", cell, "--seed", "3300000033", "--seconds", "1",
         "--trace", "1", "--rehearsal", "--ticks-out", str(ticks)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    rows = [json.loads(ln) for ln in ticks.read_text().splitlines()]
    timed = [r for r in rows if r["timed"]]
    assert timed
    for r in rows:
        t = r["flush_path"]["global"]
        # every tick lands the fleet's timers: lanes handed over, some
        # of them filled, no pile of this traffic wide enough to be cut
        assert 0 < t["import_land_lanes_filled"] <= t["import_land_lanes"]
        assert t["import_land_prechunked"] == 0
        assert r["acks_s"]["first"] <= r["acks_s"]["last"]
    got = line["metrics"]["import.land_pad_share"]
    assert got["unit"] == "%" and 0 <= got["value"] < 100
    assert got["value"] == layers.read_metric(
        "import.land_pad_share", _ctx(timed))
    # times and device metrics are the chip's: never from a CPU run
    assert not set(line["metrics"]) & {
        "fanin.land_stage_ms", "fanin.land_cluster_ms",
        "fanin.ack_last_s", "import.cluster_roofline"}
    # whatever widths the harness's ladder ran besides, nothing
    # compiled after the warm-up: the program pads them to its own
    assert line["compared"]["compile.in_window"]["value"] == 0

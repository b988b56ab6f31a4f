"""PR 43's two cells, `ssf_two_tier_1chip.spans_10k` and
`fanin32_mesh_global_4chip.fleet_10k`, in the manifest: their entries,
the lists they were appended to, and the three `ssf.*` readers on
hand-made tick records (on records as the parent commit's program gives
them, two of the three find nothing to read and raise nothing).

It also holds what two tests of `test_perfbench_mesh_readers.py` assert
of PR 36's cells and entries with the cells found by name and the lists
held as appended to: those tests pin `workloads[-4:]` and each `mesh.*`
list to what PR 36 left, their file is not a cell PR's to edit, and
`tests/conftest.py` marks them expected failures while the pins are
outgrown."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import harness, layers, run  # noqa: E402

MANIFEST = run.load_manifest()
MS = 1_000_000
SSF = "ssf_two_tier_1chip.spans_10k"
FLEET_1K = "fanin32_mesh_global_4chip.fleet_1k"
FLEET_10K = "fanin32_mesh_global_4chip.fleet_10k"
MESH = ["mesh_global_4chip.steady_10k", FLEET_1K,
        "mesh_global_4chip.wide_100k", FLEET_10K]

# name -> (unit, better, source, layer), the cells: PR 36's entries with
# the four-chip fleet_10k appended and nothing else changed
MESH_ENTRIES = {
    "mesh.import_stage_ms": (("ms", "lower", "program_span", "import"),
                             MESH),
    "mesh.import_dispatch_ms": (("ms", "lower", "program_span", "import"),
                                MESH),
    "mesh.import_dispatches": (
        ("programs", "lower", "program_counter", "import"), MESH),
    "mesh.shard_fill_least": (("%", "higher", "program_counter", "import"),
                              MESH),
    "mesh.device_busy_least": (("%", "higher", "device_trace", "device"),
                               MESH),
    "mesh.ack_last_s": (("s", "lower", "host_clock", "import"),
                        [FLEET_1K, FLEET_10K]),
}
SSF_ENTRIES = {"ssf.span_us": ("us", "program_span"),
               "ssf.fallback_share": ("%", "program_counter"),
               "ssf.ring_wait_ms": ("ms", "program_span")}


def _ctx(ticks):
    return {"ticks": ticks, "trace": None, "device": {}, "run": {}}


def _tick(counters=None, phases=()):
    return {"flush_path": {}, "spans": {}, "counters": counters or {},
            "phase_rows": [(name, a * MS, b * MS) for name, a, b in phases]}


# ----------------------------------------------- PR 36's entries, appended to

@pytest.mark.parametrize("name", list(MESH_ENTRIES))
def test_mesh_entry_and_reader(name):
    (unit, better, source, layer), cells = MESH_ENTRIES[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "emit_latency_s", "workloads": cells}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [
        c for c in checks.cell_names(MANIFEST) if c in cells]
    assert any(os.path.exists(os.path.join(
        REPO, "perfbench", "metrics", name + ext))
        for ext in (".json", ".py"))
    assert layers.read_metric(name, _ctx([])) is None


def test_pr33s_and_pr36s_cells_found_by_name():
    one_k, ten_k = ("fanin32_global_1chip.fleet_1k",
                    "fanin32_global_1chip.fleet_10k")
    waiting = checks.waiting_entries()
    by_name = {w["name"]: w for w in MANIFEST["workloads"]}
    assert waiting["configs"][0] in MANIFEST["configs"]
    assert by_name[one_k] == waiting["workloads"][0]
    big = by_name[ten_k]
    assert {k: v for k, v in big.items() if k != "why"} == {
        "name": ten_k, "config": "fanin32_global_1chip",
        "traffic": "fleet_10k", "chips": 1}
    assert checks.line_ok(big["why"])
    # in the order their PRs appended them, this PR's two behind them
    names = checks.cell_names(MANIFEST)
    at = [names.index(c) for c in (one_k, ten_k, FLEET_1K,
                                   "mesh_global_4chip.wide_100k", SSF,
                                   FLEET_10K)]
    assert at == sorted(at) and at[-2:] == [len(names) - 2, len(names) - 1]
    small, mix = harness.load_mix("fleet_1k"), harness.load_mix("fleet_10k")
    assert mix["timers"].pop("keys") == 10 * small["timers"].pop("keys")
    told = ("name", "why", "scale", "rehearsal")
    assert {k: v for k, v in mix.items() if k not in told} == \
        {k: v for k, v in small.items() if k not in told}
    assert all(mix[k] != small[k] for k in told)
    for cell in (one_k, ten_k, FLEET_1K, "mesh_global_4chip.wide_100k",
                 FLEET_10K):
        assert [m["name"] for m in run.cell_metrics(
            MANIFEST, cell, "end_to_end")] == ["emit_latency_s", "setup_s"]
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, one_k, "per_layer")] == [m["name"] for m in run.cell_metrics(
            MANIFEST, ten_k, "per_layer")]
    checks.check_waiting_entries(MANIFEST)
    checks.check_every_cell_reports_what_the_contract_asks(MANIFEST)
    checks.check_every_entry_has_its_files(MANIFEST)


# ------------------------------------------------------------ this PR's cells

def test_the_four_chip_fleet_10k_is_fleet_1ks_twin():
    """One entry on a configuration and a mix that were there: it
    reports what the four-chip `fleet_1k` reports, metric for metric."""
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == FLEET_10K)
    assert {k: v for k, v in cell.items() if k != "why"} == {
        "name": FLEET_10K, "config": "fanin32_mesh_global_4chip",
        "traffic": "fleet_10k", "chips": 4}
    for group in ("end_to_end", "per_layer"):
        assert [m["name"] for m in run.cell_metrics(
            MANIFEST, FLEET_10K, group)] == [m["name"] for m in
                                             run.cell_metrics(
            MANIFEST, FLEET_1K, group)]
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= len(MANIFEST["workloads"]) // 2


def test_the_ssf_deployment_is_two_tier_1chips_behind_another_front_end():
    cfg = harness.load_config("ssf_two_tier_1chip")
    two = harness.load_config("two_tier_1chip")
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "ssf_two_tier_1chip")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == [
        "fan_in_locals", "sharded_global", "proxysrv"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    for same in ("population", "sketches", "percentiles", "global",
                 "rehearsal"):
        assert cfg[same] == two[same], same
    assert cfg["guarantees"]["tolerances"] == two["guarantees"]["tolerances"]
    ours = dict(cfg["common"])
    assert ours.pop("indicator_span_timer_name").startswith("smoke.")
    assert ours == two["common"]
    # every sample arrives inside a span, on a framed UNIX stream
    assert "statsd_listen_addresses" not in cfg["local"]
    assert cfg["local"]["native_ingest"] is True
    assert all(a.startswith("unix://")
               for a in cfg["local"]["ssf_listen_addresses"])
    assert cfg["guarantees"]["samples_lost"] == 0
    assert "drops" in cfg["guarantees"]["full_ring"]
    assert "UNVERIFIED" in cfg["assumed"]["frame_layout"]
    assert cfg["assumed"]["process"] == two["assumed"]["process"]
    assert list(cfg["controls"]) == ["no_indicator_timer", "compression20",
                                     "bf16_extremes"]
    assert cfg["controls"]["no_indicator_timer"]["common"] == {
        "indicator_span_timer_name": ""}
    for kept in ("compression20", "bf16_extremes"):
        assert cfg["controls"][kept] == two["controls"][kept]


def test_the_ssf_cell_reports_steady_10ks_metrics_and_its_own():
    """The half behind the rings is `steady_10k`'s: the cell reports
    every metric that cell reports (but the three whose lists an
    accepted test pins to the cells they had, PERF.md 7), and the
    three `ssf.*` entries, which list it alone."""
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
    assert mine[-3:] == list(SSF_ENTRIES)
    assert [m["name"] for m in MANIFEST["per_layer"]][-3:] == list(
        SSF_ENTRIES)
    for name, (unit, source) in SSF_ENTRIES.items():
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


# ------------------------------------------------------------- the readers

# a tick as the parent commit's program records it: the frames went
# through the Python loop, so no stream reader tallied and no phase
PARENT_TICK = _tick(counters={"ssf.spans": 42_652, "ssf.fallbacks": 100},
                    phases=[("local:ingest.pump.batch", 0, 30)])


@pytest.mark.parametrize("name", list(SSF_ENTRIES))
def test_no_tick_gives_nothing_to_read(name):
    assert layers.read_metric(name, _ctx([])) is None
    assert layers.read_metric(name, _ctx([_tick()])) is None


@pytest.mark.parametrize("name", ["ssf.span_us", "ssf.ring_wait_ms"])
def test_the_parents_records_give_nothing_to_read(name):
    assert layers.read_metric(name, _ctx([PARENT_TICK] * 3)) is None


def test_span_us_is_the_readers_seconds_over_the_spans_median_a_tick():
    ticks = [_tick({"ssf.spans": spans},
                   [("local:ingest.ssf.read", 0, ms),
                    ("local:ingest.pump.batch", 0, 500)])
             for spans, ms in ((1000, 2), (1000, 4), (500, 4))]
    assert layers.read_metric("ssf.span_us", _ctx(ticks)) \
        == pytest.approx(4.0)
    # a tick without spans is no reading
    assert layers.read_metric("ssf.span_us", _ctx(
        ticks[:1] + [_tick({"ssf.spans": 0})])) == pytest.approx(2.0)


def test_fallback_share_is_over_all_the_windows_spans():
    ticks = [_tick({"ssf.spans": 42_652, "ssf.fallbacks": 100}),
             _tick({"ssf.spans": 42_652, "ssf.fallbacks": 100})]
    assert layers.read_metric("ssf.fallback_share", _ctx(ticks)) \
        == pytest.approx(100 * 100 / 42_652)
    # it reads on the parent too: `handle_ssf` counted there as well
    assert layers.read_metric("ssf.fallback_share", _ctx([PARENT_TICK])) \
        == pytest.approx(0.2345, abs=1e-4)


def test_ring_wait_reads_zero_while_a_reader_drops_and_counts():
    ticks = [_tick({"ssf.ring_wait_ns": ns}) for ns in (0, 0, 3_000_000)]
    assert layers.read_metric("ssf.ring_wait_ms", _ctx(ticks)) == 0.0
    assert layers.read_metric("ssf.ring_wait_ms", _ctx(ticks[2:])) \
        == pytest.approx(3.0)

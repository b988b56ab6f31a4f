"""The readers PR 36 added for the three mesh cells:
`mesh.import_stage_ms`, `mesh.import_dispatch_ms`,
`mesh.import_dispatches`, `mesh.shard_fill_least`,
`mesh.device_busy_least` and the fan-in cell's `mesh.ack_last_s`. Each
takes hand-made tick records or a hand-made reduced trace; on records
as the parent commit writes them (no counter, no child phase under
`import.land`) each finds nothing to read and raises nothing, which is
what lets the parent run the new cells under this PR's benchmark files.
Each agrees with its BENCHMARK.json entry."""

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
FLEET = "fanin32_mesh_global_4chip.fleet_1k"
MESH = ["mesh_global_4chip.steady_10k", FLEET, "mesh_global_4chip.wide_100k"]

# name -> (unit, better, source, layer), the cells
ENTRIES = {
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
    "mesh.ack_last_s": (("s", "lower", "host_clock", "import"), [FLEET]),
}


def _ctx(ticks, trace=None):
    return {"ticks": ticks, "trace": trace, "device": {}, "run": {}}


def _tick(glob=None, phases=(), acks=None):
    t = {"flush_path": {"global": glob or {}}, "spans": {}, "counters": {},
         "phase_rows": [(name, a * MS, b * MS) for name, a, b in phases]}
    if acks is not None:
        t["acks_s"] = acks
    return t


# a tick as the parent commit records it: `import.land` without
# children, a flush note without the mesh engine's tally
PARENT_TICK = _tick(glob={"path": "full", "import_batches": 32},
                    phases=[("global:import.land", 0, 30)],
                    acks={"first": 0.1, "median": 0.12, "last": 0.15})


@pytest.mark.parametrize("name", list(ENTRIES))
def test_entry_and_reader(name):
    (unit, better, source, layer), cells = ENTRIES[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "emit_latency_s", "workloads": cells}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [
        c for c in checks.cell_names(MANIFEST) if c in cells]
    assert any(os.path.exists(os.path.join(
        REPO, "perfbench", "metrics", name + ext))
        for ext in (".json", ".py"))
    # no tick, no trace: nothing to read, nothing raised
    assert layers.read_metric(name, _ctx([])) is None


@pytest.mark.parametrize("name", [n for n in ENTRIES
                                  if n != "mesh.ack_last_s"])
def test_the_parents_records_give_nothing_to_read(name):
    """`mesh.ack_last_s` reads a field the driver writes on either
    tree; the other five read what this PR added to the program."""
    ctx = _ctx([PARENT_TICK] * 3, trace={"idle_share": 0.9})
    assert layers.read_metric(name, ctx) is None


@pytest.mark.parametrize("kid, name", [
    ("stage", "mesh.import_stage_ms"),
    ("dispatch", "mesh.import_dispatch_ms")])
def test_a_ticks_landings_are_summed_and_the_median_taken(kid, name):
    phase = f"global:import.land.{kid}"
    ticks = [_tick(phases=[(phase, 0, 10), (phase, 20, 40),
                           ("global:import.land", 0, 40)]),
             _tick(phases=[(phase, 0, 50)]),
             _tick(phases=[(phase, 5, 10), ("local:engine.drain", 0, 90)])]
    assert layers.read_metric(name, _ctx(ticks)) == pytest.approx(30.0)


@pytest.mark.parametrize("rows, want", [
    ([[236, 241, 250, 273]] * 3, 100.0 * 236 * 4 / 1000),
    # slots handed out in order: shard 0 holds every key
    ([[1008, 0, 0, 0]], 0.0),
    ([[25, 25, 25, 25], [10, 30, 30, 30], [0, 0, 0, 0]], 70.0),
    ([None, []], None),
], ids=["by_digest", "slot_order", "median_of_filled_ticks", "no_counter"])
def test_shard_fill_least(rows, want):
    ticks = [_tick(glob={} if r is None else {"mesh_shard_rows": r})
             for r in rows]
    got = layers.read_metric("mesh.shard_fill_least", _ctx(ticks))
    assert got == (want if want is None else pytest.approx(want))


def test_import_dispatches_is_the_median_over_ticks_that_count():
    ticks = [_tick(glob={"mesh_import_dispatches": n})
             for n in (146, 149, 0)] + [PARENT_TICK]
    assert layers.read_metric("mesh.import_dispatches",
                              _ctx(ticks)) == 146.0


@pytest.mark.parametrize("busy, want", [
    ({"0": 4.96, "1": 4.93, "2": 4.99, "3": 4.93}, 100.0 * 4.93 / 4.99),
    ({"0": 6.25, "1": 1.36, "2": 1.36, "3": 1.25}, 20.0),
    ({"0": 3.0}, None),                     # one chip: nothing to compare
    ({"0": 0.0, "1": 0.0}, None),           # nothing ran
    ({}, None),
], ids=["even", "a_local_tier_on_chip_0", "one_device", "idle", "empty"])
def test_device_busy_least(busy, want):
    got = layers.read_metric("mesh.device_busy_least",
                             _ctx([], trace={"busy_s_by_device": busy}))
    assert got == (want if want is None else pytest.approx(want))


def test_ack_last_reads_what_the_one_chip_cells_metric_reads():
    ticks = [_tick(acks={"first": 0.1, "median": 0.12, "last": last})
             for last in (0.15, 0.14, 0.19)] + [_tick(), _tick(acks={})]
    got = layers.read_metric("mesh.ack_last_s", _ctx(ticks))
    assert got == 0.15 == layers.read_metric("fanin.ack_last_s", _ctx(ticks))


def test_pr33s_cells_found_by_name():
    """What `test_perfbench_fixed_landing.py` asserts of PR 33's two
    cells and their mixes, with the cells found by name: that test takes
    them from the end of `workloads`, where this PR's two now stand, and
    its file is not this PR's to edit (`conftest.py` beside this file)."""
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
    # the new cells stand behind them, in the order ISSUE.md names them
    assert [w["name"] for w in MANIFEST["workloads"]][-4:] == [
        one_k, ten_k, FLEET, "mesh_global_4chip.wide_100k"]
    small, mix = harness.load_mix("fleet_1k"), harness.load_mix("fleet_10k")
    assert mix["timers"].pop("keys") == 10 * small["timers"].pop("keys")
    told = ("name", "why", "scale", "rehearsal")
    assert {k: v for k, v in mix.items() if k not in told} == \
        {k: v for k, v in small.items() if k not in told}
    assert all(mix[k] != small[k] for k in told)
    for cell in (one_k, ten_k, FLEET, "mesh_global_4chip.wide_100k"):
        assert [m["name"] for m in run.cell_metrics(
            MANIFEST, cell, "end_to_end")] == ["emit_latency_s", "setup_s"]
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, one_k, "per_layer")] == [m["name"] for m in run.cell_metrics(
            MANIFEST, ten_k, "per_layer")]
    checks.check_waiting_entries(MANIFEST)
    checks.check_every_cell_reports_what_the_contract_asks(MANIFEST)
    checks.check_every_entry_has_its_files(MANIFEST)

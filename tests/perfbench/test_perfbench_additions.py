"""The test that keeps the benchmark's tests open to additions.

A `model_config`, `tracing` or `perf_opt` PR may add files and entries
to the benchmark and may edit nothing that is there, `tests/perfbench/`
included. So a test there that holds BENCHMARK.json to today's census
(how many cells, how many metrics, an entry's absence, a shape inside
the program) refuses a sound later PR. This file builds the manifest
such a PR could write, in memory, over a copied `perfbench/` that holds
the new files:

  (a) the two waiting fan-in entries of
      `perfbench/study/fanin32.entries.json`, letter for letter;
  (b) a per-layer `program_counter` metric with a new reader file and
      no `workloads` list;
  (c) a per-layer `program_span` metric with a new reader file and a
      `workloads` list of one existing cell;

and runs every check of `contract_checks.py`, the same functions the
tests of the real tree call, against it. Each check also still refuses
what it is there to refuse. Subprocess rehearsals stay out: a new cell
is rehearsed by `test_perfbench_rehearsal.py`, which is parametrised
over the manifest's cells, the day it is added.
"""

import copy
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import layers, run  # noqa: E402

COUNTER, SPAN = "added.ticks_counted", "added.sink_flush_ms"
SPAN_CELL = "two_tier_1chip.steady_10k"
FOUR = ["two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
        "mesh_global_4chip.steady_10k", "two_tier_1chip.hot_1k"]


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """(manifest, root): BENCHMARK.json plus (a), (b) and (c), and a
    copy of `perfbench/`'s directories of files found by name with the
    two new readers in it. Nothing that was there is edited."""
    root = tmp_path_factory.mktemp("additions") / "perfbench"
    for d in ("configs", "mixes", "metrics", "drivers", "generators",
              "study"):
        shutil.copytree(os.path.join(REPO, "perfbench", d), root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "metrics" / (COUNTER + ".py")).write_text(
        '"""Timed ticks of the run: a count a rehearsal prints too."""\n\n\n'
        "def read(ctx):\n"
        "    return float(len(ctx['ticks'])) if ctx['ticks'] else None\n")
    (root / "metrics" / (SPAN + ".json")).write_text(json.dumps({
        "name": SPAN, "unit": "ms", "layer": "global flush",
        "moves": "emit_latency_s", "source": "program_span",
        "read": {"from": "phases", "names": ["global:sink.flush"],
                 "reduce": "max", "scale": 1000}}))
    manifest = checks.merged(run.load_manifest(),
                             checks.waiting_entries(str(root)))
    manifest["per_layer"] += [
        {"name": COUNTER, "unit": "ticks", "better": "higher",
         "source": "program_counter", "layer": "host",
         "moves": "emit_latency_s"},
        {"name": SPAN, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "global flush",
         "moves": "emit_latency_s", "workloads": [SPAN_CELL]}]
    yield manifest, str(root)
    assert all(p.read_bytes() == b for p, b in before.items())


def test_the_additions_are_what_this_file_says(added):
    manifest, root = added
    real = run.load_manifest()
    for group, was in real.items():
        if group in ("configs", "workloads", "per_layer"):
            assert manifest[group][:len(was)] == was
        else:
            assert manifest[group] == was
    assert checks.FANIN_CELL in checks.cell_names(manifest)
    assert [m["name"] for m in manifest["per_layer"][-2:]] == [COUNTER, SPAN]
    ctx = {"ticks": [{"phase_rows": [("global:sink.flush", 0, int(3e6))]}] * 2,
           "trace": None, "device": {}, "run": {}}
    assert layers.read_metric(COUNTER, ctx, root=root) == 2.0
    assert layers.read_metric(SPAN, ctx, root=root) == 3.0


@pytest.mark.parametrize("check", [
    checks.check_top_level,
    checks.check_names_units_and_entries,
    checks.check_every_cell_reports_what_the_contract_asks,
    checks.check_overflow_rows_entry,
], ids=lambda f: f.__name__)
def test_the_contracts_letter_takes_the_additions(added, check):
    check(added[0])


@pytest.mark.parametrize("check", [
    checks.check_every_entry_has_its_files,
    checks.check_drivers_and_generators_fit,
    checks.check_waiting_entries,
    checks.check_the_fan_in_cell_is_asked_for_no_metric_of_an_absent_tier,
], ids=lambda f: f.__name__)
def test_the_files_found_by_name_take_the_additions(added, check):
    check(*added)


def test_the_floor_of_pr27_takes_the_additions(added):
    manifest, root = added
    files = checks.goldens()
    was = files["golden_rehearsal_pr27.json"]["reports"]
    assert sorted(was) == sorted(FOUR)
    checks.check_the_four_cells_report_at_least_what_they_reported(
        manifest, was, root)
    checks.check_golden_coverage(manifest, files)
    # a later PR's golden file for its own cell is one more file
    more = dict(files, **{"golden_rehearsal_later.json": {"runs": {
        checks.FANIN_CELL + "@1": {}, checks.FANIN_CELL + "@2": {}}}})
    checks.check_golden_coverage(manifest, more)
    assert len(checks.golden_runs(more)) == len(checks.golden_runs(files)) + 2


def test_every_metric_is_reported_where_it_says(added):
    manifest, _root = added
    cells = {m["name"]: checks.check_reported_where_it_says(
        manifest, m["name"]) for m in manifest["per_layer"]}
    every = checks.cell_names(manifest)
    assert cells[COUNTER] == every and cells[SPAN] == [SPAN_CELL]
    # a metric of the forward is still the four cells', and not asked
    # of a cell without a forward
    assert set(FOUR) <= set(cells["forward.export_ms"])
    assert checks.FANIN_CELL not in cells["forward.export_ms"]
    assert cells["import.route_ms"] == every
    assert checks.FANIN_CELL not in cells["ingest.pump_batches"]


def test_a_rehearsals_line_may_print_an_added_count(added):
    manifest, _root = added
    for key, want in checks.golden_runs(checks.goldens()).items():
        cell = key.split("@")[0]
        units = dict(want["metric_units"])
        checks.check_printed_units(manifest, cell, units, units)
        checks.check_printed_units(manifest, cell,
                                   dict(units, **{COUNTER: "ticks"}), units)
        with pytest.raises(AssertionError):       # a time from a CPU run
            checks.check_printed_units(
                manifest, cell, dict(units, **{SPAN: "ms"}), units)
        with pytest.raises(AssertionError):       # a unit that moved
            checks.check_printed_units(
                manifest, cell, dict(units, **{COUNTER: "s"}), units)
        lost = dict(units)
        lost.pop("compile.in_window")
        with pytest.raises(AssertionError):       # a metric that went
            checks.check_printed_units(manifest, cell, lost, units)


def test_a_rehearsal_is_asked_only_what_its_cell_has(added):
    manifest, root = added
    fanin = checks.rehearsal_expectations(manifest, checks.FANIN_CELL, root)
    assert "forward.tick_bytes" not in fanin["counts"]
    assert fanin["chips"] == 1
    assert fanin["global_devices"] == 1
    assert {COUNTER, "compile.in_window"} <= fanin["counts"]
    assert not [n for n in fanin["counts"]
                if n.startswith(checks.LOCAL_TIER)]
    assert fanin["env"] == {"PYTHONHASHSEED": "0"}
    steady = checks.rehearsal_expectations(manifest, SPAN_CELL, root)
    assert {"forward.tick_bytes", COUNTER} <= steady["counts"]
    assert SPAN not in steady["counts"]            # a time, not a count
    mesh = checks.rehearsal_expectations(
        manifest, "mesh_global_4chip.steady_10k", root)
    assert mesh["chips"] == mesh["global_devices"] == 4


def test_landing_shapes_are_the_programs_and_only_their_form_is_held():
    checks.check_landing_shapes([
        {"timed": True},                                  # lands another way
        {"timed": True, "landing_shapes": []},
        {"timed": False, "landing_shapes": [[40, 512], [40, 640]]},
        {"timed": True, "landing_shapes": [[984, 512], [1000, 2048]]}])
    for bad in ([[40]], [[40, 0]], [[40, 512.0]], [[40, 512, 1]]):
        with pytest.raises(AssertionError):
            checks.check_landing_shapes([{"landing_shapes": bad}])


# ------------------------------- and each check still refuses what it should

def altered(manifest, fn):
    out = copy.deepcopy(manifest)
    fn(out)
    return out


def test_the_checks_still_refuse_a_manifest_at_fault(added):
    manifest, root = added
    files = checks.goldens()
    was = files["golden_rehearsal_pr27.json"]["reports"]

    def drop_cell(m):
        m["workloads"] = [w for w in m["workloads"]
                          if w["name"] != "two_tier_1chip.hot_1k"]
        for e in m["end_to_end"] + m["per_layer"]:
            if "workloads" in e:
                e["workloads"] = [c for c in e["workloads"]
                                  if c != "two_tier_1chip.hot_1k"]

    gone = altered(manifest, drop_cell)
    with pytest.raises(AssertionError):
        checks.check_the_four_cells_report_at_least_what_they_reported(
            gone, was, root)
    with pytest.raises(AssertionError):
        checks.check_golden_coverage(gone, files)

    def drop_metric(m):
        m["per_layer"] = [e for e in m["per_layer"]
                          if e["name"] != "import.land_ms"]

    with pytest.raises(AssertionError):
        checks.check_the_four_cells_report_at_least_what_they_reported(
            altered(manifest, drop_metric), was, root)

    def swap_order(m):
        names = [e["name"] for e in m["per_layer"]]
        i, j = names.index("global.import_s"), names.index("global.flush_s")
        m["per_layer"][i], m["per_layer"][j] = (m["per_layer"][j],
                                                m["per_layer"][i])

    with pytest.raises(AssertionError):
        checks.check_the_four_cells_report_at_least_what_they_reported(
            altered(manifest, swap_order), was, root)

    def forward_asked_of_the_fan_in(m):
        next(e for e in m["per_layer"] if e["name"] == "forward.rpc_ms")[
            "workloads"].append(checks.FANIN_CELL)

    asked = altered(manifest, forward_asked_of_the_fan_in)
    with pytest.raises(AssertionError):
        checks.check_the_four_cells_report_at_least_what_they_reported(
            asked, was, root)
    with pytest.raises(AssertionError):
        checks.check_the_fan_in_cell_is_asked_for_no_metric_of_an_absent_tier(
            asked, root)

    def entry_not_letter_for_letter(m):
        next(w for w in m["workloads"]
             if w["name"] == checks.FANIN_CELL)["why"] += "."

    with pytest.raises(AssertionError):
        checks.check_waiting_entries(
            altered(manifest, entry_not_letter_for_letter), root)

    def source_taken_twice(m):
        m["configs"][0]["source"] = checks.waiting_entries(root)[
            "configs"][0]["source"]

    with pytest.raises(AssertionError):
        checks.check_waiting_entries(altered(manifest, source_taken_twice),
                                     root)

    def metric_without_a_reader(m):
        m["per_layer"].append({
            "name": "added.no_reader", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "host",
            "moves": "emit_latency_s"})

    with pytest.raises(AssertionError):
        checks.check_every_entry_has_its_files(
            altered(manifest, metric_without_a_reader), root)

    def moves_what_its_cell_does_not_report(m):
        m["per_layer"].append({
            "name": COUNTER + "2", "unit": "ticks", "better": "higher",
            "source": "program_counter", "layer": "host",
            "moves": "ingest_rate", "workloads": [checks.FANIN_CELL]})

    with pytest.raises(AssertionError):
        checks.check_every_cell_reports_what_the_contract_asks(
            altered(manifest, moves_what_its_cell_does_not_report))

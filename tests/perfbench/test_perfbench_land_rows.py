"""`import.land_rows` (PR 32): rows the global's import landings took
through a work set in a tick, `flush_path.global.import_land_rows`,
the median over the timed ticks. The reader takes hand-made ticks
(present, absent, in some ticks only), agrees with its BENCHMARK.json
entry, and a rehearsal of each cell the entry lists prints it. A
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
from perfbench import layers, run  # noqa: E402

NAME = "import.land_rows"
MANIFEST = run.load_manifest()
ENTRY = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)


def _tick(glob):
    return {"flush_path": {"local": {}, "global": glob},
            "phase_rows": [], "spans": {}, "counters": {}}


@pytest.mark.parametrize("ticks, want", [
    # wide_100k's: thirteen landings a tick, every key once
    ([{"import_land_rows": 100000, "import_land_bank": 0}] * 4, 100000.0),
    ([{"import_land_rows": 10000, "import_land_bank": 0},
      {"import_land_rows": 9990, "import_land_bank": 0},
      {"import_land_rows": 10000, "import_land_bank": 0}], 10000.0),
    # a bank no larger than the smallest set: whole-bank passes only
    ([{"import_land_rows": 0, "import_land_bank": 2}] * 3, 0.0),
    # the parent commit's ticks: a flush path without the counter
    ([{"path": "incremental", "import_batches": 2,
       "import_metrics": 10300}] * 3, None),
    # a tier that counts in some ticks only reads those
    ([{"path": "full"}, {"path": "full", "import_land_rows": 1000,
                         "import_land_bank": 0}], 1000.0),
    ([], None),
], ids=["every_key", "median", "whole_bank_only", "no_counter",
        "some_ticks", "no_ticks"])
def test_reader_takes_the_median_over_ticks(ticks, want):
    ctx = {"ticks": [_tick(t) for t in ticks], "trace": None,
           "device": {}, "run": {}}
    assert layers.read_metric(NAME, ctx) == want


def test_reader_does_not_raise_on_a_tick_without_a_flush_path():
    ctx = {"ticks": [{"phase_rows": [], "spans": {}, "counters": {}},
                     {"flush_path": {"local": {}}}],
           "trace": None, "device": {}, "run": {}}
    assert layers.read_metric(NAME, ctx) is None


def test_entry_is_the_imports_and_reported_where_it_says():
    assert {k: v for k, v in ENTRY.items() if k != "workloads"} == {
        "name": NAME, "unit": "rows", "better": "lower",
        "source": "program_counter", "layer": "import",
        "moves": "emit_latency_s"}
    cells = checks.check_reported_where_it_says(MANIFEST, NAME)
    # the cells whose global lands through `_land_imports_clustered`;
    # the mesh engine lands its own way and its counter stays at 0
    assert {"two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
            "two_tier_1chip.hot_1k"} <= set(cells)
    assert "mesh_global_4chip.steady_10k" not in cells
    # a count: a CPU rehearsal of a cell that lists it may print it
    for cell in cells:
        assert NAME in checks.counts_of(MANIFEST, cell)
    checks.check_every_cell_reports_what_the_contract_asks(MANIFEST)
    checks.check_every_entry_has_its_files(MANIFEST)


@pytest.fixture(scope="module")
def jax_cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.mark.parametrize("cell", ENTRY["workloads"])
def test_a_rehearsal_prints_it_and_every_tick_carries_both_counts(
        cell, tmp_path, jax_cache):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=jax_cache)
    ticks = tmp_path / "ticks.jsonl"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", cell, "--seed", "3200000032", "--seconds", "1",
         "--trace", "1", "--rehearsal", "--ticks-out", str(ticks)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    rows = [json.loads(ln) for ln in ticks.read_text().splitlines()]
    glob = [r["flush_path"]["global"] for r in rows if r["timed"]]
    assert glob
    # every tick forwards timers and the global lands them. Which arm a
    # landing takes at the rehearsal's bank is the program's (at PR 32:
    # no work set is smaller than 512 slots, so whole-bank passes only
    # and `import_land_rows` 0); the two counts are there either way
    for t in glob:
        assert isinstance(t["import_land_rows"], int)
        assert isinstance(t["import_land_bank"], int)
        assert t["import_land_rows"] >= 0 and t["import_land_bank"] >= 0
        assert t["import_land_rows"] + t["import_land_bank"] >= 1
    got = line["metrics"][NAME]
    assert got["unit"] == "rows"
    assert got["value"] == layers.read_metric(NAME, {
        "ticks": [r for r in rows if r["timed"]], "trace": None,
        "device": {}, "run": {}})
    # the local tier lands no import
    assert all(r["flush_path"]["local"].get("import_land_rows", 0) == 0
               and r["flush_path"]["local"].get("import_land_bank", 0) == 0
               for r in rows)

"""`import.batch_sketches` (PR 30): forwarded sketches per import batch
the global's engine applied in a tick, `import_metrics / import_batches`
of `flush_path.global`, the median over the timed ticks. The reader
takes hand-made ticks (present, absent, zero batches), agrees with its
BENCHMARK.json entry, and a rehearsal of each cell the entry lists
prints it. A rehearsal is a CPU run: it proves names and counts, never
a time."""

import json
import os
import statistics
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import layers, run  # noqa: E402

NAME = "import.batch_sketches"
MANIFEST = run.load_manifest()
ENTRY = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)


def _tick(glob):
    return {"flush_path": {"local": {}, "global": glob},
            "phase_rows": [], "spans": {}, "counters": {}}


@pytest.mark.parametrize("ticks, want", [
    # steady_10k: 10,300 sketches in two requests, tick after tick
    ([{"import_batches": 2, "import_metrics": 10300}] * 3, 5150.0),
    # the median over ticks of each tick's own ratio
    ([{"import_batches": 16, "import_metrics": 103000},
      {"import_batches": 1, "import_metrics": 2500},
      {"import_batches": 2, "import_metrics": 6000}], 3000.0),
    # a program that hands sketches over one by one
    ([{"import_batches": 10300, "import_metrics": 10300}] * 2, 1.0),
    # the parent commit's ticks: a flush path without the counter
    ([{"path": "incremental", "dirty": [48, 2, 2, 2]}] * 3, None),
    # a tick in which nothing was forwarded is left out, not a zero
    ([{"import_batches": 0, "import_metrics": 0},
      {"import_batches": 4, "import_metrics": 4440}], 1110.0),
    ([{"import_batches": 0, "import_metrics": 0}] * 2, None),
    ([], None),
], ids=["present", "median_of_ratios", "one_by_one", "no_counter",
        "idle_tick_left_out", "zero_batches", "no_ticks"])
def test_reader_takes_the_median_of_the_ticks_ratios(ticks, want):
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
        "name": NAME, "unit": "sketches", "better": "higher",
        "source": "program_counter", "layer": "import",
        "moves": "emit_latency_s"}
    cells = checks.check_reported_where_it_says(MANIFEST, NAME)
    assert {"two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
            "mesh_global_4chip.steady_10k", "two_tier_1chip.hot_1k"} \
        <= set(cells)
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
         "--workload", cell, "--seed", "3000000030", "--seconds", "1",
         "--trace", "1", "--rehearsal", "--ticks-out", str(ticks)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    rows = [json.loads(ln) for ln in ticks.read_text().splitlines()]
    glob = [r["flush_path"]["global"] for r in rows if r["timed"]]
    assert glob
    # every tick forwards, and a request reaches the engine as one
    # batch: as many batches as the local's send made chunks (one or
    # two at rehearsal size), never one a sketch
    for t in glob:
        assert isinstance(t["import_batches"], int)
        assert isinstance(t["import_metrics"], int)
        assert 1 <= t["import_batches"] <= 4 < t["import_metrics"]
    assert line["metrics"][NAME] == {
        "value": float(statistics.median(
            t["import_metrics"] / t["import_batches"] for t in glob)),
        "unit": "sketches"}
    # the local tier imports nothing
    assert all(r["flush_path"]["local"].get("import_batches", 0) == 0
               for r in rows)

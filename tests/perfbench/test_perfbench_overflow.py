"""`ingest.overflow_rows` (PR 27): the reader takes the median over the
timed ticks of `flush_path.local.overflow_rows`, leaves the metric out
on a program without the counter (the parent commit's case), agrees
with its BENCHMARK.json entry, and a rehearsal of a cell prints it. A
rehearsal is a CPU run: it proves names and counts, never a time."""

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

NAME = "ingest.overflow_rows"
MANIFEST = run.load_manifest()


def _tick(local):
    return {"flush_path": {"local": local, "global": {}},
            "phase_rows": [], "spans": {}, "counters": {}}


@pytest.mark.parametrize("ticks, want", [
    ([{"overflow_rows": 812, "overflow_bank": 0},
      {"overflow_rows": 790, "overflow_bank": 0},
      {"overflow_rows": 805, "overflow_bank": 0}], 805.0),
    ([{"overflow_rows": 0, "overflow_bank": 8},
      {"overflow_rows": 0, "overflow_bank": 9}], 0.0),
    # the parent commit's ticks: a flush path without the counter
    ([{"path": "incremental", "dirty": [48, 2, 2, 2]}] * 3, None),
    # a tier that counts in some ticks only reads those
    ([{"path": "full"}, {"path": "full", "overflow_rows": 7,
                         "overflow_bank": 0}], 7.0),
    ([], None),
], ids=["median", "whole_bank_only", "no_counter", "some_ticks", "no_ticks"])
def test_reader_takes_the_median_over_ticks(ticks, want):
    ctx = {"ticks": [_tick(t) for t in ticks], "trace": None,
           "device": {}, "run": {}}
    assert layers.read_metric(NAME, ctx) == want


def test_reader_does_not_raise_on_a_tick_without_a_flush_path():
    ctx = {"ticks": [{"phase_rows": [], "spans": {}, "counters": {}}],
           "trace": None, "device": {}, "run": {}}
    assert layers.read_metric(NAME, ctx) is None


def test_entry_is_the_ingest_layers_and_reported_where_ingest_rate_is():
    """It came after every metric that was there before it (later PRs
    append behind it) and is reported by the cells that report
    `ingest_rate`, `steady_10k` and `hot_1k` among them."""
    checks.check_overflow_rows_entry(MANIFEST)


def test_a_rehearsal_prints_it_and_every_tick_carries_both_counts(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    ticks = tmp_path / "ticks.jsonl"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", "two_tier_1chip.hot_1k", "--seed", "2700000027",
         "--seconds", "1", "--trace", "1", "--rehearsal",
         "--ticks-out", str(ticks)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    rows = [json.loads(ln) for ln in ticks.read_text().splitlines()]
    local = [r["flush_path"]["local"] for r in rows if r["timed"]]
    assert local
    # every tick carries both counts, and the line prints the median of
    # the one the metric reads. Which arm the program's overflow takes
    # at the rehearsal's 512-slot bank is the program's (at PR 27:
    # whole-bank passes only, `overflow_rows` 0)
    for t in local:
        assert isinstance(t["overflow_rows"], int) and t["overflow_rows"] >= 0
        assert isinstance(t["overflow_bank"], int) and t["overflow_bank"] >= 0
    assert sum(t["overflow_rows"] + t["overflow_bank"] for t in local) >= 1
    assert line["metrics"][NAME] == {
        "value": float(statistics.median(t["overflow_rows"] for t in local)),
        "unit": "rows"}
    assert all("overflow_rows" not in r["flush_path"]["global"]
               or r["flush_path"]["global"]["overflow_rows"] == 0
               for r in rows)

"""The five readers PR 39 added for `import.apply` from inside:
`import.apply_decode_ms`, `import.apply_lock_wait_ms`,
`import.apply_stage_ms`, `import.apply_cpu_share` and
`import.sketch_us` (`perfbench/apply_split.py` has what they share).
Each agrees with its BENCHMARK.json entry; each takes hand-made ticks
and reads the arithmetic its file says; on records as the parent commit
writes them (an `import.apply` run without children, a flush note
without the two CPU counters) each finds nothing to read and raises
nothing, which is what lets the parent's lines lack them; and each
finds its names in the ticks a rehearsal of a two-tier cell, of a
fan-in cell and of a mesh cell really writes. A rehearsal is a CPU
run: it proves names and arithmetic, never a time, and its line prints
none of the five."""

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import apply_split, layers, run  # noqa: E402

MANIFEST = run.load_manifest()
MS = 1_000_000
EIGHT = ["two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
         "mesh_global_4chip.steady_10k", "two_tier_1chip.hot_1k",
         "fanin32_global_1chip.fleet_1k", "fanin32_global_1chip.fleet_10k",
         "fanin32_mesh_global_4chip.fleet_1k",
         "mesh_global_4chip.wide_100k"]
# name -> (unit, better, source, reader file's extension)
ENTRIES = {
    "import.apply_decode_ms": ("ms", "lower", "program_span", ".json"),
    "import.apply_lock_wait_ms": ("ms", "lower", "program_span", ".py"),
    "import.apply_stage_ms": ("ms", "lower", "program_span", ".json"),
    "import.apply_cpu_share": ("%", "higher", "host_clock", ".py"),
    "import.sketch_us": ("us", "lower", "program_span", ".py"),
}
DECODE, WAIT, STAGE = (apply_split.DECODE, apply_split.LOCK_WAIT,
                       apply_split.STAGE)
REHEARSED = ["two_tier_1chip.steady_10k", "fanin32_global_1chip.fleet_1k",
             "mesh_global_4chip.steady_10k"]


def _ctx(ticks):
    return {"ticks": ticks, "trace": None, "device": {}, "run": {}}


def _tick(glob=None, phases=()):
    return {"flush_path": {"global": glob or {}}, "spans": {}, "counters": {},
            "phase_rows": [(name, a * MS, b * MS) for name, a, b in phases]}


def _read(name, ticks):
    return layers.read_metric(name, _ctx(ticks))


# a tick as the parent commit records it: the worker's run and a
# landing, no child of the run, a flush note without the CPU counters
PARENT_TICK = _tick(glob={"path": "incremental", "import_batches": 32,
                          "import_metrics": 35520},
                    phases=[("global:import.apply", 0, 770),
                            ("global:import.land", 100, 150),
                            ("global:import.land.stage", 100, 120)])


@pytest.mark.parametrize("name", list(ENTRIES))
def test_entry_and_reader(name):
    unit, better, source, ext = ENTRIES[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "import", "moves": "emit_latency_s"}
    # all eight cells there were, in the manifest's order; a later cell
    # may be appended behind them
    assert entry["workloads"][:8] == EIGHT
    assert checks.check_reported_where_it_says(MANIFEST, name) == [
        c for c in checks.cell_names(MANIFEST) if c in entry["workloads"]]
    assert os.path.exists(os.path.join(REPO, "perfbench", "metrics",
                                       name + ext))
    # a time or a share of the host's clocks: a rehearsal prints none
    assert source != "program_counter"
    for cell in EIGHT:
        assert name not in checks.counts_of(MANIFEST, cell)
    assert _read(name, []) is None


def test_the_five_stand_at_the_end_in_the_order_named():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index("import.apply_decode_ms")
    assert names[first:first + 5] == list(ENTRIES)
    checks.check_every_cell_reports_what_the_contract_asks(MANIFEST)
    checks.check_every_entry_has_its_files(MANIFEST)


@pytest.mark.parametrize("name", list(ENTRIES))
def test_the_parents_records_give_nothing_to_read(name):
    assert _read(name, [PARENT_TICK] * 3) is None
    # nor does a tick without a flush path, or without anything
    assert _read(name, [{"phase_rows": []}, {"flush_path": None,
                                             "phase_rows": []}]) is None


@pytest.mark.parametrize("name, phase", [
    ("import.apply_decode_ms", DECODE), ("import.apply_stage_ms", STAGE)])
def test_a_ticks_requests_are_summed_and_the_median_taken(name, phase):
    ticks = [_tick(phases=[(phase, 0, 10), (phase, 20, 40),
                           ("global:import.apply", 0, 50)]),
             _tick(phases=[(phase, 0, 50)]),
             _tick(phases=[(phase, 5, 10), ("global:import.land", 6, 9)]),
             PARENT_TICK]
    assert _read(name, ticks) == pytest.approx(30.0)


def test_lock_wait_reads_zero_where_the_harness_kept_no_row():
    """`server_phases` drops a row with t1 == t0: a tick that decoded
    and shows no lock_wait waited 0.0 ms; a tick that decoded nothing
    is left out."""
    waited = _tick(phases=[(DECODE, 0, 4), (WAIT, 4, 6), (STAGE, 6, 9),
                           (DECODE, 10, 14), (WAIT, 14, 15)])
    none = _tick(phases=[(DECODE, 0, 4), (STAGE, 4, 9)])
    assert _read("import.apply_lock_wait_ms", [none] * 3) == 0.0
    assert _read("import.apply_lock_wait_ms",
                 [waited, none, waited, PARENT_TICK]) == pytest.approx(3.0)
    assert _read("import.apply_lock_wait_ms", [waited, none]) \
        == pytest.approx(1.5)


def test_sketch_us_reads_the_arithmetic_it_says():
    """3 ms of decode and 5 ms of stage over 400 sketches: 20 us."""
    t = _tick(glob={"import_batches": 2, "import_metrics": 400},
              phases=[(DECODE, 0, 1), (WAIT, 1, 2), (STAGE, 2, 4),
                      (DECODE, 5, 7), (STAGE, 7, 10),
                      ("global:import.apply", 0, 11)])
    assert _read("import.sketch_us", [t]) == pytest.approx(20.0)
    dearer = _tick(glob={"import_metrics": 100},
                   phases=[(DECODE, 0, 1), (STAGE, 1, 4)])    # 40 us
    idle = _tick(glob={"import_metrics": 0}, phases=[(DECODE, 0, 1)])
    uncounted = _tick(phases=[(DECODE, 0, 1), (STAGE, 1, 4)])
    assert _read("import.sketch_us", [t, dearer, idle, uncounted,
                                      PARENT_TICK]) == pytest.approx(30.0)
    assert _read("import.sketch_us", [idle, uncounted]) is None


def test_cpu_share_is_cpu_seconds_over_wall_seconds_of_the_same_ticks():
    t = _tick(glob={"import_decode_cpu_ns": 2 * MS,
                    "import_stage_cpu_ns": 4 * MS},
              phases=[(DECODE, 0, 3), (WAIT, 3, 103), (STAGE, 103, 108)])
    assert _read("import.apply_cpu_share", [t]) == pytest.approx(75.0)
    # summed over the ticks, not a median of shares; the wait is in
    # neither sum; a tick without the counters is in neither
    busy = _tick(glob={"import_decode_cpu_ns": 20 * MS,
                       "import_stage_cpu_ns": 4 * MS},
                 phases=[(DECODE, 0, 20), (STAGE, 20, 24)])
    uncounted = _tick(phases=[(DECODE, 0, 50), (STAGE, 50, 90)])
    assert _read("import.apply_cpu_share", [t, busy, uncounted,
                                            PARENT_TICK]) \
        == pytest.approx(100.0 * 30 / 32)
    assert _read("import.apply_cpu_share", [uncounted]) is None


def test_a_row_of_ticks_out_reads_as_the_tick_record_does():
    """`--ticks-out` writes the phases' summed seconds under `phases`
    in `phase_rows`' place; the study reads those rows."""
    t = _tick(glob={"import_metrics": 400, "import_decode_cpu_ns": 2 * MS,
                    "import_stage_cpu_ns": 4 * MS},
              phases=[(DECODE, 0, 3), (WAIT, 3, 4), (STAGE, 4, 9)])
    row = {"flush_path": t["flush_path"],
           "phases": {DECODE: 0.003, WAIT: 0.001, STAGE: 0.005}}
    assert apply_split.split(row) == pytest.approx(apply_split.split(t))
    assert apply_split.split({"phases": {"global:import.apply": 0.7}}) \
        is None
    for name in ("import.apply_lock_wait_ms", "import.apply_cpu_share",
                 "import.sketch_us"):
        assert _read(name, [row]) == pytest.approx(_read(name, [t]))


# ------------------------------------------ the ticks a rehearsal writes

@pytest.fixture(scope="module")
def jax_cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


@pytest.mark.parametrize("cell", REHEARSED)
def test_the_readers_find_their_names_in_a_rehearsals_ticks(
        cell, tmp_path, jax_cache):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=jax_cache)
    out = tmp_path / "ticks.jsonl"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", cell, "--seed", "3900000039", "--seconds", "1",
         "--trace", "1", "--rehearsal", "--ticks-out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    assert not set(ENTRIES) & set(line["metrics"])      # no time printed
    rows = [json.loads(ln) for ln in out.read_text().splitlines()]
    timed = [r for r in rows if r["timed"]]
    assert timed
    for r in timed:
        # the JSON readers take rows of phases: one a name will do
        r["phase_rows"] = [(n, 0, round(s * 1e9))
                           for n, s in r["phases"].items()]
        s = apply_split.split(r)
        info = r["flush_path"]["global"]
        assert s and info["import_batches"] >= 1
        assert s["decode"] > 0 and s["stage"] > 0 and s["lock_wait"] >= 0
        # the children lie inside the worker's runs
        assert sum(s.values()) <= r["phases"]["global:import.apply"]
        assert all(info[k] > 0 for k in apply_split.CPU_NS)
        # nothing of the kind on a tier that imports nothing
        assert not [n for n in r["phases"]
                    if n.startswith("local:import.apply")]
    got = {name: _read(name, timed) for name in ENTRIES}
    assert all(v is not None and math.isfinite(v) for v in got.values())
    assert got["import.apply_decode_ms"] > 0
    assert got["import.apply_stage_ms"] > 0
    assert got["import.apply_lock_wait_ms"] >= 0
    assert got["import.sketch_us"] > 0
    # the CPU clock is read inside the wall clock's window; the margin
    # is the two clocks' resolution over a rehearsal's millisecond
    assert 0 < got["import.apply_cpu_share"] <= 101.0

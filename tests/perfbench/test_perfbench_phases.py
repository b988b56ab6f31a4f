"""The per-layer metrics that read the flight recorder's phases of
`forward.send`, the import, the mesh flush and the pump (PR 26; the
landing's two child phases since PR 29): each reader file loads, agrees
with its BENCHMARK.json entry, is reported by the cells its entry says
(its `workloads` list, or every cell that reports the end-to-end metric
it moves: however many cells the manifest has), names only phases a
rehearsal of its cells really produced, and returns nothing (never
raises) on ticks of a program that lacks the phases. A rehearsal is a
CPU run: it proves names and counts, never a time."""

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

RUN = os.path.join(REPO, "perfbench", "run.py")
MANIFEST = run.load_manifest()
ONE_CHIP, MESH = "two_tier_1chip.steady_10k", "mesh_global_4chip.steady_10k"
NEW = ["forward.export_ms", "forward.serialize_ms", "forward.rpc_ms",
       "import.decode_ms", "import.route_ms", "import.apply_ms",
       "import.land_ms", "mesh.flush_device_ms", "ingest.pump_dispatch_ms",
       "ingest.pump_batches", "import.land_stage_ms",
       "import.land_cluster_ms"]
# the mesh engine stamps `import.land` and neither of its children
LANDING_CHILDREN = ("import.land_stage_ms", "import.land_cluster_ms")
ENTRY = {m["name"]: m for m in MANIFEST["per_layer"]}


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """{cell: (result line, [timed tick rows of --ticks-out])}."""
    tmp = tmp_path_factory.mktemp("perfbench_phases")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"))
    out = {}
    for cell in (ONE_CHIP, MESH):
        ticks = tmp / f"{cell}.jsonl"
        p = subprocess.run(
            [sys.executable, RUN, "--workload", cell, "--seed", "2600000026",
             "--seconds", "1", "--trace", "1", "--rehearsal",
             "--ticks-out", str(ticks)],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
        assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
        rows = [json.loads(ln) for ln in ticks.read_text().splitlines()]
        out[cell] = (json.loads(p.stdout.strip().splitlines()[-1]),
                     [r for r in rows if r["timed"]])
    return out


@pytest.mark.parametrize("name", NEW)
def test_reader_agrees_with_its_entry_and_reads_real_phases(name, rehearsed):
    entry = ENTRY[name]
    cells = checks.check_reported_where_it_says(MANIFEST, name)
    if name == "mesh.flush_device_ms":
        assert MESH in cells and ONE_CHIP not in cells
    elif entry["moves"] == "ingest_rate" or name in LANDING_CHILDREN:
        assert MESH not in cells and ONE_CHIP in cells
    else:
        assert MESH in cells and ONE_CHIP in cells
    base = os.path.join(REPO, "perfbench", "metrics", name)
    if os.path.exists(base + ".py"):
        assert entry["source"] == "program_counter"
        return
    spec = layers.load_metric(name)
    assert {k: spec[k] for k in ("unit", "layer", "moves", "source")} == {
        k: entry[k] for k in ("unit", "layer", "moves", "source")}
    read = spec["read"]
    assert read["from"] == "phases" and read["scale"] == 1000
    for cell in (c for c in cells if c in rehearsed):
        _line, ticks = rehearsed[cell]
        assert ticks
        for t in ticks:
            missing = [n for n in read["names"] if n not in t["phases"]]
            assert not missing, (cell, t["index"], missing)


def test_pump_batches_counts_rows_and_a_rehearsal_prints_it(rehearsed):
    line, ticks = rehearsed[ONE_CHIP]
    got = line["metrics"]["ingest.pump_batches"]
    assert got["unit"] == "batches" and got["value"] >= 1
    # counts only: no time of the new metrics in a CPU run's line
    assert not any(k.endswith("_ms") for k in line["metrics"])
    assert "ingest.pump_batches" not in rehearsed[MESH][0]["metrics"]
    assert all("local:ingest.pump.batch" in t["phases"] for t in ticks)
    row = ("local:ingest.pump.batch", 10, 20)
    ctx = {"ticks": [{"phase_rows": [row] * 3 + [("local:engine", 0, 9)]},
                     {"phase_rows": [row] * 5},
                     {"phase_rows": [row] * 4}]}
    assert layers.read_metric("ingest.pump_batches", ctx) == 4.0


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_a_program_without_the_phases(name):
    """The driver lays these files over the parent commit too: there
    the phases do not exist, the reader returns None and the line
    leaves the metric out."""
    old = [("local:forward.send", 0, 50), ("global:engine.flush", 60, 90)]
    ctx = {"ticks": [{"phase_rows": old, "spans": {}, "counters": {}}] * 2,
           "trace": None, "device": {}, "run": {}}
    if name in ("forward.rpc_ms", "mesh.flush_device_ms"):
        # the parent has egress.attempt and the one-chip device phases
        ctx["ticks"] = [{**t, "phase_rows": old + [
            ("local:egress.attempt", 10, 40),
            ("global:engine.device.exec", 70, 80)]} for t in ctx["ticks"]]
        assert layers.read_metric(name, ctx) > 0
    else:
        assert layers.read_metric(name, ctx) is None

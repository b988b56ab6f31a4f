"""The three per-layer metrics of the import landing (PR 29), the
yardstick for a landing at fixed shapes and for a compress of the
touched rows only: `import.land_stage_ms` and `import.land_cluster_ms`
read the child phases PR 26 stamped inside `import.land`;
`import.compress_device_ms` sums the device time of the standalone
whole-bank compress, `jit__compress_impl`, over the timed ticks of a
traced run. Each agrees with its BENCHMARK.json entry, reads rows whose
answer is known by hand, and reports nothing where there is nothing to
read: an engine that stamps no child phase, a run without a trace, a
trace in which the program never ran (the mesh engine's)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import layers, run, tracered  # noqa: E402

MANIFEST = run.load_manifest()
ENTRY = {m["name"]: m for m in MANIFEST["per_layer"]}
PHASE = {"import.land_stage_ms": "global:import.land.stage",
         "import.land_cluster_ms": "global:import.land.cluster"}
COMPRESS = "import.compress_device_ms"
MS = 1_000_000


def ctx_of(ticks, trace=None):
    return {"ticks": ticks, "trace": trace, "device": {}, "run": {}}


@pytest.mark.parametrize("name", [*PHASE, COMPRESS])
def test_entry_is_the_imports_and_lists_the_cells_that_can_read_it(name):
    entry = ENTRY[name]
    assert entry["layer"] == "import" and entry["moves"] == "emit_latency_s"
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["source"] == ("device_trace" if name == COMPRESS
                               else "program_span")
    cells = checks.check_reported_where_it_says(MANIFEST, name)
    # every cell it lists has a one-device global: the mesh engine
    # stamps no child phase and runs no standalone compress, and a line
    # that lacks a listed metric is refused
    for cell in cells:
        assert checks.rehearsal_expectations(
            MANIFEST, cell)["global_devices"] == 1, cell
    assert {"two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
            "two_tier_1chip.hot_1k"} <= set(cells)
    if name != COMPRESS:
        spec = layers.load_metric(name)
        assert {k: spec[k] for k in ("unit", "layer", "moves", "source")} \
            == {k: entry[k] for k in ("unit", "layer", "moves", "source")}
        assert spec["read"] == {"from": "phases", "names": [PHASE[name]],
                                "reduce": "median", "scale": 1000}


@pytest.mark.parametrize("name", list(PHASE))
def test_a_child_phase_is_summed_a_tick_and_the_median_taken(name):
    row = PHASE[name]
    other = [p for p in PHASE.values() if p != row][0]
    ticks = [
        # two landings in the tick: 100 + 40 ms
        {"phase_rows": [(row, 0, 100 * MS), (row, 200 * MS, 240 * MS),
                        (other, 100 * MS, 190 * MS),
                        ("global:import.land", 0, 400 * MS)]},
        {"phase_rows": [(row, 0, 60 * MS)]},
        {"phase_rows": [(row, 0, 90 * MS), ("global:import.land", 0, MS)]},
        # a tick whose global landed nothing is left out, not a zero
        {"phase_rows": [("global:engine.flush", 0, 5 * MS)]}]
    assert layers.read_metric(name, ctx_of(ticks)) == pytest.approx(90.0)


@pytest.mark.parametrize("name", list(PHASE))
def test_an_engine_that_stamps_no_child_phase_gives_nothing_to_read(name):
    mesh = [{"phase_rows": [("global:import.land", 0, 600 * MS),
                            ("global:engine.device.exec", 0, 50 * MS)]}] * 3
    assert layers.read_metric(name, ctx_of(mesh)) is None
    assert layers.read_metric(name, ctx_of([])) is None


def hand_made_trace(modules):
    """One device, two timed ticks of 100 ms each (0-100, 200-300 on the
    trace's clock); `modules`: [[name, start_ms, dur_ms]]."""
    off = 7_000 * MS
    trace = {
        "device": {0: [["%fusion.1 = f32[8]{0} fusion(...)", 0, 10 * MS]]},
        "modules": {0: [[n, a * MS, d * MS] for n, a, d in modules]},
        "host": [[tracered.SYNC, -2 * MS, MS]]}
    bench = [(tracered.SYNC, -2 * MS + off, -1 * MS + off)]
    windows = [(off, 100 * MS + off), (200 * MS + off, 300 * MS + off)]
    return tracered.reduce_trace(trace, bench, [], windows)


def test_compress_device_time_is_the_programs_seconds_over_the_ticks():
    tr = hand_made_trace([
        ["jit__compress_impl(17105616013299372607)", 0, 20],
        ["jit__compress_impl(17105616013299372607)", 30, 22],
        ["jit__compress_impl(17105616013299372607)", 210, 21],
        # not the landing's: the ingest's landing program, the hot-slot
        # sidestep's compress (one underscore), the flush program
        ["jit_add_batch_impl(5)", 60, 30],
        ["jit_compress_impl(6)", 250, 40],
        ["jit_flush(99)", 295, 4],
        # between the ticks: outside every window, nobody's time
        ["jit__compress_impl(17105616013299372607)", 120, 50]])
    ticks = [{"phase_rows": []}, {"phase_rows": []}]
    assert tr["module_seconds"]["jit__compress_impl"] == pytest.approx(0.063)
    assert layers.read_metric(COMPRESS, ctx_of(ticks, tr)) \
        == pytest.approx(31.5)                    # 63 ms over two ticks


def test_compress_device_time_reports_nothing_without_the_program():
    ticks = [{"phase_rows": []}, {"phase_rows": []}]
    assert layers.read_metric(COMPRESS, ctx_of(ticks)) is None   # untraced
    mesh = hand_made_trace([["jit_merge(1)", 0, 40], ["jit_local(2)", 50, 30],
                            ["jit_compress_impl(6)", 210, 40]])
    assert layers.read_metric(COMPRESS, ctx_of(ticks, mesh)) is None
    tr = hand_made_trace([["jit__compress_impl(3)", 0, 20]])
    assert layers.read_metric(COMPRESS, ctx_of([], tr)) is None  # no tick

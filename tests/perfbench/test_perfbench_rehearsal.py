"""Each cell of BENCHMARK.json, rehearsed on the CPU the way the driver
runs it (one subprocess a run): the cell's file pair loads and drives
whole ticks end to end at tiny size, every tick checked against the
plain reference: with a local tier real sockets, the C++ bridge, the
gRPC forward and both flushes; the mesh cell on four virtual devices.
The test is parametrised over the manifest's cells, so a cell is
rehearsed in tier-1 the day it is added, and asks of a cell only what
that cell has (`contract_checks.rehearsal_expectations`): forwarded
bytes of a cell with a forward, a landing ladder of a one-device global.
A rehearsal is marked as one and prints no time, rate or device metric.
Off the chip the measuring path fails and prints no result."""

import json
import os
import subprocess
import sys

import pytest

import contract_checks as checks

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "perfbench", "run.py")
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perfbench_jax_cache"))


def run_cell(args, cache_dir, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    full.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir,
                BENCH_RUN="7", **env)
    return subprocess.run([sys.executable, RUN, *args], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=900)


def last_line(p):
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def failing(p):
    """The names of the compared numbers over their limits. The import
    landing's lane width (128 or 256, from the widest digest of a
    handful of hot keys) flips with thread timing, so set-up warms the
    widths the warm-up ticks did not meet (`Tiers.warm_landing_widths`).
    A rehearsal still proves the wiring only and leaves
    `compile.in_window` to the chip run, where a compile in a timed
    tick is not correct: a tier-1 test must not hang on thread timing."""
    return {ln.split()[1] for ln in p.stdout.splitlines()
            if ln.startswith("compared:") and ln.endswith("FAIL")
            } - {"compile.in_window"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(cell, cache_dir):
    want = checks.rehearsal_expectations(MANIFEST, cell)
    p = run_cell(["--workload", cell, "--seed", str(2**31 + 77),
                  "--seconds", "1", "--trace", "1", "--rehearsal"],
                 cache_dir)
    out = last_line(p)
    assert not failing(p) and out["rehearsal"] is True
    assert "compared: exact_mismatches = 0 " in p.stdout
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == want["chips"]
    assert "busy_s" not in out["device"] and "breakdown" not in out
    # counts only: never a time, a rate or a device metric from a CPU
    assert out["metrics"] and set(out["metrics"]) <= want["counts"]
    assert "compile.in_window" in out["metrics"]
    if "forward.tick_bytes" in want["counts"]:  # a cell with a forward
        assert out["metrics"]["forward.tick_bytes"]["value"] > 0
    else:
        assert "forward.tick_bytes" not in out["metrics"]
    for name, value in want["env"].items():    # re-executed itself
        assert f"{name} {value}" in p.stdout
    assert "timed ticks" in p.stdout
    # a one-device global lands imports through a lane-width ladder,
    # whose widths the warm-up ticks did not meet set-up warms (none is
    # left where they met them all); the mesh global has no ladder
    warmed = next(ln for ln in p.stdout.splitlines()
                  if ln.startswith("import landing: warmed"))
    assert want["global_devices"] == 1 or "warmed []" in warmed
    n = want["global_devices"]
    assert (f"every bank leaf of the global on {n} distinct device(s): "
            f"found {n}") in p.stdout


def test_untraced_rehearsal_prints_no_end_to_end_number(cache_dir):
    p = run_cell(["--workload", CELLS[0], "--seed", "3", "--seconds", "1",
                  "--trace", "0", "--rehearsal"], cache_dir)
    out = last_line(p)
    assert not failing(p) and out["metrics"] == {}


def test_off_the_chip_the_measuring_path_fails(cache_dir):
    p = run_cell(["--workload", CELLS[0], "--seed", "3", "--seconds", "1",
                  "--trace", "0"], cache_dir)
    assert p.returncode != 0
    assert "needs 1 TPU chip(s)" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_unknown_cell_is_refused(cache_dir):
    p = run_cell(["--workload", "no.such_cell", "--seed", "3", "--seconds",
                  "1", "--trace", "0"], cache_dir)
    assert p.returncode != 0 and "no cell" in p.stderr
    assert p.stdout.strip() == ""


def test_the_lower_precision_control_comes_out_not_correct(cache_dir):
    """The control at a size a test can hold: extremes kept in bfloat16
    in the program's place break the exact min/max and nothing else.
    (The other control, a digest of compression 20, separates only at
    the cell's own 2,000 samples a key: PERF.md has its chip readings.)"""
    p = run_cell(["--workload", "two_tier_1chip.steady_10k", "--seed", "77",
                  "--seconds", "1", "--trace", "0", "--rehearsal",
                  "--control", "bf16_extremes"], cache_dir)
    out = last_line(p)
    assert out["control"] == "bf16_extremes" and out["correct"] is False
    assert failing(p) == {"exact_mismatches"}

"""The four cells of PR 27 still print at least what they printed then:
their `--rehearsal` runs on two seeds, recorded at PR 27 (before PR 28
took `run.py` apart into a driver and a generator), against the same
runs now.

`golden_rehearsal_pr27.json` keeps what two runs of one seed at that
commit agreed on: `correct`, `failed`, the device block, the names and
units of every metric printed, the names and limits of every number
compared, the compared numbers that do not follow thread timing, and
the payloads byte for byte (a hash of the datagrams and of the
reference). What a second run at that commit itself did not repeat is
held loosely: `attempted` is a whole number of ticks,
`forward.tick_bytes` within 5% of what was seen (a digest's centroid
count follows how the pump batched its samples).

A floor, not a census: a metric printed then is printed now under the
same unit, and a run may print more, if it is a count the manifest gives
the cell. The numbers compared and their limits stay an equality: a
limit is a guarantee, and a PR that adds a compared number to a cell
that is there is a `benchmark` PR. Golden runs are read from every
`golden_rehearsal_*.json` beside this file, so a later PR brings a new
cell's as a new file, if it wants one."""

import hashlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import harness, run  # noqa: E402

RUN = os.path.join(REPO, "perfbench", "run.py")
GOLDEN_FILES = checks.goldens()
GOLDEN = checks.golden_runs(GOLDEN_FILES)
MANIFEST = run.load_manifest()
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}


def canon(o):
    if isinstance(o, dict):
        return {str(k): canon(v) for k, v in sorted(
            o.items(), key=lambda kv: str(kv[0]))}
    if isinstance(o, (list, tuple)):
        return [canon(v) for v in o]
    if isinstance(o, np.ndarray):
        return [repr(float(x)) for x in o.tolist()]
    if isinstance(o, (float, np.floating)):
        return repr(float(o))
    return o


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("perfbench_golden_cache"))


def test_every_golden_cell_is_in_the_manifest_on_two_seeds():
    checks.check_golden_coverage(MANIFEST, GOLDEN_FILES)


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_the_generator_makes_the_parents_payloads_byte_for_byte(key):
    cell, seed = key.split("@")
    cfg = harness.load_config(CELLS[cell]["config"], rehearsal=True)
    mix = harness.load_mix(CELLS[cell]["traffic"], rehearsal=True)
    payloads, ref_s = harness.load_generator(mix).build(
        cfg, mix, int(seed), lambda _msg: None)
    assert ref_s >= 0.0
    got = [{"n_lines": p["n_lines"], "timer_lines": p["timer_lines"],
            "n_datagrams": len(p["datagrams"]),
            "datagrams_sha256": hashlib.sha256(
                b"\x00".join(p["datagrams"])).hexdigest(),
            "ref_sha256": hashlib.sha256(json.dumps(
                canon(p["ref"])).encode()).hexdigest()} for p in payloads]
    assert got == GOLDEN[key]["payloads"]


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_the_rehearsal_prints_what_the_parent_printed(key, cache_dir):
    cell, seed = key.split("@")
    want = GOLDEN[key]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONHASHSEED")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir)
    p = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", seed,
         "--seconds", "1", "--trace", "1", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.splitlines()
    out = json.loads(lines[-1])
    compared = {}
    for ln in lines:
        m = re.match(r"compared: (\S+) = (\S+)  limit (\S+)  (ok|FAIL)", ln)
        if m:
            compared[m.group(1)] = (float(m.group(2)), float(m.group(3)))
    # the landing's lane width flips with thread timing (see
    # test_perfbench_rehearsal.failing): a rehearsal leaves that number
    # to the chip run
    numbers = {k: v for k, v in compared.items()
               if k != "compile.in_window"}
    assert all(v <= lim for v, lim in numbers.values()), compared
    assert out["failed"] == want["failed"] == 0
    assert out["device"] == want["device"]
    assert out["rehearsal"] is want["rehearsal"] is True
    checks.check_printed_units(
        MANIFEST, cell, {k: v["unit"] for k, v in out["metrics"].items()},
        want["metric_units"])
    # the same names beside the same limits; their order is the
    # harness's own (`bridge.lost_lines` is a number of each tick's
    # verdict since PR 28's review, and so comes before the run's)
    assert sorted(compared) == sorted(want["compared_limits"])
    assert {k: lim for k, (_v, lim) in compared.items()} \
        == want["compared_limits"]
    for name, value in want["compared_that_repeat"].items():
        assert compared[name][0] == value, name
    for name, value in want["metrics_that_repeat"].items():
        assert out["metrics"][name]["value"] == value, name
    assert out["attempted"] > 0
    assert out["attempted"] % want["lines_a_tick"] == 0
    seen = want.get("tick_bytes_seen")       # of a cell with a forward
    if seen:
        assert 0.95 * min(seen) <= out["metrics"]["forward.tick_bytes"][
            "value"] <= 1.05 * max(seen)

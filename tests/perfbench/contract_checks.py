"""What the tests under `tests/perfbench/` hold of a manifest, each check
a function of `(manifest, root)`: `manifest` a BENCHMARK.json as loaded,
`root` the `perfbench/` directory its files are found under. The tests
of the real tree call them with BENCHMARK.json and `perfbench/`;
`test_perfbench_additions.py` calls the same functions with a manifest a
later PR could write (the waiting fan-in entries, a per-layer metric
without a `workloads` list, one with) over a copied `perfbench/` that
holds the new files, so that a check which only today's census passes
fails here, in tier-1, and not in the PR that brings the addition.

The rule (ISSUE 29). A test of the benchmark may pin
  (i)  what the benchmark's own code computes from given inputs;
  (ii) what the four cells reported at PR 27, as a floor: they still
       report it, under the same units and limits, in the same order,
       and more is allowed;
and never (iii) how many cells or metrics the manifest has, (iv) that an
entry is absent from it, (v) a shape or a call count inside the program
under test.
"""

import copy
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import harness, layers, run  # noqa: E402

TESTS = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.join(REPO, "perfbench")
FANIN_CELL = "fanin32_global_1chip.fleet_1k"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DRIVER_METHODS = ("mesh_devices", "watch_warmup", "finish_warmup", "tick",
                  "check", "drop_counters", "stop")
# a per-layer metric named for a tier is that tier's: a cell without the
# tier is not asked for it (the metric lists its cells, or moves an
# end-to-end metric only such cells report)
LOCAL_TIER = ("local.", "forward.", "ingest.", "gen.", "bridge.")


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


# ------------------------------------------------------------ what is there

def waiting_entries(root=PERFBENCH):
    """The `configs` and `workloads` entries of the fan-in cell, as a
    later PR appends them (`perfbench/study/fanin32.entries.json`)."""
    with open(os.path.join(root, "study", "fanin32.entries.json")) as f:
        return json.load(f)


def merged(manifest, entries):
    """The manifest plus `entries`, the way `fanin_probe.merged_manifest`
    merges them: only where the manifest lacks the name."""
    out = copy.deepcopy(manifest)
    for group, new in entries.items():
        have = {e["name"] for e in out[group]}
        out[group] += [copy.deepcopy(e) for e in new
                       if e["name"] not in have]
    return out


def goldens(tests_dir=TESTS):
    """{file name: contents} of every `golden_rehearsal_*.json`: a later
    PR brings a new cell's golden runs as a new file, if it wants any."""
    out = {}
    for path in sorted(glob.glob(os.path.join(
            tests_dir, "golden_rehearsal_*.json"))):
        with open(path) as f:
            out[os.path.basename(path)] = json.load(f)
    return out


def golden_runs(files):
    """{"<cell>@<seed>": run} over all the golden files."""
    runs = {}
    for name, data in files.items():
        for key, want in data["runs"].items():
            assert key not in runs, (name, key)
            runs[key] = want
    return runs


def cell_names(manifest):
    return [w["name"] for w in manifest["workloads"]]


def reports_end_to_end(manifest, cell, name):
    m = next(m for m in manifest["end_to_end"] if m["name"] == name)
    return "workloads" not in m or cell in m["workloads"]


def cells_reporting(manifest, name, group="per_layer"):
    """The cells, in the manifest's order, that `run.cell_metrics` gives
    the metric `name`."""
    return [c for c in cell_names(manifest)
            if any(m["name"] == name
                   for m in run.cell_metrics(manifest, c, group))]


def local_tier_cells(manifest, root=PERFBENCH):
    """The cells whose deployment has a local tier: its file has a
    `local` block beside the `global` one."""
    return [w["name"] for w in manifest["workloads"]
            if "local" in harness.load_config(w["config"], root=root)]


def counts_of(manifest, cell):
    """The per-layer metrics a rehearsal of the cell may print: a CPU
    run prints counts, never a time, a rate or a device metric."""
    return {m["name"] for m in run.cell_metrics(manifest, cell, "per_layer")
            if m["source"] == "program_counter"}


# ---------------------------------------------------- the contract's letter

def check_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line_ok(w) for w in manifest["command"])
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in manifest["paths"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    assert len(json.dumps(manifest, indent=1)) <= 64 << 10


def check_names_units_and_entries(manifest):
    seen = set()
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in manifest[group]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            assert m["name"] not in seen
            seen.add(m["name"])
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    for m in manifest["per_layer"]:
        assert line_ok(m["layer"])
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"]) \
            and line_ok(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
    assert len({c["file"] for c in manifest["configs"]}) == len(
        manifest["configs"])
    assert len({c["source"] for c in manifest["configs"]}) == len(
        manifest["configs"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert line_ok(w["why"]) and w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)
    assert len(set(cell_names(manifest))) == len(manifest["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def check_every_cell_reports_what_the_contract_asks(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for cell in cell_names(manifest):
        ends = [m["name"] for m in run.cell_metrics(
            manifest, cell, "end_to_end")]
        assert "setup_s" in ends and len(ends) >= 2
        per = run.cell_metrics(manifest, cell, "per_layer")
        assert per
        # a per-layer metric is reported only where the end-to-end
        # metric it should move is reported too
        for m in per:
            assert m["moves"] in e2e and m["moves"] in ends, (cell, m)


def check_every_entry_has_its_files(manifest, root=PERFBENCH):
    cells = set(cell_names(manifest))
    for c in manifest["configs"]:
        cfg = harness.load_config(c["name"], root=root)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg for k in c["reduced"])
        assert cfg["guarantees"]["tolerances"] and cfg["assumed"]["process"]
        assert c["file"] == f"perfbench/configs/{c['name']}.json"
        assert os.path.exists(os.path.join(os.path.dirname(root), c["file"]))
    for w in manifest["workloads"]:
        mix = harness.load_mix(w["traffic"], root=root)
        assert mix["name"] == w["traffic"]
        assert harness.load_config(w["config"], root=root)["chips"] \
            == w["chips"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
        base = os.path.join(root, "metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")
        if os.path.exists(base + ".json"):
            spec = layers.load_metric(m["name"], root)
            assert spec["unit"] == m["unit"]
            assert spec.get("layer", m.get("layer")) == m.get("layer")


def check_drivers_and_generators_fit(manifest, root=PERFBENCH):
    """Every configuration under `root` names a driver and every mix a
    generator, those of the manifest and those that wait for a later
    PR's entries; each cell's driver takes what its generator makes;
    every driver and generator there has a user."""
    def names(d, ext=".json"):
        return sorted(f[:-len(ext)] for f in os.listdir(
            os.path.join(root, d)) if f.endswith(ext))

    configs, mixes = names("configs"), names("mixes")
    assert {c["name"] for c in manifest["configs"]} <= set(configs)
    assert {w["traffic"] for w in manifest["workloads"]} <= set(mixes)
    makes = {}
    for name in mixes:
        mix = harness.load_mix(name, root=root)
        gen = harness.load_generator(mix, root=root)   # no default
        assert isinstance(gen.MAKES, str) and callable(gen.build)
        makes[name] = gen.MAKES
    takes = {}
    for name in configs:
        cfg = harness.load_config(name, root=root)
        drv = harness.load_driver(cfg, root=root).Driver
        assert isinstance(drv.TAKES, str) and isinstance(drv.OPS, str)
        assert all(callable(getattr(drv, m)) for m in DRIVER_METHODS)
        takes[name] = drv.TAKES
    for w in merged(manifest, waiting_entries(root))["workloads"]:
        assert takes[w["config"]] == makes[w["traffic"]], w["name"]
    used = {harness.load_config(n, root=root)["driver"] for n in configs}
    assert used == set(names("drivers", ".py"))
    used = {harness.load_mix(n, root=root)["generator"] for n in mixes}
    assert used == set(names("generators", ".py"))


# ------------------------------------------------- the waiting fan-in entries

def check_waiting_entries(manifest, root=PERFBENCH):
    """The entries keep the manifest's rules; each is either absent from
    the manifest or in it letter for letter, and no other configuration
    of the manifest has the waiting one's `source`."""
    entries = waiting_entries(root)
    assert set(entries) == {"configs", "workloads"}
    (c,), (w,) = entries["configs"], entries["workloads"]
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(c["name"]) and NAME.match(w["name"]) \
        and NAME.match(w["traffic"])
    assert all(line_ok(s) for s in (c["source"], c["why"], w["why"]))
    assert w["chips"] == 1 and w["config"] == c["name"]
    assert w["name"] == FANIN_CELL == f"{c['name']}.{w['traffic']}"
    assert c["file"] == f"perfbench/configs/{c['name']}.json"
    assert os.path.exists(os.path.join(os.path.dirname(root), c["file"]))
    cfg = harness.load_config(c["name"], root=root)
    assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"] == []
    assert cfg["chips"] == 1 and cfg["fan_in_locals"] == 32
    assert harness.load_mix(w["traffic"], root=root)["name"] == w["traffic"]
    for group, entry in (("configs", c), ("workloads", w)):
        same = [x for x in manifest[group] if x["name"] == entry["name"]]
        assert same in ([], [entry]), (group, same)
    assert c["source"] not in {x["source"] for x in manifest["configs"]
                               if x["name"] != c["name"]}
    # the global is two_tier_1chip's, but for the sum it also emits
    two = harness.load_config("two_tier_1chip", root=root)
    assert cfg["global"] == two["global"]
    assert cfg["population"] == two["population"]
    assert {k: v for k, v in cfg["common"].items() if k != "aggregates"} \
        == {k: v for k, v in two["common"].items() if k != "aggregates"}


def check_the_fan_in_cell_is_asked_for_no_metric_of_an_absent_tier(
        manifest, root=PERFBENCH):
    """Under the manifest plus the waiting entries the cell reports
    `emit_latency_s` and `setup_s` and nothing of a local tier, and of
    the per-layer metrics at least those of the import, the global
    flush, the host and the device."""
    full = merged(manifest, waiting_entries(root))
    assert FANIN_CELL not in local_tier_cells(full, root)
    ends = [m["name"] for m in run.cell_metrics(full, FANIN_CELL,
                                                "end_to_end")]
    assert {"emit_latency_s", "setup_s"} <= set(ends)
    assert "ingest_rate" not in ends
    per = run.cell_metrics(full, FANIN_CELL, "per_layer")
    assert {m["layer"] for m in per} >= {"import", "global flush", "host",
                                        "device"}
    assert not [m["name"] for m in per
                if m["name"].startswith(LOCAL_TIER + ("mesh.",))]
    assert {"global.import_s", "global.flush_s", "import.route_ms",
            "import.apply_ms", "import.land_ms", "tick.median_emit_s",
            "host.gc_ms", "device.idle_share", "compile.in_window"} \
        <= {m["name"] for m in per}


# ------------------------------------------------ the floor recorded at PR 27

def is_subsequence(short, long):
    it = iter(long)
    return all(x in it for x in short)


def check_the_four_cells_report_at_least_what_they_reported(
        manifest, was, root=PERFBENCH):
    """`was`: `golden_rehearsal_pr27.json`'s `reports`, {cell: {group:
    [names]}}. Each of those cells is still in the manifest and reports
    those names in that order, group by group (more may stand between
    and after). The per-layer metrics of the local tier and the forward
    list their cells (a cell without a local tier is not asked for
    them): PR 27's four at least, and none without a local tier."""
    assert set(was) <= set(cell_names(manifest))
    for cell, groups in was.items():
        for group, names in groups.items():
            today = [m["name"] for m in run.cell_metrics(
                manifest, cell, group)]
            assert is_subsequence(names, today), (cell, group, today)
    floor = {n for groups in was.values() for n in groups["per_layer"]
             if n.startswith(("local.", "forward."))}
    listed = {m["name"]: m.get("workloads") for m in manifest["per_layer"]
              if m["name"].startswith(("local.", "forward."))}
    assert floor and floor <= set(listed)
    has_local = set(local_tier_cells(manifest, root))
    assert set(was) <= has_local
    for name, cells in listed.items():
        assert cells is not None, name
        assert set(cells) <= has_local, name
        if name in floor:
            assert set(was) <= set(cells), name


def check_golden_coverage(manifest, files):
    """Every golden cell is in the manifest, on two seeds; the four
    cells of `golden_rehearsal_pr27.json` are all still there."""
    runs = golden_runs(files)
    cells = {k.split("@")[0] for k in runs}
    assert cells <= set(cell_names(manifest))
    assert all(sum(k.startswith(c + "@") for k in runs) == 2 for c in cells)
    pr27 = files["golden_rehearsal_pr27.json"]
    assert {k.split("@")[0] for k in pr27["runs"]} == set(pr27["reports"])
    assert set(pr27["reports"]) <= cells


def check_printed_units(manifest, cell, printed, want):
    """`printed`, `want`: {metric: unit} of a rehearsal's result line
    now and at the golden run. Every metric the parent printed is
    printed with the same unit; any other printed metric is a
    `program_counter` the manifest gives the cell."""
    assert {k: printed.get(k) for k in want} == want
    extra = set(printed) - set(want)
    assert extra <= counts_of(manifest, cell), extra
    units = {m["name"]: m["unit"] for m in manifest["per_layer"]}
    assert all(printed[k] == units[k] for k in extra)


def check_reported_where_it_says(manifest, name):
    """A per-layer metric with a `workloads` list is reported by exactly
    that list; one without by every cell that reports the end-to-end
    metric it `moves`. Returns the cells."""
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    cells = cells_reporting(manifest, name)
    assert cells, name
    if "workloads" in entry:
        assert set(entry["workloads"]) <= set(cell_names(manifest))
        assert cells == [c for c in cell_names(manifest)
                         if c in entry["workloads"]]
    else:
        assert cells == [c for c in cell_names(manifest)
                         if reports_end_to_end(manifest, c, entry["moves"])]
    return cells


# ------------------------------------------- what a rehearsal of a cell shows

def rehearsal_expectations(manifest, cell, root=PERFBENCH):
    """What `test_cell_rehearses_end_to_end` may ask of the cell's
    rehearsal, each item from the thing it is about: the counts the
    manifest gives the cell (`forward.tick_bytes` among them only where
    the cell has a forward), the process
    environment its deployment's file states, and how many devices its
    global's banks lie on (a mesh global has no landing ladder)."""
    w = next(w for w in manifest["workloads"] if w["name"] == cell)
    cfg = harness.load_config(w["config"], rehearsal=True, root=root)
    env = cfg.get("assumed", {}).get("process", {}).get("env", {})
    return {"chips": int(w["chips"]), "counts": counts_of(manifest, cell),
            "env": {k: str(v) for k, v in env.items()},
            "global_devices": int(cfg["global"].get("tpu_num_devices", 1))}


def check_overflow_rows_entry(manifest):
    """`ingest.overflow_rows` (PR 27) is the ingest layer's, came after
    every metric that was there before it, and is reported where
    `ingest_rate` is: `steady_10k` and `hot_1k` among those cells."""
    name = "ingest.overflow_rows"
    names = [m["name"] for m in manifest["per_layer"]]
    assert [m for m in manifest["per_layer"] if m["name"] == name] == [{
        "name": name, "unit": "rows", "better": "lower",
        "source": "program_counter",
        "layer": "pump + engine ingest programs", "moves": "ingest_rate"}]
    assert names.index(name) > names.index("ingest.pump_batches")
    cells = cells_reporting(manifest, name)
    assert cells == [c for c in cell_names(manifest)
                     if reports_end_to_end(manifest, c, "ingest_rate")]
    assert {"two_tier_1chip.steady_10k", "two_tier_1chip.hot_1k"} \
        <= set(cells)


def check_landing_shapes(records):
    """Where a tick's record carries `landing_shapes` (a study's run of
    a driver that wraps the program's `cluster_rows`), each is a pair of
    positive whole numbers. Which shapes the program's landing takes,
    and whether it goes through that attribute at all, is the
    program's: an engine that lands another way records nothing
    (`harness.LandingWatch`)."""
    for r in records:
        for shape in r.get("landing_shapes", []):
            assert len(shape) == 2, shape
            assert all(isinstance(n, int) and n > 0 for n in shape), shape

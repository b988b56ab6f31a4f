"""BENCHMARK.json against the contract's letter, and the harness taking
later cells and metrics as data: a configuration, a traffic mix and a
per-layer metric each arrive as new files plus one entry, with no edit
to a file that is there."""

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import harness, layers, run  # noqa: E402
from perfbench.generators import dogstatsd_lines as traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return run.load_manifest()


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line_ok(w) for w in manifest["command"])
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in manifest["paths"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    assert 1 <= cells <= 24
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


def test_names_units_and_entries(manifest):
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
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_files_under_paths_use_allowed_characters(manifest):
    for p in manifest["paths"]:
        for base, _dirs, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert PATH.match(rel), rel


def test_every_cell_reports_what_the_contract_asks(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        ends = [m["name"] for m in run.cell_metrics(
            manifest, w["name"], "end_to_end")]
        assert "setup_s" in ends and len(ends) >= 2
        per = run.cell_metrics(manifest, w["name"], "per_layer")
        assert per
        # a per-layer metric is reported only where the end-to-end
        # metric it should move is reported too
        for m in per:
            assert m["moves"] in e2e and m["moves"] in ends, (w["name"], m)


def test_every_entry_has_its_files(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        cfg = harness.load_config(c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg for k in c["reduced"])
        assert cfg["guarantees"]["tolerances"] and cfg["assumed"]["process"]
        assert os.path.join(REPO, c["file"]) == os.path.join(
            REPO, "perfbench", "configs", c["name"] + ".json")
    for w in manifest["workloads"]:
        mix = harness.load_mix(w["traffic"])
        assert mix["name"] == w["traffic"]
        assert harness.load_config(w["config"])["chips"] == w["chips"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
        base = os.path.join(REPO, "perfbench", "metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")
        if os.path.exists(base + ".json"):
            spec = layers.load_metric(m["name"])
            assert spec["unit"] == m["unit"]
            assert spec.get("layer", m.get("layer")) == m.get("layer")


def tick(emit, spans=None, phases=None, counters=None, **extra):
    return {"emit_latency_s": emit, "spans": spans or {},
            "phase_rows": phases or [], "counters": counters or {}, **extra}


def test_fixed_reductions():
    ticks = [tick(1.0, {"bench.x": 2.0}, [("local:a", 0, int(1e9)),
                                          ("local:a", 0, int(5e8))],
                  {"c": 3}, lines=10, ingest_s=2.0),
             tick(3.0, {"bench.x": 4.0}, [("local:a", 0, int(2e9))],
                  {"c": 4}, lines=30, ingest_s=2.0),
             tick(2.0, {"bench.x": 9.0}, [], {"c": 5}, lines=20,
                  ingest_s=4.0)]
    ctx = {"ticks": ticks, "trace": None, "device": {}, "run": {}}

    import tempfile
    with tempfile.TemporaryDirectory() as root:
        os.makedirs(os.path.join(root, "metrics"))

        def put(read_spec):
            with open(os.path.join(root, "metrics", "m.json"), "w") as f:
                json.dump({"name": "m", "read": read_spec}, f)
            return layers.read_metric("m", ctx, root=root)

        assert put({"from": "tick", "names": ["emit_latency_s"],
                    "reduce": "median"}) == 2.0
        assert put({"from": "tick", "names": ["emit_latency_s"],
                    "reduce": "max"}) == 3.0
        assert put({"from": "spans", "names": ["bench.x"],
                    "reduce": "sum", "scale": 1000}) == 15000.0
        # phases: summed per tick (1.5 s, 2 s), the tick without the
        # phase left out; median of two
        assert put({"from": "phases", "names": ["local:a"]}) == 1.75
        assert put({"from": "counters", "names": ["c"],
                    "reduce": "sum"}) == 12
        # a rate over all the work and all the time of the window
        assert put({"from": "tick", "names": ["lines"], "per": {
            "from": "tick", "names": ["ingest_s"]}}) == 60 / 8.0
        # nothing to read -> nothing reported
        assert put({"from": "spans", "names": ["bench.absent"]}) is None
        assert put({"from": "trace", "what": "idle_share"}) is None


DRIVER = '''
class Driver:
    TAKES = "pings"
    OPS = "pings"

    def __init__(self, cfg, rehearsal):
        self.sets = cfg["common"]["tpu_set_slots"]

    def tick(self, payload, ts, spans, gcm, meter):
        return {"attempted": len(payload["pings"]), "sets": self.sets}
'''
GENERATOR = '''
MAKES = "pings"


def build(cfg, mix, seed, log):
    n = mix["timers"]["keys"]
    return [{"pings": [seed] * n, "ref": n}], 0.0
'''


def test_a_config_a_mix_and_a_metric_arrive_as_new_files(tmp_path):
    """Copy the benchmark's directories of data and of code found by
    name, add one file of each kind (a configuration, a mix, a metric's
    reader, and a driver and a generator for them) and one manifest
    entry each; nothing that was there is edited, and the harness's
    loaders find the new ones by name."""
    root = tmp_path / "perfbench"
    for d in ("configs", "mixes", "metrics", "drivers", "generators"):
        shutil.copytree(os.path.join(REPO, "perfbench", d), root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    cfg = harness.load_config("two_tier_1chip")
    cfg.update(name="two_tier_small_sets", source="a test's own",
               driver="pinger")
    cfg["common"] = {**cfg["common"], "tpu_set_slots": 2048}
    (root / "configs" / "two_tier_small_sets.json").write_text(
        json.dumps(cfg))
    mix = harness.load_mix("steady_10k")
    mix.update(name="steady_1k", generator="pings")
    (root / "drivers" / "pinger.py").write_text(DRIVER)
    (root / "generators" / "pings.py").write_text(GENERATOR)
    mix["timers"] = {**mix["timers"], "keys": 1000, "hot_keys": 10}
    (root / "mixes" / "steady_1k.json").write_text(json.dumps(mix))
    (root / "metrics" / "sink.flush_ms.json").write_text(json.dumps({
        "name": "sink.flush_ms", "unit": "ms", "layer": "global flush",
        "moves": "emit_latency_s", "source": "program_span",
        "read": {"from": "phases", "names": ["global:sink.flush"],
                 "reduce": "max", "scale": 1000}}))
    (root / "metrics" / "tick.emit_range_s.py").write_text(
        "def read(ctx):\n"
        "    v = [t['emit_latency_s'] for t in ctx['ticks']]\n"
        "    return max(v) - min(v) if v else None\n")

    manifest = run.load_manifest()
    manifest["configs"].append({
        "name": "two_tier_small_sets", "source": "a test's own",
        "file": "perfbench/configs/two_tier_small_sets.json",
        "reduced": ["fan_in_locals"], "why": "test"})
    manifest["workloads"].append({
        "name": "two_tier_small_sets.steady_1k",
        "config": "two_tier_small_sets", "traffic": "steady_1k",
        "chips": 1, "why": "test"})
    for name, unit in (("sink.flush_ms", "ms"), ("tick.emit_range_s", "s")):
        manifest["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "global flush",
            "moves": "emit_latency_s"})

    got = harness.load_config("two_tier_small_sets", root=str(root))
    assert got["common"]["tpu_set_slots"] == 2048
    driver = harness.load_driver(got, root=str(root))
    got = harness.load_mix("steady_1k", root=str(root))
    touched = traffic.touched_keys(got, cfg["population"], 7)
    assert touched["timers"].size == 1000 and touched["hot"].size == 10
    # the new driver and generator are found by the names the new files
    # give them, and fit each other; the old ones are still found
    generator = harness.load_generator(got, root=str(root))
    assert driver.Driver.TAKES == generator.MAKES == "pings"
    payloads, ref_s = generator.build(cfg, got, 7, print)
    rec = driver.Driver(cfg, True).tick(payloads[0], 0, None, None, None)
    assert rec == {"attempted": 1000, "sets": 2048} and ref_s == 0.0
    assert harness.load_driver({"name": "x", "driver": "two_tier"},
                               root=str(root)).Driver.OPS == "lines"
    with pytest.raises(SystemExit, match="no perfbench/drivers/nope.py"):
        harness.load_driver({"name": "x", "driver": "nope"}, root=str(root))
    with pytest.raises(SystemExit, match="no perfbench/generators/nope.py"):
        harness.load_generator({"name": "x", "generator": "nope"},
                               root=str(root))
    names = [m["name"] for m in run.cell_metrics(
        manifest, "two_tier_small_sets.steady_1k", "per_layer")]
    assert "sink.flush_ms" in names and "tick.emit_range_s" in names
    assert "hll_stats_roofline" not in names      # it lists its cells
    assert "gen.wait_share" not in names          # moves ingest_rate
    ctx = {"ticks": [tick(1.0, phases=[("global:sink.flush", 0, int(2e6))]),
                     tick(1.5, phases=[("global:sink.flush", 0, int(5e6))])],
           "trace": None, "device": {}, "run": {}}
    assert layers.read_metric("sink.flush_ms", ctx, root=str(root)) == 5.0
    assert layers.read_metric("tick.emit_range_s", ctx,
                              root=str(root)) == 0.5
    assert all(p.read_bytes() == b for p, b in before.items())


DRIVER_METHODS = ("mesh_devices", "watch_warmup", "finish_warmup", "tick",
                  "check", "drop_counters", "stop")


def all_configs_and_mixes(manifest):
    """Every configuration and mix under perfbench/, the manifest's and
    those that wait for a later PR's entries."""
    def names(d):
        return sorted(f[:-5] for f in os.listdir(os.path.join(
            REPO, "perfbench", d)) if f.endswith(".json"))

    assert {c["name"] for c in manifest["configs"]} <= set(names("configs"))
    assert {w["traffic"] for w in manifest["workloads"]} <= set(
        names("mixes"))
    return names("configs"), names("mixes")


def test_every_configuration_names_a_driver_and_every_mix_a_generator(
        manifest):
    configs, mixes = all_configs_and_mixes(manifest)
    makes = {}
    for name in mixes:
        mix = harness.load_mix(name)
        gen = harness.load_generator(mix)     # no default, no fallback
        assert isinstance(gen.MAKES, str) and callable(gen.build)
        makes[name] = gen.MAKES
    takes = {}
    for name in configs:
        cfg = harness.load_config(name)
        drv = harness.load_driver(cfg).Driver
        assert isinstance(drv.TAKES, str) and isinstance(drv.OPS, str)
        assert all(callable(getattr(drv, m)) for m in DRIVER_METHODS)
        takes[name] = drv.TAKES
    with open(os.path.join(REPO, "perfbench", "study",
                           "fanin32.entries.json")) as f:
        waiting = json.load(f)["workloads"]
    for w in manifest["workloads"] + waiting:
        assert takes[w["config"]] == makes[w["traffic"]], w["name"]
    # every driver and generator under perfbench/ has a user
    used = {harness.load_config(n)["driver"] for n in configs}
    have = {f[:-3] for f in os.listdir(os.path.join(
        REPO, "perfbench", "drivers")) if f.endswith(".py")}
    assert used == have
    used = {harness.load_mix(n)["generator"] for n in mixes}
    have = {f[:-3] for f in os.listdir(os.path.join(
        REPO, "perfbench", "generators")) if f.endswith(".py")}
    assert used == have


def test_the_four_cells_report_exactly_what_they_reported_at_pr27(manifest):
    """Eight per-layer metrics of the local tier and the forward list
    their cells since PR 28 (a cell without a local tier is not asked
    for them); what each of the four cells reports did not move."""
    with open(os.path.join(os.path.dirname(__file__),
                           "golden_rehearsal_pr27.json")) as f:
        was = json.load(f)["reports"]
    assert set(was) == {w["name"] for w in manifest["workloads"]}
    for cell, groups in was.items():
        for group, names in groups.items():
            assert [m["name"] for m in run.cell_metrics(
                manifest, cell, group)] == names, (cell, group)
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"]
              if m["name"].startswith(("local.", "forward."))}
    assert len(listed) == 8
    assert all(cells == [w["name"] for w in manifest["workloads"]]
               for cells in listed.values())


def test_the_seed_changes_keys_and_values_never_sizes():
    cfg = harness.load_config("two_tier_1chip", rehearsal=True)
    mix = harness.load_mix("steady_10k", rehearsal=True)
    shapes = set()
    texts = []
    for seed in (1, 2, 2**31 + 12345):
        touched = traffic.touched_keys(mix, cfg["population"], seed)
        p = traffic.Payload(mix, touched, seed, 1)
        lines = p.lines()
        shapes.add((len(lines), p.t_key.size, p.s_key.size, p.c_key.size,
                    p.g_key.size, touched["hot"].size))
        texts.append(lines)
        again = traffic.Payload(mix, traffic.touched_keys(
            mix, cfg["population"], seed), seed, 1).lines()
        assert again == lines
    assert len(shapes) == 1
    assert texts[0] != texts[1]
    grams = traffic.datagrams(texts[0], 80, 4000)
    assert all(len(g) <= 4000 and g.count(b"\n") < 80 for g in grams)
    assert b"\n".join(grams).decode().split("\n") == texts[0]

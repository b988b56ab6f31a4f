"""BENCHMARK.json against the contract's letter, and the harness taking
later cells and metrics as data: a configuration, a traffic mix and a
per-layer metric each arrive as new files plus one entry, with no edit
to a file that is there.

What is held of the manifest is held by `contract_checks.py`'s functions
of `(manifest, root)`, which `test_perfbench_additions.py` runs again on
a manifest with additions: the contract's letter, every cell reporting
what the contract asks, every entry having its files, every driver
fitting its generator; and of PR 27's census its floor: the four cells
of PR 27 still report at least what they reported then, in that order.
Nothing here counts the manifest's cells or metrics."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
from perfbench import harness, layers, run  # noqa: E402
from perfbench.generators import dogstatsd_lines as traffic  # noqa: E402


@pytest.fixture(scope="module")
def manifest():
    return run.load_manifest()


def test_top_level_keys_and_command(manifest):
    checks.check_top_level(manifest)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


def test_names_units_and_entries(manifest):
    checks.check_names_units_and_entries(manifest)


def test_files_under_paths_use_allowed_characters(manifest):
    for p in manifest["paths"]:
        for base, _dirs, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert checks.PATH.match(rel), rel


def test_every_cell_reports_what_the_contract_asks(manifest):
    checks.check_every_cell_reports_what_the_contract_asks(manifest)


def test_every_entry_has_its_files(manifest):
    checks.check_every_entry_has_its_files(manifest)


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


def test_every_configuration_names_a_driver_and_every_mix_a_generator(
        manifest):
    checks.check_drivers_and_generators_fit(manifest)


def test_the_four_cells_report_at_least_what_they_reported_at_pr27(manifest):
    """The floor, not the census: the four cells of PR 27 are still in
    the manifest and report PR 27's names in PR 27's order, group by
    group, with whatever later PRs added between and after; the eight
    per-layer metrics of the local tier and the forward list their cells
    since PR 28, those four at least and none without a local tier."""
    was = checks.goldens()["golden_rehearsal_pr27.json"]["reports"]
    checks.check_the_four_cells_report_at_least_what_they_reported(
        manifest, was)


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

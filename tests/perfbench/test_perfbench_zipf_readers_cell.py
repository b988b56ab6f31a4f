"""PR 49's cell, `dogstatsd_readers4_two_tier_1chip.zipf_flows64_600k`:
the deployment file against the Zipf cell's, the mix against
`zipf_churn_600k`, the generator's flows (the same lines as
`dogstatsd_zipf` from the same seed, a gauge key on one flow, the
handed-over keys' halves, the reference by flow), the entries in the
manifest with the three readers on hand-made tick records (on records
as the parent commit's program gives them they find nothing and raise
nothing), and the cell end to end in this process at its rehearsal's
sizes with its controls (through `run.py` it runs as every cell does, in
`test_perfbench_rehearsal.py`).

It also holds what twelve tests of `test_perfbench_zipf_cell.py` assert,
with the cells and entries found by name and the lists as appended to:
that file pins PR 45's cell to the end of `workloads`, its five entries
to the end of `per_layer` and nine lists to end with its cell, it is not
a cell PR's to edit, and `tests/conftest.py` marks those tests expected
failures while outgrown."""

import copy
import itertools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import contract_checks as checks  # noqa: E402
import test_perfbench_zipf_cell as zc  # noqa: E402
from perfbench import harness, layers, reference, run  # noqa: E402
from perfbench.generators import dogstatsd_flows as flows  # noqa: E402
from perfbench.generators import dogstatsd_zipf as zipf  # noqa: E402

MANIFEST = run.load_manifest()
CONFIG = "dogstatsd_readers4_two_tier_1chip"
MIX = "zipf_flows64_600k"
CELL = CONFIG + "." + MIX
ZIPF, SSF, FLEET_10K = zc.CELL, zc.SSF, zc.FLEET_10K
ENTRIES = {   # name -> source; every one "%", lower, the bridge's layer
    "ingest.reader_busy_most": "program_span",
    "ingest.reader_lines_most": "program_counter",
    "ingest.ring_fill_most": "program_counter",
}


@pytest.fixture(scope="module")
def built():
    """The rehearsal's first payload with what it was made from."""
    cfg, mix = (harness.load_config(CONFIG, True),
                harness.load_mix(MIX, True))
    plan = zipf.key_plan(mix, cfg["population"], 49)
    p = zipf.Payload(mix, plan, 49, 1)
    fl = flows.Flows(p, mix["flows"], 49)
    payloads, _s = flows.build(cfg, mix, 49, lambda _m: None)
    return cfg, mix, p, fl, payloads


# ---------------------------------------------------------- the deployment

def test_the_deployment_is_the_zipf_cells_but_for_what_the_issue_lists():
    cfg, zf = harness.load_config(CONFIG), harness.load_config(zc.CONFIG)
    assert set(cfg) == set(zf)
    for same in ("chips", "fan_in_locals", "population", "sketches",
                 "percentiles", "global", "reduced"):
        assert cfg[same] == zf[same], same
    assert cfg["driver"] == "flows_two_tier"
    assert cfg["local"] == {**zf["local"], "num_readers": 4}
    retired = {"tpu_fused_kernels"}         # PR 48 retired the key
    assert cfg["common"] == {k: v for k, v in zf["common"].items()
                             if k not in retired}
    assert cfg["rehearsal"]["common"] == {
        k: v for k, v in zf["rehearsal"]["common"].items()
        if k not in retired}
    for same in ("population", "local", "guarantees"):
        assert cfg["rehearsal"][same] == zf["rehearsal"][same], same
    g, gz = cfg["guarantees"], zf["guarantees"]
    for kept in ("lines_lost", "drop_and_error_counters", "forward",
                 "compile_in_window", "tolerances", "own_timers"):
        assert g[kept] == gz[kept], kept
    assert len(g["exact"]) == len(gz["exact"]) + 1
    assert sum(a == b for a, b in zip(g["exact"], gz["exact"])) == len(
        gz["exact"]) - 1
    assert any("whichever reader received it" in e for e in g["exact"])
    assert any("no line lost with any spread" in e for e in g["exact"])
    assert list(cfg["controls"]) == [*zf["controls"],
                                     "handover_first_writer"]
    for kept, control in zf["controls"].items():
        assert cfg["controls"][kept] == control
    assert cfg["controls"]["handover_first_writer"]["handover"] == \
        "first_writer"
    for kept, said in zf["assumed"].items():
        assert cfg["assumed"][kept] == said
    assert set(cfg["assumed"]) - set(zf["assumed"]) == {"num_readers",
                                                        "flows"}
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert "num_readers" in cfg["source"] and "SO_REUSEPORT" in cfg["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == ["fan_in_locals"]
    sources = [c["source"] for c in MANIFEST["configs"]]
    assert len(set(sources)) == len(sources)
    # the servers take the file's keys with no warning of an unknown one
    from veneur_tpu.config import Config
    known = set(Config.__dataclass_fields__)
    assert set(cfg["common"]) | set(cfg["local"]) | set(cfg["global"]) \
        <= known


def test_the_mix_is_zipf_churn_600ks_lines_with_flows():
    mix, zm = harness.load_mix(MIX), harness.load_mix(zc.MIX)
    assert mix["generator"] == "dogstatsd_flows"
    assert flows.MAKES == "flow_datagrams"
    for same in ("lines", "zipf_s", "moving_share", "distinct_ticks",
                 "counters", "timers", "sets", "keys_repeat", "datagram",
                 "rehearsal"):
        assert mix[same] == zm[same], same
    assert {k: v for k, v in mix["flows"].items() if k != "why"} == {
        "count": 64, "threads": 4, "handover_every": 10, "in_flight": 64}
    assert mix["tick"].startswith(zm["tick"])
    assert mix["seed_use"].startswith(zm["seed_use"])
    drv = harness.load_driver(harness.load_config(CONFIG))
    assert drv.Driver.TAKES == flows.MAKES and drv.Driver.OPS == "lines"


# ---------------------------------------------------------------- the flows

def test_the_lines_are_dogstatsd_zipfs_from_the_same_seed(built):
    cfg, mix, p, _fl, payloads = built
    theirs, _s = zipf.build(cfg, mix, 49, lambda _m: None)
    for mine, ref in zip(payloads, theirs):
        sent = sorted(ln for half in mine["datagrams"] for t in half
                      for _f, d in t for ln in d.split(b"\n"))
        assert sent == sorted(ln for d in ref["datagrams"]
                              for ln in d.split(b"\n"))
        assert mine["n_lines"] == ref["n_lines"] == len(sent)
        assert mine["timer_lines"] == ref["timer_lines"]
        for tier in ("local", "global"):
            for bank, ids in ref["keys"][tier].items():
                assert np.array_equal(mine["keys"][tier][bank], ids)
        # every flow sends in its places' order, so the last write by
        # flow is the last write of the one shuffle: the same answers
        assert mine["ref"]["gauge"] == ref["ref"]["gauge"]
        for same in ("timer", "counter_local", "counter_global", "set"):
            assert mine["ref"][same] == ref["ref"][same]
        dg = mix["datagram"]
        assert all(d.count(b"\n") < dg["max_lines"]
                   and len(d) <= dg["max_bytes"]
                   for half in mine["datagrams"] for t in half
                   for _f, d in t)


def test_two_seeds_give_the_same_sizes_and_other_flows():
    cfg, mix = (harness.load_config(CONFIG, True),
                harness.load_mix(MIX, True))
    a, _s = flows.build(cfg, mix, 2**31 + 7, lambda _m: None)
    b, _s = flows.build(cfg, mix, 49, lambda _m: None)
    again, _s = flows.build(cfg, mix, 49, lambda _m: None)
    for pa, pb, pc in zip(a, b, again):
        assert pb["datagrams"] == pc["datagrams"]
        assert pb["handover"] == pc["handover"]
        assert pa["datagrams"] != pb["datagrams"]
        assert pa["n_lines"] == pb["n_lines"]
        assert len(pa["handover"]["first"]) == len(pb["handover"]["first"]) > 0
        assert pa["in_flight"] == pb["in_flight"] == 64
        assert len(pa["datagrams"]) == 2
        assert [len(half) for half in pa["datagrams"]] == [4, 4]


def _by_flow(payload):
    """{gauge name: [(half, flow, value)]} in the order sent, from the
    payload's datagrams alone."""
    seen = {}
    for h, half in enumerate(payload["datagrams"]):
        for thread in half:
            for f, d in thread:
                for ln in d.decode().split("\n"):
                    head, kind, *_rest = ln.split("|")
                    if kind == "g":
                        name, value = head.split(":")
                        seen.setdefault(name, []).append(
                            (h, f, float(np.float32(float(value)))))
    return seen


def test_a_gauge_key_is_one_flows_unless_it_is_handed_over(built):
    _cfg, mix, p, fl, payloads = built
    payload = payloads[0]
    seen = _by_flow(payload)
    handed = payload["handover"]["first"]
    assert set(seen) == set(payload["ref"]["gauge"])
    per = mix["flows"]["count"] // mix["flows"]["threads"]
    for t, thread in enumerate(payload["datagrams"][0]):
        assert {f // per for f, _d in thread} == {t}
    for name, writes in seen.items():
        if name not in handed:
            assert len({f for _h, f, _v in writes}) == 1, name
            # its last write in its flow's order is the answer
            assert writes[-1][2] == payload["ref"]["gauge"][name]
    # every tenth, in key order, of the keys with at least two lines
    keys, n = np.unique(p.g_key, return_counts=True)
    twice = keys[n >= 2]
    assert sorted(handed) == [zipf.gauge_name(k)
                              for k in twice[9::10].tolist()]
    assert len(handed) == len(fl.handed) == len(twice) // 10 > 0


def test_a_handed_over_keys_halves(built):
    _cfg, _mix, _p, fl, payloads = built
    payload = payloads[0]
    seen, hand = _by_flow(payload), payload["handover"]
    for name in hand["first"]:
        writes = seen[name]
        first = [w for w in writes if w[0] == 0]
        second = [w for w in writes if w[0] == 1]
        # the earlier ceil(n / 2) in the first half on one flow, the
        # rest in the second half on another
        assert len(first) == (len(writes) + 1) // 2 and second
        (a,), (b,) = {f for _h, f, _v in first}, {f for _h, f, _v in second}
        assert a != b
        assert first[-1][2] == hand["first"][name]
        assert second[-1][2] == hand["second"][name] \
            == payload["ref"]["gauge"][name]
    assert {(a, b) for a, b in fl.handed.values()} and all(
        a != b for a, b in fl.handed.values())
    # a thread's flows take turns: its sending is its flows' queues
    # dealt one datagram a flow a round, in the order the flows first
    # appear
    for half in payload["datagrams"]:
        for thread in half:
            queues = {}
            for f, d in thread:
                queues.setdefault(f, []).append((f, d))
            assert len(queues) > 1
            assert [x for row in itertools.zip_longest(*queues.values())
                    for x in row if x is not None] == thread


# -------------------------------------------------------------- the entries

def test_the_cell_and_its_entries_keep_the_contract():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert {k: v for k, v in cell.items() if k != "why"} == {
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert checks.line_ok(cell["why"])
    assert checks.cell_names(MANIFEST)[-2:] == [ZIPF, CELL]
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, CELL, "end_to_end")] == ["ingest_rate", "emit_latency_s",
                                          "setup_s"]
    rate = next(m for m in MANIFEST["end_to_end"]
                if m["name"] == "ingest_rate")
    assert rate["workloads"][-3:] == [SSF, ZIPF, CELL]
    assert rate["bound"] == 0.14
    every = [m["name"] for m in MANIFEST["per_layer"]]
    assert every[-len(ENTRIES):] == list(ENTRIES)
    # everything the Zipf cell reports, in its order, then its own
    theirs = [m["name"] for m in run.cell_metrics(MANIFEST, ZIPF,
                                                  "per_layer")]
    mine = [m["name"] for m in run.cell_metrics(MANIFEST, CELL, "per_layer")]
    assert mine == theirs + list(ENTRIES)
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) == 4 and len(MANIFEST["workloads"]) == 12
    assert len(MANIFEST["configs"]) == 7
    checks.check_top_level(MANIFEST)
    checks.check_names_units_and_entries(MANIFEST)
    checks.check_every_cell_reports_what_the_contract_asks(MANIFEST)
    checks.check_every_entry_has_its_files(MANIFEST)
    checks.check_drivers_and_generators_fit(MANIFEST)
    checks.check_waiting_entries(MANIFEST)


MS = 1_000_000


def _tick(send_s=None, busy_ms=(), lines=None, high=None, way=131_073):
    rec = {"flush_path": {}, "counters": {},
           "spans": {} if send_s is None else {"bench.send": send_s},
           "phase_rows": [("local:ingest.reader.busy", 10 * MS,
                           (10 + ms) * MS) for ms in busy_ms]
           + [("local:ingest.pump.batch", 0, 900 * MS)]}
    if lines is not None:
        rec["readers"] = [{"packets": n // 80, "lines": n, "busy_ns": 1}
                          for n in lines]
    if high is not None:
        rec["ring"] = {"high": dict(zip(("histo", "counter", "gauge",
                                         "set"), high)),
                       "way_capacity": way}
    return rec


def _ctx(ticks):
    return {"ticks": ticks, "trace": None, "device": {}, "run": {},
            "config": harness.load_config(CONFIG)}


@pytest.mark.parametrize("name", list(ENTRIES))
def test_entry_and_reader(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": ENTRIES[name],
                     "layer": "sockets + C++ bridge",
                     "moves": "ingest_rate", "workloads": [CELL]}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [CELL]
    # a record of the parent commit's program: nothing to read, no raise
    assert layers.read_metric(name, _ctx([])) is None
    assert layers.read_metric(name, _ctx([_tick(send_s=0.5)])) is None
    assert layers.read_metric(name, _ctx([zc._tick()])) is None
    ticks = [_tick(0.5, (100, 150, 50, 25), (100, 300, 400, 200),
                   (10, 13_107, 500, 0)),
             _tick(1.0, (100, 400, 300, 200), (250, 250, 250, 250),
                   (65_536, 100, 100, 7)),
             _tick(2.0, (100, 200, 1_000), (0, 0, 900, 100),
                   (10, 20, 98_305, 40))]
    # the busiest reader's 150 of 500 ms, 400 of 1,000, 1,000 of 2,000;
    # its 40, 25 and 90% of the lines; the fullest way a tick over
    # 131,073: the median of 10, 50 and 75%
    want = {"ingest.reader_busy_most": 40.0,
            "ingest.reader_lines_most": 40.0,
            "ingest.ring_fill_most": 100.0 * 65_536 / 131_073}
    assert layers.read_metric(name, _ctx(ticks)) == pytest.approx(want[name])
    # a rehearsal prints the counts, never the span
    assert (name in checks.counts_of(MANIFEST, CELL)) == (
        ENTRIES[name] == "program_counter")


# ------ what twelve outgrown tests of test_perfbench_zipf_cell.py held

def test_the_zipf_cell_and_its_entries_keep_the_contract():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == ZIPF)
    assert {k: v for k, v in cell.items() if k != "why"} == {
        "name": ZIPF, "config": zc.CONFIG, "traffic": zc.MIX, "chips": 1}
    assert checks.line_ok(cell["why"])
    names = checks.cell_names(MANIFEST)
    assert names.index(ZIPF) == names.index(FLEET_10K) + 1
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, ZIPF, "end_to_end")] == ["ingest_rate", "emit_latency_s",
                                          "setup_s"]
    every = [m["name"] for m in MANIFEST["per_layer"]]
    at = every.index("ingest.intern_us")
    assert every[at:at + len(zc.ENTRIES)] == list(zc.ENTRIES)
    assert every[at + len(zc.ENTRIES):] == list(ENTRIES)
    mine = [m["name"] for m in run.cell_metrics(MANIFEST, ZIPF, "per_layer")]
    ssf = {m["name"] for m in run.cell_metrics(MANIFEST, SSF, "per_layer")
           if not m["name"].startswith("ssf.")}
    assert mine == [n for n in every
                    if n in ssf | set(zc.APPENDED) | set(zc.ENTRIES)]


@pytest.mark.parametrize("name", list(zc.ENTRIES))
def test_the_zipf_cells_entry_and_reader(name):
    unit, source, layer, moves = zc.ENTRIES[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": [ZIPF, CELL]}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [ZIPF,
                                                                    CELL]
    assert layers.read_metric(name, zc._ctx([])) is None
    assert layers.read_metric(name, zc._ctx([zc._tick(
        {"keys.interned.local": 0}, [])])) is None
    ticks = [zc._tick({"keys.interned.local": keys},
                      [("local:ingest.intern", 0, ms),
                       ("local:engine.advance", 0, ms),
                       ("global:engine.advance", 0, 2 * ms)], live=live)
             for keys, ms, live in ((1_000, 2, 100), (4_000, 4, 32_768),
                                    (2_000, 6, 200))]
    want = {"ingest.intern_us": 2.0, "local.advance_ms": 4.0,
            "global.advance_ms": 8.0,
            "keys.slot_fill": 100.0 * 600 / 131_072,
            "ingest.sidestep_device_ms": 300.0}
    trace = {"module_seconds": {"jit_compress_impl": 0.9,
                                "jit__compress_impl": 5.0}}
    assert layers.read_metric(name, zc._ctx(ticks, trace)) == pytest.approx(
        want[name])
    if source == "device_trace":
        assert layers.read_metric(name, zc._ctx(ticks)) is None
        assert layers.read_metric(name, zc._ctx(ticks, {
            "module_seconds": {"jit__compress_impl": 5.0}})) is None


@pytest.mark.parametrize("name", list(zc.APPENDED))
def test_an_accepted_metric_of_the_globals_layers_lists_both_cells(name):
    (unit, better, source, layer), cells = zc.APPENDED[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "emit_latency_s",
                     "workloads": cells + [ZIPF, CELL]}
    assert checks.check_reported_where_it_says(MANIFEST, name) == [
        c for c in checks.cell_names(MANIFEST)
        if c in cells + [ZIPF, CELL]]
    for cell in (ZIPF, CELL):
        assert (name in checks.counts_of(MANIFEST, cell)) == (
            source == "program_counter")


def test_pr33s_to_pr45s_cells_found_by_name():
    one_k, ten_k = ("fanin32_global_1chip.fleet_1k",
                    "fanin32_global_1chip.fleet_10k")
    fleet_1k = "fanin32_mesh_global_4chip.fleet_1k"
    waiting = checks.waiting_entries()
    by_name = {w["name"]: w for w in MANIFEST["workloads"]}
    assert waiting["configs"][0] in MANIFEST["configs"]
    assert by_name[one_k] == waiting["workloads"][0]
    big = by_name[ten_k]
    assert {k: v for k, v in big.items() if k != "why"} == {
        "name": ten_k, "config": "fanin32_global_1chip",
        "traffic": "fleet_10k", "chips": 1}
    assert checks.line_ok(big["why"])
    # in the order their PRs appended them, this PR's one behind them
    names = checks.cell_names(MANIFEST)
    at = [names.index(c) for c in (one_k, ten_k, fleet_1k,
                                   "mesh_global_4chip.wide_100k", SSF,
                                   FLEET_10K, ZIPF, CELL)]
    assert at == sorted(at) and at[-4:] == list(range(len(names) - 4,
                                                      len(names)))
    small, mix = harness.load_mix("fleet_1k"), harness.load_mix("fleet_10k")
    assert mix["timers"].pop("keys") == 10 * small["timers"].pop("keys")
    told = ("name", "why", "scale", "rehearsal")
    assert {k: v for k, v in mix.items() if k not in told} == \
        {k: v for k, v in small.items() if k not in told}
    assert all(mix[k] != small[k] for k in told)
    for cell in (one_k, ten_k, fleet_1k, "mesh_global_4chip.wide_100k",
                 FLEET_10K):
        assert [m["name"] for m in run.cell_metrics(
            MANIFEST, cell, "end_to_end")] == ["emit_latency_s", "setup_s"]
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, one_k, "per_layer")] == [m["name"] for m in run.cell_metrics(
            MANIFEST, ten_k, "per_layer")]


def test_the_ssf_cell_reports_what_it_did_behind_two_more_prs():
    ssf_entries = {"ssf.span_us": ("us", "program_span"),
                   "ssf.fallback_share": ("%", "program_counter"),
                   "ssf.ring_wait_ms": ("ms", "program_span")}
    assert [m["name"] for m in run.cell_metrics(
        MANIFEST, SSF, "end_to_end")] == ["ingest_rate", "emit_latency_s",
                                          "setup_s"]
    mine = [m["name"] for m in run.cell_metrics(MANIFEST, SSF, "per_layer")]
    steady = [m["name"] for m in run.cell_metrics(
        MANIFEST, "two_tier_1chip.steady_10k", "per_layer")]
    pinned = {"global.flush_device_ms", "import.compress_device_ms",
              "import.land_pad_share"}
    assert [n for n in mine if not n.startswith("ssf.")] == [
        n for n in steady if n not in pinned]
    assert mine[-3:] == list(ssf_entries)
    # behind them what PR 45 appended, then what PR 49 did
    every = [m["name"] for m in MANIFEST["per_layer"]]
    at = every.index("ssf.span_us")
    assert every[at:] == list(ssf_entries) + list(zc.ENTRIES) + list(ENTRIES)
    for name, (unit, source) in ssf_entries.items():
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": "sockets + C++ bridge",
                         "moves": "ingest_rate", "workloads": [SSF]}
        assert checks.check_reported_where_it_says(MANIFEST, name) == [SSF]
    mix = harness.load_mix("spans_10k")
    steady_mix = harness.load_mix("steady_10k")
    for same in ("sets", "counters", "gauges", "distinct_ticks"):
        assert mix[same] == steady_mix[same]
    assert {k: v for k, v in mix["timers"].items() if k != "units"} \
        == steady_mix["timers"]


# ------------------------------------------- the cell end to end, in process

# `test_perfbench_rehearsal.py::test_cell_rehearses_end_to_end` runs the
# cell through `run.py` in a process of its own, as it does every cell of
# the manifest. Here the same driver and generator run in this process
# (two servers, four reader sockets, 64 client sockets on loopback), so
# that every tick's record and verdict can be looked at, and each
# control judged on the ticks the sound program was judged on.

TICKS = 7       # past the first evictions (the fourth flush)


def _drive(cfg, mix, ticks, control=None):
    """[(payload, record, verdict, verdict under `control`)] and the
    drop counters; `control` is one that alters the answers alone."""
    from veneur_tpu import kernels
    payloads, _s = flows.build(cfg, mix, 2**31 + 49, lambda _m: None)
    fallbacks = kernels.fallback_total()     # process-wide: as a delta
    driver = harness.load_driver(cfg).Driver(cfg, True)
    spans, gcm = harness.Spans(), harness.GcMeter()
    meter = harness.CompileMeter()
    tol = cfg["guarantees"]["tolerances"]
    out = []
    try:
        for i in range(ticks):
            p = payloads[i % len(payloads)]
            rec = driver.tick(p, 1_000 + 10 * i, spans, gcm, meter)
            rows = (list(driver.lsink.flushes[-1]),
                    list(driver.gsink.flushes[-1]))
            ledgers = copy.deepcopy(driver.ledgers)
            v, vc = driver.check(p, rec, tol), None
            if control is not None:
                # the same tick again as the control answers it
                driver.lsink.flushes[-1], driver.gsink.flushes[-1] = rows
                ledgers, driver.ledgers = driver.ledgers, ledgers
                driver.cfg["control"] = control
                vc = driver.check(p, rec, tol)
                driver.cfg["control"], driver.ledgers = None, ledgers
            out.append((p, rec, v, vc))
        drops = driver.drop_counters()
        drops["kernels.fallback_total"] -= fallbacks
    finally:
        driver.stop()
        gcm.close()
    return out, drops


def _rehearsal(**common):
    cfg, mix = (harness.load_config(CONFIG, True),
                harness.load_mix(MIX, True))
    cfg["control"], cfg["study"] = None, False
    cfg["common"] = {**cfg["common"], **common}
    return cfg, mix


@pytest.fixture(scope="module")
def driven():
    cfg, mix = _rehearsal()
    return (cfg, *_drive(cfg, mix, TICKS,
                         cfg["controls"]["handover_first_writer"]))


def test_the_cell_rehearsed_comes_out_correct(driven):
    cfg, ticks, drops = driven
    assert not any(drops.values()), drops
    for p, rec, v, _vc in ticks:
        assert v["mismatches"] == []
        assert reference.within(v["numbers"]), v["numbers"]
        for number in ("exact_mismatches", "handover_gauge_mismatches",
                       "keys_interned_mismatch", "keys_evicted_mismatch",
                       "own_timers_mismatch", "bridge.lost_lines"):
            assert v["numbers"][number] == (0.0, 0.0), number
        assert {"worst_p50_rank", "worst_p99_rank", "worst_set_rel",
                "worst_small_set_off",
                "worst_pct_outside_rel"} <= set(v["numbers"])
        assert v["failed"] == 0 and v["attempted"] == p["n_lines"]
        assert not layers.missing_keys(rec)
        # four readers took the tick's lines between them, each line
        # once, and no sub-ring came near full
        assert len(rec["readers"]) == cfg["local"]["num_readers"] == 4
        assert sum(r["lines"] for r in rec["readers"]) == p["n_lines"]
        assert sum(r["packets"] for r in rec["readers"]) == sum(
            p["n_datagrams"])
        assert 0 < max(rec["ring"]["high"].values()) \
            < rec["ring"]["way_capacity"] // 2
        busy = [row for row in rec["phase_rows"]
                if row[0] == "local:ingest.reader.busy"]
        assert len(busy) == sum(1 for r in rec["readers"] if r["packets"])
    # the steady state of interning and evicting is reached
    assert sum(ticks[-1][1]["flush_path"]["local"]["keys_evicted"]) > 0


def test_the_three_readers_on_the_recorded_ticks(driven):
    _cfg, ticks, _drops = driven
    ctx = _ctx([rec for _p, rec, _v, _vc in ticks])
    share = layers.read_metric("ingest.reader_lines_most", ctx)
    assert 25.0 <= share <= 100.0
    assert 0 < layers.read_metric("ingest.ring_fill_most", ctx) < 50
    # a CPU's seconds, never reported: the reader only has to find them
    assert layers.read_metric("ingest.reader_busy_most", ctx) > 0


def test_the_handover_control_comes_out_not_correct(driven):
    """`handover_first_writer` answers every handed-over gauge with its
    first flow's last write: those and nothing else stop agreeing."""
    cfg, ticks, _drops = driven
    assert cfg["controls"]["handover_first_writer"] == {
        "why": cfg["controls"]["handover_first_writer"]["why"],
        "handover": "first_writer"}
    for p, _rec, v, vc in ticks:
        handed = len(p["handover"]["first"])
        assert handed > 0
        assert vc["numbers"]["handover_gauge_mismatches"] == (handed, 0.0)
        assert vc["numbers"]["exact_mismatches"] == (handed, 0.0)
        assert not reference.within(vc["numbers"])
        same = set(v["numbers"]) - {"handover_gauge_mismatches",
                                    "exact_mismatches"}
        assert {k: vc["numbers"][k] for k in same} == {
            k: v["numbers"][k] for k in same}


def test_the_precision_control_comes_out_not_correct():
    """`hll_precision13`, the Zipf file's, on this cell: the set bank
    one step of precision down breaks the set limits and nothing of
    the readers'."""
    cfg, mix = _rehearsal()
    control = cfg["controls"]["hll_precision13"]
    cfg, mix = _rehearsal(**control["common"])
    ticks, _drops = _drive(cfg, mix, 2)
    failing = {name for _p, _rec, v, _vc in ticks
               for name, (val, lim) in v["numbers"].items() if val > lim}
    assert "worst_small_set_off" in failing
    assert not failing & {"exact_mismatches", "handover_gauge_mismatches",
                          "keys_interned_mismatch", "keys_evicted_mismatch",
                          "own_timers_mismatch", "bridge.lost_lines"}

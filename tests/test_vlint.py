"""vlint unit tests: every check ID must catch its seeded fixture
violation (exact rule AND line), the clean fixture must stay silent,
and the suppression contract must hold (reason suppresses, no reason
reports VL00 and keeps the finding)."""

import os

from tools.vlint import run_paths

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "vlint_fixtures")


def lint(*names):
    vs = run_paths([os.path.join(FIX, n) for n in names])
    return [(v.rule, v.line) for v in vs]


def test_jx01_tracer_leak_item():
    assert lint("jx01_bad.py") == [("JX01", 7)]


def test_jx02_donation_use_after_dispatch():
    assert lint("jx02_bad.py") == [("JX02", 9)]


def test_jx03_host_sync_outside_flush_modules():
    assert lint("jx03_bad.py") == [("JX03", 6)]


def test_th01_unguarded_write_multi_thread_method():
    # exactly the unguarded write — the lock-guarded one on line 21
    # must NOT be reported
    assert lint("server.py") == [("TH01", 19)]


def test_cf01_cfg_plumbing_missing_at_sibling():
    assert lint("cf01_bad.py") == [("CF01", 21)]


def test_na01_nullptr_assign():
    # the guarded twin function in the same file must stay silent
    assert lint("na01_bad.cpp") == [("NA01", 12)]


def test_na02_magic_recursion_cap():
    assert lint("na02_bad.cpp") == [("NA02", 5)]


def test_na02_cap_diverges_from_python_constant():
    assert lint("na02_diverge.cpp", "na02_parity.py") == [("NA02", 7)]


def test_na03_frame_layout_diverges_from_python_constants():
    # the missing byte order is reported where the layout starts, the
    # diverging maximum on its own line; the two equal pairs are silent
    assert lint("na03_diverge.cpp", "na03_parity.py") == [("NA03", 3),
                                                          ("NA03", 5)]


def test_na03_frame_layout_without_a_python_side():
    assert [r for r, _l in lint("na03_diverge.cpp")] == ["NA03"] * 4


def test_na03_holds_the_real_tree():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(repo, "native", "vtpu_ingest.cpp"),
             os.path.join(repo, "veneur_tpu", "ssf", "framing.py"),
             os.path.join(repo, "veneur_tpu", "ingest", "native.py")]
    assert [v for v in run_paths(paths) if v.rule.startswith("NA")] == []
    # and it is looking: the bridge's file does define the layout
    with open(paths[0]) as f:
        assert "constexpr int kSsfMaxFrameLength" in f.read()


def test_na04_stats_array_diverges_from_python_and_from_its_writes():
    # the length against its Python twin, and against the highest index
    # written (out[2 + i] over four banks is out[5]: six fields)
    assert lint("na04_diverge.cpp", "na04_parity.py") == [("NA04", 4),
                                                          ("NA04", 4)]


def test_na04_stats_array_without_a_python_side():
    assert [r for r, _l in lint("na04_diverge.cpp")] == ["NA04"] * 2


def test_na04_holds_the_real_tree():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(repo, "native", "vtpu_ingest.cpp"),
             os.path.join(repo, "veneur_tpu", "ingest", "native.py"),
             os.path.join(repo, "veneur_tpu", "ssf", "framing.py")]
    assert [v for v in run_paths(paths) if v.rule.startswith("NA")] == []
    # and it is looking: the per-bank key counts are among the fields
    with open(paths[0]) as f:
        text = f.read()
    assert "constexpr int kStatsFields" in text
    assert "out[28 + i] = br->banks[i].keys_evicted" in text


def test_na05_a_stage_parsed_into_without_its_arrival_stamp():
    # the stamped loop in the same file stays silent
    assert lint("na05_bad.cpp") == [("NA05", 15)]


def test_na05_holds_the_real_tree():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "native", "vtpu_ingest.cpp")
    assert [v for v in run_paths([path]) if v.rule == "NA05"] == []
    # and it is looking: five functions own a stage and parse into it
    with open(path) as f:
        text = f.read()
    assert text.count("LocalStage st;") == 5
    assert text.count("st.order = ") == 5
    assert "st->c[bk].push_back(static_cast<int32_t>(st->order));" in text


def test_gc01_the_collectors_switch_outside_the_guard():
    # the import of a switch, disable/enable around a build, a
    # threshold through an alias, a bare reference inside a class that
    # only shares the guard's name; reads, collect() and the suppressed
    # call stay silent
    assert lint("gc01_bad.py") == [("GC01", 7), ("GC01", 11),
                                   ("GC01", 15), ("GC01", 19),
                                   ("GC01", 27)]


def test_gc01_holds_the_real_tree_and_finds_the_guard():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tree = os.path.join(repo, "veneur_tpu")
    assert [v for v in run_paths([tree]) if v.rule == "GC01"] == []
    # and it is looking: the guard's two calls are there, and they are
    # flagged the moment the class is not the configured home
    from tools.vlint.config import DEFAULT_CONFIG
    from tools.vlint.core import load_project
    from tools.vlint.py_checks import check_gc01
    mod = load_project(
        [os.path.join(tree, "metrics.py")]).py_modules[0]
    moved = dict(DEFAULT_CONFIG,
                 gc01_home=("veneur_tpu/metrics.py", "Elsewhere"))
    assert check_gc01(mod, DEFAULT_CONFIG) == []
    found = check_gc01(mod, moved)
    assert len(found) == 2 and {v.rule for v in found} == {"GC01"}


def test_rs01_raw_egress_bypasses_resilience():
    # one urlopen + one grpc channel construction, exact lines
    assert lint("rs01_bad.py") == [("RS01", 9), ("RS01", 14)]


def test_rs01_allows_the_resilience_layer_itself():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "resilience.py")
    assert [v for v in run_paths([path]) if v.rule == "RS01"] == []


def test_dr01_raw_writes_in_durability_scope():
    # open('wb'), write-flag os.open, os.write, Path.write_bytes, and
    # the statically-opaque variable mode — exact lines; the rb read,
    # the O_RDONLY os.open, and the suppressed write must all stay
    # silent
    assert lint("dr01_bad.py") == [("DR01", 10), ("DR01", 15),
                                   ("DR01", 16), ("DR01", 21),
                                   ("DR01", 44)]


def test_dr01_allows_the_journal_module_itself():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(repo, "veneur_tpu", "durability")
    assert [v for v in run_paths([pkg]) if v.rule == "DR01"] == []


def test_dr01_out_of_scope_modules_unchecked():
    # raw writes OUTSIDE the durability scope (e.g. the localfile
    # plugin) are not DR01's business
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "sinks", "basic.py")
    assert [v for v in run_paths([path]) if v.rule == "DR01"] == []


def test_dr02_bank_leaf_bytes_outside_records():
    # .tobytes() on a leaf and np.frombuffer — exact lines; the
    # suppressed wire row and plain bytes() must stay silent
    assert lint("dr02_bad.py") == [("DR02", 9), ("DR02", 13)]


def test_dr02_allows_the_records_module_itself():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "durability", "records.py")
    assert [v for v in run_paths([path]) if v.rule == "DR02"] == []


def test_dr02_out_of_scope_modules_unchecked():
    # byte moves OUTSIDE the engine-state scope (e.g. the native
    # bridge's poll-buffer marshalling) are not DR02's business
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "ingest", "native.py")
    assert [v for v in run_paths([path]) if v.rule == "DR02"] == []


def test_sr02_tdigest_bank_writes_outside_owner():
    # the construction (line 9), the _replace(weight=...) (line 20) and
    # the statically-opaque **kwargs forms (lines 34/38) are flagged;
    # the scalar-field _replace and the suppressed write must stay
    # silent
    assert lint("sr02_bad.py") == [("SR02", 9), ("SR02", 20),
                                   ("SR02", 34), ("SR02", 38)]


def test_sr02_allows_the_ops_module_itself():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "ops", "tdigest.py")
    assert [v for v in run_paths([path]) if v.rule == "SR02"] == []


def test_tl01_adhoc_self_metric_names():
    # the hand-built InterMetric (13), the f-string head (17), and the
    # raw dict counter's two literals (21/22); the docstring mention,
    # the suppressed legacy exporter, and the non-matching prefix all
    # stay silent
    assert lint("tl01_bad.py") == [("TL01", 13), ("TL01", 17),
                                   ("TL01", 21), ("TL01", 22)]


def test_tl01_allows_the_registry_itself():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "observe", "registry.py")
    assert [v for v in run_paths([path]) if v.rule == "TL01"] == []


def test_tl01_out_of_scope_modules_unchecked():
    # tooling outside veneur_tpu/ may spell metric names freely
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "tools", "vlint", "py_checks.py")
    assert [v for v in run_paths([path]) if v.rule == "TL01"] == []


def test_tr01_trace_literals_outside_wire():
    # the hand-rolled trace header (7), close header (11), re-spelled
    # lowercase read (16), and the gRPC metadata carrier key (20); the
    # docstring mention, the suppressed diagnostic, and the envelope
    # headers (TR01 covers only the TRACE context + the metadata
    # carrier) all stay silent
    assert lint("tr01_bad.py") == [("TR01", 7), ("TR01", 11),
                                   ("TR01", 16), ("TR01", 20)]


def test_tr01_allows_wire_itself():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "cluster", "wire.py")
    assert [v for v in run_paths([path]) if v.rule == "TR01"] == []


def test_tr01_out_of_scope_modules_unchecked():
    # tooling outside veneur_tpu/ may name the headers freely
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "tools", "vlint", "py_checks.py")
    assert [v for v in run_paths([path]) if v.rule == "TR01"] == []


def test_wc01_q16_spellings_outside_wire():
    # the hand-rolled JSON key (15), the pb-field read (19) and write
    # (23); the docstring mention and the suppressed presence probe
    # stay silent
    assert lint("wc01_bad.py") == [("WC01", 15), ("WC01", 19),
                                   ("WC01", 23)]


def test_wc01_allows_wire_itself():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "cluster", "wire.py")
    assert [v for v in run_paths([path]) if v.rule == "WC01"] == []


def test_wc01_out_of_scope_modules_unchecked():
    # tooling outside veneur_tpu/ may name the wire keys freely
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "tools", "vlint", "py_checks.py")
    assert [v for v in run_paths([path]) if v.rule == "WC01"] == []


def test_ov01_uncounted_drop_verdicts():
    # the uncounted branch drop (12), the count-in-another-branch drop
    # (21) and the bare-return drop (39); the counted verdicts, the
    # nested conditional count, the non-decision helper, and the
    # suppressed escape all stay silent
    assert lint("ov01_bad.py") == [("OV01", 12), ("OV01", 21),
                                   ("OV01", 39)]


def test_ov01_admission_layer_is_clean():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "ingest", "admission.py")
    assert [v for v in run_paths([path]) if v.rule == "OV01"] == []


def test_ov01_out_of_scope_modules_unchecked():
    # decision-ish names outside the admission scope are not OV01's
    # business (the resilience layer has its own accounting)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "resilience.py")
    assert [v for v in run_paths([path]) if v.rule == "OV01"] == []


def test_clean_fixture_is_clean():
    assert lint("clean.py") == []


def test_suppression_with_reason_suppresses():
    got = lint("suppressed.py")
    # documented sync on line 8 is suppressed; the reasonless disable
    # on line 12 suppresses nothing and is itself reported as VL00
    assert ("JX03", 8) not in got
    assert ("JX03", 12) in got
    assert ("VL00", 12) in got
    assert len(got) == 2


def test_violation_str_is_clickable():
    vs = run_paths([os.path.join(FIX, "jx01_bad.py")])
    assert str(vs[0]).startswith(
        os.path.join(FIX, "jx01_bad.py").replace(os.sep, "/") + ":7: ")


def test_sk01_sketch_boundary_violations():
    # direct sketch-module imports (5, 7, 9), bank constructions (15 —
    # which also trips SR02's mean/weight heuristic — and 19); the
    # docstring mention, the suppressed bench exception, and the
    # registry-obtained engine stay silent
    assert lint("sk01_bad.py") == [
        ("SK01", 5), ("SK01", 7), ("SK01", 9), ("SK01", 15),
        ("SR02", 15), ("SK01", 19)]


def test_sk01_registry_and_ops_are_allowed():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in (("veneur_tpu", "sketches", "ull.py"),
                ("veneur_tpu", "sketches", "tdigest_engine.py"),
                ("veneur_tpu", "ops", "tdigest.py"),
                ("veneur_tpu", "parallel", "mesh.py")):
        path = os.path.join(repo, *rel)
        assert [v for v in run_paths([path]) if v.rule == "SK01"] == []


def test_sk01_pipeline_routes_through_registry():
    # the refactored pipeline holds engine objects only — a future
    # direct ops import there is exactly the drift SK01 exists for
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "models", "pipeline.py")
    assert [v for v in run_paths([path]) if v.rule == "SK01"] == []


def test_ds01_unmarked_bank_landings():
    # one finding per function, at its first landing line: the bank-
    # attr assignment through _kern, the inert-helper delegation, and
    # the landing-leaf call in the helper itself; the marked, the
    # marking-helper-delegating, and the suppressed functions stay
    # silent
    assert lint("ds01_bad.py") == [("DS01", 11), ("DS01", 29),
                                   ("DS01", 34)]


def test_ds01_pipeline_landing_sites_all_marked():
    # the bitmap feeds BOTH delta checkpoints and the incremental
    # flush (ISSUE 11): every device-landing write in the live
    # pipeline must mark, or carry a documented suppression
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "models", "pipeline.py")
    assert [v for v in run_paths([path]) if v.rule == "DS01"] == []


def test_ds01_out_of_scope_modules_unchecked():
    # the mesh engine carries no per-slot bitmaps (excluded from both
    # consumers) — its bank writes are not DS01's business
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "parallel", "engine.py")
    assert [v for v in run_paths([path]) if v.rule == "DS01"] == []


def test_qt01_query_path_touches_live_engine():
    # one finding per offense: the `with engine.lock:`, the explicit
    # .lock.acquire(), the bank-attr write, and BOTH halves of the
    # tuple bank write; the scratch-engine shape, the tier's own
    # private lock (`self._lock`), and the suppressed block stay
    # silent
    assert lint("qt01_bad.py") == [("QT01", 10), ("QT01", 14),
                                   ("QT01", 21), ("QT01", 24),
                                   ("QT01", 24)]


def test_qt01_history_module_is_clean():
    # the invariant the check exists for: the shipping query tier
    # never acquires an engine lock or writes a bank
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "durability", "history.py")
    assert [v for v in run_paths([path]) if v.rule == "QT01"] == []


def test_qt01_out_of_scope_modules_unchecked():
    # the pipeline legitimately takes its own lock and writes its own
    # banks — not QT01's business
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "veneur_tpu", "models", "pipeline.py")
    assert [v for v in run_paths([path]) if v.rule == "QT01"] == []


def test_pk01_pallas_outside_kernels_package():
    # both import spellings + the pallas_call invocation; the
    # suppressed entry and the attribute-only use stay silent
    assert lint("pk01_bad.py") == [("PK01", 6), ("PK01", 7),
                                   ("PK01", 16)]


def test_pk01_kernel_entry_without_counted_fallback():
    # flagged: the bare delegating entry, the direct entry, the entry
    # that only READS fallback_total (a getter is not a degradation
    # branch), and the class METHOD reaching pallas_call. Silent: the
    # guarded entry, the entry delegating to it, the guarded method,
    # the private helpers, and the non-kernel helper
    assert lint("pk01_kernels_bad.py") == [("PK01", 25), ("PK01", 29),
                                           ("PK01", 56), ("PK01", 64)]


def test_pk01_shipping_tree_is_clean():
    # the invariant the check exists for: every pl.* primitive lives
    # in veneur_tpu/kernels/ with counted-fallback entry points, and
    # the kernel consumers (ops/hll.py, the pipeline, the engines)
    # never touch pallas directly
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(repo, "veneur_tpu", p) for p in
             ("kernels", "ops", os.path.join("models", "pipeline.py"),
              "sketches")]
    assert [v for v in run_paths(paths) if v.rule == "PK01"] == []

"""Default check configuration.

Paths are suffix-matched against posix-normalised file paths, so the
tool behaves the same whether invoked from the repo root or with
absolute paths.
"""

DEFAULT_CONFIG = {
    # JX03: modules allowed to synchronise with the device. The flush /
    # fetch layer owns every legitimate device_get/block_until_ready in
    # the serving path; the native/*.py entry is an offline stress
    # harness, not a server.
    "jx03_allow": (
        "veneur_tpu/models/pipeline.py",
        "veneur_tpu/parallel/mesh.py",
        "veneur_tpu/parallel/engine.py",
        "native/tsan_stress.py",
    ),
    # TH01: files whose classes run methods from multiple threads
    # (listener/worker/flush topology lives here).
    "th01_files": ("server.py", "engine.py"),
    # TH01: methods whose name ends with one of these run entirely under
    # a lock the CALLER holds (project convention).
    "th01_locked_suffixes": ("_locked",),
    # CF01: attribute-call families checked for config-plumbing parity —
    # sibling calls share a receiver and a method-name prefix token.
    "cf01_prefixes": ("start",),
    # NA02: the Python-side parity constant for the native decoder's
    # recursion cap.
    "na02_py_constant": "PB_SKIP_MAX_DEPTH",
    # NA03: the SSF stream's frame layout, native constant -> its twin
    # in ssf/framing.py.
    "na03_pairs": {
        "kSsfFrameVersion": "VERSION_BYTE",
        "kSsfFrameLengthBytes": "LENGTH_BYTES",
        "kSsfFrameLengthLittleEndian": "LENGTH_LITTLE_ENDIAN",
        "kSsfMaxFrameLength": "MAX_FRAME_LENGTH",
    },
    # NA04: the layout of the bridge's stats array, native constant ->
    # its twin in ingest/native.py.
    "na04_pairs": {
        "kStatsFields": "STATS_FIELDS",
    },
    # RS01: modules allowed to make raw urlopen / grpc-channel calls —
    # the resilience layer itself owns the one raw transport.
    "rs01_allow": (
        "veneur_tpu/resilience.py",
    ),
    # SR02: the one module allowed to write TDigestBank.mean/weight —
    # it owns the sorted-prefix invariant the merge-path compress
    # depends on for correctness. sketches/req.py is allowed because
    # its REQBank NamedTuple ALSO carries a `weight` field (the
    # compactor item weights — no cluster-order invariant applies to
    # them) and SR02's _replace heuristic matches by field name.
    "sr02_allow": (
        "veneur_tpu/ops/tdigest.py",
        "veneur_tpu/sketches/req.py",
    ),
    # DR01: where the durable-state write discipline applies (path
    # substring match; the /dr01_ entry scopes the check's own test
    # fixtures in) and the one module allowed raw file writes — the
    # journal owns the CRC32C framing / fsync / atomic-rename contract.
    "dr01_scope": (
        "veneur_tpu/durability/",
        "/dr01_",
    ),
    "dr01_allow": (
        "veneur_tpu/durability/journal.py",
    ),
    # DR02: engine-state serialization discipline — raw bank-leaf
    # byte moves (`.tobytes()` / `np.frombuffer`) are single-homed in
    # durability/records.py (path substring match; /dr02_ scopes the
    # check's own fixture in). A stray tobytes/frombuffer in the
    # engine/ops/cluster layers could re-encode bank rows outside the
    # bit-exact record codecs the kill-restart identity depends on.
    # Intentional non-bank byte moves (the HLL wire row, the CRC lane
    # fold) suppress with a reason.
    "dr02_scope": (
        "veneur_tpu/durability/",
        "veneur_tpu/models/",
        "veneur_tpu/ops/",
        "veneur_tpu/cluster/",
        "/dr02_",
    ),
    "dr02_allow": (
        "veneur_tpu/durability/records.py",
    ),
    # OV01: counted-degradation discipline for the overload-defense
    # layer (path substring match; /ov01_ scopes the check's own
    # fixture in): a drop verdict (`return None`) in an admit*/fold*/
    # shed* decision function must increment a registry counter in the
    # same branch — silent degradation is the bug class this layer
    # exists to eliminate.
    "ov01_scope": (
        "veneur_tpu/ingest/",
        "/ov01_",
    ),
    "ov01_decision_prefixes": ("admit", "fold", "shed"),
    # TL01: where the veneur.* self-metric naming monopoly applies
    # (path substring match; /tl01_ scopes the check's own fixture in)
    # and the one module allowed to mint those names — the unified
    # telemetry registry owns the key -> wire-name mapping.
    "tl01_scope": (
        "veneur_tpu/",
        "/tl01_",
    ),
    "tl01_allow": (
        "veneur_tpu/observe/registry.py",
    ),
    # SK01: sketch-engine registry boundary (path substring match;
    # /sk01_ scopes the check's own fixture in). Sketch banks and
    # sketch math live in veneur_tpu/sketches/ + the blessed ops/
    # kernels; everywhere else holds engine objects from the registry.
    # parallel/ is allowed: the mesh engine owns its sharded banks
    # directly on the t-digest/HLL ops, and the backend selection
    # refuses non-default engines there (config validation + the mesh
    # constructor guard).
    "sk01_scope": (
        "veneur_tpu/",
        "/sk01_",
    ),
    "sk01_allow": (
        "veneur_tpu/sketches/",
        "veneur_tpu/ops/",
        "veneur_tpu/parallel/",
    ),
    # DS01: dirty-bitmap marking discipline (path substring match;
    # /ds01_ scopes the check's own fixture in): every device-landing
    # bank write in the pipeline module must mark the dirty bitmap —
    # it feeds BOTH the incremental flush and delta checkpoints
    # (ISSUE 11). Non-landing writes (fresh swap, warmup padding,
    # setup) carry documented suppressions.
    "ds01_scope": (
        "veneur_tpu/models/pipeline.py",
        "/ds01_",
    ),
    # TR01: where the trace-context wire-literal monopoly applies
    # (path substring match; /tr01_ scopes the check's own fixture in)
    # and the one module allowed to spell the forward trace headers /
    # envelope metadata key — cluster/wire.py owns both directions of
    # the encoding, like it owns the envelope codecs.
    "tr01_scope": (
        "veneur_tpu/",
        "/tr01_",
    ),
    "tr01_allow": (
        "veneur_tpu/cluster/wire.py",
    ),
    # WC01: quantized-centroid codec single-homing (path substring
    # match; /wc01_ scopes the check's own fixture in) — the q16 wire
    # row's spellings ("centroids_q16" JSON key, `packed_centroids` pb
    # field) and therefore its quantization math live ONLY in
    # cluster/wire.py, like the envelope/trace codecs (TR01).
    "wc01_scope": (
        "veneur_tpu/",
        "/wc01_",
    ),
    "wc01_allow": (
        "veneur_tpu/cluster/wire.py",
    ),
    # QT01: read-path isolation for the time-travel query tier (path
    # substring match; /qt01_ scopes the check's own fixture in) —
    # query code must never acquire an engine ingest/flush lock or
    # write live bank attributes; it works on scratch engines through
    # their public restore/import/flush surface only.
    "qt01_scope": (
        "veneur_tpu/durability/history.py",
        "/qt01_",
    ),
    # PK01: pallas-kernel containment (ISSUE 15; path substring match,
    # /pk01_ scopes the check's own fixtures in): pl.* imports and
    # pallas_call invocations outside veneur_tpu/kernels/ are flagged,
    # and inside the package every public entry reaching a pallas_call
    # must carry a counted fallback branch (count_fallback ->
    # veneur.kernels.fallback_total). pk01_kernel_paths names the
    # kernel-package scope (the fixtures' path rides along).
    "pk01_scope": (
        "veneur_tpu/",
        "/pk01_",
    ),
    "pk01_kernel_paths": (
        "veneur_tpu/kernels/",
        "/pk01_kernels_",
    ),
    # GC01: the collector's switch (gc.disable / enable / freeze /
    # set_threshold) is single-homed in the guard that holds collection
    # off while a frame's rows are built (ISSUE 50; path substring
    # match, /gc01_ scopes the check's own fixture in): the file and
    # the class in it that may touch it.
    "gc01_scope": (
        "veneur_tpu/",
        "/gc01_",
    ),
    "gc01_home": ("veneur_tpu/metrics.py", "_CollectorHold"),
}

"""Pluggable sketch engines (ISSUE 10): cross-engine oracle suite,
bit-commutative merge properties, wire/stamp codecs, the parameterized
two-tier engine-parity probe, and the mixed-fleet loud-reject gate.

Every engine runs against the same ingest streams and must satisfy its
OWN documented error bound vs a numpy exact oracle; merge(a, b) must
equal merge(b, a) bit-for-bit per engine; a deliberately mismatched
sender/global pair must be refused loudly (counted + visible at
/debug/fleet), never silently merged.
"""

import functools
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu import observe, sketches
from veneur_tpu.config import read_config
from veneur_tpu.ingest.parser import parse_metric
from veneur_tpu.models.pipeline import AggregationEngine, EngineConfig
from veneur_tpu.server import Server
from veneur_tpu.sinks.basic import CaptureMetricSink
from veneur_tpu.sketches.hll_engine import HLLEngine
from veneur_tpu.sketches.req import REQEngine
from veneur_tpu.sketches.tdigest_engine import TDigestEngine
from veneur_tpu.sketches.ull import ULLEngine

S = observe.SERVER_SCOPE


@functools.lru_cache(maxsize=None)
def _jit(eng, name):
    # engines are frozen dataclasses (hashable): one compiled kernel
    # per (engine params, op) across the whole suite, not per test
    return jax.jit(getattr(eng, name))


def _bits_equal(a, b) -> bool:
    """Bit-exact pytree equality (NaN-safe: compares byte views)."""
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype \
                or x.tobytes() != y.tobytes():
            return False
    return True


def _member_hashes(n, tag=""):
    from veneur_tpu.utils.hashing import set_member_hash
    return np.array([set_member_hash(f"member-{tag}-{i}")
                     for i in range(n)], np.uint64)


def _insert_members(eng, bank, slot, hashes, batch=8192):
    ins = _jit(eng, "insert_impl")
    idx, vals = eng.host_hash_to_updates(hashes)
    for i in range(0, len(hashes), batch):
        seg = slice(i, min(len(hashes), i + batch))
        n = seg.stop - seg.start
        s = np.full(batch, -1, np.int32)
        s[:n] = slot
        ip = np.zeros(batch, np.int32)
        ip[:n] = idx[seg]
        vp = np.zeros(batch, np.uint8)
        vp[:n] = vals[seg]
        bank = ins(bank, jnp.asarray(s), jnp.asarray(ip),
                   jnp.asarray(vp))
    return bank


def _estimate(eng, bank):
    host = jax.device_get(eng.estimate_device(bank))
    host = {k: np.asarray(v) for k, v in host.items()}
    eng.estimate_finalize(host)
    return np.asarray(host["s_est"], np.float64)


class TestCardinalityOracle:
    """Each set engine vs exact distinct counts, inside its documented
    bound (deterministic hash streams -> deterministic estimates; the
    4-sigma margin makes the bound stream-robust, not flaky)."""

    @pytest.mark.parametrize("eng", [HLLEngine(precision=14),
                                     ULLEngine(precision=13)],
                             ids=["hll", "ull"])
    @pytest.mark.parametrize("n", [500, 60_000])
    def test_estimate_within_bound(self, eng, n):
        bank = eng.init(2)
        bank = _insert_members(eng, bank, 0, _member_hashes(n))
        est = _estimate(eng, bank)
        bound = 4.0 * eng.nominal_error() + 0.01  # + small-n fuzz
        assert abs(est[0] - n) / n <= bound, (est[0], n, bound)
        assert est[1] == 0.0                      # untouched slot

    @pytest.mark.parametrize("eng", [HLLEngine(precision=12),
                                     ULLEngine(precision=12)],
                             ids=["hll", "ull"])
    def test_merge_matches_union_oracle(self, eng):
        a = eng.init(1)
        b = eng.init(1)
        ha = _member_hashes(8000, "a")
        hb = np.concatenate([ha[:4000], _member_hashes(6000, "b")])
        a = _insert_members(eng, a, 0, ha)
        b = _insert_members(eng, b, 0, hb)
        merged = eng.merge_banks(a, b)
        est = _estimate(eng, merged)[0]
        true_union = 8000 + 6000                  # 4000 overlap
        assert abs(est - true_union) / true_union <= \
            4.0 * eng.nominal_error() + 0.01

    def test_ull_bank_half_the_hll_bytes_at_nominal_error(self):
        """The state-size claim the bench row demonstrates: the default
        ULL bank (p=13) is <= 0.75x the default HLL bank (p=14) while
        both sit in the same ~1%% nominal error class."""
        hll, ull = HLLEngine(precision=14), ULLEngine(precision=13)
        assert ull.state_bytes(100) <= 0.75 * hll.state_bytes(100)
        assert ull.nominal_error() <= 0.011
        assert hll.nominal_error() <= 0.011


class TestMergeCommutativity:
    """merge(a, b) == merge(b, a) bit-identically, per engine."""

    @pytest.mark.parametrize("eng", [HLLEngine(precision=10),
                                     ULLEngine(precision=10)],
                             ids=["hll", "ull"])
    def test_set_engines(self, eng):
        a = _insert_members(eng, eng.init(3), 1, _member_hashes(3000, "x"))
        b = _insert_members(eng, eng.init(3), 1, _member_hashes(2000, "y"))
        assert _bits_equal(eng.merge_banks(a, b), eng.merge_banks(b, a))

    @staticmethod
    def _ull_batch(seed, K=11, p=9, n=2048):
        """A pre-populated ULL bank and one batch against it: padding
        slots, and a quarter of the batch aimed at registers another
        quarter also writes, with conflicting packed values."""
        rng = np.random.default_rng(seed)
        eng = ULLEngine(precision=p)
        regs = rng.integers(0, 200, (K, 1 << p)).astype(np.uint8)
        slots = rng.integers(-1, K, n).astype(np.int32)
        idx = rng.integers(0, 1 << p, n).astype(np.int32)
        idx[: n // 4] = idx[n // 4: n // 2]
        slots[: n // 4] = slots[n // 4: n // 2]
        vals = ((rng.integers(1, 50, n) << 2)
                | rng.integers(0, 4, n)).astype(np.uint8)
        return eng, regs, (slots, idx, vals)

    @pytest.mark.parametrize("case", ["again", "reversed", "shuffled"])
    def test_ull_insert_is_a_lattice_join(self, case):
        """The insert that serves (sort + segmented scan + gather) is a
        join: landing a batch a second time leaves the registers as
        they are, and the order of a batch's rows does not reach the
        bytes — what lets a replayed or re-chunked landing be exact."""
        eng, regs, batch = self._ull_batch(seed=7)
        ins = _jit(eng, "insert_impl")

        def land(bank, rows):
            return ins(bank, *(jnp.asarray(c[rows]) for c in batch))

        n = batch[0].size
        warm = type(eng.init(1))(registers=jnp.asarray(regs))
        once = land(warm, np.arange(n))
        rows = {"again": np.arange(n),
                "reversed": np.arange(n)[::-1].copy(),
                "shuffled": np.random.default_rng(1).permutation(n)}[case]
        got = land(once if case == "again" else warm, rows)
        assert np.asarray(once.registers).tobytes() \
            == np.asarray(got.registers).tobytes()
        assert (np.asarray(once.registers) != regs).any()

    @pytest.mark.parametrize(
        "eng", [TDigestEngine(compression=100.0, buffer_depth=64),
                REQEngine(levels=2, capacity=64)],
        ids=["tdigest", "req"])
    def test_histogram_engines(self, eng):
        rng = np.random.default_rng(7)
        add = _jit(eng, "add_batch_impl")

        def fill(seed):
            r = np.random.default_rng(seed)
            bank = eng.init(3)
            for _ in range(20):
                slots = r.integers(-1, 3, 256).astype(np.int32)
                v = r.lognormal(0, 2, 256).astype(np.float32)
                w = r.choice([1.0, 2.0, 8.0], 256).astype(np.float32)
                bank = add(bank, jnp.asarray(slots), jnp.asarray(v),
                           jnp.asarray(w))
            return bank

        a, b = fill(1), fill(2)
        assert _bits_equal(eng.merge_banks(a, b), eng.merge_banks(b, a))


class TestQuantileOracle:
    """Each histogram engine vs numpy exact quantiles, inside its own
    documented contract. The pareto stream is the REQ tail gate: at
    p99.9 the same-budget t-digest's k1 clusters blur across the
    heavy tail while REQ's protected sections hold exact samples."""

    def _fill(self, eng, streams):
        add = _jit(eng, "add_batch_impl")
        bank = eng.init(len(streams))
        B = 8192
        for s, vals in streams.items():
            vals = vals.astype(np.float32)
            for i in range(0, len(vals), B):
                chunk = vals[i:i + B]
                slots = np.full(B, s, np.int32)
                slots[len(chunk):] = -1
                v = np.zeros(B, np.float32)
                v[:len(chunk)] = chunk
                w = np.ones(B, np.float32)
                bank = add(bank, jnp.asarray(slots), jnp.asarray(v),
                           jnp.asarray(w))
        return bank

    def _streams(self, n=50_000):
        rng = np.random.default_rng(11)
        return {
            0: rng.normal(1000, 10, n),                       # compact
            1: (1.0 / (1.0 - rng.uniform(0, 1, n))) ** (1 / 1.5),
        }

    def test_req_tail_contract_and_exact_scalars(self):
        eng = REQEngine()
        streams = self._streams()
        bank = self._fill(eng, streams)
        qs = jnp.asarray([0.5, 0.999], jnp.float32)
        q = np.asarray(_jit(eng, "quantile_impl")(bank, qs))
        for s, vals in streams.items():
            exact = np.percentile(vals.astype(np.float64), [50, 99.9])
            # the documented tail contract: ~1%% relative at p99.9
            assert abs(q[s, 1] - exact[1]) / abs(exact[1]) <= 0.015
        # compact distributions are tight everywhere
        exact50 = np.percentile(streams[0], 50)
        assert abs(q[0, 0] - exact50) / exact50 <= 0.01
        # exact scalars (weight conservation through every compaction)
        n = len(streams[0])
        cnt = np.asarray(bank.count, np.float64) \
            + np.asarray(bank.count_lo, np.float64)
        np.testing.assert_allclose(cnt[:2], [n, n], rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(bank.weight).sum(axis=1)[:2], [n, n], rtol=1e-6)

    def test_req_beats_same_budget_tdigest_at_p999_on_heavy_tail(self):
        """The bench gate's substance, pinned in tier-1: on the pareto
        stream REQ's p99.9 stays inside 1%% where the same-budget
        t-digest exceeds it."""
        streams = {0: self._streams()[1]}
        req, td = REQEngine(), TDigestEngine()
        # same item budget class (~4 KiB/slot both)
        assert req.state_bytes(1) <= 1.1 * td.state_bytes(1)
        qs = jnp.asarray([0.999], jnp.float32)
        exact = np.percentile(streams[0].astype(np.float64), 99.9)
        rbank = self._fill(req, streams)
        rq = float(np.asarray(_jit(req, "quantile_impl")(rbank, qs))[0, 0])
        tbank = req_ = self._fill(td, streams)
        tbank = _jit(td, "compress_impl")(tbank)
        tq = float(np.asarray(_jit(td, "quantile_impl")(tbank, qs))[0, 0])
        req_err = abs(rq - exact) / exact
        td_err = abs(tq - exact) / exact
        assert req_err <= 0.01, (rq, exact)
        assert td_err > 0.01, (tq, exact)

    def test_tdigest_contract_unchanged(self):
        """The default engine through the adapter is the ops module:
        same bank type, same quantile program."""
        eng = TDigestEngine()
        from veneur_tpu.ops import tdigest as td_ops
        bank = eng.init(4)
        assert isinstance(bank, td_ops.TDigestBank)
        streams = {0: self._streams()[0]}
        bank = self._fill(eng, {0: streams[0]})
        bank = _jit(eng, "compress_impl")(bank)
        qs = jnp.asarray([0.5], jnp.float32)
        q = float(np.asarray(_jit(eng, "quantile_impl")(bank, qs))[0, 0])
        exact = np.percentile(streams[0], 50)
        assert abs(q - exact) / exact <= 0.01


class TestWireAndStamps:
    def test_set_register_codec_roundtrip_both_engines(self):
        rng = np.random.default_rng(3)
        for eng_id, m in (("hll", 1 << 10), ("ull", 1 << 10)):
            regs = rng.integers(0, 200, m).astype(np.uint8)
            data = sketches.encode_set_registers(eng_id, regs)
            back_id, back = sketches.decode_set_registers(data)
            assert back_id == eng_id
            np.testing.assert_array_equal(regs, back)

    def test_hll_wire_row_byte_compatible(self):
        """Code byte 1 + precision — the pre-registry HLL row exactly
        (old payloads decode, old receivers decode ours)."""
        from veneur_tpu.cluster import wire
        regs = np.arange(16, dtype=np.uint8)
        data = wire.encode_hll(regs)
        assert data[0] == 1 and data[1] == 4
        np.testing.assert_array_equal(wire.decode_hll(data), regs)

    def test_unknown_engine_code_rejected(self):
        with pytest.raises(ValueError):
            sketches.decode_set_registers(bytes([9, 4]) + bytes(16))

    def test_stamp_parse_and_compat(self):
        default = sketches.DEFAULT_STAMP
        assert sketches.parse_stamp(default) == {
            "h": ("tdigest", 1, "lossless"), "s": ("hll", 1, "lossless")}
        # absent stamp == legacy default pair
        assert sketches.stamp_compatible(default, None)
        assert sketches.stamp_compatible(default, default)
        other = "h=req/1,s=ull/1"
        assert sketches.stamp_compatible(other, other)
        assert not sketches.stamp_compatible(default, other)
        assert not sketches.stamp_compatible(other, None)
        # malformed stamps are the mismatch case, never the legacy case
        assert not sketches.stamp_compatible(default, "junk")

    def test_stamp_centroid_codec_marker(self):
        """The q16 codec is part of the wire format: folded into the
        histogram component's version ("1q"), so a quantized fleet and
        a lossless fleet refuse each other loudly — and legacy (no
        stamp) peers refuse a q16 fleet too."""
        default = sketches.DEFAULT_STAMP
        q = sketches.stamp_with_codec(default, "q16")
        assert q == "h=tdigest/1q,s=hll/1"
        assert sketches.stamp_with_codec(default, "lossless") == default
        assert sketches.parse_stamp(q) == {
            "h": ("tdigest", 1, "q16"), "s": ("hll", 1, "lossless")}
        assert sketches.stamp_compatible(q, q)
        assert not sketches.stamp_compatible(q, default)
        assert not sketches.stamp_compatible(default, q)
        assert not sketches.stamp_compatible(q, None)

    def test_engine_stamp_of_config(self):
        e = AggregationEngine(EngineConfig(
            histogram_slots=64, counter_slots=32, gauge_slots=32,
            set_slots=16, histogram_backend="req", set_backend="ull"))
        assert e.engine_stamp == "h=req/1,s=ull/1"
        desc = e.engines_describe()
        assert desc["histogram"]["id"] == "req"
        assert desc["set"]["id"] == "ull"

    @pytest.mark.parametrize("mesh", [False, True],
                             ids=["single", "mesh"])
    def test_describe_kernels_block_has_two_fields(self, mesh):
        """/debug/flush's `sketch_engines.kernels`: what implements the
        set estimate here and the fallback count, nothing about arms;
        off a TPU the estimate is the jnp reduction on either engine."""
        from veneur_tpu import kernels
        kw = dict(histogram_slots=64, counter_slots=32, gauge_slots=32,
                  set_slots=16, batch_size=64)
        if mesh:
            from veneur_tpu.parallel.engine import MeshAggregationEngine
            e = MeshAggregationEngine(EngineConfig(**kw), n_devices=4)
        else:
            e = AggregationEngine(EngineConfig(**kw))
        assert e.engines_describe()["kernels"] == {
            "estimate": "jnp", "fallback_total": kernels.fallback_total()}

    def test_prefix_sketch_header_roundtrip(self):
        from veneur_tpu.cluster import wire
        items = [("api", bytes(range(16))), ("web.x", b"\x00" * 8)]
        enc = wire.encode_prefix_sketches_header(items)
        assert wire.decode_prefix_sketches_header(enc) == items
        assert wire.decode_prefix_sketches_header("!!!junk") == []


class TestEngineFingerprint:
    def test_restore_refuses_different_backend(self):
        """A durability checkpoint taken under one engine pair refuses
        to restore into another — loudly, before any rows land."""
        kw = dict(histogram_slots=64, counter_slots=32, gauge_slots=32,
                  set_slots=16, batch_size=64)
        a = AggregationEngine(EngineConfig(**kw))
        a.enable_dirty_tracking()
        a.process(parse_metric(b"t:1.5|ms"))
        snap = a.checkpoint_state()
        b = AggregationEngine(EngineConfig(
            **kw, histogram_backend="req", set_backend="ull"))
        b.enable_dirty_tracking()
        with pytest.raises(ValueError, match="fingerprint"):
            b.restore_checkpoint(
                snap["fingerprint"], snap["gauge_seq"],
                snap["last_import_op"], snap["interner"],
                snap["banks"], snap["staged"])

    def test_fingerprint_default_shape_unchanged(self):
        """Default engines keep the original 8-tuple (legacy journals
        restore into default servers unchanged)."""
        from veneur_tpu.durability import records as drec
        cfg = EngineConfig(histogram_slots=64, counter_slots=32,
                           gauge_slots=32, set_slots=16)
        assert len(drec.engine_fingerprint(cfg, 256)) == 8
        cfg2 = EngineConfig(histogram_slots=64, counter_slots=32,
                            gauge_slots=32, set_slots=16,
                            set_backend="ull")
        fpr = drec.engine_fingerprint(cfg2, 256)
        assert len(fpr) == 10 and fpr[6] == 1 << 13
        # meta record roundtrips the extended tuple
        payload = drec.encode_engine_meta(0, 1, 5, 7, fpr)
        assert drec.decode_engine_meta(payload) == (0, 1, 5, 7, fpr)


_BASE = """
interval: "3600s"
hostname: h
statsd_listen_addresses: ["udp://127.0.0.1:0"]
flush_phase_timers: false
aggregates: ["min", "max", "count", "sum"]
percentiles: [0.5, 0.99, 0.999]
tpu_histogram_slots: 256
tpu_counter_slots: 128
tpu_gauge_slots: 64
tpu_set_slots: 32
tpu_batch_size: 8192
tpu_buffer_depth: 256
"""

_ENGINES = "histogram_backend: \"req\"\nset_backend: \"ull\"\n"


def _global(extra=""):
    cfg = read_config(text=_BASE + "http_address: \"127.0.0.1:0\"\n"
                      + "is_global: true\n" + extra)
    cap = CaptureMetricSink()
    srv = Server(cfg, sinks=[cap], plugins=[], span_sinks=[])
    srv.start()
    return srv, cap


def _local(glob, extra="", sender_id="snd-sketch"):
    from veneur_tpu import resilience
    from veneur_tpu.cluster.forward import HttpJsonForwarder
    loc = Server(
        read_config(text=_BASE + "forward_address: \"placeholder:1\"\n"
                    + extra),
        sinks=[CaptureMetricSink()], plugins=[], span_sinks=[])
    # wrapped like production: envelopes (sender identity + seqs) ride
    # every chunk, so the receiver's fleet page keys rows by sender
    loc.forwarder = resilience.ResilientForwarder(
        HttpJsonForwarder(f"http://127.0.0.1:{glob.http_api.port}",
                          engine_stamp=loc.engine_stamp),
        destination="sketch-probe", sender_id=sender_id)
    return loc


class TestTwoTierEngineParity:
    """The engine-parity gate: a two-tier fleet (local forwards over
    the real HTTP contract into a real global Server) runs green under
    `ull`+`req`, with flushed estimates inside each engine's documented
    error bound, and exact counter/count/sum conservation."""

    def test_two_tier_ull_req_within_bounds(self):
        glob, gcap = _global(_ENGINES)
        try:
            loc = _local(glob, _ENGINES)
            rng = np.random.default_rng(5)
            # n sizes the p99.9 rank (n/1000 from the top): order-
            # statistic spacing at that rank is ~1/(1.5*rank) relative
            # for this pareto, so the bound below is granularity-aware
            n = 50_000
            vals = (1.0 / (1.0 - rng.uniform(0, 1, n))) ** (1 / 1.5)
            n_members = 5_000
            for i in range(n):
                loc.engines[0].process(parse_metric(
                    b"lat.req:%.6f|ms|#veneurglobalonly"
                    % float(vals[i])))
            for i in range(n_members):
                loc.engines[0].process(parse_metric(
                    b"users:u%d|s" % i))
            loc.engines[0].process(parse_metric(
                b"hits:41|c|#veneurglobalonly"))
            loc.flush_once(timestamp=50)     # real POST /import
            assert glob.drain(20.0)
            glob.flush_once(timestamp=100)
            assert gcap.wait_for_flush()
            out = {m.name: m.value for m in gcap.all_metrics}
            # exact legs
            assert out["hits"] == 41.0
            assert out["lat.req.count"] == float(n)
            np.testing.assert_allclose(
                out["lat.req.sum"], float(vals.sum()), rtol=1e-5)
            # REQ tail bound through a forward+re-merge hop (the
            # documented ~1% contract + the rank-granularity fuzz at
            # rank 50 from the top)
            exact999 = np.percentile(vals.astype(np.float64), 99.9)
            assert abs(out["lat.req.99.9percentile"] - exact999) \
                / exact999 <= 0.03
            # ULL cardinality through the register wire row
            assert abs(out["users"] - n_members) / n_members <= 0.05
            # both tiers agree on the stamp; the global recorded it
            fleet = glob._debug_fleet_state()
            assert fleet["sketch_engines"]["local"] == "h=req/1,s=ull/1"
            rows = fleet["senders"]
            assert any(r.get("sketch_engines") == "h=req/1,s=ull/1"
                       for r in rows.values())
            assert fleet["sketch_engines"]["mismatch_rejects"] == 0
        finally:
            glob.stop()

    def test_mismatched_fleet_refused_loudly(self):
        """A default-engine sender against a `ull`+`req` global: every
        chunk is rejected with the reject counted and the sender's
        stamp visible at /debug/fleet; nothing merges."""
        from veneur_tpu.resilience import DEFAULT_REGISTRY
        base = DEFAULT_REGISTRY.total("import", "import.engine_mismatch")
        glob, gcap = _global(_ENGINES)
        try:
            loc = _local(glob)      # default engines — the mixed fleet
            loc.engines[0].process(parse_metric(
                b"mm.c:7|c|#veneurglobalonly"))
            loc.flush_once(timestamp=50)
            # the forward failed loudly on the sender: the interval
            # parked for replay instead of being dropped
            assert loc.forwarder is not None
            glob.flush_once(timestamp=100)
            gvals = {m.name for m in gcap.all_metrics}
            assert "mm.c" not in gvals          # nothing merged
            assert DEFAULT_REGISTRY.total(
                "import", "import.engine_mismatch") > base
            fleet = glob._debug_fleet_state()
            assert fleet["sketch_engines"]["mismatch_rejects"] > 0
            rows = fleet["senders"]
            assert any(r.get("sketch_engines") == sketches.DEFAULT_STAMP
                       and r.get("engine_mismatch_rejects", 0) > 0
                       for r in rows.values())
            # ... and over a REAL GET /debug/fleet
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{glob.http_api.port}/debug/fleet",
                timeout=10).read())
            assert body["sketch_engines"]["mismatch_rejects"] >= 1
        finally:
            glob.stop()

    def test_prefix_sketches_merge_at_global(self):
        """The overload-defense satellite: a defense-on local forwards
        its per-prefix Huffman-Bucket sketches; the global's
        /debug/fleet serves ONE fleet-wide estimate per prefix."""
        glob, _gcap = _global()
        try:
            loc = _local(glob, "overload_defense_enabled: true\n")
            for i in range(300):
                m = parse_metric(b"api.k%d:1|c|#veneurglobalonly" % i)
                loc.engines[0].process(m)
            loc.flush_once(timestamp=50)
            assert glob.drain(20.0)
            card = glob._debug_fleet_state()["fleet_cardinality"]
            assert "api" in card
            assert 0.5 * 300 <= card["api"] <= 2.0 * 300
        finally:
            glob.stop()

    def test_debug_flush_reports_engines(self):
        glob, _ = _global(_ENGINES)
        try:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{glob.http_api.port}/debug/flush",
                timeout=10).read())
            se = body["sketch_engines"]
            assert se["stamp"] == "h=req/1,s=ull/1"
            assert se["histogram"]["id"] == "req"
            assert se["set"]["id"] == "ull"
            assert se["set"]["params"]["precision"] == 13
            assert sorted(se["kernels"]) == ["estimate", "fallback_total"]
        finally:
            glob.stop()


class TestProxyPassthrough:
    def test_proxy_passes_stamp_and_prefix_sketches(self):
        """A proxy between tiers must not strip the engine stamp (a
        non-default fleet would read as legacy and be refused at the
        globals) nor the advisory cardinality rows."""
        from veneur_tpu.cluster.protos import forward_pb2, metric_pb2
        from veneur_tpu.cluster.proxy import ProxyServer

        class Cap:
            instances: dict = {}

            def __init__(self, dest):
                self.dest = dest
                self.calls = []
                Cap.instances[dest] = self

            def send_metrics(self, metrics, sketch_engines=None,
                             prefix_sketches=None):
                self.calls.append((list(metrics), sketch_engines,
                                   list(prefix_sketches or [])))

        class Disc:
            def get_destinations_for_service(self, service):
                return ["d1:1", "d2:1"]

        proxy = ProxyServer(Disc(), forwarder_factory=Cap)
        ml = forward_pb2.MetricList()
        for i in range(20):
            m = ml.metrics.add()
            m.name = f"m{i}"
            m.type = metric_pb2.Counter
            m.counter.value = i
        ml.sketch_engines = "h=req/1,s=ull/1"
        ml.prefix_sketches.add(prefix="api", registers=b"\x01\x02")
        assert not proxy.handle_metric_list(ml)
        assert Cap.instances
        for cap in Cap.instances.values():
            for _ms, stamp, rows in cap.calls:
                assert stamp == "h=req/1,s=ull/1"
                assert rows == [("api", b"\x01\x02")]


def test_fleet_sketch_map_bounded():
    """A network-facing receiver's fleet cardinality map must stay
    bounded however many prefixes senders churn through (overflow rows
    dropped + counted, never grown)."""
    import threading
    import types

    stub = types.SimpleNamespace(
        _fleet_sketch_lock=threading.Lock(), _fleet_sketches={},
        MAX_FLEET_SKETCH_PREFIXES=Server.MAX_FLEET_SKETCH_PREFIXES)
    rows = [(f"p{i}", b"\x01" * 16)
            for i in range(Server.MAX_FLEET_SKETCH_PREFIXES + 50)]
    Server.merge_prefix_sketches(stub, rows)
    assert len(stub._fleet_sketches) == Server.MAX_FLEET_SKETCH_PREFIXES
    # existing prefixes still merge by max past the cap
    Server.merge_prefix_sketches(stub, [("p0", b"\x05" * 16)])
    assert stub._fleet_sketches["p0"] == bytearray(b"\x05" * 16)

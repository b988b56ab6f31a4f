"""Pallas kernel tests (interpret mode on CPU).

Kernel contracts under test:

  * hll_stats must agree exactly with the plain-jnp row statistics for
    any register bank, so the Pallas and jnp estimate paths are
    interchangeable on every platform.
  * the fused t-digest compress (kernels/compress.py) must reproduce
    the XLA compress path BIT-FOR-BIT under interpret=True — ±0.0
    canonicalization, duplicate keys, NaN payload bits, the cluster-id
    overflow clip, and the SR02 cummax ordering invariant included —
    in BOTH in-kernel sort arms (the lax.sort form the interpret arm
    serves, and the compare-exchange network the TPU arm compiles).
  * the ULL scatter-join insert (kernels/ull_insert.py) must land
    register-byte-identical state to the XLA sort+scan+dedup path.
  * one flush program embeds exactly ONE pallas_call per bucket — the
    structural no-HBM-round-trip assertion (no wall-clock win has been
    measured on a chip).
  * the arm model: `auto` on a TPU follows each kernel's written
    decision, `on` builds every kernel and a refusal RAISES at engine
    construction instead of demoting.

The TPU-compiled arm env-skips here (envprobes.needs_pallas_tpu);
interpret mode on CPU is the tier-1 correctness bar, and
chip_smoke.py's kernel leg is where the kernels meet Mosaic.
"""

import functools

import numpy as np
import pytest

from envprobes import needs_pallas_interpret, needs_pallas_tpu

from veneur_tpu.ops import hll
from veneur_tpu.kernels.hll_stats import hll_stats


def jnp_stats(regs):
    import jax.numpy as jnp
    ez = np.asarray(jnp.sum(regs == 0, axis=1), np.float32)
    zsum = np.asarray(jnp.sum(jnp.exp2(-regs.astype(jnp.float32)), axis=1))
    return ez, zsum


@pytest.mark.parametrize("k,m", [(32, 512), (5, 1024), (100, 16384)])
def test_stats_match_jnp(k, m):
    rng = np.random.default_rng(0)
    regs = rng.integers(0, 50, (k, m)).astype(np.uint8)
    regs[0] = 0                      # empty row
    regs[1, : m // 2] = 0            # half-zero row
    ez_p, zsum_p = hll_stats(regs, interpret=True)
    ez_j, zsum_j = jnp_stats(regs)
    np.testing.assert_array_equal(np.asarray(ez_p), ez_j)
    np.testing.assert_allclose(np.asarray(zsum_p), zsum_j, rtol=1e-6)


def test_padding_rows_dont_leak():
    # K=5 pads to 32 internally; padded rows must not appear in output
    regs = np.full((5, 512), 3, np.uint8)
    ez, zsum = hll_stats(regs, interpret=True)
    assert ez.shape == (5,) and zsum.shape == (5,)
    np.testing.assert_array_equal(np.asarray(ez), np.zeros(5))


def test_estimate_via_pallas_stats_matches_jnp_estimate():
    """Full estimator equality: wiring the pallas stats into the beta
    polynomial must reproduce the jnp estimate bit-for-bit-ish."""
    rng = np.random.default_rng(1)
    bank = hll.init(8, precision=10)
    import jax.numpy as jnp
    regs = rng.integers(0, 30, (8, 1024)).astype(np.uint8)
    regs[3] = 0
    bank = hll.HLLBank(registers=jnp.asarray(regs))
    ez, zsum = hll_stats(regs, interpret=True)
    est_pallas = hll._estimate_from_stats(bank, jnp.asarray(ez),
                                          jnp.asarray(zsum))
    est_jnp = hll._estimate_jnp(bank)
    np.testing.assert_allclose(np.asarray(est_pallas),
                               np.asarray(est_jnp), rtol=1e-5)
    assert float(est_pallas[3]) == 0.0   # empty slot stays 0


def test_pallas_stats_inside_shard_map():
    """The mesh flush places the Pallas kernel INSIDE shard_map (device-
    local block compute after the dp register union). Validate the
    pattern on the CPU mesh via interpret mode: per-shard hll_stats
    under shard_map must match the whole-array jnp reduction."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.default_rng(4)
    regs = rng.integers(0, 25, (16, 512)).astype(np.uint8)
    regs[5] = 0
    devs = np.array(jax.devices()[:8]).reshape(8)
    mesh = Mesh(devs, ("shard",))

    def local_stats(r):
        ez, zsum = hll_stats(r, interpret=True)
        return ez, zsum

    # check_vma=False like the product merge_fn: pallas_call outputs
    # can't declare their varying mesh axes
    f = jax.jit(jax.shard_map(
        local_stats, mesh=mesh, in_specs=(P("shard", None),),
        out_specs=(P("shard"), P("shard")), check_vma=False))
    ez, zsum = f(regs)
    ez_ref = (regs == 0).sum(axis=1).astype(np.float32)
    zsum_ref = np.exp2(-regs.astype(np.float64)).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(ez), ez_ref)
    np.testing.assert_allclose(np.asarray(zsum), zsum_ref, rtol=1e-5)


# ---------------------------------------------------------------------
# fused t-digest compress (ISSUE 15): bit-identity vs the XLA path
# ---------------------------------------------------------------------

def _bits(x):
    return np.asarray(x).view(np.uint32)


def _mk_bank(seed, K=37, compression=100.0, B=256, adversarial=False):
    """A bank with a LEGAL cluster-ordered prefix (built by the XLA
    compress itself) and a refilled sample buffer."""
    import jax.numpy as jnp

    from veneur_tpu.ops import tdigest

    rng = np.random.default_rng(seed)
    bank = tdigest.init(K, compression, B)
    slots = rng.integers(0, K, 4096).astype(np.int32)
    vals = rng.lognormal(3, 1, 4096).astype(np.float32)
    bank = tdigest._add_batch_impl(
        bank, jnp.asarray(slots), jnp.asarray(vals),
        jnp.ones(4096, jnp.float32), compression)
    bank = tdigest._compress_impl(bank, compression)
    bv = rng.normal(20, 30, (K, B)).astype(np.float32)
    bw = (np.abs(rng.normal(1, 0.5, (K, B))) + 0.01).astype(np.float32)
    if adversarial:
        bv[:, 0] = -0.0                     # signed-zero key folding
        bv[:, 1] = 0.0
        bv[:, 2] = bv[:, 3]                 # duplicate values
        bv[:, 5] = np.asarray(bank.mean)[:, 0]   # dup vs prefix means
        nanbits = np.uint32(0x7FC01234)     # NaN with a payload
        bv[0, 4] = np.frombuffer(nanbits.tobytes(), np.float32)[0]
        bw[2, 100:] = 0.0                   # zero-weight buffer tail
        bw[3, :] = 0.0                      # empty buffer, live prefix
    empty_rows = np.asarray(bank.weight).sum(axis=1) == 0
    bank = bank._replace(buf_value=jnp.asarray(bv),
                         buf_weight=jnp.asarray(bw),
                         buf_n=jnp.full((K,), B, jnp.int32))
    if adversarial and empty_rows.any():
        # at least one fully-empty row (fresh-init fixed point)
        bwz = np.array(bv * 0.0)
        bank = bank._replace(buf_weight=jnp.asarray(
            np.where(empty_rows[:, None], bwz, bw)))
    return bank


@needs_pallas_interpret
@pytest.mark.parametrize("network", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fused_compress_bit_identity_fuzz(seed, network):
    import jax

    from veneur_tpu.kernels import compress as kc
    from veneur_tpu.ops import tdigest

    comp = 100.0
    bank = _mk_bank(seed, adversarial=(seed == 0))
    ref = jax.jit(functools.partial(
        tdigest._compress_impl, compression=comp))(bank)
    got = jax.jit(functools.partial(
        kc.fused_compress_bank, compression=comp, interpret=True,
        network=network))(bank)
    for name in ("mean", "weight"):
        np.testing.assert_array_equal(
            _bits(getattr(ref, name)), _bits(getattr(got, name)),
            err_msg=f"{name} diverged (network={network})")
    assert int(np.asarray(got.buf_n).sum()) == 0
    assert float(np.abs(np.asarray(got.buf_value)).sum()) == 0.0


@needs_pallas_interpret
@pytest.mark.parametrize("network", [False, True])
def test_fused_compress_cluster_overflow_clip(network):
    """More natural clusters than centroid lanes: the greedy ids run
    past C and both paths must clip to C-1 identically (the
    pathological-overflow safety branch of _cluster_core)."""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.kernels import compress as kc
    from veneur_tpu.ops import tdigest

    rng = np.random.default_rng(9)
    K, C, B, comp = 5, 64, 512, 100.0   # C << 2*compression
    mean = jnp.zeros((K, C), jnp.float32)
    weight = jnp.zeros((K, C), jnp.float32)
    bv = jnp.asarray(np.sort(rng.normal(0, 100, (K, B)))
                     .astype(np.float32))
    bw = jnp.ones((K, B), jnp.float32)

    def ref_fn(m, w, v, ww):
        return tdigest._cluster_core(
            jnp.concatenate([m, v], axis=1),
            jnp.concatenate([w, ww], axis=1), comp, C,
            sorted_prefix=C)

    rm, rw = jax.jit(ref_fn)(mean, weight, bv, bw)
    gm, gw = jax.jit(functools.partial(
        kc.fused_compress, compression=comp, interpret=True,
        network=network))(mean, weight, bv, bw)
    np.testing.assert_array_equal(_bits(rm), _bits(gm))
    np.testing.assert_array_equal(_bits(rw), _bits(gw))
    # the overflow actually happened: the last lane absorbed the tail
    assert float(np.asarray(rw)[:, -1].min()) > 1.0


def test_bitonic_network_equals_stable_sort():
    """The Mosaic-targeted sort network, validated as plain jnp against
    the XLA packed-radix stable sort: distinct (key, tag) pairs have
    ONE ascending order, so the network must land exactly
    _stable_sort_perm's (sorted_key, perm) — ties in the key broken by
    original lane, bit-for-bit."""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.kernels import compress as kc
    from veneur_tpu.ops import tdigest

    rng = np.random.default_rng(4)
    for B in (8, 64, 256):
        vals = rng.normal(0, 50, (19, B)).astype(np.float32)
        vals[:, : B // 4] = np.round(vals[:, : B // 4])  # tie-heavy
        vals[0, 0] = -0.0
        vals[0, 1] = 0.0
        key = tdigest._canonical_sort_key(jnp.asarray(vals))
        skey, sperm = jax.jit(tdigest._stable_sort_perm)(key)
        tag = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
        nk, nt, _nv, _nw = jax.jit(kc._bitonic_sort)(
            key, tag, jnp.asarray(vals), jnp.asarray(vals))
        np.testing.assert_array_equal(np.asarray(skey), np.asarray(nk))
        np.testing.assert_array_equal(np.asarray(sperm),
                                      np.asarray(nt))


@needs_pallas_interpret
def test_one_pallas_dispatch_per_bucket():
    """The structural HBM assertion: the whole fused flush program —
    compress + quantiles + aggregates + estimates over the gathered
    [D, ·] work set — contains exactly ONE pallas_call. Intermediates
    of the sort/merge/cluster stages therefore never round-trip
    through HBM between kernel dispatches."""
    import jax

    from veneur_tpu.models import pipeline
    from veneur_tpu.ops import scalar
    from veneur_tpu.sketches.hll_engine import HLLEngine
    from veneur_tpu.sketches.tdigest_engine import TDigestEngine

    heng = TDigestEngine(compression=100.0, buffer_depth=256)
    seng = HLLEngine(precision=10)
    body = pipeline._flush_program_body(
        heng, seng, False, ("min", "max", "count"), False,
        kernel_arm="interpret")
    qs = np.asarray([0.5, 0.99], np.float32)
    jaxpr = jax.make_jaxpr(body)(
        heng.init(64), scalar.init_counters(8), scalar.init_gauges(8),
        seng.init(8), qs)

    def count_pallas(jx):
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    n += count_pallas(v.jaxpr)
        return n

    assert count_pallas(jaxpr.jaxpr) == 1


@needs_pallas_interpret
def test_fused_compress_fallback_counted():
    """A shape the kernel cannot serve degrades to the XLA program —
    loudly, counted on veneur.kernels.fallback_total — and still
    returns the identical result (PK01's runtime contract)."""
    import jax.numpy as jnp

    from veneur_tpu import kernels
    from veneur_tpu.kernels import compress as kc
    from veneur_tpu.ops import tdigest

    before = kernels.fallback_total()
    mean = jnp.zeros((4, 1), jnp.float32)     # C=1: degenerate
    weight = jnp.zeros((4, 1), jnp.float32)
    bv = jnp.asarray(np.random.default_rng(2)
                     .normal(0, 1, (4, 8)).astype(np.float32))
    bw = jnp.ones((4, 8), jnp.float32)
    gm, gw = kc.fused_compress(mean, weight, bv, bw,
                               compression=100.0, interpret=True)
    rm, rw = tdigest._cluster_core(
        jnp.concatenate([mean, bv], axis=1),
        jnp.concatenate([weight, bw], axis=1), 100.0, 1,
        sorted_prefix=1)
    np.testing.assert_array_equal(_bits(rm), _bits(gm))
    assert kernels.fallback_total() == before + 1


# ---------------------------------------------------------------------
# ULL scatter-join insert (ISSUE 15)
# ---------------------------------------------------------------------

@needs_pallas_interpret
@pytest.mark.parametrize("seed", [0, 1])
def test_ull_fused_insert_register_identity(seed):
    import jax
    import jax.numpy as jnp

    from veneur_tpu.kernels import ull_insert as ki
    from veneur_tpu.sketches.ull import ULLEngine, _insert_impl

    rng = np.random.default_rng(seed)
    eng = ULLEngine(precision=9)
    K, m, n = 11, 1 << 9, 2048
    # pre-populated bank so joins against existing state are exercised
    bank = eng.init(K)
    regs0 = rng.integers(0, 200, (K, m)).astype(np.uint8)
    bank = type(bank)(registers=jnp.asarray(regs0))
    slots = rng.integers(-1, K, n).astype(np.int32)   # incl. padding
    idx = rng.integers(0, m, n).astype(np.int32)
    # force duplicate targets with conflicting packed values
    idx[: n // 4] = idx[n // 4: n // 2]
    slots[: n // 4] = slots[n // 4: n // 2]
    vals = ((rng.integers(1, 50, n) << 2)
            | rng.integers(0, 4, n)).astype(np.uint8)
    ref = jax.jit(_insert_impl)(
        bank, jnp.asarray(slots), jnp.asarray(idx), jnp.asarray(vals))
    got = jax.jit(functools.partial(ki.fused_insert, interpret=True))(
        type(bank)(registers=jnp.asarray(regs0)), jnp.asarray(slots),
        jnp.asarray(idx), jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(ref.registers),
                                  np.asarray(got.registers))


@needs_pallas_interpret
def test_ull_fused_insert_idempotent_rejoin():
    """Re-landing the identical batch must be a lattice no-op — the
    join's idempotency, through the kernel."""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.kernels import ull_insert as ki
    from veneur_tpu.sketches.ull import ULLEngine

    rng = np.random.default_rng(7)
    eng = ULLEngine(precision=9)
    n = 512
    ins = jax.jit(functools.partial(ki.fused_insert, interpret=True))
    slots = np.zeros(n, np.int32)
    idx = rng.integers(0, 1 << 9, n).astype(np.int32)
    vals = (rng.integers(1, 40, n) << 2).astype(np.uint8)
    b1 = ins(eng.init(4), jnp.asarray(slots), jnp.asarray(idx),
             jnp.asarray(vals))
    r1 = np.asarray(b1.registers).copy()
    b2 = ins(b1, jnp.asarray(slots), jnp.asarray(idx),
             jnp.asarray(vals))
    np.testing.assert_array_equal(r1, np.asarray(b2.registers))


# ---------------------------------------------------------------------
# end-to-end: the knob through the whole engine (oracle-style parity)
# ---------------------------------------------------------------------

def _engine_flush_fingerprint(fused, hb, sb, seed=5):
    import veneur_tpu.utils.hashing as hashing
    from veneur_tpu.ingest.parser import MetricKey
    from veneur_tpu.models.pipeline import (AggregationEngine,
                                            EngineConfig)

    eng = AggregationEngine(EngineConfig(
        histogram_slots=256, counter_slots=64, gauge_slots=64,
        set_slots=64, batch_size=512, percentiles=(0.5, 0.99),
        aggregates=("min", "max", "count"), histogram_backend=hb,
        set_backend=sb, fused_kernels=fused))
    rng = np.random.default_rng(seed)
    for k in range(32):
        s = eng.histo_keys.lookup(MetricKey(f"a.h{k}", "timer", ""), 0)
        eng.ingest_histo_batch(
            np.full(64, s, np.int32),
            rng.gamma(2, 20, 64).astype(np.float32),
            np.ones(64, np.float32), count=64)
    hashes = np.array([hashing.set_member_hash(f"m{i}")
                       for i in range(300)], np.uint64)
    idx, vals = eng._seng.host_hash_to_updates(hashes)
    for k in range(8):
        s = eng.set_keys.lookup(MetricKey(f"a.s{k}", "set", ""), 0)
        eng.ingest_set_batch(np.full(300, s, np.int32),
                             idx.astype(np.int32), vals, count=300)
    res = eng.flush(timestamp=5)
    fp = sorted((m.name, repr(m.value)) for m in res.metrics)
    return fp, eng


@needs_pallas_interpret
@pytest.mark.parametrize("hb,sb", [("tdigest", "hll"), ("req", "ull")])
def test_engine_flush_knob_parity(hb, sb):
    """tpu_fused_kernels=on routes the serving executables through the
    interpret-mode kernels on CPU; every flushed value must equal the
    knob-off (XLA) engine bit-for-bit — which is why the existing
    oracle/chaos suites pass unmodified with the knob on."""
    fp_off, e_off = _engine_flush_fingerprint("off", hb, sb)
    fp_on, e_on = _engine_flush_fingerprint("on", hb, sb)
    assert fp_off == fp_on
    assert e_off._kernel_arms == {"histogram": "xla", "set": "xla",
                                  "estimate": "xla"}
    want_h = "interpret" if hb == "tdigest" else "xla"
    want_s = "interpret" if sb == "ull" else "xla"
    # the CPU flush program estimates in plain jnp under every knob
    assert e_on._kernel_arms == {"histogram": want_h, "set": want_s,
                                 "estimate": "xla"}
    desc = e_on.engines_describe()["kernels"]
    assert desc["requested"] == "on"
    assert desc["histogram_arm"] == want_h
    assert desc["set_arm"] == want_s
    assert "fallback_total" in desc


def test_resolve_arm_serving_defaults():
    """auto/off never serve interpret kernels on CPU (interpret is the
    testing arm); bad knob values refuse loudly."""
    from veneur_tpu import kernels

    for kernel in kernels.KERNELS:
        assert kernels.resolve_arm("off", "cpu", kernel) == "xla"
        assert kernels.resolve_arm("off", "tpu", kernel) == "xla"
        assert kernels.resolve_arm("auto", "cpu", kernel) == "xla"
    with pytest.raises(ValueError):
        kernels.resolve_arm("definitely-not-a-mode", "cpu", "compress")
    with pytest.raises(ValueError):
        kernels.tpu_auto_arm("definitely-not-a-kernel")


def test_resolve_arm_on_a_tpu_follows_the_written_decisions():
    """On a TPU nothing is probed at start-up: `auto` serves each
    kernel under the arm recorded next to it (TPU_AUTO_ARM — what
    Mosaic said on the chip), and `on` names every kernel."""
    from veneur_tpu import kernels
    from veneur_tpu.kernels import compress, hll_stats, ull_insert

    decided = {"compress": compress.TPU_AUTO_ARM,
               "ull_insert": ull_insert.TPU_AUTO_ARM,
               "hll_stats": hll_stats.TPU_AUTO_ARM}
    before = kernels.fallback_total()
    for kernel, arm in decided.items():
        assert arm in ("fused", "xla")
        assert kernels.resolve_arm("auto", "tpu", kernel) == arm
        assert kernels.resolve_arm("on", "tpu", kernel) == "fused"
    # a decision is not a fallback: nothing was counted
    assert kernels.fallback_total() == before

    from veneur_tpu.sketches.hll_engine import HLLEngine
    from veneur_tpu.sketches.req import REQEngine
    from veneur_tpu.sketches.tdigest_engine import TDigestEngine
    from veneur_tpu.sketches.ull import ULLEngine
    assert kernels.engine_arms(
        "auto", "tpu", TDigestEngine(), HLLEngine()) == {
        "histogram": decided["compress"], "set": "xla",
        "estimate": decided["hll_stats"]}
    # engines without the kernel stay on xla whatever the knob
    assert kernels.engine_arms(
        "on", "tpu", REQEngine(), ULLEngine()) == {
        "histogram": "xla", "set": "fused", "estimate": "xla"}
    # hll_stats walks 512-lane chunks: a narrower register file is jnp
    assert kernels.engine_arms(
        "on", "tpu", TDigestEngine(), HLLEngine(precision=8))[
        "estimate"] == "xla"


def test_knob_on_raises_when_the_compiler_refuses():
    """`tpu_fused_kernels: on` names the kernels: a refusal at the
    engine's serving shape raises at construction — never a demotion
    to the XLA program. (The CPU stands in for the refusing compiler:
    it cannot build a non-interpret pallas_call at all.)"""
    from veneur_tpu import kernels
    from veneur_tpu.models import pipeline
    from veneur_tpu.sketches.tdigest_engine import TDigestEngine
    from veneur_tpu.sketches.ull import ULLEngine

    before = kernels.fallback_total()
    for arms in ({"histogram": "fused"}, {"set": "fused"}):
        with pytest.raises(kernels.KernelRefused, match="refused"):
            kernels.require_engine_kernels(
                TDigestEngine(), ULLEngine(precision=9), arms,
                set_slots=64, batch_size=128)
    # xla/interpret arms involve no compiler: nothing to require
    kernels.require_engine_kernels(
        TDigestEngine(), ULLEngine(precision=9),
        {"histogram": "interpret", "set": "xla", "estimate": "xla"},
        set_slots=64, batch_size=128)

    # and the engine asks at construction when it sits on a TPU
    # (platform faked where the engine resolves its arms)
    real_arms = kernels.engine_arms
    kernels.engine_arms = lambda mode, _platform, heng, seng: \
        real_arms(mode, "tpu", heng, seng)
    try:
        with pytest.raises(kernels.KernelRefused):
            pipeline.AggregationEngine(pipeline.EngineConfig(
                histogram_slots=64, counter_slots=8, gauge_slots=8,
                set_slots=64, batch_size=128, fused_kernels="on"))
    finally:
        kernels.engine_arms = real_arms
    assert kernels.fallback_total() == before


@needs_pallas_tpu
def test_fused_compress_compiled_on_tpu():
    """The TPU-compiled arm (env-skipped off hardware, like mesh): the
    Mosaic kernel must compile and agree with the XLA program on the
    accuracy contract (bitwise equality is interpret's bar; hardware
    transcendentals may legally differ in ulps)."""
    import jax

    from veneur_tpu.kernels import compress as kc
    from veneur_tpu.ops import tdigest

    comp = 100.0
    bank = _mk_bank(3, K=64)
    ref = jax.jit(functools.partial(
        tdigest._compress_impl, compression=comp))(bank)
    got = jax.jit(functools.partial(
        kc.fused_compress_bank, compression=comp, interpret=False))(
        bank)
    np.testing.assert_allclose(np.asarray(got.weight),
                               np.asarray(ref.weight), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got.mean),
                               np.asarray(ref.mean), rtol=1e-4)

"""The one Pallas kernel (interpret mode on CPU) and what selects it.

  * hll_stats must agree exactly with the plain-jnp row statistics for
    any register bank, so the Pallas and jnp estimate paths are
    interchangeable on every platform, alone and inside a shard_map.
  * `ops/hll.py:will_use_pallas` is the only place that decides which
    kernel runs: a TPU, and a register width on the kernel's lane
    grid. The set engines' `estimate_device(bank)` takes nothing else.

chip_smoke.py's kernel leg is where the kernel meets Mosaic.
"""

import numpy as np
import pytest

from envprobes import needs_pallas_interpret

from veneur_tpu.ops import hll
from veneur_tpu.kernels.hll_stats import hll_stats


def jnp_stats(regs):
    import jax.numpy as jnp
    ez = np.asarray(jnp.sum(regs == 0, axis=1), np.float32)
    zsum = np.asarray(jnp.sum(jnp.exp2(-regs.astype(jnp.float32)), axis=1))
    return ez, zsum


@needs_pallas_interpret
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


@needs_pallas_interpret
def test_padding_rows_dont_leak():
    # K=5 pads to 32 internally; padded rows must not appear in output
    regs = np.full((5, 512), 3, np.uint8)
    ez, zsum = hll_stats(regs, interpret=True)
    assert ez.shape == (5,) and zsum.shape == (5,)
    np.testing.assert_array_equal(np.asarray(ez), np.zeros(5))


@needs_pallas_interpret
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


@needs_pallas_interpret
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
# what selects the kernel
# ---------------------------------------------------------------------

@pytest.mark.parametrize("on_tpu,m,want", [
    (False, 1 << 14, False),     # the serving width, off a TPU
    (True, 1 << 14, True),
    (True, 1000, False),         # off the 512-lane grid, whatever runs
    (False, 1000, False),
])
def test_will_use_pallas_reads_platform_and_width(monkeypatch, on_tpu, m,
                                                 want):
    from veneur_tpu.utils import platform
    monkeypatch.setattr(platform, "is_tpu", lambda: on_tpu)
    assert hll.will_use_pallas(m) is want


@pytest.mark.parametrize("backend", ["hll", "ull"])
def test_estimate_device_takes_the_bank_alone(backend):
    """The flush program hands a set engine its bank and nothing about
    kernels: off a TPU the HLL engine's output is the jnp estimate bit
    for bit, and no kernel entry point fell back on the way."""
    import inspect

    import jax

    from veneur_tpu import kernels, sketches
    from veneur_tpu.models.pipeline import EngineConfig

    seng = sketches.set_engine(EngineConfig(set_backend=backend))
    assert list(inspect.signature(seng.estimate_device).parameters) \
        == ["bank"]
    before = kernels.fallback_total()
    rng = np.random.default_rng(3)
    bank = seng.init(8)
    regs = rng.integers(0, 40, bank.registers.shape).astype(np.uint8)
    regs[2] = 0
    bank = type(bank)(registers=jax.numpy.asarray(regs))
    host = jax.device_get(jax.jit(seng.estimate_device)(bank))
    if backend == "hll":
        np.testing.assert_array_equal(
            host["s_est"], np.asarray(hll._estimate_jnp(bank)))
    seng.estimate_finalize(host)
    assert host["s_est"].shape == (8,) and host["s_est"][2] == 0.0
    assert kernels.fallback_total() == before

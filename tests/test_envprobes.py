"""Meta-test: the environmental skip probes must match reality.

Each probe in `envprobes.py` gates tier-1 tests behind a claimed
missing capability. These tests assert the claim itself, both ways:
when the probe says "missing", exercising the capability must fail
with exactly the failure class the gated tests died of; when it says
"present", the capability must actually work — so an installation that
gains the capability un-skips the gated tests AND keeps this meta-test
green, while a probe that drifted from reality fails loudly here."""

import jax
import pytest


def test_pallas_interpret_probe_matches_reality():
    from envprobes import (PALLAS_INTERPRET_MISSING,
                           PALLAS_INTERPRET_SKIP_REASON)
    assert PALLAS_INTERPRET_SKIP_REASON.startswith("environmental:")
    if PALLAS_INTERPRET_MISSING:
        # the gated tests would die constructing/running a trivial
        # interpret-mode kernel — the probe must imply that failure
        with pytest.raises(Exception):
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def k(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            pl.pallas_call(
                k, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                interpret=True)(jnp.zeros((8, 128), jnp.float32))
    else:
        # present: the capability the kernel's tests consume
        # must actually produce numbers
        import numpy as np

        from veneur_tpu.kernels.hll_stats import hll_stats
        regs = np.zeros((4, 512), np.uint8)
        ez, zsum = hll_stats(regs, interpret=True)
        assert float(np.asarray(ez)[0]) == 512.0

"""Environment probes for the tier-1 skips that depend on where the
tests run.

One capability gates the Pallas kernel's tests (tests/test_pallas.py):
the Pallas interpreter, which runs `hll_stats` on the CPU. Gating it
behind a precise probe keeps the run green-or-skipped, and a NEW
failure is immediately visible instead of hiding in a familiar count.

The probe tests EXACTLY the capability its gated tests consume, and
`tests/test_envprobes.py` is the meta-test asserting the probe
condition against reality.
"""

import pytest


def _interpret_works() -> bool:
    """Can this jax run the kernel under `interpret=True`? Asked of
    the kernel itself: a missing Pallas makes `hll_stats` fall back to
    its jnp twin (counted), which is not what the gated tests test."""
    try:
        import numpy as np

        from veneur_tpu.kernels import hll_stats as k
        if k.pl is None:
            return False
        ez, _ = k.hll_stats(np.zeros((4, 512), np.uint8), interpret=True)
        return float(np.asarray(ez)[0]) == 512.0
    except Exception:               # noqa: BLE001 — any failure = absent
        return False


PALLAS_INTERPRET_MISSING = not _interpret_works()
PALLAS_INTERPRET_SKIP_REASON = (
    "environmental: this jax cannot run pallas_call(interpret=True) — "
    "the hll_stats kernel has nothing to execute on the CPU (serving "
    "degrades to the counted jnp fallback)")
needs_pallas_interpret = pytest.mark.skipif(
    PALLAS_INTERPRET_MISSING, reason=PALLAS_INTERPRET_SKIP_REASON)

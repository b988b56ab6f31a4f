"""Environment probes for the tier-1 skips that depend on where the
tests run.

Two capabilities gate the fused-kernel tests (ISSUE 15): Pallas
interpret mode for the CPU bit-identity arm, and — for the compiled
arm — a TPU on which `auto` serves the compress kernel. Tier-1 runs on
the CPU, so the second always skips there; gating it behind a precise
probe keeps the run green-or-skipped, and a NEW failure is immediately
visible instead of hiding in a familiar count.

Each probe tests EXACTLY the capability its gated tests consume, and
`tests/test_envprobes.py` is the meta-test asserting each probe
condition against reality.
"""

import pytest

from veneur_tpu import kernels as _kernels
from veneur_tpu.utils.platform import is_tpu

# -- pallas: interpret-mode + TPU-compiled kernel arms -----------------
# The fused-kernel tests (tests/test_pallas.py) run the kernels under
# `interpret=True` on CPU — the bit-identity proof needs exactly the
# pallas interpreter, probed by running a trivial kernel through it.
PALLAS_INTERPRET_MISSING = not _kernels.probe_interpret()
PALLAS_INTERPRET_SKIP_REASON = (
    "environmental: this jax cannot run pallas_call(interpret=True) — "
    "the CPU bit-identity arm of the fused kernels has nothing to "
    "execute (serving degrades to the counted XLA fallback)")
needs_pallas_interpret = pytest.mark.skipif(
    PALLAS_INTERPRET_MISSING, reason=PALLAS_INTERPRET_SKIP_REASON)

# The TPU-COMPILED compress test needs a TPU whose Mosaic builds the
# kernel — which is what its module's TPU_AUTO_ARM records (today:
# refused, see kernels/compress.py). chip_smoke.py's kernel leg is
# where every kernel meets the compiler on the chip.
PALLAS_TPU_COMPILE_MISSING = not (
    is_tpu() and _kernels.tpu_auto_arm("compress") == "fused")
PALLAS_TPU_SKIP_REASON = (
    "environmental: no TPU here, or Mosaic refuses the compress kernel "
    "(kernels/compress.TPU_AUTO_ARM) — the compiled fused arm cannot "
    "build; interpret-mode tests prove the kernel math on CPU and "
    "chip_smoke.py's kernel leg puts every kernel to Mosaic on the chip")
needs_pallas_tpu = pytest.mark.skipif(
    PALLAS_TPU_COMPILE_MISSING, reason=PALLAS_TPU_SKIP_REASON)

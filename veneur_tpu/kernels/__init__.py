"""Pallas TPU kernels: every `pl.*` primitive in the tree lives under
this package (machine-checked by vlint PK01).

  hll_stats.py  the streaming HLL estimate reduction: one pass over the
                u8 register file, the two row statistics the
                LogLog-Beta estimator needs.

What selects it: `ops/hll.py:will_use_pallas` — a TPU, and a register
width on the kernel's 512-lane grid. Everything else the engines
dispatch is an XLA program.

What degrades, counted and logged (`veneur.kernels.fallback_total`): a
kernel entry point handed a shape it cannot serve, or an installation
without Pallas (vlint PK01 requires every kernel entry point to carry
that branch).

Two other kernels lived here from PR 15 to PR 48 (a one-call t-digest
compress, a ULL scatter-join insert). Mosaic (jax 0.9.0 / libtpu
0.0.34) refuses the first ("Unimplemented primitive in Pallas TPU
lowering": rev, asin, cumsum, cummax, dynamic_slice), and the second
ran four times slower on the chip than the XLA insert it was byte-equal
to (6.2 ms against 1.5 ms a batch, PR 23's chip runs).
"""

from __future__ import annotations

import logging

logger = logging.getLogger(__name__)


def count_fallback(reason: str):
    """Count + log one kernel->XLA degradation. Every kernel entry
    point's fallback branch routes through here (vlint PK01): the
    counter is `veneur.kernels.fallback_total` on the process registry,
    surfaced at /debug/flush's `sketch_engines.kernels`."""
    from ..observe.registry import DEFAULT_REGISTRY, SERVER_SCOPE
    DEFAULT_REGISTRY.incr(SERVER_SCOPE, "kernels.fallback")
    logger.warning("fused-kernel fallback to the XLA program: %s",
                   reason)


def fallback_total() -> int:
    """Cumulative kernel->XLA degradations this process (/debug)."""
    from ..observe.registry import DEFAULT_REGISTRY, SERVER_SCOPE
    return DEFAULT_REGISTRY.total(SERVER_SCOPE, "kernels.fallback")

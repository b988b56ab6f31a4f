"""Test harness config.

Tests run on a virtual 8-device CPU mesh (the in-process "multi-node"
strategy of the reference test suite — two Servers on loopback — maps here
to N XLA host devices; see SURVEY.md §4). The chip is for chip_smoke.py
and the bench scripts, through the chip tool; nothing under tests/ needs
it. pin_cpu holds this process to the CPU whatever JAX_PLATFORMS says,
before any backend initializes.
"""

import gc
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veneur_tpu.utils.platform import pin_cpu  # noqa: E402

pin_cpu(8)


# memory mappings at which the compiled programs are let go: far enough
# under vm.max_map_count (65530) that the busiest module (~+18k) cannot
# reach it, far enough up that engines of like shape in neighbouring
# modules still share their compiles
_RELEASE_AT_MAPPINGS = 20_000


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """Free compiled programs between modules once they have piled up.
    XLA's CPU executables are memory mappings that nothing else ever
    releases (the engine's executable factories and jit's own caches
    are process-wide and unbounded): one tier-1 process otherwise
    climbs to vm.max_map_count about 60% of the way through and the
    next compile faults."""
    yield
    with open("/proc/self/maps") as f:
        mappings = sum(1 for _ in f)
    if mappings < _RELEASE_AT_MAPPINGS:
        return
    import jax

    from veneur_tpu.models import pipeline
    pipeline.release_executables()
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def fault_harness():
    """Deterministic egress fault injection (utils/faults.py): a shared
    FakeClock + scripted transports + pre-wired Egress factory, so
    retry/breaker/re-merge transitions are asserted without sockets or
    real sleeps."""
    from veneur_tpu.utils.faults import FaultHarness

    return FaultHarness(seed=0)

# The fused flush program's donation warnings ("Some donated buffers
# were not usable" — unused donated buffers are simply freed, which is
# the point) are suppressed via pytest.ini's filterwarnings: pytest
# resets warning filters per test, so a module-level
# warnings.filterwarnings here would be discarded.

"""Which device this process computes on, and where its compiled
programs are kept.

Three questions every entry point (the daemon CLI, chip_smoke.py, the
bench scripts, the test harness) has to settle before the first JAX
operation, answered in one place:

  * pin_cpu()              — tests and explicit host-only runs stay on
                             the CPU (optionally as an n-device virtual
                             mesh);
  * is_tpu()/require_tpu() — "is this a TPU": the one definition the
                             kernel arms, the flush programs and the
                             entry points share. With libtpu installed
                             and no chip attached JAX warns and hands
                             back the CPU; nothing may serve or measure
                             on that and call it a TPU;
  * setup_compile_cache()  — JAX's persistent compilation cache, placed
                             from outside (JAX_COMPILATION_CACHE_DIR)
                             or at one fixed path in the checkout.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pin_cpu(n_devices: int | None = None) -> None:
    """Pin JAX to the host CPU platform; optionally request a virtual
    n-device CPU mesh. Must run before any jax operation (backend init);
    the device-count flag additionally requires that no XLA CPU client
    exists yet in this process."""
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        opt = f"{_COUNT_FLAG}={n_devices}"
        if _COUNT_FLAG in flags:
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", opt, flags)
        else:
            flags = (flags + " " + opt).strip()
        os.environ["XLA_FLAGS"] = flags

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialized; caller's device check will see


def is_tpu(device=None) -> bool:
    """Is `device` (default: the first device JAX reports) a TPU?
    Initializes the backend when it is not up yet, and lets a backend
    that cannot start raise."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return device.platform == "tpu"


def require_tpu(what: str):
    """The first JAX device, or SystemExit naming the platform found
    when it is not a TPU — for entry points whose output would
    otherwise pass a CPU run off as a chip run."""
    import jax
    device = jax.devices()[0]
    if not is_tpu(device):
        raise SystemExit(
            f"{what}: needs a TPU, but JAX's first device is platform "
            f"{device.platform!r} ({device.device_kind})")
    return device


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the
    first compile. When JAX_COMPILATION_CACHE_DIR is set JAX already
    reads its directory from there and none is set here; otherwise the
    cache lives at <checkout>/.jax_cache — a fixed path, because the
    path is part of what a later process has to find again. Every
    program is kept, however quick its compile: a cold serving start
    is hundreds of small programs next to the few large ones. Returns
    the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path

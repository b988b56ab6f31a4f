"""Pallas TPU kernels — the fused-kernel layer of the flush hot path.

ISSUE 15: the flight recorder attributes ~94% of the 100k tick to
`device.exec`, and with the incremental flush (ISSUE 11) bounding the
work set to the dirty [D, C] slice, the one structural lever left is
killing the HBM round-trips BETWEEN the compress stages: XLA
materializes the sort keys, the merged runs, and the cumsum/cluster
intermediates as [D, M] HBM arrays between fused subcomputations. The
kernels here fuse each hot path into ONE `pallas_call` whose
intermediates live in VMEM:

  compress.py   packed-key sort of the sample buffer + log-depth
                bitonic rank-merge against the cluster-ordered centroid
                prefix + greedy k1 cluster/cummax-clamp — the whole
                t-digest compress, one kernel invocation per bucket.
  ull_insert.py scatter-join insert for UltraLogLog register banks —
                sequential lattice-join RMW replacing the XLA
                sort + segmented-scan + gather path.
  hll_stats.py  the streaming HLL estimate reduction (moved from
                ops/pallas_hll.py — every pl.* primitive in the tree
                now lives under this package, machine-checked by
                vlint PK01).

ARM MODEL (the `tpu_fused_kernels` knob): every kernel-routed
executable is built under exactly one arm —

  "fused"      the Mosaic-compiled kernel on a TPU;
  "interpret"  the same kernel under `interpret=True` — the CPU
               testing arm that proves BIT-IDENTITY against the XLA
               program without hardware (tier-1's correctness bar);
  "xla"        the existing XLA program, untouched.

`resolve_arm` maps the knob (auto|on|off), the platform and the kernel
to an arm. On a TPU the compiler has already been asked: each kernel
module carries `TPU_AUTO_ARM`, the arm `auto` serves there, written
next to the kernel together with Mosaic's message when that arm is
"xla" — a decision, not a probe repeated (and a kernel demoted) at
every start. `on` names the kernels outright: on a TPU it builds
"fused" whatever the decision says, and `require_engine_kernels`
compiles each kernel the engine routes through at its serving shape
and RAISES when Mosaic refuses one — the operator asked for the
kernel, and its reference program is not a substitute. A kernel that
`auto` serves and a later shape or compiler refuses fails the flush
compile in `warmup()`: loud, before the first listener binds.

What still degrades, counted and logged
(`veneur.kernels.fallback_total`): an entry point handed a shape its
kernel cannot serve (vlint PK01 requires every kernel entry point to
carry that branch), and `on` off-chip on an installation whose Pallas
cannot interpret.

Bit-identity contract (tests/test_pallas.py): under the "interpret"
arm every kernel reproduces its XLA twin EXACTLY — including ±0.0
canonicalization in the sort keys, duplicate-key stability, NaN
payload bits riding the payload lanes, and the SR02 cummax ordering
invariant — because the sort/merge networks are order-isomorphic to
the XLA path's (distinct lexicographic (key, tag) pairs have ONE
ascending order) and the numeric stages run the identical jnp ops on
identical inputs.
"""

from __future__ import annotations

import functools
import logging

logger = logging.getLogger(__name__)

ARMS = ("fused", "interpret", "xla")
MODES = ("auto", "on", "off")


def count_fallback(reason: str):
    """Count + log one kernel->XLA degradation. Every kernel entry
    point's fallback branch routes through here (vlint PK01): the
    counter is `veneur.kernels.fallback_total` on the process registry,
    surfaced at /debug/flush next to the per-engine arm stamps."""
    from ..observe.registry import DEFAULT_REGISTRY, SERVER_SCOPE
    DEFAULT_REGISTRY.incr(SERVER_SCOPE, "kernels.fallback")
    logger.warning("fused-kernel fallback to the XLA program: %s",
                   reason)


def fallback_total() -> int:
    """Cumulative kernel->XLA degradations this process (/debug)."""
    from ..observe.registry import DEFAULT_REGISTRY, SERVER_SCOPE
    return DEFAULT_REGISTRY.total(SERVER_SCOPE, "kernels.fallback")


# the kernel behind each engine capability, and whether the CPU
# interpret arm serves it (hll_stats' interpret form is a unit-test
# harness only: the CPU flush program estimates in plain jnp)
KERNELS = ("compress", "ull_insert", "hll_stats")
_INTERPRET_SERVED = ("compress", "ull_insert")


class KernelRefused(RuntimeError):
    """`tpu_fused_kernels: on` named a kernel Mosaic will not build at
    this engine's serving shape."""


@functools.lru_cache(maxsize=None)
# vlint: disable=PK01 reason=availability probe, not a serving entry
# point — resolve_arm owns the counted fallback when this is False
def probe_interpret() -> bool:
    """Can this jax run a trivial `pallas_call(interpret=True)`? The
    EXACT capability the interpret arm (and its tier-1 tests) consume;
    tests/envprobes.py gates on this probe."""
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def k(x_ref, o_ref):
            o_ref[:] = x_ref[:] + 1.0

        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True)(jnp.zeros((8, 128), jnp.float32))
        return bool(out[0, 0] == 1.0)
    except Exception as e:          # noqa: BLE001 — any failure = absent
        logger.info("pallas interpret probe failed: %s", e)
        return False


def tpu_auto_arm(kernel: str) -> str:
    """The arm `auto` serves `kernel` under on a TPU: the decision
    written next to the kernel (its module's TPU_AUTO_ARM)."""
    import importlib
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}")
    return importlib.import_module(f"{__name__}.{kernel}").TPU_AUTO_ARM


def resolve_arm(mode: str, platform: str, kernel: str) -> str:
    """Map the `tpu_fused_kernels` knob to the arm `kernel`'s
    executables are built under on `platform`.

      off   -> "xla" always.
      auto  -> on a TPU the kernel's written decision (TPU_AUTO_ARM);
               "xla" on CPU — the interpret arm is a CORRECTNESS
               harness, not a serving default (it simulates the kernel).
      on    -> "fused" on a TPU (require_engine_kernels then compiles
               it and raises on refusal); on CPU the interpret arm
               serves (the testing stance: the oracle/chaos suites run
               the actual kernel math through the whole pipeline), with
               a counted fallback when even interpret is unavailable.
    """
    if mode not in MODES:
        raise ValueError(
            f"tpu_fused_kernels must be one of {'/'.join(MODES)}, "
            f"got {mode!r}")
    if mode == "off":
        return "xla"
    if platform == "tpu":
        return "fused" if mode == "on" else tpu_auto_arm(kernel)
    if mode == "on" and kernel in _INTERPRET_SERVED:
        if probe_interpret():
            return "interpret"
        count_fallback(
            "tpu_fused_kernels=on without a TPU backend and "
            "pallas interpret mode unavailable")
    return "xla"


def engine_arms(mode: str, platform: str, heng, seng) -> dict:
    """One resolved arm per kernel-backed engine capability: an engine
    without the kernel (REQ compress, HLL insert, ULL estimate) stays
    on "xla" no matter the knob, so the /debug arm stamps name what
    each engine's executables are ACTUALLY built with."""
    from . import hll_stats

    def arm(kernel, routed):
        return resolve_arm(mode, platform, kernel) if routed else "xla"

    return {
        "histogram": arm("compress",
                         hasattr(heng, "compress_fused_impl")),
        "set": arm("ull_insert", hasattr(seng, "insert_fused_impl")),
        "estimate": arm("hll_stats", seng.id == "hll"
                        and seng.num_registers % hll_stats._LANES == 0),
    }


def require_engine_kernels(heng, seng, arms: dict, set_slots: int,
                           batch_size: int) -> None:
    """`tpu_fused_kernels: on` on a TPU: AOT-compile every kernel this
    engine routes through (`arms` value "fused") at the ENGINE'S
    serving shapes — the compress at its real centroid/buffer widths
    over the row block, the insert at the real [set_slots, m] register
    file and batch width, the estimate reduction over that register
    file — and raise KernelRefused with the compiler's message when
    Mosaic refuses one. At construction, never mid-tick."""
    import jax
    import jax.numpy as jnp

    def compile_or_raise(what, fn, *avals):
        try:
            jax.jit(fn).lower(*avals).compile()
        except Exception as e:      # noqa: BLE001 — any refusal raises
            raise KernelRefused(
                f"tpu_fused_kernels=on: Mosaic refused the {what}: "
                f"{type(e).__name__}: {e}") from e

    sds = jax.ShapeDtypeStruct
    if arms.get("histogram") == "fused":
        from . import compress as _compress
        proto = jax.eval_shape(lambda: heng.init(1))
        C, B = proto.mean.shape[1], proto.buf_value.shape[1]
        R = _compress._BLOCK_ROWS
        comp = float(heng.compression)
        compile_or_raise(
            f"fused compress at C={C} B={B} (block {R})",
            lambda m, w, bv, bw: _compress.fused_compress(
                m, w, bv, bw, compression=comp, interpret=False),
            sds((R, C), jnp.float32), sds((R, C), jnp.float32),
            sds((R, B), jnp.float32), sds((R, B), jnp.float32))
    bank_aval = jax.eval_shape(lambda: seng.init(set_slots))
    if arms.get("set") == "fused":
        from . import ull_insert as _ull_insert
        compile_or_raise(
            f"fused ULL insert at [{set_slots}, {seng.num_registers}] "
            f"x batch {batch_size}",
            lambda b, s, i, v: _ull_insert.fused_insert(
                b, s, i, v, interpret=False),
            bank_aval, sds((batch_size,), jnp.int32),
            sds((batch_size,), jnp.int32),
            sds((batch_size,), jnp.uint8))
    if arms.get("estimate") == "fused":
        from . import hll_stats as _hll_stats
        compile_or_raise(
            f"hll_stats at [{set_slots}, {seng.num_registers}]",
            lambda r: _hll_stats.hll_stats(r, interpret=False),
            bank_aval.registers)

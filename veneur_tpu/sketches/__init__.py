"""Pluggable sketch engines — the registry and selection layer.

ISSUE 10's engine-selection subsystem: the aggregation pipeline's
histogram/timer sketches and set-cardinality sketches are selected
here via the `histogram_backend` / `set_backend` config keys (the
`aggregation_backend` selection pattern), instead of being hard-wired
to the t-digest + HLL pair:

  histogram_backend:  "tdigest" (default) | "req"
  set_backend:        "hll" (default)     | "ull"

Every engine presents the fixed contract documented in
`sketches/base.py`; the pipeline (models/pipeline.py) holds ONE
histogram-engine and ONE set-engine object and never names a concrete
sketch again (vlint SK01 machine-checks the boundary: bank
constructions and sketch-ops imports outside this package + the
blessed ops/ kernels are flagged).

MIXED-FLEET SAFETY — the engine/wire-format stamp: both forward
contracts carry a compact engine stamp ("h=<id>/<ver>,s=<id>/<ver>")
per request; a receiver whose own stamp differs REJECTS the request
loudly (counted `veneur.import.engine_mismatch_total`, surfaced
per-sender at GET /debug/fleet) rather than silently merging
incompatible register banks. An absent stamp means a legacy peer and
is interpreted as the DEFAULT engine pair, so an un-upgraded fleet
keeps working and only a fleet that actually switched backends
refuses legacy senders. The header/field codecs live in
cluster/wire.py (the TR01 single-homing precedent); the stamp
STRINGS, and the set-register byte codec, live here.

Set-register wire codec: byte 0 tags the engine+format (1 = HLL v1 —
the pre-registry byte, so old payloads decode unchanged; 2 = ULL v1),
byte 1 the precision, then the raw u8 registers. Decoding returns
(engine_id, registers); feeding a payload into a bank of the other
engine raises before any register merges (belt to the stamp check's
suspenders).
"""

from __future__ import annotations

import numpy as np

from .. import kernels
from ..ops import hll
from .hll_engine import HLLEngine
from .req import REQEngine
from .tdigest_engine import TDigestEngine
from .ull import ULLEngine

HISTOGRAM_BACKENDS = ("tdigest", "req")
SET_BACKENDS = ("hll", "ull")

# set-register wire codes (byte 0 of the payload)
_SET_WIRE_CODES = {"hll": 1, "ull": 2}
_SET_WIRE_IDS = {v: k for k, v in _SET_WIRE_CODES.items()}


def histogram_engine(cfg):
    """Engine object for an EngineConfig-like cfg (duck-typed: reads
    histogram_backend + the per-engine shape keys). Frozen dataclass —
    hashable, so it keys the pipeline's lru_cached executables."""
    backend = getattr(cfg, "histogram_backend", "tdigest")
    if backend == "tdigest":
        return TDigestEngine(compression=float(cfg.compression),
                             buffer_depth=int(cfg.buffer_depth))
    if backend == "req":
        return REQEngine(levels=int(getattr(cfg, "req_levels", 2)),
                         capacity=int(getattr(cfg, "req_capacity", 256)))
    raise ValueError(
        f"unknown histogram_backend {backend!r} "
        f"(known: {', '.join(HISTOGRAM_BACKENDS)})")


def set_engine(cfg):
    backend = getattr(cfg, "set_backend", "hll")
    if backend == "hll":
        return HLLEngine(precision=int(cfg.hll_precision))
    if backend == "ull":
        return ULLEngine(precision=int(getattr(cfg, "ull_precision", 13)))
    raise ValueError(
        f"unknown set_backend {backend!r} "
        f"(known: {', '.join(SET_BACKENDS)})")


def engine_stamp(heng, seng) -> str:
    """The wire stamp of an engine pair: "h=<id>/<ver>,s=<id>/<ver>"."""
    return (f"h={heng.id}/{heng.wire_version},"
            f"s={seng.id}/{seng.wire_version}")


def stamp_with_codec(stamp: str, centroid_codec: str) -> str:
    """Fold the forward centroid codec into an engine stamp (ISSUE
    13): "q16" appends a "q" marker to the histogram component's wire
    version ("h=tdigest/1" -> "h=tdigest/1q"), so a quantized-centroid
    fleet and a lossless fleet read as DIFFERENT wire formats and
    reject each other loudly before decode — quantized rows must never
    be mistaken for (or silently mixed with) lossless ones. "lossless"
    returns the stamp unchanged (legacy peers stay compatible)."""
    if centroid_codec != "q16":
        return stamp
    return ",".join(part + "q" if part.startswith("h=") else part
                    for part in stamp.split(","))


# what an unstamped (legacy) peer is running, by definition
DEFAULT_STAMP = engine_stamp(TDigestEngine(), HLLEngine())


def parse_stamp(stamp: str) -> dict | None:
    """"h=tdigest/1,s=hll/1" -> {"h": ("tdigest", 1, "lossless"),
    "s": ("hll", 1, "lossless")}; a trailing "q" on a version (the
    quantized-centroid marker, see stamp_with_codec) parses as codec
    "q16". None for a malformed stamp (the receiver then rejects — an
    unparseable stamp is a peer we cannot reason about, which is the
    mismatch case, not the legacy case)."""
    out = {}
    try:
        for part in stamp.split(","):
            kind, _, rest = part.partition("=")
            eng, _, ver = rest.partition("/")
            if kind not in ("h", "s") or not eng:
                return None
            codec = "lossless"
            if ver.endswith("q"):
                ver, codec = ver[:-1], "q16"
            out[kind] = (eng, int(ver or 1), codec)
    except ValueError:
        return None
    return out if ("h" in out and "s" in out) else None


def stamp_compatible(local: str, remote: str | None) -> bool:
    """Is a peer's stamp (None = legacy peer = DEFAULT_STAMP)
    mergeable into engines running `local`? Compared component-wise on
    (engine id, wire version) so ordering/whitespace never matter."""
    mine = parse_stamp(local)
    theirs = parse_stamp(remote) if remote is not None \
        else parse_stamp(DEFAULT_STAMP)
    if mine is None or theirs is None:
        return False
    return mine == theirs


def encode_set_registers(engine_id: str, registers) -> bytes:
    regs = np.asarray(registers, np.uint8)
    precision = int(np.log2(len(regs)))
    # vlint: disable=DR02 reason=the versioned set-register WIRE row
    # (u8 registers are exact either way); single-homed here per SK01
    return bytes([_SET_WIRE_CODES[engine_id], precision]) + regs.tobytes()


def decode_set_registers(data: bytes) -> tuple:
    """-> (engine_id, registers u8[m]); raises ValueError on an
    unknown code or a length mismatch (the poison-pill reject path)."""
    if len(data) < 2 or data[0] not in _SET_WIRE_IDS:
        raise ValueError("bad set-sketch payload (unknown engine code)")
    precision = data[1]
    # vlint: disable=DR02 reason=inverse of the set-register wire row
    # above — same single-homed wire codec, not a bank-leaf byte move
    regs = np.frombuffer(data[2:], np.uint8)
    if len(regs) != 1 << precision:
        raise ValueError("set-sketch register count mismatch")
    return _SET_WIRE_IDS[data[0]], regs


def set_engine_for_id(engine_id: str, precision: int):
    """Engine object for a decoded wire payload (spill re-merge joins
    registers by the payload's own engine, whatever the local bank
    runs — the stamp check keeps mixed payloads out of BANKS, but the
    sender-side spill buffer merges its own exports)."""
    if engine_id == "hll":
        return HLLEngine(precision=precision)
    if engine_id == "ull":
        return ULLEngine(precision=precision)
    raise ValueError(f"unknown set engine {engine_id!r}")


def merge_registers(engine_id: str, a, b):
    """Host-side register union under the payload's engine semantics
    (max for HLL, lattice join for ULL)."""
    if engine_id == "ull":
        from .ull import join_registers_np
        return join_registers_np(a, b)
    return np.maximum(np.asarray(a, np.uint8), np.asarray(b, np.uint8))


def describe(heng, seng) -> dict:
    """JSON-ready engine description for /debug/flush. `kernels`:
    what implements the set estimate here (the one Pallas kernel, where
    ops/hll.will_use_pallas takes it) and the process-wide count of
    kernel entry points that fell back to their XLA twin."""
    pallas = seng.id == "hll" and hll.will_use_pallas(seng.num_registers)
    return {
        "stamp": engine_stamp(heng, seng),
        "histogram": {"id": heng.id, "wire_version": heng.wire_version,
                      "params": {k: getattr(heng, k)
                                 for k in heng.__dataclass_fields__},
                      "error_contract": heng.error_contract},
        "set": {"id": seng.id, "wire_version": seng.wire_version,
                "params": {k: getattr(seng, k)
                           for k in seng.__dataclass_fields__},
                "error_contract": seng.error_contract},
        "kernels": {"estimate": "pallas" if pallas else "jnp",
                    "fallback_total": kernels.fallback_total()},
    }


def release_executables() -> None:
    """Drop the compiled programs the engines hold process-wide (the
    t-digest landing's cluster programs, one a shape): the sketch
    engines' share of models/pipeline.release_executables."""
    from ..ops import tdigest
    tdigest.cluster_program.cache_clear()

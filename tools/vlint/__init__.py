"""vlint — project-native static analysis for veneur-tpu.

Checks (see tools/vlint/README.md for the full contract):
  JX01  tracer leak inside a jitted function
  JX02  donated buffer read after dispatch
  JX03  host sync outside the flush/fetch modules
  TH01  unguarded shared-state write in the threaded server files
  CF01  config-plumbing parity across sibling listener-start calls
  NA01  nullptr-reachable string::assign in the native bridge
  NA02  native/Python decoder recursion-cap divergence
  NA03  native/Python SSF frame-layout divergence
  NA04  native/Python stats-array layout divergence
  NA05  a native stage parsed into before its arrival stamp is set
  GC01  the collector's switch touched outside the row-building guard
  VL00  suppression without a reason
  VL01  file failed to parse

Run: `python -m tools.vlint veneur_tpu/ native/`
"""

from .core import Violation, run_paths  # noqa: F401

__all__ = ["Violation", "run_paths"]

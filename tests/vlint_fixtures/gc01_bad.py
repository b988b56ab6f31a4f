"""GC01 fixture: the collector's switch touched outside the guard in
veneur_tpu/metrics.py. The filename carries the /gc01_ scope marker.
Line numbers are pinned by tests/test_vlint.py."""

import gc
import gc as collector
from gc import freeze                                        # GC01


def build_rows_quietly(make):
    gc.disable()                                             # GC01
    try:
        return make()
    finally:
        gc.enable()                                          # GC01


def tune():
    collector.set_threshold(100_000)                         # GC01
    return gc.get_threshold(), gc.isenabled()                # ok: reads


class _CollectorHold:
    """Named like the guard, but not in the guard's file."""

    def __enter__(self):
        off = gc.disable                                     # GC01
        off()

    def __exit__(self, *exc):
        # vlint: disable=GC01 reason=fixture-only: the suppression
        # syntax for a documented second switch
        gc.enable()


def collects():
    return gc.collect(), freeze                              # ok

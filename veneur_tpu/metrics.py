"""Core metric value types shared across the pipeline.

Parity: samplers/samplers.go (sym: InterMetric, MetricScope) — the flushed
representation handed to sinks — and samplers/metricpb's wire shapes for
forwarded aggregates (re-expressed in veneur_tpu.cluster.wire).
"""

from __future__ import annotations

import gc
import threading
import time
from dataclasses import dataclass, field
from enum import IntEnum
from itertools import chain, cycle, repeat


class MetricType(IntEnum):
    COUNTER = 0
    GAUGE = 1
    HISTOGRAM = 2
    SET = 3
    TIMER = 4
    STATUS = 5


@dataclass
class InterMetric:
    """One flushed metric handed to MetricSink.Flush — the unit of egress
    (samplers.InterMetric)."""
    name: str
    timestamp: int          # unix seconds
    value: float
    tags: list[str] = field(default_factory=list)
    type: MetricType = MetricType.GAUGE
    message: str = ""
    hostname: str = ""
    sinks: list[str] = field(default_factory=list)  # empty = all sinks


class _CollectorHold:
    """Generational collection held off while a frame's rows are born.

    300,000 young, acyclic, tracked rows (and their 300,000 `sinks`
    lists) cross the young generation's threshold some 850 times and
    are walked again by every full collection their own growth sets
    off: more than half of what building them cost. Inside the hold the
    collector sees them once, when it next runs.

    Counted under a lock, because the local's and the global's sink
    threads build at once and the first to finish must not switch
    collection back on under the other. Restores what it found: a
    process that runs with `gc` disabled stays disabled. The ONE place
    under veneur_tpu/ that may touch the collector's switch (vlint
    GC01), held only across the construction of one frame's rows,
    never across a sink's flush or I/O."""

    def __init__(self):
        self._lock = threading.Lock()
        self._depth = 0
        self._was_enabled = False

    def __enter__(self):
        with self._lock:
            if self._depth == 0:
                self._was_enabled = gc.isenabled()
                gc.disable()
            self._depth += 1

    def __exit__(self, *exc):
        with self._lock:
            self._depth -= 1
            if self._depth == 0 and self._was_enabled:
                gc.enable()


_collector_held = _CollectorHold()


def _rows_per_row(names, tags, values, types, ts, host):
    """One generator resumption and one keyword call a row: what every
    block paid before the column path, kept for a block that path
    cannot take (a ragged names[i]: it reads the first m names)."""
    rows = values.tolist()
    m = values.shape[1]
    if m == 1:
        t0 = types[0]
        for nm, tg, row in zip(names, tags, rows):
            yield InterMetric(
                name=nm if isinstance(nm, str) else nm[0],
                timestamp=ts, value=row[0], tags=tg,
                type=t0, hostname=host)
    else:
        for nms, tg, row in zip(names, tags, rows):
            for j in range(m):
                yield InterMetric(
                    name=nms[j], timestamp=ts, value=row[j],
                    tags=tg, type=types[j], hostname=host)


def _block_rows(block, ts, host):
    """(lazy rows of one block, took the per-row fallback?).

    The row columns are made once (values flattened row-major, the
    names flattened, each key's shared tags list repeated m times BY
    REFERENCE, types cycled) and the rows come from one C-level map
    over InterMetric's positional fields, in the per-row order: key by
    key, column by column. `sinks` is the default factory's fresh list
    a row."""
    names, tags, values, types = block
    n, m = values.shape
    if m == 1:
        flat = [nm if isinstance(nm, str) else nm[0] for nm in names]
        tag_col = tags
    elif n and set(map(len, names)) != {m}:
        return _rows_per_row(names, tags, values, types, ts, host), True
    else:
        flat = chain.from_iterable(names)
        tag_col = chain.from_iterable(map(repeat, tags, repeat(m)))
    return map(InterMetric, flat, repeat(ts), values.ravel().tolist(),
               tag_col, cycle(types), repeat(""), repeat(host)), False


class MetricFrame:
    """Columnar flushed metrics — the TPU-first egress representation.

    A flush at 100k histogram keys emits ~600k metrics; building 600k
    Python objects inside the flush would dominate the <50ms latency
    budget. Instead the flush assembles blocks of (per-key names, per-key
    tag refs, a [n, m] numpy value matrix, m column types) and hands this
    frame to the server; InterMetric objects are materialized lazily, only
    when a sink iterates (where the cost is amortized into serialization).

    `names[i]` is either one string (m == 1) or a sequence of m strings;
    `tags[i]` is a list[str] SHARED across all metrics of that key (and
    across flushes, via the engine's presentation cache) — consumers must
    treat it as read-only.
    """

    __slots__ = ("timestamp", "hostname", "_blocks", "_n", "_list",
                 "_mat_lock", "rows_built", "build_ns", "rows_fallback",
                 "_builder")

    def __init__(self, timestamp: int, hostname: str = ""):
        self.timestamp = timestamp
        self.hostname = hostname
        self._blocks: list = []
        self._n = 0
        self._list: list[InterMetric] | None = None
        self._mat_lock = threading.Lock()
        # what to_list() built: rows, the pass's ns, rows of blocks the
        # column path could not take; and the thread that built them,
        # until FrameSet.claim_build() hands the numbers to its phase
        self.rows_built = 0
        self.build_ns = 0
        self.rows_fallback = 0
        self._builder: int | None = None

    def add_block(self, names, tags, values, types) -> None:
        import numpy as np

        values = np.asarray(values)
        if values.ndim == 1:
            values = values[:, None]
        if len(names) != values.shape[0] or len(tags) != values.shape[0]:
            raise ValueError("block rows mismatch")
        if len(types) != values.shape[1]:
            raise ValueError("block cols mismatch")
        self._blocks.append((names, tags, values, tuple(types)))
        self._n += values.size
        self._list = None

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        if self._list is not None:
            yield from self._list
            return
        ts, host = self.timestamp, self.hostname
        for block in self._blocks:
            yield from _block_rows(block, ts, host)[0]

    def to_list(self) -> list[InterMetric]:
        # several sink threads may materialize concurrently; the lock
        # makes the (expensive) materialization happen exactly once
        if self._list is None:
            with self._mat_lock:
                if self._list is None:
                    self._list = self._build()
        return self._list

    def _build(self) -> list[InterMetric]:
        ts, host = self.timestamp, self.hostname
        out: list[InterMetric] = []
        fallback = 0
        t0 = time.perf_counter_ns()
        with _collector_held:
            for block in self._blocks:
                rows, slow = _block_rows(block, ts, host)
                out.extend(rows)
                if slow:
                    fallback += block[2].size
        self.build_ns = time.perf_counter_ns() - t0
        self.rows_built = len(out)
        self.rows_fallback = fallback
        self._builder = threading.get_ident()
        return out

    @property
    def blocks(self):
        """The raw (names, tags, values[n, m], types) blocks — the
        frame-native sink serialization surface."""
        return self._blocks


class FrameSet:
    """One flush's complete output: the engines' columnar frames plus
    loose InterMetrics (self-telemetry). This is what the server hands
    to sinks. Frame-native sinks serialize straight from the blocks;
    legacy sinks iterate, which materializes InterMetric objects lazily
    in the SINK's thread (off the flush critical path) and caches them
    once for all such sinks."""

    __slots__ = ("frames", "extra")

    def __init__(self, frames=None, extra=None):
        self.frames = frames or []
        self.extra = extra or []

    def __len__(self) -> int:
        return sum(len(f) for f in self.frames) + len(self.extra)

    def __iter__(self):
        for f in self.frames:
            yield from f
        yield from self.extra

    def to_list(self) -> list[InterMetric]:
        out = []
        for f in self.frames:
            out.extend(f.to_list())
        out.extend(self.extra)
        return out

    def claim_build(self) -> dict[str, int]:
        """rows_built / build_ns / rows_fallback of the frames the
        CALLING thread materialized, handed out once: the sink that did
        the build carries them on its phase, a sink that found the list
        cached carries zeros."""
        me = threading.get_ident()
        got = {"rows_built": 0, "build_ns": 0, "rows_fallback": 0}
        for f in self.frames:
            if f._builder == me:
                f._builder = None
                for k in got:
                    got[k] += getattr(f, k)
        return got


@dataclass
class SampleBatchStats:
    """Per-flush ingest bookkeeping, reported as veneur.* self-metrics."""
    samples: int = 0
    dropped_no_slot: int = 0
    parse_errors: int = 0

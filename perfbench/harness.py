"""What every run shares, whatever its topology and traffic kind.

`run.py` finds the parts of a cell by name, each in a file of its own:

  perfbench/configs/<name>.json      the deployment; names its `driver`
  perfbench/drivers/<name>.py        `Driver`: builds and starts the
                                     servers from the deployment's keys
                                     and drives one tick (contract below)
  perfbench/mixes/<name>.json        the traffic mix; names its `generator`
  perfbench/generators/<name>.py     `build(cfg, mix, seed, log)`: the
                                     run's payloads with the plain
                                     reference's answers for each
  perfbench/metrics/<name>.json|.py  a per-layer metric's reader
                                     (`layers.py`)

A driver is a class `Driver` with `TAKES` (the kind of payload it sends:
its generator's `MAKES`), `OPS` (what `attempted` and `failed` count) and

  Driver(cfg, rehearsal)     build and start the servers
  mesh_devices() -> int      distinct devices under the global's banks
  watch_warmup()             before the warm-up ticks
  finish_warmup() -> list    after them: what it warmed besides
  tick(payload, ts, spans, gcm, meter) -> record    (`layers.py`)
  check(payload, record, tol) -> {"numbers": {name: (value, limit)},
                                  "mismatches": [...], "attempted": n,
                                  "failed": n}
                             that tick's record and the sinks' newest
                             flush against the payload's reference,
                             between ticks; `run.py` keeps the worst of
                             each number over the run's ticks
  drop_counters() -> {name: count}          all 0 or not `correct`
  stop()

A generator is a module with `MAKES` and `build`, which returns
`(payloads, reference_seconds)`: each payload a dict with what its
driver sends and under `"ref"` what the servers must answer; the
reference's seconds are reported apart because they are not set-up.
`cfg["control"]` is the control being run in the program's place, or
None: a driver or a generator reads the part that is its own.
`cfg["study"]` is true where the run writes every tick's record
(`--ticks-out`): a driver may then record what it can only read through
a wrapper on the program, which a benchmark run's window never times.

The meters, the spans and the sink below are the benchmark's own clock
readings; from the program a driver takes the servers, their flight
recorder phases and their counters. `CompileMeter` is copied from
`chip_smoke.py`.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg: str = "") -> None:
    print(msg, flush=True)


# ----------------------------------------------------------- files by name

def load_data(kind: str, name: str, rehearsal: bool = False,
              root: str = HERE) -> dict:
    """`<root>/<kind>/<name>.json`; in a rehearsal the file's own
    `rehearsal` block overrides its groups key by key (tiny sizes)."""
    with open(os.path.join(root, kind, name + ".json")) as f:
        data = json.load(f)
    if rehearsal:
        for group, over in data.get("rehearsal", {}).items():
            data[group] = ({**data.get(group, {}), **over}
                           if isinstance(over, dict) else over)
    return data


def load_config(name: str, rehearsal: bool = False,
                root: str = HERE) -> dict:
    return load_data("configs", name, rehearsal, root)


def load_mix(name: str, rehearsal: bool = False, root: str = HERE) -> dict:
    return load_data("mixes", name, rehearsal, root)


def load_code(kind: str, name: str, root: str = HERE):
    """The module `<root>/<kind>/<name>.py`, or None where there is no
    such file: a driver, a generator or a metric's reader, found by the
    name a data file gives it."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(kind: str, key: str, data: dict, root: str):
    mod = load_code(kind, data[key], root)
    if mod is None:
        raise SystemExit(
            f"perfbench: {data['name']!r} names the {key} {data[key]!r}, "
            f"and there is no perfbench/{kind}/{data[key]}.py")
    return mod


def load_driver(cfg: dict, root: str = HERE):
    """The module of the driver the deployment file names."""
    return _named("drivers", "driver", cfg, root)


def load_generator(mix: dict, root: str = HERE):
    """The module of the generator the mix file names."""
    return _named("generators", "generator", mix, root)


# ------------------------------------------------------------------ meters

class CompileMeter:
    """Counts what JAX compiles (or fetches from the persistent cache)
    through jax.monitoring. Copied from chip_smoke.py."""

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GcMeter:
    """Every garbage collection of the process with its edges on the
    monotonic clock (`gc.callbacks`), so a tick can say how many fell
    inside it and how long they took."""

    def __init__(self):
        self.spans: list = []          # (t0_ns, t1_ns, generation)
        self._t0 = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.monotonic_ns()
        else:
            self.spans.append((self._t0, time.monotonic_ns(),
                               info.get("generation", -1)))

    def close(self):
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(self._cb)

    def inside(self, t0_ns: int, t1_ns: int):
        hit = [(a, b) for a, b, _g in self.spans if a < t1_ns and b > t0_ns]
        return len(hit), sum(min(b, t1_ns) - max(a, t0_ns)
                             for a, b in hit) / 1e9


class Spans:
    """The benchmark's own spans: (name, t0_ns, t1_ns) on the monotonic
    clock, each also written into the profiler's trace when one is being
    taken (`jax.profiler.TraceAnnotation`), so the trace reduction can
    lay host spans and device operations on one clock."""

    def __init__(self):
        self.rows: list = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.monotonic_ns()))


class TickBooks:
    """The part of a tick record that is the process's, the same under
    every driver: opened before the tick's first span, closed with the
    tick's three edges (`layers.py` has the record's contract)."""

    def __init__(self, spans: Spans, meter: CompileMeter):
        self.spans, self.meter = spans, meter
        self.n0 = len(spans.rows)
        self.compiles0 = meter.requests       # == len(meter.names)
        self.cpu0 = time.process_time()

    def close(self, gcm: GcMeter, t_first: int, t_last: int,
              t_end: int) -> dict:
        gc_n, gc_s = gcm.inside(t_last, t_end)
        return {
            "t_first_ns": t_first, "t_last_ns": t_last, "t_end_ns": t_end,
            "emit_latency_s": (t_end - t_last) / 1e9,
            "wall_s": (t_end - t_first) / 1e9,
            "spans": {name: (t1 - t0) / 1e9
                      for name, t0, t1 in self.spans.rows[self.n0:]},
            "counters": {"compile.programs":
                         self.meter.requests - self.compiles0},
            "compiled": self.meter.names[self.compiles0:],
            "cpu_s": time.process_time() - self.cpu0,
            "gc_n": gc_n, "gc_s": gc_s,
            "threads": threading.active_count(),
            "loadavg": os.getloadavg()[0],
        }


def phase_seconds(rows: list) -> dict:
    """{name: summed seconds} of one tick's flight recorder phases."""
    out: dict = {}
    for name, t0, t1 in rows:
        out[name] = out.get(name, 0.0) + (t1 - t0) / 1e9
    return out


# ------------------------------------------- what the drivers share: servers

def make_sink():
    """A sink that keeps only the newest flush and stamps its arrival:
    `emit_latency_s` ends here."""
    from veneur_tpu.sinks.basic import CaptureMetricSink

    class StampSink(CaptureMetricSink):
        def __init__(self):
            super().__init__()
            self.count = 0
            self.arrived_ns = 0

        def flush(self, metrics):
            now = time.monotonic_ns()
            with self._cv:
                self.flushes = [list(metrics)]
                self.count += 1
                self.arrived_ns = now
                self._cv.notify_all()

        def wait_count(self, n, timeout):
            with self._cv:
                return self._cv.wait_for(lambda: self.count >= n, timeout)

        def take(self) -> list:
            """The newest flush's rows, and forget them."""
            rows, self.flushes = self.flushes[-1], [[]]
            return rows

    return StampSink()


def build_server(cfg: dict, tier: str, extra: dict, sink, rehearsal: bool):
    """A `Server` from the deployment file's `common` keys under the
    tier's own, as an operator would write them."""
    import yaml

    from veneur_tpu.config import read_config
    from veneur_tpu.server import Server
    common = dict(cfg["common"])
    if rehearsal:
        common["aggregation_backend"] = "cpu"
    text = yaml.safe_dump({**common, **cfg[tier], **extra})
    return Server(read_config(text=text, env={}), sinks=[sink])


def server_phases(srv, tier: str) -> list:
    """The flight recorder's phases of the server's newest flush tick,
    as the program names them, behind `<tier>:`."""
    tick = srv.flight.last_tick() if srv.flight is not None else None
    if tick is None:
        return []
    return [(f"{tier}:{name}", t0, t1)
            for name, t0, t1, _p in tick.phases() if t1 > t0]


def server_counters(srv, tier: str) -> dict:
    from veneur_tpu.observe import SERVER_SCOPE
    return {f"{tier}.{name}": int(srv.telemetry.total(SERVER_SCOPE, name))
            for name in ("flush.error", "packet.error", "worker.dropped",
                         "samples.dropped_no_slot", "import.rejected")}


def registry_total(srv, name: str) -> int:
    """A counter summed over its scopes (a destination-scoped family
    such as `forward.bytes` or the dedupe ledger's), in the server's
    registry and the process's default one."""
    from veneur_tpu import resilience
    regs = {id(r): r for r in (srv.telemetry, resilience.DEFAULT_REGISTRY)}
    return int(sum(
        v for r in regs.values()
        for (_s, n), v in r.totals_by_name_prefix(name).items()
        if n == name))


def mesh_devices(engine) -> int:
    """Distinct devices under the least-spread leaf of the engine's
    banks (1 for a one-chip engine)."""
    import jax
    me = getattr(engine, "me", None)
    if me is None:
        return 1
    return min(len({s.device for s in leaf.addressable_shards})
               for leaf in jax.tree_util.tree_leaves(me.banks))


def flush_global(srv, ts: int, spans: Spans, timeout_s: float):
    """The global's half of a tick: every import applied (a forward is
    acknowledged once its metrics sit on the worker queues), then its
    `flush_once`. The caller waits on the sinks."""
    with spans.span("bench.global_drain"):
        if not srv.drain(timeout=timeout_s):
            raise TimeoutError("global tier did not drain its imports")
    with spans.span("bench.global_flush"):
        srv.flush_once(timestamp=ts)


class LandingWatch:
    """The one-chip global lands a batch of imported digests through
    `cluster_rows` on a [slots, lanes] matrix whose lanes are the widest
    slot's pile of centroids rounded up to 128. A hot key's digest holds
    118-130 centroids at compression 100, and which side of 128 it falls
    on follows how the local's pump happened to batch its samples: a
    width the warm-up ticks did not meet can turn up in a timed tick. So
    the warm-up ticks record every shape the landing clusters, and
    `warm_other_widths` then runs each at the other widths. An engine
    that lands another way (the mesh engine's fixed batches) records
    nothing. `keep=True` goes on recording after the warm-up, for a
    driver that reports each tick's shapes (`taken`). Behind a fleet the
    width follows which senders' requests shared a stage, up to all of
    them: that driver names the widest pile (`upto`)."""

    def __init__(self, engine):
        self.seen: set = set()       # ((rows, lanes), sorted kw items)
        self.log: list = []          # [rows, lanes] since the last `taken`
        self._heng = getattr(engine, "_heng", None)
        self._inner = getattr(self._heng, "cluster_rows", None)
        if self._inner is None:
            return
        inner = self._inner

        def recording(values, weights, **kw):
            self.seen.add((values.shape, tuple(sorted(kw.items()))))
            self.log.append(list(values.shape))
            return inner(values, weights, **kw)

        # the engine's sketch adapter is a frozen dataclass
        object.__setattr__(self._heng, "cluster_rows", recording)

    def taken(self) -> list:
        shapes, self.log = self.log, []
        return shapes

    def warm_other_widths(self, keep: bool = False, upto: int = 0) -> list:
        """Cluster zeros at every multiple of 128 lanes, up to the
        widest met, at least 256 and at least `upto` (the widest pile
        the driver knows its traffic can make), that a recorded landing
        did not use; stop recording unless `keep`. Returns the shapes
        warmed."""
        import jax
        import numpy as np
        if self._inner is None:
            return []
        inner, seen = self._inner, self.seen
        if not keep:
            object.__delattr__(self._heng, "cluster_rows")  # the class's own
            self._inner = None
        widest = max([256, -(-upto // 128) * 128]
                     + [shape[1] for shape, _kw in seen])
        warmed = []
        for (rows, _lanes), kw in sorted(seen):
            for lanes in range(128, widest + 1, 128):
                if ((rows, lanes), kw) in seen:
                    continue
                seen.add(((rows, lanes), kw))
                zeros = np.zeros((rows, lanes), np.float32)
                jax.block_until_ready(inner(zeros, zeros, **dict(kw)))
                warmed.append((rows, lanes))
        return warmed

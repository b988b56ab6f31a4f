"""The generator `ssf_spans`: a tick's samples carried as SSF spans on a
framed stream, for a local tier that listens on `unix://` or `tcp://`.

The samples are those of the generator `dogstatsd_lines` (the same keys,
values and counts from the same seed: `touched_keys`, `Payload`), so the
half of the tick behind the bridge's rings is a cell the ledger already
has. What differs is the wire. Every sample is an `SSFSample` inside an
`SSFSpan`, the way upstream's `trace` client reports:

  * `trace_spans` trace spans of `services` services (service, name,
    trace / span / parent ids, both timestamps, `span_tags` span-level
    tags, `error` on `error_share`), each with one timer sample and one
    sample of another kind; `indicator_spans` of them are indicator
    spans, which the tier turns into one more timer sample each, named
    by `indicator_span_timer_name` and tagged `error:<bool>,service:<s>`;
    `status_spans` of them carry one STATUS sample besides, so the whole
    span goes through the tier's fallback;
  * the rest in `trace/metrics.ReportBatch` spans: nothing but
    `batch_samples` samples.

Timer samples carry a time unit, spread evenly over the mix's `units`
(a test holds them to `ssf/__init__.py:TIME_UNITS`); a sample's
`sample_rate` is 1 or left out (0 on the wire, which reads as 1);
`duplicate_tag_every`-th samples carry a stale first entry for a tag
key they then give again (map semantics: the last wins); counters with
odd names are `scope: GLOBAL`, gauges with odd names `scope: LOCAL`.

The frames are `version byte | u32le length | protobuf`, encoded here
by hand (`varint`, `ld`): nothing of `veneur_tpu` is imported, not even
its generated protobuf module. `reference` is numpy over the samples:
upstream's extraction rules as `sinks/ssfmetrics.py` states them. The
same seed gives the same bytes.
"""

from __future__ import annotations

import struct
import time

import numpy as np

from perfbench.generators import dogstatsd_lines as lines

MAKES = "ssf_frames"

COUNTER, GAUGE, HISTOGRAM, SET, STATUS = range(5)
SCOPE_LOCAL, SCOPE_GLOBAL = 1, 2
# unit -> milliseconds, as native/vtpu_ingest.cpp:time_unit_ms scales
UNIT_MS = {"ns": 1e-6, "\u00b5s": 1e-3, "us": 1e-3, "ms": 1.0, "s": 1e3}
CHUNK_BYTES = 1 << 16       # what the driver hands to one sendall
EPOCH_NS = 1_700_000_000_000_000_000
CHECK = "smoke.check."      # + a STATUS span's number: its service check


# ---------------------------------------------------------- protobuf, by hand

def varint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def ld(field: int, payload: bytes) -> bytes:
    """A length-delimited field (strings, submessages, map entries)."""
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def vi(field: int, n: int) -> bytes:
    return varint(field << 3) + varint(n)


def f32(field: int, packed: bytes) -> bytes:
    """A fixed32 field from four packed little-endian bytes."""
    return varint(field << 3 | 5) + packed


def tag_entry(field: int, key: str, value: str) -> bytes:
    return ld(field, ld(1, key.encode()) + ld(2, value.encode()))


def sample_head(metric: int, name: str, tags, scope: int = 0) -> bytes:
    """The part of an SSFSample its key fixes: metric, name, tags, scope."""
    out = (vi(1, metric) if metric else b"") + ld(2, name.encode())
    out += b"".join(tag_entry(8, k, v) for k, v in tags)
    return out + (vi(10, scope) if scope else b"")


def frame(span: bytes) -> bytes:
    return b"\x00" + struct.pack("<I", len(span)) + span


# -------------------------------------------------------------------- a tick

def indicator_key(name: str, service: str, error: bool) -> str:
    """The reference's name of an indicator timer's series: the tier
    emits one name under many tag sets, so the tags are part of it (the
    driver names the sinks' rows the same way)."""
    return f"{name}|error:{'true' if error else 'false'},service:{service}"


class Spans:
    """One tick's samples (a `dogstatsd_lines.Payload`) cut into spans,
    with what the reference needs that the samples alone do not say:
    each timer sample's unit and each indicator span's key and length."""

    def __init__(self, cfg: dict, mix: dict, p, seed: int):
        self.p, self.mix = p, mix
        sp = mix["spans"]
        rng = np.random.default_rng([seed, p.index, 7])
        units = mix["timers"]["units"]
        n_t = p.t_key.size
        self.t_unit = rng.permutation(np.arange(n_t) % len(units))
        # the wire's f32 in the sample's unit, and what the tier makes
        # of it: f32 -> f64, times the unit's factor, stored as f32
        factor = np.array([UNIT_MS[u] for u in units])[self.t_unit]
        self.t_wire = (p.t_milli / 1000.0 / factor).astype(np.float32)
        self.t_ms64 = self.t_wire.astype(np.float64) * factor
        # the name the deployment promises, whatever a control has set
        # in the program's place
        self.timer_name = cfg["guarantees"]["indicator_timer"]

        n = sp["trace_spans"]
        self.n_trace = n
        self.service = rng.integers(0, sp["services"], n)
        self.error = rng.random(n) < sp["error_share"]
        self.indicator = np.zeros(n, bool)
        self.indicator[rng.choice(n, sp["indicator_spans"], False)] = True
        self.dur_ns = np.maximum(1, np.rint(rng.lognormal(
            np.log(100.0), 0.1, n) * 1e6)).astype(np.int64)
        self.start_ns = EPOCH_NS + p.index * 10**10 + np.arange(n) * 1000
        self.ids = rng.integers(1, 2**62, (n, 3))
        tag_len = rng.integers(sp["span_tag_bytes"][0],
                               sp["span_tag_bytes"][1] + 1,
                               (sp["services"], sp["span_tags"]))
        self.span_tags = [b"".join(
            tag_entry(9, f"t{j}", "v" * int(ln - 2))
            for j, ln in enumerate(row)) for row in tag_len.tolist()]

        # the other kinds in one seeded order: (kind, index in its arrays)
        kinds = np.concatenate([np.full(p.s_key.size, SET),
                                np.full(p.c_key.size, COUNTER),
                                np.full(p.g_key.size, GAUGE)])
        index = np.concatenate([np.arange(p.s_key.size),
                                np.arange(p.c_key.size),
                                np.arange(p.g_key.size)])
        order = rng.permutation(kinds.size)
        self.o_kind, self.o_index = kinds[order], index[order]
        if n > min(n_t, self.o_kind.size):
            raise ValueError(f"{n} trace spans want a timer sample and "
                             f"another each: the mix has {n_t} and "
                             f"{self.o_kind.size}")
        # a STATUS span goes through the fallback, whose samples land in
        # no defined order against the fast path's: never beside a gauge
        ok = np.nonzero(self.o_kind[:n] != GAUGE)[0]
        self.status = np.zeros(n, bool)
        self.status[rng.choice(ok, sp["status_spans"], False)] = True
        self.rng = rng
        # where in the tick each gauge sample arrives (`frames` fills
        # it): a gauge answers its last write
        self.g_arrival = np.zeros(p.g_key.size, np.int64)

    # -- bytes

    def _timer_samples(self) -> list:
        p, mix = self.p, self.mix
        every = mix["duplicate_tag_every"]
        units = [ld(9, u.encode()) for u in mix["timers"]["units"]]
        rate1 = f32(7, struct.pack("<f", 1.0))
        heads, stale = {}, {}
        for k in p.touched["timers"].tolist():
            env, shard = lines.timer_tags(k).split(",")
            tags = [tuple(env.split(":")), tuple(shard.split(":"))]
            heads[k] = sample_head(HISTOGRAM, lines.timer_name(k), tags)
            stale[k] = sample_head(HISTOGRAM, lines.timer_name(k),
                                   [("env", "stale")] + tags)
        wire = self.t_wire.astype("<f4").tobytes()
        out = []
        for i, (k, u) in enumerate(zip(p.t_key.tolist(),
                                       self.t_unit.tolist())):
            head = stale[k] if i % every == 0 else heads[k]
            out.append(head + f32(3, wire[4 * i:4 * i + 4]) + units[u]
                       + (rate1 if i & 1 else b""))
        return out

    def _other_samples(self) -> list:
        p = self.p
        env = [("env", "prod")]
        s_head = {k: sample_head(SET, f"smoke.set.s{k:04d}", env)
                  for k in p.touched["sets"].tolist()}
        c_head = {k: sample_head(COUNTER, f"smoke.counter.c{k:04d}", env,
                                 SCOPE_GLOBAL if k % 2 else 0)
                  for k in p.touched["counters"].tolist()}
        g_head = {k: sample_head(GAUGE, f"smoke.gauge.g{k:04d}",
                                 env + [("kind", "gauge")],
                                 SCOPE_LOCAL if k % 2 else 0)
                  for k in p.touched["gauges"].tolist()}
        s_key, s_member = p.s_key.tolist(), p.s_member.tolist()
        c_key = p.c_key.tolist()
        c_val = p.c_val.astype("<f4").tobytes()
        g_key = p.g_key.tolist()
        g_val = (p.g_milli / 1000.0).astype("<f4").tobytes()
        out = []
        for kind, i in zip(self.o_kind.tolist(), self.o_index.tolist()):
            if kind == SET:
                out.append(s_head[s_key[i]]
                           + ld(5, f"m{s_member[i]}".encode()))
            elif kind == COUNTER:
                out.append(c_head[c_key[i]]
                           + f32(3, c_val[4 * i:4 * i + 4]))
            else:
                out.append(g_head[g_key[i]]
                           + f32(3, g_val[4 * i:4 * i + 4]))
        return out

    def frames(self) -> tuple:
        """(frames in send order, samples the tier must stage for each):
        batch spans spread evenly through the trace spans."""
        sp, n = self.mix["spans"], self.n_trace
        timers = [ld(12, s) for s in self._timer_samples()]
        others = [ld(12, s) for s in self._other_samples()]
        names = [ld(8, f"svc-{s:03d}".encode()) + ld(11, f"op.{s % 17}"
                                                     .encode())
                 for s in range(sp["services"])]
        # one service check a STATUS span, each under a name of its own:
        # the local's sink holds a check as a row, and counts them so
        checks = iter(ld(12, vi(1, STATUS) + ld(2, f"{CHECK}{j:03d}".encode())
                         + ld(5, b"ok") + tag_entry(8, "env", "prod"))
                      for j in range(sp["status_spans"]))
        trace, staged = [], []
        start, end = self.start_ns.tolist(), (self.start_ns
                                              + self.dur_ns).tolist()
        for i, (svc, err, ind, st, ids) in enumerate(zip(
                self.service.tolist(), self.error.tolist(),
                self.indicator.tolist(), self.status.tolist(),
                self.ids.tolist())):
            trace.append(frame(
                vi(1, 1) + vi(2, ids[0]) + vi(3, ids[1]) + vi(4, ids[2])
                + vi(5, start[i]) + vi(6, end[i])
                + (vi(7, 1) if err else b"") + names[svc]
                + self.span_tags[svc] + (vi(10, 1) if ind else b"")
                + timers[i] + others[i] + (next(checks) if st else b"")))
            staged.append(2 + ind)
        rest = timers[n:] + others[n:]
        shuffle = self.rng.permutation(len(rest))
        rest = [rest[j] for j in shuffle.tolist()]
        size = sp["batch_samples"]
        batches = [frame(b"".join(rest[a:a + size]))
                   for a in range(0, len(rest), size)]
        b_staged = [min(size, len(rest) - a)
                    for a in range(0, len(rest), size)]
        step = max(1, len(trace) // max(1, len(batches)))
        out, counts, bi = [], [], 0
        at_trace, at_batch = [], []          # each frame's place in `out`
        for a in range(0, len(trace), step):
            at_trace += range(len(out), len(out) + len(trace[a:a + step]))
            out += trace[a:a + step]
            counts += staged[a:a + step]
            if bi < len(batches):
                at_batch.append(len(out))
                out.append(batches[bi])
                counts.append(b_staged[bi])
                bi += 1
        at_batch += range(len(out), len(out) + len(batches) - bi)
        out += batches[bi:]
        counts += b_staged[bi:]
        # a gauge sample's arrival: its frame's place, then its place in
        # the frame (the other sample `o` sits in trace span `o` or, past
        # them, where the shuffle put it among the batches)
        place = np.empty(len(rest), np.int64)
        place[shuffle] = np.arange(len(rest))
        o = np.nonzero(self.o_kind == GAUGE)[0]
        q = place[np.maximum(o - n, 0) + len(timers) - n]
        self.g_arrival[self.o_index[o]] = np.where(
            o < n, np.array(at_trace)[np.minimum(o, n - 1)] * size,
            np.array(at_batch)[q // size] * size + q % size)
        return out, counts


def chunks(frames: list, staged: list) -> list:
    """[(bytes for one sendall, samples staged once they are read)]."""
    out, cur, size, n = [], [], 0, 0
    for fr, c in zip(frames, staged):
        if cur and size + len(fr) > CHUNK_BYTES:
            out.append((b"".join(cur), n))
            cur, size, n = [], 0, 0
        cur.append(fr)
        size += len(fr)
        n += c
    if cur:
        out.append((b"".join(cur), n))
    return out


def reference(s: Spans, percentiles) -> dict:
    """What both tiers must answer: `dogstatsd_lines.reference` over the
    same samples, the timers' values as the wire's unit and f32 make
    them, and the indicator spans' timers beside them."""
    p = s.p
    ref = lines.reference(p, percentiles)
    val32 = s.t_ms64.astype(np.float32)
    order = np.argsort(p.t_key, kind="stable")
    keys, starts = np.unique(p.t_key[order], return_index=True)
    ends = np.append(starts[1:], order.size)
    v32, v64 = val32[order], s.t_ms64[order]
    mins = np.minimum.reduceat(v32, starts).tolist()
    maxs = np.maximum.reduceat(v32, starts).tolist()
    hot = set(p.touched["hot"].tolist())
    for k, a, b, lo, hi in zip(keys.tolist(), starts.tolist(),
                               ends.tolist(), mins, maxs):
        ref["timer"][lines.timer_name(k)] = (float(b - a), lo, hi)
        if k in hot:
            ref["hot"][lines.timer_name(k)] = np.quantile(v64[a:b],
                                                         percentiles)
    for k in np.unique(p.g_key).tolist():
        mine = np.nonzero(p.g_key == k)[0]
        last = p.g_milli[mine[np.argmax(s.g_arrival[mine])]]
        ref["gauge"][f"smoke.gauge.g{k:04d}"] = float(
            np.float32(last / 1000.0))
    ms32 = (s.dur_ns / 1e6).astype(np.float32)
    series: dict = {}
    for svc, err, v in zip(s.service[s.indicator].tolist(),
                           s.error[s.indicator].tolist(),
                           ms32[s.indicator].tolist()):
        series.setdefault(indicator_key(
            s.timer_name, f"svc-{svc:03d}", err), []).append(v)
    for key, vals in series.items():
        ref["timer"][key] = (float(len(vals)), min(vals), max(vals))
    return ref


def build(cfg: dict, mix: dict, seed: int, log) -> tuple:
    touched = lines.touched_keys(mix, cfg["population"], seed)
    payloads, ref_s = [], 0.0
    for k in range(mix["distinct_ticks"]):
        p = lines.Payload(mix, touched, seed, k + 1)
        s = Spans(cfg, mix, p, seed)
        frames, staged = s.frames()
        r0 = time.monotonic()
        ref = reference(s, cfg["percentiles"])
        ref_s += time.monotonic() - r0
        n_ind = int(s.indicator.sum())
        payloads.append({
            "chunks": chunks(frames, staged), "n_frames": len(frames),
            "n_bytes": sum(len(f) for f in frames),
            # what the tier must stage: every embedded sample and one
            # timer an indicator span
            "n_lines": int(sum(staged)), "indicator_lines": n_ind,
            "timer_lines": int(p.t_key.size) + n_ind,
            # the STATUS spans go through the fallback whole: their
            # samples never pass the bridge's `samples`
            "fallback_spans": int(s.status.sum()),
            "fallback_lines": int((2 + s.indicator)[s.status].sum()),
            "fallback_indicator_lines": int(s.indicator[s.status].sum()),
            "check_prefix": CHECK, "ref": ref})
        log(f"payload {k + 1}: {payloads[-1]['n_lines']} samples in "
            f"{len(frames)} frames, {payloads[-1]['n_bytes']} bytes, "
            f"{len(payloads[-1]['chunks'])} chunks")
    return payloads, ref_s

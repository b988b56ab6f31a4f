"""Multi-chip tests on the virtual 8-device CPU mesh: sharded ingest +
collective flush-merge must reproduce single-digest results
(BASELINE config 5: multi-chip hash-shard with ICI merge)."""

import jax
import numpy as np
import pytest

from veneur_tpu.parallel.mesh import MeshEngine, make_mesh

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def make_engine(n_dp=2, n_shard=4, **kw):
    mesh = make_mesh(n_dp, n_shard)
    defaults = dict(histogram_slots=64, counter_slots=32, gauge_slots=32,
                    set_slots=8, buf_size=64, hll_precision=12,
                    percentiles=(0.5, 0.9))
    defaults.update(kw)
    return MeshEngine(mesh, **defaults)


def _empty_batches(eng, n=64):
    shape = (eng.D, eng.S * n)
    z = lambda dt, fill: np.full(shape, fill, dt)
    return dict(
        h_slots=z(np.int32, -1), h_vals=z(np.float32, 0),
        h_wts=z(np.float32, 0), c_slots=z(np.int32, -1),
        c_vals=z(np.float32, 0), c_wts=z(np.float32, 0),
        g_slots=z(np.int32, -1), g_vals=z(np.float32, 0),
        g_seqs=z(np.int32, 0), s_slots=z(np.int32, -1),
        s_idx=z(np.int32, 0), s_rho=z(np.uint8, 0))


def test_dp_merge_reproduces_union():
    """Two dp replicas each ingest half the samples for the same global
    slots; the merged flush must match numpy over the union."""
    eng = make_engine(n_dp=2, n_shard=4)
    rng = np.random.default_rng(0)
    n = 64
    K, S = eng.histogram_slots, eng.S
    per_shard = K // S

    data = {}  # global slot -> all values
    batches = _empty_batches(eng, n)
    for d in range(2):
        for s in range(S):
            base = s * n
            gslots = rng.integers(0, K, n)
            owned = gslots[gslots // per_shard == s][: n]
            vals = rng.normal(loc=gslots[gslots // per_shard == s][: n]
                              .astype(np.float32), scale=0.1)[: n]
            k = len(owned)
            batches["h_slots"][d, base:base + k] = owned % per_shard
            batches["h_vals"][d, base:base + k] = vals
            batches["h_wts"][d, base:base + k] = 1.0
            for g, v in zip(owned, vals):
                data.setdefault(int(g), []).append(float(v))

    eng.ingest(**batches)
    out = eng.flush_merged()
    assert out["quantiles"].shape == (K, 2)
    for g, vals in data.items():
        assert out["agg"]["count"][g] == pytest.approx(len(vals))
        assert out["agg"]["min"][g] == pytest.approx(min(vals), rel=1e-5)
        assert out["agg"]["max"][g] == pytest.approx(max(vals), rel=1e-5)
        med = out["quantiles"][g][0]
        assert med == pytest.approx(np.median(vals), abs=0.3)


def test_counters_psum_and_gauges_lww():
    eng = make_engine(n_dp=2, n_shard=4)
    b = _empty_batches(eng)
    # counter global slot 5 (shard 0 owns 0..7): +3 on dp0, +4 on dp1
    b["c_slots"][0, 0] = 5
    b["c_vals"][0, 0] = 3.0
    b["c_wts"][0, 0] = 1.0
    b["c_slots"][1, 0] = 5
    b["c_vals"][1, 0] = 4.0
    b["c_wts"][1, 0] = 1.0
    # gauge slot 9 (shard 1 owns 8..15): dp0 writes seq 1, dp1 seq 7
    b["g_slots"][0, eng.S * 0 + 1] = 9 % 8  # local id within shard...
    eng2 = eng  # clarity
    # write gauge into the segment of its owning shard (shard 1)
    n = b["g_slots"].shape[1] // eng.S
    b["g_slots"][0, n + 0] = 9 - 8
    b["g_vals"][0, n + 0] = 111.0
    b["g_seqs"][0, n + 0] = 1
    b["g_slots"][1, n + 0] = 9 - 8
    b["g_vals"][1, n + 0] = 222.0
    b["g_seqs"][1, n + 0] = 7
    eng.ingest(**b)
    out = eng.flush_merged()
    assert out["counters"][5] == pytest.approx(7.0)
    assert out["gauge_val"][9] == 222.0
    assert out["gauge_seq"][9] == 7

    # flush reset: everything zero afterwards
    out2 = eng.flush_merged()
    assert out2["counters"][5] == 0.0
    assert out2["agg"]["count"].sum() == 0.0


def test_hll_union_across_dp():
    from veneur_tpu.ops import hll as hll_mod
    from veneur_tpu.utils import hashing
    eng = make_engine(n_dp=2, n_shard=4, set_slots=8)
    b = _empty_batches(eng, n=512)
    per_shard = eng.set_slots // eng.S  # 2 per shard
    # global set slot 3 -> shard 1, local 1; dp rows get overlapping members
    n = b["s_slots"].shape[1] // eng.S
    members = {0: [f"m-{i}" for i in range(300)],
               1: [f"m-{i}" for i in range(150, 450)]}
    for d, ms in members.items():
        hashes = np.array([hashing.set_member_hash(m) for m in ms],
                          np.uint64)
        idx, rho = hll_mod.host_hash_to_updates(hashes, eng.hll_precision)
        base = 1 * n  # shard 1 segment
        k = len(ms)
        b["s_slots"][d, base:base + k] = 3 - per_shard * 1  # local id 1
        b["s_idx"][d, base:base + k] = idx
        b["s_rho"][d, base:base + k] = rho
    eng.ingest(**b)
    out = eng.flush_merged()
    assert out["set_est"][3] == pytest.approx(450, rel=0.1)


def test_route_batch_helper():
    eng = make_engine(n_dp=1, n_shard=4)
    slots = np.array([0, 17, 33, 49, 1, -1], np.int32)
    vals = np.array([1., 2., 3., 4., 5., 6.], np.float32)
    per_shard = eng.histogram_slots // eng.S  # 16
    rs, rv, overflow = eng.route_batch(slots, vals,
                                       slots_per_shard=per_shard,
                                       n_per_segment=4)
    assert rs.shape == (1, 16)
    assert overflow == 0
    # shard 0 segment holds slots 0 and 1 (local ids 0, 1)
    seg0 = rs[0, :4]
    assert set(seg0[seg0 >= 0].tolist()) == {0, 1}
    # shard 1 segment holds 17 -> local 1
    assert 1 in rs[0, 4:8].tolist()
    # shard 3: 49 -> local 1
    assert 1 in rs[0, 12:16].tolist()


def test_hot_slot_through_the_sharded_ingest(monkeypatch):
    """The second caller of _add_batch_impl: a hot slot (five buffer
    depths in one batch, among slots that fit) through the mesh
    engine's shard_map ingest, its overflow compressed row by row (a
    work set of 4 rows against shards of 16), held to the one-chip
    bank's answer: exact fields exact, quantiles the same."""
    from veneur_tpu.ops import tdigest
    monkeypatch.setattr(tdigest, "_OVERFLOW_ROWS", (4,))
    eng = make_engine(n_dp=1, n_shard=4)
    rng = np.random.default_rng(27)
    K, S, B = eng.histogram_slots, eng.S, eng.buf_size
    per_shard = K // S
    counts = {37: 5 * B + 9, 38: B // 2, 3: B + 1, 60: 7}
    n = sum(counts.values())
    batches = _empty_batches(eng, n)
    one = tdigest.init(K, buf_size=B)
    data = {}
    for g, c in counts.items():
        vals = rng.lognormal(np.log(100.0), 0.1, c).astype(np.float32)
        data[g] = vals
        shard, at = g // per_shard, sum(
            len(v) for h, v in data.items()
            if h != g and h // per_shard == g // per_shard)
        base = shard * n + at
        batches["h_slots"][0, base:base + c] = g % per_shard
        batches["h_vals"][0, base:base + c] = vals
        batches["h_wts"][0, base:base + c] = 1.0
        one = tdigest.add_batch(one, np.full(c, g, np.int32), vals,
                                np.ones(c, np.float32),
                                overflow_rows=(4,))
    eng.ingest(**batches)
    out = eng.flush_merged()
    one = tdigest.compress(one, compression=100.0)
    want_q = np.asarray(tdigest.quantile(
        one, np.asarray([0.5, 0.9], np.float32)))
    for g, vals in data.items():
        assert out["agg"]["count"][g] == len(vals)
        assert out["agg"]["min"][g] == vals.min()
        assert out["agg"]["max"][g] == vals.max()
        assert out["agg"]["sum"][g] == pytest.approx(
            float(vals.astype(np.float64).sum()), rel=1e-6)
        np.testing.assert_allclose(out["quantiles"][g], want_q[g],
                                   rtol=1e-6)
        np.testing.assert_allclose(
            out["quantiles"][g], np.quantile(vals, [0.5, 0.9]), rtol=0.02)

"""Each chip of the mesh global owns a slice of the key space (ISSUE 36):
`ShardedKeyInterner` mints a new key's row on shard
`metric_digest(key) % shards`, behind `KeyInterner`'s interface. The
one-chip engine's table hands out slots in the order it always did.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from veneur_tpu.ingest.parser import GLOBAL_ONLY, MIXED_SCOPE, MetricKey
from veneur_tpu.models.pipeline import AggregationEngine, EngineConfig
from veneur_tpu.models.worker import FOLD_SLOT, KeyInterner
from veneur_tpu.parallel.interner import ShardedKeyInterner
from veneur_tpu.utils.hashing import metric_digest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _key(i, kind="timer"):
    return MetricKey(f"svc.lat.k{i:05d}", kind, f"env:prod,shard:{i % 7}")


def _home(key, shards=4):
    return metric_digest(key.name, key.type, key.joined_tags) % shards


_PLACE = """
import json, sys
from veneur_tpu.ingest.parser import MetricKey
from veneur_tpu.parallel.interner import ShardedKeyInterner
ki = ShardedKeyInterner(64, 4)
print(json.dumps([hash("svc.lat"), [ki.lookup(MetricKey(
    f"svc.lat.k{i:05d}", "timer", f"env:prod,shard:{i % 7}"), 0)
    for i in range(40)]]))
"""


def test_placement_follows_the_digest_not_pythonhashseed():
    """Two interpreters whose `hash()` differ place the same keys on the
    same rows, and each on its digest's shard."""
    outs = []
    for seed in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu"}
        p = subprocess.run([sys.executable, "-c", _PLACE], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    (hash1, slots1), (hash2, slots2) = outs
    assert hash1 != hash2              # the seeds did reach the processes
    assert slots1 == slots2
    assert [s // 16 for s in slots1] == [_home(_key(i)) for i in range(40)]


def test_four_thousand_keys_fill_each_shard_within_a_tenth_of_the_mean():
    ki = ShardedKeyInterner(8192, 4)
    slots = [ki.lookup(_key(i), MIXED_SCOPE) for i in range(4000)]
    assert len(set(slots)) == 4000 and ki.spills == 0
    rows = ki.shard_rows()
    assert sum(rows) == 4000 == len(ki)
    assert all(abs(n - 1000) <= 100 for n in rows), rows
    # a row is its shard's lowest free one, and the table knows it back
    for i in (0, 1, 3999):
        assert ki.key_of(slots[i]) == _key(i)
        assert slots[i] // 2048 == _home(_key(i))
        assert ki.lookup(_key(i), MIXED_SCOPE) == slots[i]   # the map hit
    by_shard = [sorted(s for s in slots if s // 2048 == sh)
                for sh in range(4)]
    assert all(got == list(range(sh * 2048, sh * 2048 + len(got)))
               for sh, got in enumerate(by_shard))


def test_a_full_shard_spills_counts_it_and_drops_nothing_while_a_row_is_free():
    ki = ShardedKeyInterner(16, 4)          # four rows a shard
    keys = [_key(i) for i in range(400)]
    home0 = [k for k in keys if _home(k) == 0][:6]
    slots = [ki.lookup(k, MIXED_SCOPE) for k in home0]
    assert [s // 4 for s in slots[:4]] == [0] * 4
    # the fifth and sixth go to a shard with most rows free
    assert all(s >= 4 for s in slots[4:]) and ki.spills == 2
    assert ki.dropped_no_slot == 0
    # fill every row: none is refused until the last is taken
    more = [k for k in keys if k not in home0][:10]
    got = [ki.lookup(k, MIXED_SCOPE) for k in more]
    assert min(got) >= 0 and len(set(slots + got)) == 16
    assert ki.shard_rows() == [4, 4, 4, 4] and ki.dropped_no_slot == 0
    assert ki.lookup(_key(999), MIXED_SCOPE) == -1
    assert ki.dropped_no_slot == 1 and len(ki) == 16


def test_eviction_returns_a_row_to_its_own_shard():
    ki = ShardedKeyInterner(64, 4, idle_ttl_intervals=1)
    slots = {i: ki.lookup(_key(i), MIXED_SCOPE) for i in range(24)}
    before = ki.shard_rows()
    ki.advance_interval()
    for i in range(12):                      # keep the first half alive
        ki.lookup(_key(i), MIXED_SCOPE)
    ki.advance_interval()                    # the second half is idle
    gone = [slots[i] for i in range(12, 24)]
    assert all(ki.key_of(s) is None for s in gone) and len(ki) == 12
    want = list(before)
    for s in gone:
        want[s // 16] -= 1
    assert ki.shard_rows() == want
    # a returned row is minted again on its own shard
    fresh = next(k for k in (_key(i) for i in range(100, 200))
                 if _home(k) == gone[0] // 16)
    assert ki.lookup(fresh, MIXED_SCOPE) // 16 == gone[0] // 16


def test_restore_rebuilds_a_free_list_a_shard():
    ki = ShardedKeyInterner(32, 4)
    for i in range(20):
        ki.lookup(_key(i), GLOBAL_ONLY)
    back = ShardedKeyInterner(32, 4)
    back.restore(ki.interval, ki.snapshot_entries())
    assert back.shard_rows() == ki.shard_rows()
    assert [back.lookup(_key(i), GLOBAL_ONLY) for i in range(20, 30)] \
        == [ki.lookup(_key(i), GLOBAL_ONLY) for i in range(20, 30)]


def test_the_admission_hook_is_asked_on_the_allocation_path_only():
    class Budget:
        def __init__(self):
            self.asked, self.released = [], []

        def admit_key(self, key):
            self.asked.append(key)
            return None if key.name.endswith("9") else key

        def release_key(self, key):
            self.released.append(key)

    ki = ShardedKeyInterner(8, 4)
    ki.admission = adm = Budget()
    s0 = ki.lookup(_key(0), MIXED_SCOPE)
    assert ki.lookup(_key(0), MIXED_SCOPE) == s0 and adm.asked == [_key(0)]
    assert ki.lookup(_key(9), MIXED_SCOPE) == FOLD_SLOT and len(ki) == 1
    for i in range(1, 8):
        ki.lookup(_key(i), MIXED_SCOPE)
    assert ki.lookup(_key(10), MIXED_SCOPE) == -1    # admitted, no row
    assert adm.released == [_key(10)]


def test_the_one_chip_table_hands_out_slots_in_the_order_it_did():
    ki = KeyInterner(8, idle_ttl_intervals=1)
    assert [ki.lookup(_key(i), MIXED_SCOPE) for i in range(5)] \
        == [0, 1, 2, 3, 4]
    ki.advance_interval()
    ki.lookup(_key(4), MIXED_SCOPE)
    ki.advance_interval()                    # 0..3 evicted, in map order
    assert [ki.lookup(_key(i), MIXED_SCOPE) for i in range(10, 14)] \
        == [3, 2, 1, 0]
    eng = AggregationEngine(EngineConfig(histogram_slots=256))
    assert type(eng.histo_keys) is KeyInterner
    assert [eng.histo_keys.lookup(_key(i), MIXED_SCOPE)
            for i in range(3)] == [0, 1, 2]


@pytest.fixture(scope="module")
def mesh_engine():
    from veneur_tpu.parallel.engine import MeshAggregationEngine
    return MeshAggregationEngine(
        EngineConfig(histogram_slots=510, counter_slots=64, gauge_slots=64,
                     set_slots=32, batch_size=256, hll_precision=10,
                     percentiles=(0.5,), is_global=True), n_devices=4)


def test_the_mesh_engine_places_every_bank_by_digest(mesh_engine):
    eng = mesh_engine
    for ki, slots in ((eng.histo_keys, 512), (eng.counter_keys, 64),
                      (eng.gauge_keys, 64), (eng.set_keys, 32)):
        # the table spans the bank as the MeshEngine padded it
        assert type(ki) is ShardedKeyInterner
        assert (ki.capacity, ki.shards) == (slots, 4)
    rng = np.random.default_rng(36)
    keys = [_key(i) for i in range(200)]
    for k in keys:
        eng.import_histogram(k, rng.lognormal(4.6, 0.1, 4), np.ones(4),
                             1.0, 200.0, 400.0, 4.0)
    eng.import_counter(_key(0, "counter"), 5.0)
    homes = np.bincount([_home(k) for k in keys], minlength=4).tolist()
    res = eng.flush(timestamp=36)
    info = eng._last_flush_info
    assert info["mesh_shard_rows"] == homes and min(homes) > 30
    assert info["mesh_interner_spills"] == 0
    assert res.stats["flush_path"]["mesh_shard_rows"] == homes
    # where the rows sit on the device is where the table put them:
    # every key answers with its own count
    counts = {m.name: m.value for m in res.metrics
              if m.name.endswith(".count")}
    assert len(counts) == 200 and set(counts.values()) == {4.0}
    # an idle flush notes the rows still held, and no landing
    eng.flush(timestamp=37)
    info = eng._last_flush_info
    assert info["mesh_shard_rows"] == homes
    assert info["mesh_import_points"] == info["mesh_import_dispatches"] == 0

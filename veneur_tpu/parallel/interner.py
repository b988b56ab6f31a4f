"""ShardedKeyInterner: the mesh engine's key table, one free list a shard.

The mesh engine keeps GLOBAL slot ids and `MeshEngine.route_batch` places
slot g on shard g // rows_per_shard, so which shard a key lives on is
decided where its slot is minted. `KeyInterner` hands slots out in order
0, 1, 2, ...: every key of a bank that is not nearly full lands on shard
0 and the other chips hold empty rows. This table places a new key on
shard `metric_digest(key) % shards` — the FNV-1a digest over name, type
and tags that the cluster tier already routes by
(`Server._group_imports`, the reference's `Workers[Digest %
len(Workers)]`), never Python's `hash()`: placement must not follow
PYTHONHASHSEED — and takes a free row of that shard, slot = shard x
rows_per_shard + row. The digest is taken on the allocation path only;
a key that holds a slot pays the map hit it pays in `KeyInterner`.

A key is never dropped while any shard has a free row: a full shard
spills to the shard with most rows free, counted in `spills`.
"""

from __future__ import annotations

from ..ingest.parser import MetricKey
from ..models.worker import KeyInterner
from ..utils.hashing import metric_digest


class ShardedKeyInterner(KeyInterner):

    def __init__(self, capacity: int, shards: int,
                 idle_ttl_intervals: int = 16):
        if capacity % shards:
            raise ValueError("capacity must divide into the shards")
        self.shards = shards
        self.rows_per_shard = capacity // shards
        super().__init__(capacity, idle_ttl_intervals)
        # keys placed off their digest's shard because it was full
        self.spills = 0

    def _take_slot(self, key: MetricKey) -> int:
        free = self._free[
            metric_digest(key.name, key.type, key.joined_tags)
            % self.shards]
        if not free:
            free = max(self._free, key=len)
            if not free:
                return -1
            self.spills += 1
        return free.pop()

    def _release_slot(self, slot: int):
        self._free[slot // self.rows_per_shard].append(slot)

    def _reset_free(self, used):
        n = self.rows_per_shard
        self._free = [[g for g in range((s + 1) * n - 1, s * n - 1, -1)
                       if g not in used] for s in range(self.shards)]

    def shard_rows(self) -> list:
        """Rows of each shard that hold a key."""
        return [self.rows_per_shard - len(f) for f in self._free]

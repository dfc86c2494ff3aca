"""Shard routing: murmur3(id) % N virtual shards.

Parity: /root/reference/src/dbnode/sharding/shardset.go:76,158-175.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from m3_tpu.utils.hash import murmur3_32, murmur3_32_batch
from m3_tpu.utils.instrument import default_registry

DEFAULT_SEED = 42

# below this, the vectorized path's setup (buffer join + pad) costs more
# than it saves over the scalar loop
_BATCH_MIN = 64


@dataclass(frozen=True)
class ShardSet:
    n_shards: int
    shard_ids: tuple[int, ...] = field(default=None)
    seed: int = DEFAULT_SEED

    def __post_init__(self):
        if self.shard_ids is None:
            object.__setattr__(self, "shard_ids", tuple(range(self.n_shards)))

    def lookup(self, series_id: bytes) -> int:
        return murmur3_32(series_id, self.seed) % self.n_shards

    def lookup_many(self, series_ids: list[bytes]) -> list[int]:
        """Batched series->shard routing: one vectorized murmur3 pass.
        The COLD path only: served reads and writes route through
        ShardRoutes, which comes here with the ids it has not seen.
        Run on every read it was two fifths of a v5e host's on-CPU
        samples and 1,058 of the 1,174 ms of a 2,000-series query
        (PERF.md, PR 26/27)."""
        if len(series_ids) < _BATCH_MIN:
            return [self.lookup(sid) for sid in series_ids]
        return (murmur3_32_batch(series_ids, self.seed)
                % self.n_shards).tolist()

    def owns(self, shard: int) -> bool:
        return shard in self.shard_ids


class ShardRoutes:
    """A namespace's remembered series -> shard routes. The shard of an
    id is a pure function of (id, seed, n_shards), so it is hashed the
    first time the id is routed and probed from a dict after that.

    Two generations bound the map: an id found only in the older one
    is copied into the younger, and rotate() (Namespace.expire, each
    time the retention cutoff moves on a block) drops the older, so
    the map holds the ids routed within the last two block periods,
    with no size option. Keyed by (seed, n_shards), not by the
    ShardSet: a placement change publishes a new ShardSet whose
    routing is the same.

    No lock: a dict probe and a dict store are atomic under the
    interpreter, two threads filling the same id store the same value,
    and a rotation replaces one reference (a thread that read the
    generations before it keeps two valid dicts)."""

    __slots__ = ("_gens",)

    def __init__(self):
        self._gens = (None, {}, {})  # (seed, n_shards), young, old

    def __len__(self) -> int:
        _, young, old = self._gens
        return len(young.keys() | old.keys())

    def __contains__(self, series_id: bytes) -> bool:
        _, young, old = self._gens
        return series_id in young or series_id in old

    def rotate(self) -> None:
        key, young, _ = self._gens
        self._gens = (key, {}, young)

    def lookup_many(self, shard_set: ShardSet,
                    series_ids: list[bytes]) -> list[int]:
        """== shard_set.lookup_many(series_ids); hashes unseen ids only."""
        gens = self._gens
        key = (shard_set.seed, shard_set.n_shards)
        if gens[0] != key:
            gens = self._gens = (key, {}, {})
        _, young, old = gens
        routed = list(map(young.get, series_ids))
        unseen: dict[bytes, None] = {}
        if None in routed:
            absent = [i for i, s in enumerate(routed) if s is None]
            for i in absent:
                sid = series_ids[i]
                shard = old.get(sid)
                if shard is None:
                    unseen[sid] = None
                else:
                    young[sid] = shard
            if unseen:
                ids = list(unseen)
                young.update(zip(ids, shard_set.lookup_many(ids)))
            for i in absent:
                routed[i] = young[series_ids[i]]
        default_registry().record_many((), (
            ("storage.shard_route.hit", (), len(series_ids) - len(unseen)),
            ("storage.shard_route.miss", (), len(unseen))))
        return routed

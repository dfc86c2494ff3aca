"""Murmur3 32-bit hash (public algorithm, Austin Appleby) for shard routing.

The reference routes series to virtual shards with murmur3(id) % n_shards
(/root/reference/src/dbnode/sharding/shardset.go:158-175 and
/root/reference/src/aggregator/sharding/hash.go:37-89); we keep the same
function family so placements stay comparable.
"""

from __future__ import annotations

_C1 = 0xCC9E2D51
_C2 = 0x1B873593
_M32 = 0xFFFFFFFF


def _rotl32(x: int, r: int) -> int:
    return ((x << r) | (x >> (32 - r))) & _M32


def murmur3_32_batch(ids: list[bytes], seed: int = 0):
    """Vectorized murmur3_32 over a batch of byte strings -> uint32
    ndarray, bit-identical to ``[murmur3_32(x) for x in ids]``.

    The per-id Python loop collapses to one buffer concatenation; the
    hash itself runs as numpy ops over a padded [n, max_len] byte matrix
    with per-row active masks (rows shorter than the current block keep
    their prior h). Arithmetic is uint64 masked back to 32 bits after
    every op so the wraparound semantics match the scalar path exactly.
    Faster than the scalar loop from about 64 ids, but not cheap: it
    allocates n x max_len x 8 bytes and makes ~20 numpy calls per
    4-byte block (on a sandbox CPU 2.9 s for 120,000 171-byte ids;
    2,000 such ids 9 ms alone, 104 ms under eight threads). Run on
    every read it was two fifths of a v5e host's on-CPU samples
    (PERF.md, PR 26), so routing remembers its answers
    (storage/sharding.py ShardRoutes) and only unseen ids come here."""
    import numpy as np

    n = len(ids)
    if n == 0:
        return np.empty(0, np.uint32)
    lengths = np.fromiter((len(s) for s in ids), np.int64, count=n)
    max_len = int(lengths.max())
    m32 = np.uint64(_M32)
    h = np.full(n, seed & _M32, np.uint64)
    if max_len:
        flat = np.frombuffer(b"".join(ids), np.uint8)
        offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]])
        idx = offsets[:, None] + np.arange(max_len)
        padded = np.where(np.arange(max_len) < lengths[:, None],
                          flat[np.minimum(idx, len(flat) - 1)],
                          0).astype(np.uint64)
        nblocks = lengths // 4
        c1, c2 = np.uint64(_C1), np.uint64(_C2)
        for i in range(max_len // 4):
            k = (padded[:, 4 * i]
                 | padded[:, 4 * i + 1] << np.uint64(8)
                 | padded[:, 4 * i + 2] << np.uint64(16)
                 | padded[:, 4 * i + 3] << np.uint64(24))
            k = k * c1 & m32
            k = (k << np.uint64(15) | k >> np.uint64(17)) & m32
            k = k * c2 & m32
            hh = h ^ k
            hh = (hh << np.uint64(13) | hh >> np.uint64(19)) & m32
            hh = (hh * np.uint64(5) + np.uint64(0xE6546B64)) & m32
            h = np.where(i < nblocks, hh, h)
        tail_len = lengths - nblocks * 4
        if tail_len.any():
            base = nblocks * 4
            cols = np.minimum(base[:, None] + np.arange(3), max_len - 1)
            tail = np.take_along_axis(padded, cols, axis=1)
            k = np.zeros(n, np.uint64)
            k = np.where(tail_len >= 3, k ^ tail[:, 2] << np.uint64(16), k)
            k = np.where(tail_len >= 2, k ^ tail[:, 1] << np.uint64(8), k)
            k ^= np.where(tail_len >= 1, tail[:, 0], 0)
            k = k * c1 & m32
            k = (k << np.uint64(15) | k >> np.uint64(17)) & m32
            k = k * c2 & m32
            h = np.where(tail_len >= 1, h ^ k, h)
    h ^= lengths.astype(np.uint64)
    h ^= h >> np.uint64(16)
    h = h * np.uint64(0x85EBCA6B) & m32
    h ^= h >> np.uint64(13)
    h = h * np.uint64(0xC2B2AE35) & m32
    h ^= h >> np.uint64(16)
    return h.astype(np.uint32)


def murmur3_32(data: bytes, seed: int = 0) -> int:
    h = seed & _M32
    n = len(data)
    nblocks = n // 4
    for i in range(nblocks):
        k = int.from_bytes(data[i * 4 : i * 4 + 4], "little")
        k = (k * _C1) & _M32
        k = _rotl32(k, 15)
        k = (k * _C2) & _M32
        h ^= k
        h = _rotl32(h, 13)
        h = (h * 5 + 0xE6546B64) & _M32
    tail = data[nblocks * 4 :]
    k = 0
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = (k * _C1) & _M32
        k = _rotl32(k, 15)
        k = (k * _C2) & _M32
        h ^= k
    h ^= n
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    h ^= h >> 16
    return h

"""Shard: columnar buffer + immutable fileset volumes for one virtual shard.

Role parity with the reference dbShard (write/read orchestration, flush,
retention expiry — /root/reference/src/dbnode/storage/shard.go:869-896,
1085); the per-series object tree is replaced by the columnar ShardBuffer
and batched device encodes (SURVEY.md §7.2).
"""

from __future__ import annotations

import threading

import numpy as np

from m3_tpu.storage.buffer import ShardBuffer, merge_dedup
from m3_tpu.storage.fileset import FilesetReader, FilesetWriter, list_filesets
from m3_tpu.storage.options import DatabaseOptions, NamespaceOptions
from m3_tpu.utils import faults


class _FilesetReadGroup:
    """One (shard, block, volume) group of a pipelined batched read.

    ``gather()`` is the worker-safe leg: cache probe + columnar stream
    gather off the immutable reader — nothing thread-local, nothing
    mutated outside the lock-guarded BlockCache. ``consume()`` runs on
    the calling thread in submission order: querystats accounting and
    the per-series parts append of the cache hits; what the cache
    missed it hands back for the read's ONE batched decode
    (``decode_misses``) — every thread-local seam (query record,
    decode-rung counters, trace spans) stays on the query's own thread."""

    __slots__ = ("shard", "block_start", "reader", "series_ids", "parts")

    def __init__(self, shard: "Shard", block_start: int, reader,
                 series_ids: list[bytes], parts: list[list]):
        self.shard = shard
        self.block_start = block_start
        self.reader = reader
        self.series_ids = series_ids
        self.parts = parts

    def _cache(self):
        """The block cache, or None when it cannot serve (capacity 0):
        a disabled cache still charges key construction + a locked probe
        per group on the serial path — the pipelined gather skips the
        whole bookkeeping (misses-by-construction carry no information)."""
        cache = self.shard.cache
        if cache is None or getattr(cache, "capacity", 1) <= 0:
            return None
        return cache

    def gather(self):
        shard = self.shard
        cache = self._cache()
        if cache is None:
            return None, None, range(len(self.series_ids)), \
                self.reader.gather_many(self.series_ids)
        keys = [(shard.namespace, shard.shard_id, self.block_start,
                 self.reader.volume, sid) for sid in self.series_ids]
        cached = cache.get_many(keys)
        miss_idx = [i for i, hit in enumerate(cached) if hit is None]
        streams = (self.reader.gather_many(
            [self.series_ids[i] for i in miss_idx]) if miss_idx else [])
        return keys, cached, miss_idx, streams

    def consume(self, payload) -> tuple:
        """Account the probe, land the hits, and return the group's
        ``decode_misses`` entry."""
        keys, cached, miss_idx, streams = payload
        parts = self.parts
        count_block_cache(len(self.series_ids) - len(miss_idx),
                          len(miss_idx))
        if cached is not None:
            for i, hit in enumerate(cached):
                if hit is not None and len(hit[0]):
                    parts[i].append(hit)
        return keys, miss_idx, streams, parts


def count_block_cache(hits: int, misses: int) -> None:
    """One (shard, block) group's probe of the decoded-block cache, one
    lookup a series: on the query's own record (``?explain``) and on the
    process's counters ``storage_block_cache_hit`` / ``_miss``."""
    from m3_tpu.utils import querystats
    from m3_tpu.utils.instrument import default_registry

    querystats.record(cache_hits=hits, cache_misses=misses)
    default_registry().record_many((), (
        ("storage.block_cache.hit", (), hits),
        ("storage.block_cache.miss", (), misses)))


def decode_misses(pending: list[tuple], opts: NamespaceOptions,
                  cache) -> None:
    """The decode leg of a batched read: ONE ``decode_streams_batch``
    call over the streams every group of ``pending`` missed in the block
    cache (the time unit and the int mode are the namespace's, so groups
    of different shards and blocks share a call), scattered back in
    group order: one cache fill (negative results too), then the
    per-series parts append. An entry is ``(keys, miss_idx, streams,
    parts)``: the group's cache keys (None: nothing is cached), the rows
    it missed, their streams, and the parts lists its rows append to."""
    from m3_tpu.encoding.m3tsz import hostpath

    pending = [p for p in pending if p[1]]
    if not pending:
        return
    decoded = hostpath.decode_streams_batch(
        [s for p in pending for s in p[2]], opts.write_time_unit,
        opts.int_optimized, groups=sum(1 for p in pending if any(p[2])))
    fills = []
    lo = 0
    for keys, miss_idx, _, parts in pending:
        for i, r in zip(miss_idx, decoded[lo : lo + len(miss_idx)]):
            if keys is not None:
                fills.append((keys[i], r))
            if len(r[0]):
                parts[i].append(r)
        lo += len(miss_idx)
    if fills:
        cache.put_many(fills)


def run_read_groups(groups: "list[_FilesetReadGroup]"):
    """Drive a read's groups through the executor seam: gathers on the
    pool in submission order, each group's hits landed on this thread as
    its gather arrives, and when the last has landed one decode over
    what all of them missed. Every group's parts are complete on
    return; so is the pass's ``pipeline.StageStats`` (None: no
    groups)."""
    from m3_tpu.storage import pipeline
    from m3_tpu.utils import querystats

    if not groups:
        return None
    pending: list[tuple] = []

    def consume(g, payload):
        pending.append(g.consume(payload))
        if g is groups[-1]:
            # one namespace, so one time unit, int mode and block cache
            decode_misses(pending, g.shard.opts, g.shard.cache)

    stats = pipeline.run_stages(groups, lambda g: g.gather(), consume)
    # overlap accounting reaches ?explain=analyze from every entry (the
    # namespace's flattened schedule, its limit-chunked loop and direct
    # shard callers)
    querystats.record_pipeline(stats.items, stats.wall_s, stats.stages)
    return stats


class Shard:
    def __init__(
        self,
        shard_id: int,
        namespace: str,
        opts: NamespaceOptions,
        db_opts: DatabaseOptions,
        fs_root: str,
    ):
        self.shard_id = shard_id
        self.namespace = namespace
        self.opts = opts
        self.db_opts = db_opts
        self.fs_root = fs_root
        self.buffer = ShardBuffer(opts.retention.block_size_ns)
        self._filesets: dict[int, FilesetReader] = {}  # block_start -> reader
        # readers swapped out by flush/expire/repair: concurrent reads may
        # still hold them from their list() snapshot, so closing immediately
        # would fail those reads on a dead mmap. Each is closed only after
        # RETIRE_GRACE_S (far longer than any single-series decode), and the
        # list is lock-guarded because repair retires from RPC threads while
        # the tick thread drains.
        self._retired: list[tuple[float, FilesetReader]] = []
        self._retired_lock = threading.Lock()
        # serializes volume assignment + fileset swap between the tick
        # thread's flush/expire and repair running on RPC threads: without
        # it two maintenance passes can both write volume v+1 for the same
        # block (interleaved files, shared cache key for divergent data)
        self._maint_lock = threading.RLock()
        self.bootstrapped = False
        self.cache = None  # decoded-block LRU, set by the owning Database
        # fileset write pacing, set by the owning Database (runtime options)
        self.persist_limiter = None
        # per-window write sequence vs last-snapshotted sequence: lets the
        # snapshot loop skip windows with no new writes (dirty tracking);
        # guarded by _seq_lock (lost increments would mark dirty windows
        # clean and skip their snapshots)
        self._write_seq: dict[int, int] = {}
        self._snap_seq: dict[int, int] = {}
        self._seq_lock = threading.Lock()
        # warm/cold write split (reference series/buffer.go:77-147
        # WriteType + storage/coldflush.go): a write landing in a block
        # that already has a flushed volume is COLD — it must not drag
        # that block back into the warm flush path (which would decode+
        # merge+rewrite the volume inside the latency-sensitive warm
        # pass). Cold-dirty blocks flush separately as version-bumped
        # volumes.
        self.warm_writes = 0
        self.cold_writes = 0
        # monotone data-content versions: bumped by every mutation a read
        # could observe (writes, flush/volume swaps, bootstrap, repair,
        # expiry), after the data is in place. `data_version` counts them
        # all (the standing engine's per-shard invalidation reads it);
        # each bump also lands in exactly one of `_block_versions[bs]`
        # (a change to what a read of block `bs` returns) or
        # `_structural_version` (what cannot be laid to one block:
        # expiry). The device-resident hot tier (storage/hottier.py) keys
        # prepared query slabs on `data_version_in` of the blocks a fetch's
        # range touches — unchanged means an identical fetch, so warm
        # device pages serve without a rebuild, and a write to the head
        # block leaves sealed history warm. No counter is ever reset or
        # pruned (one int a block, as `_write_seq`): a sum of them never
        # returns to a value it had. Guarded by _seq_lock (a lost bump
        # would serve stale pages, the one unacceptable failure mode).
        self.data_version = 0
        self._block_versions: dict[int, int] = {}
        self._structural_version = 0

    # -- write --

    def write(self, series_id: bytes, t_ns: int, value_bits: int,
              encoded_tags: bytes = b"") -> int:
        bs = self.opts.retention.block_start(t_ns)
        idx = self.buffer.write(series_id, t_ns, value_bits, encoded_tags)
        if bs in self._filesets:
            self.cold_writes += 1
        else:
            self.warm_writes += 1
        # seq bumps AFTER the point is in the buffer: a snapshot racing in
        # between re-snapshots next pass instead of marking the window
        # clean without the point
        with self._seq_lock:
            self._write_seq[bs] = self._write_seq.get(bs, 0) + 1
            self._bump_locked(bs)
        return idx

    def write_many(self, series_ids: list[bytes], times: np.ndarray,
                   vbits: np.ndarray, tags_list: list[bytes]) -> None:
        """Bulk write: one buffer lock for the whole shard-local batch
        (ShardBuffer.write_many) and one warm/cold + write-seq update per
        touched window instead of per point."""
        self.buffer.write_many(series_ids, times, vbits, tags_list)
        bs = times - (times % self.opts.retention.block_size_ns)
        uniq, counts = np.unique(bs, return_counts=True)
        for w, c in zip(uniq.tolist(), counts.tolist()):
            if w in self._filesets:
                self.cold_writes += c
            else:
                self.warm_writes += c
        # seq bumps AFTER the points are in the buffer: a snapshot racing
        # in between re-snapshots next pass instead of marking the window
        # clean without the points (same rule as the per-point write)
        with self._seq_lock:
            for w, c in zip(uniq.tolist(), counts.tolist()):
                self._write_seq[w] = self._write_seq.get(w, 0) + c
                self._bump_locked(w)

    def _bump_locked(self, block_start: int | None) -> None:
        self.data_version += 1
        if block_start is None:
            self._structural_version += 1
        else:
            self._block_versions[block_start] = \
                self._block_versions.get(block_start, 0) + 1

    def bump_data_version(self, block_start: int | None = None) -> None:
        """Mark readable content changed: of the block at `block_start`
        (a volume swap from flush/bootstrap/repair), or with None of the
        shard as a whole (expiry, anything not laid to one block) —
        hot-tier entries keyed on the old version of a range that holds
        the block, or with None of any range, stop matching."""
        with self._seq_lock:
            self._bump_locked(block_start)

    def data_version_in(self, first_block: int, last_block: int) -> int:
        """Content version of the blocks `first_block`..`last_block`
        (block starts, inclusive): the structural counter plus each
        block's own, a never-touched block reading 0. Every term is
        monotone and none is ever reset or dropped, so two different
        readable contents of those blocks never share a value. Lock-free
        like a read of `data_version`: the key is sampled before the
        read, so a racing bump can only make an entry stale."""
        # one int a block ever touched (a handful within retention), so
        # this walk is cheap for any range, a query from time 0 too;
        # list() because writers add blocks meanwhile
        blocks = sum(v for bs, v in list(self._block_versions.items())
                     if first_block <= bs <= last_block)
        return self._structural_version + blocks

    def write_seq(self, block_start: int) -> int:
        return self._write_seq.get(block_start, 0)

    def snapshotted_seq(self, block_start: int) -> int | None:
        return self._snap_seq.get(block_start)

    def mark_snapshotted(self, block_start: int, seq: int) -> None:
        self._snap_seq[block_start] = seq

    # -- read --

    def read(self, series_id: bytes, start_ns: int, end_ns: int):
        """Merged (times, value_bits) from flushed volumes + buffer."""
        from m3_tpu.encoding.m3tsz import hostpath

        parts_t, parts_v = [], []
        # snapshot: the tick thread swaps fileset volumes concurrently
        for bs, reader in list(self._filesets.items()):
            if bs + reader.block_size_ns <= start_ns or bs >= end_ns:
                continue
            # volume in the key: a read racing a flush may put() a decode of
            # the OLD volume after the swap; under a versioned key that
            # stale entry lands where no future read (which uses the new
            # reader's volume) will find it
            key = (self.namespace, self.shard_id, bs, reader.volume, series_id)
            cached = self.cache.get(key) if self.cache is not None else None
            if cached is not None:
                ct, cv = cached
                if len(ct):
                    parts_t.append(ct)
                    parts_v.append(cv)
                continue
            stream = reader.read(series_id)
            ct = np.empty(0, np.int64)
            cv = np.empty(0, np.uint64)
            if stream:
                ct, cv = hostpath.decode_stream(
                    stream, self.opts.write_time_unit,
                    self.opts.int_optimized,
                )
            if self.cache is not None:  # negative results cached too
                self.cache.put(key, (ct, cv))
            if len(ct):
                parts_t.append(ct)
                parts_v.append(cv)
        bt, bv = self.buffer.read(series_id, start_ns, end_ns)
        if len(bt):
            parts_t.append(bt)
            parts_v.append(bv)
        if not parts_t:
            return np.empty(0, np.int64), np.empty(0, np.uint64)
        # buffer parts were appended last, so last-write-wins keeps them
        return merge_dedup(
            np.concatenate(parts_t), np.concatenate(parts_v), start_ns, end_ns
        )

    def read_many(self, series_ids: list[bytes], start_ns: int, end_ns: int
                  ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Batched read: one fetch per (block, volume) group and ONE
        decode dispatch for the call instead of one per series. Cache
        hits are served without entering the batch; the misses of every
        group fill the decoded-block LRU in one pass. Identical results
        to per-series read() — parts accumulate in the same
        (filesets-then-buffer) order so last-write-wins resolution is
        unchanged.

        Default path is the PIPELINED dataflow (storage/pipeline.py):
        per-(block, volume) gather legs run on the executor pool up to
        depth-N ahead of the caller landing their cache hits, the
        decode follows the last gather (run_read_groups), and the gather
        itself is the reader's cached columnar row index instead of a
        per-query merge-join walk. ``M3_TPU_PIPELINE=0`` pins the serial
        body — the seed behavior for bisection: one fetch and one decode
        per group."""
        from m3_tpu.storage import pipeline

        if pipeline.active():
            parts: list[list] = [[] for _ in series_ids]
            run_read_groups(
                self.plan_read_groups(series_ids, start_ns, end_ns, parts))
            t, v, offs = self.finish_read_many(series_ids, parts,
                                               start_ns, end_ns)
            return [(t[offs[i]:offs[i + 1]], v[offs[i]:offs[i + 1]])
                    for i in range(len(series_ids))]
        return self._read_many_serial(series_ids, start_ns, end_ns)

    def plan_read_groups(self, series_ids: list[bytes], start_ns: int,
                         end_ns: int, parts: list[list]
                         ) -> "list[_FilesetReadGroup]":
        """One `_FilesetReadGroup` per (block, volume) reader overlapping
        the range — the schedulable unit of the pipelined read path.
        Planning snapshots `_filesets` on the calling thread (the tick
        thread swaps volumes concurrently; the retire grace keeps any
        captured reader alive for the whole read)."""
        groups = []
        for bs, reader in list(self._filesets.items()):
            if bs + reader.block_size_ns <= start_ns or bs >= end_ns:
                continue
            groups.append(_FilesetReadGroup(self, bs, reader, series_ids,
                                            parts))
        return groups

    def finish_read_many(self, series_ids: list[bytes], parts: list[list],
                         start_ns: int, end_ns: int):
        """Batched RAGGED finalize (ROADMAP #3): the per-series
        ``np.concatenate`` + ``merge_dedup`` pass in finish_read —
        profiled at ~15% of the sparse read path — becomes ONE buffer
        CSR gather (`ShardBuffer.read_many_csr`), one preallocated fill
        and one vectorized merge over every series at once
        (`ops.ragged.assemble_rows`).  Returns the (times, vbits,
        offsets) CSR aligned to `series_ids`; per-row results are
        element-identical to finish_read (same part order, same
        keep-last dedup, same range filter)."""
        from m3_tpu.ops import ragged

        if len(set(series_ids)) != len(series_ids):
            # duplicate ids: the CSR position map is one row per id —
            # take the per-series finalize (correctness over speed
            # on a shape no production caller emits)
            pairs = [self.finish_read(sid, list(pl), start_ns, end_ns)
                     for sid, pl in zip(series_ids, parts)]
            return ragged.pairs_to_csr(pairs)
        self.append_buffered(series_ids, parts, start_ns, end_ns)
        return ragged.assemble_rows(parts, start_ns, end_ns)

    def append_buffered(self, series_ids: list[bytes], parts: list[list],
                        start_ns: int, end_ns: int) -> None:
        """The buffer leg of a batched finalize: each series' buffered
        points in the windows the range touches, appended LAST to its
        parts list (one `ShardBuffer.read_many_csr`, not range-filtered):
        last-write-wins keeps them, exactly the finish_read append order
        (parts lists are owned by the read — appending in place, like
        finish_read). Requires unique ids."""
        bt, bv, boffs = self.buffer.read_many_csr(series_ids, start_ns,
                                                  end_ns)
        if len(bt):
            for pl, a, b in zip(parts, boffs[:-1].tolist(),
                                boffs[1:].tolist()):
                if b > a:
                    pl.append((bt[a:b], bv[a:b]))

    def finish_read(self, series_id: bytes, parts: list, start_ns: int,
                    end_ns: int) -> tuple[np.ndarray, np.ndarray]:
        """Per-series finalize: buffer leg LAST (last-write-wins keeps
        buffered points, same as the serial path), then one merge."""
        bt, bv = self.buffer.read(series_id, start_ns, end_ns)
        if len(bt):
            parts.append((bt, bv))
        if not parts:
            return np.empty(0, np.int64), np.empty(0, np.uint64)
        return merge_dedup(
            np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]),
            start_ns, end_ns,
        )

    def _read_many_serial(self, series_ids: list[bytes], start_ns: int,
                          end_ns: int) -> list[tuple[np.ndarray, np.ndarray]]:
        n = len(series_ids)
        parts: list[list] = [[] for _ in range(n)]
        # snapshot: the tick thread swaps fileset volumes concurrently
        for bs, reader in list(self._filesets.items()):
            if bs + reader.block_size_ns <= start_ns or bs >= end_ns:
                continue
            keys = [(self.namespace, self.shard_id, bs, reader.volume, sid)
                    for sid in series_ids]
            cached = (self.cache.get_many(keys) if self.cache is not None
                      else [None] * n)
            miss_idx: list[int] = []
            for i, hit in enumerate(cached):
                if hit is not None:
                    ct, cv = hit
                    if len(ct):
                        parts[i].append((ct, cv))
                    continue
                miss_idx.append(i)
            count_block_cache(n - len(miss_idx), len(miss_idx))
            if not miss_idx:
                continue
            # batched fetch: one merge-join walk of the volume's index for
            # the whole miss set, then one batched decode of its streams
            streams = reader.read_many([series_ids[i] for i in miss_idx])
            decode_misses(
                [(keys if self.cache is not None else None, miss_idx,
                  streams, parts)], self.opts, self.cache)
        out = []
        for i, sid in enumerate(series_ids):
            bt, bv = self.buffer.read(sid, start_ns, end_ns)
            if len(bt):  # buffer last, so last-write-wins keeps it
                parts[i].append((bt, bv))
            if not parts[i]:
                out.append((np.empty(0, np.int64), np.empty(0, np.uint64)))
                continue
            out.append(merge_dedup(
                np.concatenate([p[0] for p in parts[i]]),
                np.concatenate([p[1] for p in parts[i]]),
                start_ns, end_ns,
            ))
        return out

    def series_ids(self) -> set[bytes]:
        ids = set(self.buffer.series_ids)
        for reader in self._filesets.values():
            ids.update(reader.series_ids())
        return ids

    # -- snapshots --

    def snapshot(self, block_start: int, snapshot_root: str,
                 snapshot_id: int) -> bool:
        """Write the window's CURRENT buffer contents as a snapshot fileset
        under snapshot_root (volume = snapshot_id, monotonic). The buffer
        keeps the data — snapshots exist so commitlogs can retire early and
        restarts recover in-flight blocks without replaying the whole WAL
        (the flush-model snapshot role, reference storage/README.md,
        persist/fs/snapshot_metadata_{read,write}.go)."""
        from m3_tpu.encoding.m3tsz import hostpath
        from m3_tpu.utils.instrument import default_registry

        faults.check("shard.snapshot", shard=self.shard_id,
                     block_start=block_start)
        # ragged seal + length-bucketed encode: no [B, max_T] rectangle
        sealed = self.buffer.seal_csr(block_start, drop=False)
        if sealed is None:
            return False
        ids = [self.buffer.series_ids[i] for i in sealed.series_indices]
        tags = [self.buffer.series_tags[i] for i in sealed.series_indices]
        try:
            streams = hostpath.encode_blocks_ragged(
                sealed.times, sealed.value_bits, sealed.offsets,
                np.full(sealed.n_series, block_start, np.int64),
                self.opts.write_time_unit, self.opts.int_optimized,
            )
        except OverflowError:
            return False
        # what the encoder took in and wrote: 16 B a sample in, the
        # streams' bytes out
        scope = default_registry().root_scope("storage")
        scope.counter("snapshot_samples", len(sealed.times))
        scope.counter("snapshot_bytes", sum(map(len, streams)))
        writer = FilesetWriter(
            snapshot_root, self.namespace, self.shard_id, block_start,
            self.opts.retention.block_size_ns, snapshot_id,
        )
        for sid, stags, stream in zip(ids, tags, streams):
            self._pace_persist(len(stream))
            writer.write_series(sid, stags, stream)
        writer.close()
        return True

    # -- flush --

    def flushable_block_starts(self, now_ns: int) -> list[int]:
        """WARM flush candidates: buffered windows past buffer_past that
        have no volume yet. Windows with an existing volume are cold-dirty
        (see cold_dirty_block_starts) — keeping them out of here is what
        keeps warm flush latency flat under backfill."""
        r = self.opts.retention
        out = []
        for bs in self.buffer.block_starts():
            if bs + r.block_size_ns + r.buffer_past_ns <= now_ns \
                    and bs not in self._filesets:
                out.append(bs)
        return out

    def cold_dirty_block_starts(self) -> list[int]:
        """Blocks holding buffered COLD writes: a flushed volume exists and
        the buffer has new points for the window (reference
        coldFlushReuseableResources.dirtySeriesToWrite role)."""
        return sorted(bs for bs in self.buffer.block_starts()
                      if bs in self._filesets)

    def cold_flush(self, block_start: int) -> bool:
        """Merge the window's buffered cold writes with its current volume
        into a version-bumped volume (reference storage/coldflush.go +
        persist/fs/merger.go). Runs on the cold cadence so backfill never
        blocks the warm pass."""
        from m3_tpu.utils import trace

        with trace.span(trace.SHARD_FLUSH, shard=self.shard_id,
                        block_start=block_start, cold=True):
            return self._flush_traced(block_start)

    def flush(self, block_start: int) -> bool:
        """Seal the window, batch-encode on device, write a fileset volume.

        If a volume already exists for the window (cold-path reflush), its
        series are decoded, merged with the buffer's, and a higher volume is
        written — the role of the reference's fs merger (persist/fs/merger.go).
        """
        from m3_tpu.utils import trace

        with trace.span(trace.SHARD_FLUSH, shard=self.shard_id,
                        block_start=block_start):
            return self._flush_traced(block_start)

    def _pace_persist(self, n_bytes: int) -> None:
        if self.persist_limiter is not None:
            self.persist_limiter.acquire(n_bytes)

    # grace before a swapped-out reader is really closed; class attribute so
    # tests can shrink it
    RETIRE_GRACE_S = 30.0

    def _retire(self, reader: FilesetReader) -> None:
        import time

        with self._retired_lock:
            self._retired.append((time.monotonic(), reader))

    def _drain_retired(self) -> None:
        """Close readers retired at least RETIRE_GRACE_S ago; any read that
        captured them in its snapshot has finished by now. A drained
        reader whose volume was SUPERSEDED (flush/cold-flush/repair wrote
        a higher volume for the block) also has its files deleted here —
        without this every repair cycle leaks a full volume on disk until
        retention expiry (continuous repair would leak without bound).
        Readers retired by expire() already had their files deleted; the
        per-volume remove is a no-op for them."""
        import time

        now = time.monotonic()
        doomed = []
        with self._retired_lock:
            keep = []
            for ts, r in self._retired:
                (doomed if now - ts >= self.RETIRE_GRACE_S else keep).append((ts, r))
            self._retired = keep
        for _, r in doomed:
            r.close()
            cur = self._filesets.get(r.block_start)
            if cur is not None and cur.volume > r.volume:
                self._delete_volume_files(r.block_start, r.volume)

    def _flush_traced(self, block_start: int) -> bool:
        from m3_tpu.utils.instrument import default_registry

        with default_registry().root_scope("db").histogram(
                "shard_flush_seconds"):
            with self._maint_lock:
                return self._flush_locked(block_start)

    def _flush_locked(self, block_start: int) -> bool:
        """Ragged seal (no [B, max_T] scatter), per-series merge against
        the previous volume on CSR slices, length-bucketed ragged encode,
        then the durability tail."""
        from m3_tpu.encoding.m3tsz import hostpath
        from m3_tpu.ops import ragged

        # the kill-mid-flush seam: a crash anywhere before the checkpoint
        # lands must leave the buffer window intact (seal below never
        # drops) and the old volume readable
        faults.check("shard.flush", shard=self.shard_id,
                     block_start=block_start)
        self._drain_retired()

        # Seal WITHOUT dropping: the buffer window is the only copy until the
        # fileset volume is durably on disk; a failed flush must leave it
        # intact (and with it the retired-commitlog coverage check).
        sealed = self.buffer.seal_csr(block_start, drop=False)
        if sealed is None:
            return False
        ids = [self.buffer.series_ids[i] for i in sealed.series_indices]
        tags = [self.buffer.series_tags[i] for i in sealed.series_indices]
        times, vbits, offsets = (sealed.times, sealed.value_bits,
                                 sealed.offsets)

        prev = self._filesets.get(block_start)
        volume = 0
        extra: list[tuple[bytes, bytes, bytes]] = []  # untouched old series
        replaced: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        if prev is not None:
            volume = prev.volume + 1
            new_ids = {sid: k for k, sid in enumerate(ids)}
            for i in range(prev.n_series):
                sid, stags, stream = prev.read_at(i)
                if sid not in new_ids:
                    extra.append((sid, stags, stream))
                    continue
                k = new_ids[sid]
                old_t, old_v = hostpath.decode_stream(
                    stream, self.opts.write_time_unit,
                    self.opts.int_optimized,
                )
                a, b = int(offsets[k]), int(offsets[k + 1])
                replaced[k] = merge_dedup(
                    np.concatenate([old_t, times[a:b]]),
                    np.concatenate([old_v, vbits[a:b]]),
                )
        if replaced:
            rows = []
            for k in range(sealed.n_series):
                hit = replaced.get(k)
                if hit is None:
                    a, b = int(offsets[k]), int(offsets[k + 1])
                    hit = (times[a:b], vbits[a:b])
                rows.append([hit])
            times, vbits, offsets = ragged.assemble_rows(rows)

        try:
            streams = hostpath.encode_blocks_ragged(
                times, vbits, offsets,
                np.full(sealed.n_series, block_start, np.int64),
                self.opts.write_time_unit, self.opts.int_optimized,
            )
        except OverflowError:
            raise RuntimeError(
                f"flush encode overflow: shard={self.shard_id} bs={block_start}"
            )

        # the durability tail: paced volume write + checkpoint, reader
        # retire/swap, cache invalidation, and only THEN dropping exactly
        # the sealed prefix — concurrent appends after the seal copy stay
        # buffered
        writer = FilesetWriter(
            self.fs_root, self.namespace, self.shard_id, block_start,
            self.opts.retention.block_size_ns, volume,
        )
        for sid, stags, stream in zip(ids, tags, streams):
            self._pace_persist(len(stream))
            writer.write_series(sid, stags, stream)
        for sid, stags, stream in extra:
            self._pace_persist(len(stream))
            writer.write_series(sid, stags, stream)
        writer.close()

        if prev is not None:
            self._retire(prev)
        self._filesets[block_start] = FilesetReader(
            self.fs_root, self.namespace, self.shard_id, block_start, volume
        )
        if self.cache is not None:  # cached decodes are for the old volume
            self.cache.invalidate_block(self.namespace, self.shard_id,
                                        block_start)
        self.buffer.drop_window_prefix(block_start, sealed.raw_count)
        self.bump_data_version(block_start)
        return True

    # -- bootstrap --

    def bootstrap_from_fs(self, now_ns: int | None = None) -> int:
        """Load complete volumes; expired ones are skipped (never deleted
        here — open() must not be destructive; the explicit tick()/expire
        path reclaims disk)."""
        r = self.opts.retention
        cutoff = None
        if now_ns is not None:
            cutoff = r.block_start(now_ns - r.retention_ns)
        n = 0
        for block_start, volume in list_filesets(self.fs_root, self.namespace, self.shard_id):
            if cutoff is not None and block_start < cutoff:
                continue
            try:
                reader = FilesetReader(
                    self.fs_root, self.namespace, self.shard_id, block_start, volume
                )
            except (FileNotFoundError, ValueError):
                continue  # incomplete or corrupt volume: ignore
            # same guard as flush/seal: re-bootstrap (live tenant
            # namespace creation, PR 7) can race a maintenance pass
            with self._maint_lock:
                self._filesets[block_start] = reader
            # a volume written under another block size reaches past the
            # one block its start names: every range's version moves
            self.bump_data_version(
                block_start if reader.block_size_ns == r.block_size_ns
                else None)
            n += 1
        return n

    # -- maintenance --

    def _delete_fileset_files(self, block_start: int) -> None:
        # every volume of the block (retention expiry)
        self._delete_matching(f"fileset-{block_start}-*.db")

    def _delete_volume_files(self, block_start: int, volume: int) -> None:
        """ONE superseded volume's files (repair/flush wrote a higher
        volume; this one is no longer the bootstrap choice). Readers
        still holding it keep reading through their open fds/mmaps."""
        self._delete_matching(f"fileset-{block_start}-{volume}-*.db")

    def _delete_matching(self, pattern: str) -> None:
        import glob
        import os

        d = os.path.join(self.fs_root, self.namespace, str(self.shard_id))
        # *.db.tmp: leftovers of a flush killed mid-write (atomic writers
        # never expose them under final names; reclaim them here)
        full = os.path.join(d, pattern)
        paths = glob.glob(full) + glob.glob(full + ".tmp")
        # checkpoint first so a crash mid-delete leaves an "incomplete"
        # (ignored) volume rather than a corrupt-looking one
        paths = sorted(paths, key=lambda p: "checkpoint" not in p)
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass

    def expire(self, now_ns: int) -> int:
        """Drop + delete block volumes and buffered windows past retention.

        Also reclaims on-disk volumes that were skipped at bootstrap as
        already-expired (they were never loaded into _filesets)."""
        r = self.opts.retention
        cutoff = r.block_start(now_ns - r.retention_ns)
        dropped = 0
        with self._maint_lock:
            self._drain_retired()
            for bs in list(self._filesets):
                if bs < cutoff:
                    # retire, don't close: a concurrent read may hold this
                    # reader; its open fds/mmaps keep the unlinked files
                    # readable until the grace period closes it
                    self._retire(self._filesets[bs])
                    del self._filesets[bs]
                    self._delete_fileset_files(bs)
                    dropped += 1
            with self._retired_lock:
                in_grace = {(r.block_start, r.volume)
                            for _ts, r in self._retired}
            for bs, vol in list_filesets(self.fs_root, self.namespace,
                                         self.shard_id, all_volumes=True):
                if bs < cutoff and bs not in self._filesets:
                    self._delete_fileset_files(bs)
                    continue
                # superseded-volume sweep: a complete volume below the one
                # currently serving the block is a crash leftover (killed
                # between the swap and the retired-reader cleanup) — only
                # the max volume is ever bootstrapped, so reclaim the rest.
                # Volumes still inside the retire grace are skipped (their
                # readers drain first; the next expire pass gets them).
                cur = self._filesets.get(bs)
                if cur is not None and vol < cur.volume \
                        and (bs, vol) not in in_grace:
                    self._delete_volume_files(bs, vol)
        expired = self.buffer.expire_before(cutoff)
        if dropped or expired:
            self.bump_data_version()
        return dropped

    def close(self) -> None:
        """Release every fileset reader (current and retired, grace
        ignored): after close the shard serves no reads, so the deferred-
        close protection no longer applies and holding the fds/mmaps would
        leak them for the rest of the process."""
        with self._maint_lock:
            with self._retired_lock:
                retired, self._retired = self._retired, []
            for _, reader in retired:
                reader.close()
            for reader in self._filesets.values():
                reader.close()
            self._filesets.clear()

    @property
    def flushed_block_starts(self) -> list[int]:
        return sorted(self._filesets)

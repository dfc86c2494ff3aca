"""Namespace index: time-partitioned reverse index over segments.

Role parity with the reference nsIndex
(/root/reference/src/dbnode/storage/index.go:623,1482,1524): index blocks
partitioned by block start; inserts land in a mutable segment per block and
compact into sealed immutable segments (the mutable->FST compaction,
storage/index/mutable_segments.go); queries evaluate over every block
overlapping the time range and dedupe series; aggregate queries surface
field names/values for label APIs.
"""

from __future__ import annotations

import re
import threading

from m3_tpu.index import packed
from m3_tpu.index.executor import search
from m3_tpu.index.query import Query
from m3_tpu.index.segment import MutableSegment, Segment


class IndexBlock:
    def __init__(self) -> None:
        # inserts arrive on request threads while the tick thread compacts
        # and persists: without the lock a doc inserted between
        # compaction's snapshot of the mutable segment and its swap for a
        # fresh one vanished from the index for good (`_seen` kept saying
        # the block had it). Found by chip_smoke's read-back, PR 21.
        self._lock = threading.Lock()
        self.mutable = MutableSegment()
        self.sealed: list[Segment] = []
        self._cache: Segment | None = None  # sealed view of `mutable`
        self._cache_docs = 0
        self.persisted_docs = -1  # doc count at last persist (persist.py)
        # series ids present anywhere in this block (mutable OR sealed),
        # built lazily: the insert pre-filter. Without it a re-insert of a
        # series that compaction moved into a sealed segment lands a
        # duplicate doc in the fresh mutable segment — growing n_docs and
        # so invalidating the sealed-view cache (a re-seal on the next
        # query) for a series the block already serves. None = not built
        # yet (or invalidated by an external sealed-segment install).
        self._seen: set[bytes] | None = None

    def _seen_locked(self) -> set[bytes]:
        """The block's series membership set (built on first use). Sealed
        segments contribute via series_ids() — id-blob slices, NOT the
        docs facade, which would decode every tag blob just to read ids
        (a restored block's first write would stall on an O(docs) tag
        decode otherwise)."""
        if self._seen is None:
            seen = set(self.mutable._by_series)
            for seg in self.sealed:
                ids_of = getattr(seg, "series_ids", None)
                if ids_of is not None:
                    seen.update(ids_of())
                else:  # segment types without the cheap surface
                    for doc in seg.docs:
                        seen.add(doc.series_id)
            self._seen = seen
        return self._seen

    def insert(self, series_id: bytes, fields) -> None:
        self.insert_many([series_id], [fields])

    def insert_many(self, series_ids, fields_list) -> int:
        """Insert the docs the block does not hold yet (mutable or
        sealed); returns how many were new."""
        inserted = 0
        with self._lock:
            seen = self._seen_locked()
            for sid, fields in zip(series_ids, fields_list):
                if sid in seen:
                    continue
                self.mutable.insert(sid, fields)
                seen.add(sid)
                inserted += 1
        return inserted

    def install_sealed(self, seg: Segment) -> None:
        """Add a restored sealed segment (bootstrap from persisted index
        files): membership grew outside insert, so the seen-set rebuilds."""
        with self._lock:
            self.sealed.append(seg)
            self._seen = None
            self.persisted_docs = sum(
                s.n_docs for s in self._segments_locked())

    def segments(self) -> list[Segment]:
        with self._lock:
            return self._segments_locked()

    def _segments_locked(self) -> list[Segment]:
        segs = list(self.sealed)
        if self.mutable.n_docs:
            # the doc-count check is the (single) cache invalidation: docs
            # are only ever appended to a mutable segment
            if self._cache is None or self._cache_docs != self.mutable.n_docs:
                self._cache = self.mutable.seal()
                self._cache_docs = self.mutable.n_docs
            segs.append(self._cache)
        return segs

    def compact(self, full: bool = False) -> None:
        """Compact this block's segments (the mutable->FST compaction,
        reference storage/index/mutable_segments.go).

        Default: SIZE-TIERED — seal the mutable segment into a packed one
        (mutable-first priority, reference plan.go OrderBy), then run the
        planner over the sealed set and merge only within-level groups.
        Per-block segment count stays bounded under churn without
        rewriting every doc each pass. ``full=True`` folds everything into
        ONE packed segment (the persist path wants a single artifact)."""
        with self._lock:
            self._compact_locked(full)

    def _compact_locked(self, full: bool) -> None:
        if full:
            segs = self._segments_locked()
            if not segs:
                return
            if len(segs) > 1 or not isinstance(segs[0], packed.PackedSegment):
                self.sealed = [packed.merge(segs)]
            self.mutable = MutableSegment()
            self._cache = None
            return
        from m3_tpu.index import compaction

        if self.mutable.n_docs:
            sealed_view = self._segments_locked()[-1]  # cached sealed view
            self.sealed.append(packed.merge([sealed_view])
                               if not isinstance(sealed_view, packed.PackedSegment)
                               else sealed_view)
            self.mutable = MutableSegment()
            self._cache = None
        for task in compaction.plan(self.sealed):
            merged = packed.merge(task.segments)
            keep = [s for s in self.sealed if s not in task.segments]
            self.sealed = keep + [merged]


class NamespaceIndex:
    def __init__(self, block_size_ns: int):
        self.block_size_ns = block_size_ns
        self._blocks: dict[int, IndexBlock] = {}
        # request threads create blocks while the tick thread walks and
        # expires them: two creators of one block would drop a block's
        # worth of docs
        self._blocks_lock = threading.Lock()

    def _block_for(self, t_ns: int) -> IndexBlock:
        bs = t_ns - (t_ns % self.block_size_ns)
        with self._blocks_lock:
            blk = self._blocks.get(bs)
            if blk is None:
                blk = self._blocks[bs] = IndexBlock()
            return blk

    def _snapshot(self) -> list[tuple[int, IndexBlock]]:
        with self._blocks_lock:
            return sorted(self._blocks.items())

    def insert(self, series_id: bytes, fields: list[tuple[bytes, bytes]], t_ns: int) -> None:
        self._block_for(t_ns).insert(series_id, fields)

    def insert_many(self, series_ids: list[bytes], fields_list: list,
                    ts_ns) -> int:
        """Batched insert with the per-block seen-set pre-filter applied
        up front: rows group by target index block, and series already
        present in their block never touch the mutable segment — so a
        steady-state write batch of existing series costs one set probe
        per row and leaves the sealed-view cache valid (no re-seal on the
        next query). Returns docs actually inserted."""
        import numpy as np

        ts = np.asarray(ts_ns, np.int64)
        bs_arr = ts - (ts % self.block_size_ns)
        inserted = 0
        # one row-index gather per distinct target block (batches land in
        # 1-2 blocks), then the per-row work is a single set probe
        for bs in np.unique(bs_arr).tolist():
            blk = self._block_for(bs)  # bs is already block-aligned
            rows = np.nonzero(bs_arr == bs)[0].tolist()
            inserted += blk.insert_many([series_ids[i] for i in rows],
                                        [fields_list[i] for i in rows])
        return inserted

    def _overlapping(self, start_ns: int, end_ns: int) -> list[IndexBlock]:
        out = []
        for bs, blk in self._snapshot():
            if bs + self.block_size_ns <= start_ns or bs >= end_ns:
                continue
            out.append(blk)
        return out

    def query(self, query: Query, start_ns: int, end_ns: int, limit: int | None = None):
        """Docs whose series matched in any overlapping index block."""
        from m3_tpu.utils import trace

        with trace.span(trace.INDEX_QUERY):
            segments = []
            for blk in self._overlapping(start_ns, end_ns):
                segments.extend(blk.segments())
            return search(segments, query, limit)

    def aggregate_field_names(self, start_ns: int, end_ns: int) -> list[bytes]:
        names: set[bytes] = set()
        for blk in self._overlapping(start_ns, end_ns):
            for seg in blk.segments():
                names.update(seg.field_names())
        return sorted(names)

    def aggregate_field_values(
        self, field: bytes, start_ns: int, end_ns: int,
        pattern: str | None = None,
    ) -> list[bytes]:
        rx = re.compile(pattern.encode()) if pattern else None
        values: set[bytes] = set()
        for blk in self._overlapping(start_ns, end_ns):
            for seg in blk.segments():
                for v in seg.terms(field):
                    if rx is None or rx.fullmatch(v):
                        values.add(v)
        return sorted(values)

    def compact(self, full: bool = False) -> None:
        for _bs, blk in self._snapshot():
            blk.compact(full=full)

    def expire_before(self, cutoff_ns: int) -> int:
        dropped = 0
        with self._blocks_lock:
            for bs in list(self._blocks):
                if bs + self.block_size_ns <= cutoff_ns:
                    del self._blocks[bs]
                    dropped += 1
        return dropped

    @property
    def n_blocks(self) -> int:
        return len(self._blocks)

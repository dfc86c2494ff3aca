"""Columnar in-memory write buffer.

The reference buffers writes per series object with one encoder per
out-of-order stream (/root/reference/src/dbnode/storage/series/buffer.go:77,
1261), merging on flush. TPU-first redesign: a shard keeps one append-only
struct-of-arrays log per block window (series idx / time / value bits);
writes are O(1) host appends and the whole window seals to compressed
blocks in a single batched device encode — the insert-queue batching
pattern (storage/shard_insert_queue.go) applied to the buffer itself.
Out-of-order and duplicate writes are resolved at seal time by a stable
sort + last-write-wins dedup, equivalent to the reference's merge of
multiple encoders at flush.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

from m3_tpu.storage import pagepool

def merge_dedup(times: np.ndarray, vbits: np.ndarray,
                start_ns: int | None = None, end_ns: int | None = None):
    """Stable sort by time + last-write-wins dedup (+ optional range filter).

    The single definition of write-conflict resolution: later appends win on
    timestamp ties, everywhere (buffer reads, seals, shard merges).
    """
    # fast path: already strictly increasing (the common case — a single
    # decoded block, or blocks concatenated in time order with no buffer
    # overlap) makes sort AND dedup no-ops; O(n) check vs O(n log n) sort
    # matters when read_many calls this once per series
    if len(times) > 1 and not np.all(times[1:] > times[:-1]):
        order = np.argsort(times, kind="stable")
        times, vbits = times[order], vbits[order]
        keep = np.ones(len(times), bool)
        keep[:-1] = times[1:] != times[:-1]
        times, vbits = times[keep], vbits[keep]
    if start_ns is not None or end_ns is not None:
        sel = np.ones(len(times), bool)
        if start_ns is not None:
            sel &= times >= start_ns
        if end_ns is not None:
            sel &= times < end_ns
        times, vbits = times[sel], vbits[sel]
    return times, vbits


@dataclass
class RaggedSealedWindow:
    """One block window sealed to the ragged (offsets, lengths) layout:
    sorted by (series, time), deduped last-write-wins, NO rectangular
    padding — the CSR the length-bucketed ragged encode consumes
    (hostpath.encode_blocks_ragged)."""

    block_start: int
    series_indices: np.ndarray  # [B] int32 buffer-level series indices
    times: np.ndarray           # [N] int64
    value_bits: np.ndarray      # [N] uint64
    offsets: np.ndarray         # [B+1] int64 row boundaries
    # raw log rows this seal covered: drop_window_prefix(bs, raw_count)
    # removes exactly these, preserving concurrent appends after the seal
    raw_count: int = 0

    @property
    def n_series(self) -> int:
        return len(self.series_indices)

    @property
    def n_points(self) -> np.ndarray:
        return np.diff(self.offsets).astype(np.int32)


class ShardBuffer:
    """Per-shard buffer: series registry + one column log per block window."""

    def __init__(self, block_size_ns: int) -> None:
        self._block_size_ns = block_size_ns
        self._series: dict[bytes, int] = {}
        self.series_ids: list[bytes] = []
        self.series_tags: list[bytes] = []  # encoded tag blobs
        self._logs: dict[int, pagepool.PagedColumnLog] = {}
        # paged columnar memory (ROADMAP #3): window logs draw fixed-size
        # pages from one pool shared by the shard's windows
        self._pool = pagepool.monitor_pool(pagepool.PagePool())
        # one lock per shard buffer (the reference's per-shard lock):
        # HTTP handler threads write while the tick thread seals/expires
        self._lock = threading.RLock()

    # -- write path --

    def series_index(self, series_id: bytes, encoded_tags: bytes = b"") -> int:
        with self._lock:
            idx = self._series.get(series_id)
            if idx is None:
                idx = len(self.series_ids)
                self._series[series_id] = idx
                self.series_ids.append(series_id)
                self.series_tags.append(encoded_tags)
            return idx

    def write(self, series_id: bytes, t_ns: int, vbits: int, encoded_tags: bytes = b"") -> int:
        """Returns the buffer-level series index (stable for this buffer)."""
        with self._lock:
            idx = self.series_index(series_id, encoded_tags)
            bs = t_ns - (t_ns % self._block_size_ns)
            log = self._logs.get(bs)
            if log is None:
                log = self._logs[bs] = pagepool.PagedColumnLog(self._pool)
            log.append(idx, t_ns, vbits)
            return idx

    def write_many(self, series_ids: list[bytes], times: np.ndarray,
                   vbits: np.ndarray, tags_list: list[bytes]) -> None:
        """Bulk write under ONE lock acquisition: resolve (registering)
        every series index, then ONE log extend per block window
        in the batch — numpy slice-assign, not N appends. Equivalent to
        calling write() per row; rows keep arrival order per window so
        seal-time conflict resolution is unchanged."""
        with self._lock:
            reg = self._series
            idxs = np.empty(len(series_ids), np.int32)
            for i, sid in enumerate(series_ids):
                idx = reg.get(sid)
                if idx is None:
                    idx = len(self.series_ids)
                    reg[sid] = idx
                    self.series_ids.append(sid)
                    self.series_tags.append(tags_list[i])
                idxs[i] = idx
            bs = times - (times % self._block_size_ns)
            for w in np.unique(bs):
                sel = bs == w
                log = self._logs.get(int(w))
                if log is None:
                    log = self._logs[int(w)] = pagepool.PagedColumnLog(self._pool)
                log.extend(idxs[sel], times[sel], vbits[sel])

    # -- read path --

    def read(self, series_id: bytes, start_ns: int, end_ns: int):
        """All buffered (t, vbits) for a series in [start, end), merged
        across block windows, deduped last-write-wins."""
        with self._lock:
            idx = self._series.get(series_id)
            if idx is None:
                return np.empty(0, np.int64), np.empty(0, np.uint64)
            ts_parts, vb_parts = [], []
            for bs, log in self._logs.items():
                if bs + self._block_size_ns <= start_ns or bs >= end_ns:
                    continue
                sidx, times, vbits = log.view()
                sel = sidx == idx
                ts_parts.append(times[sel])
                vb_parts.append(vbits[sel])
        if not ts_parts:
            return np.empty(0, np.int64), np.empty(0, np.uint64)
        return merge_dedup(
            np.concatenate(ts_parts), np.concatenate(vb_parts), start_ns, end_ns
        )

    def read_many_csr(self, series_ids: list[bytes], start_ns: int,
                      end_ns: int):
        """Buffered rows for MANY series in ONE pass per window: the
        batched twin of read(), returning a (times, vbits, offsets) CSR
        aligned to the request.  Rows keep the exact concatenation order
        read() produces per series (windows in _logs iteration order,
        append order within a window) and are NOT merged/filtered — the
        caller's ragged finalize (`ops.ragged.merge_csr`) applies the
        one last-write-wins + range pass over filesets AND buffer parts
        together, which resolves identically.  Requires unique ids (the
        caller falls back to per-series read() on duplicates)."""
        R = len(series_ids)
        empty = (np.empty(0, np.int64), np.empty(0, np.uint64),
                 np.zeros(R + 1, np.int64))
        with self._lock:
            pos_of = np.full(len(self.series_ids), -1, np.int64)
            found = False
            for pos, sid in enumerate(series_ids):
                idx = self._series.get(sid)
                if idx is not None:
                    pos_of[idx] = pos
                    found = True
            if not found:
                return empty
            parts_p, parts_t, parts_v = [], [], []
            for bs, log in self._logs.items():
                if bs + self._block_size_ns <= start_ns or bs >= end_ns:
                    continue
                sidx, times, vbits = log.view()
                pos = pos_of[sidx]
                m = pos >= 0
                if m.any():
                    parts_p.append(pos[m])
                    parts_t.append(times[m])
                    parts_v.append(vbits[m])
        if not parts_t:
            return empty
        rid = np.concatenate(parts_p) if len(parts_p) > 1 else parts_p[0]
        t = np.concatenate(parts_t) if len(parts_t) > 1 else parts_t[0]
        v = np.concatenate(parts_v) if len(parts_v) > 1 else parts_v[0]
        order = np.argsort(rid, kind="stable")
        counts = np.bincount(rid, minlength=R)
        offsets = np.empty(R + 1, np.int64)
        offsets[0] = 0
        np.cumsum(counts, out=offsets[1:])
        return t[order], v[order], offsets

    # -- seal/flush path --

    def block_starts(self) -> list[int]:
        with self._lock:
            return sorted(self._logs)

    def points_in(self, block_start: int) -> int:
        log = self._logs.get(block_start)
        return log.n if log else 0

    def seal_csr(self, block_start: int,
                 drop: bool = True) -> RaggedSealedWindow | None:
        """Seal one block window to the RAGGED layout: stable sort by
        (series, time), same-timestamp dedupe keeping the LAST append,
        and the output stays a CSR — no rectangular scatter, no padding,
        so a window where one series wrote 10k points and a million wrote
        one costs O(samples), not O(series x 10k).  The length-bucketed
        ragged encode (hostpath.encode_blocks_ragged) consumes this
        directly."""
        from m3_tpu.utils.instrument import default_registry

        with self._lock:
            log = self._logs.get(block_start)
            if log is None or log.n == 0:
                return None
            raw_count = log.n
            sidx, times, vbits = (a.copy() for a in log.view())
            fill = log.fill_ratio()
            if drop:
                del self._logs[block_start]
                log.release()
        order = np.lexsort((np.arange(len(sidx)), times, sidx))
        sidx, times, vbits = sidx[order], times[order], vbits[order]
        keep = np.ones(len(sidx), bool)
        if len(sidx) > 1:
            same = (sidx[1:] == sidx[:-1]) & (times[1:] == times[:-1])
            keep[:-1] = ~same
        sidx, times, vbits = sidx[keep], times[keep], vbits[keep]
        # page-occupancy telemetry: how much of the window's page
        # allocation held real rows at seal time (padding-waste measure)
        default_registry().root_scope("storage").subscope(
            "page_pool").observe("page_fill", fill)
        uniq, counts = np.unique(sidx, return_counts=True)
        offsets = np.empty(len(uniq) + 1, np.int64)
        offsets[0] = 0
        np.cumsum(counts, out=offsets[1:])
        return RaggedSealedWindow(
            block_start=block_start,
            series_indices=uniq.astype(np.int32),
            times=times,
            value_bits=vbits,
            offsets=offsets,
            raw_count=raw_count,
        )

    def drop_window(self, block_start: int) -> None:
        with self._lock:
            log = self._logs.pop(block_start, None)
            if log is not None:
                log.release()

    def drop_window_prefix(self, block_start: int, n: int) -> None:
        """Drop the first n appended rows of a window — the rows a seal
        covered — KEEPING anything appended concurrently after the seal
        (they flush with the next volume instead of vanishing)."""
        with self._lock:
            log = self._logs.get(block_start)
            if log is None:
                return
            if log.n <= n:
                del self._logs[block_start]
                log.release()
                return
            # advance the head, free covered pages — no suffix copy under
            # the shard lock
            log.drop_prefix(n)

    def expire_before(self, cutoff_block_start: int) -> int:
        with self._lock:
            dropped = 0
            for bs in list(self._logs):
                if bs < cutoff_block_start:
                    log = self._logs.pop(bs)
                    dropped += log.n
                    log.release()
            return dropped

    @property
    def n_series(self) -> int:
        return len(self.series_ids)

"""Commit log: write-ahead durability for the in-memory buffer.

Role parity with the reference WAL (/root/reference/src/dbnode/persist/fs/
commitlog: batched writes drained by one writer, chunked format with
digests, rotation + snapshot-based truncation). Here the queue is a
host-side byte buffer flushed on size/explicit fsync; the chunk format is:

  chunk:  u32 magic, u32 payload_len, u32 adler32(payload), payload
  entry:  u8 kind
          kind 0 (register): u32 sidx, u32 id_len + id, u32 tags_len + tags
          kind 1 (write):    u32 sidx, i64 time_ns, u64 value_bits, u8 unit
Series are registered once per log file and then referenced by index,
mirroring the reference's commit-log series registry.

Recovery modes: `replay` is strict (corrupt interior chunks raise — the
inspector/verifier behavior), `replay_salvage` truncates at the first bad
chunk and reports what was dropped (the bootstrap behavior: a damaged log
must never brick a node; the reference's commitlog bootstrapper likewise
reads until the first unrecoverable error). Torn TRAILING chunks — the
tail of a crashed process — are skipped by both.

Fault points (utils/faults.py): commitlog.write, commitlog.flush (torn
writes land a prefix of the chunk, the kill-mid-flush case),
commitlog.fsync.
"""

from __future__ import annotations

import os
import struct
import zlib
from dataclasses import dataclass

import numpy as np

from m3_tpu.utils import faults
from m3_tpu.utils.instrument import default_registry

_MAGIC = 0xC0881706

# one kind-1 (write) record, as a packed big-endian numpy dtype: a whole
# batch of datapoint records renders with four vectorized column stores +
# one tobytes() instead of one struct.pack per entry (write_many)
_WRITE_REC = np.dtype([("kind", "u1"), ("sidx", ">u4"), ("t", ">i8"),
                       ("v", ">u8"), ("unit", "u1")])
assert _WRITE_REC.itemsize == 22  # must match the ">BIqQB" wire layout

# fsync latency distribution — the durability seam whose p99 bounds write
# ack latency; exposed as db_commitlog_fsync_seconds_bucket on /metrics
_scope = default_registry().root_scope("db")


def _fsync_timed(fileno: int) -> None:
    import time as _time

    t0 = _time.perf_counter()
    os.fsync(fileno)
    _scope.observe("commitlog_fsync_seconds", _time.perf_counter() - t0)


@dataclass
class CommitLogEntry:
    series_id: bytes
    encoded_tags: bytes
    time_ns: int
    value_bits: int
    unit: int


@dataclass
class SalvageReport:
    """What a salvage replay recovered and what it gave up on."""
    entries: int = 0            # entries successfully recovered
    chunks: int = 0             # complete chunks replayed
    truncated_at: int | None = None  # byte offset of the first bad chunk
    dropped_bytes: int = 0      # bytes abandoned from truncated_at on
    torn_tail: bool = False     # ended at a torn trailing chunk (benign)
    reason: str = ""

    @property
    def clean(self) -> bool:
        return self.truncated_at is None


class CommitLogWriter:
    def __init__(self, path: str, flush_every_bytes: int = 1 << 20):
        import threading

        os.makedirs(os.path.dirname(path), exist_ok=True)
        self._f = open(path, "ab")
        self._buf = bytearray()
        self._series: dict[bytes, int] = {}
        self._flush_every = flush_every_bytes
        self.path = path
        # writer lock: concurrent ingest threads interleaving the
        # multi-append register records (or racing the series registry)
        # would tear the entry framing inside a digest-valid chunk — an
        # undecodable-chunk salvage truncation with NO crash involved.
        # The pipelined write path routes appends through a per-namespace
        # FIFO lane (storage/pipeline.py) so in steady state exactly one
        # thread holds this; the lock is the correctness backstop (and
        # the measured WAL class in the lock-wait profile: serial-path
        # ingest threads contend here for the full flush+fsync I/O).
        self._lock = threading.Lock()
        # a failed flush POISONS the writer: the file may hold a torn
        # interior chunk, and salvage replay truncates everything after
        # the first bad chunk — so acking any later write on this file
        # would be a silent-loss lie. Callers that survive the error (a
        # request handler swallowing it) must rotate to a fresh log.
        self._failed: Exception | None = None
        # block windows this FILE holds entries of: added to with the
        # append, under the lock, and handed over whole by rotate(), so a
        # retired log is never missing a window it has a datapoint of
        self.windows: set[int] = set()
        # saturation plane: acked bytes sitting in the user-space buffer
        # (lost on SIGKILL until flushed) vs the flush threshold
        from m3_tpu.utils.instrument import monitor_queue

        self._unmonitor = monitor_queue(
            "commitlog_flush_backlog", lambda: len(self._buf),
            flush_every_bytes, owner=self,
            log=os.path.basename(os.path.dirname(path)))

    def write(self, series_id: bytes, encoded_tags: bytes, time_ns: int,
              value_bits: int, unit: int, window: int | None = None) -> None:
        """`window`: the block window of `time_ns`, where the caller
        tracks which windows a log covers (Database's retirement rule)."""
        faults.check("commitlog.write")
        with self._lock:
            # poison check INSIDE the lock: a writer blocked here while a
            # concurrent flush fails must not append (and so ack) onto a
            # poisoned log — salvage replay would truncate those bytes
            self._check_poisoned_locked()
            sidx = self._series.get(series_id)
            if sidx is None:
                sidx = len(self._series)
                self._series[series_id] = sidx
                self._buf += struct.pack(">BI", 0, sidx)
                self._buf += struct.pack(">I", len(series_id)) + series_id
                self._buf += struct.pack(">I", len(encoded_tags)) \
                    + encoded_tags
            self._buf += struct.pack(">BIqQB", 1, sidx, time_ns, value_bits,
                                     unit)
            if window is not None:
                self.windows.add(window)
            if len(self._buf) >= self._flush_every:
                # the WAL write/fsync seam deliberately completes under
                # the writer lock: the lock IS the append/flush ordering
                # (same class as the raft persist-before-ack waivers)
                # m3lint: disable=lock-blocking-call
                self._flush_locked(fsync=False)

    def _check_poisoned_locked(self) -> None:
        if self._failed is not None:
            raise OSError(
                f"commitlog writer poisoned by earlier flush failure "
                f"({self.path})"
            ) from self._failed

    def write_many(self, series_ids: list[bytes], tags_list: list[bytes],
                   times: np.ndarray, value_bits: np.ndarray,
                   unit: int, windows=()) -> None:
        """ONE commitlog append for a whole batch (columns: parallel
        series/tags lists + int64 time and uint64 value-bit arrays, all
        sharing the namespace's time unit). The datapoint records render
        as one vectorized pack (four column stores + tobytes) with
        new-series register records spliced in at each first occurrence,
        so the emitted byte stream is IDENTICAL to calling write() per
        entry — replay/replay_salvage and the poison/torn-chunk semantics
        see nothing new. One fault-point hit and one flush-threshold
        check per batch (the per-point path checks per entry, so chunk
        BOUNDARIES may differ once a batch crosses the threshold; the
        entry stream never does). `windows`: the block windows of
        `times`, as for write()."""
        # same semantic seam as the per-point write() above — one name, one
        # injection schedule, whichever path the caller took
        # m3lint: disable=inv-fault-point-unique
        faults.check("commitlog.write", batch=len(series_ids))
        n = len(series_ids)
        if n == 0:
            return
        with self._lock:
            # deliberate: the batched append (incl. a threshold flush)
            # completes under the writer lock — see write()
            # m3lint: disable=lock-blocking-call
            self._write_many_locked(series_ids, tags_list, times,
                                    value_bits, unit)
            self.windows.update(windows)

    def _write_many_locked(self, series_ids, tags_list, times, value_bits,
                           unit) -> None:
        # same poisoned-writer rule as write(): checked under the lock
        self._check_poisoned_locked()
        n = len(series_ids)
        series = self._series
        # register records for series this log hasn't seen, keyed by the
        # batch position they must precede
        registers: list[tuple[int, bytes]] = []
        sidx_l: list = [0] * n
        for i, sid in enumerate(series_ids):
            sidx = series.get(sid)
            if sidx is None:
                sidx = len(series)
                series[sid] = sidx
                tags = tags_list[i]
                registers.append((i, struct.pack(">BI", 0, sidx)
                                  + struct.pack(">I", len(sid)) + sid
                                  + struct.pack(">I", len(tags)) + tags))
            sidx_l[i] = sidx
        rec = np.empty(n, _WRITE_REC)
        rec["kind"] = 1
        rec["unit"] = unit
        rec["sidx"] = np.array(sidx_l, np.uint32)
        rec["t"] = times
        rec["v"] = value_bits
        blob = rec.tobytes()
        if not registers:
            self._buf += blob
        else:
            sz = _WRITE_REC.itemsize
            pieces: list[bytes] = []
            prev = 0
            for i, reg in registers:
                pieces.append(blob[prev * sz : i * sz])
                pieces.append(reg)
                prev = i
            pieces.append(blob[prev * sz :])
            self._buf += b"".join(pieces)
        if len(self._buf) >= self._flush_every:
            self._flush_locked(fsync=False)

    def flush(self, fsync: bool = False) -> None:
        with self._lock:
            # deliberate: the flush+fsync seam holds the writer lock so
            # no append can interleave a half-flushed chunk
            # m3lint: disable=lock-blocking-call
            self._flush_locked(fsync)

    def _flush_locked(self, fsync: bool) -> None:
        self._check_poisoned_locked()
        try:
            if not self._buf:
                if fsync:
                    faults.check("commitlog.fsync")
                    _fsync_timed(self._f.fileno())
                return
            payload = bytes(self._buf)
            self._buf.clear()
            header = struct.pack(">III", _MAGIC, len(payload),
                                 zlib.adler32(payload))
            # a crash here may land any byte prefix of the chunk — the
            # torn tail that replay/replay_salvage skip
            faults.torn_write(self._f, header + payload, "commitlog.flush")
            self._f.flush()
            if fsync:
                # same fsync seam as the empty-buffer branch above: one
                # name for "the WAL fsync", whichever branch ran
                # m3lint: disable=inv-fault-point-unique
                faults.check("commitlog.fsync")
                _fsync_timed(self._f.fileno())
        except BaseException as e:
            self._failed = e
            raise

    def rotate(self, path: str) -> tuple[str, set[int]]:
        """Retire the current file and go on in `path`, under the
        writer's own lock: an append is either whole in the retired file
        (flushed and fsynced here, so it holds whole chunks only) or
        whole in the new one, and no caller ever holds a closed writer.
        Returns the retired file's (path, windows). A poisoned writer
        retires its file unflushed (as close() leaves it) and is sound
        again in the new one; a flush that fails HERE leaves the writer
        poisoned in the old file for the next rotation."""
        with self._lock:
            if self._failed is None:
                # deliberate: the fsync completes under the writer lock,
                # as in flush()
                # m3lint: disable=lock-blocking-call
                self._flush_locked(fsync=True)
            self._f.close()
            retired = (self.path, self.windows)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._f = open(path, "ab")
            self.path = path
            self._buf.clear()
            self._series = {}
            self.windows = set()
            self._failed = None
        return retired

    def close(self) -> None:
        self._unmonitor()
        if self._failed is None:
            self.flush(fsync=True)
        with self._lock:
            self._f.close()


def _decode_payload(payload: bytes, series: dict[int, tuple[bytes, bytes]],
                    entries: list[CommitLogEntry]) -> None:
    """Decode one chunk payload into `entries`, updating the series
    registry. Raises ValueError/struct.error on a malformed entry."""
    p = 0
    while p < len(payload):
        kind, sidx = struct.unpack_from(">BI", payload, p)
        p += 5
        if kind == 0:
            (idlen,) = struct.unpack_from(">I", payload, p)
            p += 4
            sid = payload[p : p + idlen]
            p += idlen
            (tlen,) = struct.unpack_from(">I", payload, p)
            p += 4
            tags = payload[p : p + tlen]
            p += tlen
            series[sidx] = (sid, tags)
        elif kind == 1:
            t_ns, vbits, unit = struct.unpack_from(">qQB", payload, p)
            p += 17
            sid, tags = series[sidx]
            entries.append(CommitLogEntry(sid, tags, t_ns, vbits, unit))
        else:
            raise ValueError(f"unknown commitlog entry kind {kind}")


def _replay(path: str, salvage: bool) -> tuple[list[CommitLogEntry], SalvageReport]:
    entries: list[CommitLogEntry] = []
    report = SalvageReport()
    if not os.path.exists(path):
        return entries, report
    with open(path, "rb") as f:
        raw = f.read()
    series: dict[int, tuple[bytes, bytes]] = {}
    off = 0

    def bad(reason: str) -> tuple[list[CommitLogEntry], SalvageReport]:
        if not salvage:
            raise ValueError(f"{reason} at {off}")
        report.truncated_at = off
        report.dropped_bytes = len(raw) - off
        report.reason = reason
        report.entries = len(entries)
        return entries, report

    while off + 12 <= len(raw):
        magic, plen, digest = struct.unpack_from(">III", raw, off)
        if magic != _MAGIC:
            return bad("bad commitlog chunk magic")
        if off + 12 + plen > len(raw):
            report.torn_tail = True
            break  # torn tail chunk from a crash: ignore
        payload = raw[off + 12 : off + 12 + plen]
        if zlib.adler32(payload) != digest:
            if off + 12 + plen == len(raw):
                report.torn_tail = True
                break  # torn tail
            return bad("corrupt commitlog chunk")
        mark = len(entries)
        try:
            _decode_payload(payload, series, entries)
        except (ValueError, KeyError, struct.error) as e:
            # digest-valid but undecodable (format bug / sidx from a
            # truncated registry): salvage keeps nothing of this chunk
            del entries[mark:]
            return bad(f"undecodable commitlog chunk ({e})")
        report.chunks += 1
        off += 12 + plen
    if off < len(raw) and not report.torn_tail:
        # trailing sub-header garbage (< 12 bytes): torn tail by definition
        report.torn_tail = True
    report.entries = len(entries)
    return entries, report


def replay(path: str) -> list[CommitLogEntry]:
    """Strict replay: torn trailing chunks are skipped (the tail of a
    crashed process), corrupt interior chunks raise."""
    entries, _report = _replay(path, salvage=False)
    return entries


def replay_salvage(path: str) -> tuple[list[CommitLogEntry], SalvageReport]:
    """Salvage replay: recover every entry up to the first bad chunk and
    report the truncation instead of raising — bootstrap must come up on
    a damaged log and say what it lost."""
    return _replay(path, salvage=True)


def log_files(directory: str) -> list[str]:
    if not os.path.isdir(directory):
        return []
    return sorted(
        os.path.join(directory, n)
        for n in os.listdir(directory)
        if n.startswith("commitlog-") and n.endswith(".db")
    )

"""Index segment persistence.

Role parity with the reference's per-index-block segment files
(/root/reference/src/dbnode/persist/fs/index_write.go + m3ninx/persist):
each index block's compacted immutable segment is written to
<root>/<namespace>/_index/segment-<blockstart>-v<version>.db with an
adler32 trailer; bootstrap loads persisted segments instead of rebuilding
the reverse index from fileset tag scans (which remains the fallback for
blocks without a persisted segment).

Current format: the packed-segment buffer (index/packed.py) written
verbatim + adler32 trailer, loaded back as ZERO-COPY views over an mmap —
no dict rebuilding, the fst-segment mmap model (segment/fst/segment.go:130).
Legacy "M3IXSEG1" files (round-1 dict segments) still load.
"""

from __future__ import annotations

import os
import struct
import zlib

from m3_tpu.index import packed
from m3_tpu.index.index import NamespaceIndex
from m3_tpu.index.segment import Segment
from m3_tpu.utils import faults

_MAGIC = b"M3IXSEG1"


def _index_dir(root: str, namespace: str) -> str:
    return os.path.join(root, namespace, "_index")


def _path(root: str, namespace: str, block_start: int) -> str:
    return os.path.join(_index_dir(root, namespace), f"segment-{block_start}.db")


def persist_index(index: NamespaceIndex, root: str, namespace: str,
                  seal_before_ns: int | None = None) -> int:
    """Compact + write every index block that has new docs since the last
    persist. Returns blocks written.

    ``seal_before_ns`` limits persistence to blocks whose window has fully
    passed (the reference persists index segments per block volume at data
    flush time, not continuously); ACTIVE blocks are left to the
    background size-tiered compaction instead of being fully rewritten
    every tick."""
    os.makedirs(_index_dir(root, namespace), exist_ok=True)
    written = 0
    for bs, blk in index._snapshot():
        if seal_before_ns is not None and \
                bs + index.block_size_ns > seal_before_ns:
            continue  # still accepting writes: tiered compaction only
        n_docs = sum(s.n_docs for s in blk.segments())
        if blk.persisted_docs == n_docs:
            continue
        blk.compact(full=True)  # the fileset wants one segment artifact
        if not blk.sealed:
            continue
        payload = blk.sealed[0].to_bytes()
        # packed buffers are written verbatim (their own magic leads) so
        # the loader can mmap them in place; trailer guards torn writes.
        # Fault seams mirror the fileset's: index.persist fires BEFORE any
        # byte lands (per-block), index.persist.write can tear the tmp
        # file — either way the committed segment under the final name
        # stays intact and bootstrap falls back to the tag-scan rebuild.
        faults.check("index.persist", block=bs)
        from m3_tpu.utils.instrument import default_registry

        raw = payload + struct.pack(">I", zlib.adler32(payload))
        tmp = _path(root, namespace, bs) + ".tmp"
        with default_registry().root_scope("index").histogram(
                "persist_seconds"):
            with open(tmp, "wb") as f:
                faults.torn_write(f, raw, "index.persist.write")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, _path(root, namespace, bs))
        # record the POST-compact doc count: pre-compact sums double-count
        # series duplicated across segments and would mask later inserts
        blk.persisted_docs = blk.sealed[0].n_docs
        written += 1
    return written


def _load_packed(path: str) -> packed.PackedSegment:
    """mmap a packed segment file; views are zero-copy over the mapping."""
    import mmap as _mmap

    with open(path, "rb") as f:
        mm = _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
    mv = memoryview(mm)
    try:
        if zlib.adler32(mv[:-4]) != struct.unpack(">I", mv[-4:])[0]:
            raise ValueError(f"checksum mismatch in {path}")
        return packed.PackedSegment(mm)
    finally:
        mv.release()


def load_index(index: NamespaceIndex, root: str, namespace: str,
               cutoff_ns: int | None = None) -> set[int]:
    """Load persisted segments into the index; returns the block starts
    restored (corrupt files are skipped — callers fall back to the fileset
    tag-scan rebuild for those blocks). Blocks fully before cutoff_ns are
    not resurrected (retention parity with the other bootstrap paths)."""
    d = _index_dir(root, namespace)
    restored: set[int] = set()
    if not os.path.isdir(d):
        return restored
    for name in sorted(os.listdir(d)):
        if not (name.startswith("segment-") and name.endswith(".db")):
            continue
        try:
            bs = int(name[len("segment-") : -len(".db")])
        except ValueError:
            continue
        if cutoff_ns is not None and bs + index.block_size_ns <= cutoff_ns:
            continue  # expired: leave for expire_index_files to reclaim
        try:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                head = f.read(8)
            if head == packed.MAGIC:
                seg = _load_packed(path)
            else:
                with open(path, "rb") as f:
                    raw = f.read()
                if not raw.startswith(_MAGIC):
                    continue
                payload, trailer = raw[len(_MAGIC) : -4], raw[-4:]
                if zlib.adler32(payload) != struct.unpack(">I", trailer)[0]:
                    continue
                seg = Segment.from_bytes(payload)  # legacy round-1 format
        except Exception:
            continue
        index._block_for(bs).install_sealed(seg)
        restored.add(bs)
    return restored


def expire_index_files(root: str, namespace: str, cutoff_ns: int,
                       block_size_ns: int) -> int:
    d = _index_dir(root, namespace)
    if not os.path.isdir(d):
        return 0
    removed = 0
    for name in list(os.listdir(d)):
        if not (name.startswith("segment-") and name.endswith(".db")):
            continue
        try:
            bs = int(name[len("segment-") : -len(".db")])
        except ValueError:
            continue
        if bs + block_size_ns <= cutoff_ns:
            try:
                os.remove(os.path.join(d, name))
                removed += 1
            except OSError:
                pass
    return removed

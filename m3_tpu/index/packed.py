"""Packed immutable index segments — the FST-segment-equivalent tier.

Role parity with the reference's mmap-able FST segments
(/root/reference/src/m3ninx/index/segment/fst/segment.go:130-180, writer
fst/writer.go) and its regex-automaton term matching
(fst/regexp/regexp.go:33-44), redesigned host-columnar instead of
FST-shaped:

- One contiguous buffer holds every doc id, tag blob, field name, term and
  postings list as offset-indexed numpy views: loading a persisted segment
  is ``np.frombuffer`` over an mmap — no dict rebuilding, no per-term
  Python objects (the round-1 gap: sealed segments were Python dicts).
- Term lookup is binary search over the sorted per-field vocab
  (the FST's ordered-lookup role).
- Regex queries run ONE C-speed ``re.finditer`` pass over the
  newline-joined vocab blob with ``(?m)^(?:pat)$`` — the batched
  replacement for automaton-FST intersection — narrowed first to the
  vocab range sharing the pattern's literal prefix.
- Per-segment LRU caches memoize regex/term postings (the
  storage/index/postings_list_cache.go role).

Layout (little-endian, every array 8-byte aligned):
  magic "M3PKSG02" | header (9x u64): n_docs, sid_blob_len, tags_blob_len,
  n_fields, fname_blob_len, n_terms, term_blob_len, postings_len, flags
  sid_offsets u64[D+1] | sid_blob | tag_offsets u64[D+1] | tags_blob |
  fname_offsets u64[F+1] | fname_blob | field_term_start u64[F+1] |
  term_offsets u64[T+1] | term_blob (each term followed by \n) |
  postings_offsets u64[T+1] | postings u32[P]
"""

from __future__ import annotations

import re
import struct
import threading
import weakref
from collections import OrderedDict

import numpy as np

from m3_tpu.index import postings as P
from m3_tpu.index.segment import Document
from m3_tpu.metrics.filters import literal_prefix as _literal_prefix
from m3_tpu.metrics.filters import literal_suffix as _literal_suffix
from m3_tpu.metrics.filters import prefix_upper_bound as _prefix_upper_bound
from m3_tpu.utils import querystats
from m3_tpu.utils.ident import decode_tags, encode_tags

MAGIC = b"M3PKSG02"
_HDR = struct.Struct("<9Q")
_CACHE_CAP = 256

# below this many candidate terms a scalar byte-compare bisect beats
# building/consulting the vectorized 8-byte key column
_KEYED_LOOKUP_MIN = 1024


def _align8(n: int) -> int:
    return (n + 7) & ~7


class _LazyDocs:
    """Sequence facade building Document objects on demand from the blobs."""

    __slots__ = ("_seg",)

    def __init__(self, seg: "PackedSegment"):
        self._seg = seg

    def __len__(self) -> int:
        return self._seg.n_docs

    def __getitem__(self, doc_id: int) -> Document:
        s = self._seg
        sid = bytes(s._sid_blob[s._sid_off[doc_id]: s._sid_off[doc_id + 1]])
        tags = decode_tags(
            bytes(s._tag_blob[s._tag_off[doc_id]: s._tag_off[doc_id + 1]])
        )
        return Document(doc_id, sid, tags)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


class PackedSegment:
    """Immutable segment over one contiguous (possibly mmap'd) buffer."""

    def __init__(self, buf):
        mv = memoryview(buf)
        if bytes(mv[:8]) != MAGIC:
            raise ValueError("not a packed segment (bad magic)")
        (n_docs, sid_len, tags_len, n_fields, fname_len, n_terms,
         term_len, post_len, _flags) = _HDR.unpack_from(mv, 8)
        self.n_docs = n_docs
        self.n_fields = n_fields
        self.n_terms = n_terms
        self._buf = buf  # keep mmap/bytes alive
        off = _align8(8 + _HDR.size)

        def u64(count):
            nonlocal off
            a = np.frombuffer(mv, dtype="<u8", count=count, offset=off)
            off += 8 * count
            return a

        def blob(length):
            nonlocal off
            b = mv[off: off + length]
            off = _align8(off + length)
            return b

        self._sid_off = u64(n_docs + 1)
        self._sid_blob = blob(sid_len)
        self._tag_off = u64(n_docs + 1)
        self._tag_blob = blob(tags_len)
        self._fname_off = u64(n_fields + 1)
        self._fname_blob = blob(fname_len)
        self._field_term_start = u64(n_fields + 1)
        self._term_off = u64(n_terms + 1)
        self._term_blob = blob(term_len)
        self._post_off = u64(n_terms + 1)
        self._postings = np.frombuffer(mv, dtype="<u4", count=post_len, offset=off)
        # payload ends after the postings array; anything beyond (e.g. the
        # persistence checksum trailer) is NOT part of this segment
        self._payload_len = off + 4 * post_len
        self.docs = _LazyDocs(self)
        self._regex_cache: OrderedDict = OrderedDict()
        self._term_idx_cache: OrderedDict = OrderedDict()
        self._vocab_clean_cache: bool | None = None
        self._term_keys_cache: np.ndarray | None = None
        self._device_postings: dict = {}  # compute mesh (or None) -> column

    def series_ids(self):
        """Every doc's series id, sliced straight out of the id blob —
        no Document construction, no tag decode. The write path's
        per-block membership set (IndexBlock.seen_series) builds from
        this; going through `docs` would decode every tag blob."""
        off = self._sid_off
        blob = self._sid_blob
        return [bytes(blob[off[i] : off[i + 1]]) for i in range(self.n_docs)]

    def series_ids_at(self, doc_ids) -> list[bytes]:
        """Series ids for many doc ids in one pass over the id blob — no
        Document construction, no tag decode. The executor's batched
        search dedups on these BEFORE paying any tag decode."""
        off = self._sid_off
        blob = self._sid_blob
        return [bytes(blob[off[i]: off[i + 1]])
                for i in np.asarray(doc_ids, np.int64).tolist()]

    def docs_at(self, doc_ids) -> list[Document]:
        """Documents for many doc ids in one pass (the batched twin of
        the per-doc _LazyDocs facade: local offset/blob bindings, one tag
        decode per requested doc)."""
        sid_off, sid_blob = self._sid_off, self._sid_blob
        tag_off, tag_blob = self._tag_off, self._tag_blob
        out = []
        for i in np.asarray(doc_ids, np.int64).tolist():
            sid = bytes(sid_blob[sid_off[i]: sid_off[i + 1]])
            tags = decode_tags(bytes(tag_blob[tag_off[i]: tag_off[i + 1]]))
            out.append(Document(i, sid, tags))
        return out

    @property
    def _vocab_clean(self) -> bool:
        """Vocab is regex-scannable iff no term contains a newline. Computed
        lazily on first regex (a bootstrap-time scan would page in the whole
        blob) and without copying the blob out of the mapping."""
        if self._vocab_clean_cache is None:
            newlines = int(
                (np.frombuffer(self._term_blob, np.uint8) == 0x0A).sum()
            )
            self._vocab_clean_cache = newlines == self.n_terms
        return self._vocab_clean_cache

    # -- field/term access --

    def field_names(self) -> list[bytes]:
        return [
            bytes(self._fname_blob[self._fname_off[i]: self._fname_off[i + 1]])
            for i in range(self.n_fields)
        ]

    def _field_index(self, name: bytes) -> int:
        lo, hi = 0, self.n_fields
        while lo < hi:
            mid = (lo + hi) // 2
            t = bytes(self._fname_blob[self._fname_off[mid]: self._fname_off[mid + 1]])
            if t < name:
                lo = mid + 1
            else:
                hi = mid
        if lo < self.n_fields:
            t = bytes(self._fname_blob[self._fname_off[lo]: self._fname_off[lo + 1]])
            if t == name:
                return lo
        return -1

    def _term_at(self, i: int) -> bytes:
        return bytes(self._term_blob[self._term_off[i]: self._term_off[i + 1] - 1])

    def _term_range(self, fi: int) -> tuple[int, int]:
        return int(self._field_term_start[fi]), int(self._field_term_start[fi + 1])

    @property
    def _term_keys(self) -> np.ndarray:
        """u64 key per term: the first 8 bytes big-endian, zero-padded.
        Key order agrees with byte order everywhere keys differ (zero-pad
        vs prefix-shorter both sort the shorter string first), so a
        vectorized ``searchsorted`` over this column replaces all but the
        tie-run tail of a Python byte-compare bisect. Built lazily in 8
        vectorized gathers over the term blob — no per-term slicing —
        and cached forever (the segment is immutable): ~8 bytes/term."""
        keys = self._term_keys_cache
        if keys is None:
            offs = self._term_off[:-1].astype(np.int64)
            lens = self._term_off[1:].astype(np.int64) - offs - 1
            blob = np.frombuffer(self._term_blob, np.uint8)
            keys = np.zeros(self.n_terms, np.uint64)
            limit = max(blob.size - 1, 0)
            for j in range(8):
                b = blob[np.minimum(offs + j, limit)]
                keys = (keys << np.uint64(8)) | np.where(
                    j < lens, b, 0).astype(np.uint64)
            self._term_keys_cache = keys
        return keys

    @staticmethod
    def _term_key(value: bytes) -> int:
        v = value[:8]
        return int.from_bytes(v + b"\0" * (8 - len(v)), "big")

    def _bisect_term(self, lo: int, hi: int, value: bytes) -> int:
        """First term index in [lo, hi) with term >= value. Wide ranges
        run ONE vectorized searchsorted over the 8-byte key column; the
        scalar byte-compare loop then only walks the (usually empty) run
        of terms sharing value's first 8 bytes. Strict key inequality
        implies the same byte inequality, so the narrowing is exact."""
        if hi - lo >= _KEYED_LOOKUP_MIN:
            keys = self._term_keys
            k = np.uint64(self._term_key(value))
            lo = lo + int(np.searchsorted(keys[lo:hi], k, side="left"))
            hi = lo + int(np.searchsorted(keys[lo:hi], k, side="right"))
        while lo < hi:
            mid = (lo + hi) // 2
            if self._term_at(mid) < value:
                lo = mid + 1
            else:
                hi = mid
        return lo

    def terms(self, field: bytes) -> list[bytes]:
        fi = self._field_index(field)
        if fi < 0:
            return []
        lo, hi = self._term_range(fi)
        return [self._term_at(i) for i in range(lo, hi)]

    def _postings_at(self, i: int) -> np.ndarray:
        return self._postings[self._post_off[i]: self._post_off[i + 1]].astype(
            np.uint32, copy=False
        )

    # -- query surface (same contract as segment.Segment) --

    def postings_term(self, field: bytes, value: bytes) -> np.ndarray:
        fi = self._field_index(field)
        if fi < 0:
            return P.EMPTY
        lo, hi = self._term_range(fi)
        i = self._bisect_term(lo, hi, value)
        if i < hi and self._term_at(i) == value:
            return self._postings_at(i)
        return P.EMPTY

    def postings_regexp(self, field: bytes, pattern: re.Pattern) -> np.ndarray:
        src = pattern.pattern
        if isinstance(src, str):
            src = src.encode()
        key = (field, src, pattern.flags)
        cached = self._regex_cache.get(key)
        if cached is not None:
            self._regex_cache.move_to_end(key)
            return cached
        out = self._gather_postings(self.term_indices_regexp(field, pattern))
        self._regex_cache[key] = out
        if len(self._regex_cache) > _CACHE_CAP:
            self._regex_cache.popitem(last=False)
        return out

    def term_indices_regexp(self, field: bytes,
                            pattern: re.Pattern) -> np.ndarray:
        """Absolute term indices matching the pattern — the term-selection
        surface the device-compiled postings programs consume
        (index/device.py needs WHICH CSR rows to intersect, not the
        materialized host union). Same narrowing as postings_regexp
        (shared LRU cache, keyed on field+source+flags): literal-prefix
        binary search bounds the vocab range before any Python ``re``
        runs, then the batched blob scan picks the matches."""
        src = pattern.pattern
        if isinstance(src, str):
            src = src.encode()
        key = (field, src, pattern.flags)
        cached = self._term_idx_cache.get(key)
        if cached is not None:
            self._term_idx_cache.move_to_end(key)
            return cached
        fi = self._field_index(field)
        if fi < 0:
            idxs = np.empty(0, np.int64)
        else:
            lo0, hi0 = self._term_range(fi)
            if pattern.flags & (re.I | re.X | re.S | re.M):
                # compile-time flags change what the literals mean —
                # prefix narrowing and the batched blob rescan (which
                # recompiles from source, losing the flags) are both
                # unsound; match per-term with the caller's own pattern
                querystats.record_index(terms_scanned=hi0 - lo0)
                idxs = np.asarray([i for i in range(lo0, hi0)
                                   if pattern.fullmatch(self._term_at(i))],
                                  np.int64)
            else:
                lo, hi = self._narrow_by_prefix(src, lo0, hi0)
                querystats.record_index(
                    terms_scanned=hi - lo,
                    terms_prefiltered=(hi0 - lo0) - (hi - lo))
                idxs = np.asarray(self._scan_vocab(src, pattern, lo, hi),
                                  np.int64)
        self._term_idx_cache[key] = idxs
        if len(self._term_idx_cache) > _CACHE_CAP:
            self._term_idx_cache.popitem(last=False)
        return idxs

    def _gather_postings(self, term_idxs) -> np.ndarray:
        """Union of the postings of many terms, gathered vectorized (no
        per-term Python) — the multi-list OR of the searcher algebra."""
        term_idxs = np.asarray(term_idxs, np.int64)
        if len(term_idxs) == 0:
            return P.EMPTY
        starts = self._post_off[term_idxs].astype(np.int64)
        lens = self._post_off[term_idxs + 1].astype(np.int64) - starts
        total = int(lens.sum())
        if total == 0:
            return P.EMPTY
        base = np.repeat(starts - np.concatenate([[0], np.cumsum(lens)[:-1]]),
                         lens)
        flat = self._postings[np.arange(total) + base]
        return np.unique(flat).astype(np.uint32, copy=False)

    def _narrow_by_prefix(self, src: bytes, lo: int, hi: int) -> tuple[int, int]:
        """Binary-search the vocab range sharing the pattern's literal
        prefix (the automaton's prefix-pruning role)."""
        prefix = _literal_prefix(src)
        if not prefix:
            return lo, hi
        new_lo = self._bisect_term(lo, hi, prefix)
        upper = _prefix_upper_bound(prefix)
        new_hi = self._bisect_term(new_lo, hi, upper) if upper else hi
        return new_lo, new_hi

    def _scan_scalar(self, src: bytes, pattern: re.Pattern,
                     lo: int, hi: int) -> list[int]:
        """Per-term matching tail for ranges the batched blob scan cannot
        soundly cover. A literal suffix (filters.literal_suffix) gates
        each term with a C-speed ``endswith`` before the Python regex
        engine ever runs — on adversarial backtracking patterns the
        endswith reject is the common case."""
        sfx = _literal_suffix(src)
        if sfx:
            return [i for i in range(lo, hi)
                    if self._term_at(i).endswith(sfx)
                    and pattern.fullmatch(self._term_at(i))]
        return [i for i in range(lo, hi)
                if pattern.fullmatch(self._term_at(i))]

    def _scan_vocab(self, src: bytes, pattern: re.Pattern,
                    lo: int, hi: int) -> list[int]:
        """Term indices in [lo, hi) fully matching the pattern: one
        C-speed multiline pass over the newline-joined vocab blob."""
        if lo >= hi:
            return []
        if not self._vocab_clean:
            return self._scan_scalar(src, pattern, lo, hi)
        start = int(self._term_off[lo])
        end = int(self._term_off[hi])
        blob = self._term_blob[start:end]
        try:
            rx = re.compile(b"(?m)^(?:" + src + b")$")
        except re.error:
            return self._scan_scalar(src, pattern, lo, hi)
        spans = [(m.start(), m.end()) for m in rx.finditer(blob)]
        if not spans:
            return []
        arr = np.asarray(spans, np.int64) + start
        offs = self._term_off[lo: hi + 1].astype(np.int64)  # one cast, reused
        idx = np.searchsorted(offs, arr[:, 0], side="right") - 1
        # zero-width matches at the very end of the blob land past the last
        # term; clamp before indexing and drop them via in_range
        in_range = (idx >= 0) & (idx < hi - lo)
        idx = np.clip(idx, 0, hi - lo - 1)
        # a match that consumed a term's trailing \n (pattern can match
        # newline: [^c]*, \D, ...) may have swallowed FOLLOWING terms that
        # match individually — finditer never revisits them, so the batched
        # scan is unsound for this pattern; fall back to per-term matching
        if bool((in_range & (arr[:, 1] >= offs[idx + 1])).any()):
            return self._scan_scalar(src, pattern, lo, hi)
        # full-term matches only: begin at the term start (rejects mid-term
        # hits of patterns containing \n) and end at the term's own \n
        valid = (in_range & (arr[:, 0] == offs[idx])
                 & (arr[:, 1] == offs[idx + 1] - 1))
        return lo + idx[valid]

    def postings_field(self, field: bytes) -> np.ndarray:
        fi = self._field_index(field)
        if fi < 0:
            return P.EMPTY
        lo, hi = self._term_range(fi)
        sl = self._postings[self._post_off[lo]: self._post_off[hi]]
        return np.unique(sl).astype(np.uint32, copy=False)

    def postings_all(self) -> np.ndarray:
        return np.arange(self.n_docs, dtype=np.uint32)

    # -- device-resident ragged CSR (index/device.py consumes these) --

    def postings_csr(self, term_idxs) -> tuple[np.ndarray, np.ndarray]:
        """(starts, lens) int64 rows of the flat postings column for the
        given absolute term indices — the host half of the ragged CSR
        a device postings program consumes (the offsets stay host-side;
        only the doc-id column lives on device)."""
        term_idxs = np.asarray(term_idxs, np.int64)
        starts = self._post_off[term_idxs].astype(np.int64)
        lens = self._post_off[term_idxs + 1].astype(np.int64) - starts
        return starts, lens

    def device_postings(self, mesh=None):
        """The flat doc-id postings column committed to device as int32,
        built once per sealed segment (and compute mesh) and cached
        forever (the segment is immutable, so seal/compaction time is the
        only transfer). Padded to a half-octave bucket so similarly-sized
        segments share device buffer shapes; the pad cells are never
        addressed by a valid CSR row, and the fused program's gather
        clips into them only for lanes it masks out anyway.

        On a compute mesh the column is committed REPLICATED, one copy
        per device: every device gathers its slice of the doc space from
        the whole column, and a column left on the first device would be
        re-broadcast to the others on every query."""
        col = self._device_postings.get(mesh)
        if col is None:
            import jax
            import jax.numpy as jnp

            from m3_tpu.utils import compute_stats, dispatch

            n = len(self._postings)
            host = np.zeros(dispatch.next_bucket(max(n, 64)), np.int32)
            host[:n] = self._postings
            if mesh is None:
                col = jnp.asarray(host)
            else:
                from m3_tpu.parallel.mesh import replicated_sharding

                col = jax.device_put(host, replicated_sharding(mesh))
            self._device_postings[mesh] = col
            # device-cache ledger: committed column bytes live as long
            # as the segment; a GC'd segment releases its share
            n_dev = len(col.sharding.device_set)
            _track_device_column(self, int(col.nbytes) * n_dev, n_dev)
            compute_stats.record_waste("postings", "column", n, host.size)
        return col

    # -- persistence --

    def to_bytes(self) -> bytes:
        return bytes(memoryview(self._buf)[: self._payload_len])


# -- device postings-column ledger ------------------------------------------
#
# Committed columns are cached forever on their (immutable) segment, so
# the only honest byte accounting is segment-lifetime: commit adds,
# segment GC subtracts (weakref.finalize). Registered as a
# compute_stats device-cache provider so /debug/compute and the soak
# trajectory see index device-memory pressure next to the hot tier's.

_dev_cols_lock = threading.Lock()
_dev_cols = {"entries": 0, "bytes": 0}
_dev_cols_width: dict = {}  # devices a column sits on -> live columns


def _dev_cols_stats() -> dict:
    with _dev_cols_lock:
        widths = [w for w, n in _dev_cols_width.items() if n > 0]
        # `devices`: how many devices the widest live column sits on (a
        # mesh that leaves every column on its first device shows 1)
        return {**_dev_cols, "devices": max(widths, default=0)}


def _untrack_device_column(nbytes: int, n_dev: int) -> None:
    with _dev_cols_lock:
        _dev_cols["entries"] -= 1
        _dev_cols["bytes"] -= nbytes
        _dev_cols_width[n_dev] -= 1


def _track_device_column(seg, nbytes: int, n_dev: int) -> None:
    from m3_tpu.utils import compute_stats

    with _dev_cols_lock:
        _dev_cols["entries"] += 1
        _dev_cols["bytes"] += nbytes
        _dev_cols_width[n_dev] = _dev_cols_width.get(n_dev, 0) + 1
    weakref.finalize(seg, _untrack_device_column, nbytes, n_dev)
    compute_stats.register_device_cache("postings_columns", _dev_cols_stats)


def build(docs) -> PackedSegment:
    """Pack an iterable of Documents (doc ids must be 0..D-1 in order)."""
    docs = list(docs)
    terms: dict[bytes, dict[bytes, list[int]]] = {}
    sid_parts: list[bytes] = []
    tag_parts: list[bytes] = []
    for d in docs:
        sid_parts.append(d.series_id)
        tag_parts.append(encode_tags(d.fields))
        for name, value in d.fields:
            terms.setdefault(name, {}).setdefault(value, []).append(d.doc_id)

    field_names = sorted(terms)
    fname_blob = b"".join(field_names)
    fname_off = np.zeros(len(field_names) + 1, "<u8")
    fname_off[1:] = np.cumsum([len(n) for n in field_names])

    term_parts: list[bytes] = []
    post_parts: list[np.ndarray] = []
    field_term_start = np.zeros(len(field_names) + 1, "<u8")
    for i, name in enumerate(field_names):
        vals = terms[name]
        vocab = sorted(vals)
        field_term_start[i + 1] = field_term_start[i] + len(vocab)
        for v in vocab:
            term_parts.append(v + b"\n")
            post_parts.append(np.asarray(sorted(set(vals[v])), dtype="<u4"))

    term_blob = b"".join(term_parts)
    n_terms = len(term_parts)
    term_off = np.zeros(n_terms + 1, "<u8")
    term_off[1:] = np.cumsum([len(t) for t in term_parts])
    post_off = np.zeros(n_terms + 1, "<u8")
    post_off[1:] = np.cumsum([len(p) for p in post_parts])
    postings = (np.concatenate(post_parts) if post_parts
                else np.empty(0, "<u4")).astype("<u4", copy=False)

    sid_blob = b"".join(sid_parts)
    sid_off = np.zeros(len(docs) + 1, "<u8")
    sid_off[1:] = np.cumsum([len(s) for s in sid_parts])
    tag_blob = b"".join(tag_parts)
    tag_off = np.zeros(len(docs) + 1, "<u8")
    tag_off[1:] = np.cumsum([len(t) for t in tag_parts])

    header = _HDR.pack(len(docs), len(sid_blob), len(tag_blob),
                       len(field_names), len(fname_blob), n_terms,
                       len(term_blob), len(postings), 0)
    out = bytearray(MAGIC + header)

    def pad(b: bytearray) -> None:
        b.extend(b"\0" * (_align8(len(b)) - len(b)))

    pad(out)
    for arr, raw in (
        (sid_off, sid_blob), (tag_off, tag_blob), (fname_off, fname_blob),
    ):
        out += arr.tobytes()
        out += raw
        pad(out)
    out += field_term_start.tobytes()
    out += term_off.tobytes()
    out += term_blob
    pad(out)
    out += post_off.tobytes()
    out += postings.tobytes()
    return PackedSegment(bytes(out))


def merge(segments: list) -> PackedSegment:
    """Compaction merge: dedupe series across segments, re-base doc ids
    (the multi_segments_builder role,
    /root/reference/src/m3ninx/index/segment/builder/multi_segments_builder.go)."""
    seen: set[bytes] = set()
    out: list[Document] = []
    for seg in segments:
        for d in seg.docs:
            if d.series_id in seen:
                continue
            seen.add(d.series_id)
            out.append(Document(len(out), d.series_id, d.fields))
    return build(out)



"""Ragged (offsets, lengths) columnar layout kernels (ROADMAP #3).

Following PAPERS.md "Ragged Paged Attention" (TPU kernels over ragged,
paged KV blocks): variable-length series stay CONCATENATED with an
(offsets) index vector — CSR, the layout `query/windows.RaggedSeries`
and the whole-query compiler's slab prep already consume — instead of
being padded to rectangles or materialized as one Python array pair per
series.  This module is the pure-kernel layer of that layout, shared by
the storage read finalize (`Namespace._assemble_read`,
`Shard.finish_read_many`), the paged buffer
seal (`ShardBuffer.seal_csr`) and the length-bucketed ragged encode
(`hostpath.encode_blocks_ragged`):

- ``merge_csr`` is the batched twin of ``buffer.merge_dedup``: one
  vectorized sortedness probe over EVERY row at once, one global
  lexsort + keep-last dedup only when some row actually needs it, one
  compress pass for the range filter — replacing the per-series
  ``np.concatenate`` + ``merge_dedup`` calls that profiled at ~15% of
  the sparse read path (PR 14 handoff).
- ``assemble_rows`` builds the CSR from per-row part lists with ONE
  bytes join a column (``join_rows``), then one ``merge_csr`` — no
  per-series concatenate objects, and no release of the interpreter
  lock per part.
- ``length_buckets`` groups rows of similar length so a batched
  rectangular consumer (the device block encoder) pads each row only to
  its bucket's max, never the global max — the ingest-side padding tax.
- ``bf16_pack``/``bf16_unpack`` are the reduced-precision page mirror
  (EQuARX's quantized-collective argument applied to the device-resident
  hot tier, and the seam ROADMAP #4's quantized wire format reuses):
  round-to-nearest-even truncation of float32 to its high 16 bits.

Parity discipline matches the stage kernels in ops/temporal.py: every
function here is pure, and the seeded property sweep in
tests/test_paged_memory.py pins exact NaN masks / exact uint64 bit
patterns against the per-series reference implementations, including
empty, singleton and page-boundary-straddling rows.
"""

from __future__ import annotations

import numpy as np


def row_ids(offsets: np.ndarray) -> np.ndarray:
    """Per-sample row id vector for a CSR offsets array."""
    return np.repeat(np.arange(len(offsets) - 1, dtype=np.int64),
                     np.diff(offsets))


def rows_strictly_increasing(times: np.ndarray, offsets: np.ndarray) -> bool:
    """True when every row's times are strictly increasing (the
    merge_dedup fast-path predicate, probed for ALL rows in one pass)."""
    n = len(times)
    if n <= 1:
        return True
    ok = times[1:] > times[:-1]
    # adjacent pairs that straddle a row boundary don't constrain order
    starts = offsets[1:-1]
    b = starts[(starts > 0) & (starts < n)]
    if len(b):
        ok = ok.copy()
        ok[np.asarray(b, np.int64) - 1] = True
    return bool(ok.all())


def merge_csr(times: np.ndarray, vbits: np.ndarray, offsets: np.ndarray,
              start_ns: int | None = None, end_ns: int | None = None):
    """``merge_dedup`` applied to every CSR row at once.

    Row semantics are identical to calling ``merge_dedup(row_t, row_v,
    start_ns, end_ns)`` per row: stable sort by time with later appends
    winning timestamp ties, then the half-open range filter.  The fast
    path (every row already strictly increasing — decoded blocks in time
    order with no buffer overlap, the steady-state read) costs one
    vectorized probe + at most one compress; only when some row is out
    of order or duplicated does the global lexsort run.
    """
    n = len(times)
    if n == 0:
        return times, vbits, offsets.astype(np.int64, copy=False)
    if not rows_strictly_increasing(times, offsets):
        rid = row_ids(offsets)
        order = np.lexsort((np.arange(n), times, rid))
        times, vbits, rid = times[order], vbits[order], rid[order]
        keep = np.ones(n, bool)
        same = (rid[1:] == rid[:-1]) & (times[1:] == times[:-1])
        keep[:-1] = ~same
        if start_ns is not None:
            keep &= times >= start_ns
        if end_ns is not None:
            keep &= times < end_ns
        counts = np.bincount(rid[keep], minlength=len(offsets) - 1)
        new_offsets = np.empty(len(offsets), np.int64)
        new_offsets[0] = 0
        np.cumsum(counts, out=new_offsets[1:])
        return times[keep], vbits[keep], new_offsets
    sel = None
    if start_ns is not None:
        sel = times >= start_ns
    if end_ns is not None:
        m = times < end_ns
        sel = m if sel is None else (sel & m)
    if sel is None or bool(sel.all()):
        return times, vbits, offsets.astype(np.int64, copy=False)
    ksum = np.empty(n + 1, np.int64)
    ksum[0] = 0
    np.cumsum(sel, out=ksum[1:])
    return times[sel], vbits[sel], ksum[np.asarray(offsets, np.int64)]


def assemble_rows(parts_rows: list[list[tuple[np.ndarray, np.ndarray]]],
                  start_ns: int | None = None, end_ns: int | None = None):
    """(times, vbits, offsets) CSR from per-row part lists: ``join_rows``
    then ``merge_csr``'s last-write-wins and range filter."""
    times, vbits, offsets, _, _ = join_rows(parts_rows)
    return merge_csr(times, vbits, offsets, start_ns, end_ns)


_TIMES = np.dtype(np.int64)
_VBITS = np.dtype(np.uint64)


def _join(parts: list, dtype: np.dtype) -> tuple[np.ndarray, list[int]]:
    """ONE copy of ``parts`` into a new column of ``dtype``, bit for bit
    ``np.concatenate`` of the parts cast to ``dtype``.  A bytes join
    holds the interpreter lock for the whole copy; ``np.concatenate``
    gives it away and takes it back around every part longer than
    numpy's threshold (about 500 elements), and under load each take
    waits behind every other thread.  A part that is not a C-contiguous
    array of ``dtype`` is first made one in place in ``parts``; their
    indices come back."""
    apart = []
    for k, p in enumerate(parts):
        if p.dtype != dtype or not p.flags.c_contiguous:
            parts[k] = np.ascontiguousarray(p, dtype)
            apart.append(k)
    return np.frombuffer(bytearray().join(parts), dtype), apart


def join_rows(parts_rows: list[list[tuple[np.ndarray, np.ndarray]]]):
    """Unmerged (times, vbits, offsets) CSR of per-row part lists, with
    the number of parts it copied and of those that first needed a
    conversion copy of their own (not C-contiguous, or not int64 times
    / uint64 value bits).

    The outer loop only FLATTENS (list appends); the data moves in ONE
    copy per column (``_join``) — no per-series concatenate objects, no
    per-part slice assigns.  Rows arrive in order, so part data is
    already row-contiguous and the offsets come from a length scatter;
    each row's part order is preserved, which is what keeps
    ``merge_csr``'s keep-last conflict resolution identical to the
    serial path's filesets-then-buffer append order.
    """
    R = len(parts_rows)
    flat_t: list = []
    flat_v: list = []
    rows_of: list = []
    lens_of: list = []
    # hot flatten loop (one iteration per (series, part)): bound methods
    # hoisted — at a million parts the attribute lookups are the loop
    ft, fv, ro, lo = (flat_t.append, flat_v.append, rows_of.append,
                      lens_of.append)
    for i, parts in enumerate(parts_rows):
        for t, v in parts:
            n = t.shape[0]
            if n:
                ft(t)
                fv(v)
                ro(i)
                lo(n)
    offsets = np.zeros(R + 1, np.int64)
    if not flat_t:
        return np.empty(0, np.int64), np.empty(0, np.uint64), offsets, 0, 0
    # rows_of is non-decreasing (outer loop order): a weighted bincount
    # scatters the per-part lengths into per-row counts in one pass
    counts = np.bincount(np.asarray(rows_of, np.int64),
                         weights=np.asarray(lens_of, np.float64),
                         minlength=R).astype(np.int64)
    np.cumsum(counts, out=offsets[1:])
    times, apart_t = _join(flat_t, _TIMES)
    vbits, apart_v = _join(flat_v, _VBITS)
    return times, vbits, offsets, len(flat_t), len(set(apart_t + apart_v))


def pairs_to_csr(pairs: list[tuple[np.ndarray, np.ndarray]]):
    """(times, vbits, offsets) from per-row (times, vbits) pairs — the
    compatibility ramp for callers that still produce per-series arrays
    (datapoint-limit chunked reads, cluster facades)."""
    R = len(pairs)
    offsets = np.empty(R + 1, np.int64)
    offsets[0] = 0
    np.cumsum(np.fromiter((len(t) for t, _ in pairs), np.int64, R),
              out=offsets[1:])
    if R == 0 or offsets[-1] == 0:
        return np.empty(0, np.int64), np.empty(0, np.uint64), offsets
    times = np.concatenate([t for t, _ in pairs])
    vbits = np.concatenate([v for _, v in pairs])
    return times, vbits.astype(np.uint64, copy=False), offsets


def split_csr(times: np.ndarray, vbits: np.ndarray, offsets: np.ndarray
              ) -> list[tuple[np.ndarray, np.ndarray]]:
    """Per-row (times, vbits) views of a CSR — the inverse ramp of
    ``pairs_to_csr`` for callers that still consume per-series pairs
    (Session.fetch_many, the read_many facades).  Rows are zero-copy
    slices of the CSR columns, so a wire frame landed by
    ``utils/wire.unpack_samples`` fans out to per-series consumers
    without duplicating the sample volume."""
    return [(times[offsets[i]:offsets[i + 1]],
             vbits[offsets[i]:offsets[i + 1]])
            for i in range(len(offsets) - 1)]


def length_buckets(lens: np.ndarray, factor: float = 2.0):
    """Row-index groups of geometrically-similar length: within a
    bucket every row is within ``factor`` of the bucket max, so padding
    each bucket to ITS max wastes < factor x the real sample volume —
    vs the one-rectangle pad to the GLOBAL max, which a single long
    row blows up to O(rows x max_len).  Zero-length rows come back as
    their own group (callers usually skip them).  Returns a list of
    int64 row-index arrays, together covering arange(len(lens))."""
    lens = np.asarray(lens, np.int64)
    if len(lens) == 0:
        return []
    buckets = np.zeros(len(lens), np.int64)
    pos = lens > 0
    if pos.any():
        buckets[pos] = 1 + np.floor(
            np.log(lens[pos].astype(np.float64)) / np.log(factor)
        ).astype(np.int64)
    out = []
    for b in np.unique(buckets):
        out.append(np.nonzero(buckets == b)[0].astype(np.int64))
    return out


def csr_to_padded(times: np.ndarray, vbits: np.ndarray,
                  offsets: np.ndarray, rows: np.ndarray):
    """Padded [len(rows), max_len] (times, vbits, n_points) for a set of
    CSR rows — the rectangular view one length bucket hands the batched
    block encoder.  Timestamp padding repeats each row's LAST value (the
    rows are time-sorted, so that is the row max — the same monotone-pad
    rule `ShardBuffer.seal` uses so masked encoder lanes see sane
    deltas); value padding is zero."""
    rows = np.asarray(rows, np.int64)
    lens = (offsets[rows + 1] - offsets[rows]).astype(np.int64)
    B = len(rows)
    T = int(lens.max()) if B else 0
    T = max(T, 1)
    out_t = np.zeros((B, T), np.int64)
    out_v = np.zeros((B, T), np.uint64)
    if B == 0:
        return out_t, out_v, lens.astype(np.int32)
    row_pos = np.repeat(np.arange(B), lens)
    cum = np.empty(B, np.int64)
    cum[0] = 0
    np.cumsum(lens[:-1], out=cum[1:])
    col = np.arange(int(lens.sum())) - np.repeat(cum, lens)
    src = np.repeat(offsets[rows], lens) + col
    out_t[row_pos, col] = times[src]
    out_v[row_pos, col] = vbits[src]
    nonempty = lens > 0
    if nonempty.any():
        last = times[(offsets[rows + 1] - 1)[nonempty]]
        pad_mask = np.arange(T)[None, :] >= lens[nonempty, None]
        sub = out_t[nonempty]
        out_t[nonempty] = np.where(pad_mask, last[:, None], sub)
    return out_t, out_v, lens.astype(np.int32)


# ---------------------------------------------------------------------------
# reduced-precision page mirror (the EQuARX argument: where the
# consumer's output tolerance permits, ship/hold half the bytes)
# ---------------------------------------------------------------------------


def bf16_pack(values: np.ndarray) -> np.ndarray:
    """float64 -> uint16 bfloat16 bit patterns (round-to-nearest-even on
    the float32 intermediate — the hardware bf16 conversion rule). NaN
    payloads collapse to the canonical quiet NaN so masks survive.

    This numpy pair is the REFERENCE semantics of the hot tier's device
    mirror (which converts with ``astype(jnp.bfloat16)`` on device) and
    the host-side codec seam ROADMAP #4's quantized wire format adopts;
    tests/test_paged_memory.py pins the two conversions value-equal so
    they cannot drift."""
    with np.errstate(over="ignore"):  # finite > f32 max rounds to inf
        f32 = np.asarray(values, np.float64).astype(np.float32)
    u32 = f32.view(np.uint32)
    rounded = u32 + 0x7FFF + ((u32 >> 16) & 1)
    out = (rounded >> 16).astype(np.uint16)
    nan = np.isnan(f32)
    if nan.any():
        out = np.where(nan, np.uint16(0x7FC0), out)
    return out


def bf16_unpack(packed: np.ndarray) -> np.ndarray:
    """uint16 bfloat16 bit patterns -> float64."""
    u32 = packed.astype(np.uint32) << 16
    return u32.view(np.float32).astype(np.float64)

"""Device-compiled inverted index: postings algebra as fused ragged
tensor programs (ROADMAP #4).

The reference evaluates label matchers with per-segment searcher loops
(/root/reference/src/m3ninx/search/searcher/conjunction.go:78-111) and
the seed kept that shape: each matcher materializes a host postings
array, then sorted-array set ops (or, past a threshold, host-built
bitmaps shipped to `ops/bitmaps` kernels) combine them. At a million
series the materialize-then-combine walk IS the latency — every matcher
pays a host union, every combine pays a transfer.

This module lowers the whole boolean combine onto the compute plane:

- Each sealed ``PackedSegment`` already stores its postings as a ragged
  CSR (flat doc-id column + per-term offsets). ``device_postings()``
  commits the column once per segment; only the selected (starts, lens)
  rows cross per query — the paged-ragged layout argument of `ops/ragged`
  applied to the index.
- Matcher resolution stays host-side and cheap: term bisect, literal
  prefix/suffix narrowed regex scans (`metrics/filters`), all LRU-cached
  on the immutable segment.
- The AND/OR/NOT combine across matchers compiles to ONE fused jit
  program per (n_pos, n_neg, conjunction, mesh) signature: a vmapped
  ragged gather expands each matcher's CSR rows to doc-membership bits,
  `ops/bitmaps.words_from_bool` packs them to uint64 words, and the
  word-wise reductions produce the result mask — no intermediate
  postings arrays, no per-matcher transfers. Shape buckets (half-octave
  on the rows/postings axes, word-aligned on the doc axis) bound the
  compile count, `dispatch.jit_tracker` proves cache behaviour.
- On an active ``("series",)`` compute mesh (PR 12) the packed word
  tensor is sharding-constrained to ``P(None, "series")`` — each device
  scatters and intersects only its own slice of the doc space; the
  reduced mask is replicated. Pure boolean algebra, so results are
  bit-identical at any device count.

Dispatch doctrine: the executor's scalar walk stays the counted
fallback — unpacked segments, nested boolean shapes and small work
never pay device overhead, and every fallback is
recorded with a reason (`querystats` index block, `dispatch` counters).
"""

from __future__ import annotations

import functools

import numpy as np

from m3_tpu.index import postings as P
from m3_tpu.index.query import (
    AllQuery,
    ConjunctionQuery,
    DisjunctionQuery,
    FieldQuery,
    NegationQuery,
    RegexpQuery,
    TermQuery,
)
from m3_tpu.utils import dispatch, querystats

# same economics as the executor's bitmap threshold: below this many
# (selected postings + doc-space) elements the sorted-array walk wins
WORK_THRESHOLD = 1 << 17

_LEAVES = (TermQuery, RegexpQuery, FieldQuery)


def _fallback(reason: str):
    """Counted and explained, never an error: dispatch tally, registry
    counter (compute.index fallback{reason=...}) — the querystats
    fallback record is the executor's (it owns per-segment accounting)."""
    from m3_tpu.utils.instrument import default_registry

    dispatch.record("index.postings", False)
    default_registry().root_scope("compute").subscope(
        "index", reason=reason).counter("fallback")
    return None, reason


def _classify(query):
    """(conjunction, positive_leaves, negative_leaves) for a covered
    boolean shape, or a fallback-reason string. Covered: one AND or OR
    level over term/regexp/field leaves, with negation (of a leaf) only
    under AND — exactly the shapes `query.matchers_to_query` emits."""
    if isinstance(query, ConjunctionQuery):
        pos, neg = [], []
        for q in query.queries:
            if isinstance(q, AllQuery):
                continue  # AND identity
            if isinstance(q, NegationQuery):
                if not isinstance(q.inner, _LEAVES):
                    return "nested_boolean"
                neg.append(q.inner)
            elif isinstance(q, _LEAVES):
                pos.append(q)
            else:
                return "nested_boolean"
        if not pos and not neg:
            return "trivial_query"  # pure match-all: host shortcut
        return True, pos, neg
    if isinstance(query, DisjunctionQuery):
        pos = []
        for q in query.queries:
            if isinstance(q, AllQuery):
                return "trivial_query"  # OR absorbs to match-all
            if isinstance(q, _LEAVES):
                pos.append(q)
            else:
                return "nested_boolean"
        if not pos:
            return "trivial_query"  # empty OR: host returns EMPTY
        return False, pos, []
    return "nested_boolean"


def _resolve(seg, leaf) -> np.ndarray:
    """Absolute term indices a leaf selects — the host half of matcher
    evaluation (bisect / narrowed regex scan, all cached on the
    immutable segment). The device program never sees terms, only the
    CSR rows these indices name."""
    if isinstance(leaf, TermQuery):
        fi = seg._field_index(leaf.field_name)
        if fi < 0:
            return np.empty(0, np.int64)
        lo, hi = seg._term_range(fi)
        i = seg._bisect_term(lo, hi, leaf.value)
        if i < hi and seg._term_at(i) == leaf.value:
            return np.asarray([i], np.int64)
        return np.empty(0, np.int64)
    if isinstance(leaf, RegexpQuery):
        return seg.term_indices_regexp(leaf.field_name, leaf.compiled())
    fi = seg._field_index(leaf.field_name)
    if fi < 0:
        return np.empty(0, np.int64)
    lo, hi = seg._term_range(fi)
    return np.arange(lo, hi, dtype=np.int64)


@functools.lru_cache(maxsize=None)
def _program(n_pos: int, n_neg: int, conjunction: bool, mesh):
    """ONE fused program per matcher-shape signature: ragged gather ->
    membership scatter -> word pack -> boolean reduce. Data shapes vary
    only through the static (lb, npad) buckets and the committed column
    length, so recompiles stay O(log) per axis."""
    import jax
    import jax.numpy as jnp

    from m3_tpu.ops import bitmaps

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        words_sharding = NamedSharding(mesh, PartitionSpec(None, "series"))

    # not `run`: the trace names a program after its function, and
    # `jit_run` is the query plan's (query/compiler.py)
    def postings_run(col, starts, lens, *, lb, npad):
        def member(starts_m, lens_m):
            # expand this matcher's CSR rows into flat column positions:
            # lane j of lb belongs to row rid[j] at row-local offset
            # (j - exclusive_prefix[rid[j]])
            k = starts_m.shape[0]
            rid = jnp.repeat(jnp.arange(k, dtype=jnp.int32), lens_m,
                             total_repeat_length=lb)
            lane = jnp.arange(lb, dtype=jnp.int32)
            valid = lane < lens_m.sum()
            cum = jnp.cumsum(lens_m) - lens_m  # exclusive prefix
            idx = starts_m[rid] + (lane - cum[rid])
            ids = col[jnp.clip(idx, 0, col.shape[0] - 1)]
            # invalid lanes (repeat padding) scatter into the dump slot
            # npad-1, which the host decode discards with ids >= n_docs
            tgt = jnp.where(valid, ids, npad - 1)
            return jnp.zeros(npad, jnp.bool_).at[tgt].set(True)

        bits = jax.vmap(member)(starts, lens)          # [M, npad] bool
        words = bitmaps.words_from_bool(bits)          # [M, W] uint64
        if mesh is not None:
            # each device owns a contiguous slice of the doc-space words:
            # scatter+reduce stay device-local, the result mask replicates
            words = jax.lax.with_sharding_constraint(words, words_sharding)
        with jax.named_scope("m3.postings.intersect"):
            if conjunction:
                acc = bitmaps.and_reduce_words(words[:n_pos])
            else:
                acc = bitmaps.or_reduce_words(words[:n_pos])
            if n_neg:
                acc = acc & ~bitmaps.or_reduce_words(words[n_pos:])
        return acc

    return jax.jit(postings_run, static_argnames=("lb", "npad"))


def match(seg, query):
    """Evaluate one boolean query against one segment on the compute
    plane. Returns ``(doc_ids, None)`` on success — sorted unique
    uint32, bit-identical to the scalar walk — or ``(None, reason)``
    when this (segment, query, process) should take the counted
    fallback."""
    if not hasattr(seg, "postings_csr"):
        return _fallback("unpacked_segment")
    shape = _classify(query)
    if isinstance(shape, str):
        return _fallback(shape)
    conjunction, pos_leaves, neg_leaves = shape

    sels = [_resolve(seg, q) for q in pos_leaves + neg_leaves]
    n_pos = len(pos_leaves)
    if conjunction and any(len(s) == 0 for s in sels[:n_pos]):
        # a positive matcher selected no terms: AND is empty, no program
        dispatch.record("index.postings", True)
        querystats.record_index(postings_rows=sum(len(s) for s in sels))
        return P.EMPTY, None

    csrs = [seg.postings_csr(s) for s in sels]
    totals = [int(lens.sum()) for _, lens in csrs]
    if not dispatch.use_device(sum(totals) + seg.n_docs, WORK_THRESHOLD):
        return _fallback("small_work")

    from m3_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.active_compute_mesh()
    n_dev = int(mesh.devices.size) if mesh is not None else 1

    import jax.numpy as jnp

    M = len(csrs)
    kb = dispatch.next_bucket(max(max(len(s) for s in sels), 1))
    lb = dispatch.next_bucket(max(max(totals), 64))
    npad = dispatch.next_bucket(seg.n_docs + 1, multiple=64 * n_dev)
    starts = np.zeros((M, kb), np.int32)
    lens = np.zeros((M, kb), np.int32)
    for m, (s, ln) in enumerate(csrs):
        starts[m, : len(s)] = s
        lens[m, : len(ln)] = ln

    from m3_tpu.utils.instrument import default_registry

    col = seg.device_postings(mesh)
    prog = _program(n_pos, M - n_pos, conjunction, mesh)
    # padding-waste ledger: selected CSR rows vs the kb bucket, postings
    # lanes vs lb, doc-space bits vs the word-aligned npad
    from m3_tpu.utils import compute_stats

    compute_stats.record_waste("postings", "terms",
                               sum(len(s) for s in sels), M * kb)
    compute_stats.record_waste("postings", "lanes", sum(totals), M * lb)
    compute_stats.record_waste("postings", "docs", seg.n_docs + 1, npad)
    sig = (f"P{n_pos}N{M - n_pos}{'&' if conjunction else '|'}"
           f"|K{kb}|L{lb}|D{npad}" + (f"|M{n_dev}" if mesh else ""))
    starts_d, lens_d = jnp.asarray(starts), jnp.asarray(lens)
    with dispatch.jit_tracker("postings_program", prog, sig=sig) as tracker:
        w = np.asarray(prog(col, starts_d, lens_d, lb=lb, npad=npad))
    dispatch.record("index.postings", True)
    sc = default_registry().root_scope("compute").subscope("index")
    sc.counter("device")
    # program wall time, the wait for the mask included; on a shape-cache
    # miss this includes the trace+compile (compute.jit{op=
    # postings_program} splits that out)
    sc.observe("postings_seconds", tracker.seconds)
    querystats.record_index(postings_rows=sum(len(s) for s in sels))

    bits = np.unpackbits(w.view(np.uint8), bitorder="little")
    ids = np.nonzero(bits)[0]
    return ids[ids < seg.n_docs].astype(np.uint32), None

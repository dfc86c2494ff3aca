"""Retention-tier read resolution: route a query's time range to the raw
and/or downsampled namespaces and stitch the results.

Role parity with the reference's aggregated-namespace fanout
(/root/reference/src/query/storage/m3/cluster_resolver.go:34-120 — choose
unaggregated vs per-policy aggregated namespaces by retention coverage,
preferring completeness then resolution — and storage.go:183-757, which
merges the fan-out). Without this, downsampled data is write-only: a query
past raw retention would return nothing even though the 1m rollup holds it
(round-4 VERDICT missing #1).

Selection semantics (the reference's "default" fanout option):
- if the unaggregated namespace covers the query start, read it alone;
- otherwise read every namespace that intersects the range, finest
  resolution first, and stitch per series: each series takes the finer
  tier's samples from that tier's earliest sample onward and fills the
  older span from coarser tiers — so a rate() spanning the boundary sees
  one continuous, deduplicated stream.

Cheapest-tier resolution (resolve_read, ROADMAP #2): BEFORE the coverage
fallback above, a query whose step is coarse enough is routed to the
cheapest (coarsest-resolution) COMPLETE aggregated namespace that covers
its range — long-range dashboards read tiny pre-aggregated series
instead of decoding raw samples. `M3_TPU_TIER_RESOLVE=0` pins reads to
the retention-driven path (raw within retention) for parity testing.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Tier:
    name: str
    resolution_ns: int  # 0 = raw
    retention_ns: int
    complete: bool = False  # holds EVERY metric (downsample-all fed)


def namespace_tiers(db) -> list[Tier]:
    """Every namespace as a tier, from its options."""
    out = []
    for name in list(db.namespaces):
        ns = db.namespaces[name]
        opts = getattr(ns, "opts", None)
        if opts is None:
            continue
        out.append(Tier(name, opts.aggregated_resolution_ns,
                        opts.retention.retention_ns,
                        getattr(opts, "aggregated_complete", False)))
    return out


def tier_resolution_enabled() -> bool:
    """M3_TPU_TIER_RESOLVE=0 disables cheapest-tier selection (reads pin
    to the retention-driven raw path). Read per query so operators and
    parity tests can flip the hatch on a live process."""
    return os.environ.get("M3_TPU_TIER_RESOLVE") != "0"


def resolve_read(db, unagg: str, t_min: int, t_max: int, step_ns: int,
                 range_ns: int = 0, now_ns: int | None = None
                 ) -> tuple[list[str], dict]:
    """Namespaces to read for one selector fetch, plus the tier-choice
    record the explain surface reports.

    Choice matrix (cheapest covering tier wins):
    - candidates are COMPLETE aggregated tiers whose resolution covers
      the requested grid (resolution <= step) and window (2*resolution
      <= range for range selectors — a rate needs >= 2 samples per
      window) and whose retention covers the range start;
    - among candidates the COARSEST resolution wins (fewest samples
      decoded); resolution ties break to the longer retention, then the
      lexically smaller name (determinism);
    - no candidate (fine step, partial tiers, uncovered range) falls
      back to the retention-driven resolve_namespaces fanout: raw alone
      when it covers, else finest-first stitching.
    """
    now_ns = now_ns if now_ns is not None else time.time_ns()
    if not tier_resolution_enabled():
        return [unagg], {"mode": "pinned_raw", "namespaces": [unagg]}
    if step_ns > 0:
        best = None
        for t in namespace_tiers(db):
            if t.name == unagg or t.resolution_ns <= 0 or not t.complete:
                continue
            if t.resolution_ns > step_ns:
                continue
            if range_ns and 2 * t.resolution_ns > range_ns:
                continue
            if now_ns - t.retention_ns > t_min:
                continue
            pref = (t.resolution_ns, t.retention_ns)
            if (best is None
                    or pref > (best.resolution_ns, best.retention_ns)
                    or (pref == (best.resolution_ns, best.retention_ns)
                        and t.name < best.name)):
                best = t
        if best is not None:
            return [best.name], {
                "mode": "aggregated", "namespaces": [best.name],
                "resolution_ns": best.resolution_ns,
                "retention_ns": best.retention_ns,
                "step_ns": step_ns,
            }
    ns_list = resolve_namespaces(db, unagg, t_min, t_max, now_ns)
    mode = "raw" if ns_list == [unagg] else "stitched"
    return ns_list, {"mode": mode, "namespaces": list(ns_list),
                     "step_ns": step_ns}


def resolve_namespaces(db, unagg: str, t_min: int, t_max: int,
                       now_ns: int | None = None) -> list[str]:
    """Ordered namespaces to read for [t_min, t_max): finest first.

    Mirrors cluster_resolver.go's coverage rule: a tier covers the query
    when now - retention <= t_min. The unaggregated tier wins outright
    when it covers; otherwise all intersecting tiers fan out, ordered
    raw-then-increasing-resolution so the stitch prefers finer data.
    """
    now_ns = now_ns if now_ns is not None else time.time_ns()
    tiers = namespace_tiers(db)
    raw = next((t for t in tiers if t.name == unagg), None)
    if raw is None:
        # no tier metadata for the unaggregated namespace (e.g. a cluster
        # client DB exposing remote namespaces without local options):
        # tier resolution cannot apply — read it directly, old behavior
        return [unagg]
    if now_ns - raw.retention_ns <= t_min:
        return [unagg]
    # tiers that hold ANY of the range (now - retention < t_max)
    live = [t for t in tiers if now_ns - t.retention_ns < t_max]
    agg = sorted((t for t in live if t.name != unagg and t.resolution_ns > 0),
                 key=lambda t: t.resolution_ns)
    out = [t.name for t in ([raw] if raw in live else [])] + [t.name for t in agg]
    return out or [unagg]


def fetch_tagged_ragged(db, namespaces: list[str], index_query, t_min: int,
                        t_max: int, limit=None, keep_empty: bool = False,
                        warnings: list | None = None):
    """Single-tier fast path of fetch_tagged returning the RAGGED CSR
    (docs, times, value_bits, offsets) — or None when the shape needs
    the stitching path (multi-tier fanout, cluster facades without a
    ragged surface).  Row order matches fetch_tagged exactly: matched
    docs in index order with empty series dropped (or appended at the
    end under keep_empty) — dropping/reordering empty rows never moves
    sample data, so the CSR arrays come through untouched."""
    from m3_tpu.utils import querystats, trace

    if len(namespaces) != 1:
        return None
    ns = db.namespaces[namespaces[0]]
    # capability marker, NOT hasattr: delegating facades (fanout) would
    # resolve a hasattr probe through __getattr__ to the local namespace
    # and this fast path would silently skip their remote legs
    if not getattr(ns, "supports_ragged_read", False):
        return None
    with trace.stage(trace.STAGE_QUERY_IDS):
        if limit is not None:
            docs = ns.query_ids(index_query, t_min, t_max, limit=limit)
        else:
            docs = ns.query_ids(index_query, t_min, t_max)
    querystats.record(series_matched=len(docs))
    ids = [d.series_id for d in docs]
    with trace.stage(trace.STAGE_READ_MANY):
        if warnings is not None and getattr(ns, "supports_read_warnings",
                                            False):
            # cluster facade on the CSR path: its partial-read warnings
            # thread through the same per-call out-param fetch_tagged
            # uses (never read back from shared facade state)
            times, vbits, offsets = ns.read_many_ragged(
                ids, t_min, t_max, warnings=warnings)
        else:
            times, vbits, offsets = ns.read_many_ragged(ids, t_min, t_max)
    lens = np.diff(offsets)
    if not (lens == 0).any():
        return docs, times, vbits, offsets
    nz = np.nonzero(lens > 0)[0]
    order = np.concatenate([nz, np.nonzero(lens == 0)[0]]) \
        if keep_empty else nz
    docs = [docs[i] for i in order.tolist()]
    new_offsets = np.empty(len(order) + 1, np.int64)
    new_offsets[0] = 0
    np.cumsum(lens[order], out=new_offsets[1:])
    return docs, times, vbits, new_offsets


def fetch_tagged(db, namespaces: list[str], index_query, t_min: int,
                 t_max: int, limit=None, keep_empty: bool = False,
                 warnings: list | None = None):
    """Query + read the namespaces and stitch per series.

    Returns (docs, [(times, value_bits)]) aligned lists, one entry per
    distinct series id across all tiers. Stitch rule: walk tiers finest →
    coarsest; a coarser tier only contributes samples OLDER than the
    earliest sample already held for that series (no interleaving — the
    overlap region is served by the finer tier alone, the reference's
    completeness preference).

    Each tier's read is ONE batched read_many — storage makes it one
    fetch per (shard, block, volume) group and one decode dispatch (or one
    RPC per node on cluster facades), so a 10k-series PromQL fetch costs
    a handful of decode dispatches, not 10k.

    ``warnings`` (out-param) accumulates the ReadWarnings degraded
    cluster facades recorded for these reads — the engine carries them to
    its results and the HTTP layer to response headers (PR-2 contract).
    It is threaded INTO facades advertising ``supports_read_warnings``
    (fanout, cluster session) as their own warnings= out-param, the
    per-call thread-safe channel — never read back from shared facade
    state, which concurrent queries would cross-contaminate.
    """
    from m3_tpu.utils import querystats, trace

    by_id: dict[bytes, list] = {}  # id -> [doc, times, vbits]
    empties: dict[bytes, object] = {}  # matched but no samples anywhere
    for ns_name in namespaces:
        ns = db.namespaces[ns_name]
        kw = {"warnings": warnings} if warnings is not None and \
            getattr(ns, "supports_read_warnings", False) else {}
        with trace.stage(trace.STAGE_QUERY_IDS):
            if limit is not None:
                docs = ns.query_ids(index_query, t_min, t_max, limit=limit,
                                    **kw)
            else:
                docs = ns.query_ids(index_query, t_min, t_max, **kw)
        querystats.record(series_matched=len(docs))
        ids = [d.series_id for d in docs]
        with trace.stage(trace.STAGE_READ_MANY):
            results = ns.read_many(ids, t_min, t_max, **kw)
        for doc, (times, vbits) in zip(docs, results):
            if len(times) == 0:
                if keep_empty and doc.series_id not in by_id:
                    empties.setdefault(doc.series_id, doc)
                continue
            cur = by_id.get(doc.series_id)
            if cur is None:
                by_id[doc.series_id] = [doc, times, vbits]
                continue
            cutoff = cur[1][0]  # earliest finer-tier sample
            older = times < cutoff
            if older.any():
                cur[1] = np.concatenate([times[older], cur[1]])
                cur[2] = np.concatenate([vbits[older], cur[2]])
    docs_out, series_out = [], []
    for doc, times, vbits in by_id.values():
        docs_out.append(doc)
        series_out.append((times, vbits))
    for sid, doc in empties.items():
        if sid not in by_id:
            docs_out.append(doc)
            series_out.append((np.empty(0, np.int64), np.empty(0, np.uint64)))
    return docs_out, series_out

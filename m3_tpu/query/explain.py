"""PromQL EXPLAIN / EXPLAIN ANALYZE: the engine's resolved plan as a
structured tree, with per-stage attribution.

Role parity with a SQL engine's EXPLAIN over the reference's executor
pipeline (the transform-node DAG in
/root/reference/src/query/executor/state.go, which the reference never
surfaced to operators): every `Engine._eval` node — selector, range
function, aggregation, binary op — becomes one plan node. In ANALYZE mode
each node additionally carries what THAT stage cost:

- wall time (inclusive of children — subtracting children gives self
  time, the tree keeps both derivable);
- series / blocks / bytes / cache hits+misses, diffed from the active
  QueryStats record around the node's evaluation;
- the decode/aggregate dispatch rung(s) that served it (device / native /
  scalar / cache), diffed the same way;
- for fan-out stages, one child leg PER REMOTE NODE (host, calls, ms,
  rows — recorded by the client session), so a cluster query's plan is
  the stitched CROSS-NODE tree: the same flat-list + parent-pointer
  machinery /debug/traces uses (trace.build_tree) nests it, and the
  record carries the trace id so the plan links to the stitched span
  tree.

Activation is a thread-local collector (`with explain.collect(analyze):`)
so the shared Engine needs no signature change and concurrent requests
never see each other's plans; an inactive engine pays one thread-local
read per AST node. Analyzed plans land in a bounded ring served at
/debug/explain (the slow-query-ring shape), and the query endpoints embed
the plan in the response envelope under `explain` when `?explain=plan` or
`?explain=analyze` is set.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager

from m3_tpu.utils import querystats, trace

_tls = threading.local()

_ring_lock = threading.Lock()
_ring: deque[dict] = deque(maxlen=64)

# bounded plan ring on the saturation plane (m3lint inv-queue-gauge)
from m3_tpu.utils import instrument as _instrument  # noqa: E402

_instrument.monitor_queue("explain_ring", lambda: len(_ring), _ring.maxlen)


def current() -> "Collector | None":
    """The thread's active plan collector (None outside EXPLAIN)."""
    return getattr(_tls, "collector", None)


@contextmanager
def collect(analyze: bool = True):
    """Install a plan collector for the scope of one engine evaluation."""
    prev = getattr(_tls, "collector", None)
    col = Collector(analyze)
    _tls.collector = col
    try:
        yield col
    finally:
        _tls.collector = prev


def describe(e) -> str:
    """One-line resolved description of an AST node (the `detail` field)."""
    from m3_tpu.query.promql import (
        AggregateExpr,
        BinaryExpr,
        Call,
        MatrixSelector,
        NumberLiteral,
        StringLiteral,
        SubqueryExpr,
        UnaryExpr,
        VectorSelector,
    )

    if isinstance(e, VectorSelector):
        parts = [f"{m.name.decode(errors='replace')}"
                 f"{m.match_type.value}"
                 f"{m.value.decode(errors='replace')!r}" for m in e.matchers]
        sel = "{" + ",".join(parts) + "}"
        if e.offset_ns:
            sel += f" offset {e.offset_ns / 1e9:g}s"
        return sel
    if isinstance(e, MatrixSelector):
        return f"{describe(e.selector)}[{e.range_ns / 1e9:g}s]"
    if isinstance(e, SubqueryExpr):
        step = f":{e.step_ns / 1e9:g}s" if e.step_ns else ":"
        return f"[{e.range_ns / 1e9:g}s{step}]"
    if isinstance(e, Call):
        return f"{e.func}()"
    if isinstance(e, AggregateExpr):
        by = ""
        if e.grouping:
            by = (" without " if e.without else " by ") \
                + "(" + ",".join(e.grouping) + ")"
        return f"{e.op}{by}"
    if isinstance(e, BinaryExpr):
        return e.op + (" bool" if e.bool_mode else "")
    if isinstance(e, UnaryExpr):
        return e.op
    if isinstance(e, NumberLiteral):
        return f"{e.value:g}"
    if isinstance(e, StringLiteral):
        return repr(e.value)
    return type(e).__name__


def kind(e) -> str:
    """Plan-node kind: the stage of the selector → range function →
    aggregation pipeline this AST node plays."""
    from m3_tpu.query.promql import (
        AggregateExpr,
        BinaryExpr,
        Call,
        MatrixSelector,
        NumberLiteral,
        StringLiteral,
        SubqueryExpr,
        UnaryExpr,
        VectorSelector,
    )

    if isinstance(e, (VectorSelector, MatrixSelector)):
        return "selector"
    if isinstance(e, SubqueryExpr):
        return "subquery"
    if isinstance(e, Call):
        from m3_tpu.query.engine import Engine

        return "range_fn" if e.func in Engine._RANGE_FNS \
            or e.func in Engine._OVER_TIME else "call"
    if isinstance(e, AggregateExpr):
        return "aggregate"
    if isinstance(e, BinaryExpr):
        return "binary"
    if isinstance(e, UnaryExpr):
        return "unary"
    if isinstance(e, (NumberLiteral, StringLiteral)):
        return "literal"
    return "expr"


class Collector:
    """Builds the plan as a FLAT list of span-shaped entries
    (span_id/parent_span_id) nested at the end by trace.build_tree — the
    exact dedupe/stitch machinery the cross-process trace endpoint uses,
    so remote legs merge in as ordinary entries."""

    def __init__(self, analyze: bool):
        self.analyze = analyze
        self.entries: list[dict] = []
        self._stack: list[dict] = []
        self._n = 0
        # whole-query compilation outcome (query/compiler.py): set once
        # per query — {"ran": bool, "cache_key": ..., "cache": hit|miss}
        # or {"ran": False, "reason": ...} on fallback
        self.compiled: dict | None = None
        # retention-tier routing (query/resolver.resolve_read): one
        # record per selector fetch — {"mode": aggregated|raw|stitched|
        # pinned_raw, "namespaces": [...], resolution/step when routed}
        self.tiers: list[dict] = []
        # legs already attributed to a (descendant) plan node: children
        # exit before parents, so a parent only claims what its subtree
        # hasn't — the selector gets the rpc legs, not every ancestor
        self._claimed: dict[str, tuple] = {}

    def _new_entry(self, node_kind: str, detail: str) -> dict:
        nid = f"plan-{self._n}"
        self._n += 1
        entry = {
            "span_id": nid,
            "parent_span_id": self._stack[-1]["span_id"] if self._stack
            else None,
            "node": node_kind,
            "detail": detail,
        }
        self.entries.append(entry)
        return entry

    @contextmanager
    def node(self, expr):
        """Wrap one engine evaluation node; in analyze mode, diff the
        active QueryStats record around it to attribute cost."""
        entry = self._new_entry(kind(expr), describe(expr))
        st = querystats.current() if self.analyze else None
        if st is not None:
            before = (st.series_matched, st.blocks_read, st.bytes_decoded,
                      st.cache_hits, st.cache_misses,
                      dict(st.decode_rungs), dict(st.node_legs),
                      dict(self._claimed),
                      (st.pipeline_groups, st.pipeline_wall_s,
                       dict(st.pipeline_stage_s)),
                      (st.index_segments, st.index_device_segments,
                       dict(st.index_fallback), st.index_terms_scanned,
                       st.index_terms_prefiltered, st.index_postings_rows))
        t0 = time.perf_counter()
        self._stack.append(entry)
        try:
            yield entry
        finally:
            self._stack.pop()
            dt = time.perf_counter() - t0
            if self.analyze:
                entry["duration_ms"] = round(dt * 1e3, 3)
            if st is not None:
                self._attribute(entry, st, before)

    def _attribute(self, entry: dict, st, before) -> None:
        (series0, blocks0, bytes0, hits0, miss0, rungs0, legs0,
         claimed0, pipe0, idx0) = before
        # postings-walk account this node's subtree accrued (the
        # selector's label matching: index/executor.py + index/device.py)
        iseg0, idev0, ifb0, iscan0, ipre0, irows0 = idx0
        d_segs = st.index_segments - iseg0
        if d_segs > 0:
            d_fb = {r: c - ifb0.get(r, 0)
                    for r, c in st.index_fallback.items()
                    if c - ifb0.get(r, 0) > 0}
            entry["index"] = {
                "segments": d_segs,
                "device_segments": st.index_device_segments - idev0,
                "fallback": d_fb,
                "terms_scanned": st.index_terms_scanned - iscan0,
                "terms_prefiltered": st.index_terms_prefiltered - ipre0,
                "postings_rows": st.index_postings_rows - irows0,
            }
        # pipelined-dataflow overlap this node's subtree accrued: wall
        # time vs sum-of-stage time per group (storage/pipeline.py) —
        # the per-query proof that gather legs overlapped decode rungs
        pg0, pw0, ps0 = pipe0
        d_groups = st.pipeline_groups - pg0
        if d_groups > 0:
            d_wall = st.pipeline_wall_s - pw0
            d_stage = {k: round((v - ps0.get(k, 0.0)) * 1e3, 3)
                       for k, v in st.pipeline_stage_s.items()}
            stage_sum = querystats.pipeline_leg_sum(d_stage)
            entry["pipeline"] = {
                "groups": d_groups,
                "wall_ms": round(d_wall * 1e3, 3),
                "stage_ms": d_stage,
                "overlap": round(stage_sum / (d_wall * 1e3), 3)
                if d_wall > 0 else 0.0,
            }
        deltas = {
            "series": st.series_matched - series0,
            "blocks": st.blocks_read - blocks0,
            "bytes": st.bytes_decoded - bytes0,
            "cache_hits": st.cache_hits - hits0,
            "cache_misses": st.cache_misses - miss0,
        }
        for k, v in deltas.items():
            if v:
                entry[k] = v
        rungs = {r: c - rungs0.get(r, 0)
                 for r, c in st.decode_rungs.items()
                 if c - rungs0.get(r, 0) > 0}
        if rungs:
            entry["rungs"] = rungs
        # remote legs this node's evaluation added AND no descendant plan
        # node already claimed (children exit first): one child entry per
        # storage node / fanout zone, nested under this plan node like a
        # remote span under its parent
        for leg, (calls, secs, rows) in st.node_legs.items():
            c0, s0, r0 = legs0.get(leg, (0, 0.0, 0))
            cc, cs, cr = self._claimed.get(leg, (0, 0.0, 0))
            cc0, cs0, cr0 = claimed0.get(leg, (0, 0.0, 0))
            n_calls = (calls - c0) - (cc - cc0)
            if n_calls <= 0:
                continue
            child = self._new_entry("rpc", leg)
            child["parent_span_id"] = entry["span_id"]
            child["calls"] = n_calls
            child["duration_ms"] = round(
                ((secs - s0) - (cs - cs0)) * 1e3, 3)
            n_rows = (rows - r0) - (cr - cr0)
            if n_rows:
                child["rows"] = n_rows
            self._claimed[leg] = (cc + n_calls,
                                  cs + (secs - s0) - (cs - cs0),
                                  cr + n_rows)

    def tree(self) -> list[dict]:
        return trace.build_tree(self.entries)

    def set_compiled(self, info: dict) -> None:
        """Record whether the compiled path served this query (the plan-
        cache key and hit/miss ride the ?explain= envelope and the ring)."""
        self.compiled = info

    def add_tier(self, info: dict) -> None:
        """Record one selector fetch's tier-resolution choice (the
        cheapest-tier routing, query/resolver.resolve_read)."""
        self.tiers.append(info)

    def to_dict(self) -> dict:
        doc = {"mode": "analyze" if self.analyze else "plan",
               "tree": self.tree()}
        if self.compiled is not None:
            doc["compiled"] = self.compiled
        if self.tiers:
            doc["tiers"] = self.tiers
        return doc


def remember(record: dict) -> None:
    """Admit one finished EXPLAIN record to the /debug/explain ring."""
    with _ring_lock:
        _ring.append(record)


def recent(limit: int = 20) -> list[dict]:
    """Ring contents, newest first."""
    with _ring_lock:
        entries = list(_ring)
    return entries[::-1][:limit]


def find(trace_id: str) -> list[dict]:
    """Ring records for one trace id (the /debug/traces cross-link)."""
    with _ring_lock:
        return [r for r in _ring if r.get("trace_id") == trace_id]


def clear() -> None:
    with _ring_lock:
        _ring.clear()

"""PromQL evaluation engine.

Role parity with the reference executor + function library
(/root/reference/src/query/executor/engine.go:111, functions/*): parse to an
AST (promql.py), then evaluate bottom-up over columnar [series x steps]
value matrices — every operator is a whole-matrix transform (the reference
streams per-series blocks through transform nodes; here the step grid is one
tensor program, the layout the TPU path consumes directly).

Numeric semantics follow upstream Prometheus: 5m lookback staleness,
extrapolated rates, population stddev, interpolated quantiles, bucket
interpolation for histogram_quantile, vector matching with __name__ excluded.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass

import numpy as np

from m3_tpu.query import promql, windows
from m3_tpu.query.promql import (
    AggregateExpr,
    BinaryExpr,
    Call,
    Expr,
    MatrixSelector,
    NumberLiteral,
    StringLiteral,
    SubqueryExpr,
    UnaryExpr,
    VectorMatching,
    VectorSelector,
)
from m3_tpu.query.windows import NS, RaggedSeries

DEFAULT_LOOKBACK_NS = 5 * 60 * NS

# accounting moved to the storage layer so every read path shares the
# budget; re-exported here for the existing query-facing API
from m3_tpu.storage.limits import QueryLimitError, QueryLimits  # noqa: E402

def _resolve_at_sentinels(e, start_ns: int, end_ns: int) -> None:
    """Replace @ start()/end() with the TOP-LEVEL query range bounds
    everywhere in the AST (upstream semantics: the sentinels always refer
    to the outer query, even inside subqueries)."""
    at = getattr(e, "at_ns", None)
    if at == "start":
        e.at_ns = start_ns
    elif at == "end":
        e.at_ns = end_ns
    for attr in ("expr", "selector", "lhs", "rhs", "param"):
        child = getattr(e, attr, None)
        if isinstance(child, Expr):
            _resolve_at_sentinels(child, start_ns, end_ns)
    for child in getattr(e, "args", ()) or ():
        if isinstance(child, Expr):
            _resolve_at_sentinels(child, start_ns, end_ns)


# functions that keep the metric name on their output
_KEEPS_NAME = {"sort", "sort_desc", "last_over_time"}


class EvalError(ValueError):
    pass


@dataclass
class Vector:
    """Evaluated instant-vector-per-step matrix."""

    labels: list[dict[bytes, bytes]]  # per series
    values: np.ndarray  # [S, n_steps]; NaN = no sample

    def drop_name(self) -> "Vector":
        return Vector(
            [{k: v for k, v in lb.items() if k != b"__name__"} for lb in self.labels],
            self.values,
        )


@dataclass
class Scalar:
    values: np.ndarray  # [n_steps]


@dataclass
class StringValue:
    value: str


class Engine:
    """Evaluates PromQL over a storage database namespace."""

    def __init__(self, db, namespace: str = "default",
                 lookback_ns: int = DEFAULT_LOOKBACK_NS,
                 limits: "QueryLimits | None" = None,
                 subquery_step_ns: int = 60 * NS,
                 resolve_tiers: bool = True,
                 now_fn=None,
                 query_compile: bool = False):
        import time as _time

        self.db = db
        self.namespace = namespace
        self.lookback_ns = lookback_ns
        # whole-query compilation (query/compiler.py, ROADMAP #2): fuse a
        # covered plan into one jit'd XLA program. Config-driven default;
        # M3_TPU_QUERY_COMPILE=1/0 is the runtime escape hatch either way
        self.query_compile = bool(query_compile)
        # retention-tier read resolution (aggregated namespaces); now_fn is
        # injectable so tests can expire raw retention deterministically
        self.resolve_tiers = resolve_tiers
        self.now_fn = now_fn or _time.time_ns
        # Budgets are enforced in the storage read path; an explicit limits
        # arg (re)binds the DATABASE-WIDE budget, mirroring the reference
        # where limits live in storage options, one set per node — so the
        # most recent binding governs every reader of this db.
        if limits is not None:
            db.limits = limits
        self.limits = limits or getattr(db, "limits", None) or QueryLimits()
        # default subquery resolution when [range:] omits the step
        # (upstream: the global evaluation interval)
        self.subquery_step_ns = subquery_step_ns
        # partial-result contract (PR-2): ReadWarnings every degraded
        # storage leg recorded during the LAST query, reset per query —
        # the HTTP layer turns these into M3-Warnings headers. THREAD-
        # LOCAL: the coordinator serves concurrent requests through one
        # Engine, and a shared field would leak query A's warnings into
        # query B's response (or hide A's entirely).
        import threading as _threading

        self._warn_tls = _threading.local()

    # -- public API --

    @property
    def last_warnings(self) -> list:
        """ReadWarnings from the last query evaluated ON THIS THREAD
        (reset per query). The HTTP handler reads this on the request
        thread that ran the query, so concurrent requests never observe
        each other's warnings."""
        return getattr(self._warn_tls, "last", [])

    @property
    def last_stats(self):
        """QueryStats of the last query evaluated ON THIS THREAD (same
        thread-local discipline as last_warnings): series matched, blocks
        read, bytes decoded, cache hit/miss, decode rungs, stage timings.
        The HTTP layer embeds it in the response envelope under `stats`."""
        return getattr(self._warn_tls, "last_stats", None)

    def _active_limits(self) -> "QueryLimits":
        """The CURRENT database-wide binding (storage accounting consults
        db.limits, so activation must target the same object even if
        another Engine rebound it after this one was constructed)."""
        return getattr(self.db, "limits", None) or self.limits

    @staticmethod
    def _parse(q: str) -> Expr:
        from m3_tpu.utils import trace

        with trace.stage(trace.STAGE_PARSE_PLAN):
            return promql.parse(q)

    def query_range(self, q: str, start_ns: int, end_ns: int, step_ns: int):
        return self.query_range_expr(self._parse(q), start_ns, end_ns,
                                     step_ns, query_text=q)

    def query_range_expr(self, expr: Expr, start_ns: int, end_ns: int,
                         step_ns: int, query_text: str = ""):
        """Evaluate a pre-parsed AST (PromQL or any front-end compiling to
        it — M3QL, Graphite-on-tags) over the step grid."""
        if step_ns <= 0:
            raise EvalError("step must be positive")
        eval_ts = np.arange(start_ns, end_ns + 1, step_ns, dtype=np.int64)
        self._active_limits().check_steps(len(eval_ts))
        return self._run_query(expr, eval_ts, query_text)

    def query_instant(self, q: str, t_ns: int):
        return self._run_query(self._parse(q),
                               np.array([t_ns], dtype=np.int64), q)

    def _run_query(self, expr: Expr, eval_ts: np.ndarray, query_text: str):
        """One query over `eval_ts`: limits, the per-query record, the
        `eval` stage (the span the engine's work hangs under), warnings."""
        limits = self._active_limits()
        limits.start_query()
        from m3_tpu.utils import querystats, trace

        self._warn_tls.sink = sink = []
        st = querystats.start(query=query_text, namespace=self.namespace)
        try:
            with trace.stage(trace.STAGE_EVAL, steps=len(eval_ts)) as fr:
                if fr.span is not None:
                    st.trace_id = fr.span.trace_id
                _resolve_at_sentinels(expr, int(eval_ts[0]),
                                      int(eval_ts[-1]))
                out = self._maybe_compiled(expr, eval_ts)
                if out is None:
                    out = self._eval(expr, eval_ts)
                return out, eval_ts
        finally:
            querystats.finish(st)
            self._warn_tls.last_stats = st
            self._warn_tls.sink = None
            self._warn_tls.last = sink
            limits.end_query()

    def _compile_enabled(self) -> bool:
        """M3_TPU_QUERY_COMPILE overrides ('1' forces on, '0' forces
        off); otherwise the engine's configured default. Read per query
        so tests and operators can flip the hatch on a live process."""
        import os

        v = os.environ.get("M3_TPU_QUERY_COMPILE")
        if v == "1":
            return True
        if v == "0":
            return False
        return self.query_compile

    def _maybe_compiled(self, expr: Expr, eval_ts: np.ndarray):
        """Whole-query compiled evaluation (query/compiler.py) when
        enabled; None hands the query to the op-by-op interpreter —
        uncovered plan shapes fall back transparently (counted, never an
        error)."""
        if not self._compile_enabled():
            return None
        from m3_tpu.query import compiler

        return compiler.try_execute(self, expr, eval_ts)

    # -- fetch --

    def _resolve_ts(self, sel, eval_ts: np.ndarray) -> np.ndarray:
        """Selector evaluation timestamps: apply the @ modifier (pin every
        step to one instant) and then the offset. start()/end() sentinels
        were already resolved against the TOP-LEVEL query range at parse
        resolution — inside a subquery they must not see the inner grid."""
        at = getattr(sel, "at_ns", None)
        if at is not None:
            eval_ts = np.full_like(eval_ts, int(at))
        return eval_ts - sel.offset_ns

    def _fetch(self, sel: VectorSelector, eval_ts: np.ndarray, range_ns: int):
        """(labels, RaggedSeries) for samples covering the windows."""
        return self._fetch_resolved(
            sel, self._resolve_fetch(sel, eval_ts, range_ns))

    def _resolve_fetch(self, sel: VectorSelector, eval_ts: np.ndarray,
                       range_ns: int):
        """(namespaces, t_min, t_max, fetch_key) of one selector fetch:
        everything about it that is known before storage is read.

        Namespaces are chosen by tier resolution (query/resolver): a
        coarse-step read goes to the cheapest complete aggregated tier
        (resolve_read), and a range past raw retention reads the
        downsampled namespaces and stitches — the reference's
        aggregated-namespace fanout (cluster_resolver.go)."""
        shifted = self._resolve_ts(sel, eval_ts)
        t_min = int(shifted[0]) - max(range_ns, self.lookback_ns)
        t_max = int(shifted[-1]) + 1
        if self.resolve_tiers:
            from m3_tpu.query import resolver

            step_ns = int(eval_ts[1] - eval_ts[0]) if len(eval_ts) > 1 else 0
            ns_list, tier_info = resolver.resolve_read(
                self.db, self.namespace, t_min, t_max, step_ns, range_ns,
                self.now_fn())
            self._record_tier_choice(tier_info)
        else:
            ns_list = [self.namespace]
        # version key sampled BEFORE the read: a write racing the fetch
        # can then only make the key stale (harmless hot-tier miss) —
        # sampling after would cache pre-write data under the post-write
        # version and serve it warm until the next bump. The compiled
        # path probes the hot tier with it between here and the read
        # (compiler._run_plan), and on a hit reads nothing
        return ns_list, t_min, t_max, self._fetch_key(sel, ns_list, t_min,
                                                      t_max)

    def _fetch_resolved(self, sel: VectorSelector, resolved):
        """The storage read of a `_resolve_fetch` result: index match,
        batched read, labels."""
        from m3_tpu.index.query import matchers_to_query
        from m3_tpu.query import resolver

        ns_list, t_min, t_max, fetch_key = resolved
        iq = matchers_to_query(sel.matchers)
        warn_sink = getattr(self._warn_tls, "sink", None)
        ragged_res = resolver.fetch_tagged_ragged(
            self.db, ns_list, iq, t_min, t_max, warnings=warn_sink)
        if ragged_res is not None:
            # single-tier storage read: the CSR lands here straight from
            # the per-shard ragged finalize — no per-series tuples, no
            # concatenate; the compiler's slab prep consumes it as-is
            docs, times, vbits, offsets = ragged_res
            labels = [dict(doc.fields) for doc in docs]
            raws = RaggedSeries(times, vbits.view(np.float64), offsets)
        else:
            docs, series = resolver.fetch_tagged(
                self.db, ns_list, iq, t_min, t_max, warnings=warn_sink)
            labels = []
            per_series = []
            for doc, (times, vbits) in zip(docs, series):
                labels.append(dict(doc.fields))
                per_series.append((times, vbits.view(np.float64)))
            raws = RaggedSeries.from_lists(per_series)
        # hot-tier identity (storage/hottier.py): the fetch is fully
        # determined by (namespace versions, selector, range), so the
        # compiled path can key prepared device slabs on it
        raws.fetch_key = fetch_key
        return labels, raws

    def _record_tier_choice(self, info: dict) -> None:
        """Per-tier read counters (query.tier scope, {tier=mode/res}) +
        the explain `tiers` block: every selector fetch records which
        tier served it, so ?explain=analyze shows the routing and
        dashboards can watch aggregated-tier hit rates."""
        from m3_tpu.query import explain as explain_mod
        from m3_tpu.utils.instrument import default_registry

        tier = info.get("mode", "raw")
        if tier == "aggregated":
            res = int(info.get("resolution_ns", 0))
            tier = f"aggregated_{res // 1_000_000_000}s"
        default_registry().root_scope("query").subscope(
            "tier", tier=tier).counter("reads")
        col = explain_mod.current()
        if col is not None:
            col.add_tier(info)

    def _fetch_key(self, sel, ns_list, t_min: int, t_max: int):
        """Content-version key for one selector fetch, or None when any
        namespace lacks version tracking (cluster facades)."""
        parts = []
        for name in ns_list:
            try:
                ns = self.db.namespaces[name]
            except Exception:  # noqa: BLE001 - facade without the map
                return None
            if not getattr(ns, "has_version_truth", False):
                # facades (cluster, fanout) have no local version truth;
                # fanout would even DELEGATE data_version_in to its local
                # namespace, keying out remote-zone changes — no hot tier
                # (cluster facades still serve ragged reads, which is why
                # this is a separate marker from supports_ragged_read)
                return None
            # the version of the blocks this range touches, not of the
            # namespace: a write to the head block leaves a fetch over
            # sealed history warm
            parts.append((name, ns.ns_uid,
                          ns.data_version_in(t_min, t_max)))
        mk = tuple(sorted((m.name, getattr(m.match_type, "value",
                                           str(m.match_type)), m.value)
                          for m in sel.matchers))
        return (tuple(parts), mk, sel.offset_ns,
                getattr(sel, "at_ns", None), t_min, t_max)

    # -- evaluation --

    def _eval(self, e: Expr, eval_ts: np.ndarray):
        """Evaluate one AST node. When an EXPLAIN collector is active on
        this thread (query/explain.py), the node also becomes one plan-
        tree entry carrying, in analyze mode, its wall time and the
        QueryStats deltas (series/blocks/bytes/rungs/remote legs) its
        subtree accrued; inactive, this is one thread-local read."""
        from m3_tpu.query import explain as explain_mod

        col = explain_mod.current()
        if col is None:
            return self._eval_node(e, eval_ts)
        with col.node(e):
            return self._eval_node(e, eval_ts)

    def _eval_node(self, e: Expr, eval_ts: np.ndarray):
        if isinstance(e, NumberLiteral):
            return Scalar(np.full(len(eval_ts), e.value))
        if isinstance(e, StringLiteral):
            return StringValue(e.value)
        if isinstance(e, VectorSelector):
            labels, raws = self._fetch(e, eval_ts, 0)
            vals = windows.instant_values(raws, self._resolve_ts(e, eval_ts),
                                          self.lookback_ns)
            return _compact(Vector(labels, vals))
        if isinstance(e, (MatrixSelector, SubqueryExpr)):
            raise EvalError("range vector must be an argument of a function")
        if isinstance(e, UnaryExpr):
            v = self._eval(e.expr, eval_ts)
            if e.op == "-":
                if isinstance(v, Scalar):
                    return Scalar(-v.values)
                return Vector(v.drop_name().labels, -v.values)
            return v
        if isinstance(e, Call):
            return self._eval_call(e, eval_ts)
        if isinstance(e, AggregateExpr):
            return self._eval_aggregate(e, eval_ts)
        if isinstance(e, BinaryExpr):
            return self._eval_binary(e, eval_ts)
        raise EvalError(f"cannot evaluate {type(e).__name__}")

    # -- functions --

    _RANGE_FNS = {
        "rate": ("extrap", True, True),
        "increase": ("extrap", True, False),
        "delta": ("extrap", False, False),
        "irate": ("instant", True, True),
        "idelta": ("instant", False, False),
    }
    _OVER_TIME = {
        "avg_over_time": "avg",
        "sum_over_time": "sum",
        "count_over_time": "count",
        "min_over_time": "min",
        "max_over_time": "max",
        "last_over_time": "last",
        "stddev_over_time": "stddev",
        "stdvar_over_time": "stdvar",
        "present_over_time": "present",
        "changes": "changes",
        "resets": "resets",
    }
    _MATH = {
        "abs": np.abs,
        "ceil": np.ceil,
        "floor": np.floor,
        "exp": np.exp,
        "ln": np.log,
        "log2": np.log2,
        "log10": np.log10,
        "sqrt": np.sqrt,
        "sgn": np.sign,
        "deg": np.degrees,
        "rad": np.radians,
        "sin": np.sin,
        "cos": np.cos,
        "tan": np.tan,
        "asin": np.arcsin,
        "acos": np.arccos,
        "atan": np.arctan,
        "sinh": np.sinh,
        "cosh": np.cosh,
        "tanh": np.tanh,
        "asinh": np.arcsinh,
        "acosh": np.arccosh,
        "atanh": np.arctanh,
    }
    # datetime component extractors over UTC second timestamps (upstream
    # promql functions.go dateWrapper family); 1970-01-01 was a Thursday
    _DATETIME = {
        "minute": lambda s, D, M, Y: (s // 60) % 60,
        "hour": lambda s, D, M, Y: (s // 3600) % 24,
        "day_of_week": lambda s, D, M, Y: (D.astype(np.int64) + 4) % 7,
        "day_of_month": lambda s, D, M, Y: (
            D - M.astype("datetime64[D]")).astype(np.int64) + 1,
        "day_of_year": lambda s, D, M, Y: (
            D - Y.astype("datetime64[D]")).astype(np.int64) + 1,
        "days_in_month": lambda s, D, M, Y: (
            (M + 1).astype("datetime64[D]")
            - M.astype("datetime64[D]")).astype(np.int64),
        "month": lambda s, D, M, Y: (M - Y).astype(np.int64) + 1,
        "year": lambda s, D, M, Y: Y.astype(np.int64) + 1970,
    }

    def _range_arg(self, e: Call, idx: int = 0):
        if len(e.args) <= idx or not isinstance(
            e.args[idx], (MatrixSelector, SubqueryExpr)
        ):
            raise EvalError(f"{e.func}() expects a range vector argument")
        return e.args[idx]

    def _eval_range_arg(self, arg, eval_ts: np.ndarray):
        """(labels, RaggedSeries, shifted_eval_ts, range_ns) for a range
        vector argument — a plain matrix selector fetch, or a SUBQUERY
        evaluated at step-aligned instants and rewrapped as ragged samples
        so every temporal function runs unchanged on it."""
        if isinstance(arg, MatrixSelector):
            # matrix selectors are consumed here rather than via _eval, so
            # give the plan tree its selector stage explicitly (the
            # selector → range function → aggregation shape)
            import contextlib

            from m3_tpu.query import explain as explain_mod

            col = explain_mod.current()
            with col.node(arg) if col is not None \
                    else contextlib.nullcontext():
                labels, raws = self._fetch(arg.selector, eval_ts,
                                           arg.range_ns)
            return labels, raws, self._resolve_ts(arg.selector, eval_ts), arg.range_ns
        # subquery: evaluate the inner expr once over the union of aligned
        # instants covering every parent step's window
        shifted = self._resolve_ts(arg, eval_ts)
        step = arg.step_ns or self.subquery_step_ns
        lo = int(shifted.min()) - arg.range_ns
        hi = int(shifted.max())
        first = (lo // step + 1) * step  # first aligned instant > lo
        last = (hi // step) * step
        if last < first:
            grid = np.array([first], dtype=np.int64)
        else:
            grid = np.arange(first, last + 1, step, dtype=np.int64)
        self.limits.check_steps(len(grid))
        inner = self._eval(arg.expr, grid)
        if not isinstance(inner, Vector):
            raise EvalError("subquery requires an instant-vector expression")
        per_series = []
        labels = []
        for i, lb in enumerate(inner.labels):
            row = inner.values[i]
            keep = ~np.isnan(row)
            if not keep.any():
                continue
            labels.append(lb)
            per_series.append((grid[keep], row[keep]))
        return labels, RaggedSeries.from_lists(per_series), shifted, arg.range_ns

    def _eval_call(self, e: Call, eval_ts: np.ndarray):
        fn = e.func
        if fn in self._RANGE_FNS:
            kind, is_counter, is_rate = self._RANGE_FNS[fn]
            labels, raws, shifted, range_ns = self._eval_range_arg(
                self._range_arg(e), eval_ts)
            if kind == "extrap":
                vals = windows.extrapolated_rate(raws, shifted, range_ns,
                                                 is_counter, is_rate)
            else:
                vals = windows.instant_delta(raws, shifted, range_ns,
                                             is_counter, is_rate)
            return _compact(Vector(labels, vals).drop_name())
        if fn in self._OVER_TIME:
            labels, raws, shifted, range_ns = self._eval_range_arg(
                self._range_arg(e), eval_ts)
            vals = windows.over_time(self._OVER_TIME[fn], raws, shifted, range_ns)
            out = Vector(labels, vals)
            return _compact(out if fn in _KEEPS_NAME else out.drop_name())
        if fn == "holt_winters":
            labels, raws, shifted, range_ns = self._eval_range_arg(
                self._range_arg(e), eval_ts)
            sf = self._scalar_param(e.args[1], eval_ts)
            tf = self._scalar_param(e.args[2], eval_ts)
            if not (0 < sf < 1) or not (0 < tf <= 1):
                raise EvalError("holt_winters smoothing factors must be in "
                                "(0, 1)")
            vals = windows.holt_winters(raws, shifted, range_ns, sf, tf)
            return _compact(Vector(labels, vals).drop_name())
        if fn == "absent_over_time":
            arg = self._range_arg(e)
            labels, raws, shifted, range_ns = self._eval_range_arg(arg, eval_ts)
            present_m = windows.over_time("present", raws, shifted, range_ns)
            present = ((~np.isnan(present_m)).any(axis=0) if len(labels)
                       else np.zeros(len(eval_ts), bool))
            lbls = (_absent_labels(arg.selector)
                    if isinstance(arg, MatrixSelector) else {})
            return Vector([lbls], np.where(present, np.nan, 1.0)[None, :])
        if fn == "quantile_over_time":
            phi = self._scalar_param(e.args[0], eval_ts)
            labels, raws, shifted, range_ns = self._eval_range_arg(
                self._range_arg(e, 1), eval_ts)
            vals = _quantile_over_time(raws, shifted, range_ns, phi)
            return _compact(Vector(labels, vals).drop_name())
        if fn in ("deriv", "predict_linear"):
            labels, raws, shifted, range_ns = self._eval_range_arg(
                self._range_arg(e), eval_ts)
            off = None
            if fn == "predict_linear":
                off = self._scalar_param(e.args[1], eval_ts)
            vals = windows.linear_regression(raws, shifted, range_ns, off)
            return _compact(Vector(labels, vals).drop_name())
        if fn in self._MATH:
            v = self._eval(e.args[0], eval_ts)
            if isinstance(v, Scalar):
                return Scalar(self._MATH[fn](v.values))
            return Vector(v.drop_name().labels, self._MATH[fn](v.values))
        if fn == "round":
            v = self._eval(e.args[0], eval_ts)
            to = self._scalar_param(e.args[1], eval_ts) if len(e.args) > 1 else 1.0
            # round half away from... Prometheus rounds half up via floor(v/to+0.5)
            vals = np.floor(v.values / to + 0.5) * to
            return Vector(v.drop_name().labels, vals)
        if fn in ("clamp", "clamp_min", "clamp_max"):
            v = self._eval(e.args[0], eval_ts)
            vals = v.values
            if fn == "clamp":
                lo = self._scalar_param(e.args[1], eval_ts)
                hi = self._scalar_param(e.args[2], eval_ts)
                vals = np.clip(vals, lo, hi)
            elif fn == "clamp_min":
                vals = np.maximum(vals, self._scalar_param(e.args[1], eval_ts))
            else:
                vals = np.minimum(vals, self._scalar_param(e.args[1], eval_ts))
            return Vector(v.drop_name().labels, vals)
        if fn == "scalar":
            v = self._eval(e.args[0], eval_ts)
            if not isinstance(v, Vector):
                raise EvalError("scalar() expects an instant vector")
            n_valid = (~np.isnan(v.values)).sum(axis=0)
            one = (n_valid == 1)
            summed = np.nansum(v.values, axis=0)
            return Scalar(np.where(one, summed, np.nan))
        if fn == "vector":
            s = self._eval(e.args[0], eval_ts)
            if not isinstance(s, Scalar):
                raise EvalError("vector() expects a scalar")
            return Vector([{}], s.values[None, :])
        if fn == "time":
            return Scalar(eval_ts.astype(np.float64) / NS)
        if fn == "pi":
            return Scalar(np.full(len(eval_ts), math.pi))
        if fn in self._DATETIME:
            if e.args:
                v = self._eval(e.args[0], eval_ts)
                if not isinstance(v, Vector):
                    raise EvalError(f"{fn}() expects an instant vector")
                labels = v.drop_name().labels
                vals = v.values
            else:
                # no argument: the evaluation timestamps themselves
                labels = [{}]
                vals = (eval_ts.astype(np.float64) / NS)[None, :]
            secs = np.floor(vals)
            safe = np.where(np.isnan(secs), 0, secs).astype(np.int64)
            dt = safe.astype("datetime64[s]")
            D = dt.astype("datetime64[D]")
            M = dt.astype("datetime64[M]")
            Y = dt.astype("datetime64[Y]")
            out = self._DATETIME[fn](safe, D, M, Y).astype(np.float64)
            return Vector(labels, np.where(np.isnan(vals), np.nan, out))
        if fn == "timestamp":
            v = self._eval(e.args[0], eval_ts)
            ts = np.broadcast_to(eval_ts.astype(np.float64) / NS, v.values.shape)
            return Vector(v.drop_name().labels, np.where(np.isnan(v.values), np.nan, ts))
        if fn == "absent":
            v = self._eval(e.args[0], eval_ts)
            present = (~np.isnan(v.values)).any(axis=0) if len(v.labels) else np.zeros(
                len(eval_ts), bool
            )
            lbls = _absent_labels(e.args[0])
            return Vector([lbls], np.where(present, np.nan, 1.0)[None, :])
        if fn == "histogram_quantile":
            phi = self._scalar_param(e.args[0], eval_ts)
            v = self._eval(e.args[1], eval_ts)
            return _histogram_quantile(phi, v)
        if fn == "label_replace":
            v = self._eval(e.args[0], eval_ts)
            dst, repl, src, rx = (a.value for a in e.args[1:5])
            pattern = re.compile(rx)
            # RE2 $1/${name} replacement syntax -> Python \1/\g<name>
            py_repl = re.sub(
                r"\$(\d+|\{(\w+)\})",
                lambda m: f"\\g<{m.group(2)}>" if m.group(2) else f"\\{m.group(1)}",
                repl.replace("$$", "\x00"),
            ).replace("\x00", "$")
            out_labels = []
            for lb in v.labels:
                lb = dict(lb)
                val = lb.get(src.encode(), b"").decode()
                m = pattern.fullmatch(val)
                if m:
                    new = m.expand(py_repl).encode() if repl else b""
                    if new:
                        lb[dst.encode()] = new
                    else:
                        lb.pop(dst.encode(), None)
                out_labels.append(lb)
            return Vector(out_labels, v.values)
        if fn == "label_join":
            v = self._eval(e.args[0], eval_ts)
            dst = e.args[1].value
            sep = e.args[2].value
            srcs = [a.value for a in e.args[3:]]
            out_labels = []
            for lb in v.labels:
                lb = dict(lb)
                joined = sep.join(lb.get(s.encode(), b"").decode() for s in srcs)
                if joined:
                    lb[dst.encode()] = joined.encode()
                else:
                    lb.pop(dst.encode(), None)
                out_labels.append(lb)
            return Vector(out_labels, v.values)
        if fn in ("sort", "sort_desc"):
            v = self._eval(e.args[0], eval_ts)
            if len(v.labels) and v.values.shape[1]:
                key = np.where(np.isnan(v.values[:, -1]), -np.inf, v.values[:, -1])
                order = np.argsort(-key if fn == "sort_desc" else key, kind="stable")
                return Vector([v.labels[i] for i in order], v.values[order])
            return v
        raise EvalError(f"unknown function {fn}()")

    def _scalar_param(self, e: Expr, eval_ts: np.ndarray) -> float:
        v = self._eval(e, eval_ts)
        if isinstance(v, Scalar):
            return float(v.values[0])
        raise EvalError("expected scalar parameter")

    # -- aggregation --

    def _eval_aggregate(self, e: AggregateExpr, eval_ts: np.ndarray):
        v = self._eval(e.expr, eval_ts)
        if not isinstance(v, Vector):
            raise EvalError(f"{e.op} expects an instant vector")
        S, T = v.values.shape if len(v.labels) else (0, len(eval_ts))
        keys, out_labels_for = grouping_keys(v.labels, e.grouping, e.without)
        uniq = sorted(set(keys))
        gid = {k: i for i, k in enumerate(uniq)}
        groups = np.array([gid[k] for k in keys], np.int64) if keys else np.empty(0, np.int64)
        G = len(uniq)
        vals = v.values if S else np.zeros((0, T))
        nan = np.isnan(vals)
        filled0 = np.where(nan, 0.0, vals)

        def seg(arr, init=0.0, op="add"):
            out = np.full((G, T), init)
            if op == "add":
                np.add.at(out, groups, arr)
            elif op == "min":
                np.minimum.at(out, groups, arr)
            elif op == "max":
                np.maximum.at(out, groups, arr)
            return out

        count = seg((~nan).astype(np.float64))
        any_present = count > 0
        op = e.op
        if op in ("sum", "avg", "stddev", "stdvar"):
            s1 = seg(filled0)
            if op == "sum":
                out = s1
            else:
                mean = s1 / np.where(any_present, count, 1)
                if op == "avg":
                    out = mean
                else:
                    s2 = seg(np.where(nan, 0.0, vals * vals))
                    var = np.maximum(s2 / np.where(any_present, count, 1) - mean**2, 0)
                    out = var if op == "stdvar" else np.sqrt(var)
        elif op == "count":
            out = count
        elif op == "min":
            out = seg(np.where(nan, np.inf, vals), np.inf, "min")
        elif op == "max":
            out = seg(np.where(nan, -np.inf, vals), -np.inf, "max")
        elif op == "group":
            out = np.ones((G, T))
        elif op == "quantile":
            phi = self._scalar_param(e.param, eval_ts)
            out = np.full((G, T), np.nan)
            for g in range(G):
                sub = vals[groups == g]
                out[g] = _quantile_cols(sub, phi)
        elif op in ("topk", "bottomk"):
            k = int(self._scalar_param(e.param, eval_ts))
            keep = np.zeros_like(vals, dtype=bool)
            for g in range(G):
                rows = np.nonzero(groups == g)[0]
                sub = vals[rows]
                for t in range(T):
                    col = sub[:, t]
                    valid = np.nonzero(~np.isnan(col))[0]
                    if len(valid) == 0:
                        continue
                    order = np.argsort(col[valid], kind="stable")
                    sel = (order[::-1] if op == "topk" else order)[:k]
                    keep[rows[valid[sel]], t] = True
            return _compact(Vector(
                [dict(lb) for lb in v.labels], np.where(keep, vals, np.nan)
            ))
        elif op == "count_values":
            if not isinstance(e.param, StringLiteral) and not isinstance(
                self._eval(e.param, eval_ts), StringValue
            ):
                raise EvalError("count_values expects a string label parameter")
            label = (
                e.param.value if isinstance(e.param, StringLiteral)
                else self._eval(e.param, eval_ts).value
            ).encode()
            bucket: dict[tuple, np.ndarray] = {}
            out_lbls: dict[tuple, dict] = {}
            for s in range(S):
                for t in range(T):
                    x = vals[s, t]
                    if np.isnan(x):
                        continue
                    vkey = keys[s] + ((label, _fmt(x).encode()),)
                    if vkey not in bucket:
                        bucket[vkey] = np.full(T, np.nan)
                        lb = dict(out_labels_for[keys[s]])
                        lb[label] = _fmt(x).encode()
                        out_lbls[vkey] = lb
                    cur = bucket[vkey][t]
                    bucket[vkey][t] = 1.0 if np.isnan(cur) else cur + 1.0
            ks = sorted(bucket)
            return Vector([out_lbls[k] for k in ks],
                          np.stack([bucket[k] for k in ks]) if ks else np.zeros((0, T)))
        else:
            raise EvalError(f"unknown aggregator {op}")
        out = np.where(any_present, out, np.nan)
        return _compact(Vector([dict(out_labels_for[k]) for k in uniq], out))

    # -- binary ops --

    def _eval_binary(self, e: BinaryExpr, eval_ts: np.ndarray):
        lhs = self._eval(e.lhs, eval_ts)
        rhs = self._eval(e.rhs, eval_ts)
        op = e.op
        if isinstance(lhs, Scalar) and isinstance(rhs, Scalar):
            out = _apply_op(op, lhs.values, rhs.values)
            if op in promql.COMPARISONS:
                if not e.bool_mode:
                    raise EvalError("comparisons between scalars must use bool")
                out = out.astype(np.float64)
            return Scalar(out)
        if op in ("and", "or", "unless"):
            return self._set_op(op, lhs, rhs, e.matching)
        if isinstance(lhs, Scalar) or isinstance(rhs, Scalar):
            vec, sc = (rhs, lhs) if isinstance(lhs, Scalar) else (lhs, rhs)
            swapped = isinstance(lhs, Scalar)
            a = sc.values[None, :] if swapped else vec.values
            b = vec.values if swapped else sc.values[None, :]
            raw = _apply_op(op, a, b)
            if op in promql.COMPARISONS:
                if e.bool_mode:
                    vals = np.where(np.isnan(vec.values), np.nan, raw.astype(np.float64))
                    return _compact(Vector(vec.drop_name().labels, vals))
                vals = np.where(raw.astype(bool), vec.values, np.nan)
                return _compact(Vector(vec.labels, vals))
            return _compact(Vector(vec.drop_name().labels, raw))
        # vector-vector
        return self._vector_binary(e, lhs, rhs)

    def _match_key(self, lb: dict, matching: VectorMatching | None):
        if matching and matching.on:
            items = [(k, lb[k]) for k in sorted(l.encode() for l in matching.labels)
                     if k in lb]
        else:
            excl = {b"__name__"}
            if matching:
                excl |= {l.encode() for l in matching.labels}
            items = sorted((k, v) for k, v in lb.items() if k not in excl)
        return tuple(items)

    def _set_op(self, op, lhs, rhs, matching):
        if not isinstance(lhs, Vector) or not isinstance(rhs, Vector):
            raise EvalError(f"set operator {op} requires vectors")
        rkeys = {self._match_key(lb, matching): i for i, lb in enumerate(rhs.labels)}
        T = lhs.values.shape[1] if len(lhs.labels) else rhs.values.shape[1] if len(rhs.labels) else 0
        if op == "and":
            out_l, out_v = [], []
            for i, lb in enumerate(lhs.labels):
                j = rkeys.get(self._match_key(lb, matching))
                if j is not None:
                    mask = ~np.isnan(rhs.values[j])
                    out_l.append(lb)
                    out_v.append(np.where(mask, lhs.values[i], np.nan))
            return _compact(Vector(out_l, np.stack(out_v) if out_v else np.zeros((0, T))))
        if op == "unless":
            out_l, out_v = [], []
            for i, lb in enumerate(lhs.labels):
                j = rkeys.get(self._match_key(lb, matching))
                vals = lhs.values[i]
                if j is not None:
                    vals = np.where(np.isnan(rhs.values[j]), vals, np.nan)
                out_l.append(lb)
                out_v.append(vals)
            return _compact(Vector(out_l, np.stack(out_v) if out_v else np.zeros((0, T))))
        # or
        out_l = [dict(lb) for lb in lhs.labels]
        out_v = [lhs.values[i] for i in range(len(lhs.labels))]
        lkeys = {self._match_key(lb, matching) for lb in lhs.labels}
        lcover = {}
        for i, lb in enumerate(lhs.labels):
            k = self._match_key(lb, matching)
            cov = ~np.isnan(lhs.values[i])
            lcover[k] = cov | lcover.get(k, np.zeros_like(cov))
        for j, lb in enumerate(rhs.labels):
            k = self._match_key(lb, matching)
            if k not in lkeys:
                out_l.append(dict(lb))
                out_v.append(rhs.values[j])
            else:
                gap = np.isnan(rhs.values[j]) | lcover[k]
                extra = np.where(gap, np.nan, rhs.values[j])
                if not np.isnan(extra).all():
                    out_l.append(dict(lb))
                    out_v.append(extra)
        return _compact(Vector(out_l, np.stack(out_v) if out_v else np.zeros((0, T))))

    def _vector_binary(self, e: BinaryExpr, lhs: Vector, rhs: Vector):
        m = e.matching
        group_left = m.group_left if m else False
        group_right = m.group_right if m else False
        if group_right:
            # evaluate as mirrored group_left
            sym = {"<": ">", ">": "<", "<=": ">=", ">=": "<=",
                   "/": None, "-": None, "%": None, "^": None}
            swapped_op = sym.get(e.op, e.op)
            if swapped_op is None:
                lhs, rhs = rhs, lhs  # keep op, swap operand roles manually below
                group_left, group_right = True, False
                flip = True
            else:
                lhs, rhs = rhs, lhs
                e = BinaryExpr(swapped_op, e.lhs, e.rhs, e.bool_mode, e.matching)
                group_left, group_right = True, False
                flip = False
        else:
            flip = False

        rmap: dict[tuple, int] = {}
        for j, lb in enumerate(rhs.labels):
            k = self._match_key(lb, m)
            if k in rmap:
                raise EvalError("many-to-many vector matching: duplicate series on 'one' side")
            rmap[k] = j
        out_l, out_v = [], []
        seen: dict[tuple, int] = {}
        for i, lb in enumerate(lhs.labels):
            k = self._match_key(lb, m)
            j = rmap.get(k)
            if j is None:
                continue
            if not group_left:
                if k in seen:
                    raise EvalError("many-to-one matching requires group_left/group_right")
                seen[k] = i
            a, b = lhs.values[i], rhs.values[j]
            if flip:
                a, b = b, a
            raw = _apply_op(e.op, a, b)
            if e.op in promql.COMPARISONS:
                if e.bool_mode:
                    vals = np.where(np.isnan(a) | np.isnan(b), np.nan,
                                    raw.astype(np.float64))
                    out_lb = self._result_labels(lb, rhs.labels[j], m, group_left)
                else:
                    vals = np.where(raw.astype(bool), lhs.values[i], np.nan)
                    out_lb = dict(lb)
            else:
                vals = raw
                out_lb = self._result_labels(lb, rhs.labels[j], m, group_left)
            out_l.append(out_lb)
            out_v.append(vals)
        T = lhs.values.shape[1] if len(lhs.labels) else (
            rhs.values.shape[1] if len(rhs.labels) else 0
        )
        return _compact(Vector(out_l, np.stack(out_v) if out_v else np.zeros((0, T))))

    def _result_labels(self, l_lb, r_lb, m: VectorMatching | None, group_left: bool):
        """Result labels per upstream: one-to-one on(...) keeps only the on
        labels; otherwise the (many-side) lhs labels minus __name__ and
        minus ignoring(...); group_left keeps the FULL many-side label set
        (minus __name__) plus any include labels copied from the one side."""
        if group_left:
            out = {k: v for k, v in l_lb.items() if k != b"__name__"}
        elif m and m.on:
            out = {k: v for k, v in l_lb.items() if k.decode() in m.labels}
        else:
            excl = {l.encode() for l in (m.labels if m else ())} | {b"__name__"}
            out = {k: v for k, v in l_lb.items() if k not in excl}
        for inc in (m.include if m else ()):
            k = inc.encode()
            if k in r_lb:
                out[k] = r_lb[k]
            else:
                out.pop(k, None)
        return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def grouping_keys(labels, grouping, without: bool):
    """Aggregation group keys: (per-series sorted-item key tuples, key ->
    kept-label dict). ONE definition of the by/without key semantics —
    the interpreter's _eval_aggregate and the whole-query compiler's
    _group_ids both build their group ids from this, so the compiled
    path cannot drift from the interpreter on grouping."""
    keys = []
    out_labels_for = {}
    for lb in labels:
        if without:
            kept = {
                k: val for k, val in lb.items()
                if k != b"__name__" and k.decode() not in grouping
            }
        elif grouping:
            kept = {k: val for k, val in lb.items() if k.decode() in grouping}
        else:
            kept = {}
        key = tuple(sorted(kept.items()))
        keys.append(key)
        out_labels_for[key] = kept
    return keys, out_labels_for


def _apply_op(op: str, a, b):
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        if op == "+":
            return a + b
        if op == "-":
            return a - b
        if op == "*":
            return a * b
        if op == "/":
            return a / b
        if op == "%":
            return np.fmod(a, b)
        if op == "^":
            return np.power(a, b)
        if op == "==":
            return a == b
        if op == "!=":
            return a != b
        if op == ">":
            return a > b
        if op == "<":
            return a < b
        if op == ">=":
            return a >= b
        if op == "<=":
            return a <= b
    raise EvalError(f"unknown operator {op}")


def _compact(v: Vector) -> Vector:
    """Drop series with no samples at any step."""
    if not len(v.labels):
        return v
    keep = ~np.isnan(v.values).all(axis=1)
    if keep.all():
        return v
    idx = np.nonzero(keep)[0]
    return Vector([v.labels[i] for i in idx], v.values[idx])


def _quantile_cols(sub: np.ndarray, phi: float) -> np.ndarray:
    """Prometheus-style interpolated quantile down columns, NaN-aware."""
    T = sub.shape[1]
    out = np.full(T, np.nan)
    for t in range(T):
        col = sub[:, t]
        col = col[~np.isnan(col)]
        if len(col) == 0:
            continue
        if phi < 0:
            out[t] = -np.inf
            continue
        if phi > 1:
            out[t] = np.inf
            continue
        s = np.sort(col)
        rank = phi * (len(s) - 1)
        lo = int(math.floor(rank))
        hi = min(lo + 1, len(s) - 1)
        out[t] = s[lo] + (rank - lo) * (s[hi] - s[lo])
    return out


def _quantile_over_time(raws: RaggedSeries, eval_ts, range_ns, phi):
    lo, hi = raws.window_bounds(eval_ts, range_ns)
    out = np.full(lo.shape, np.nan)
    for s in range(lo.shape[0]):
        for t in range(lo.shape[1]):
            w = raws.values[lo[s, t] : hi[s, t]]
            if len(w) == 0:
                continue
            out[s, t] = _quantile_cols(w[:, None], phi)[0]
    return out


def _histogram_quantile(phi: float, v: Vector) -> Vector:
    groups: dict[tuple, list[int]] = {}
    lbls_for: dict[tuple, dict] = {}
    for i, lb in enumerate(v.labels):
        key = tuple(sorted(
            (k, val) for k, val in lb.items() if k not in (b"le", b"__name__")
        ))
        groups.setdefault(key, []).append(i)
        lbls_for[key] = {k: val for k, val in lb.items()
                         if k not in (b"le", b"__name__")}
    T = v.values.shape[1] if len(v.labels) else 0
    out_l, out_v = [], []
    for key, rows in sorted(groups.items()):
        les = []
        for i in rows:
            le_raw = v.labels[i].get(b"le", b"")
            try:
                les.append(float(le_raw))
            except ValueError:
                les.append(np.nan)
        order = np.argsort(les)
        les_sorted = np.array(les)[order]
        counts = v.values[[rows[int(o)] for o in order]]
        vals = np.full(T, np.nan)
        if len(les_sorted) >= 2 and np.isinf(les_sorted[-1]):
            # monotonize cumulative counts then interpolate
            counts = np.maximum.accumulate(np.where(np.isnan(counts), 0, counts), axis=0)
            total = counts[-1]
            with np.errstate(invalid="ignore", divide="ignore"):
                for t in range(T):
                    obs = total[t]
                    if not obs > 0:
                        continue
                    rank = phi * obs
                    b = int(np.searchsorted(counts[:, t], rank, side="left"))
                    b = min(b, len(les_sorted) - 1)
                    if b == len(les_sorted) - 1:
                        vals[t] = les_sorted[-2]
                        continue
                    if b == 0 and les_sorted[0] <= 0:
                        vals[t] = les_sorted[0]
                        continue
                    b_start = 0.0 if b == 0 else les_sorted[b - 1]
                    b_end = les_sorted[b]
                    cnt = counts[b, t] - (0.0 if b == 0 else counts[b - 1, t])
                    r = rank - (0.0 if b == 0 else counts[b - 1, t])
                    if cnt <= 0:
                        vals[t] = b_end
                    else:
                        vals[t] = b_start + (b_end - b_start) * (r / cnt)
        out_l.append(lbls_for[key])
        out_v.append(vals)
    return _compact(Vector(out_l, np.stack(out_v) if out_v else np.zeros((0, T))))


def _absent_labels(e: Expr) -> dict:
    if isinstance(e, VectorSelector):
        out = {}
        from m3_tpu.index.query import MatchType

        for m in e.matchers:
            if m.match_type == MatchType.EQUAL and m.name != b"__name__":
                out[m.name] = m.value
        return out
    return {}


def _fmt(x: float) -> str:
    if x == int(x) and abs(x) < 1e15:
        return str(int(x))
    return repr(x)

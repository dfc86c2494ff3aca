"""Whole-query compilation: fuse a resolved PromQL plan into ONE XLA
program per plan shape (ROADMAP #2).

The interpreter (`Engine._eval`) walks the expression tree op by op —
decode, range function, aggregation and binary ops each pay their own
dispatch ladder and materialize a host-side intermediate between stages.
Following PAPERS.md "Automatic Full Compilation of Julia Programs and ML
Models to Cloud TPUs" (compile the whole program, not the ops), this
module lowers a covered plan — selector → range function → by/without
aggregation → scalar binary ops — into a single traced/jit'd program
composed from the SAME pure stage kernels the per-op device path uses
(`ops/temporal.stage_*`, `ops/windowed_agg.stage_grouped_*`), so decoded
columns stay on device across stages and the XLA/native/scalar dispatch
decision moves from per-op to per-plan.

Covered plan shapes (the high-traffic core; everything else falls back
to the interpreter, counted, never an error):

  base:   vector selector (instant lookback gather), or
          rate/increase/delta/irate/idelta(sel[range]), or
          avg/sum/count/present_over_time(sel[range]), or
          min/max_over_time(sel[range]) (sparse-table range-min stage)
  over:   any chain of sum/avg/min/max/count/quantile `by`/`without`
          aggregations (at most one) and scalar-literal binary
          arithmetic (+ - * / % ^), in any order
  binop:  a TOP-LEVEL vector-vector arithmetic op between two covered
          chains under default one-to-one matching (`match_vecbin`):
          both sides run as their own fused programs and the combine is
          the interpreter's exact numpy one-to-one match — same keys,
          same duplicate-series errors, same result labels.
          on()/ignoring()/group modifiers, bool mode and comparisons
          stay with the interpreter (counted fallback).

Sharded compute plane (PR 12, ROADMAP #1): when a ``("series",)``
compute mesh is active (`parallel.mesh.active_compute_mesh` —
M3_TPU_QUERY_SHARD or a live multi-device accelerator), the SAME plan
runs across every device: host prep slices the CSR sample arrays into
per-device SLABS (each device owns a contiguous block of series rows
and only its own samples — gathers stay device-local instead of
thrashing a replicated sample array), the base stage runs under an
inner shard_map over those slabs, and every later stage boundary emits
``jax.lax.with_sharding_constraint`` (series-sharded [S, T] until the
aggregation, replicated [G, T] after it) so XLA's SPMD partitioner
lowers the grouped segment reductions to psums over the series axis
itself. The series axis pads to a multiple of the mesh size
(``dispatch.next_bucket(S, multiple=n_devices)``); numerics are
device-count independent up to float reassociation in the cross-device
reductions (exact NaN masks, 1e-9 relative — the same envelope as
single-device XLA, enforced at 1 and 8 devices by tests/test_parallel).

Plan-shape cache: compiled programs are cached per plan SIGNATURE (the
op sequence) by an ``functools.lru_cache`` factory — the m3lint-blessed
keyed-cache idiom, so ``jax.jit`` is constructed once per signature, not
per call — and jax's own executable cache buckets the (series count,
step count, group count) axes, which the host prep pads to half-octave
buckets (`dispatch.next_bucket`: the smallest of {2^k, 3*2^(k-1)} that
fits). Recompiles are therefore bounded by
O(signatures x log S x log T x log G). An explicit bounded LRU
(`_PLAN_CACHE`) tracks every (signature, bucket) key served; hit/miss is
the jit tracker's executable-cache ground truth (not LRU membership) and
feeds the per-plan-shape counters and the `?explain=analyze` surface.

Numeric parity: stage math is shared with the per-op kernels and mirrors
the interpreter formula-for-formula; results are element-identical up to
XLA reassociation (prefix sums, segment-sum accumulation order — last-ulp
differences) and the documented extrapolation-threshold knife edge in
``stage_extrapolated_rate``. The seeded property sweep in
tests/test_query_compile.py enforces this envelope.
"""

from __future__ import annotations

import contextlib
import functools
import os
import sys
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from m3_tpu.query.promql import (
    AggregateExpr,
    BinaryExpr,
    Call,
    Expr,
    MatrixSelector,
    NumberLiteral,
    VectorSelector,
)
from m3_tpu.utils import dispatch

# range-function bases: name -> (is_counter, is_rate)
_EXTRAP = {"rate": (True, True), "increase": (True, False),
           "delta": (False, False)}
_INSTANT = {"irate": (True, True), "idelta": (False, False)}
_OVER_TIME = {"avg_over_time": "avg", "sum_over_time": "sum",
              "count_over_time": "count", "present_over_time": "present"}
_MINMAX = {"min_over_time": True, "max_over_time": False}  # name -> is_min
_AGG_OPS = {"sum", "avg", "min", "max", "count", "quantile"}
_BIN_OPS = {"+", "-", "*", "/", "%", "^"}

# bound on distinct (signature, bucket) keys tracked; jit programs are
# cached per signature below (the buckets share one traced callable)
_PLAN_CACHE_CAP = 128
_PROGRAM_CACHE_CAP = 64


@dataclass
class PlanSpec:
    """A matched, compilable plan."""

    selector: VectorSelector
    range_ns: int                 # 0 for an instant-selector base
    base: str                     # "instant" | range-function name
    stages: tuple                 # inner->outer ("bin", op, swapped, value)
    #                             # | ("agg", op, grouping, without, phi)
    nodes: tuple                  # AST nodes outer->inner for EXPLAIN

    @property
    def sig(self) -> tuple:
        """Program signature: exactly what changes the traced callable
        (ops + sides), never the data (scalars, phi, grouping labels)."""
        return (self.base, tuple(
            (st[0], st[1], st[2]) if st[0] == "bin" else (st[0], st[1])
            for st in self.stages))

    @property
    def agg(self):
        """The chain's one aggregation stage, or None."""
        return next((st for st in self.stages if st[0] == "agg"), None)

    @property
    def sig_str(self) -> str:
        parts = [self.base]
        for st in self.stages:
            if st[0] == "bin":
                parts.append(f"bin:{st[1]}:{'r' if st[2] else 'l'}")
            else:
                parts.append(f"agg:{st[1]}")
        return "|".join(parts)


@dataclass
class VecBinSpec:
    """A covered vector-vector binary op: both sides are covered chains,
    matched one-to-one on their full label sets (default matching). The
    sides compile into their own fused programs; the element-wise
    combine is the interpreter's exact numpy op over the matched rows,
    so parity composes from the sides' parity."""

    op: str
    lhs: PlanSpec
    rhs: PlanSpec


def match_vecbin(expr: Expr) -> VecBinSpec | None:
    """VecBinSpec when `expr` is a top-level arithmetic binop between
    two covered chains under DEFAULT one-to-one matching, else None.
    on()/ignoring()/group modifiers, bool mode and comparisons keep the
    interpreter's richer matching machinery (counted fallback)."""
    if not isinstance(expr, BinaryExpr) or expr.op not in _BIN_OPS \
            or expr.bool_mode:
        return None
    m = expr.matching
    if m is not None and (m.on or m.labels or m.group_left
                          or m.group_right or m.include):
        return None
    if _scalar_literal(expr.lhs) is not None \
            or _scalar_literal(expr.rhs) is not None:
        return None  # scalar arithmetic is covered in-chain by match()
    lhs = match(expr.lhs)
    if lhs is None:
        return None
    rhs = match(expr.rhs)
    if rhs is None:
        return None
    return VecBinSpec(expr.op, lhs, rhs)


def _scalar_literal(e: Expr) -> float | None:
    """The float of a (possibly sign-wrapped) number literal, else None —
    the parser spells -1.5 as UnaryExpr('-', NumberLiteral(1.5))."""
    from m3_tpu.query.promql import UnaryExpr

    if isinstance(e, NumberLiteral):
        return float(e.value)
    if isinstance(e, UnaryExpr) and isinstance(e.expr, NumberLiteral):
        v = float(e.expr.value)
        return -v if e.op == "-" else v
    return None


def match(expr: Expr) -> PlanSpec | None:
    """PlanSpec when the expression is a covered chain, else None."""
    outer = []   # outer->inner stage list
    nodes = []
    e = expr
    while True:
        if isinstance(e, BinaryExpr) and e.op in _BIN_OPS \
                and not e.bool_mode:
            lhs_lit = _scalar_literal(e.lhs)
            rhs_lit = _scalar_literal(e.rhs)
            if lhs_lit is not None:
                swapped, scalar, inner = True, lhs_lit, e.rhs
            elif rhs_lit is not None:
                swapped, scalar, inner = False, rhs_lit, e.lhs
            else:
                return None
            outer.append(("bin", e.op, swapped, scalar))
            nodes.append(e)
            e = inner
            continue
        if isinstance(e, AggregateExpr) and e.op in _AGG_OPS:
            if any(st[0] == "agg" for st in outer):
                return None  # one aggregation per compiled chain
            phi = None
            if e.op == "quantile":
                phi = _scalar_literal(e.param)
                if phi is None:
                    return None
            elif e.param is not None:
                return None
            outer.append(("agg", e.op, tuple(e.grouping), bool(e.without),
                          phi))
            nodes.append(e)
            e = e.expr
            continue
        break
    if isinstance(e, VectorSelector):
        if getattr(e, "at_ns", None) in ("start", "end"):
            return None  # unresolved sentinel: not a compilable instant
        sel, range_ns, base = e, 0, "instant"
        nodes.append(e)
    elif isinstance(e, Call) and (
            e.func in _EXTRAP or e.func in _INSTANT
            or e.func in _OVER_TIME or e.func in _MINMAX) \
            and len(e.args) == 1 and isinstance(e.args[0], MatrixSelector):
        sel = e.args[0].selector
        if getattr(sel, "at_ns", None) in ("start", "end"):
            return None
        range_ns, base = e.args[0].range_ns, e.func
        nodes.append(e)
        nodes.append(e.args[0])
    else:
        return None
    # execution order is inner->outer
    return PlanSpec(selector=sel, range_ns=range_ns, base=base,
                    stages=tuple(reversed(outer)), nodes=tuple(nodes))


# ---------------------------------------------------------------------------
# program factory (the per-plan jit dispatcher)
# ---------------------------------------------------------------------------


def _apply_scalar_op(op: str, a, b):
    """jnp twin of engine._apply_op restricted to arithmetic."""
    import jax.numpy as jnp

    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    if op == "/":
        return a / b
    if op == "%":
        return jnp.fmod(a, b)
    if op == "^":
        return jnp.power(a, b)
    raise ValueError(f"unknown scalar op {op}")


@functools.lru_cache(maxsize=_PROGRAM_CACHE_CAP)
def _program(sig: tuple, mesh=None):
    """ONE jit'd whole-plan callable per (signature, mesh) — the blessed
    lru_cache factory idiom (see tools/m3lint rules_jax): shape buckets
    reuse it through jax's own executable cache, and the cached
    ``compute_mesh`` singletons make the mesh key identity-stable.

    Sample inputs arrive as [n_dev, cap] SLABS (n_dev == 1 without a
    mesh): device d owns rows [d*Sp/n, (d+1)*Sp/n) and exactly those
    rows' samples, with lo/hi rebased slab-local by host prep. On a mesh
    the base stage runs under shard_map (every gather device-local) and
    each later stage boundary emits with_sharding_constraint — series-
    sharded until the aggregation stage, replicated after it — so the
    SPMD partitioner lowers the grouped segment reductions to psums over
    the series axis."""
    import jax
    import jax.numpy as jnp

    from m3_tpu.ops import temporal, windowed_agg

    base, stages = sig
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from m3_tpu.parallel.mesh import replicated_sharding, row_sharding

        row_sh = row_sharding(mesh)
        rep_sh = replicated_sharding(mesh)

    def _constrain(x, grouped: bool):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, rep_sh if grouped else row_sh)

    def _base_stage(v, adj, t, csum, bmat, lo, hi, eval_ts, range_ns,
                    mm_levels: int):
        """The slab-local base stage: pure stage-kernel math over ONE
        device's samples (or the whole array when unsharded)."""
        if base == "instant":
            return temporal.stage_instant_values(v, lo, hi)
        if base in _EXTRAP:
            is_counter, is_rate = _EXTRAP[base]
            return temporal.stage_extrapolated_rate(
                v, adj, t, lo, hi, eval_ts, range_ns, is_counter, is_rate)
        if base in _INSTANT:
            is_counter, is_rate = _INSTANT[base]
            return temporal.stage_instant_delta(v, t, lo, hi, is_counter,
                                                is_rate)
        if base in _MINMAX:
            if mm_levels == 0:
                # sparse table would exceed the scratch cap: host prep
                # computed the base matrix with the interpreter's exact
                # reduceat math and ships it through bmat
                return bmat
            return temporal.stage_window_minmax(v, lo, hi, mm_levels,
                                                _MINMAX[base])
        return temporal.stage_over_time(_OVER_TIME[base], csum, lo, hi)

    def run(vs, adjs, ts, csums, bmat, lo, hi, eval_ts, range_ns, seg,
            phi, scalars, num_groups: int, mm_levels: int):
        # the named scopes change operation metadata only: the phases'
        # stable names in a device trace
        with jax.named_scope("m3.plan.base"):
            if mesh is None:
                cur = _base_stage(vs[0], adjs[0], ts[0], csums[0], bmat,
                                  lo, hi, eval_ts, range_ns, mm_levels)
            elif base in _MINMAX and mm_levels == 0:
                cur = bmat  # host-computed base, already row-sharded
            else:
                def local(vs, adjs, ts, csums, lo, hi, eval_ts, range_ns):
                    return _base_stage(vs[0], adjs[0], ts[0], csums[0],
                                       None, lo, hi, eval_ts, range_ns,
                                       mm_levels)

                cur = shard_map(
                    local, mesh=mesh,
                    in_specs=(P("series", None),) * 6 + (P(None), P()),
                    out_specs=P("series", None),
                )(vs, adjs, ts, csums, lo, hi, eval_ts, range_ns)
            cur = _constrain(cur, grouped=False)
        si = 0
        grouped = False
        for st in stages:
            if st[0] == "bin":
                _, op, swapped = st
                c = scalars[si]
                si += 1
                a, b = (c, cur) if swapped else (cur, c)
                nxt = _apply_scalar_op(op, a, b)
                if op == "^":
                    # the interpreter _compacts (drops all-NaN rows)
                    # between stages, and ^ is the one covered op whose
                    # elementwise math can resurrect a dead row
                    # (NaN ** 0 == 1 ** NaN == 1.0): a row dead before
                    # the stage must stay dead, so the final _compact
                    # drops exactly the rows the interpreter dropped
                    dead = jnp.all(jnp.isnan(cur), axis=1, keepdims=True)
                    nxt = jnp.where(dead, jnp.nan, nxt)
                cur = nxt
            else:
                _, op = st
                with jax.named_scope("m3.plan.agg"):
                    if op == "quantile":
                        cur = windowed_agg.stage_grouped_quantile(
                            cur, seg, num_groups, phi)
                    else:
                        cur = windowed_agg.stage_grouped_reduce(
                            op, cur, seg, num_groups)
                grouped = True
            cur = _constrain(cur, grouped)
        return cur

    return jax.jit(run, static_argnames=("num_groups", "mm_levels"))


# ---------------------------------------------------------------------------
# plan-shape cache bookkeeping (telemetry + boundedness)
# ---------------------------------------------------------------------------

_plan_lock = threading.Lock()
_plan_cache: OrderedDict = OrderedDict()  # key -> {"hits": n, "misses": n}
_plan_cache_evictions = 0

# metric-label guard: registry counters persist forever, so the shape=
# label set must be bounded even though the signature space is user-
# controlled (ever-longer scalar chains mint fresh signatures — the PR 7
# tenant-label cardinality class). First N distinct shapes get their own
# label; the tail shares "other". ?explain= still carries the full key.
_SHAPE_LABEL_CAP = 64
_shape_labels_seen: set = set()


def _shape_label(key_str: str) -> str:
    with _plan_lock:
        if key_str in _shape_labels_seen:
            return key_str
        if len(_shape_labels_seen) < _SHAPE_LABEL_CAP:
            _shape_labels_seen.add(key_str)
            return key_str
        return "other"


def _plan_cache_record(key: tuple, miss: bool) -> None:
    """Record one use of a plan-shape key. ``miss`` is the GROUND-TRUTH
    compile outcome from the jit tracker (did the executable cache grow),
    not this LRU's own membership — so an eviction here can never relabel
    a still-compiled plan as a miss, nor a real recompile after program-
    factory eviction as a hit."""
    global _plan_cache_evictions
    with _plan_lock:
        rec = _plan_cache.get(key)
        if rec is None:
            rec = _plan_cache[key] = {"hits": 0, "misses": 0}
            while len(_plan_cache) > _PLAN_CACHE_CAP:
                _plan_cache.popitem(last=False)
                _plan_cache_evictions += 1
        else:
            _plan_cache.move_to_end(key)
        rec["misses" if miss else "hits"] += 1


def plan_cache_info() -> dict:
    """Snapshot for tests and /debug surfaces."""
    with _plan_lock:
        return {"|".join(str(p) for p in k): dict(v)
                for k, v in _plan_cache.items()}


def plan_cache_stats() -> dict:
    """Occupancy summary for /debug/compute: bookkeeping entries, cap,
    LRU evictions, and cumulative hit/miss totals across live keys."""
    with _plan_lock:
        hits = sum(r["hits"] for r in _plan_cache.values())
        misses = sum(r["misses"] for r in _plan_cache.values())
        return {"entries": len(_plan_cache), "cap": _PLAN_CACHE_CAP,
                "evictions": _plan_cache_evictions,
                "hits": hits, "misses": misses}


def clear_plan_cache() -> None:
    with _plan_lock:
        _plan_cache.clear()
        _shape_labels_seen.clear()


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------


def _fallback(reason: str):
    """Counted, traced, never an error."""
    from m3_tpu.query import explain as explain_mod
    from m3_tpu.utils import trace
    from m3_tpu.utils.instrument import default_registry

    dispatch.counters["query.compile[fallback]"] += 1
    default_registry().root_scope("compute").subscope(
        "query_plan").counter("fallback")
    with trace.span(trace.QUERY_COMPILE_FALLBACK, reason=reason):
        pass
    col = explain_mod.current()
    if col is not None:
        col.set_compiled({"ran": False, "reason": reason})
    return None


def _host_prefers_interpreter(spec: PlanSpec) -> bool:
    """The per-PLAN rung of the XLA/native/scalar dispatch ladder: on a
    CPU-only backend, extrapolated-rate bases are served faster by the
    interpreter's native columnar kernel (ops.native_hostops.rate_csr —
    a pointer-walk the XLA lowering can't match on host; profiled ~2.4x
    on a CPU host), so a config-enabled engine declines them
    unless an accelerator is live. M3_TPU_QUERY_COMPILE=1 (the explicit
    hatch) overrides — tests and accelerator-bound benches force the
    fused program."""
    if spec.base not in _EXTRAP:
        return False
    if dispatch._accelerator_present():
        return False
    if os.environ.get("M3_TPU_NATIVE_OPS") == "0":
        return False
    from m3_tpu.ops import native_hostops

    return native_hostops.available()


def _group_ids(labels: list, grouping: tuple, without: bool):
    """(seg ids [S], output group labels) built from the engine's shared
    ``grouping_keys`` helper — ONE definition of the by/without key
    semantics, so the compiled path cannot drift from _eval_aggregate."""
    from m3_tpu.query.engine import grouping_keys

    keys, out_labels_for = grouping_keys(labels, grouping, without)
    uniq = sorted(set(keys))
    gid = {k: i for i, k in enumerate(uniq)}
    seg = np.array([gid[k] for k in keys], np.int32) if keys \
        else np.empty(0, np.int32)
    return seg, [dict(out_labels_for[k]) for k in uniq]


def try_execute(engine, expr: Expr, eval_ts: np.ndarray):
    """Compile-and-run `expr` when covered; None means "interpreter's
    turn" (uncovered shape, or a CPU host that serves it faster
    natively), with the fallback counted.

    The decision is made BEFORE any storage work, so falling back never
    double-fetches or double-accounts query limits; past this point the
    compiled path either returns a result or raises like the interpreter
    would (storage errors, limits)."""
    from m3_tpu.utils import trace

    with trace.stage(trace.STAGE_PARSE_PLAN):
        spec = match(expr)
        vspec = match_vecbin(expr) if spec is None else None
    if spec is None:
        if vspec is None:
            return _fallback("uncovered_plan_shape")
        return _try_execute_vecbin(engine, expr, vspec, eval_ts)
    if os.environ.get("M3_TPU_QUERY_COMPILE") != "1" \
            and _host_prefers_interpreter(spec):
        return _fallback("host_native_faster")
    dispatch.counters["query.compile[compiled]"] += 1
    from m3_tpu.query import explain as explain_mod

    col = explain_mod.current()
    return _run_plan(engine, spec, eval_ts, col)


class _TierProbe(NamedTuple):
    """One plan's hot-tier lookup, made BEFORE its fetch."""
    shifted: np.ndarray  # the selector's evaluation grid (@ and offset)
    precision: str  # "f64" | "bf16" (the negotiated mirror): in the key
    hkey: tuple | None  # None: no tier, or a facade without version truth
    entry: dict | None  # the warm prepared entry; None on a miss


def _probe_tier(engine, spec: PlanSpec, eval_ts, fetch_key) -> _TierProbe:
    """Look the plan up in the device-resident hot tier (ROADMAP #3).
    The prepared slab set is fully determined by (fetch content version,
    base, grid, grouping, precision, requested device count), and all of
    it is known before storage is read: `fetch_key` is the engine's
    version key (taken before the read by design), the rest is the
    plan's. One `tier.get` a plan."""
    import zlib

    from m3_tpu.parallel import mesh as mesh_mod
    from m3_tpu.storage import hottier

    shifted = engine._resolve_ts(spec.selector, eval_ts)
    precision = "f64"
    if hottier.query_precision() == "bf16" and spec.base in _BF16_OK_BASES:
        precision = "bf16"
    tier = hottier.default()
    if tier is None or fetch_key is None:
        return _TierProbe(shifted, precision, None, None)
    mesh_req = mesh_mod.active_compute_mesh()
    n_dev_req = int(mesh_req.devices.size) if mesh_req is not None else 1
    bounds_range = spec.range_ns if spec.base != "instant" \
        else engine.lookback_ns
    agg = spec.agg
    agg_key = (agg[2], agg[3]) if agg is not None else None
    grid_fp = (len(eval_ts), zlib.adler32(shifted.tobytes()))
    hkey = (fetch_key, spec.base, int(bounds_range), grid_fp,
            agg_key, precision, n_dev_req)
    return _TierProbe(shifted, precision, hkey, tier.get(hkey))


def _run_plan(engine, spec: PlanSpec, eval_ts, col):
    """Probe + fetch + fused execution of ONE covered chain (shared by
    single-plan queries and each side of a compiled vector-vector
    binop). The hot tier is probed first: a warm entry holds everything
    `_execute` takes from a fetch, so a hit reads nothing — no index
    match, no `read_many` — and charges the query limits with what the
    fetch that prepared the entry was charged."""
    from m3_tpu.utils.instrument import default_registry

    with contextlib.ExitStack() as stack:
        if col is not None:
            for node in spec.nodes[:-1]:
                stack.enter_context(col.node(node))
        # innermost node wraps the fetch: selector-stage attribution
        # lands exactly where the interpreter's plan tree puts it
        with col.node(spec.nodes[-1]) if col is not None \
                else contextlib.nullcontext():
            resolved = engine._resolve_fetch(spec.selector, eval_ts,
                                             spec.range_ns)
            probe = _probe_tier(engine, spec, eval_ts, resolved[-1])
            limits = engine._active_limits()
            if probe.entry is None:
                series0, datapoints0 = limits.charged()
                labels, raws = engine._fetch_resolved(spec.selector,
                                                      resolved)
                series1, datapoints1 = limits.charged()
                charged = (series1 - series0, datapoints1 - datapoints0)
            else:
                labels = raws = None
                charged = probe.entry["charged"]
                # a repeat over a configured limit is refused as the
                # first run was: same counts, same order
                limits.add_series(charged[0])
                limits.add_datapoints(charged[1])
                default_registry().root_scope("storage").subscope(
                    "hot_tier").counter("fetch_skipped")
        out = _execute(engine, spec, labels, raws, charged, eval_ts, col,
                       probe)
    return out


def _try_execute_vecbin(engine, expr, vspec: VecBinSpec, eval_ts):
    """Serve a covered vector-vector binop: each side runs as its own
    fused program (two fetches, exactly like the interpreter's two
    subtree evaluations), then the interpreter's one-to-one default
    matching combines them element-wise in numpy — identical match-key,
    duplicate-series and result-label semantics, including the
    EvalErrors the interpreter raises for many-to-many/many-to-one."""
    if os.environ.get("M3_TPU_QUERY_COMPILE") != "1" \
            and (_host_prefers_interpreter(vspec.lhs)
                 or _host_prefers_interpreter(vspec.rhs)):
        return _fallback("host_native_faster")
    dispatch.counters["query.compile[compiled]"] += 1
    from m3_tpu.query import explain as explain_mod

    col = explain_mod.current()
    with col.node(expr) if col is not None else contextlib.nullcontext():
        lhs = _run_plan(engine, vspec.lhs, eval_ts, col)
        l_info = col.compiled if col is not None else None
        rhs = _run_plan(engine, vspec.rhs, eval_ts, col)
        r_info = col.compiled if col is not None else None
        out = _combine_vecbin(engine, vspec.op, lhs, rhs)
    if col is not None:
        col.set_compiled({"ran": True, "binop": vspec.op,
                          "sides": [l_info, r_info]})
    return out


def _combine_vecbin(engine, op: str, lhs, rhs):
    """The interpreter's `_vector_binary` restricted to the covered
    shape (arithmetic op, default matching, no group modifiers): same
    match keys, same duplicate-series errors, same result labels, same
    numpy element-wise math — so NaN masks and values are exactly what
    the interpreter computes from the same side vectors."""
    from m3_tpu.query.engine import EvalError, Vector, _apply_op, _compact

    rmap: dict[tuple, int] = {}
    for j, lb in enumerate(rhs.labels):
        k = engine._match_key(lb, None)
        if k in rmap:
            raise EvalError(
                "many-to-many vector matching: duplicate series on "
                "'one' side")
        rmap[k] = j
    out_l, out_v = [], []
    seen: dict[tuple, int] = {}
    for i, lb in enumerate(lhs.labels):
        k = engine._match_key(lb, None)
        j = rmap.get(k)
        if j is None:
            continue
        if k in seen:
            raise EvalError(
                "many-to-one matching requires group_left/group_right")
        seen[k] = i
        raw = _apply_op(op, lhs.values[i], rhs.values[j])
        out_l.append(engine._result_labels(lb, rhs.labels[j], None, False))
        out_v.append(raw)
    T = lhs.values.shape[1] if len(lhs.labels) else (
        rhs.values.shape[1] if len(rhs.labels) else 0
    )
    return _compact(Vector(out_l, np.stack(out_v) if out_v
                           else np.zeros((0, T))))


def _pad_bounds(lo: np.ndarray, hi: np.ndarray, n_samples: int, Sp: int):
    """Half-octave (next_bucket) padding of the [S, T] bound matrices:
    the fused program pays for every padded cell, so the compiler uses
    finer buckets than the per-op kernels' powers of two. ``Sp`` is the
    caller's series bucket (a multiple of the mesh size when sharded).
    Bounds are slab-local CSR sample indices in [0, n_samples]; they
    ship as int32 when that fits — on the hot [S, T] axes that halves
    both the host->device bytes and the gather-index reads — and int64
    on a >2^31-sample slab (int32 would wrap negative and gather
    garbage silently)."""
    S, T = lo.shape
    Tp = dispatch.next_bucket(T)
    dt = np.int32 if n_samples < 2**31 else np.int64
    lo_p = np.zeros((Sp, Tp), dt)
    hi_p = np.zeros((Sp, Tp), dt)
    lo_p[:S, :T] = lo
    hi_p[:S, :T] = hi
    return lo_p, hi_p


# slabs beyond this multiple of the balanced sample volume mean a
# pathologically skewed series->sample distribution; the unsharded
# program is cheaper than shipping mostly-padding slabs
_MESH_SKEW_FACTOR = 4


def _slab_cuts(offsets: np.ndarray, S: int, Sp: int, n_dev: int):
    """Per-device sample-slab boundaries: device d owns the contiguous
    row block [d*Sp/n, (d+1)*Sp/n) and — CSR rows being contiguous —
    exactly one sample slice. Returns (sample cut [n+1], per-row slab
    base offset [S]); padded rows (S..Sp) keep their zero bounds and
    never rebase.

    ``offsets`` may come straight off a binary wire frame
    (utils/wire.unpack_samples -> session CSR merge -> RaggedSeries):
    the frame codec lands int64 row offsets in exactly this layout, so
    a cluster fanout read reaches slab prep with zero per-series
    re-assembly between the HTTP socket and the device slabs."""
    offsets = np.ascontiguousarray(offsets, np.int64)
    rows_per = Sp // n_dev
    row_cut = np.minimum(np.arange(n_dev + 1) * rows_per, S)
    cut = offsets[row_cut]
    base_off = np.repeat(cut[:-1], np.diff(row_cut))
    return cut, base_off


def _fill_slabs(arr: np.ndarray, cut: np.ndarray, cap: int, fill, dtype):
    """[n_dev, cap] slab matrix from one CSR array (one slice per slab)."""
    n_dev = len(cut) - 1
    out = np.full((n_dev, cap), fill, dtype)
    for d in range(n_dev):
        a, b = int(cut[d]), int(cut[d + 1])
        out[d, :b - a] = arr[a:b]
    return out


def _pad_eval_ts(eval_ts: np.ndarray) -> np.ndarray:
    T = len(eval_ts)
    Tp = dispatch.next_bucket(T)
    if Tp == T:
        return eval_ts
    fill = eval_ts[-1] if T else 0
    return np.concatenate([eval_ts, np.full(Tp - T, fill, np.int64)])


# plan bases whose output tolerance permits the hot tier's bf16 value
# mirror (negotiated per query via hottier.negotiated_precision): bases
# that read raw values directly and whose consumers accept last-point /
# extremum precision at bf16 (~3 decimal digits). Rate/delta bases stay
# full precision — differences of close counter values amplify
# quantization — and csum-driven bases gain nothing (the program never
# reads the value slab).
_BF16_OK_BASES = {"instant", "min_over_time", "max_over_time"}


def _prepare_slabs(engine, spec: PlanSpec, labels, raws, charged, shifted,
                   T: int, S: int, agg, precision: str) -> dict:
    """Host prep for one covered plan: window bounds, per-device slab
    fill, grouping — everything about the call that is determined by
    (fetch content, plan base, grid) and therefore cacheable in the
    device-resident hot tier.  Returns the prepared-entry dict; arrays
    are committed to device (ordinary host buffers on CPU backends) so
    a warm entry re-runs the program with zero host->device transfer.
    The entry also keeps, on the host, what `_execute` takes from the
    fetch itself (series and sample counts, the series' labels where no
    aggregation replaces them, what the query limits were `charged`),
    so that a warm entry is served without the fetch (`_run_plan`)."""
    from m3_tpu.ops import temporal
    from m3_tpu.parallel import mesh as mesh_mod
    from m3_tpu.query import windows
    from m3_tpu.utils.instrument import default_registry

    bounds_range = spec.range_ns if spec.base != "instant" \
        else engine.lookback_ns
    lo, hi = raws.window_bounds_batch(shifted, bounds_range)

    # Host prep mirrors the bounds policy: per-SAMPLE sequential passes
    # (prefix sums, counter monotonization) run as one numpy pass — the
    # exact arrays the interpreter gathers from, and numpy's cumsum is an
    # order of magnitude faster than XLA:CPU's — while every per-(series,
    # step) stage fuses into the one traced program below. Samples ship
    # as per-device SLABS (one slab without a mesh): each device owns a
    # contiguous block of series rows and exactly those rows' samples,
    # with lo/hi rebased slab-local, so sharded gathers never touch
    # another device's sample volume.
    n = len(raws.values)
    mesh = mesh_mod.active_compute_mesh()
    n_dev = int(mesh.devices.size) if mesh is not None else 1
    Sp = dispatch.next_bucket(S, multiple=n_dev)
    cut, base_off = _slab_cuts(raws.offsets, S, Sp, n_dev)
    cap = dispatch.next_pow2(int(np.diff(cut).max()))
    if mesh is not None and \
            n_dev * cap > _MESH_SKEW_FACTOR * dispatch.next_pow2(max(n, 1)):
        default_registry().root_scope("compute").subscope(
            "mesh", devices=str(n_dev)).counter("skew_fallback")
        mesh, n_dev = None, 1
        Sp = dispatch.next_bucket(S)
        cut, base_off = _slab_cuts(raws.offsets, S, Sp, 1)
        cap = dispatch.next_pow2(max(n, 1))

    lo_p, hi_p = _pad_bounds(lo - base_off[:, None], hi - base_off[:, None],
                             cap, Sp)
    eval_pad = _pad_eval_ts(shifted)
    Tp = lo_p.shape[1]

    dummy = np.zeros((n_dev, 1))
    ts = np.zeros((n_dev, 1), np.int64)
    mm_levels = 0
    bmat = np.zeros((1, 1))
    vs = adjs = None
    if spec.base in _MINMAX:
        max_len = int((hi - lo).max()) if lo.size else 0
        mm_levels = temporal.minmax_levels(max_len)
        if mm_levels * cap * n_dev > temporal.MINMAX_SCRATCH_ELEMS:
            # sparse table over the scratch cap: compute the base matrix
            # with the interpreter's exact host reduceat and fuse only
            # the downstream stages (mm_levels == 0 selects this in the
            # program signature's static bucket; the sample slabs stay
            # unbuilt — the program only reads bmat on this path)
            mm_levels = 0
            op = np.minimum if _MINMAX[spec.base] else np.maximum
            bmat = np.full((Sp, Tp), np.nan)
            bmat[:S, :T] = windows._reduceat(op, raws.values, lo, hi, np.nan)
    if spec.base == "instant" or spec.base in _EXTRAP \
            or spec.base in _INSTANT or mm_levels > 0:
        vs = _fill_slabs(raws.values, cut, cap, 0.0, np.float64)
    if spec.base in _EXTRAP or spec.base in _INSTANT:
        ts = _fill_slabs(raws.times, cut, cap, np.iinfo(np.int64).max,
                         np.int64)
    if spec.base in _EXTRAP and _EXTRAP[spec.base][0]:
        # counter monotonization is global host prep (bit parity with the
        # interpreter's _reset_adjusted), then sliced per slab
        adjs = _fill_slabs(windows._reset_adjusted(raws), cut, cap, 0.0,
                           np.float64)
    if spec.base in ("sum_over_time", "avg_over_time"):
        csum = np.empty(n + 1)
        csum[0] = 0.0
        np.cumsum(raws.values, out=csum[1:n + 1])
        # slab csums are SLICES of the one global prefix array, so the
        # fused csums[hi]-csums[lo] gather stays bit-identical to the
        # interpreter's global gather on every device count
        csums = np.empty((n_dev, cap + 1))
        for d in range(n_dev):
            a, b = int(cut[d]), int(cut[d + 1])
            csums[d, :b - a + 1] = csum[a:b + 1]
            csums[d, b - a + 1:] = csum[b]
    else:
        # unused by the traced program for every other base (a trace-time
        # constant) — ship one element per device, not O(samples) zeros
        csums = dummy
    if vs is None:
        vs = dummy
    if adjs is None:
        adjs = vs

    if agg is not None:
        _, _aop, grouping, without, _phi = agg
        seg, group_labels = _group_ids(labels, grouping, without)
        G = len(group_labels)
        Gp = dispatch.next_bucket(G + 1)  # +1 reserves the pad-row group
        seg_pad = np.full(Sp, Gp - 1, np.int32)
        seg_pad[:S] = seg
    else:
        group_labels = None
        G, Gp = 0, 1
        seg_pad = np.zeros(Sp, np.int32)

    adjs_is_vs = adjs is vs
    import jax
    import jax.numpy as jnp

    if mesh is not None:
        row_sh = mesh_mod.row_sharding(mesh)

        def put(a):
            return jax.device_put(a, row_sh)

        seg_dev = jax.device_put(seg_pad, mesh_mod.vec_sharding(mesh))
    else:
        put = jax.device_put
        seg_dev = jax.device_put(seg_pad)
    if adjs_is_vs:
        vs = adjs = put(vs)
    else:
        vs, adjs = put(vs), put(adjs)
    if precision == "bf16":
        # the reduced-precision mirror: half the resident bytes; the
        # same quantized values serve the miss call and every warm hit,
        # so repeats are self-consistent
        vs = vs.astype(jnp.bfloat16)
        if adjs_is_vs:
            adjs = vs
    ts, csums = put(ts), put(csums)
    lo_p, hi_p = put(lo_p), put(hi_p)
    eval_pad = jax.device_put(eval_pad)
    if spec.base in _MINMAX and mm_levels == 0:
        bmat = put(bmat)
    arrays = [vs, ts, csums, lo_p, hi_p, eval_pad, seg_dev, bmat]
    if not adjs_is_vs:
        arrays.append(adjs)
    nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
    if agg is None:
        # the host bytes a warm entry holds beside its slabs: the label
        # dicts (their keys and values are the index documents' own)
        nbytes += sys.getsizeof(labels) + sum(map(sys.getsizeof, labels))
    return {"mesh": mesh, "n_dev": n_dev, "Sp": Sp, "Tp": Tp, "Gp": Gp,
            "G": G, "cap": cap, "mm_levels": mm_levels,
            "S": S, "n_samples": n, "charged": charged,
            "labels": labels if agg is None else None,
            "group_labels": group_labels, "adjs_is_vs": adjs_is_vs,
            "vs": vs, "adjs": adjs, "ts": ts, "csums": csums,
            "bmat": bmat, "lo_p": lo_p, "hi_p": hi_p,
            "eval_pad": eval_pad, "seg_pad": seg_dev,
            "precision": precision, "nbytes": nbytes}


def _execute(engine, spec: PlanSpec, labels, raws, charged, eval_ts, col,
             probe: _TierProbe):
    """Run the fused program over the plan's prepared slabs: those of
    the warm hot-tier entry `probe` found (labels, raws: None — nothing
    was fetched; the entry holds what this function would take from
    them), or those prepared here from the fetch, which then enter the
    tier under `probe.hkey`."""
    from m3_tpu.query.engine import Vector, _compact
    from m3_tpu.storage import hottier
    from m3_tpu.utils import trace
    from m3_tpu.utils.instrument import default_registry

    T = len(eval_ts)
    agg = spec.agg
    hkey, entry = probe.hkey, probe.entry
    S = entry["S"] if entry is not None else raws.n_series
    if S == 0:
        # interpreter parity: an empty fetch compacts to an empty vector
        # at the base stage, and every covered stage preserves emptiness
        vec = Vector([], np.zeros((0, T)))
        if col is not None:
            col.set_compiled({"ran": True, "cache_key": "empty",
                              "cache": "hit"})
        return vec

    # one lookup a plan: a warm entry skips the index match and the
    # read (in _run_plan), window bounds, slab fill AND the host->device
    # transfer
    tier = hottier.default()
    hot_state = None
    if hkey is not None:
        hot_state = "hit" if entry is not None else "miss"
        default_registry().root_scope("storage").subscope(
            "hot_tier").counter(hot_state)
    if entry is None:
        with trace.stage(trace.STAGE_SLAB_PREP):
            entry = _prepare_slabs(engine, spec, labels, raws, charged,
                                   probe.shifted, T, S, agg,
                                   probe.precision)
        if hkey is not None:
            tier.put(hkey, entry, entry["nbytes"])
            default_registry().root_scope("storage").subscope(
                "hot_tier").observe("hot_tier_entry_bytes",
                                    float(entry["nbytes"]))
    else:
        labels = entry["labels"]

    mesh = entry["mesh"]
    n_dev = entry["n_dev"]
    Sp, Tp, Gp, cap = entry["Sp"], entry["Tp"], entry["Gp"], entry["cap"]
    mm_levels = entry["mm_levels"]
    G = entry["G"]
    group_labels = entry["group_labels"]
    vs, adjs = entry["vs"], entry["adjs"]
    ts, csums, bmat = entry["ts"], entry["csums"], entry["bmat"]
    lo_p, hi_p = entry["lo_p"], entry["hi_p"]
    eval_pad, seg_pad = entry["eval_pad"], entry["seg_pad"]
    if entry["precision"] == "bf16":
        import jax.numpy as jnp

        vs = vs.astype(jnp.float64)
        if entry["adjs_is_vs"]:
            adjs = vs
    phi = agg[4] if agg is not None else None
    scalars = np.array([st[3] for st in spec.stages if st[0] == "bin"],
                       np.float64)

    sig = spec.sig
    key = (spec.sig_str, Sp, Tp, Gp) + \
        ((n_dev, cap) if mesh is not None else ())
    key_str = f"{spec.sig_str}|S{Sp}|T{Tp}|G{Gp}" + \
        (f"|M{n_dev}x{cap}" if mesh is not None else "")
    with trace.stage(trace.STAGE_PARSE_PLAN):
        program = _program(sig, mesh)
    if mesh is not None:
        dispatch.counters["query.compile[sharded]"] += 1
        default_registry().root_scope("compute").subscope(
            "mesh", devices=str(n_dev)).counter("dispatch")
    prog_args = (vs, adjs, ts, csums, bmat, lo_p, hi_p,
                 eval_pad, np.int64(spec.range_ns), seg_pad,
                 np.float64(phi if phi is not None else 0.0), scalars)
    # the tracker's block holds the enqueue and the read that waits for
    # the program: compute_execute_seconds{op="query_plan"} times
    # completion, one count per launch
    with dispatch.jit_tracker("query_plan", program,
                              sig=key_str) as tracker:
        with trace.stage(trace.STAGE_PLAN_DISPATCH) as fr:
            out = program(*prog_args, num_groups=Gp, mm_levels=mm_levels)
            if tracker.missed():
                fr.name = trace.STAGE_PLAN_COMPILE
        with trace.stage(trace.STAGE_PLAN_WAIT):
            out = np.asarray(out)
    hit = not tracker.miss
    _plan_cache_record(key, miss=tracker.miss)
    sc = default_registry().root_scope("compute").subscope(
        "plan_cache", shape=_shape_label(key_str))
    sc.counter("hit" if hit else "miss")
    if not hit:
        # trace+lower+compile dominates the first call of a new shape
        default_registry().root_scope("compute").subscope(
            "query_plan").observe("plan_compile_seconds", tracker.seconds)

    if agg is not None:
        mat = out[:G, :T]
        out_labels = group_labels
    else:
        mat = out[:S, :T]
        drops_name = spec.base != "instant" or any(
            st[0] == "bin" for st in spec.stages)
        if drops_name:
            out_labels = [{k: v for k, v in lb.items() if k != b"__name__"}
                          for lb in labels]
        else:
            out_labels = [dict(lb) for lb in labels]
    # padding-waste ledger: logical vs half-octave-padded elements per
    # program axis, for THIS query's slabs (warm hot-tier entries count
    # too — the padded cells re-run every call, not just at prep)
    from m3_tpu.utils import compute_stats

    n_samples = entry["n_samples"]
    compute_stats.record_waste("query_slabs", "series", S, Sp)
    compute_stats.record_waste("query_slabs", "time", T, Tp)
    if agg is not None:
        compute_stats.record_waste("query_slabs", "groups", G + 1, Gp)
    compute_stats.record_waste("query_slabs", "samples", n_samples,
                               n_dev * cap)

    if col is not None:
        info = {"ran": True, "cache_key": key_str,
                "cache": "hit" if hit else "miss"}
        # the ?explain=analyze device block: what this query cost on the
        # compute plane — execute/compile wall, padding waste, mesh width
        padding = {"series": {"logical": S, "padded": Sp},
                   "time": {"logical": T, "padded": Tp}}
        if agg is not None:
            padding["groups"] = {"logical": G + 1, "padded": Gp}
        device = {"program": "query_plan", "sig": key_str,
                  "cache": "hit" if hit else "miss",
                  ("compile_seconds" if not hit else "execute_seconds"):
                      tracker.seconds,
                  "padding": padding,
                  "waste_ratio": round(1.0 - (S * T) / (Sp * Tp), 6),
                  "mesh_devices": n_dev}
        info["device"] = device
        if hot_state is not None:
            # the ?explain=analyze hot_tier block: did warm device pages
            # serve this query's slabs, and at what precision
            info["hot_tier"] = {
                "hit": hot_state == "hit",
                "precision": entry["precision"],
                "entries": len(tier),
                "bytes": tier.bytes_used,
            }
        if mesh is not None:
            info["mesh"] = {"axis": "series", "devices": n_dev}
            stage_shardings = [{"stage": f"base:{spec.base}",
                               "spec": "P('series', None)"}]
            grouped = False
            for st in spec.stages:
                if st[0] == "agg":
                    grouped = True
                    stage_shardings.append(
                        {"stage": f"agg:{st[1]}", "spec": "P()"})
                else:
                    stage_shardings.append(
                        {"stage": f"bin:{st[1]}",
                         "spec": "P()" if grouped else "P('series', None)"})
            info["sharding"] = stage_shardings
        col.set_compiled(info)
    return _compact(Vector(out_labels, mat))

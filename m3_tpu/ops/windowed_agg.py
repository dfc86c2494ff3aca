"""Batched windowed aggregation over (element x window) groups.

The device-grid replacement for the reference's per-elem streaming
accumulators (/root/reference/src/aggregator/aggregation/{counter,gauge,
timer}.go and the CKMS quantile streams in aggregation/quantile/cm): raw
(elem, window, value) triples are segment-reduced in one vectorized pass;
quantiles come from a grouped sort — EXACT, unlike CKMS's eps-approximation
(deviation documented per SURVEY.md §7.5; memory is bounded by samples per
open window rather than sketch size).

Two implementations share one contract: the numpy host path below, and a
jax lowering (sort + ``jax.ops.segment_*`` reductions) that
``utils.dispatch`` selects for large flushes on an accelerator — the device
path the aggregator's production flush actually runs, not a test-only
kernel. Inputs are padded to a power of two so XLA compiles O(log) shapes.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from m3_tpu.metrics.aggregation import AggregationType
from m3_tpu.utils import dispatch

# device sort+segment-reduce pays off later than pure elementwise ops
DEVICE_THRESHOLD = 32_768
# below this the numpy path wins over the FFI round trip
NATIVE_THRESHOLD = 4_096


def _order_is_append(order_seq: np.ndarray) -> bool:
    return (len(order_seq) == 0
            or (order_seq[0] == 0 and order_seq[-1] == len(order_seq) - 1
                and bool((np.diff(order_seq) == 1).all())))


def aggregate_groups(
    elem_ids: np.ndarray,  # [N] int64
    window_ids: np.ndarray,  # [N] int64
    values: np.ndarray,  # [N] float64
    order_seq: np.ndarray | None = None,  # [N] append order (LAST tiebreak)
    times: np.ndarray | None = None,  # [N] timestamps; LAST = max time
    need_sorted: bool = True,  # grouped-sorted vq (quantile input) wanted?
):
    """Group by (elem, window) and compute every base statistic.

    Returns (group_elem, group_window, stats dict of [G] arrays, and a
    grouped-sorted values array + group offsets for quantile extraction).
    With ``need_sorted=False`` the returned vq is empty (callers with no
    quantile aggregations skip the grouped sort entirely).
    """
    n = len(values)
    if order_seq is None:
        order_seq = np.arange(n)
    if times is None:
        times = np.zeros(n, np.int64)
    device = n > 0 and dispatch.use_device(n, DEVICE_THRESHOLD)
    dispatch.record("windowed_agg.aggregate_groups", device)
    if device:
        return _aggregate_groups_device(elem_ids, window_ids, values,
                                        order_seq, times)
    # CPU serving path: the native columnar kernel when available and the
    # flush is big enough to amortize the FFI call. The native "last" uses
    # (time, append-index) — identical to the numpy (time, order_seq)
    # tiebreak only when order_seq IS append order, which every engine
    # caller passes; custom order_seq falls through to numpy. NaN values
    # fall through too (native min/max comparisons would skip NaNs).
    if (n >= NATIVE_THRESHOLD and os.environ.get("M3_TPU_NATIVE_OPS") != "0"
            and _order_is_append(order_seq)):
        from m3_tpu.ops import native_hostops

        if native_hostops.available() and not np.isnan(values).any():
            dispatch.counters["windowed_agg.aggregate_groups[native]"] += 1
            return native_hostops.agg_groups(elem_ids, window_ids, values,
                                             times, want_sorted=need_sorted)
    # group identity via lexsort on (elem, window); within a group rows
    # order by (time, append-seq) so LAST = latest timestamp, ties -> the
    # later append (reference gauge lastAt semantics)
    order = np.lexsort((order_seq, times, window_ids, elem_ids))
    e, w, v = elem_ids[order], window_ids[order], values[order]
    if n == 0:
        empty = np.empty(0)
        return (
            np.empty(0, np.int64), np.empty(0, np.int64),
            {k: empty for k in ("count", "sum", "sumsq", "min", "max", "mean",
                                 "last", "stdev")},
            empty, np.zeros(1, np.int64),
        )
    new_group = np.ones(n, bool)
    new_group[1:] = (e[1:] != e[:-1]) | (w[1:] != w[:-1])
    group_start = np.nonzero(new_group)[0]
    offsets = np.concatenate([group_start, [n]])
    counts = np.diff(offsets).astype(np.float64)

    csum = np.concatenate([[0.0], np.cumsum(v)])
    s1 = csum[offsets[1:]] - csum[offsets[:-1]]
    csq = np.concatenate([[0.0], np.cumsum(v * v)])
    s2 = csq[offsets[1:]] - csq[offsets[:-1]]
    gmin = np.minimum.reduceat(v, offsets[:-1])
    gmax = np.maximum.reduceat(v, offsets[:-1])
    mean = s1 / counts
    var = np.maximum(s2 / counts - mean**2, 0.0)
    last = v[offsets[1:] - 1]  # order_seq tiebreak: last append wins

    # grouped sort for quantiles: sort values WITHIN groups
    vq = (values[np.lexsort((values, window_ids, elem_ids))]
          if need_sorted else np.empty(0))

    stats = {
        "count": counts,
        "sum": s1,
        "sumsq": s2,
        "min": gmin,
        "max": gmax,
        "mean": mean,
        "last": last,
        "stdev": np.sqrt(var),
    }
    return e[group_start], w[group_start], stats, vq, offsets


@functools.lru_cache(maxsize=None)
def _grouped_stats_jit():
    """Build the jitted device kernel lazily (jax import deferred)."""
    import jax
    import jax.numpy as jnp

    import m3_tpu.ops  # noqa: F401  (x64)

    @jax.jit
    def kernel(e, w, v, seq, t):
        # sort rows by (elem, window, time, append-seq): group identity plus
        # the LAST-wins ordering inside each group
        order = jnp.lexsort((seq, t, w, e))
        es, ws, vs = e[order], w[order], v[order]
        n = e.shape[0]
        new_group = jnp.concatenate(
            [jnp.ones(1, bool), (es[1:] != es[:-1]) | (ws[1:] != ws[:-1])]
        )
        seg = jnp.cumsum(new_group) - 1  # [N] group index, 0-based
        ones = jnp.ones(n, jnp.float64)
        count = jax.ops.segment_sum(ones, seg, num_segments=n)
        s1 = jax.ops.segment_sum(vs, seg, num_segments=n)
        s2 = jax.ops.segment_sum(vs * vs, seg, num_segments=n)
        gmin = jax.ops.segment_min(vs, seg, num_segments=n)
        gmax = jax.ops.segment_max(vs, seg, num_segments=n)
        idx_last = jax.ops.segment_max(jnp.arange(n), seg, num_segments=n)
        last = vs[jnp.clip(idx_last, 0, n - 1)]
        # grouped sort for quantiles: values ascending WITHIN (elem, window)
        vq = v[jnp.lexsort((v, w, e))]
        return es, ws, new_group, count, s1, s2, gmin, gmax, last, vq

    return kernel


def _aggregate_groups_device(elem_ids, window_ids, values, order_seq, times):
    """jax lowering of aggregate_groups; pads N to a power of two with a
    sentinel group that is trimmed on the way out.

    When the series-sharded compute mesh is armed (M3_TPU_QUERY_SHARD /
    a live multi-device accelerator — parallel.mesh.active_compute_mesh),
    the padded sample triples are placed across it so the flush rollup
    runs as one SPMD program: the kernel's grouped sort makes XLA gather
    rows across devices, but the segment reductions and their combines
    stay partitioned — the m3_agg_groups path rides the same mesh as the
    fused-query plane (the psum-lowered grouped reductions live there)."""
    n = len(values)
    N = dispatch.next_pow2(n)
    pad = N - n
    BIG = np.iinfo(np.int64).max
    e_p = np.concatenate([elem_ids, np.full(pad, BIG, np.int64)])
    w_p = np.concatenate([window_ids, np.full(pad, BIG, np.int64)])
    v_p = np.concatenate([values, np.zeros(pad)])
    s_p = np.concatenate([order_seq.astype(np.int64),
                          np.arange(pad, dtype=np.int64) + (1 << 60)])
    t_p = np.concatenate([times, np.full(pad, BIG, np.int64)])

    from m3_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.active_compute_mesh()
    if mesh is not None and N % int(mesh.devices.size) == 0:
        import jax

        sh = mesh_mod.vec_sharding(mesh)
        e_p, w_p, v_p, s_p, t_p = (jax.device_put(a, sh)
                                   for a in (e_p, w_p, v_p, s_p, t_p))
        dispatch.counters["windowed_agg.aggregate_groups[mesh]"] += 1

    from m3_tpu.utils import compute_stats

    # padding-waste ledger: real sample rows vs the pow2-padded batch
    compute_stats.record_waste("windowed_agg", "samples", n, N)
    kernel = _grouped_stats_jit()
    with dispatch.jit_tracker("grouped_stats", kernel, sig=f"N{N}"):
        out = kernel(e_p, w_p, v_p, s_p, t_p)
        es, ws, new_group, count, s1, s2, gmin, gmax, last, vq = (
            np.asarray(x) for x in out
        )
    group_start = np.nonzero(new_group)[0]
    n_groups_total = len(group_start)
    # pads share the (BIG, BIG) key: exactly one trailing sentinel group
    G = n_groups_total - (1 if pad else 0)
    sel = slice(0, G)
    counts = count[sel]
    mean = s1[sel] / counts
    var = np.maximum(s2[sel] / counts - mean**2, 0.0)
    stats = {
        "count": counts,
        "sum": s1[sel],
        "sumsq": s2[sel],
        "min": gmin[sel],
        "max": gmax[sel],
        "mean": mean,
        "last": last[sel],
        "stdev": np.sqrt(var),
    }
    offsets = np.concatenate([group_start[:G], [n]]).astype(np.int64)
    return es[group_start[:G]], ws[group_start[:G]], stats, vq[:n], offsets


# ---------------------------------------------------------------------------
# pure traced stage kernels over [S, T] value matrices
# ---------------------------------------------------------------------------
#
# The whole-query compiler (query/compiler.py, ROADMAP #2) composes these
# into its fused per-plan XLA program: PromQL `by`/`without` aggregations
# over a [series, steps] matrix with the exact NaN semantics of
# Engine._eval_aggregate (count counts non-NaN; empty groups are NaN).
# ``seg`` maps each series row to its group id; ``num_groups`` is a
# trace-time constant (the compiler's group-count bucket).


def stage_grouped_reduce(op: str, vals, seg, num_groups: int):
    """sum/avg/min/max/count over groups of rows; [num_groups, T] out."""
    import jax
    import jax.numpy as jnp

    nan = jnp.isnan(vals)
    count = jax.ops.segment_sum((~nan).astype(jnp.float64), seg,
                                num_segments=num_groups)
    any_present = count > 0
    if op == "count":
        out = count
    elif op in ("sum", "avg"):
        s1 = jax.ops.segment_sum(jnp.where(nan, 0.0, vals), seg,
                                 num_segments=num_groups)
        out = s1 if op == "sum" else s1 / jnp.where(any_present, count, 1)
    elif op == "min":
        out = jax.ops.segment_min(jnp.where(nan, jnp.inf, vals), seg,
                                  num_segments=num_groups)
    elif op == "max":
        out = jax.ops.segment_max(jnp.where(nan, -jnp.inf, vals), seg,
                                  num_segments=num_groups)
    else:
        raise ValueError(f"unknown grouped reduce op {op}")
    return jnp.where(any_present, out, jnp.nan)


def stage_grouped_quantile(vals, seg, num_groups: int, phi):
    """Prometheus-interpolated quantile per (group, step), NaN-aware.

    One grouped sort per step column (rows ordered (group, value), NaN
    last within each group — the jnp sort order matches numpy's) and a
    rank-interpolating gather, mirroring Engine._quantile_cols: empty
    (group, step) -> NaN, phi < 0 -> -inf, phi > 1 -> +inf."""
    import jax
    import jax.numpy as jnp

    S = vals.shape[0]
    T = vals.shape[1]
    sizes = jax.ops.segment_sum(jnp.ones(S), seg, num_segments=num_groups)
    starts = jnp.concatenate(
        [jnp.zeros(1), jnp.cumsum(sizes)])[:-1].astype(jnp.int64)  # [G]
    # one 2-D lexsort down the columns: primary key seg, ties by value,
    # NaN last within each group (jnp float sort order matches numpy's)
    order = jnp.lexsort(
        (vals, jnp.broadcast_to(seg[:, None], vals.shape)), axis=0)
    sorted_cols = jnp.take_along_axis(vals, order, axis=0)
    cnt = jax.ops.segment_sum((~jnp.isnan(vals)).astype(jnp.float64), seg,
                              num_segments=num_groups)  # [G, T]
    present = cnt > 0
    rank = jnp.where(present, phi * (cnt - 1), 0.0)
    rank_lo = jnp.floor(rank)
    i_lo = jnp.clip(rank_lo.astype(jnp.int64), 0, S - 1)
    i_hi = jnp.clip(jnp.minimum(i_lo + 1, cnt.astype(jnp.int64) - 1),
                    0, S - 1)
    cols = jnp.arange(T)[None, :]
    base = starts[:, None]
    v0 = sorted_cols[jnp.clip(base + i_lo, 0, S - 1), cols]
    v1 = sorted_cols[jnp.clip(base + i_hi, 0, S - 1), cols]
    out = v0 + (rank - rank_lo) * (v1 - v0)
    out = jnp.where(phi < 0, -jnp.inf, out)
    out = jnp.where(phi > 1, jnp.inf, out)
    return jnp.where(present, out, jnp.nan)


def group_quantiles(vq: np.ndarray, offsets: np.ndarray, q: float) -> np.ndarray:
    """Interpolated quantile per group from grouped-sorted values.

    Same interpolation as the reference timer aggregation contract
    (linear between closest ranks).
    """
    counts = np.diff(offsets)
    rank = q * (counts - 1)
    lo = np.floor(rank).astype(np.int64)
    frac = rank - lo
    i0 = offsets[:-1] + lo
    i1 = np.minimum(i0 + 1, offsets[1:] - 1)
    return vq[i0] * (1 - frac) + vq[i1] * frac


def extract(
    agg_type: AggregationType,
    stats: dict,
    vq: np.ndarray,
    offsets: np.ndarray,
) -> np.ndarray:
    q = agg_type.quantile
    if q is not None:
        return group_quantiles(vq, offsets, q)
    key = {
        AggregationType.LAST: "last",
        AggregationType.MIN: "min",
        AggregationType.MAX: "max",
        AggregationType.MEAN: "mean",
        AggregationType.COUNT: "count",
        AggregationType.SUM: "sum",
        AggregationType.SUMSQ: "sumsq",
        AggregationType.STDEV: "stdev",
    }[agg_type]
    return stats[key]

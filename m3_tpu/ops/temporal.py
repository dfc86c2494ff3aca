"""Device kernels for the PromQL temporal hot loops.

The jax lowering of m3_tpu.query.windows' columnar math (reference hot
loops: /root/reference/src/query/functions/temporal/{rate,aggregation}.go):
window bounds (cheap searchsorted) stay on host; the heavy [S x steps]
matrix math — prefix-sum window reductions, extrapolated-rate algebra,
staleness gathers — runs as one fused XLA program per shape.

All kernels take ragged sample arrays padded to a power of two (values
pad 0.0, so prefix sums are unaffected; lo/hi indices never reach pads)
and a [S, steps] lo/hi bound pair. ``query.windows`` dispatches here via
``utils.dispatch`` and keeps numpy as the flag-off fallback.

The math bodies live in the module-level ``stage_*`` functions: PURE
traced functions of jax arrays with no dispatch, padding or host logic.
The per-op jitted wrappers below (``_kernels``) and the whole-query
compiler (``query/compiler.py``, ROADMAP #2) compose the SAME stage
functions — op-by-op dispatch and whole-plan fusion share one
implementation, so a plan fused end-to-end cannot drift numerically from
the per-op kernels it replaced.
"""

from __future__ import annotations

import functools

import numpy as np

from m3_tpu.utils import dispatch

NS = 1_000_000_000



def _pad_samples(values: np.ndarray, times: np.ndarray | None = None):
    n = len(values)
    N = dispatch.next_pow2(n)
    v = np.concatenate([values, np.zeros(N - n)])
    if times is None:
        return v, None
    t = np.concatenate([times, np.full(N - n, np.iinfo(np.int64).max, np.int64)])
    return v, t


# ---------------------------------------------------------------------------
# pure traced stage kernels (composable: see module doc)
# ---------------------------------------------------------------------------


def stage_sum_avg_std(v, lo, hi):
    """(count, s1, s2) per window via prefix sums (pads are 0.0, so the
    cumsum tail never changes a window that ends before the pad)."""
    import jax.numpy as jnp

    csum = jnp.concatenate([jnp.zeros(1), jnp.cumsum(v)])
    csq = jnp.concatenate([jnp.zeros(1), jnp.cumsum(v * v)])
    count = (hi - lo).astype(jnp.float64)
    return count, csum[hi] - csum[lo], csq[hi] - csq[lo]


def stage_instant_values(v, lo, hi):
    """Latest sample per (series, step) window, NaN when empty — the
    PromQL lookback/staleness gather."""
    import jax.numpy as jnp

    has = hi > lo
    idx = jnp.clip(hi - 1, 0, v.shape[0] - 1)
    return jnp.where(has, v[idx], jnp.nan)


def stage_over_time(fn: str, csum, lo, hi):
    """sum/avg/count/present_over_time matrices with the NaN-when-empty
    masking of windows.over_time — ``fn`` is a trace-time constant.

    ``csum`` is the [n+1] sample prefix-sum array, computed on HOST like
    the window bounds (np.cumsum — the exact array windows._window_sums
    gathers from, so the fused path is bit-identical to the interpreter
    here; XLA:CPU's own cumsum is also an order of magnitude slower than
    numpy's, see the whole-query compiler's host-prep note)."""
    import jax.numpy as jnp

    count = (hi - lo).astype(jnp.float64)
    empty = count == 0
    if fn == "count":
        return jnp.where(empty, jnp.nan, count)
    if fn == "present":
        return jnp.where(empty, jnp.nan, 1.0)
    s1 = csum[hi] - csum[lo]
    if fn == "sum":
        return jnp.where(empty, jnp.nan, s1)
    if fn == "avg":
        return jnp.where(empty, jnp.nan, s1 / jnp.where(empty, 1, count))
    raise ValueError(f"unknown composable over_time fn {fn}")


def stage_extrapolated_rate(v, adj, t, lo, hi, eval_ts, range_ns,
                            is_counter: bool, is_rate: bool):
    """Mirrors upstream promql extrapolatedRate (windows.py host path).

    Known deviation: XLA may reassociate (sampled/count)*1.1 when
    computing the extrapolation threshold, so a window whose edge gap
    EXACTLY equals the threshold (possible only with perfectly regular
    sample spacing) can take the other extrapolation branch than the
    numpy path. Both branches are valid upstream-Prometheus behavior;
    off the knife edge the paths agree bit-for-bit on exact inputs."""
    import jax.numpy as jnp

    n = v.shape[0]
    count = (hi - lo).astype(jnp.float64)
    ok = count >= 2
    safe_lo = jnp.clip(lo, 0, n - 1)
    safe_hi = jnp.clip(hi - 1, 0, n - 1)
    first_v = adj[safe_lo]
    last_v = adj[safe_hi]
    raw_first_v = v[safe_lo]
    first_t = t[safe_lo].astype(jnp.float64)
    last_t = t[safe_hi].astype(jnp.float64)
    result = last_v - first_v

    window_start = (eval_ts - range_ns).astype(jnp.float64)[None, :]
    window_end = eval_ts.astype(jnp.float64)[None, :]
    sampled = (last_t - first_t) / NS
    dur_to_start = (first_t - window_start) / NS
    dur_to_end = (window_end - last_t) / NS
    avg_between = sampled / jnp.maximum(count - 1, 1)
    threshold = avg_between * 1.1

    if is_counter:
        dur_to_zero = jnp.where(
            result > 0, sampled * (raw_first_v / result), jnp.inf
        )
        dur_to_start = jnp.where(
            (result > 0) & (raw_first_v >= 0) & (dur_to_zero < dur_to_start),
            dur_to_zero,
            dur_to_start,
        )

    dur_to_start = jnp.where(dur_to_start >= threshold, avg_between / 2,
                             dur_to_start)
    dur_to_end = jnp.where(dur_to_end >= threshold, avg_between / 2,
                           dur_to_end)

    extrap = sampled + dur_to_start + dur_to_end
    factor = jnp.where(sampled > 0, extrap / sampled, jnp.nan)
    out = result * factor
    if is_rate:
        out = out / (range_ns / NS)
    return jnp.where(ok & (sampled > 0), out, jnp.nan)


def stage_instant_delta(v, t, lo, hi, is_counter: bool, is_rate: bool):
    """irate/idelta from the last two samples in each window
    (windows.instant_delta host math)."""
    import jax.numpy as jnp

    n = v.shape[0]
    ok = (hi - lo) >= 2
    i_last = jnp.clip(hi - 1, 0, n - 1)
    i_prev = jnp.clip(hi - 2, 0, n - 1)
    v_last, v_prev = v[i_last], v[i_prev]
    t_last = t[i_last].astype(jnp.float64)
    t_prev = t[i_prev].astype(jnp.float64)
    diff = v_last - v_prev
    if is_counter:
        diff = jnp.where(v_last < v_prev, v_last, diff)
    out = diff
    if is_rate:
        dt = (t_last - t_prev) / NS
        out = jnp.where(dt > 0, diff / dt, jnp.nan)
    return jnp.where(ok, out, jnp.nan)


def stage_window_minmax(v, lo, hi, levels: int, is_min: bool):
    """min/max_over_time via a sparse table (ROADMAP carried follow-up):
    ``levels`` log-levels of shifted pairwise min/max over the sample
    array — m[k][i] = op(v[i : i + 2^k]) — then every (series, step)
    window answers with TWO gathers: op(m[k][lo], m[k][hi - 2^k]) where
    k = floor(log2(hi - lo)). The two anchored ranges tile [lo, hi)
    with overlap, which min/max absorb. O(N log W) build amortized over
    all S x T windows vs the O(N W) rescan; NaN samples propagate
    through the table exactly like np.minimum.reduceat on the host
    path, so the compiled result is bit-identical to the interpreter.

    ``levels`` is a trace-time constant (bucketed from the query's max
    window sample count, so executables stay O(log) per axis); window
    reads never cross a series row (bounds are row-local), so pad and
    neighbor-row contamination in high table levels is unreachable."""
    import jax
    import jax.numpy as jnp

    op = jnp.minimum if is_min else jnp.maximum
    fill = jnp.inf if is_min else -jnp.inf
    n = v.shape[0]
    rows = [v]
    cur = v
    for k in range(1, levels):
        w = 1 << (k - 1)
        shifted = jnp.concatenate([cur[w:], jnp.full((w,), fill)])[:n]
        cur = op(cur, shifted)
        rows.append(cur)
    tbl = jnp.stack(rows)  # [levels, N]
    length = hi - lo
    has = length > 0
    safe_len = jnp.maximum(length, 1).astype(jnp.int64)
    k = (63 - jax.lax.clz(safe_len)).astype(lo.dtype)
    k = jnp.clip(k, 0, levels - 1)
    span = jnp.left_shift(jnp.ones((), lo.dtype), k)
    a = tbl[k, jnp.clip(lo, 0, n - 1)]
    b = tbl[k, jnp.clip(hi - span, 0, n - 1)]
    return jnp.where(has, op(a, b), jnp.nan)


def stage_reset_adjusted(v, is_first, row_start_index):
    """Counter monotonization: v + cumulative in-row reset drops.
    row_start_index[i] = index of sample i's row's first sample."""
    import jax.numpy as jnp

    prev = jnp.concatenate([jnp.zeros(1), v[:-1]])
    drop = jnp.where((v < prev) & ~is_first, prev, 0.0)
    cdrop = jnp.cumsum(drop)
    cdrop0 = jnp.concatenate([jnp.zeros(1), cdrop])
    row_base = cdrop0[row_start_index]
    return v + (cdrop - row_base)


@functools.lru_cache(maxsize=None)
def _kernels():
    import jax
    import jax.numpy as jnp

    import m3_tpu.ops  # noqa: F401  (x64)

    @functools.partial(jax.jit, static_argnames=("max_len",))
    def holt_winters(v, lo, hi, sf, tf, max_len):
        """Double exponential smoothing per window (windows.holt_winters
        host math, upstream Prometheus semantics): fori_loop over window
        OFFSETS with [S, steps] state matrices — the per-sample recurrence
        is sequential, so time is the loop axis and (series x step) the
        vector axis (the layout the TPU VPU wants)."""
        n = v.shape[0]
        shape = lo.shape

        def body(j, st):
            found_first, found_second, prev, curr, trend, idx = st
            pos = lo + j
            val = v[jnp.clip(pos, 0, n - 1)]
            valid = (pos < hi) & ~jnp.isnan(val)
            take_first = valid & ~found_first
            curr = jnp.where(take_first, val, curr)
            idx = idx + take_first
            found_first = found_first | take_first
            sub = valid & found_first & ~take_first
            take_second = sub & ~found_second
            trend = jnp.where(take_second, val - curr, trend)
            found_second = found_second | take_second
            tv = jnp.where(idx == 1, trend,
                           tf * (curr - prev) + (1 - tf) * trend)
            new_curr = sf * val + (1 - sf) * (curr + tv)
            prev = jnp.where(sub, curr, prev)
            trend = jnp.where(sub, tv, trend)
            curr = jnp.where(sub, new_curr, curr)
            idx = idx + sub
            return (found_first, found_second, prev, curr, trend, idx)

        init = (jnp.zeros(shape, bool), jnp.zeros(shape, bool),
                jnp.zeros(shape), jnp.zeros(shape), jnp.zeros(shape),
                jnp.zeros(shape, jnp.int64))
        _ff, fs, _p, curr, _tr, _i = jax.lax.fori_loop(0, max_len, body, init)
        return jnp.where(fs, curr, jnp.nan)

    return {
        "sum_avg_std": jax.jit(stage_sum_avg_std),
        "instant_values": jax.jit(stage_instant_values),
        "extrapolated_rate": jax.jit(
            stage_extrapolated_rate,
            static_argnames=("is_counter", "is_rate")),
        "holt_winters": holt_winters,
        "reset_adjusted": jax.jit(stage_reset_adjusted),
        "window_minmax": jax.jit(
            stage_window_minmax, static_argnames=("levels", "is_min")),
    }


def _pad_bounds(lo: np.ndarray, hi: np.ndarray):
    """Pad BOTH axes to powers of two with empty windows, so varying
    series counts AND step counts (dashboard zooms) reuse O(log^2)
    compiled shapes instead of one XLA program per exact shape."""
    S, T = lo.shape
    Sp, Tp = dispatch.next_pow2(S), dispatch.next_pow2(T)
    if Sp == S and Tp == T:
        return lo, hi, S, T
    lo_p = np.zeros((Sp, Tp), np.int64)
    hi_p = np.zeros((Sp, Tp), np.int64)
    lo_p[:S, :T] = lo
    hi_p[:S, :T] = hi
    return lo_p, hi_p, S, T


def _pad_eval_ts(eval_ts: np.ndarray) -> np.ndarray:
    T = len(eval_ts)
    Tp = dispatch.next_pow2(T)
    if Tp == T:
        return eval_ts
    fill = eval_ts[-1] if T else 0
    return np.concatenate([eval_ts, np.full(Tp - T, fill, np.int64)])


def reset_adjust_inputs(offsets: np.ndarray, n: int, n_padded: int):
    """(is_first, row_start_index) arrays for stage_reset_adjusted over a
    CSR sample array padded from n to n_padded (pads form their own row)."""
    is_first = np.zeros(n_padded, bool)
    is_first[offsets[:-1][offsets[:-1] < n]] = True
    row_id = np.repeat(np.arange(len(offsets) - 1), np.diff(offsets))  # [n]
    row_start = np.full(n_padded, n, np.int64)
    row_start[:n] = offsets[:-1][row_id]
    if n_padded > n:
        is_first[n] = True
    return is_first, row_start


def instant_values(values: np.ndarray, lo: np.ndarray, hi: np.ndarray):
    v, _ = _pad_samples(values)
    lo_p, hi_p, S, T = _pad_bounds(lo, hi)
    out = _kernels()["instant_values"](v, lo_p, hi_p)
    return np.asarray(out)[:S, :T]


def sum_avg_std(values: np.ndarray, lo: np.ndarray, hi: np.ndarray):
    v, _ = _pad_samples(values)
    lo_p, hi_p, S, T = _pad_bounds(lo, hi)
    count, s1, s2 = _kernels()["sum_avg_std"](v, lo_p, hi_p)
    return (np.asarray(count)[:S, :T], np.asarray(s1)[:S, :T],
            np.asarray(s2)[:S, :T])


def extrapolated_rate(
    values: np.ndarray,
    adjusted: np.ndarray,
    times: np.ndarray,
    lo: np.ndarray,
    hi: np.ndarray,
    eval_ts: np.ndarray,
    range_ns: int,
    is_counter: bool,
    is_rate: bool,
):
    v, t = _pad_samples(values, times)
    adj, _ = _pad_samples(adjusted)
    lo_p, hi_p, S, T = _pad_bounds(lo, hi)
    out = _kernels()["extrapolated_rate"](
        v, adj, t, lo_p, hi_p, _pad_eval_ts(eval_ts), np.int64(range_ns),
        bool(is_counter), bool(is_rate),
    )
    return np.asarray(out)[:S, :T]


def holt_winters(values: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                 sf: float, tf: float):
    v, _ = _pad_samples(values)
    lo_p, hi_p, S, T = _pad_bounds(lo, hi)
    max_len = int((hi - lo).max()) if lo.size else 0
    # pad the static loop bound to a power of two: extra offsets fall
    # outside every window (pos >= hi) and no-op, buying shape reuse
    max_len = dispatch.next_pow2(max(max_len, 1))
    out = _kernels()["holt_winters"](v, lo_p, hi_p, float(sf), float(tf),
                                     max_len)
    return np.asarray(out)[:S, :T]


# sparse-table scratch bound: levels x padded-sample f64 elements (128MB);
# past it the min/max base stays on the host reduceat path
MINMAX_SCRATCH_ELEMS = 1 << 24


def minmax_levels(max_len: int) -> int:
    """Static level count for stage_window_minmax, bucketed to powers of
    two so nearby max-window-lengths share one executable."""
    return max(dispatch.next_pow2(max(max_len, 1)).bit_length(), 1)


def window_minmax(values: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                  is_min: bool):
    """Device min/max_over_time over [lo, hi) windows (sparse table)."""
    v, _ = _pad_samples(values)
    lo_p, hi_p, S, T = _pad_bounds(lo, hi)
    levels = minmax_levels(int((hi - lo).max()) if lo.size else 0)
    out = _kernels()["window_minmax"](v, lo_p, hi_p, levels, bool(is_min))
    return np.asarray(out)[:S, :T]


def reset_adjusted(values: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Device counter monotonization over CSR rows."""
    n = len(values)
    if n == 0:
        return values
    v, _ = _pad_samples(values)
    is_first, row_start = reset_adjust_inputs(offsets, n, len(v))
    out = _kernels()["reset_adjusted"](v, is_first, row_start)
    return np.asarray(out)[:n]

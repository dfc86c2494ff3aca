"""Windowed series math: the PromQL temporal-function core.

Role parity with the reference's temporal op library
(/root/reference/src/query/functions/temporal/{rate,aggregation,functions,
linear_regression}.go), reproducing upstream Prometheus numeric semantics
(extrapolated rates with counter-reset adjustment and zero-point capping,
population stddev, least-squares deriv) so results diff cleanly against
Prometheus — the comparator requirement in SURVEY.md §4.6.

Everything here is columnar: one call computes a whole [n_series, n_steps]
matrix from ragged per-series sample arrays using prefix sums + searchsorted
window bounds (no per-sample Python loops). Large fetches dispatch the
matrix math to the jax kernels in m3_tpu.ops.temporal (ops.dispatch policy,
M3_TPU_DEVICE_OPS to force); numpy remains the flag-off host fallback.
min/max over overlapping windows stay host-side (ufunc.reduceat has no
segment-op equivalent).
"""

from __future__ import annotations

import os

import numpy as np

from m3_tpu.utils import dispatch

NS = 1_000_000_000


# elementwise matrix math wins earlier than sort-based ops (kept here, not
# in ops/temporal: the numpy interpreter must run in a process without jax)
DEVICE_THRESHOLD = 16_384


def _use_device(raws: "RaggedSeries", eval_ts: np.ndarray) -> bool:
    work = len(raws.values) + raws.n_series * len(eval_ts)
    return dispatch.use_device(work, DEVICE_THRESHOLD)


class RaggedSeries:
    """Concatenated samples of S series + row offsets (CSR-style)."""

    def __init__(self, times: np.ndarray, values: np.ndarray, offsets: np.ndarray):
        self.times = times  # [N] int64 ns, ascending within each row
        self.values = values  # [N] float64
        self.offsets = offsets  # [S+1] int64 row boundaries

    @classmethod
    def from_lists(cls, per_series: list[tuple[np.ndarray, np.ndarray]]):
        if per_series:
            times = np.concatenate([t for t, _ in per_series])
            values = np.concatenate([v for _, v in per_series])
            lens = np.array([len(t) for t, _ in per_series], np.int64)
        else:
            times = np.empty(0, np.int64)
            values = np.empty(0, np.float64)
            lens = np.empty(0, np.int64)
        offsets = np.concatenate([[0], np.cumsum(lens)])
        return cls(times, values, offsets)

    @property
    def n_series(self) -> int:
        return len(self.offsets) - 1

    def window_bounds(self, eval_ts: np.ndarray, range_ns: int):
        """[lo, hi) sample index bounds of window (t-range, t] per
        (series, step)."""
        S = self.n_series
        lo = np.empty((S, len(eval_ts)), np.int64)
        hi = np.empty((S, len(eval_ts)), np.int64)
        for s in range(S):
            a, b = self.offsets[s], self.offsets[s + 1]
            row = self.times[a:b]
            lo[s] = a + np.searchsorted(row, eval_ts - range_ns, side="right")
            hi[s] = a + np.searchsorted(row, eval_ts, side="right")
        return lo, hi

    def window_bounds_batch(self, eval_ts: np.ndarray, range_ns: int):
        """window_bounds without the per-series Python loop, for an
        ASCENDING eval grid (what the engine always evaluates on).

        Inverts the search: instead of S x T binary searches over sample
        rows, every SAMPLE finds its first covering step in the tiny
        eval grid (one N x log T searchsorted, cache-hot), and the per-
        (series, step) counts come from a 2-D bincount + cumsum along
        steps — hi[s, t] = offsets[s] + #{samples in row s with time <=
        eval_ts[t]} by construction. The whole-query compiler's host
        prep uses this; a 100k-series fetch costs two vectorized passes,
        not 200k searchsorted calls. Falls back to the loop for
        non-ascending grids."""
        S = self.n_series
        T = len(eval_ts)
        n = len(self.times)
        if S == 0 or n == 0 or T == 0:
            z = np.zeros((S, T), np.int64)
            return z, z.copy()
        diffs = np.diff(eval_ts)
        if not bool((diffs >= 0).all()) \
                or S * (T + 1) > (1 << 26):  # bincount scratch cap ~0.5GB
            return self.window_bounds(eval_ts, range_ns)
        row_id = np.repeat(np.arange(S, dtype=np.int64),
                           np.diff(self.offsets))

        def counts(grid: np.ndarray) -> np.ndarray:
            # first step whose grid value >= sample time: the sample is
            # inside windows ending at that step and later (last slot =
            # outside every window, dropped before the cumsum)
            W = len(grid)
            pos = np.searchsorted(grid, self.times, side="left")
            hist = np.bincount(row_id * (W + 1) + pos,
                               minlength=S * (W + 1))
            return np.cumsum(hist.reshape(S, W + 1)[:, :W], axis=1)

        base = self.offsets[:-1][:, None]
        step = int(diffs[0]) if T > 1 else 0
        if step > 0 and range_ns % step == 0 \
                and bool((diffs == step).all()) \
                and S * (T + range_ns // step + 1) <= (1 << 26):
            # uniform grid, range a step multiple (every dashboard query):
            # lo's grid is hi's shifted k steps, so ONE counts pass over
            # the k-extended grid yields both bound matrices
            k = range_ns // step
            ext = np.concatenate([
                eval_ts[0] - np.arange(k, 0, -1, dtype=np.int64) * step,
                eval_ts])
            c = counts(ext)
            hi = base + c[:, k:]
            lo = base + c[:, :T]
        else:
            hi = base + counts(eval_ts)
            lo = base + counts(eval_ts - range_ns)
        return lo.astype(np.int64), hi.astype(np.int64)


def instant_values(raws: RaggedSeries, eval_ts: np.ndarray, lookback_ns: int):
    """Instant-vector matrix [S, n_steps]: latest sample in (t-lookback, t],
    NaN when none (the PromQL staleness rule)."""
    if len(raws.values) == 0:
        return np.full((raws.n_series, len(eval_ts)), np.nan)
    lo, hi = raws.window_bounds(eval_ts, lookback_ns)
    device = _use_device(raws, eval_ts)
    dispatch.record("temporal.instant_values", device)
    if device:
        from m3_tpu.ops import temporal

        return temporal.instant_values(raws.values, lo, hi)
    has = hi > lo
    idx = np.clip(hi - 1, 0, len(raws.values) - 1)
    return np.where(has, raws.values[idx], np.nan)


def _window_sums(raws: RaggedSeries, lo, hi, arr):
    """Sum of arr over [lo, hi) via prefix sums."""
    csum = np.concatenate([[0.0], np.cumsum(arr, dtype=np.float64)])
    return csum[hi] - csum[lo]


def _reduceat(op, arr, lo, hi, empty_fill):
    """Per-window reduce for overlapping [lo, hi) windows via ufunc.reduceat."""
    lo_f, hi_f = lo.ravel(), hi.ravel()
    n = len(arr)
    if n == 0:
        return np.full(lo.shape, empty_fill)
    pairs = np.empty(2 * len(lo_f), np.int64)
    pairs[0::2] = np.minimum(lo_f, n - 1)
    pairs[1::2] = np.minimum(hi_f, n - 1)
    # reduceat([i, j]) reduces arr[i:j] at even slots (arr[i] when i >= j)
    red = op.reduceat(arr, pairs)[0::2]
    red = np.where(hi_f > lo_f, red, empty_fill)
    # windows whose hi was clipped from n to n-1 are missing the last sample
    clipped = (hi_f == n) & (hi_f > lo_f)
    if clipped.any():
        red = np.where(clipped, op(red, arr[-1]), red)
    return red.reshape(lo.shape)


def over_time(fn: str, raws: RaggedSeries, eval_ts: np.ndarray, range_ns: int):
    """<fn>_over_time matrices; NaN where the window holds no samples."""
    lo, hi = raws.window_bounds(eval_ts, range_ns)
    count = (hi - lo).astype(np.float64)
    empty = count == 0
    if fn in ("sum", "avg", "stddev", "stdvar") and _use_device(raws, eval_ts):
        from m3_tpu.ops import temporal

        dispatch.record("temporal.over_time", True)
        dcount, s1, s2 = temporal.sum_avg_std(raws.values, lo, hi)
        if fn == "sum":
            return np.where(empty, np.nan, s1)
        if fn == "avg":
            return np.where(empty, np.nan, s1 / np.where(empty, 1, dcount))
        mean = s1 / np.where(empty, 1, dcount)
        var = np.maximum(s2 / np.where(empty, 1, dcount) - mean**2, 0.0)
        out = var if fn == "stdvar" else np.sqrt(var)
        return np.where(empty, np.nan, out)
    if fn in ("sum", "avg", "stddev", "stdvar"):
        dispatch.record("temporal.over_time", False)
    if fn == "count":
        return np.where(empty, np.nan, count)
    if fn == "present":
        return np.where(empty, np.nan, 1.0)
    if fn == "sum":
        return np.where(empty, np.nan, _window_sums(raws, lo, hi, raws.values))
    if fn == "avg":
        s = _window_sums(raws, lo, hi, raws.values)
        return np.where(empty, np.nan, s / np.where(empty, 1, count))
    if fn in ("stddev", "stdvar"):
        s1 = _window_sums(raws, lo, hi, raws.values)
        s2 = _window_sums(raws, lo, hi, raws.values**2)
        mean = s1 / np.where(empty, 1, count)
        var = np.maximum(s2 / np.where(empty, 1, count) - mean**2, 0.0)
        out = var if fn == "stdvar" else np.sqrt(var)
        return np.where(empty, np.nan, out)
    if fn in ("min", "max"):
        device = _use_device(raws, eval_ts)
        if device:
            from m3_tpu.ops import temporal

            max_len = int((hi - lo).max()) if lo.size else 0
            device = (temporal.minmax_levels(max_len)
                      * dispatch.next_pow2(len(raws.values))
                      <= temporal.MINMAX_SCRATCH_ELEMS)
        dispatch.record("temporal.window_minmax", device)
        if device:
            return temporal.window_minmax(raws.values, lo, hi, fn == "min")
        op = np.minimum if fn == "min" else np.maximum
        return _reduceat(op, raws.values, lo, hi, np.nan)
    if fn == "last":
        idx = np.clip(hi - 1, 0, max(len(raws.values) - 1, 0))
        return np.where(empty, np.nan, raws.values[idx] if len(raws.values) else np.nan)
    if fn == "changes":
        prev = np.concatenate([[np.nan], raws.values[:-1]])
        is_first = np.zeros(len(raws.values), bool)
        is_first[raws.offsets[:-1][raws.offsets[:-1] < len(is_first)]] = True
        changed = (raws.values != prev) & ~is_first
        # NaN -> NaN is not a change (Prometheus: both NaN means no change)
        both_nan = np.isnan(raws.values) & np.isnan(prev)
        changed &= ~both_nan
        c = _window_sums(raws, lo, hi, changed.astype(np.float64))
        # the first sample in a window has no predecessor inside it: subtract
        # a change counted at lo when its predecessor is outside the window
        first_in_window_changed = changed[np.clip(lo, 0, max(len(changed) - 1, 0))] if len(changed) else np.zeros(lo.shape)
        c -= np.where((hi > lo), first_in_window_changed.astype(np.float64), 0.0)
        return np.where(empty, np.nan, c)
    if fn == "resets":
        prev = np.concatenate([[np.inf], raws.values[:-1]])
        is_first = np.zeros(len(raws.values), bool)
        is_first[raws.offsets[:-1][raws.offsets[:-1] < len(is_first)]] = True
        reset = (raws.values < prev) & ~is_first
        c = _window_sums(raws, lo, hi, reset.astype(np.float64))
        first_in_window_reset = reset[np.clip(lo, 0, max(len(reset) - 1, 0))] if len(reset) else np.zeros(lo.shape)
        c -= np.where((hi > lo), first_in_window_reset.astype(np.float64), 0.0)
        return np.where(empty, np.nan, c)
    raise ValueError(f"unknown over_time fn {fn}")


def _reset_adjusted(raws: RaggedSeries) -> np.ndarray:
    """Counter values with resets accumulated (monotonized per series)."""
    v = raws.values
    prev = np.concatenate([[0.0], v[:-1]])
    is_first = np.zeros(len(v), bool)
    starts = raws.offsets[:-1]
    is_first[starts[starts < len(v)]] = True
    drop = np.where((v < prev) & ~is_first, prev, 0.0)
    # accumulate drops within each series: global cumsum minus row base
    cdrop = np.cumsum(drop)
    row_base = np.concatenate([[0.0], cdrop])[raws.offsets[:-1]]
    row_base_per_sample = np.repeat(row_base, np.diff(raws.offsets))
    return v + (cdrop - row_base_per_sample) + 0.0 if len(v) else v


def extrapolated_rate(
    raws: RaggedSeries,
    eval_ts: np.ndarray,
    range_ns: int,
    is_counter: bool,
    is_rate: bool,
):
    """rate/increase/delta with upstream Prometheus extrapolation.

    Mirrors promql extrapolatedRate: extrapolate to the window edges unless
    the first/last samples are further than 1.1x the average sample spacing
    from them, and (counters) cap start extrapolation at the zero point.
    """
    n = len(raws.values)
    if n == 0:
        return np.full((raws.n_series, len(eval_ts)), np.nan)

    device = _use_device(raws, eval_ts)
    dispatch.record("temporal.extrapolated_rate", device)
    if device:
        from m3_tpu.ops import temporal

        lo, hi = raws.window_bounds(eval_ts, range_ns)
        adj = (temporal.reset_adjusted(raws.values, raws.offsets)
               if is_counter else raws.values)
        return temporal.extrapolated_rate(
            raws.values, adj, raws.times, lo, hi, eval_ts, range_ns,
            is_counter, is_rate,
        )

    # CPU serving path: the native columnar kernel (same math, pointer-walk
    # windows — skips the per-series searchsorted loop entirely) when
    # available and the fetch is big enough to amortize FFI; requires the
    # ascending step grid the engine always evaluates on.
    work = n + raws.n_series * len(eval_ts)
    if (work >= 16_384 and os.environ.get("M3_TPU_NATIVE_OPS") != "0"
            and len(eval_ts) > 0 and bool((np.diff(eval_ts) >= 0).all())):
        from m3_tpu.ops import native_hostops

        if native_hostops.available():
            dispatch.counters["temporal.extrapolated_rate[native]"] += 1
            return native_hostops.rate_csr(raws.times, raws.values,
                                           raws.offsets, eval_ts, range_ns,
                                           is_counter, is_rate)

    lo, hi = raws.window_bounds(eval_ts, range_ns)
    count = (hi - lo).astype(np.float64)
    ok = count >= 2
    safe_lo = np.clip(lo, 0, max(n - 1, 0))
    safe_hi = np.clip(hi - 1, 0, max(n - 1, 0))
    v = _reset_adjusted(raws) if is_counter else raws.values
    first_v = v[safe_lo]
    last_v = v[safe_hi]
    raw_first_v = raws.values[safe_lo]
    first_t = raws.times[safe_lo].astype(np.float64)
    last_t = raws.times[safe_hi].astype(np.float64)
    result = last_v - first_v

    window_start = (eval_ts - range_ns).astype(np.float64)[None, :]
    window_end = eval_ts.astype(np.float64)[None, :]
    sampled = (last_t - first_t) / NS
    dur_to_start = (first_t - window_start) / NS
    dur_to_end = (window_end - last_t) / NS
    avg_between = sampled / np.maximum(count - 1, 1)
    threshold = avg_between * 1.1

    if is_counter:
        # don't extrapolate below zero (upstream caps BEFORE the threshold)
        with np.errstate(divide="ignore", invalid="ignore"):
            dur_to_zero = np.where(result > 0, sampled * (raw_first_v / result), np.inf)
        dur_to_start = np.where(
            (result > 0) & (raw_first_v >= 0) & (dur_to_zero < dur_to_start),
            dur_to_zero,
            dur_to_start,
        )

    dur_to_start = np.where(dur_to_start >= threshold, avg_between / 2, dur_to_start)
    dur_to_end = np.where(dur_to_end >= threshold, avg_between / 2, dur_to_end)

    extrap = sampled + dur_to_start + dur_to_end
    with np.errstate(divide="ignore", invalid="ignore"):
        factor = np.where(sampled > 0, extrap / sampled, np.nan)
        out = result * factor
        if is_rate:
            out = out / (range_ns / NS)
    return np.where(ok & (sampled > 0), out, np.nan)


def holt_winters(raws: RaggedSeries, eval_ts: np.ndarray, range_ns: int,
                 sf: float, tf: float):
    """Double exponential smoothing per window (upstream Prometheus
    holt_winters / the reference temporal/holt_winters.go:90-140):
    smoothed value s and trend b fold over the window's non-NaN samples;
    needs >= 2 samples, NaN otherwise.

    Columnar formulation: one pass over window OFFSETS with [S, n_steps]
    state matrices — the per-sample recurrence is inherently sequential,
    so the vectorization axis is (series x step), not time.
    """
    lo, hi = raws.window_bounds(eval_ts, range_ns)
    n = len(raws.values)
    if n == 0:
        return np.full(lo.shape, np.nan)
    device = _use_device(raws, eval_ts)
    dispatch.record("temporal.holt_winters", device)
    if device:
        from m3_tpu.ops import temporal

        return temporal.holt_winters(raws.values, lo, hi, sf, tf)
    max_len = int((hi - lo).max()) if lo.size else 0
    shape = lo.shape
    found_first = np.zeros(shape, bool)
    found_second = np.zeros(shape, bool)
    prev = np.zeros(shape)
    curr = np.zeros(shape)
    trend = np.zeros(shape)
    idx = np.zeros(shape, np.int64)  # non-NaN samples consumed so far
    for j in range(max_len):
        pos = lo + j
        valid = pos < hi
        val = raws.values[np.clip(pos, 0, n - 1)]
        valid &= ~np.isnan(val)
        take_first = valid & ~found_first
        curr = np.where(take_first, val, curr)
        idx = idx + take_first
        found_first |= take_first
        sub = valid & found_first & ~take_first
        take_second = sub & ~found_second
        trend = np.where(take_second, val - curr, trend)
        found_second |= take_second
        # calcTrendValue(i-1): the second sample (i-1 == 0) uses b as-is
        tv = np.where(idx == 1, trend,
                      tf * (curr - prev) + (1 - tf) * trend)
        new_curr = sf * val + (1 - sf) * (curr + tv)
        prev = np.where(sub, curr, prev)
        trend = np.where(sub, tv, trend)
        curr = np.where(sub, new_curr, curr)
        idx = idx + sub
    return np.where(found_second, curr, np.nan)


def instant_delta(raws: RaggedSeries, eval_ts: np.ndarray, range_ns: int,
                  is_counter: bool, is_rate: bool):
    """irate/idelta: from the last two samples in the window."""
    lo, hi = raws.window_bounds(eval_ts, range_ns)
    ok = (hi - lo) >= 2
    n = len(raws.values)
    if n == 0:
        return np.full(lo.shape, np.nan)
    i_last = np.clip(hi - 1, 0, n - 1)
    i_prev = np.clip(hi - 2, 0, n - 1)
    v_last, v_prev = raws.values[i_last], raws.values[i_prev]
    t_last = raws.times[i_last].astype(np.float64)
    t_prev = raws.times[i_prev].astype(np.float64)
    diff = v_last - v_prev
    if is_counter:
        diff = np.where(v_last < v_prev, v_last, diff)
    out = diff
    if is_rate:
        dt = (t_last - t_prev) / NS
        with np.errstate(divide="ignore", invalid="ignore"):
            out = np.where(dt > 0, diff / dt, np.nan)
    return np.where(ok, out, np.nan)


def linear_regression(raws: RaggedSeries, eval_ts: np.ndarray, range_ns: int,
                      predict_offset_s: float | None = None):
    """deriv (slope) / predict_linear via least squares over each window.

    Times are re-centered on the window's first sample (upstream's intercept
    time) before the sums, keeping t^2 within float64 precision.
    """
    lo, hi = raws.window_bounds(eval_ts, range_ns)
    count = (hi - lo).astype(np.float64)
    ok = count >= 2
    n = len(raws.values)
    if n == 0:
        return np.full(lo.shape, np.nan)
    t0 = raws.times[0] if n else 0
    x = (raws.times - t0).astype(np.float64) / NS  # seconds, small magnitude
    v = raws.values
    sx = _window_sums(raws, lo, hi, x)
    sv = _window_sums(raws, lo, hi, v)
    sxx = _window_sums(raws, lo, hi, x * x)
    sxv = _window_sums(raws, lo, hi, x * v)
    cnt = np.where(count > 0, count, 1)
    # re-center on the window's first sample time c:
    c = x[np.clip(lo, 0, n - 1)]
    #   sum((x-c)v) = sxv - c*sv ; sum(x-c) = sx - cnt*c
    #   sum((x-c)^2) = sxx - 2c*sx + cnt*c^2
    sxv_c = sxv - c * sv
    sx_c = sx - cnt * c
    sxx_c = sxx - 2 * c * sx + cnt * c * c
    with np.errstate(divide="ignore", invalid="ignore"):
        cov = sxv_c - sx_c * sv / cnt
        var = sxx_c - sx_c * sx_c / cnt
        slope = cov / var
        intercept = sv / cnt - slope * sx_c / cnt
    if predict_offset_s is None:
        return np.where(ok & (var > 0), slope, np.nan)
    # predict at eval time + offset, in the re-centered coordinate system
    eval_x = (eval_ts[None, :] - t0).astype(np.float64) / NS - c
    pred = intercept + slope * (eval_x + predict_offset_s)
    return np.where(ok & (var > 0), pred, np.nan)

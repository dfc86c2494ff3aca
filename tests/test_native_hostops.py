"""Parity tests: native CPU host ops vs the numpy serving paths.

The native kernels (native/hostops.cpp) are the CPU serving path for large
flushes/fetches; these tests pin them to the numpy reference implementations
they replace (same grouping, same stats, same Prometheus rate math), plus
the scalar reference loops to the serving outputs.
"""

import numpy as np
import pytest

from m3_tpu.ops import native_hostops, windowed_agg
from m3_tpu.query.windows import NS, RaggedSeries, extrapolated_rate

pytestmark = pytest.mark.skipif(
    not native_hostops.available(), reason="no C++ toolchain"
)


def _random_samples(n, n_elems=37, n_windows=5, seed=0, with_ties=True):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n_elems, n).astype(np.int64)
    w = rng.integers(0, n_windows, n).astype(np.int64)
    v = rng.normal(100, 25, n)
    t = rng.integers(0, 50, n).astype(np.int64)
    if with_ties:  # duplicate timestamps exercise the append-order tiebreak
        t[rng.integers(0, n, n // 4)] = 7
    return e, w, v, t


def _numpy_groups(e, w, v, t, need_sorted=True):
    import os

    os.environ["M3_TPU_NATIVE_OPS"] = "0"
    try:
        return windowed_agg.aggregate_groups(
            e, w, v, order_seq=np.arange(len(e)), times=t,
            need_sorted=need_sorted)
    finally:
        os.environ.pop("M3_TPU_NATIVE_OPS", None)


class TestAggGroups:
    def test_matches_numpy(self):
        e, w, v, t = _random_samples(20_000)
        ge_n, gw_n, st_n, vq_n, off_n = _numpy_groups(e, w, v, t)
        ge, gw, st, vq, off = native_hostops.agg_groups(e, w, v, t)
        np.testing.assert_array_equal(ge, ge_n)
        np.testing.assert_array_equal(gw, gw_n)
        np.testing.assert_array_equal(off, off_n)
        for k in ("count", "min", "max", "last"):
            np.testing.assert_array_equal(st[k], st_n[k], err_msg=k)
        for k in ("sum", "sumsq", "mean", "stdev"):
            np.testing.assert_allclose(st[k], st_n[k], rtol=1e-9,
                                       atol=1e-9, err_msg=k)
        np.testing.assert_array_equal(vq, vq_n)

    def test_large_elem_ids_fall_back_to_comparison_sort(self):
        # (elem range bits + window range bits) > 64 exercises stable_sort
        n = 5_000
        rng = np.random.default_rng(3)
        e = rng.integers(0, 2**62, n).astype(np.int64)
        w = rng.integers(0, 2**40, n).astype(np.int64)
        v = rng.normal(0, 1, n)
        t = rng.integers(0, 100, n).astype(np.int64)
        ge_n, gw_n, st_n, _, _ = _numpy_groups(e, w, v, t)
        ge, gw, st, _, _ = native_hostops.agg_groups(e, w, v, t)
        np.testing.assert_array_equal(ge, ge_n)
        np.testing.assert_array_equal(gw, gw_n)
        np.testing.assert_array_equal(st["last"], st_n["last"])

    def test_adversarial_id_ranges_span_int64(self):
        """Ids spanning (almost) the full int64 range: the min/max range
        computation must be u64 subtraction (signed overflow is UB) and a
        64-bit window range must route to the comparison sort (a 64-bit
        shift in the radix key packing is UB)."""
        imin = np.iinfo(np.int64).min
        imax = np.iinfo(np.int64).max
        n = 4_096
        rng = np.random.default_rng(11)
        e = rng.integers(-2**62, 2**62, n).astype(np.int64)
        w = rng.integers(-2**62, 2**62, n).astype(np.int64)
        # pin the extremes so e_range and w_range both wrap int64
        e[:4] = [imin, imax, imin + 1, imax - 1]
        w[:4] = [imax, imin, imax - 1, imin + 1]
        # duplicates so grouping actually groups at the extremes
        e[4:8] = e[:4]
        w[4:8] = w[:4]
        v = rng.normal(0, 1, n)
        t = rng.integers(0, 100, n).astype(np.int64)
        ge_n, gw_n, st_n, vq_n, off_n = _numpy_groups(e, w, v, t)
        ge, gw, st, vq, off = native_hostops.agg_groups(e, w, v, t)
        np.testing.assert_array_equal(ge, ge_n)
        np.testing.assert_array_equal(gw, gw_n)
        np.testing.assert_array_equal(off, off_n)
        np.testing.assert_array_equal(st["last"], st_n["last"])
        np.testing.assert_allclose(st["sum"], st_n["sum"], rtol=1e-9)

    def test_wbits_exactly_64_takes_comparison_sort(self):
        """w range needing all 64 bits with a single elem id: the radix
        condition (0 + 64 <= 64) used to pass and shift by 64 — UB."""
        imin = np.iinfo(np.int64).min
        imax = np.iinfo(np.int64).max
        e = np.zeros(64, np.int64)
        w = np.concatenate([np.array([imin, imax, imin, imax], np.int64),
                            np.arange(-30, 30, dtype=np.int64)])
        rng = np.random.default_rng(5)
        v = rng.normal(0, 1, len(w))
        t = np.arange(len(w), dtype=np.int64)
        ge_n, gw_n, st_n, _, off_n = _numpy_groups(e, w, v, t)
        ge, gw, st, _, off = native_hostops.agg_groups(e, w, v, t)
        np.testing.assert_array_equal(ge, ge_n)
        np.testing.assert_array_equal(gw, gw_n)
        np.testing.assert_array_equal(off, off_n)
        np.testing.assert_array_equal(st["last"], st_n["last"])

    def test_dispatch_uses_native_for_large_flushes(self):
        from m3_tpu.utils import dispatch

        e, w, v, t = _random_samples(windowed_agg.NATIVE_THRESHOLD + 1)
        before = dispatch.counters["windowed_agg.aggregate_groups[native]"]
        windowed_agg.aggregate_groups(e, w, v, times=t)
        after = dispatch.counters["windowed_agg.aggregate_groups[native]"]
        assert after == before + 1

    def test_nan_values_fall_back_to_numpy(self):
        e, w, v, t = _random_samples(windowed_agg.NATIVE_THRESHOLD + 1)
        v[5] = np.nan
        ge, gw, stats, vq, off = windowed_agg.aggregate_groups(
            e, w, v, times=t)
        assert np.isnan(stats["sum"]).any()

    def test_want_sorted_false_skips_vq(self):
        e, w, v, t = _random_samples(8_000)
        _, _, _, vq, _ = native_hostops.agg_groups(e, w, v, t,
                                                   want_sorted=False)
        assert len(vq) == 0

    def test_baseline_checksum_matches_serving_sum(self):
        n = 10_000
        e, w, v, t = _random_samples(n, n_elems=500)
        ids = [b"stats.counter.%06d+env=prod,host=h%04d" % (x, x % 100)
               for x in e]
        total, n_done = native_hostops.agg_baseline_scalar(ids, w, v)
        assert n_done == n
        _, _, stats, _, _ = native_hostops.agg_groups(e, w, v, t)
        np.testing.assert_allclose(total, stats["sum"].sum(), rtol=1e-9)


def _ragged(seed=0, S=40, counter=True):
    rng = np.random.default_rng(seed)
    per = []
    for _ in range(S):
        T = int(rng.integers(0, 50))
        t = np.sort(rng.integers(0, 3600, T)).astype(np.int64) * NS
        t = np.unique(t)
        if counter:
            v = rng.integers(0, 10, len(t)).astype(np.float64).cumsum()
            resets = rng.random(len(t)) < 0.05  # occasional counter resets
            if len(t):
                v[resets] = rng.random(int(resets.sum())) * 3
        else:
            v = rng.normal(10, 5, len(t))
        per.append((t, v))
    return RaggedSeries.from_lists(per)


class TestRateCsr:
    @pytest.mark.parametrize("is_counter,is_rate", [
        (True, True), (True, False), (False, False)])
    def test_matches_numpy(self, is_counter, is_rate):
        import os

        raws = _ragged(seed=11, counter=is_counter)
        eval_ts = np.arange(300, 3600, 60, dtype=np.int64) * NS
        got = native_hostops.rate_csr(raws.times, raws.values, raws.offsets,
                                      eval_ts, 300 * NS, is_counter, is_rate)
        os.environ["M3_TPU_NATIVE_OPS"] = "0"
        try:
            want = extrapolated_rate(raws, eval_ts, 300 * NS, is_counter,
                                     is_rate)
        finally:
            os.environ.pop("M3_TPU_NATIVE_OPS", None)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)

    def test_baseline_matches_serving(self):
        raws = _ragged(seed=5)
        eval_ts = np.arange(300, 3600, 45, dtype=np.int64) * NS
        got = native_hostops.rate_baseline_scalar(
            raws.times, raws.values, raws.offsets, eval_ts, 300 * NS,
            True, True)
        want = native_hostops.rate_csr(
            raws.times, raws.values, raws.offsets, eval_ts, 300 * NS,
            True, True)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)

    def test_dispatch_uses_native_for_large_fetches(self):
        from m3_tpu.utils import dispatch

        S, T = 300, 120
        base_t = np.arange(T, dtype=np.int64) * 15 * NS
        per = [(base_t, np.arange(T, dtype=np.float64)) for _ in range(S)]
        raws = RaggedSeries.from_lists(per)
        eval_ts = np.arange(300, 1800, 60, dtype=np.int64) * NS
        before = dispatch.counters["temporal.extrapolated_rate[native]"]
        extrapolated_rate(raws, eval_ts, 300 * NS, True, True)
        after = dispatch.counters["temporal.extrapolated_rate[native]"]
        assert after == before + 1

"""TSan-lane parity driver: the test_native*/hostops assertions, re-run
against the ThreadSanitizer builds (native/tsan/*.so).

Why not just `pytest` under TSan?  ctypes can only load a
`-fsanitize=thread` library when libtsan is LD_PRELOADed into the whole
interpreter, and in this image pytest deadlocks under that preload (its
capture layer and TSan's runtime fight over stdio).  Plain Python
workloads run fine — m3_tpu/tools/race_check.py has relied on that since
PR 1 — so the tsan lane splits the work:

* ``pytest tests/test_race_native.py`` (uninstrumented pytest) spawns
  its OWN preloaded children: the planted-race sensitivity check plus
  race_check's threaded race workloads;
* this driver re-runs the core test_native.py / test_native_hostops.py
  parity battery in ONE preloaded child with M3TSZ_SO/M3HOSTOPS_SO
  swapped to the instrumented builds — proving the TSan artifacts are
  not just race-silent but bit-exact with the production builds.

Exit codes: 0 green, 66 TSan reported a race (TSAN_OPTIONS exitcode),
1 parity failure.

NOTE: the child must not touch ``np.testing`` — its assert machinery
deadlocks under the TSan runtime on this kernel the same way pytest's
capture layer does.  Comparisons use plain ``np.array_equal`` /
``np.allclose`` (verified TSan-safe).
"""

from __future__ import annotations

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD_ENV = "M3_TSAN_NATIVE_CHILD"


def _parent() -> int:
    sys.path.insert(0, _REPO)
    from m3_tpu.tools.race_check import _build_tsan, _libtsan_path

    outs = _build_tsan()  # cached: rebuilds only when the .cpp is newer
    env = dict(os.environ)
    env.update({
        _CHILD_ENV: "1",
        "LD_PRELOAD": _libtsan_path(),
        "M3TSZ_SO": outs["m3tsz.cpp"],
        "M3HOSTOPS_SO": outs["hostops.cpp"],
        "JAX_PLATFORMS": "cpu",
        "TSAN_OPTIONS": os.environ.get(
            "TSAN_OPTIONS", "exitcode=66 halt_on_error=0"),
    })
    r = subprocess.run([sys.executable, "-u", os.path.abspath(__file__)],
                       env=env, cwd=_REPO, timeout=900)
    if r.returncode == 0:
        print("tsan_native: parity battery green against the TSan builds")
    elif r.returncode == 66:
        print("tsan_native: ThreadSanitizer reported a data race — see "
              "report above", file=sys.stderr)
    else:
        print(f"tsan_native: FAILED (rc={r.returncode})", file=sys.stderr)
    return r.returncode


# ---------------------------------------------------------------------------
# child: the instrumented parity battery
# ---------------------------------------------------------------------------

_START = 1_599_998_400_000_000_000


def _eq(a, b, err_msg: str = "") -> None:
    import numpy as np

    assert np.array_equal(np.asarray(a), np.asarray(b)), \
        f"arrays differ {err_msg}"


def _close(a, b, rtol: float, atol: float, err_msg: str = "") -> None:
    import numpy as np

    assert np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=True), \
        f"arrays not close {err_msg}"


def _series(rng, n=150, unit_step=10**9, scale=60):
    import numpy as np

    times = _START + np.cumsum(rng.integers(1, scale, n)) * unit_step
    return times.astype(np.int64), rng.normal(100, 25, n)


def _codec_battery() -> None:
    import numpy as np

    from m3_tpu.encoding.m3tsz import Encoder, native
    from m3_tpu.encoding.m3tsz import decode as py_decode
    from m3_tpu.utils.xtime import TimeUnit

    print("  codec: imports done", flush=True)
    assert native.available(), "tsan m3tsz build failed to load"
    print("  codec: tsan build loaded", flush=True)
    rng = np.random.default_rng(42)

    # bit-exact vs the Python scalar codec + roundtrip + cross decode
    times, values = _series(rng)
    stream = native.encode_series(times, values, _START, TimeUnit.SECOND)
    enc = Encoder(_START, int_optimized=False)
    for t, v in zip(times, values):
        enc.encode(int(t), float(v), TimeUnit.SECOND)
    assert stream == enc.stream(), "native stream != python stream"
    dt, dv = native.decode_series(stream, TimeUnit.SECOND)
    _eq(dt, times)
    _eq(dv, values)
    assert [d.value for d in py_decode(stream, int_optimized=False)] == \
        list(values)
    print("  codec: v1 bit-exact + roundtrip + cross decode", flush=True)

    # nanosecond unit
    tn, vn = _series(rng, unit_step=1, scale=10**10)
    sn = native.encode_series(tn, vn, _START, TimeUnit.NANOSECOND)
    dtn, dvn = native.decode_series(sn, TimeUnit.NANOSECOND)
    _eq(dtn, tn)
    _eq(dvn, vn)

    # special values
    ts = _START + (np.arange(8) + 1) * 10**9
    vs = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e300, 1e-300, 7.0])
    _, got = native.decode_series(
        native.encode_series(ts, vs, _START, TimeUnit.SECOND),
        TimeUnit.SECOND)
    for a, b in zip(got, vs):
        assert a == b or (np.isnan(a) and np.isnan(b))
    print("  codec: ns unit + special values", flush=True)

    # v2 batch: bit-identical to v1, threaded roundtrip, ragged n_points
    B, T = 64, 100
    bt = np.stack([_series(rng, n=T)[0] for _ in range(B)])
    bv = np.stack([_series(rng, n=T)[1] for _ in range(B)])
    streams = native.encode_batch(bt, bv, np.full(B, _START),
                                  TimeUnit.SECOND, threads=4)
    for b in range(0, B, 7):
        assert streams[b] == native.encode_series(
            bt[b], bv[b], _START, TimeUnit.SECOND)
    dbt, dbv, ns = native.decode_batch(streams, TimeUnit.SECOND,
                                       max_points=T, threads=4)
    assert (ns == T).all()
    _eq(dbt[:, :T], bt)
    _eq(dbv[:, :T].view(np.float64), bv)

    n_points = np.array([T, 0, 10, T, 1, 25, T, 3], np.int32)
    streams = native.encode_batch(bt[:8], bv[:8], np.full(8, _START),
                                  TimeUnit.SECOND, n_points=n_points)
    _, _, ns = native.decode_batch(streams, TimeUnit.SECOND, max_points=T)
    _eq(ns, n_points)

    rate, lt, lv = native.bench_roundtrip_batch(
        bt, bv, _START, TimeUnit.SECOND, threads=2)
    assert rate > 0
    _eq(lt, bt[-1])
    print("  codec: v2 batch bit-identical + threaded roundtrip", flush=True)


def _hostops_battery() -> None:
    import numpy as np

    from m3_tpu.ops import native_hostops, windowed_agg
    from m3_tpu.query.windows import NS, RaggedSeries, extrapolated_rate

    assert native_hostops.available(), "tsan hostops build failed to load"

    def numpy_groups(e, w, v, t):
        os.environ["M3_TPU_NATIVE_OPS"] = "0"
        try:
            return windowed_agg.aggregate_groups(
                e, w, v, order_seq=np.arange(len(e)), times=t,
                need_sorted=True)
        finally:
            os.environ.pop("M3_TPU_NATIVE_OPS", None)

    rng = np.random.default_rng(0)
    n = 20_000
    e = rng.integers(0, 37, n).astype(np.int64)
    w = rng.integers(0, 5, n).astype(np.int64)
    v = rng.normal(100, 25, n)
    t = rng.integers(0, 50, n).astype(np.int64)
    t[rng.integers(0, n, n // 4)] = 7  # ties: append-order tiebreak
    ge_n, gw_n, st_n, vq_n, off_n = numpy_groups(e, w, v, t)
    ge, gw, st, vq, off = native_hostops.agg_groups(e, w, v, t)
    _eq(ge, ge_n)
    _eq(gw, gw_n)
    _eq(off, off_n)
    for k in ("count", "min", "max", "last"):
        _eq(st[k], st_n[k], err_msg=k)
    for k in ("sum", "sumsq", "mean", "stdev"):
        _close(st[k], st_n[k], 1e-9, 1e-9, k)
    _eq(vq, vq_n)
    print("  hostops: agg_groups parity (20k, ties)", flush=True)

    # adversarial int64 ranges: comparison-sort fallback, no UB
    imin, imax = np.iinfo(np.int64).min, np.iinfo(np.int64).max
    n = 4_096
    e = rng.integers(-2**62, 2**62, n).astype(np.int64)
    w = rng.integers(-2**62, 2**62, n).astype(np.int64)
    e[:4] = [imin, imax, imin + 1, imax - 1]
    w[:4] = [imax, imin, imax - 1, imin + 1]
    e[4:8] = e[:4]
    w[4:8] = w[:4]
    v = rng.normal(0, 1, n)
    t = rng.integers(0, 100, n).astype(np.int64)
    ge_n, gw_n, st_n, _, off_n = numpy_groups(e, w, v, t)
    ge, gw, st, _, off = native_hostops.agg_groups(e, w, v, t)
    _eq(ge, ge_n)
    _eq(gw, gw_n)
    _eq(off, off_n)
    _eq(st["last"], st_n["last"])
    print("  hostops: int64-spanning ids (stable_sort path)", flush=True)

    # rate_csr parity vs the numpy Prometheus rate math
    per = []
    for _ in range(40):
        T = int(rng.integers(0, 50))
        ts = np.unique(np.sort(rng.integers(0, 3600, T)).astype(np.int64) * NS)
        vv = rng.integers(0, 10, len(ts)).astype(np.float64).cumsum()
        per.append((ts, vv))
    raws = RaggedSeries.from_lists(per)
    eval_ts = np.arange(300, 3600, 60, dtype=np.int64) * NS
    for is_counter, is_rate in ((True, True), (True, False), (False, False)):
        got = native_hostops.rate_csr(raws.times, raws.values, raws.offsets,
                                      eval_ts, 300 * NS, is_counter, is_rate,
                                      threads=2)
        os.environ["M3_TPU_NATIVE_OPS"] = "0"
        try:
            want = extrapolated_rate(raws, eval_ts, 300 * NS, is_counter,
                                     is_rate)
        finally:
            os.environ.pop("M3_TPU_NATIVE_OPS", None)
        _close(got, want, 1e-9, 1e-12)
    print("  hostops: rate_csr parity x3 modes (threaded)", flush=True)


def _child() -> int:
    if _REPO not in sys.path:  # script-mode child: repo root for m3_tpu
        sys.path.insert(0, _REPO)
    print("tsan_native child: parity battery against "
          f"{os.environ.get('M3TSZ_SO')}", flush=True)
    _codec_battery()
    _hostops_battery()
    return 0


def main() -> int:
    if os.environ.get(_CHILD_ENV) != "1":
        return _parent()
    return _child()


if __name__ == "__main__":
    raise SystemExit(main())

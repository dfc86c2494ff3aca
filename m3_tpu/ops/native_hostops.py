"""ctypes bindings for the native CPU host ops (native/hostops.cpp).

Serving-path kernels (grouped aggregation, extrapolated rate) used by
``ops.windowed_agg`` / ``query.windows`` when no accelerator is live, plus
per-sample scalar loops kept as references for the tests.
Built on demand with g++ like the native m3tsz codec; every caller falls
back to the numpy host path when no compiler is available.
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import subprocess
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
_SRC = os.path.join(_REPO_ROOT, "native", "hostops.cpp")
# M3HOSTOPS_SO points the loader at an instrumented build
# (tools/race_check.py swaps in the ThreadSanitizer variant); overrides
# load AS-IS — no stale-mtime rebuild over the instrumented artifact
_SO_OVERRIDE = "M3HOSTOPS_SO" in os.environ
_SO = os.environ.get("M3HOSTOPS_SO",
                     os.path.join(_REPO_ROOT, "native", "libm3hostops.so"))

_lock = threading.Lock()
_lib = None
_tried = False

_P = ctypes.c_void_p
_I64 = ctypes.c_int64
_I32 = ctypes.c_int32


def _build() -> bool:
    # to a name of this process's own, then renamed onto _SO, as in
    # encoding/m3tsz/native.py
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError):
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        return False


def load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        src_mtime = os.path.getmtime(_SRC) if os.path.exists(_SRC) else 0
        if not _SO_OVERRIDE and (
                not os.path.exists(_SO) or os.path.getmtime(_SO) < src_mtime):
            # intentional build-under-lock: single-flight one-time g++
            # build, as in encoding/m3tsz/native.py
            # m3lint: disable=lock-blocking-call
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.m3_agg_groups.restype = _I64
        lib.m3_agg_groups.argtypes = [_P, _P, _P, _P, _I64, _I32] + [_P] * 12
        lib.m3_agg_baseline_scalar.restype = ctypes.c_double
        lib.m3_agg_baseline_scalar.argtypes = [_P, _P, _P, _P, _I64]
        lib.m3_rate_csr.restype = None
        lib.m3_rate_csr.argtypes = [_P, _P, _P, _I64, _P, _I64, _I64,
                                    _I32, _I32, _I32, _P]
        lib.m3_rate_baseline_scalar.restype = None
        lib.m3_rate_baseline_scalar.argtypes = [_P, _P, _P, _I64, _P, _I64,
                                                _I64, _I32, _I32, _P]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def default_threads() -> int:
    v = os.environ.get("M3_NATIVE_THREADS")
    if v:
        return max(1, int(v))
    try:
        return max(1, len(os.sched_getaffinity(0)))
    except (AttributeError, OSError):
        return max(1, os.cpu_count() or 1)


def agg_groups(elem_ids, window_ids, values, times, want_sorted: bool = True):
    """Native grouped aggregation; same contract as the numpy host path in
    windowed_agg.aggregate_groups. Returns (ge, gw, stats, vq, offsets)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native hostops unavailable")
    n = len(values)
    e = np.ascontiguousarray(elem_ids, np.int64)
    w = np.ascontiguousarray(window_ids, np.int64)
    v = np.ascontiguousarray(values, np.float64)
    t = np.ascontiguousarray(times, np.int64)
    ge = np.empty(n, np.int64)
    gw = np.empty(n, np.int64)
    outs = [np.empty(n, np.float64) for _ in range(8)]
    vq = np.empty(n if want_sorted else 0, np.float64)
    offsets = np.empty(n + 1, np.int64)
    G = lib.m3_agg_groups(
        e.ctypes.data, w.ctypes.data, v.ctypes.data, t.ctypes.data,
        n, 1 if want_sorted else 0,
        ge.ctypes.data, gw.ctypes.data,
        *(o.ctypes.data for o in outs),
        vq.ctypes.data if want_sorted else None, offsets.ctypes.data,
    )
    if G < 0:
        raise ValueError("native agg_groups failed")
    names = ("count", "sum", "sumsq", "min", "max", "mean", "last", "stdev")
    stats = {k: outs[i][:G] for i, k in enumerate(names)}
    return ge[:G], gw[:G], stats, vq, offsets[:G + 1].copy()


def rate_csr(times, values, offsets, eval_ts, range_ns: int,
             is_counter: bool, is_rate: bool, threads: int | None = None):
    """Native columnar extrapolated rate; [S, K] matrix, numpy-path math."""
    lib = load()
    if lib is None:
        raise RuntimeError("native hostops unavailable")
    t = np.ascontiguousarray(times, np.int64)
    v = np.ascontiguousarray(values, np.float64)
    off = np.ascontiguousarray(offsets, np.int64)
    ev = np.ascontiguousarray(eval_ts, np.int64)
    S = len(off) - 1
    K = len(ev)
    out = np.empty((S, K), np.float64)
    lib.m3_rate_csr(
        t.ctypes.data, v.ctypes.data, off.ctypes.data, S,
        ev.ctypes.data, K, range_ns,
        1 if is_counter else 0, 1 if is_rate else 0,
        threads or default_threads(), out.ctypes.data,
    )
    return out


def agg_baseline_scalar(ids: list[bytes], window_ids, values) -> tuple[float, int]:
    """Reference for the tests: the per-sample loop (one FFI call);
    returns (checksum of window sums, n samples)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native hostops unavailable")
    blob = b"".join(ids)
    off = np.zeros(len(ids) + 1, np.int64)
    np.cumsum([len(i) for i in ids], out=off[1:])
    buf = np.frombuffer(blob, np.uint8)
    w = np.ascontiguousarray(window_ids, np.int64)
    v = np.ascontiguousarray(values, np.float64)
    total = lib.m3_agg_baseline_scalar(
        buf.ctypes.data, off.ctypes.data, w.ctypes.data, v.ctypes.data,
        len(ids),
    )
    return float(total), len(ids)


def rate_baseline_scalar(times, values, offsets, eval_ts, range_ns: int,
                         is_counter: bool, is_rate: bool):
    """Reference for the tests: the per-(series, step) window rescan;
    returns the [S, K] matrix."""
    lib = load()
    if lib is None:
        raise RuntimeError("native hostops unavailable")
    t = np.ascontiguousarray(times, np.int64)
    v = np.ascontiguousarray(values, np.float64)
    off = np.ascontiguousarray(offsets, np.int64)
    ev = np.ascontiguousarray(eval_ts, np.int64)
    S = len(off) - 1
    K = len(ev)
    out = np.empty((S, K), np.float64)
    lib.m3_rate_baseline_scalar(
        t.ctypes.data, v.ctypes.data, off.ctypes.data, S,
        ev.ctypes.data, K, range_ns,
        1 if is_counter else 0, 1 if is_rate else 0, out.ctypes.data,
    )
    return out

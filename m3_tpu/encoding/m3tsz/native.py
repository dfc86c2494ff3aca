"""ctypes bindings for the native C++ M3TSZ codec (native/m3tsz.cpp).

The shared library is built on demand with g++ (no pip deps); callers fall
back to the pure-Python scalar codec when no compiler is available, so the
native path is an accelerator, never a requirement.
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import subprocess
import threading

import numpy as np

from m3_tpu.utils.xtime import TimeUnit, unit_value_ns

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "m3tsz.cpp")
# M3TSZ_SO points the loader at an instrumented build (tools/race_check.py
# swaps in the ThreadSanitizer variant); overrides are loaded AS-IS (no
# stale-mtime rebuild, which would overwrite the instrumented artifact
# with a plain -O3 build)
_SO_OVERRIDE = "M3TSZ_SO" in os.environ
_SO = os.environ.get("M3TSZ_SO",
                     os.path.join(_REPO_ROOT, "native", "libm3tsz.so"))

_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> bool:
    # to a name of this process's own beside _SO, then renamed onto it:
    # another process (an xdist worker on a fresh checkout) never loads
    # a half-written library
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError):
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        return False


def load():
    """The loaded library or None (no compiler / build failed)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        src_mtime = os.path.getmtime(_SRC) if os.path.exists(_SRC) else 0
        if not _SO_OVERRIDE and (
                not os.path.exists(_SO) or os.path.getmtime(_SO) < src_mtime):
            # intentional build-under-lock: single-flight one-time g++
            # build — concurrent callers must block until the artifact
            # exists (they would only dogpile the compiler otherwise)
            # m3lint: disable=lock-blocking-call
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.m3tsz_encode.restype = ctypes.c_int64
        lib.m3tsz_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.m3tsz_decode.restype = ctypes.c_int32
        lib.m3tsz_decode.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
        ]
        lib.m3tsz_encode_batch.restype = ctypes.c_int64
        lib.m3tsz_encode_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int32,
        ]
        lib.m3tsz_decode_batch.restype = ctypes.c_int64
        lib.m3tsz_decode_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.m3tsz_roundtrip_batch.restype = ctypes.c_int64
        lib.m3tsz_roundtrip_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def _default_bits(unit: TimeUnit) -> int:
    return 32 if unit in (TimeUnit.SECOND, TimeUnit.MILLISECOND) else 64


def encode_series(times: np.ndarray, values: np.ndarray, start: int,
                  unit: TimeUnit = TimeUnit.SECOND) -> bytes:
    lib = load()
    if lib is None:
        raise RuntimeError("native codec unavailable")
    times = np.ascontiguousarray(times, dtype=np.int64)
    vbits = np.ascontiguousarray(values, dtype=np.float64).view(np.uint64)
    cap = 8 + (len(times) * 146 + 11) // 8 + 16
    out = np.zeros(cap, dtype=np.uint8)
    n = lib.m3tsz_encode(
        times.ctypes.data, vbits.ctypes.data, len(times),
        start, unit_value_ns(unit), _default_bits(unit),
        out.ctypes.data, cap,
    )
    if n == -1:
        raise ValueError("native encode overflow or misaligned start")
    if n == -2:
        raise OverflowError("delta-of-delta overflows 32 bits for this unit")
    return out[:n].tobytes()


def decode_series(stream: bytes, unit: TimeUnit = TimeUnit.SECOND,
                  max_points: int = 1 << 20):
    lib = load()
    if lib is None:
        raise RuntimeError("native codec unavailable")
    data = np.frombuffer(stream, dtype=np.uint8)
    # a datapoint costs >= 2 bits, so the stream bounds the output size
    max_points = min(max_points, len(data) * 4 + 16)
    times = np.empty(max_points, dtype=np.int64)
    vbits = np.empty(max_points, dtype=np.uint64)
    n = lib.m3tsz_decode(
        data.ctypes.data, len(data), unit_value_ns(unit), _default_bits(unit),
        times.ctypes.data, vbits.ctypes.data, max_points,
    )
    if n < 0:
        raise ValueError("native decode failed (corrupt or host-path stream)")
    return times[:n].copy(), vbits[:n].view(np.float64).copy()


def default_threads() -> int:
    """Thread count for the batch codec: the cores this process may use,
    overridable via M3_NATIVE_THREADS."""
    v = os.environ.get("M3_NATIVE_THREADS")
    if v:
        return max(1, int(v))
    try:
        return max(1, len(os.sched_getaffinity(0)))
    except (AttributeError, OSError):
        return max(1, os.cpu_count() or 1)


def encode_batch(times: np.ndarray, values_or_bits: np.ndarray,
                 starts: np.ndarray, unit: TimeUnit = TimeUnit.SECOND,
                 n_points: np.ndarray | None = None,
                 threads: int | None = None) -> list[bytes]:
    """Encode [B, T] series to per-series streams with the v2 word-level
    codec, threaded across series. values_or_bits may be f64 values or u64
    bit patterns; series b encodes its first n_points[b] points (default
    all T). Bit-identical to the scalar/XLA encoders."""
    lib = load()
    if lib is None:
        raise RuntimeError("native codec unavailable")
    B, T = times.shape
    times = np.ascontiguousarray(times, dtype=np.int64)
    if values_or_bits.dtype == np.uint64:
        vbits = np.ascontiguousarray(values_or_bits)
    else:
        vbits = np.ascontiguousarray(values_or_bits, dtype=np.float64).view(np.uint64)
    starts = np.ascontiguousarray(np.broadcast_to(starts, (B,)), dtype=np.int64)
    np_ptr = 0
    if n_points is not None:
        n_points = np.ascontiguousarray(n_points, dtype=np.int32)
        np_ptr = n_points.ctypes.data
    stride = 8 + (T * 146 + 11) // 8 + 32
    out = np.zeros((B, stride), dtype=np.uint8)
    lens = np.empty(B, dtype=np.int64)
    rc = lib.m3tsz_encode_batch(
        times.ctypes.data, vbits.ctypes.data, B, T, starts.ctypes.data,
        np_ptr, unit_value_ns(unit), _default_bits(unit),
        out.ctypes.data, stride, lens.ctypes.data,
        threads or default_threads(),
    )
    if rc != 0:
        # OverflowError for both codes, matching the device path's single
        # blocks.overflow flag (misaligned start folds into overflow there
        # too) so Shard.snapshot/_flush_locked degrade identically on CPU.
        bad = int(np.argmax(lens < 0))
        code = int(lens[bad])
        if code == -2:
            raise OverflowError("delta-of-delta overflows 32 bits for this unit")
        raise OverflowError(
            f"native batch encode failed for series {bad} (overflow or "
            "misaligned start)")
    return [out[b, :lens[b]].tobytes() for b in range(B)]


def decode_batch(streams: list[bytes], unit: TimeUnit = TimeUnit.SECOND,
                 max_points: int | None = None, threads: int | None = None):
    """Decode per-series streams into padded [B, T] arrays + counts with the
    v2 codec, threaded across series. Returns (times, vbits, n_points)."""
    lib = load()
    if lib is None:
        raise RuntimeError("native codec unavailable")
    B = len(streams)
    if B == 0:
        z = np.zeros((0, 0))
        return z.astype(np.int64), z.astype(np.uint64), np.zeros(0, np.int32)
    maxlen = max(len(s) for s in streams)
    if max_points is None:
        # a datapoint costs >= 2 bits, so the stream bounds the output
        max_points = maxlen * 4 + 16
    stride = maxlen + 16  # >= 9 bytes of slack for unaligned tail loads
    buf = np.zeros((B, stride), dtype=np.uint8)
    lens = np.empty(B, dtype=np.int64)
    for b, s in enumerate(streams):
        buf[b, :len(s)] = np.frombuffer(s, dtype=np.uint8)
        lens[b] = len(s)
    times = np.zeros((B, max_points), dtype=np.int64)
    vbits = np.zeros((B, max_points), dtype=np.uint64)
    out_ns = np.empty(B, dtype=np.int32)
    rc = lib.m3tsz_decode_batch(
        buf.ctypes.data, lens.ctypes.data, stride, B,
        unit_value_ns(unit), _default_bits(unit),
        times.ctypes.data, vbits.ctypes.data, max_points, out_ns.ctypes.data,
        threads or default_threads(),
    )
    if rc != 0:
        bad = int(np.argmax(out_ns < 0))
        raise ValueError(f"native batch decode failed for stream {bad}")
    return times, vbits, out_ns


def bench_roundtrip_batch(times: np.ndarray, values: np.ndarray, start: int,
                          unit: TimeUnit = TimeUnit.SECOND,
                          threads: int | None = None) -> tuple[float, np.ndarray, np.ndarray]:
    """Datapoints/sec for a [B, T] round trip on the v2 serving-path codec
    (word-level bit I/O, threaded). Returns (dp_per_sec, last_times,
    last_vbits) so callers can verify correctness of the final series."""
    import time as _time

    lib = load()
    if lib is None:
        raise RuntimeError("native codec unavailable")
    B, T = times.shape
    times = np.ascontiguousarray(times, dtype=np.int64)
    vbits = np.ascontiguousarray(values, dtype=np.float64).view(np.uint64)
    out_t = np.empty(T, dtype=np.int64)
    out_v = np.empty(T, dtype=np.uint64)
    nth = threads or default_threads()
    t0 = _time.perf_counter()
    n = lib.m3tsz_roundtrip_batch(
        times.ctypes.data, vbits.ctypes.data, B, T,
        start, unit_value_ns(unit), _default_bits(unit),
        out_t.ctypes.data, out_v.ctypes.data, nth,
    )
    dt = _time.perf_counter() - t0
    if n < 0:
        raise ValueError("native batch roundtrip failed")
    return n / dt, out_t, out_v

"""Round benchmark: batched M3TSZ encode+decode round-trip throughput.

Workload mirrors BASELINE.md config #1 (100k-series M3TSZ round-trip) scaled
to a single dispatch: B series x T datapoints encoded to storage blocks and
decoded back through the batched XLA codec (m3_tpu/encoding/m3tsz/tpu.py),
the device path the storage engine flushes and reads through.

One process, JAX's default backend; the metric name states the platform
the number was taken on (`jax.devices()[0].platform`). A CPU run is an
XLA:CPU number and says so.

Baseline: the reference publishes no absolute throughput numbers
(BASELINE.md) and no Go toolchain exists in this image, so the CPU baseline
is MEASURED here: the repo's FROZEN v1 single-core scalar C++ codec
(native/m3tsz.cpp, byte-at-a-time bit I/O structurally matching the
reference Go ostream/istream) running the same workload — the closest
stand-in for the reference's hand-optimized Go hot loop. If the native
build is unavailable, falls back to a 10M dp/s constant (the estimated Go
single-core rate).

Prints exactly one JSON line on stdout; exits non-zero on an exception or
a correctness failure.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FALLBACK_BASELINE_DP_PER_SEC = 10_000_000.0


def _measure_cpu_baseline(times, values, start, T) -> float | None:
    """Single-core native C++ encode+decode round-trip dp/s, or None."""
    try:
        from m3_tpu.encoding.m3tsz import native
        from m3_tpu.utils.xtime import TimeUnit

        if not native.available():
            return None
        n_series = min(len(times), 4000)  # enough for a stable rate
        return native.bench_roundtrip(
            times[:n_series], values[:n_series], int(start[0]), TimeUnit.SECOND
        )
    except Exception:
        return None


def _bench() -> dict:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from m3_tpu.encoding.m3tsz import tpu
    from m3_tpu.utils.xtime import TimeUnit

    from __graft_entry__ import _example_batch

    platform = jax.devices()[0].platform

    B = int(os.environ.get("M3_BENCH_B", "8192"))
    T = int(os.environ.get("M3_BENCH_T", "120"))  # ~1M datapoints per dispatch
    times, vbits, start, n_points = _example_batch(B=B, T=T)
    values = vbits.view(np.float64)

    jt = jnp.asarray(times)
    jv = jnp.asarray(vbits)
    js = jnp.asarray(start)
    jn = jnp.asarray(n_points)

    # Capacity tuning: the worst-case default (~146 bits/dp) makes the
    # scatter write mostly zeros; real gauge data needs ~60-80 bits/dp.
    # Try a tight capacity first and fall back on overflow — the overflow
    # flag exists exactly so callers can do this.
    tight_cap = (64 + 80 * T + 11 + 63) // 64
    cap = tight_cap

    def roundtrip():
        blocks = tpu.encode_bits(jt, jv, js, jn, TimeUnit.SECOND, cap)
        dec = tpu.decode(blocks.words, TimeUnit.SECOND, max_points=T)
        return blocks, dec

    # compile + correctness check (falls back to worst-case capacity)
    blocks, dec = roundtrip()
    jax.block_until_ready((blocks.words, dec.times))
    if bool(blocks.overflow):
        cap = None
        blocks, dec = roundtrip()
        jax.block_until_ready((blocks.words, dec.times))
    # bit-level value comparison: exact on every backend. (A float64 on
    # the TPU is a pair of float32: f32 exponent range, about 49 mantissa
    # bits, seen on the v5e in PR 21. A float compare could not be exact.)
    ok = bool(
        (np.asarray(dec.times)[:, :T] == times).all()
        and (np.asarray(dec.value_bits)[:, :T] == vbits).all()
        and not bool(blocks.overflow)
    )

    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        blocks, dec = roundtrip()
    jax.block_until_ready((blocks.words, dec.times))
    dt = (time.perf_counter() - t0) / iters

    dp_per_sec = B * T / dt
    baseline = _measure_cpu_baseline(times, values, start, T)
    baseline = baseline if baseline else FALLBACK_BASELINE_DP_PER_SEC
    return {
        "metric": f"m3tsz encode+decode roundtrip throughput [{platform}]"
        + ("" if ok else " (CORRECTNESS FAILED)"),
        "value": round(dp_per_sec / 1e6, 3),
        "unit": "M datapoints/sec",
        "vs_baseline": round(dp_per_sec / baseline, 3),
    }


def main() -> int:
    out = _bench()
    print(json.dumps(out))
    return 1 if "CORRECTNESS FAILED" in out["metric"] else 0


if __name__ == "__main__":
    sys.exit(main())

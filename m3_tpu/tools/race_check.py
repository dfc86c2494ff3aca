"""ThreadSanitizer harness for the native hot-path libraries.

Role parity with the reference's systematic race detection (SURVEY §5:
`go test -race` on every unit/prop CI run). Python-side concurrency is
covered by tests/test_race_stress.py; this tool closes the gap for the
THREADED NATIVE layer (the v2 batch codec's parallel_over fan-out and the
hostops rate kernel), where the GIL protects nothing:

    python -m m3_tpu.tools.race_check

1. builds TSan-instrumented variants of native/m3tsz.cpp and
   native/hostops.cpp (-fsanitize=thread -O1 -g),
2. re-execs itself under LD_PRELOAD=libtsan.so with M3TSZ_SO/M3HOSTOPS_SO
   pointing the ctypes loaders at the instrumented builds,
3. drives the threaded entry points concurrently from multiple Python
   threads (encode/decode batches at nthreads>1, simultaneous rate_csr
   and agg_groups calls over shared input buffers),
4. stresses the fault-injection registry's lock discipline
   (utils/faults.py): many threads hitting shared fault points while the
   plan is concurrently reconfigured — counters, per-point RNGs, and the
   fire schedule must stay consistent and deadlock-free (the registry sits
   on every durability hot path, so a lock bug there corrupts chaos runs),
5. exits 0 when TSan stays silent and the workloads hold their
   invariants, 66 (TSAN_OPTIONS exitcode) on any reported race.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE = os.path.join(_REPO, "native")
_TSAN_DIR = os.path.join(_NATIVE, "tsan")
_CHILD_ENV = "M3_RACE_CHECK_CHILD"


def _build_tsan() -> dict:
    os.makedirs(_TSAN_DIR, exist_ok=True)
    outs = {}
    for src, so, std in (("m3tsz.cpp", "libm3tsz_tsan.so", None),
                         ("hostops.cpp", "libm3hostops_tsan.so", "c++17")):
        out = os.path.join(_TSAN_DIR, so)
        src_path = os.path.join(_NATIVE, src)
        if not os.path.exists(out) or \
                os.path.getmtime(out) < os.path.getmtime(src_path):
            cmd = ["g++", "-O1", "-g", "-fsanitize=thread", "-shared",
                   "-fPIC", "-pthread"]
            if std:
                cmd.append(f"-std={std}")
            cmd += ["-o", out, src_path]
            subprocess.run(cmd, check=True, timeout=180)
        outs[src] = out
    return outs


def _libtsan_path() -> str:
    out = subprocess.run(["g++", "-print-file-name=libtsan.so"],
                         capture_output=True, text=True, check=True)
    return out.stdout.strip()


def main() -> int:
    if os.environ.get(_CHILD_ENV) != "1":
        outs = _build_tsan()
        env = dict(os.environ)
        env.update({
            _CHILD_ENV: "1",
            "LD_PRELOAD": _libtsan_path(),
            "M3TSZ_SO": outs["m3tsz.cpp"],
            "M3HOSTOPS_SO": outs["hostops.cpp"],
            # jax must not initialize under TSan: the workloads below
            # never import it
            "JAX_PLATFORMS": "cpu",
            "TSAN_OPTIONS": os.environ.get(
                "TSAN_OPTIONS", "exitcode=66 halt_on_error=0"),
        })
        r = subprocess.run([sys.executable, "-m", "m3_tpu.tools.race_check"],
                           env=env, cwd=_REPO, timeout=600)
        if r.returncode == 0:
            print("race_check: no data races reported by ThreadSanitizer")
        else:
            print(f"race_check: FAILED (rc={r.returncode}) — see TSan "
                  "report above", file=sys.stderr)
        return r.returncode

    # ---- child: the instrumented workloads -------------------------------
    import numpy as np

    from m3_tpu.encoding.m3tsz import native
    from m3_tpu.ops import native_hostops
    from m3_tpu.utils.xtime import TimeUnit

    assert native.available(), "tsan m3tsz build failed to load"
    assert native_hostops.available(), "tsan hostops build failed to load"

    rng = np.random.default_rng(0)
    B, T = 64, 60
    start = 1_600_000_000 * 10**9
    times = start + np.cumsum(rng.integers(1, 50, (B, T)),
                              axis=1).astype(np.int64) * 10**9
    values = rng.normal(100, 10, (B, T))

    # 1) the codec's own thread fan-out (parallel_over chunks)
    streams = native.encode_batch(times, values, times[:, 0] - 10**9,
                                  TimeUnit.SECOND, threads=4)
    native.decode_batch(streams, TimeUnit.SECOND, max_points=T, threads=4)

    # 2) concurrent python callers sharing input buffers
    n = 20_000
    e = rng.integers(0, 500, n)
    w = rng.integers(0, 8, n)
    v = rng.normal(0, 1, n)
    t = rng.integers(0, 10**9, n)
    off = np.arange(0, n + 1, 100, dtype=np.int64)
    ts_sorted = np.sort(rng.integers(0, 10**12, n)).astype(np.int64)
    eval_ts = np.arange(10**10, 10**12, 10**10, dtype=np.int64)

    errs = []

    def worker(k):
        try:
            for _ in range(3):
                native_hostops.agg_groups(e, w, v, t)
                native_hostops.rate_csr(ts_sorted, v, off, eval_ts,
                                        5 * 10**10, True, True, threads=2)
                native.bench_roundtrip_batch(times, values,
                                             int(times[0, 0]) - 10**9,
                                             TimeUnit.SECOND, threads=2)
        except Exception as ex:  # noqa: BLE001
            errs.append((k, ex))

    workers = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for wk in workers:
        wk.start()
    for wk in workers:
        wk.join()
    if errs:
        print(f"workload errors: {errs}", file=sys.stderr)
        return 1

    # 3) fault-registry lock discipline: concurrent check() on shared
    # points while another thread reconfigures the active plan
    from m3_tpu.utils import faults

    fault_errs: list = []

    def fault_worker(k):
        try:
            with open(os.devnull, "wb") as devnull:
                for i in range(2_000):
                    try:
                        faults.check("race.shared", worker=k, i=i)
                        if i % 499 == 0:
                            faults.torn_write(devnull, b"x" * 64, "race.torn")
                    except (faults.InjectedError, faults.InjectedTimeout,
                            faults.SimulatedCrash):
                        pass  # injected on purpose; anything else is a bug
        except Exception as ex:  # noqa: BLE001
            fault_errs.append((k, ex))

    def toggler():
        try:
            for i in range(200):
                faults.configure("race.shared=error:p0.01;race.torn=torn:p0.5",
                                 seed=i)
            faults.disable()
        except Exception as ex:  # noqa: BLE001
            fault_errs.append(("toggler", ex))

    threads = [threading.Thread(target=fault_worker, args=(k,))
               for k in range(6)] + [threading.Thread(target=toggler)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    faults.disable()
    if fault_errs:
        print(f"fault-registry errors: {fault_errs}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

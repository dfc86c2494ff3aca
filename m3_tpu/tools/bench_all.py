"""BASELINE.md configs #1-#5 as one harness, plus #6 (the batched
read_many path — config #3's fetch leg measured directly), #7 (the
write-hot-path observability overhead guard), #8 (the batched
write_batch ingest path vs the per-entry loop), #9 (end-to-end
query_range latency, whole-query-compiled vs interpreted), #10 (the
profiler-overhead guard: sampling profiler + lock-wait profiling +
stall watchdog armed vs off, same pairing discipline as #7), #11
(the sharded query plane: the same fused query_range + grouped
aggregation on the series-sharded device mesh vs single-device, swept
over device counts), #12 (the pipelined dataflow: sparse
multi-group read_many->query e2e, executor-pipelined vs the pinned
serial seed path, pair-median, correctness-gated) and #14 (the
device-compiled inverted index: boolean matcher evaluation at 1M/10M
terms, fused ragged postings program vs the PR-0 scalar walk,
pair-median, correctness-gated at every device count).

Prints one JSON line per config (same shape as bench.py). Sizes are
env-tunable; defaults are sized to finish on CPU in a few minutes —
on a real TPU set M3_BENCH_SCALE=1 for the full north-star shapes.

    python -m m3_tpu.tools.bench_all [--configs 1,2,3,4,5] [--record FILE]

Methodology (the config-#1 approach throughout): the VALUE is the
framework's best serving path on the platform that exists — the XLA device
kernels when an accelerator is live, the native C++ batch/columnar kernels
(the real CPU dispatch targets per utils/dispatch + ops wiring) otherwise.
The BASELINE is a measured stand-in for the reference's hand-optimized Go
hot loop running the same workload:
  #1  frozen v1 single-core scalar C++ codec (byte-at-a-time bit I/O
      structurally matching the reference Go ostream/istream)
  #2  per-sample string-keyed entry lookup + lock + accumulator update
      (native/hostops.cpp m3_agg_baseline_scalar — the reference
      aggregator's AddUntimed map.go/entry.go/counter.go hot-loop shape)
  #3  per-(series, step) window re-scan rate (m3_rate_baseline_scalar —
      the prometheus/reference temporal-engine iteration shape)
  #4  compiled-regex fullmatch scan over the term vocabulary
  #5  numpy partition + scatter-add (selection-based, no strawman)
Every config asserts the serving output equals the baseline output before
reporting, so the speedup is never bought with a different answer.

One process, JAX's default backend: a CPU run is a CPU number. A failed
config exits non-zero after the others have run.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _scale() -> float:
    try:
        return float(os.environ.get("M3_BENCH_SCALE", "0.1"))
    except ValueError:
        return 0.1


_RECORD: list[dict] = []


def _emit(metric: str, dp_per_sec: float, baseline: float) -> None:
    line = {
        "metric": metric,
        "value": round(dp_per_sec / 1e6, 3),
        "unit": "M datapoints/sec",
        "vs_baseline": round(dp_per_sec / baseline, 3) if baseline else 0.0,
    }
    _RECORD.append(line)
    print(json.dumps(line), flush=True)


def _time(fn, iters=3):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    _block(out)
    return (time.perf_counter() - t0) / iters


def _block(out):
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:
        pass


def _accelerator() -> bool:
    from m3_tpu.utils import dispatch

    return dispatch._accelerator_present()


def config1_codec_roundtrip():
    """100k-series M3TSZ round-trip on the serving path vs the frozen v1
    scalar C++ baseline (the Go-hot-loop stand-in) — same methodology as
    bench.py: XLA codec on an accelerator, native v2 batch codec on CPU."""
    from __graft_entry__ import _example_batch
    from m3_tpu.encoding.m3tsz import native
    from m3_tpu.utils.xtime import TimeUnit

    B = max(int(100_000 * _scale()), 1024)
    T = 120
    times, vbits, start, n_points = _example_batch(B=B, T=T)
    values = vbits.view(np.float64)

    if _accelerator():
        import jax.numpy as jnp

        from m3_tpu.encoding.m3tsz import tpu

        jt, jv = jnp.asarray(times), jnp.asarray(vbits)
        js, jn = jnp.asarray(start), jnp.asarray(n_points)
        cap = (64 + 80 * T + 11 + 63) // 64

        def run():
            blocks = tpu.encode_bits(jt, jv, js, jn, TimeUnit.SECOND, cap)
            dec = tpu.decode(blocks.words, TimeUnit.SECOND, max_points=T)
            return blocks.words, dec.times

        dt = _time(run)
        rate = B * T / dt
        path = "xla device"
    elif native.available():
        native.bench_roundtrip_batch(times, values, int(start[0]),
                                     TimeUnit.SECOND)  # warm
        rates = [native.bench_roundtrip_batch(times, values, int(start[0]),
                                              TimeUnit.SECOND)[0]
                 for _ in range(3)]
        rate = sum(rates) / len(rates)
        path = f"native batch, {native.default_threads()}t"
    else:
        _emit(f"#1 m3tsz roundtrip {B}x{T} (no serving codec)", 0.0, 10e6)
        return
    base = None
    if native.available():
        base = native.bench_roundtrip(
            times[:4000], values[:4000], int(start[0]), TimeUnit.SECOND)
    _emit(f"#1 m3tsz roundtrip {B}x{T} [{path}]", rate, base or 10e6)


def config2_rollup():
    """1M-series counter+gauge rollup 10s -> 1m: the flush reduction on the
    serving path (device kernel on an accelerator, native columnar kernel on
    CPU — what windowed_agg dispatch actually runs) vs the measured
    per-sample scalar baseline (string-keyed entry lookup + lock + update,
    the reference AddUntimed hot-loop shape)."""
    from m3_tpu.ops import native_hostops, windowed_agg

    n = max(int(6_000_000 * _scale()), 100_000)  # 1M series x 6 samples
    rng = np.random.default_rng(0)
    n_series = n // 6
    e = rng.integers(0, n_series, n)
    w = rng.integers(0, 6, n)
    v = rng.normal(100, 10, n)
    t = rng.integers(0, 10**9, n)

    if _accelerator():
        os.environ["M3_TPU_DEVICE_OPS"] = "1"
        path = "xla device"
    else:
        path = f"native columnar, {native_hostops.default_threads()}t" \
            if native_hostops.available() else "numpy host"

    def serving():
        return windowed_agg.aggregate_groups(e, w, v, times=t,
                                             need_sorted=False)[2]["sum"]

    try:
        dt_serve = _time(serving)
    finally:
        os.environ.pop("M3_TPU_DEVICE_OPS", None)

    if not native_hostops.available():
        _emit(f"#2 rollup {n} samples -> {n_series} series [{path}, "
              "no native baseline]", n / dt_serve, 10e6)
        return
    # baseline: the reference per-sample shape over the SAME samples, with
    # the UNRESOLVED string ids it would hash per add
    ids = [b"stats.counter.%07d+env=prod,host=h%04d,dc=dc1" % (x, x % 1024)
           for x in e]
    native_hostops.agg_baseline_scalar(ids[:1000], w[:1000], v[:1000])  # warm
    t0 = time.perf_counter()
    checksum, _ = native_hostops.agg_baseline_scalar(ids, w, v)
    dt_base = time.perf_counter() - t0
    # correctness: same total across both paths
    serve_sum = float(np.asarray(serving()).sum())
    ok = np.isclose(checksum, serve_sum, rtol=1e-8)
    _emit(f"#2 rollup {n} samples -> {n_series} series [{path}]"
          + ("" if ok else " (CORRECTNESS FAILED)"),
          n / dt_serve, n / dt_base)


def config3_promql_rate_sum(tmp=None):
    """PromQL rate() over a wide fetch: the serving path (device kernel on
    an accelerator, native columnar pointer-walk on CPU — what
    windows.extrapolated_rate dispatch actually runs) vs the measured
    per-(series, step) window-rescan scalar baseline."""
    from m3_tpu.ops import native_hostops
    from m3_tpu.query.windows import NS, RaggedSeries
    from m3_tpu.query import windows

    S = max(int(100_000 * _scale()), 4_000)
    T = 240  # 1h at 15s
    per = []
    rng = np.random.default_rng(1)
    base_t = np.arange(T, dtype=np.int64) * 15 * NS
    for s in range(S):
        v = rng.integers(1, 10, T).astype(np.float64).cumsum()
        per.append((base_t, v))
    raws = RaggedSeries.from_lists(per)
    eval_ts = np.arange(300, 3600, 60, dtype=np.int64) * NS
    n_dp = S * T

    if _accelerator():
        os.environ["M3_TPU_DEVICE_OPS"] = "1"
        path = "xla device"
    else:
        path = f"native columnar, {native_hostops.default_threads()}t" \
            if native_hostops.available() else "numpy host"

    def serving():
        return windows.extrapolated_rate(raws, eval_ts, 300 * NS, True, True)

    try:
        dt_serve = _time(serving)
        served = np.asarray(serving())
    finally:
        os.environ.pop("M3_TPU_DEVICE_OPS", None)

    if not native_hostops.available():
        _emit(f"#3 rate() {S} series x {T} pts [{path}, no native baseline]",
              n_dp / dt_serve, 10e6)
        return
    sub = max(1, S // 10)  # baseline on a slice, extrapolated (it's slow)
    sub_off = raws.offsets[:sub + 1]

    def base():
        return native_hostops.rate_baseline_scalar(
            raws.times, raws.values, sub_off, eval_ts, 300 * NS, True, True)

    base()  # warm
    t0 = time.perf_counter()
    based = base()
    dt_base = (time.perf_counter() - t0) * (S / sub)
    ok = np.allclose(served[:sub], based, rtol=1e-9, equal_nan=True)
    _emit(f"#3 rate() {S} series x {T} pts [{path}]"
          + ("" if ok else " (CORRECTNESS FAILED)"),
          n_dp / dt_serve, n_dp / dt_base)


def config4_regex_postings():
    """High-cardinality regex queries over packed postings vs naive scan."""
    import re

    from m3_tpu.index import packed
    from m3_tpu.index.segment import Document

    n = max(int(10_000_000 * _scale()), 200_000)
    docs = [Document(i, b"s%08d" % i, [(b"pod", b"pod-%08d" % i)])
            for i in range(n)]
    seg = packed.build(docs)
    pats = [rb"pod-0000\d\d\d\d", rb"pod-000[0-4]\d+", rb"pod-.*99",
            rb"pod-0(1|2)\d+", rb"pod-00001[0-9]{3}"]
    pats = (pats * 10)[:50]

    def run_packed():
        total = 0
        for p in pats:
            seg._regex_cache.clear()
            total += len(seg.postings_regexp(b"pod", re.compile(p)))
        return total

    t0 = time.perf_counter()
    run_packed()
    dt = time.perf_counter() - t0
    # naive baseline: per-term fullmatch of ONE pattern, extrapolated to 50
    terms = seg.terms(b"pod")[: min(n, 200_000)]
    rx = re.compile(pats[0])
    t0 = time.perf_counter()
    sum(1 for t in terms if rx.fullmatch(t))
    naive_per_query = (time.perf_counter() - t0) * (n / len(terms))
    _emit(f"#4 50 regex queries over {n}-term postings",
          50 * n / dt, 50 * n / (50 * naive_per_query))


def config5_sharded_quantile():
    """4-shard timer quantile rollup with explicit cross-shard psum.

    The device program is the flagship ICI pattern: shard_map over the
    mesh, per-shard selection-based quantile (top_k, NOT a full sort — a
    p99 over a T-point window needs only the top T-ceil(0.99 T) elements)
    + local segment sums, then one psum pair across the shard axis. The
    host baseline is the same computation in numpy (np.partition + add.at,
    also selection-based — no strawman)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    import m3_tpu.ops  # noqa: F401  (x64)

    shard_map = jax.shard_map

    n_dev = min(4, len(jax.devices()))
    devices = np.array(jax.devices()[:n_dev])
    # bench-only: one mesh per config run, compile paid before timing
    # m3lint: disable=jax-jit-per-call
    mesh = Mesh(devices, axis_names=("shard",))
    S = max(int(10_000_000 * _scale()) // 64, 4096)
    S -= S % n_dev
    T = 64
    G = 128
    rng = np.random.default_rng(2)
    vals = rng.gamma(2.0, 10.0, (S, T))
    gids = (np.arange(S) % G).astype(np.int32)
    q_idx = int(T * 0.99)
    k = T - q_idx  # selection depth: sorted[q_idx] == k-th largest

    def kth_largest_time_major(v, kk):
        # iterative masked-max selection over the TIME axis of a
        # time-major [T, S] elem grid: kk-1 passes peel the larger
        # elements, pass kk's max is the answer. O(kk*T) elementwise — no
        # sort, no top_k (XLA:CPU lowers top_k to a full variadic sort;
        # TPU tiles elementwise reductions onto the VPU directly). Each
        # pass's reduction is a vertical SIMD op across series lanes.
        for _ in range(kk - 1):
            m = jnp.max(v, axis=0, keepdims=True)
            # mask exactly one occurrence of the max per series
            first = jnp.cumsum(v == m, axis=0) == 1
            v = jnp.where(first & (v == m), -jnp.inf, v)
        return jnp.max(v, axis=0)

    # group counts AND the group->series one-hot placement matrix depend
    # only on the shard->group placement, not on the flushed values:
    # precompute both once (the host baseline likewise only does the
    # per-flush work — partition + scatter-add — in its timed section)
    cnt_host = np.bincount(gids, minlength=G).astype(np.float64)
    onehot_t_host = np.zeros((G, S))
    onehot_t_host[gids, np.arange(S)] = 1.0

    # the segment reduction is a one-hot MATVEC, not segment_sum:
    # XLA:CPU lowers segment_sum to a serial scatter-add, while
    # [G, S_shard] @ [S_shard] runs through the tuned GEMV (a TPU tiles
    # it onto the MXU). Orientation matters: the GROUP-major [G, S]
    # one-hot makes every output group one contiguous SIMD dot; the
    # [S, G] orientation (q @ oh) pays a stride-G gather per group —
    # profiled ~2.6x between them, ~4x over segment_sum

    def per_shard_select(v, oht, cnt):  # time-major [T, S_shard]
        seg = oht @ kth_largest_time_major(v, k)
        return jax.lax.psum(seg, "shard") / cnt

    def per_shard_max(v, oht, cnt):  # series-major [S_shard, T]
        seg = oht @ jnp.max(v, axis=1)
        return jax.lax.psum(seg, "shard") / cnt

    # layout is ours to choose for device-resident state, PER selection
    # depth: k == 1 (p99 over a 64-pt window) degenerates to a plain max,
    # which the series-major [S, T] grid serves with one contiguous
    # horizontal reduce per row — profiled ~1.9x over running the k=1
    # peel on the time-major grid. Deeper selections keep the time-major
    # grid the iterative peel prefers. The host baseline keeps its own
    # best layout (row-major [S, T] for np.partition) either way.
    if k == 1:
        fn, spec, dev_vals = per_shard_max, P("shard", None), vals
    else:
        fn, spec, dev_vals = per_shard_select, P(None, "shard"), vals.T.copy()
    # bench-only: built once per config run, and the warmup call below
    # pays the compile before the timed region starts
    # m3lint: disable=jax-jit-per-call
    quantile_rollup = jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(spec, P(None, "shard"), P()), out_specs=P(),
    ))

    # bench-only, once per config run (not per eval)
    # m3lint: disable=jax-jit-per-call
    sh_v, sh_oh, sh_c = (jax.NamedSharding(mesh, s)
                         for s in (spec, P(None, "shard"), P()))
    jv = jax.device_put(jnp.asarray(dev_vals), sh_v)
    joh = jax.device_put(jnp.asarray(onehot_t_host), sh_oh)
    jc = jax.device_put(jnp.asarray(np.maximum(cnt_host, 1.0)), sh_c)
    # both sides run the same iteration count, high enough to average
    # out scheduler noise (at 3 iters the run-to-run spread exceeded the
    # device/host gap on shared-CPU hosts)
    iters = 15
    # bench-only: the timed region measures raw kernel dispatch — a
    # tracker would add exactly the overhead config #16 bounds
    # m3lint: disable=inv-jit-tracked
    dt = _time(lambda: quantile_rollup(jv, joh, jc), iters=iters)

    # host numpy baseline of the same computation
    def host():
        q = np.partition(vals, q_idx, axis=1)[:, q_idx]
        out = np.zeros(G)
        np.add.at(out, gids, q)
        return out

    t0 = time.perf_counter()
    for _ in range(iters):
        host()
    dt_host = (time.perf_counter() - t0) / iters
    # correctness: device result == host result (bench-only, same raw
    # dispatch as the timed region)
    # m3lint: disable=inv-jit-tracked
    dev = np.asarray(quantile_rollup(jv, joh, jc))
    ok = np.allclose(dev, host() / np.maximum(cnt_host, 1), rtol=1e-9)
    _emit(f"#5 {n_dev}-shard timer quantile rollup {S}x{T}"
          + ("" if ok else " (CORRECTNESS FAILED)"),
          S * T / dt, S * T / dt_host)


def config6_read_many():
    """Batched multi-series fetch (config #3's fetch leg, measured
    directly): Namespace.read_many — grouping by (shard, block, volume)
    with ONE fused fetch+decode dispatch per group — vs the per-series
    read loop it replaced (one Python round-trip + cache probe + decode
    dispatch per series). Both single-threaded, cold cache, so the ratio
    is pure dispatch economy, not parallelism."""
    import tempfile

    from m3_tpu.encoding.m3tsz import hostpath
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.fileset import FilesetWriter
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils.xtime import TimeUnit

    NS = 10**9
    BLOCK = 3600 * NS
    START = 1_600_000_000 * NS
    T = 24
    n_blocks, n_shards = 2, 8
    prev_threads = os.environ.get("M3_NATIVE_THREADS")
    os.environ["M3_NATIVE_THREADS"] = "1"
    try:
        for B in (10_000, 100_000):
            with tempfile.TemporaryDirectory() as root:
                db = Database(root, DatabaseOptions(
                    n_shards=n_shards, block_cache_entries=0))  # cold cache
                ns = db.create_namespace("default", NamespaceOptions(
                    retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                               block_size_ns=BLOCK),
                    index=IndexOptions(enabled=False),
                    writes_to_commitlog=False, snapshot_enabled=False))
                ids = [b"series-%07d" % i for i in range(B)]
                by_shard: dict[int, list[bytes]] = {}
                for sid in ids:
                    by_shard.setdefault(ns.shard_set.lookup(sid), []).append(sid)
                rng = np.random.default_rng(0)
                for shard_id, sids in by_shard.items():
                    for b in range(n_blocks):
                        bs = START + b * BLOCK
                        nb = len(sids)
                        times = np.broadcast_to(
                            bs + np.arange(T, dtype=np.int64) * 10 * NS,
                            (nb, T)).copy()
                        vbits = rng.normal(100.0, 20.0, (nb, T)) \
                            .view(np.uint64)
                        streams = hostpath.encode_blocks(
                            times, vbits, np.full(nb, bs, np.int64),
                            np.full(nb, T, np.int32), TimeUnit.SECOND, False)
                        w = FilesetWriter(db.fs_root, "default", shard_id,
                                          bs, BLOCK, 0)
                        for sid, stream in zip(sids, streams):
                            w.write_series(sid, b"", stream)
                        w.close()
                db.open(START + n_blocks * BLOCK)
                t_lo, t_hi = START, START + n_blocks * BLOCK
                n_dp = B * T * n_blocks

                batched = ns.read_many(ids, t_lo, t_hi)  # warm code paths
                t0 = time.perf_counter()
                batched = ns.read_many(ids, t_lo, t_hi)
                dt_batch = time.perf_counter() - t0

                t0 = time.perf_counter()
                scalar = [ns.read(sid, t_lo, t_hi) for sid in ids]
                dt_loop = time.perf_counter() - t0
                ok = all(np.array_equal(bt, st) and np.array_equal(bv, sv)
                         for (bt, bv), (st, sv)
                         in zip(batched[::max(1, B // 200)],
                                scalar[::max(1, B // 200)]))
                db.close()
            _emit(f"#6 read_many {B} series x {T * n_blocks} pts cold "
                  "fetch+decode [batched per (shard, block), 1t]"
                  + ("" if ok else " (CORRECTNESS FAILED)"),
                  n_dp / dt_batch, n_dp / dt_loop)
    finally:
        if prev_threads is None:
            os.environ.pop("M3_NATIVE_THREADS", None)
        else:
            os.environ["M3_NATIVE_THREADS"] = prev_threads


def config7_tracing_overhead():
    """Observability-overhead guard on the write hot path (PR-4, widened
    in PR-6): the SHIPPED path (tracer enabled at sample_every=1,
    per-write latency histogram WITH exemplar capture, and a live
    telemetry-exporter drainer shipping the registry+span ring to a file
    sink every 0.5s) vs the seed-equivalent path (tracer disabled,
    histogram observe no-oped, no exporter). The disabled-path cost must
    stay within noise of seed: vs_baseline is shipped/seed throughput and
    the run flags anything below 0.85 (beyond run-to-run noise on shared
    hosts)."""
    import tempfile

    from m3_tpu.storage import database as database_mod
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils import trace

    NS = 10**9
    START = 1_600_000_000 * NS
    N = max(int(400_000 * _scale()), 40_000)

    # pure CPU write path (no commitlog/index I/O): filesystem jitter on
    # shared hosts would otherwise swamp the effect being guarded
    def run_once() -> float:
        with tempfile.TemporaryDirectory() as root:
            db = Database(root, DatabaseOptions(n_shards=4))
            db.create_namespace("default", NamespaceOptions(
                retention=RetentionOptions(retention_ns=1000 * 3600 * NS,
                                           block_size_ns=3600 * NS),
                index=IndexOptions(enabled=False),
                writes_to_commitlog=False, snapshot_enabled=False))
            db.open(START)
            names = [b"m%05d" % i for i in range(1000)]
            tags = [(b"k", b"v")]
            t0 = time.perf_counter()
            for i in range(N):
                db.write_tagged("default", names[i % 1000], tags,
                                START + (i % 3600) * NS, float(i))
            dt = time.perf_counter() - t0
            db.close()
        return N / dt

    tracer = trace.default_tracer()
    real_observe_write = database_mod._observe_write

    def seed_equivalent(on: bool):
        tracer.enabled = on
        database_mod._observe_write = real_observe_write if on \
            else (lambda v: None)

    # paired interleaved runs, median of the per-pair ratios: host drift
    # on shared CPUs exceeds the effect size, and back-to-back pairing +
    # median is the standard way to cancel it. The shipped side runs
    # under a LIVE exporter drainer (file sink, 0.5s interval) so the
    # guard covers the full PR-6 observability stack.
    from m3_tpu.utils.export import FileSink, TelemetryExporter

    ratios: list[float] = []
    rate_on = rate_off = 0.0
    try:
        seed_equivalent(True)
        run_once()  # warm the code paths once, outside any pair
        for _ in range(5):
            seed_equivalent(True)
            with tempfile.TemporaryDirectory() as sink_dir:
                exporter = TelemetryExporter(
                    "bench", FileSink(f"{sink_dir}/telemetry.jsonl"),
                    interval_s=0.5)
                exporter.start()
                try:
                    on = run_once()
                finally:
                    exporter.close()
            seed_equivalent(False)
            off = run_once()
            ratios.append(on / off)
            rate_on, rate_off = max(rate_on, on), max(rate_off, off)
    finally:
        seed_equivalent(True)
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    _emit("#7 write hot path w/ observability vs seed-equivalent"
          + ("" if ratio >= 0.85 else " (OVERHEAD EXCEEDED)"),
          ratio * rate_off, rate_off)


def config8_write_batch():
    """Batched ingest (the write-side twin of #6): Database.write_batch —
    one columnar pass per (namespace, shard): memoized series identity,
    vectorized murmur3 shard routing, ONE commitlog append per batch,
    one buffer lock per (shard, window) group, pre-filtered index
    inserts — vs the per-entry write_tagged loop it replaces. Both
    single-threaded with commitlog + index ON (the real ingest path).
    Correctness: both databases must read back identically and their
    commitlogs must replay the same entry stream."""
    import tempfile

    from m3_tpu.storage import commitlog
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils.ident import tags_to_id

    NS = 10**9
    START = 1_600_000_000 * NS

    def new_db(root):
        db = Database(root, DatabaseOptions(n_shards=8))
        db.create_namespace("default", NamespaceOptions(
            retention=RetentionOptions(retention_ns=1000 * 3600 * NS,
                                       block_size_ns=3600 * NS),
            index=IndexOptions(enabled=True, block_size_ns=3600 * NS),
            writes_to_commitlog=True, snapshot_enabled=False))
        db.open(START)
        return db

    names = [b"m%05d" % i for i in range(1000)]
    for B in (10_000, 100_000):
        # ~2000 distinct series, 2 block windows: a realistic ingest mix
        # of repeated identities across shards
        entries = [
            (names[i % 1000], [(b"host", b"h%03d" % (i % 100))],
             START + (i % 7200) * NS, float(i))
            for i in range(B)
        ]
        with tempfile.TemporaryDirectory() as r1, \
                tempfile.TemporaryDirectory() as r2, \
                tempfile.TemporaryDirectory() as rw:
            warm = new_db(rw)  # warm both code paths off the timed dbs
            warm.write_batch("default", entries[:256])
            for m, tg, t, v in entries[:256]:
                warm.write_tagged("default", m, tg, t, v)
            warm.close()

            db_b = new_db(r1)
            t0 = time.perf_counter()
            results = db_b.write_batch("default", entries)
            dt_batch = time.perf_counter() - t0
            ok = all(r is None for r in results)

            db_l = new_db(r2)
            t0 = time.perf_counter()
            for m, tg, t, v in entries:
                db_l.write_tagged("default", m, tg, t, v)
            dt_loop = time.perf_counter() - t0

            # parity: sampled series read identically, and both WALs
            # replay the same entry stream
            sample = {tags_to_id(m, tg) for m, tg, _t, _v in entries[::503]}
            for sid in sample:
                bt, bv = db_b.namespaces["default"].read(
                    sid, START, START + 7200 * NS)
                lt, lv = db_l.namespaces["default"].read(
                    sid, START, START + 7200 * NS)
                ok = ok and np.array_equal(bt, lt) and np.array_equal(bv, lv)
            db_b._commitlogs["default"].flush(fsync=True)
            db_l._commitlogs["default"].flush(fsync=True)
            eb = commitlog.replay(
                commitlog.log_files(db_b.commitlog_dir("default"))[0])
            el = commitlog.replay(
                commitlog.log_files(db_l.commitlog_dir("default"))[0])
            ok = ok and [(e.series_id, e.time_ns, e.value_bits) for e in eb] \
                == [(e.series_id, e.time_ns, e.value_bits) for e in el]
            db_b.close()
            db_l.close()
        _emit(f"#8 write_batch {B} entries commitlog+index "
              "[columnar per (shard, window), 1t]"
              + ("" if ok else " (CORRECTNESS FAILED)"),
              B / dt_batch, B / dt_loop)


def config9_query_compile():
    """End-to-end query_range latency, whole-query-compiled vs op-by-op
    interpreted (ROADMAP #2 — the number a p99 user actually sees, not
    per-op throughput): one coordinator-shaped Engine over a real
    fileset+index namespace, 10k series x 48h of samples, a 2m-step
    dashboard grid (~1.4k steps). Paired INTERLEAVED runs with the
    median of per-pair ratios (this host is +-30% noisy; single shots
    are meaningless). Both sides share fetch/decode/limits — the ratio
    isolates exactly what compilation changes. Correctness gate: the
    compiled result must match the interpreter element-identically
    (NaN-mask equal, values within 1e-9 relative — the documented XLA
    reassociation envelope) before anything is reported.

    Shapes: the instant-delta dashboard (`max by (host) (irate(...))`,
    no native interpreter kernel — the fused program's win) and the
    windowed-aggregation dashboard (`avg by (host) (avg_over_time(...))`).
    Extrapolated-rate plans are deliberately absent: on a CPU-only
    backend the per-plan dispatch policy hands those to the
    interpreter's native rate_csr kernel (compiler._host_prefers_
    interpreter), which profiled ~2.4x faster than the XLA lowering."""
    import tempfile

    from m3_tpu.encoding.m3tsz import hostpath
    from m3_tpu.query.engine import Engine
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.fileset import FilesetWriter
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils.xtime import TimeUnit

    NS = 10**9
    BLOCK = 48 * 3600 * NS
    START = 1_600_000_000 * NS
    S = 10_000
    SAMP = 300 * NS                # one sample per 5m per series
    T = (48 * 3600 * NS) // SAMP   # 576 samples per series
    with tempfile.TemporaryDirectory() as root:
        db = Database(root, DatabaseOptions(
            n_shards=8, block_cache_entries=100_000))  # warm-cache serving
        ns = db.create_namespace("default", NamespaceOptions(
            retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                       block_size_ns=BLOCK),
            index=IndexOptions(enabled=True, block_size_ns=BLOCK),
            writes_to_commitlog=False, snapshot_enabled=False))
        ids = [b"reqs,host=h%04d,i=%05d" % (i % 200, i) for i in range(S)]
        fields = [[(b"__name__", b"reqs"), (b"host", b"h%04d" % (i % 200)),
                   (b"i", b"%05d" % i)] for i in range(S)]
        by_shard: dict[int, list[int]] = {}
        for j, sid in enumerate(ids):
            by_shard.setdefault(ns.shard_set.lookup(sid), []).append(j)
        rng = np.random.default_rng(0)
        for shard_id, rows in by_shard.items():
            nb = len(rows)
            times = np.broadcast_to(
                START + np.arange(T, dtype=np.int64) * SAMP, (nb, T)).copy()
            vals = rng.integers(1, 10, (nb, T)).astype(np.float64) \
                .cumsum(axis=1)
            streams = hostpath.encode_blocks(
                times, vals.view(np.uint64), np.full(nb, START, np.int64),
                np.full(nb, T, np.int32), TimeUnit.SECOND, False)
            w = FilesetWriter(db.fs_root, "default", shard_id, START,
                              BLOCK, 0)
            for j, stream in zip(rows, streams):
                w.write_series(ids[j], b"", stream)
            w.close()
        db.open(START + BLOCK)
        ns.index.insert_many(ids, fields, np.full(S, START, np.int64))
        eng = Engine(db, resolve_tiers=False)
        qstart = START + 30 * 60 * NS
        qend = START + 48 * 3600 * NS - SAMP
        step = 2 * 60 * NS
        n_dp = S * T  # samples the query reads end to end

        prev = os.environ.get("M3_TPU_QUERY_COMPILE")
        try:
            for label, q in (
                ("irate max-by", "max by (host) (irate(reqs[30m]))"),
                ("avg_over_time avg-by",
                 "avg by (host) (avg_over_time(reqs[30m]))"),
            ):
                def run():
                    return eng.query_range(q, qstart, qend, step)[0]

                os.environ["M3_TPU_QUERY_COMPILE"] = "1"
                v_c = run()  # warm: pays the one plan compile
                os.environ["M3_TPU_QUERY_COMPILE"] = "0"
                v_i = run()
                ok = (v_c.labels == v_i.labels
                      and np.array_equal(np.isnan(v_c.values),
                                         np.isnan(v_i.values))
                      and np.allclose(v_c.values, v_i.values, rtol=1e-9,
                                      atol=0, equal_nan=True))
                pairs: list[tuple[float, float, float]] = []
                for _ in range(5):
                    os.environ["M3_TPU_QUERY_COMPILE"] = "1"
                    t0 = time.perf_counter()
                    run()
                    dt_c = time.perf_counter() - t0
                    os.environ["M3_TPU_QUERY_COMPILE"] = "0"
                    t0 = time.perf_counter()
                    run()
                    dt_i = time.perf_counter() - t0
                    pairs.append((dt_i / dt_c, n_dp / dt_c, n_dp / dt_i))
                # report the MEDIAN pair's measured numbers: value is a
                # real compiled-side throughput and vs_baseline is the
                # pair-median ratio, not a synthetic best-x-median blend
                pairs.sort(key=lambda p: p[0])
                _ratio, thr_c, thr_i = pairs[len(pairs) // 2]
                _emit(f"#9 query_range e2e {S} series x ~1.4k steps "
                      f"[{label}, compiled vs interpreted]"
                      + ("" if ok else " (CORRECTNESS FAILED)"),
                      thr_c, thr_i)
        finally:
            if prev is None:
                os.environ.pop("M3_TPU_QUERY_COMPILE", None)
            else:
                os.environ["M3_TPU_QUERY_COMPILE"] = prev


def config10_profiler_overhead():
    """Profiler-overhead guard (the PR-11 twin of #7): the write hot
    path with the WHOLE profiling & saturation plane armed — sampling
    profiler at ~19 Hz, lock-wait profiling wrapping every
    threading.Lock/RLock the timed code creates, stall-watchdog checker
    running — vs the same path with all of it off. Same pairing
    discipline as #7 (interleaved pairs, median ratio, 0.85 noise bar):
    'always-on profiling' is only true if this number stays at 1.0-ish."""
    import tempfile

    from m3_tpu.storage.database import Database
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils import profiler

    NS = 10**9
    START = 1_600_000_000 * NS
    N = max(int(400_000 * _scale()), 40_000)

    # pure CPU write path (no commitlog/index I/O), as in #7: the effect
    # being guarded is per-write lock/sampling overhead, not disk jitter
    def run_once() -> float:
        with tempfile.TemporaryDirectory() as root:
            db = Database(root, DatabaseOptions(n_shards=4))
            db.create_namespace("default", NamespaceOptions(
                retention=RetentionOptions(retention_ns=1000 * 3600 * NS,
                                           block_size_ns=3600 * NS),
                index=IndexOptions(enabled=False),
                writes_to_commitlog=False, snapshot_enabled=False))
            db.open(START)
            names = [b"m%05d" % i for i in range(1000)]
            tags = [(b"k", b"v")]
            t0 = time.perf_counter()
            for i in range(N):
                db.write_tagged("default", names[i % 1000], tags,
                                START + (i % 3600) * NS, float(i))
            dt = time.perf_counter() - t0
            db.close()
        return N / dt

    prof = profiler.default_profiler()
    wd = profiler.default_watchdog()

    def armed(on: bool):
        # the timed Database is constructed AFTER the factory swap, so
        # the armed side's storage locks are all profiled wrappers
        if on:
            profiler.install_lock_profiling()
            prof.start(profiler.DEFAULT_HZ)
            wd.start()
        else:
            prof.stop()
            wd.stop()
            profiler.uninstall_lock_profiling()

    ratios: list[float] = []
    rate_off = 0.0
    try:
        armed(True)
        run_once()  # warm code paths once, outside any pair
        for _ in range(5):
            armed(True)
            on_rate = run_once()
            armed(False)
            off_rate = run_once()
            ratios.append(on_rate / off_rate)
            rate_off = max(rate_off, off_rate)
    finally:
        armed(False)
        profiler.reset_lock_stats()
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    _emit("#10 write hot path w/ profiler+locks+watchdog armed vs off"
          + ("" if ratio >= 0.85 else " (OVERHEAD EXCEEDED)"),
          ratio * rate_off, rate_off)


def config11_sharded_query():
    """Sharded multi-device query plane (PR 12, ROADMAP #1): end-to-end
    query_range + grouped aggregation with the SAME fused program on the
    series-sharded mesh vs single-device, swept over device counts on
    whatever devices the backend has (8 virtual CPU devices under
    run_tests.sh's XLA_FLAGS). Both sides run whole-query-compiled
    (M3_TPU_QUERY_COMPILE=1), so the ratio isolates exactly what the
    mesh changes: per-device sample slabs (device-local gathers), SPMD
    stage partitioning, psum-lowered grouped reductions. Pairing
    discipline as #9 (interleaved pairs, median-pair numbers; this host
    is +-30% noisy). Correctness gate: the sharded result must match the
    interpreter element-identically (NaN masks exact, values within the
    documented 1e-9 reassociation envelope) before anything is
    reported."""
    import tempfile

    import jax

    from m3_tpu.encoding.m3tsz import hostpath
    from m3_tpu.query.engine import Engine
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.fileset import FilesetWriter
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils.xtime import TimeUnit

    NS = 10**9
    BLOCK = 48 * 3600 * NS
    START = 1_600_000_000 * NS
    S = 4096
    SAMP = 300 * NS                # one sample per 5m per series
    T = (48 * 3600 * NS) // SAMP   # 576 samples per series
    n_devices = len(jax.devices())
    if n_devices < 2:
        # a live single-device accelerator runs in-process (no virtual
        # CPU re-exec): nothing to shard — note it, record nothing
        print(json.dumps({"metric": "#11 sharded query skipped: 1 device",
                          "value": 0.0, "unit": "M datapoints/sec",
                          "vs_baseline": 0.0}), flush=True)
        return
    with tempfile.TemporaryDirectory() as root:
        db = Database(root, DatabaseOptions(
            n_shards=8, block_cache_entries=100_000))  # warm-cache serving
        ns = db.create_namespace("default", NamespaceOptions(
            retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                       block_size_ns=BLOCK),
            index=IndexOptions(enabled=True, block_size_ns=BLOCK),
            writes_to_commitlog=False, snapshot_enabled=False))
        ids = [b"reqs,host=h%04d,i=%05d" % (i % 128, i) for i in range(S)]
        fields = [[(b"__name__", b"reqs"), (b"host", b"h%04d" % (i % 128)),
                   (b"i", b"%05d" % i)] for i in range(S)]
        by_shard: dict[int, list[int]] = {}
        for j, sid in enumerate(ids):
            by_shard.setdefault(ns.shard_set.lookup(sid), []).append(j)
        rng = np.random.default_rng(0)
        for shard_id, rows in by_shard.items():
            nb = len(rows)
            times = np.broadcast_to(
                START + np.arange(T, dtype=np.int64) * SAMP, (nb, T)).copy()
            vals = rng.integers(1, 10, (nb, T)).astype(np.float64) \
                .cumsum(axis=1)
            streams = hostpath.encode_blocks(
                times, vals.view(np.uint64), np.full(nb, START, np.int64),
                np.full(nb, T, np.int32), TimeUnit.SECOND, False)
            w = FilesetWriter(db.fs_root, "default", shard_id, START,
                              BLOCK, 0)
            for j, stream in zip(rows, streams):
                w.write_series(ids[j], b"", stream)
            w.close()
        db.open(START + BLOCK)
        ns.index.insert_many(ids, fields, np.full(S, START, np.int64))
        eng = Engine(db, resolve_tiers=False)
        qstart = START + 30 * 60 * NS
        qend = START + 48 * 3600 * NS - SAMP
        step = 2 * 60 * NS
        n_dp = S * T
        q = "sum by (host) (rate(reqs[30m]))"

        prev = {k: os.environ.get(k)
                for k in ("M3_TPU_QUERY_COMPILE", "M3_TPU_QUERY_SHARD")}
        try:
            os.environ["M3_TPU_QUERY_COMPILE"] = "1"

            def run(shard: int):
                os.environ["M3_TPU_QUERY_SHARD"] = str(shard)
                return eng.query_range(q, qstart, qend, step)[0]

            # correctness gate: sharded fused result vs the interpreter
            v_s = run(n_devices)
            os.environ["M3_TPU_QUERY_COMPILE"] = "0"
            v_i = eng.query_range(q, qstart, qend, step)[0]
            os.environ["M3_TPU_QUERY_COMPILE"] = "1"
            ok = (v_s.labels == v_i.labels
                  and np.array_equal(np.isnan(v_s.values),
                                     np.isnan(v_i.values))
                  and np.allclose(v_s.values, v_i.values, rtol=1e-9,
                                  atol=0, equal_nan=True))
            run(0)  # warm the single-device executable too
            sweep_ratios: list[str] = []
            headline = None
            for n_dev in [n for n in (2, 4, 8) if n <= n_devices]:
                run(n_dev)  # pay this mesh's compile outside the pairs
                pairs: list[tuple[float, float, float]] = []
                for _ in range(9):
                    t0 = time.perf_counter()
                    run(n_dev)
                    dt_s = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    run(0)
                    dt_1 = time.perf_counter() - t0
                    pairs.append((dt_1 / dt_s, n_dp / dt_s, n_dp / dt_1))
                pairs.sort(key=lambda p: p[0])
                med = pairs[len(pairs) // 2]
                sweep_ratios.append(f"{n_dev}dev:{med[0]:.2f}x")
                headline = med  # the widest mesh is the recorded headline
            _ratio, thr_s, thr_1 = headline
            _emit(f"#11 sharded query_range e2e {S} series x ~1.4k steps "
                  f"[sum-by(rate), {n_devices}-device series mesh vs "
                  f"single-device; sweep {' '.join(sweep_ratios)}]"
                  + ("" if ok else " (CORRECTNESS FAILED)"),
                  thr_s, thr_1)
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        db.close()


def _sparse_multigroup_setup(root, S, NB, T):
    """The #12 workload: a SPARSE high-cardinality multi-group namespace
    — S series x NB block volumes, a handful of points per (series,
    block) — plus the query that scans it end to end.  Shared by #12
    (pipelined vs serial) and #13 (paged ragged finalize vs the seed
    per-series concatenate path)."""
    from m3_tpu.encoding.m3tsz import hostpath
    from m3_tpu.query.engine import Engine
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.fileset import FilesetWriter
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils.xtime import TimeUnit

    NS = 10**9
    BLOCK = 3600 * NS
    START = 1_600_000_000 * NS
    db = Database(root, DatabaseOptions(
        n_shards=8, block_cache_entries=0))  # cold multi-group scans
    ns = db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                   block_size_ns=BLOCK),
        index=IndexOptions(enabled=True, block_size_ns=BLOCK),
        writes_to_commitlog=False, snapshot_enabled=False))
    ids = [b"reqs,host=h%04d,i=%05d" % (i % 100, i) for i in range(S)]
    fields = [[(b"__name__", b"reqs"), (b"host", b"h%04d" % (i % 100)),
               (b"i", b"%05d" % i)] for i in range(S)]
    by_shard: dict[int, list[int]] = {}
    for j, sid in enumerate(ids):
        by_shard.setdefault(ns.shard_set.lookup(sid), []).append(j)
    rng = np.random.default_rng(0)
    for b in range(NB):
        bs = START + b * BLOCK
        for shard_id, rows in by_shard.items():
            nb = len(rows)
            times = np.broadcast_to(
                bs + np.arange(T, dtype=np.int64) * (BLOCK // T),
                (nb, T)).copy()
            vals = rng.integers(1, 10, (nb, T)).astype(np.float64) \
                .cumsum(axis=1)
            streams = hostpath.encode_blocks(
                times, vals.view(np.uint64), np.full(nb, bs, np.int64),
                np.full(nb, T, np.int32), TimeUnit.SECOND, False)
            w = FilesetWriter(db.fs_root, "default", shard_id, bs,
                              BLOCK, 0)
            for j, stream in zip(rows, streams):
                w.write_series(ids[j], b"", stream)
            w.close()
    db.open(START + NB * BLOCK)
    ns.index.insert_many(ids, fields, np.full(S, START, np.int64))
    eng = Engine(db, resolve_tiers=False)
    q = "sum by (host) (sum_over_time(reqs[30m]))"
    qs = START + 30 * 60 * NS
    qe = START + NB * BLOCK - 60 * NS
    step = 30 * 60 * NS

    def run():
        return eng.query_range(q, qs, qe, step)[0]

    return run


def config12_pipelined_read():
    """Pipelined dataflow (ISSUE 14 / ROADMAP #2): end-to-end
    query_range over the sparse multi-group workload
    (_sparse_multigroup_setup) — the shape where the per-(shard, block)
    gather rung dominates the fetch (ROADMAP #3's sparse-series
    premise). Pipelined (M3_TPU_PIPELINE=1: per-group gathers prefetched
    on the executor behind the decode rung, columnar row-index gather,
    cache bookkeeping skipped while the block cache is disabled — this
    is a cold scan) vs the pinned serial seed path (=0: per-query
    merge-join walk, inline legs). Same pairing discipline as #9:
    interleaved pairs, MEDIAN pair reported, correctness gated on exact
    NaN masks + 1e-9 values BEFORE anything is emitted. On a multi-core
    host the executor adds genuine gather/decode wall-clock overlap on
    top of the columnar gather; this 1-core container measures the
    restructured dataflow alone."""
    import tempfile

    NS = 10**9
    BLOCK = 3600 * NS
    S = max(int(160_000 * _scale()), 2048)
    NB, T = 12, 4
    with tempfile.TemporaryDirectory() as root:
        run = _sparse_multigroup_setup(root, S, NB, T)
        n_dp = S * NB * T  # samples the query reads end to end

        prev = os.environ.get("M3_TPU_PIPELINE")
        try:
            os.environ["M3_TPU_PIPELINE"] = "1"
            v_p = run()
            os.environ["M3_TPU_PIPELINE"] = "0"
            v_s = run()
            ok = (v_p.labels == v_s.labels
                  and np.array_equal(np.isnan(v_p.values),
                                     np.isnan(v_s.values))
                  and np.allclose(v_p.values, v_s.values, rtol=1e-9,
                                  atol=0, equal_nan=True))
            pairs: list[tuple[float, float, float]] = []
            for _ in range(7):
                os.environ["M3_TPU_PIPELINE"] = "1"
                t0 = time.perf_counter()
                run()
                dt_p = time.perf_counter() - t0
                os.environ["M3_TPU_PIPELINE"] = "0"
                t0 = time.perf_counter()
                run()
                dt_s = time.perf_counter() - t0
                pairs.append((dt_s / dt_p, n_dp / dt_p, n_dp / dt_s))
            pairs.sort(key=lambda p: p[0])
            _ratio, thr_p, thr_s = pairs[len(pairs) // 2]
            _emit(f"#12 pipelined read_many->query e2e {S} series x "
                  f"{NB} blocks [sparse multi-group scan, pipelined vs "
                  f"serial]" + ("" if ok else " (CORRECTNESS FAILED)"),
                  thr_p, thr_s)
        finally:
            if prev is None:
                os.environ.pop("M3_TPU_PIPELINE", None)
            else:
                os.environ["M3_TPU_PIPELINE"] = prev


def config13_paged_memory():
    """Paged ragged columnar memory (ISSUE 15 / ROADMAP #3), two legs.

    (a) Write+read STEADY STATE at 1M live series (the default-scale
    lane runs the honest million): bulk write_many rounds into the
    page-pool buffer, one warm flush (ragged seal + length-bucketed
    encode), more live rounds, then a batched read merging fileset +
    live buffer — M3_TPU_PAGED=1 vs the pinned seed grow-array/
    per-series-concatenate path (=0), interleaved pairs, MEDIAN pair
    reported with RSS and p99 ingest-round wall time in the metric
    line.  The baseline's read rate is measured on a 1/64 series
    subset (its per-series cost is constant in subset size — the full
    quadratic scan takes hours, which is the point of this PR) and
    charged at that rate for the full read volume.

    (b) The #12 sparse multi-group e2e query shape with the PIPELINE
    armed on BOTH sides, toggling only M3_TPU_PAGED — isolating the
    ragged finalize (finish_read's per-series np.concatenate +
    merge_dedup tax, profiled ~15% of this path in PR 14) from the
    overlap win #12 already records. Correctness gated on exact NaN
    masks + 1e-9 values before anything is emitted."""
    import tempfile

    from m3_tpu.storage.database import Database
    from m3_tpu.storage.options import (
        DatabaseOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils.selfscrape import rss_bytes

    NS = 10**9
    BLOCK = 3600 * NS
    START = 1_600_000_000 * NS - (1_600_000_000 * NS) % (3600 * NS)
    # 1M live series AT THE DEFAULT 0.1 SCALE — the ROADMAP #3 acceptance
    # bench is the honest million, not a scaled stand-in
    S = max(int(10_000_000 * _scale()), 8192)
    ROUNDS = 2  # write rounds per block window

    def steady_state(root, paged: str):
        """One full side: write ROUNDS rounds into two block windows
        (flushing the first — live buffer + fileset merge on the read),
        then a batched read.  The PAGED side reads every live series;
        the grow-array baseline reads a 1/64 SUBSET — its per-series
        finalize cost is CONSTANT in subset size (each buffer.read masks
        the whole window log regardless), so the subset's datapoints/sec
        is the baseline's exact full-read rate, measured in minutes
        instead of the hours the quadratic full scan actually takes at
        1M live series.  Throughput combines the measured write wall
        with the full read volume at the measured read rate."""
        os.environ["M3_TPU_PAGED"] = paged
        db = Database(root, DatabaseOptions(n_shards=4,
                                            block_cache_entries=0))
        ns = db.create_namespace("default", NamespaceOptions(
            retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                       block_size_ns=BLOCK),
            writes_to_commitlog=False, snapshot_enabled=False))
        db.open(START)
        ids = [b"m%07d" % i for i in range(S)]
        tags = [b""] * S
        lat = []
        write_dp = 0
        t_write0 = time.perf_counter()
        for b in range(2):
            bs = START + b * BLOCK
            for r in range(ROUNDS):
                times = np.full(S, bs + (r + 1) * 60 * NS, np.int64)
                # per-series distinct values: the correctness digest
                # below sums them, so a read path that scrambles or
                # zeroes values across series cannot slip through
                vals = (np.arange(S, dtype=np.float64) * 0.5
                        + r).view(np.uint64)
                t0 = time.perf_counter()
                ns.write_many(ids, times, vals, tags)
                lat.append(time.perf_counter() - t0)
                write_dp += S
            if b == 0:  # warm flush: the seal + encode + volume write —
                # counted in the wall (throughput) but NOT in lat: p99
                # reports INGEST-round latency, not flush cost
                for shard in ns.shards.values():
                    shard.flush(bs)
        write_wall = time.perf_counter() - t_write0
        # RSS at end of ingest: the buffer-resident state (page pool vs
        # grow-arrays), before the read materializes result columns —
        # the two sides read different volumes (subset methodology), so
        # post-read RSS would not be comparable
        rss = rss_bytes()
        read_ids = ids if paged == "1" else ids[::64]
        t0 = time.perf_counter()
        out = ns.read_many(read_ids, START, START + 2 * BLOCK)
        read_wall = time.perf_counter() - t0
        read_dp = sum(len(t) for t, _ in out)
        read_rate = read_dp / read_wall if read_wall else 0.0
        full_read_dp = 2 * ROUNDS * S
        thr = (write_dp + full_read_dp) \
            / (write_wall + full_read_dp / max(read_rate, 1e-9))
        # correctness digest over the shared subset
        sub = out if paged != "1" else out[::64]
        digest = (sum(int(len(t)) for t, _ in sub),
                  sum(int(t.sum()) for t, _ in sub if len(t)),
                  sum(int(v.view(np.float64).sum()) for _, v in sub
                      if len(v)))
        db.close()
        return thr, float(np.quantile(lat, 0.99)), rss, digest

    # a 1M-series pair costs minutes; run interleaved pairs until the
    # wall budget is spent (≥1 pair always) and report the median pair
    budget_s = float(os.environ.get("M3_TPU_BENCH13_BUDGET_S", "360"))
    prev_paged = os.environ.get("M3_TPU_PAGED")
    try:
        with tempfile.TemporaryDirectory() as root:
            pairs = []
            meta = {}
            t_budget0 = time.perf_counter()
            for it in range(3):
                thr_p, p99_p, rss_p, dig_p = steady_state(
                    os.path.join(root, f"p{it}"), "1")
                thr_s, p99_s, rss_s, dig_s = steady_state(
                    os.path.join(root, f"s{it}"), "0")
                if dig_p != dig_s:
                    _emit("#13 paged 1M steady state (CORRECTNESS FAILED)",
                          0.0, 1.0)
                    return
                pairs.append((thr_p / thr_s, thr_p, thr_s))
                meta[thr_p / thr_s] = (p99_p, p99_s, rss_p, rss_s)
                if time.perf_counter() - t_budget0 > budget_s:
                    break
            pairs.sort(key=lambda p: p[0])
            ratio, thr_p, thr_s = pairs[len(pairs) // 2]
            p99_p, p99_s, rss_p, rss_s = meta[ratio]
            _emit(f"#13 paged write+read steady state {S} live series "
                  f"[p99 {p99_p * 1e3:.0f}ms vs {p99_s * 1e3:.0f}ms, RSS "
                  f"{rss_p >> 20}MB vs {rss_s >> 20}MB, paged vs "
                  f"grow-array; baseline read rate via 1/64 subset]",
                  thr_p, thr_s)
    finally:
        # steady_state exports the hatch per side: restore so a custom
        # --configs order never benchmarks later configs on the wrong path
        if prev_paged is None:
            os.environ.pop("M3_TPU_PAGED", None)
        else:
            os.environ["M3_TPU_PAGED"] = prev_paged

    # leg (b): the #12 shape, pipeline armed both sides, PAGED toggled
    S12 = max(int(160_000 * _scale()), 2048)
    NB, T = 12, 4
    with tempfile.TemporaryDirectory() as root:
        prev_pipe = os.environ.get("M3_TPU_PIPELINE")
        try:
            os.environ["M3_TPU_PAGED"] = "1"
            os.environ["M3_TPU_PIPELINE"] = "1"
            run = _sparse_multigroup_setup(root, S12, NB, T)
            n_dp = S12 * NB * T
            v_p = run()
            os.environ["M3_TPU_PAGED"] = "0"
            v_s = run()
            ok = (v_p.labels == v_s.labels
                  and np.array_equal(np.isnan(v_p.values),
                                     np.isnan(v_s.values))
                  and np.allclose(v_p.values, v_s.values, rtol=1e-9,
                                  atol=0, equal_nan=True))
            pairs = []
            for _ in range(7):
                os.environ["M3_TPU_PAGED"] = "1"
                t0 = time.perf_counter()
                run()
                dt_p = time.perf_counter() - t0
                os.environ["M3_TPU_PAGED"] = "0"
                t0 = time.perf_counter()
                run()
                dt_s = time.perf_counter() - t0
                pairs.append((dt_s / dt_p, n_dp / dt_p, n_dp / dt_s))
            pairs.sort(key=lambda p: p[0])
            _ratio, thr_p, thr_s = pairs[len(pairs) // 2]
            _emit(f"#13 ragged finalize e2e {S12} series x {NB} blocks "
                  f"[#12 shape, pipeline on, paged vs per-series "
                  f"concatenate]" + ("" if ok else " (CORRECTNESS FAILED)"),
                  thr_p, thr_s)
        finally:
            # RESTORE (not pop): an operator-pinned M3_TPU_PAGED must
            # survive into later configs of a custom --configs order
            if prev_paged is None:
                os.environ.pop("M3_TPU_PAGED", None)
            else:
                os.environ["M3_TPU_PAGED"] = prev_paged
            if prev_pipe is None:
                os.environ.pop("M3_TPU_PIPELINE", None)
            else:
                os.environ["M3_TPU_PIPELINE"] = prev_pipe


def config14_matcher_postings():
    """Device-compiled inverted index (ISSUE 16 / ROADMAP #4): boolean
    label-matcher evaluation over one packed segment at 1M and 10M
    terms — the fused ragged postings program (index/device.py: prefix-
    narrowed term resolution + ONE jit'd AND/OR/NOT combine over CSR
    rows) vs the PR-0 scalar walk reconstructed inline (per-term
    ``re.fullmatch`` over the full field vocabulary, pairwise sorted-
    array set ops).  Segment caches are cleared per evaluation so the
    device side pays matcher RESOLUTION every time; only the program-
    shape cache stays warm (that persistence is the design).  Pairing
    discipline as #11 (interleaved pairs, median pair reported), swept
    over the single-device and full virtual-mesh shard settings, and
    correctness-gated: the device doc-id sets must equal the scalar
    walk's exactly at every device count before anything is emitted."""
    import functools as _ft
    import re  # noqa: F401 - patterns below are compiled by the leaves

    import jax

    from m3_tpu.index import device, packed
    from m3_tpu.index import postings as P
    from m3_tpu.index.query import (
        ConjunctionQuery, DisjunctionQuery, NegationQuery, RegexpQuery,
        TermQuery,
    )
    from m3_tpu.index.segment import Document

    def scalar_leaf(seg, leaf):
        # the PR-0 walk: every term in the field pays a compiled-regex
        # fullmatch, every matched term pays a pairwise union
        if isinstance(leaf, TermQuery):
            return seg.postings_term(leaf.field_name, leaf.value)
        rx = leaf.compiled()
        out = P.EMPTY
        for t in seg.terms(leaf.field_name):
            if rx.fullmatch(t):
                out = P.union(out, seg.postings_term(leaf.field_name, t))
        return out

    def scalar_eval(seg, query):
        if isinstance(query, DisjunctionQuery):
            return _ft.reduce(P.union,
                              (scalar_leaf(seg, q) for q in query.queries),
                              P.EMPTY)
        pos = [q for q in query.queries
               if not isinstance(q, NegationQuery)]
        acc = _ft.reduce(P.intersect,
                         (scalar_leaf(seg, q) for q in pos))
        for q in query.queries:
            if isinstance(q, NegationQuery):
                acc = P.difference(acc, scalar_leaf(seg, q.inner))
        return acc

    def device_eval(seg, query):
        # resolution caches cleared: the device side re-pays term
        # bisect/narrowed regex scan per evaluation, like a cold query
        seg._regex_cache.clear()
        seg._term_idx_cache.clear()
        ids, reason = device.match(seg, query)
        if reason is not None:
            raise RuntimeError(f"unexpected fallback: {reason}")
        return ids

    n_devices = len(jax.devices())
    prev = {k: os.environ.get(k)
            for k in ("M3_TPU_DEVICE_OPS", "M3_TPU_QUERY_SHARD")}
    try:
        # pin the dispatch hatches: the bench isolates the two paths,
        # it does not re-test the work-threshold doctrine
        os.environ["M3_TPU_DEVICE_OPS"] = "1"
        for n in (max(int(1_000_000 * _scale()), 50_000),
                  max(int(10_000_000 * _scale()), 200_000)):
            seg = packed.build([
                Document(i, b"s%08d" % i,
                         [(b"pod", b"pod-%08d" % i),
                          (b"dc", b"dc-%d" % (i % 4)),
                          (b"app", b"app-%03d" % (i % 50))])
                for i in range(n)])
            # fixed-selectivity shapes (10k regex-matched terms at any
            # n >= 50k): conj regex+term, disj of regexes, conj with NOT
            queries = [
                ConjunctionQuery((RegexpQuery(b"pod", rb"pod-0000\d+"),
                                  TermQuery(b"dc", b"dc-1"))),
                DisjunctionQuery((RegexpQuery(b"pod", rb"pod-00001\d+"),
                                  RegexpQuery(b"pod", rb"pod-00002\d+"))),
                ConjunctionQuery((TermQuery(b"dc", b"dc-2"),
                                  NegationQuery(
                                      TermQuery(b"app", b"app-007")))),
            ]
            want = [scalar_eval(seg, q) for q in queries]
            shards = ["0"] + ([str(n_devices)] if n_devices > 1 else [])
            ok = True
            for shard in shards:  # gate at every device count
                os.environ["M3_TPU_QUERY_SHARD"] = shard
                got = [device_eval(seg, q) for q in queries]
                ok = ok and all(
                    np.array_equal(g.astype(np.int64), w.astype(np.int64))
                    for g, w in zip(got, want))
            n_dp = len(queries) * n
            sweep: list[str] = []
            headline = None
            for shard in shards:
                os.environ["M3_TPU_QUERY_SHARD"] = shard
                tag = "1dev" if shard == "0" else f"{shard}dev"
                # this mesh's executables were compiled by the gate pass;
                # interleaved pairs below measure steady-state serving
                pairs: list[tuple[float, float, float]] = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    for q in queries:
                        device_eval(seg, q)
                    dt_d = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    for q in queries:
                        scalar_eval(seg, q)
                    dt_h = time.perf_counter() - t0
                    pairs.append((dt_h / dt_d, n_dp / dt_d, n_dp / dt_h))
                pairs.sort(key=lambda p: p[0])
                med = pairs[len(pairs) // 2]
                sweep.append(f"{tag}:{med[0]:.2f}x")
                headline = med  # widest mesh is the recorded headline
            _ratio, thr_d, thr_h = headline
            _emit(f"#14 matcher postings {n}-term segment [3 boolean "
                  f"matcher queries, fused device program vs PR-0 scalar "
                  f"walk; sweep {' '.join(sweep)}]"
                  + ("" if ok else " (CORRECTNESS FAILED)"),
                  thr_d, thr_h)
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def config15_tier_resolution():
    """Cheapest-tier read resolution (ISSUE 18 / ROADMAP #2): a 30-day
    dashboard query_range at a 1h step, served from the complete 1h
    aggregated tier (resolve_read routes the fetch there) vs the same
    query pinned to the raw namespace (M3_TPU_TIER_RESOLVE=0) decoding
    every 2m raw sample. Both sides run the same engine over the same
    Database; the ratio isolates exactly what tier routing changes: the
    sample count decoded (30x fewer at 2m->1h). Pairing discipline as
    #11/#14 (interleaved pairs, median pair reported) and correctness-
    gated before emission: label sets equal, NaN masks element-
    identical, values within 1e-9 relative — the tiers hold LAST-at-
    mark identical series so the instant-selector grids must agree
    exactly."""
    import tempfile

    from m3_tpu.query.engine import Engine
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.options import (
        DatabaseOptions, NamespaceOptions, RetentionOptions,
    )

    NS = 10**9
    MIN_NS = 60 * NS
    HOUR = 3600 * NS
    DAY = 24 * HOUR
    SAMP = 2 * MIN_NS
    DAYS = 30
    S = max(int(200 * _scale()), 8)
    T_RAW = DAYS * DAY // SAMP       # 21600 raw samples per series
    T_AGG = DAYS * DAY // HOUR       # 720 aggregated samples per series
    START = 1_600_000_000 * NS
    END = START + DAYS * DAY
    with tempfile.TemporaryDirectory() as root:
        db = Database(root, DatabaseOptions(n_shards=8))
        db.create_namespace("default", NamespaceOptions(
            retention=RetentionOptions(retention_ns=40 * DAY,
                                       block_size_ns=2 * DAY),
            writes_to_commitlog=False, snapshot_enabled=False))
        db.create_namespace("aggregated_1h_365d", NamespaceOptions(
            retention=RetentionOptions(retention_ns=365 * DAY,
                                       block_size_ns=7 * DAY),
            aggregated_resolution_ns=HOUR, aggregated_complete=True,
            writes_to_commitlog=False, snapshot_enabled=False))
        db.open(now_ns=START)

        def value(i, t):
            # deterministic + LAST-at-mark: the raw value AT each hour
            # mark IS the tier's aggregate there, so both grids agree
            return float((t // SAMP + i * 37) % 1000)

        for ns_name, step_w in (("default", SAMP),
                                ("aggregated_1h_365d", HOUR)):
            entries = []
            for i in range(S):
                tags = [(b"host", b"h%04d" % i)]
                entries.extend(
                    (b"reqs", tags, t, value(i, t))
                    for t in range(START, END + 1, step_w))
            for lo in range(0, len(entries), 65536):
                db.write_batch(ns_name, entries[lo:lo + 65536])

        eng = Engine(db, "default", now_fn=lambda: END)
        n_dp = S * T_RAW  # raw samples the pinned path decodes

        def run():
            return eng.query_range("reqs", START + HOUR, END, HOUR)[0]

        prev = os.environ.get("M3_TPU_TIER_RESOLVE")
        try:
            os.environ.pop("M3_TPU_TIER_RESOLVE", None)
            v_t = run()  # tier-routed (warm)
            os.environ["M3_TPU_TIER_RESOLVE"] = "0"
            v_r = run()  # raw-pinned (warm)
            key = lambda d: sorted(d.items())  # noqa: E731
            ot = np.argsort([str(key(d)) for d in v_t.labels])
            orr = np.argsort([str(key(d)) for d in v_r.labels])
            tv, rv = v_t.values[ot], v_r.values[orr]
            ok = ([key(v_t.labels[i]) for i in ot]
                  == [key(v_r.labels[i]) for i in orr]
                  and np.array_equal(np.isnan(tv), np.isnan(rv))
                  and np.allclose(tv, rv, rtol=1e-9, atol=0,
                                  equal_nan=True))
            pairs: list[tuple[float, float, float]] = []
            for _ in range(5):
                os.environ.pop("M3_TPU_TIER_RESOLVE", None)
                t0 = time.perf_counter()
                run()
                dt_t = time.perf_counter() - t0
                os.environ["M3_TPU_TIER_RESOLVE"] = "0"
                t0 = time.perf_counter()
                run()
                dt_r = time.perf_counter() - t0
                pairs.append((dt_r / dt_t, n_dp / dt_t, n_dp / dt_r))
            pairs.sort(key=lambda p: p[0])
            _ratio, thr_t, thr_r = pairs[len(pairs) // 2]
            _emit(f"#15 tier-resolved 30d query_range @1h step, {S} series "
                  f"[aggregated 1h tier ({T_AGG}/series) vs raw 2m decode "
                  f"({T_RAW}/series)]"
                  + ("" if ok else " (CORRECTNESS FAILED)"),
                  thr_t, thr_r)
        finally:
            if prev is None:
                os.environ.pop("M3_TPU_TIER_RESOLVE", None)
            else:
                os.environ["M3_TPU_TIER_RESOLVE"] = prev


def config16_compute_overhead():
    """Device-compute observability overhead guard (this PR): the
    write+query hot path with the execute-telemetry ledger ARMED
    (every tracked jit_tracker exit attributing wall time into
    compute_stats — per-program execute histograms, the ranked program
    table, padding-waste records, eviction ground-truth bookkeeping)
    vs DISARMED (``compute_stats.arm(False)``: every record_* returns
    at the flag check — the seed-equivalent cost). Same pairing
    discipline as #7/#10: interleaved on/off pairs, median of per-pair
    ratios, flagged below 0.85.

    The workload is one run_once = a hot-buffer write burst (the
    ingest side the tracker must never tax) followed by compiled
    query_range evaluations on the cache-HIT path — the exact site
    where record_execute/record_waste fire per call."""
    import tempfile

    from m3_tpu.encoding.m3tsz import hostpath
    from m3_tpu.query.engine import Engine
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.fileset import FilesetWriter
    from m3_tpu.storage.options import (
        DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
    )
    from m3_tpu.utils import compute_stats
    from m3_tpu.utils.xtime import TimeUnit

    NS = 10**9
    BLOCK = 24 * 3600 * NS
    START = 1_600_000_000 * NS
    S = max(int(2_000 * _scale()), 200)
    SAMP = 300 * NS
    T = BLOCK // SAMP              # 288 samples per series
    W = max(int(60_000 * _scale()), 6_000)   # write burst per run
    with tempfile.TemporaryDirectory() as root:
        db = Database(root, DatabaseOptions(
            n_shards=4, block_cache_entries=100_000))
        ns = db.create_namespace("default", NamespaceOptions(
            retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                       block_size_ns=BLOCK),
            index=IndexOptions(enabled=True, block_size_ns=BLOCK),
            writes_to_commitlog=False, snapshot_enabled=False))
        ids = [b"reqs,host=h%03d,i=%05d" % (i % 50, i) for i in range(S)]
        fields = [[(b"__name__", b"reqs"), (b"host", b"h%03d" % (i % 50)),
                   (b"i", b"%05d" % i)] for i in range(S)]
        by_shard: dict[int, list[int]] = {}
        for j, sid in enumerate(ids):
            by_shard.setdefault(ns.shard_set.lookup(sid), []).append(j)
        rng = np.random.default_rng(0)
        for shard_id, rows in by_shard.items():
            nb = len(rows)
            times = np.broadcast_to(
                START + np.arange(T, dtype=np.int64) * SAMP, (nb, T)).copy()
            vals = rng.integers(1, 10, (nb, T)).astype(np.float64) \
                .cumsum(axis=1)
            streams = hostpath.encode_blocks(
                times, vals.view(np.uint64), np.full(nb, START, np.int64),
                np.full(nb, T, np.int32), TimeUnit.SECOND, False)
            w = FilesetWriter(db.fs_root, "default", shard_id, START,
                              BLOCK, 0)
            for j, stream in zip(rows, streams):
                w.write_series(ids[j], b"", stream)
            w.close()
        db.open(START + BLOCK)
        ns.index.insert_many(ids, fields, np.full(S, START, np.int64))
        eng = Engine(db, resolve_tiers=False)
        qstart = START + 30 * 60 * NS
        qend = START + BLOCK - SAMP
        step = 2 * 60 * NS
        q = "max by (host) (irate(reqs[30m]))"
        wtags = [(b"k", b"v")]
        wnames = [b"w%04d" % i for i in range(500)]
        n_dp = S * T  # samples each query reads

        def run_once() -> float:
            t0 = time.perf_counter()
            for i in range(W):  # hot-buffer ingest leg (active block)
                db.write_tagged("default", wnames[i % 500], wtags,
                                START + BLOCK + (i % 3600) * NS, float(i))
            for _ in range(2):  # compiled cache-HIT query leg
                eng.query_range(q, qstart, qend, step)
            return (W + 2 * n_dp) / (time.perf_counter() - t0)

        prev = os.environ.get("M3_TPU_QUERY_COMPILE")
        os.environ["M3_TPU_QUERY_COMPILE"] = "1"
        ratios: list[float] = []
        rate_on = rate_off = 0.0
        try:
            compute_stats.arm(True)
            run_once()  # warm: pays the plan + postings compiles once
            for _ in range(5):
                compute_stats.arm(True)
                on = run_once()
                compute_stats.arm(False)
                off = run_once()
                ratios.append(on / off)
                rate_on, rate_off = max(rate_on, on), max(rate_off, off)
        finally:
            compute_stats.arm(
                os.environ.get("M3_TPU_COMPUTE_STATS", "1") != "0")
            if prev is None:
                os.environ.pop("M3_TPU_QUERY_COMPILE", None)
            else:
                os.environ["M3_TPU_QUERY_COMPILE"] = prev
        db.close()
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    _emit("#16 write+query hot path w/ device-compute telemetry armed "
          "vs disarmed"
          + ("" if ratio >= 0.85 else " (OVERHEAD EXCEEDED)"),
          ratio * rate_off, rate_off)


def config17_wire_read():
    """Binary wire format (ISSUE 20 / ROADMAP #1): coordinator fanout
    read over real HTTP sockets — the packed read_batch frame (ragged
    CSR offsets + m3tsz-re-encoded sample columns, utils/wire) vs the
    legacy float64-JSON rows the M3_TPU_WIRE=json hatch pins. Bytes on
    the wire are read off the client-side net.bytes.{sent,recv}
    {flow=read_batch} counters (the satellite accounting this PR adds),
    so the ratio measures exactly what a fleet's NIC sees. Correctness
    is gated on EXACT sample equality (default precision is exact —
    m3tsz re-encode round-trips bit-identical float64) before anything
    is emitted; the emitted line carries the bytes reduction in the
    metric name and packed-vs-json fetch throughput as value/baseline,
    so both acceptance axes (>=3x fewer bytes, QPS no worse) live in
    one recorded line."""
    import tempfile

    from m3_tpu.client.http_conn import HTTPNodeConnection
    from m3_tpu.client.session import Session
    from m3_tpu.cluster import placement as pl
    from m3_tpu.cluster.kv import KVStore
    from m3_tpu.cluster.placement import Instance, initial_placement
    from m3_tpu.cluster.topology import ConsistencyLevel, TopologyMap
    from m3_tpu.services.dbnode import DBNodeService
    from m3_tpu.utils.ident import tags_to_id
    from m3_tpu.utils.instrument import default_registry

    NS = 10**9
    START = 1_600_000_000 * NS
    S = max(int(1_000 * _scale()), 100)
    T = 360  # one hour at 10s resolution
    n_dp = S * T

    reg = default_registry()

    def net_bytes() -> float:
        total = 0.0
        for d in ("sent", "recv"):
            c = reg.counters.get(
                (f"net.bytes.{d}", (("flow", "read_batch"),)))
            total += c.value if c is not None else 0.0
        return total

    prev = os.environ.get("M3_TPU_WIRE")
    with tempfile.TemporaryDirectory() as root:
        kv = KVStore()
        p = initial_placement([Instance("n0", isolation_group="g0")],
                              n_shards=4, replica_factor=1)
        p = pl.mark_available(p, "n0")
        pl.store_placement(kv, p)
        svc = DBNodeService(
            {"db": {"path": root, "n_shards": 4,
                    "namespaces": [{"name": "default"}]},
             "cluster": {"instance_id": "n0"}}, kv=kv)
        svc.db.open(START)
        svc.sync_placement()
        port = svc.api.serve(host="127.0.0.1", port=0)

        def set_endpoint(cur):
            cur.instances["n0"].endpoint = f"http://127.0.0.1:{port}"
            return cur

        pl.cas_update_placement(kv, set_endpoint)
        p, _ = pl.load_placement(kv)
        sess = Session(
            TopologyMap(p),
            {iid: HTTPNodeConnection(inst.endpoint)
             for iid, inst in p.instances.items()},
            write_consistency=ConsistencyLevel.ALL,
            read_consistency=ConsistencyLevel.ONE)
        # counter-style series: regular 10s cadence, small integer-ish
        # increments — the fleet shape m3tsz was built for
        sids = []
        for i in range(S):
            tags = [(b"host", b"h%04d" % i)]
            sids.append(tags_to_id(b"reqs", tags))
            for k in range(T):
                svc.db.write_tagged(
                    "default", b"reqs", tags, START + k * 10 * NS,
                    float((k * 7 + i) % 120))

        def fetch():
            return sess.fetch_many("default", sids, START,
                                   START + 3600 * NS)

        try:
            os.environ.pop("M3_TPU_WIRE", None)  # default: packed
            packed = fetch()  # warm
            b0 = net_bytes()
            packed = fetch()
            bytes_packed = net_bytes() - b0
            t0 = time.perf_counter()
            for _ in range(3):
                fetch()
            dt_packed = (time.perf_counter() - t0) / 3

            os.environ["M3_TPU_WIRE"] = "json"
            legacy = fetch()  # warm
            b0 = net_bytes()
            legacy = fetch()
            bytes_json = net_bytes() - b0
            t0 = time.perf_counter()
            for _ in range(3):
                fetch()
            dt_json = (time.perf_counter() - t0) / 3
        finally:
            if prev is None:
                os.environ.pop("M3_TPU_WIRE", None)
            else:
                os.environ["M3_TPU_WIRE"] = prev
            svc.api.shutdown()
            svc.db.close()

    ok = (len(packed) == len(legacy) == S
          and sum(len(t) for t, _ in packed) == n_dp
          and all(np.array_equal(ta, tb) and np.array_equal(va, vb)
                  for (ta, va), (tb, vb) in zip(packed, legacy)))
    bratio = bytes_json / bytes_packed if bytes_packed else 0.0
    _emit(f"#17 wire read_batch {S} series x {T} pts over HTTP "
          f"[packed CSR+m3tsz vs json, {bratio:.1f}x fewer bytes]"
          + ("" if ok else " (CORRECTNESS FAILED)")
          + ("" if bratio >= 3.0 else " (BYTES TARGET MISSED)"),
          n_dp / dt_packed, n_dp / dt_json)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs",
                    default="1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16,17")
    ap.add_argument("--record", default=None,
                    help="also append the JSON lines to this file")
    args = ap.parse_args(argv)
    fns = {"1": config1_codec_roundtrip, "2": config2_rollup,
           "3": config3_promql_rate_sum, "4": config4_regex_postings,
           "5": config5_sharded_quantile, "6": config6_read_many,
           "7": config7_tracing_overhead, "8": config8_write_batch,
           "9": config9_query_compile, "10": config10_profiler_overhead,
           "11": config11_sharded_query, "12": config12_pipelined_read,
           "13": config13_paged_memory, "14": config14_matcher_postings,
           "15": config15_tier_resolution,
           "16": config16_compute_overhead, "17": config17_wire_read}
    failed = []
    for c in args.configs.split(","):
        c = c.strip()
        try:
            fns[c]()
        except Exception as e:  # noqa: BLE001 - one config must not stop
            # the rest; the run still fails (exit code) once they are done
            failed.append(c)
            print(json.dumps({"metric": f"#{c} failed: {e}"[:200],
                              "value": 0.0, "unit": "M datapoints/sec",
                              "vs_baseline": 0.0}), flush=True)
    if args.record:
        # append, as documented: a partial-config run (--configs 9) must
        # not clobber the other configs' recorded history
        with open(args.record, "a") as f:
            for line in _RECORD:
                f.write(json.dumps(line) + "\n")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())

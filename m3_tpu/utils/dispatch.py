"""Device-dispatch policy for the hot serving paths.

Each serving-path op (aggregator flush reductions, postings bitmap algebra,
PromQL temporal math) has a numpy host implementation and a jax device
kernel. This module decides which runs:

- ``M3_TPU_DEVICE_OPS=1`` forces the device path (tests use this to assert
  kernel parity), ``=0`` forces host numpy;
- otherwise the device path runs when an accelerator backend is live and
  the workload is big enough to amortize dispatch (~O(100us) per call), the
  same batching rationale as the reference's insert-queue batching
  (/root/reference/src/dbnode/storage/shard_insert_queue.go).

Counters record which path executed so tests (and /metrics) can verify the
device path actually serves production queries — the round-1 failure mode
was device kernels that only tests invoked.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from collections import Counter

from m3_tpu.utils import trace

counters: Counter = Counter()

# below this many elements the fixed dispatch cost dominates on any backend
DEFAULT_DEVICE_THRESHOLD = 16_384

_accel_cache: bool | None = None


def _accelerator_present() -> bool:
    """True when JAX's default backend is an accelerator.

    A service initialises the backend at start (utils/backend.init), so
    on a serving path this is a cached read; library use outside a
    service initialises lazily here, on first use."""
    global _accel_cache
    if _accel_cache is None:
        import jax

        _accel_cache = jax.default_backend() != "cpu"
    return _accel_cache


def use_device(n: int, threshold: int = DEFAULT_DEVICE_THRESHOLD) -> bool:
    force = os.environ.get("M3_TPU_DEVICE_OPS")
    if force == "1":
        return True
    if force == "0":
        return False
    return n >= threshold and _accelerator_present()


def record(op: str, device: bool) -> None:
    counters[f"{op}[{'device' if device else 'host'}]"] += 1


def next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length() if n > 2 else max(n, 1)


def next_bucket(n: int, multiple: int = 1) -> int:
    """Smallest of {2^k, 3*2^(k-1)} >= n: half-octave shape buckets.

    The whole-query compiler pads its matrix axes with these instead of
    plain powers of two — worst-case padding waste drops from 2x to
    1.33x (the padded cells are real work for a fused [S, T] program)
    while the compile count per axis stays O(log), just with twice the
    constant.

    ``multiple`` > 1 additionally requires the bucket to divide evenly
    (the sharded compute plane pads its series axis to a multiple of the
    mesh size so every device owns the same row count): prefer the next
    ladder rung that divides WHEN it costs no more than rounding the
    bucket up to the multiple (keeps 2/3-smooth mesh sizes on the
    ladder); otherwise round up — never more than one ``multiple`` of
    extra padding, and deterministic per (n, multiple) either way, so
    shape-bucket reuse is unaffected."""
    p = next_pow2(n)
    half = 3 * p // 4
    b = half if 0 < n <= half else p
    if multiple > 1 and b % multiple:
        r = b + (-b) % multiple
        c = max(b, 2)
        for _ in range(4):
            # next half-octave rung: 2^k -> 3*2^(k-1), 3*2^(k-1) -> 2^(k+1)
            c = 3 * c // 2 if (c & (c - 1)) == 0 else 4 * c // 3
            if c % multiple == 0 and c <= r:
                return c
        return r
    return b


# -- jit/plan-cache telemetry ------------------------------------------------
#
# Every XLA entry point on the serving paths is a jax.jit'd function keyed
# on static args (shape bucket, unit, impl). Whether a call HIT that plan
# cache or paid a trace+compile is the number the whole-query-compilation
# work (ROADMAP #2) will be judged against — so the dispatch layer records
# it: jit_tracker() wraps a call site, diffs the jitted function's cache
# size across the call, and lands hit/miss counters plus a compile-time
# histogram in the metrics registry (visible on /metrics, the self-scrape
# and the exporter).

_jit_scopes: dict = {}


def _jit_scope(op: str, result: str):
    key = (op, result)
    sc = _jit_scopes.get(key)
    if sc is None:
        from m3_tpu.utils.instrument import default_registry

        sc = default_registry().root_scope("compute").subscope(
            "jit", op=op, result=result)
        _jit_scopes[key] = sc
    return sc


# per-jitted-function last-seen executable-cache size: the eviction
# ground truth. An entry that disappears between tracked calls
# (jax.clear_caches(), a donated/evicted executable) shrinks the cache,
# which would make the next call's size diff under-report a re-trace as
# a hit — comparing against the LAST SEEN size catches both the
# eviction (compute_jit_evictions{op}) and the subsequent re-compile.
# Weak keys: a dropped program factory must not pin its executables.
_last_sizes: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


class LaunchLedger:
    """Device idle charged to what the host was doing, in process.

    Every launch on the served paths is a ``jit_tracker`` block, which
    holds the call and the wait for its result, so while no block is
    open anywhere in the process nothing runs on the device or is on its
    way there. The ledger counts the open blocks under a lock of its
    own. Where the count falls to 0 a gap starts; at the next launch (0
    to 1) the gap is added to ``compute_launch_gap_seconds{stage}``,
    split over the launching thread's stages innermost first
    (``Tracer.stages_between``: ``tools/trace_gaps.py``'s rule). Where a
    block closes the count (1 to 0), its time since the 0-to-1 edge is
    added to ``compute_launch_outstanding_seconds``: dispatch, device
    time, transfers, a compile, and the wait for the interpreter to take
    the result. Gap and outstanding tile the time from the first launch
    on. The counters ride the thread's batched publish of its stages;
    ``gap_ns`` and ``outstanding_ns`` are the same totals, current to
    the last edge, for the device-trace session (utils/backend.py)."""

    def __init__(self, tracer: "trace.Tracer | None" = None):
        self._tracer = tracer
        self._lock = threading.Lock()
        self._open = 0
        self._edge_ns = 0           # the last 0-to-1 or 1-to-0 edge
        self.gap_ns = 0
        self.outstanding_ns = 0

    def opened(self) -> None:
        with self._lock:
            self._open += 1
            if self._open > 1:
                return
            now = time.perf_counter_ns()
            since, self._edge_ns = self._edge_ns, now
            if not since:           # the first launch: no gap before it
                return
            self.gap_ns += now - since
        tr = self._tracer or trace.default_tracer()
        tr.add_counters([("compute.launch_gap_seconds", (("stage", name),),
                          s) for name, s in tr.stages_between(
                              since, now).items()])

    def closed(self) -> None:
        with self._lock:
            self._open -= 1
            if self._open:
                return
            now = time.perf_counter_ns()
            since, self._edge_ns = self._edge_ns, now
            self.outstanding_ns += now - since
        (self._tracer or trace.default_tracer()).add_counters(
            [("compute.launch_outstanding_seconds", (), (now - since) / 1e9)])


# the process's ledger: every jit_tracker block opens and closes it
launches = LaunchLedger()


class jit_tracker:
    """`with jit_tracker("m3tsz_decode", jitted_fn, sig="..."): ...` —
    records compute.jit_calls{op,result=hit|miss}; on a miss, the
    trace+compile wall time into compute.jit_compile_seconds{op}; on a
    hit (with a ``sig``), the wall into compute.execute_seconds{op,sig}
    and the per-program ledger (utils/compute_stats). The block holds
    the call AND the read that waits for its result (``np.asarray``,
    ``block_until_ready``): JAX returns at the enqueue, so a block
    without the wait times the dispatch, not the program. The jitted
    function's private executable cache (`_cache_size`) is the ground
    truth; entries that vanished since the last tracked call bump
    compute_jit_evictions{op}. Each block opens and closes the process's
    ``launches`` ledger."""

    def __init__(self, op: str, jitted_fn, sig: str | None = None):
        self.op = op
        self.sig = sig
        self._fn = jitted_fn
        self._size_fn = jitted_fn._cache_size
        # ground-truth compile outcome of the wrapped call, readable after
        # the with-block (the whole-query compiler keys its plan-cache
        # hit/miss accounting off this rather than guessing)
        self.miss = False
        # wrapped-block wall time, readable after the with-block (the
        # explain `device` block attributes it per query)
        self.seconds = 0.0

    def missed(self) -> bool:
        """Inside the block, after the call has returned: whether it
        compiled (a stage that dispatched a miss closes as *.compile)."""
        return self._size_fn() > self._before

    def __enter__(self):
        self._before = self._size_fn()
        last = _last_sizes.get(self._fn)
        if last is not None and self._before < last:
            from m3_tpu.utils import compute_stats

            compute_stats.record_evictions(self.op, last - self._before)
        launches.opened()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = self.seconds = time.perf_counter() - self._t0
        launches.closed()
        after = self._size_fn()
        miss = self.miss = after > self._before
        _last_sizes[self._fn] = after
        result = "miss" if miss else "hit"
        counters[f"jit_{self.op}[{result}]"] += 1
        sc = _jit_scope(self.op, result)
        sc.counter("calls")
        if exc and exc[0] is not None:
            return False  # the call raised: no execute/compile attribution
        from m3_tpu.utils import compute_stats

        if miss:
            # the whole block IS the compile on a miss (execution time is
            # noise next to trace+lower+compile)
            sc.observe("compile_seconds", dt)
            compute_stats.record_compile(self.op, self.sig or "default", dt)
        else:
            compute_stats.record_execute(self.op, self.sig or "default", dt)
        return False

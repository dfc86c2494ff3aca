"""Instrumentation: metrics scopes + structured logging.

Role parity with the reference's x/instrument (tally scopes + zap logging):
a process-local metrics registry with counters/gauges/timers/histograms and
tagged subscopes, exportable in strict Prometheus text format (served on
/metrics by the services, `# TYPE` metadata + escaped labels + safe
NaN/Inf), plus a minimal structured logger. The platform monitors itself
with the same metric model it stores: the coordinator's self-scrape loop
(utils/selfscrape.py) ingests this registry into the `_m3_system`
namespace so p99s over these histograms are one PromQL query away.

Exemplars: every histogram observation made inside a SAMPLED trace pins a
``(trace_id, value, timestamp)`` exemplar to the bucket it landed in —
last observation wins per bucket, so each bucket of a latency histogram
always points at a recent representative trace. The OpenMetrics-style render
(``render_openmetrics``, served on ``/metrics?format=openmetrics`` —
explicit opt-in only) emits them as
``# {trace_id="..."} value ts`` suffixes on `_bucket` lines, so a p99
bucket is one /debug/traces lookup away from its stitched trace. The
plain Prometheus render is byte-compatible with PR 4 (no exemplars —
that format has no syntax for them).
"""

from __future__ import annotations

import bisect
import json
import math
import sys
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field


@dataclass
class _Counter:
    value: float = 0.0


@dataclass
class _Gauge:
    value: float = 0.0


@dataclass
class _Timer:
    count: int = 0
    total_s: float = 0.0
    max_s: float = 0.0


# log-bucketed histogram bounds: powers of two from ~1us to ~64s — 14
# buckets per 1000x decade, enough that p99 interpolation error stays
# under ~2x anywhere in the range while one histogram costs ~30 ints
DEFAULT_BUCKETS: tuple = tuple(2.0 ** e for e in range(-20, 7))
# bounds for COUNT-shaped distributions (batch sizes, fan-out widths):
# powers of two from 1 to ~1M
COUNT_BUCKETS: tuple = tuple(float(2 ** e) for e in range(0, 21))


# bound lazily (first traced observation), then a straight thread-local
# read per observation: an in-function `import` here costs ~2us per call,
# which at per-datapoint seam frequency is the difference between
# exemplars being free and blowing the bench-#7 overhead guard
_tracer_tl = None


def _active_exemplar_trace() -> str | None:
    """The trace id an observation should pin as its exemplar: the
    thread's active SAMPLED span context, or None outside a recorded
    trace (one thread-local read — the histogram hot paths call this per
    observation)."""
    global _tracer_tl
    if _tracer_tl is None:
        from m3_tpu.utils import trace

        _tracer_tl = trace.default_tracer()._tl
    ctx = getattr(_tracer_tl, "ctx", None)
    if ctx is None or not ctx.sampled or not ctx.span_id:
        return None
    return ctx.trace_id


@dataclass
class _Histogram:
    bounds: tuple = DEFAULT_BUCKETS
    counts: list = field(default_factory=lambda: [0] * (len(DEFAULT_BUCKETS) + 1))
    sum: float = 0.0
    count: int = 0
    # per-bucket (trace_id, value, unix_seconds) exemplar, last-wins;
    # allocated on the first traced observation so untraced histograms
    # stay three scalars + a counts list
    exemplars: list | None = None

    def observe_locked(self, value: float,
                       exemplar_trace: str | None = None) -> None:
        """Record one observation; caller holds the registry lock."""
        i = bisect.bisect_left(self.bounds, value)
        self.counts[i] += 1
        self.sum += value
        self.count += 1
        if exemplar_trace is not None:
            if self.exemplars is None:
                self.exemplars = [None] * len(self.counts)
            self.exemplars[i] = (exemplar_trace, value, time.time())

    def cumulative(self) -> list[tuple[float, int]]:
        """[(upper_bound, cumulative_count)] incl. the +Inf bucket."""
        out = []
        running = 0
        for ub, c in zip(self.bounds, self.counts):
            running += c
            out.append((ub, running))
        out.append((math.inf, running + self.counts[-1]))
        return out

    def quantile(self, q: float) -> float:
        """Interpolated quantile (the histogram_quantile rule) — used by
        in-process consumers (slow-query thresholds, tests)."""
        if self.count == 0:
            return math.nan
        rank = q * self.count
        running = 0
        prev_ub = 0.0
        for ub, c in zip(self.bounds, self.counts):
            if running + c >= rank:
                if c == 0:
                    return ub
                return prev_ub + (ub - prev_ub) * (rank - running) / c
            running += c
            prev_ub = ub
        return self.bounds[-1]


class Scope:
    """Tagged metrics scope; subscope() adds tags, prefix joins with '.'"""

    def __init__(self, registry: "MetricsRegistry", prefix: str = "",
                 tags: tuple = ()):
        self._registry = registry
        self._prefix = prefix
        self._tags = tags

    def _name(self, name: str) -> str:
        return f"{self._prefix}.{name}" if self._prefix else name

    def subscope(self, prefix: str, **tags) -> "Scope":
        merged = tuple(sorted({**dict(self._tags), **tags}.items()))
        return Scope(self._registry, self._name(prefix), merged)

    def counter(self, name: str, delta: float = 1.0) -> None:
        with self._registry._lock:
            self._registry.counters[(self._name(name), self._tags)].value += delta

    def gauge(self, name: str, value: float) -> None:
        with self._registry._lock:
            self._registry.gauges[(self._name(name), self._tags)].value = value

    def timer(self, name: str):
        """Context manager recording a duration."""
        scope = self

        class _Ctx:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                dt = time.perf_counter() - self.t0
                with scope._registry._lock:
                    t = scope._registry.timers[(scope._name(name), scope._tags)]
                    t.count += 1
                    t.total_s += dt
                    t.max_s = max(t.max_s, dt)

        return _Ctx()

    def _histogram_locked(self, name: str, bounds: tuple | None):
        """Get-or-create under the registry lock; `bounds` only applies on
        creation (first binding wins, like Prometheus client libs)."""
        reg = self._registry
        key = (self._name(name), self._tags)
        h = reg.histograms.get(key)
        if h is None:
            h = _Histogram(bounds=tuple(bounds)) if bounds else _Histogram()
            if bounds:
                h.counts = [0] * (len(h.bounds) + 1)
            reg.histograms[key] = h
        return h

    def observe(self, name: str, value: float,
                bounds: tuple | None = None) -> None:
        """One histogram observation (seconds for latency seams; pass
        COUNT_BUCKETS bounds for size-shaped distributions). Unlike a
        timer, the distribution survives: p50/p99 are derivable from the
        `_bucket` exposition instead of only count/total/max. Observed
        inside a sampled trace, the bucket pins a (trace_id, value)
        exemplar."""
        ex = _active_exemplar_trace()
        with self._registry._lock:
            self._histogram_locked(name, bounds).observe_locked(value, ex)

    def histogram(self, name: str):
        """Context manager observing a duration into the histogram."""
        scope = self

        class _Ctx:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                scope.observe(name, time.perf_counter() - self.t0)

        return _Ctx()

    def histogram_handle(self, name: str, bounds: tuple | None = None):
        """Pre-resolved observe(value) callable for HOT paths: the metric
        key is built once here and the closure binds everything it touches,
        so each observation is a bisect (outside the lock — bounds are
        immutable) plus three adds under a bare acquire/release. Scope
        .observe rebuilds the key string and enters a context manager per
        call — measurably slower on per-datapoint seams. Exemplar-capable
        like observe: a sampled trace context pins its trace_id to the
        bucket (one thread-local read when no trace is active)."""
        from m3_tpu.utils import trace

        reg = self._registry
        with reg._lock:
            h = self._histogram_locked(name, bounds)
        acquire = reg._lock.acquire
        release = reg._lock.release
        h_bounds = h.bounds
        counts = h.counts
        _bisect = bisect.bisect_left
        # the tracer's raw thread-local, read inline (no function call):
        # per-datapoint seams pay one getattr for exemplar capability
        tracer_tl = trace.default_tracer()._tl
        _getattr = getattr
        _now = time.time

        def observe(value: float) -> None:
            i = _bisect(h_bounds, value)
            ctx = _getattr(tracer_tl, "ctx", None)
            acquire()
            counts[i] += 1
            h.sum += value
            h.count += 1
            if ctx is not None and ctx.sampled and ctx.span_id:
                if h.exemplars is None:
                    h.exemplars = [None] * len(counts)
                h.exemplars[i] = (ctx.trace_id, value, _now())
            release()

        return observe


# ---------------------------------------------------------------------------
# snapshot hooks + bounded-queue saturation monitors
# ---------------------------------------------------------------------------

# hooks run at the top of every MetricsRegistry.snapshot() — the one
# choke point every consumer (the /metrics render, the telemetry
# exporter, the _m3_system self-scrape) already goes through — so
# pull-model telemetry (queue depths, lock-wait deltas) is always fresh
# at read time without its own refresh loops. Guarded against
# re-entrancy: a hook that snapshots a registry runs with hooks off.
_hooks_lock = threading.Lock()
_snapshot_hooks: list = []
_hooks_tl = threading.local()


def register_snapshot_hook(fn) -> None:
    """Register fn(registry) to run before every registry snapshot."""
    with _hooks_lock:
        if fn not in _snapshot_hooks:
            _snapshot_hooks.append(fn)


def _run_snapshot_hooks(registry: "MetricsRegistry") -> None:
    if getattr(_hooks_tl, "running", False):
        return
    _hooks_tl.running = True
    try:
        _refresh_queue_monitors(registry)
        with _hooks_lock:
            hooks = list(_snapshot_hooks)
        for fn in hooks:
            try:
                fn(registry)
            except Exception:  # noqa: BLE001 - telemetry hooks must never
                pass           # break a scrape
    finally:
        _hooks_tl.running = False


class _MonitorFns:
    """The callables of one registration. With an `owner`, the STRONG
    reference to this holder lives on the owner object itself and the
    registry keeps only a weakref — the registered closures almost
    always close over the owner, so holding them strongly here would pin
    an abandoned owner (and its buffers/sockets) for process lifetime.
    Owner + holder + closures form a cycle; the gc collects it whole,
    the weakref dies, and the monitor prunes itself."""

    __slots__ = ("depth_fn", "capacity", "drops_fn", "__weakref__")

    def __init__(self, depth_fn, capacity, drops_fn):
        self.depth_fn = depth_fn
        self.capacity = capacity
        self.drops_fn = drops_fn


class _QueueMonitor:
    __slots__ = ("name", "tags", "fns_ref", "registry")

    def __init__(self, name, tags, fns_ref, registry):
        self.name = name
        self.tags = tags
        self.fns_ref = fns_ref  # () -> _MonitorFns | None (None = dead)
        self.registry = registry


_monitors_lock = threading.Lock()
_queue_monitors: list[_QueueMonitor] = []


def monitor_queue(name: str, depth_fn, capacity=None, drops_fn=None,
                  registry: "MetricsRegistry | None" = None, owner=None,
                  **tags):
    """Register a bounded queue/ring with the saturation plane: its
    depth/capacity/drop gauges (``queue_depth{queue=...}`` etc.) refresh
    at every registry snapshot, so /metrics, the exporter and the
    ``_m3_system`` self-scrape all see saturation without the queue
    owner pushing anything. `capacity` is an int or a callable;
    `drops_fn` (optional) reads a monotonic dropped-items counter.
    Passing `owner` ties the registration's lifetime to that object:
    the callables are anchored ON the owner and the registry keeps only
    a weakref, so an owner dropped without close() is still collectable
    (closures over `self` would otherwise pin it here forever) and its
    monitor prunes itself at the next refresh. Returns an unregister
    callable. m3lint's ``inv-queue-gauge`` invariant holds every bounded
    queue in the tree to this registration."""
    import weakref

    fns = _MonitorFns(depth_fn, capacity, drops_fn)
    if owner is not None:
        anchors = getattr(owner, "_m3_monitor_fns", None)
        if anchors is None:
            anchors = []
            try:
                owner._m3_monitor_fns = anchors
            except AttributeError:  # __slots__ owner: fall back to a
                anchors = None      # strong (immortal) registration
        if anchors is not None:
            anchors.append(fns)
            fns_ref = weakref.ref(fns)
        else:
            fns_ref = (lambda f=fns: f)
    else:
        fns_ref = (lambda f=fns: f)
    mon = _QueueMonitor(name, tuple(sorted(tags.items())), fns_ref, registry)
    with _monitors_lock:
        _queue_monitors.append(mon)

    def unregister():
        with _monitors_lock:
            try:
                _queue_monitors.remove(mon)
            except ValueError:
                pass

    return unregister


def _refresh_queue_monitors(registry: "MetricsRegistry") -> None:
    dead: list[_QueueMonitor] = []
    with _monitors_lock:
        monitors = list(_queue_monitors)
    for mon in monitors:
        target = mon.registry if mon.registry is not None \
            else _default_registry
        if target is not registry:
            continue
        fns = mon.fns_ref()
        if fns is None:  # owner (and its anchored callables) collected
            dead.append(mon)
            continue
        try:
            depth = float(fns.depth_fn())
            cap = fns.capacity() if callable(fns.capacity) else fns.capacity
            drops = float(fns.drops_fn()) if fns.drops_fn is not None else None
        except Exception:  # noqa: BLE001 - a mid-teardown queue must not
            continue       # break the scrape
        scope = Scope(registry, "queue",
                      tuple(sorted((("queue", mon.name), *mon.tags))))
        scope.gauge("depth", depth)
        if cap is not None:
            scope.gauge("capacity", float(cap))
        if drops is not None:
            scope.gauge("dropped", drops)
    if dead:
        with _monitors_lock:
            for mon in dead:
                try:
                    _queue_monitors.remove(mon)
                except ValueError:
                    pass


def _prom_name(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def _escape_label(v) -> str:
    """Prometheus label-value escaping: backslash, quote, newline."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_number(value) -> str:
    """Exposition-safe value: NaN / +Inf / -Inf tokens, floats via repr."""
    v = float(value)
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 2 ** 53:
        return str(int(v))
    return repr(v)


class MetricsRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counters: dict = defaultdict(_Counter)
        self.gauges: dict = defaultdict(_Gauge)
        self.timers: dict = defaultdict(_Timer)
        self.histograms: dict = defaultdict(_Histogram)

    def root_scope(self, prefix: str = "") -> Scope:
        return Scope(self, prefix)

    def record_many(self, observations, increments) -> None:
        """Histogram observations [(name, tags, value)] and counter
        increments [(name, tags, delta)] under ONE acquisition of the
        lock: the stage clock (utils/trace.py) publishes a request's
        stages together when its outermost span closes, not two
        acquisitions a stage from inside the request."""
        with self._lock:
            for name, tags, value in observations:
                self.histograms[(name, tags)].observe_locked(value)
            for name, tags, delta in increments:
                self.counters[(name, tags)].value += delta

    def merge_histogram(self, name: str, tags: tuple, bounds: tuple,
                        counts_delta, sum_delta: float) -> None:
        """Fold externally-accumulated histogram DELTAS into this
        registry (the lock-wait profiler publishes through here: its hot
        path must not touch the registry lock, so it accumulates raw and
        merges at snapshot time). First merge binds the bounds."""
        with self._lock:
            key = (name, tags)
            h = self.histograms.get(key)
            if h is None:
                h = _Histogram(bounds=tuple(bounds))
                h.counts = [0] * (len(h.bounds) + 1)
                self.histograms[key] = h
            for i, c in enumerate(counts_delta):
                if i < len(h.counts):
                    h.counts[i] += c
            h.sum += sum_delta
            h.count += sum(counts_delta)

    def snapshot(self):
        """Point-in-time copy of every metric, one lock acquisition:
        (counters, gauges, timers, histograms) dicts keyed (name, tags).
        Histogram entries are (bounds, counts, sum, count) tuples.
        Registered snapshot hooks (queue-saturation gauges, lock-wait
        publishing) run first, so every consumer reads fresh values."""
        _run_snapshot_hooks(self)
        with self._lock:
            counters = {k: c.value for k, c in self.counters.items()}
            gauges = {k: g.value for k, g in self.gauges.items()}
            timers = {k: (t.count, t.total_s, t.max_s)
                      for k, t in self.timers.items()}
            hists = {k: (h.bounds, list(h.counts), h.sum, h.count)
                     for k, h in self.histograms.items()}
        return counters, gauges, timers, hists

    def render_prometheus(self) -> bytes:
        """Strict Prometheus text exposition: `# TYPE` metadata per family,
        escaped label values, NaN/±Inf rendered as exposition tokens, and
        histograms as cumulative `_bucket`/`_sum`/`_count` series. The
        device-dispatch counters (utils/dispatch) are merged in so the
        XLA / native / scalar path choice is visible on /metrics."""
        out: list[str] = []
        typed: set[str] = set()

        def fmt(name, tags, value, mtype=None):
            name = _prom_name(name)
            if mtype is not None and name not in typed:
                typed.add(name)
                out.append(f"# TYPE {name} {mtype}")
            if tags:
                t = ",".join(f'{k}="{_escape_label(v)}"' for k, v in tags)
                out.append(f"{name}{{{t}}} {_fmt_number(value)}")
            else:
                out.append(f"{name} {_fmt_number(value)}")

        counters, gauges, timers, hists = self.snapshot()
        for (name, tags), v in sorted(counters.items()):
            fmt(name, tags, v, "counter")
        for (name, tags), v in sorted(gauges.items()):
            fmt(name, tags, v, "gauge")
        for (name, tags), (count, total_s, max_s) in sorted(timers.items()):
            fmt(name + "_count", tags, count, "counter")
            fmt(name + "_total_seconds", tags, round(total_s, 9), "counter")
            fmt(name + "_max_seconds", tags, round(max_s, 9), "gauge")
        for (name, tags), (bounds, counts, hsum, hcount) in sorted(hists.items()):
            h = _Histogram(bounds, counts, hsum, hcount)
            base = _prom_name(name)
            if base not in typed:
                typed.add(base)
                out.append(f"# TYPE {base} histogram")
            for ub, cum in h.cumulative():
                le = "+Inf" if math.isinf(ub) else _fmt_number(ub)
                fmt(name + "_bucket", (*tags, ("le", le)), cum)
            fmt(name + "_sum", tags, round(hsum, 9))
            fmt(name + "_count", tags, hcount)
        # device-dispatch path counters ("op" or "op[path]" keys)
        try:
            from m3_tpu.utils import dispatch

            items = sorted(dispatch.counters.items())
        except Exception:  # noqa: BLE001 - never break /metrics
            items = []
        for key, v in items:
            op, _, path = key.partition("[")
            tags = (("op", op),)
            if path:
                tags += (("path", path.rstrip("]")),)
            fmt("m3_dispatch_ops_total", tags, v, "counter")
        return ("\n".join(out) + "\n").encode()

    def render_openmetrics(self) -> bytes:
        """OpenMetrics-style text exposition: the Prometheus render plus
        histogram-bucket EXEMPLARS (`# {trace_id="..."} value ts` suffix
        per the OpenMetrics exemplar syntax) and the `# EOF` terminator.
        Served only on explicit opt-in (`/metrics?format=openmetrics`):
        family names match the Prometheus render exactly (counters keep
        their PR-4 names rather than gaining the `_total` suffix strict
        OpenMetrics mandates) so dashboards and the `_m3_system`
        self-scrape series line up across both formats — which is also
        why this render must never be Accept-negotiated to a stock
        scraper expecting spec-strict OpenMetrics."""
        # exemplars are not in snapshot() (its consumers - selfscrape,
        # the prometheus render - have no use for them), so take one
        # dedicated locked pass here, capturing bounds alongside
        with self._lock:
            exemplars = {}
            bounds_of = {}
            for k, h in self.histograms.items():
                if h.exemplars:
                    exemplars[k] = list(h.exemplars)
                    bounds_of[k] = h.bounds
        # per rendered-line prefix (`name_bucket{tags,le="..."` — the exact
        # string render_prometheus emits before the space): the exemplar
        # pinned to that bucket. Tags participate in the key, so two
        # histograms sharing a family name cannot cross-pollinate.
        by_prefix: dict[str, tuple] = {}
        for (name, tags), ex in exemplars.items():
            bounds = bounds_of[(name, tags)]
            tag_str = ",".join(f'{k}="{_escape_label(v)}"' for k, v in tags)
            for slot, pinned in enumerate(ex):
                if pinned is None:
                    continue
                le = "+Inf" if slot >= len(bounds) \
                    else _fmt_number(bounds[slot])
                labels = (tag_str + "," if tag_str else "") + f'le="{le}"'
                by_prefix[f"{_prom_name(name)}_bucket{{{labels}}}"] = pinned
        base = self.render_prometheus().decode()
        out: list[str] = []
        for line in base.splitlines():
            brace = line.find("{")
            pinned = by_prefix.get(line[: line.rfind(" ")]) \
                if brace > 0 else None
            if pinned is not None:
                trace_id, value, ts = pinned
                line = (f'{line} # {{trace_id="{_escape_label(trace_id)}"}} '
                        f"{_fmt_number(value)} {ts:.3f}")
            out.append(line)
        return ("\n".join(out) + "\n# EOF\n").encode()


_default_registry = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    return _default_registry


class Logger:
    """Structured JSON-lines logger (the zap role)."""

    LEVELS = {"debug": 10, "info": 20, "warn": 30, "error": 40}

    def __init__(self, name: str = "", level: str = "info", stream=None):
        self.name = name
        self.level = self.LEVELS[level]
        self.stream = stream if stream is not None else sys.stderr
        self.fields: dict = {}

    def with_fields(self, **fields) -> "Logger":
        lg = Logger(self.name, stream=self.stream)
        lg.level = self.level
        lg.fields = {**self.fields, **fields}
        return lg

    def _log(self, level: str, msg: str, **fields) -> None:
        if self.LEVELS[level] < self.level:
            return
        rec = {
            "ts": time.time(),
            "level": level,
            "logger": self.name,
            "msg": msg,
            **self.fields,
            **fields,
        }
        print(json.dumps(rec, default=str), file=self.stream, flush=True)

    def debug(self, msg: str, **fields) -> None:
        self._log("debug", msg, **fields)

    def info(self, msg: str, **fields) -> None:
        self._log("info", msg, **fields)

    def warn(self, msg: str, **fields) -> None:
        self._log("warn", msg, **fields)

    def error(self, msg: str, **fields) -> None:
        self._log("error", msg, **fields)

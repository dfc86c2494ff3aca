"""Always-on profiling & saturation plane: sampling profiler, lock-wait
profiling, and stall watchdogs.

The platform can trace a request (utils/trace), explain a plan
(query/explain) and price a tenant (utils/tenantlimits) — this module
covers where time goes when nothing is computing: lock waits under the
consensus persist-before-ack sections, saturated bounded queues silently
dropping, periodic loops wedged mid-cycle. Three cooperating pieces, all
cheap enough to leave armed in production:

**Sampling profiler** — a daemon thread walks ``sys._current_frames()``
at a jittered ~19 Hz (prime-ish, so it cannot phase-lock with 10 ms/1 s
periodic work) and aggregates folded stacks per THREAD ROLE (thread
names normalized: ``repair-daemon``, ``telemetry-export-coordinator``,
``ThreadPoolExecutor``, ...) into a bounded table. Served as
collapsed-stack text (the flamegraph.pl wire format) and top-N self-time
JSON at ``/debug/profile`` on all four services. ``M3_TPU_PROFILE``
arms it at service start (a number > 1 sets the rate); POST
``/debug/profile {"enabled": true}`` toggles it live. The telemetry
exporter ships table snapshots with the PR-6 cursor discipline (a
snapshot ships at most once; no new samples, nothing shipped).

**Lock-wait profiling** — ``M3_TPU_LOCK_PROFILE=1`` (read at ``m3_tpu``
import, like the shadow-lock checker) swaps ``threading.Lock/RLock``
for wrappers keyed by CONSTRUCTION SITE (lockcheck's lock-class
semantics: every ``Shard._lock`` is one class however many shards
exist). The fast path is a non-blocking try-acquire plus one counter
increment — an uncontended acquire pays no clock read at all. A failed
trylock IS the contention signal: only then does the wrapper time the
blocking acquire and land the wait in the per-class histogram, so
"which lock burns our p99" is a measured table — the consensus fsync
sections ROADMAP #2 wants to dissolve become a list, not a waiver file.
The accumulated per-class histograms publish into the metrics registry
as ``lock_wait_seconds{cls=...}`` at every snapshot, so
``histogram_quantile`` over lock-wait works on /metrics, via the
exporter, AND through the ``_m3_system`` self-scrape.

**Stall watchdog** — every periodic loop (aggregator flush, repair
cycle, raft tick, service ticks, self-scrape, exporter drain) registers
a heartbeat and beats it once per iteration. A checker thread flags
loops whose last beat is older than ``miss_factor`` intervals: one
stall tracepoint + counter per EPISODE (recovery clears, a new wedge
fires again), with the wedged thread's captured stack in the event ring
— the post-mortem a hung loop never writes for itself.

Composability: the profiled lock wrapper wraps whatever
``threading.Lock`` currently is, so under ``M3_TPU_LOCK_CHECK`` the
shadow-lock checker keeps seeing every blocking acquisition (ordering
edges are recorded by the inner checked lock).
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
import threading
import time
import traceback
from collections import deque

from m3_tpu.utils import lockcheck
from m3_tpu.utils.instrument import (
    DEFAULT_BUCKETS,
    Scope,
    default_registry,
    register_snapshot_hook,
)

# raw (never-instrumented) lock factory: the profiler's own bookkeeping
# must not recurse through the profiled wrappers it implements
_RAW_LOCK = lockcheck._REAL_LOCK


DEFAULT_HZ = 19.0  # prime-ish; jittered further per sleep


def _truthy(value: str | None) -> bool:
    return lockcheck.env_enabled(value)


# ---------------------------------------------------------------------------
# sampling profiler
# ---------------------------------------------------------------------------

_ROLE_RE = re.compile(r"[-_]?\d+(?:_\d+)?$")


def thread_role(name: str) -> str:
    """Normalize a thread name to its ROLE: strip instance counters so
    every worker of a kind folds into one row (``Thread-12 (worker)`` ->
    ``Thread``, ``ThreadPoolExecutor-0_3`` -> ``ThreadPoolExecutor``,
    ``repair-daemon`` stays itself)."""
    head = (name or "").partition(" ")[0]
    return _ROLE_RE.sub("", head) or "thread"


def _fold_frame(frame, max_depth: int = 48) -> str:
    """Root-first ``file:func;file:func;...`` folded stack for one live
    frame (the collapsed-stack convention flamegraph tooling eats)."""
    parts: list[str] = []
    f = frame
    while f is not None and len(parts) < max_depth:
        code = f.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:{code.co_name}")
        f = f.f_back
    parts.reverse()
    return ";".join(parts)


class SamplingProfiler:
    """Wall-clock sampling profiler over ``sys._current_frames()``.

    The aggregate table is bounded (``max_stacks`` distinct
    (role, folded-stack) keys): on overflow the current minimum-count
    entry is evicted and its samples land in ``evicted_samples`` — the
    table can mis-attribute the cold tail, never grow without bound."""

    def __init__(self, hz: float = DEFAULT_HZ, max_stacks: int = 2048,
                 registry=None, clock=time.monotonic):
        self.hz = float(hz)
        self.max_stacks = int(max_stacks)
        self.clock = clock
        self.enabled = False
        self.samples = 0           # sampling passes taken
        self.evicted_samples = 0   # samples lost to table eviction
        self._table: dict[tuple[str, str], int] = {}
        self._lock = _RAW_LOCK()
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None
        self._registry = registry
        self._observe_pass = None  # lazy histogram handle

    def _scope(self):
        return (self._registry or default_registry()).root_scope("profiler")

    # -- recording --

    def _record(self, role: str, folded: str, count: int = 1) -> None:
        key = (role, folded)
        with self._lock:
            cur = self._table.get(key)
            if cur is not None:
                self._table[key] = cur + count
                return
            if len(self._table) >= self.max_stacks:
                # evict the current cold-tail entry; its samples stay
                # accounted (evicted_samples) so totals never lie
                victim = min(self._table, key=self._table.get)
                self.evicted_samples += self._table.pop(victim)
            self._table[key] = count

    def sample_once(self) -> int:
        """One sampling pass over every live thread (the sampler thread
        itself excluded). Returns threads sampled."""
        if self._observe_pass is None:
            self._observe_pass = self._scope().histogram_handle(
                "sample_seconds")
        t0 = time.perf_counter()
        names = {t.ident: t.name for t in threading.enumerate()}
        own = threading.get_ident()
        n = 0
        for tid, frame in sys._current_frames().items():
            if tid == own:
                continue
            self._record(thread_role(names.get(tid, "")), _fold_frame(frame))
            n += 1
        with self._lock:
            self.samples += 1
        self._observe_pass(time.perf_counter() - t0)
        return n

    # -- rendering --

    def collapsed(self) -> str:
        """The whole table in collapsed-stack text: one
        ``role;frame;frame count`` line per aggregated stack."""
        with self._lock:
            items = sorted(self._table.items(),
                           key=lambda kv: -kv[1])
        return "\n".join(f"{role};{folded} {count}"
                         for (role, folded), count in items) + \
            ("\n" if items else "")

    def top(self, n: int = 20) -> list[dict]:
        """Top-N frames by SELF samples (leaf of the folded stack), with
        total (anywhere-on-stack) samples alongside."""
        self_c: dict[str, int] = {}
        total_c: dict[str, int] = {}
        with self._lock:
            items = list(self._table.items())
        for (_role, folded), count in items:
            frames = folded.split(";")
            if not frames:
                continue
            self_c[frames[-1]] = self_c.get(frames[-1], 0) + count
            for fr in set(frames):
                total_c[fr] = total_c.get(fr, 0) + count
        ranked = sorted(self_c.items(), key=lambda kv: -kv[1])[:n]
        return [{"frame": fr, "self": c, "total": total_c.get(fr, c)}
                for fr, c in ranked]

    def status(self) -> dict:
        with self._lock:
            stacks = len(self._table)
        return {"enabled": self.enabled, "hz": self.hz,
                "samples": self.samples, "stacks": stacks,
                "evicted_samples": self.evicted_samples,
                "max_stacks": self.max_stacks}

    def export_since(self, cursor: int) -> tuple[dict | None, int]:
        """Cursor-disciplined snapshot for the telemetry exporter: the
        current table summary if sampling advanced past `cursor`, else
        None — each sampling epoch ships at most once."""
        if self.samples <= cursor:
            return None, cursor
        return ({"samples": self.samples, "top": self.top(50),
                 "evicted_samples": self.evicted_samples}, self.samples)

    def reset(self) -> None:
        with self._lock:
            self._table.clear()
            self.samples = 0
            self.evicted_samples = 0

    # -- lifecycle --

    def start(self, hz: float | None = None) -> None:
        if hz is not None and hz > 0:
            self.hz = float(hz)
        self.enabled = True
        self._wake.set()
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            import random

            rng = random.Random(os.getpid())
            while not self._stop.is_set():
                if not self.enabled:
                    # parked: clear the (set-by-start) wake flag so the
                    # wait actually blocks, re-checking enabled after
                    # the clear so a concurrent start() is never missed
                    self._wake.clear()
                    if not self.enabled:
                        self._wake.wait(0.25)
                    continue
                try:
                    self.sample_once()
                except Exception:  # noqa: BLE001 - a torn frame walk must
                    pass           # never kill the sampler
                # jittered period: mean 1/hz, +-25% so the sampler can't
                # alias against the platform's own periodic loops
                period = 1.0 / max(self.hz, 0.1)
                self._stop.wait(period * (0.75 + 0.5 * rng.random()))

        self._thread = threading.Thread(target=loop, name="profiler-sampler",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop sampling AND the thread (tests); `enabled = False` alone
        parks the thread for a cheap runtime toggle."""
        self.enabled = False
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(2.0)
            self._thread = None
        self._wake.clear()


# ---------------------------------------------------------------------------
# lock-wait profiling
# ---------------------------------------------------------------------------

MAX_LOCK_CLASSES = 512  # construction sites are code-defined; the cap is
#                         a backstop against lock-constructing loops

_stats_lock = _RAW_LOCK()
_lock_classes: dict[str, "_LockClass"] = {}


class _LockClass:
    """Accumulated wait statistics for one lock construction site."""

    __slots__ = ("site", "acquisitions", "contended", "wait_total_s",
                 "wait_max_s", "hist_counts", "hist_sum",
                 "_pub_counts", "_pub_sum", "_pub_acq", "_pub_contended")

    def __init__(self, site: str):
        self.site = site
        # racy (GIL-interleaved +=) by design: the uncontended fast path
        # must not take any lock; occasional lost increments are noise
        self.acquisitions = 0
        self.contended = 0
        self.wait_total_s = 0.0
        self.wait_max_s = 0.0
        self.hist_counts = [0] * (len(DEFAULT_BUCKETS) + 1)
        self.hist_sum = 0.0
        # publish cursors: deltas since the last registry publish
        self._pub_counts = [0] * (len(DEFAULT_BUCKETS) + 1)
        self._pub_sum = 0.0
        self._pub_acq = 0
        self._pub_contended = 0

    def note_wait(self, dt: float) -> None:
        """One contended acquisition (the trylock failed) — exact,
        under the stats lock (contention is rare; that's the point)."""
        i = bisect.bisect_left(DEFAULT_BUCKETS, dt)
        with _stats_lock:
            self.contended += 1
            self.wait_total_s += dt
            if dt > self.wait_max_s:
                self.wait_max_s = dt
            self.hist_counts[i] += 1
            self.hist_sum += dt

    def to_doc(self) -> dict:
        with _stats_lock:
            contended, total, mx = (self.contended, self.wait_total_s,
                                    self.wait_max_s)
        return {"site": self.site, "acquisitions": self.acquisitions,
                "contended": contended,
                "wait_total_ms": round(total * 1e3, 3),
                "wait_max_ms": round(mx * 1e3, 3)}


def _lock_class(site: str) -> _LockClass:
    cls = _lock_classes.get(site)
    if cls is not None:
        return cls
    with _stats_lock:
        cls = _lock_classes.get(site)
        if cls is None:
            if len(_lock_classes) >= MAX_LOCK_CLASSES:
                site = "other"
                cls = _lock_classes.get(site)
                if cls is not None:
                    return cls
            cls = _lock_classes[site] = _LockClass(site)
    return cls


def _construction_site() -> str:
    """file:line of the profiled lock's construction (the lock-class
    key lockcheck uses), skipping this module's own frames."""
    f = sys._getframe(1)
    while f is not None and f.f_globals.get("__name__") == __name__:
        f = f.f_back
    if f is None:  # pragma: no cover - interpreter teardown
        return "<unknown>"
    return f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno}"


class _ProfiledLockBase:
    """Fast path: one non-blocking C acquire + one counter increment —
    no clock reads on an uncontended acquire. A failed trylock IS the
    contention signal; only then is the blocking acquire timed and the
    wait recorded. (A profiled RLock's reentrant re-acquire also takes
    the trylock fast path — the owner's acquire(False) succeeds.)

    Composing over the shadow-lock checker: the wrapper wraps whatever
    ``threading.Lock`` currently is, so a checked inner lock still
    records held-stack state on every acquire; ordering EDGES are only
    recorded on the contended path (the uncontended trylock is edge-free
    by lockcheck's own trylock rule) — arm the checker without the
    profiler when hunting ordering bugs."""

    _reentrant = False

    def __init__(self):
        site = _construction_site()
        self._cls = _lock_class(site)
        inner = self._inner_factory()
        # hand a checked inner lock OUR construction site (it would
        # otherwise key every lock in the tree to this module's line)
        if hasattr(inner, "site"):
            inner.site = site
        self._inner = inner
        self._try = inner.acquire
        self._release = inner.release

    def acquire(self, blocking: bool = True, timeout: float = -1):
        if self._try(False):
            self._cls.acquisitions += 1
            return True
        if not blocking:
            return False
        return self._slow(timeout)

    def _slow(self, timeout: float = -1):
        t0 = time.perf_counter()
        ok = self._try(True, timeout)
        dt = time.perf_counter() - t0
        cls = self._cls
        cls.acquisitions += 1
        # the wait is recorded whether or not the acquire ultimately
        # succeeded: a bounded acquire that times out spent exactly
        # timeout seconds stuck behind the holder — the WORST waits —
        # and skipping it would rank a perpetually-timing-out gate as
        # uncontended
        cls.note_wait(dt)
        return ok

    def release(self):
        self._release()

    def __enter__(self):
        # flattened fast path: `with lock:` is the hot idiom
        if self._try(False):
            self._cls.acquisitions += 1
        else:
            self._slow()
        return self

    def __exit__(self, *exc):
        self._release()
        return False

    def locked(self):
        return self._inner.locked()

    def _at_fork_reinit(self):
        self._inner._at_fork_reinit()


class ProfiledLock(_ProfiledLockBase):
    pass


class ProfiledRLock(_ProfiledLockBase):
    _reentrant = True

    # Condition support: delegate the save/restore protocol so
    # cond.wait() on a recursively-held profiled RLock releases every
    # level (exactly the lockcheck wrapper's reasoning)
    def _is_owned(self):
        return self._inner._is_owned()

    def _release_save(self):
        return self._inner._release_save()

    def _acquire_restore(self, saved):
        self._inner._acquire_restore(saved)


_install_lock = _RAW_LOCK()
_installed = False
_prev_factories: tuple | None = None


def install_lock_profiling() -> None:
    """Swap threading.Lock/RLock for the timed wrappers, wrapping
    whatever the factories currently are (so the shadow-lock checker,
    if installed first, keeps its ordering edges). Locks created BEFORE
    install stay raw — m3_tpu/__init__ installs under
    ``M3_TPU_LOCK_PROFILE`` so service-lifetime locks are all covered;
    the metrics registry's own lock (created at instrument import) stays
    deliberately raw, keeping the hottest lock overhead-free."""
    global _installed, _prev_factories
    with _install_lock:
        if _installed:
            return
        _installed = True
        _prev_factories = (threading.Lock, threading.RLock)
        prev_lock, prev_rlock = _prev_factories

        class _Lock(ProfiledLock):
            _inner_factory = staticmethod(prev_lock)

        class _RLock(ProfiledRLock):
            _inner_factory = staticmethod(prev_rlock)

        threading.Lock = _Lock
        threading.RLock = _RLock


def uninstall_lock_profiling() -> None:
    """Restore the previous factories (test isolation)."""
    global _installed, _prev_factories
    with _install_lock:
        if not _installed:
            return
        _installed = False
        threading.Lock, threading.RLock = _prev_factories
        _prev_factories = None


def lock_profiling_installed() -> bool:
    return _installed


def lock_classes(min_contended: int = 0) -> list[dict]:
    """The contended-lock table, hottest (total wait) first."""
    with _stats_lock:
        classes = list(_lock_classes.values())
    docs = [c.to_doc() for c in classes]
    docs = [d for d in docs if d["contended"] >= min_contended]
    docs.sort(key=lambda d: -d["wait_total_ms"])
    return docs


def reset_lock_stats() -> None:
    with _stats_lock:
        _lock_classes.clear()


def _publish_lock_stats(registry) -> None:
    """Snapshot hook: fold per-class wait-histogram DELTAS into the
    default metrics registry (``lock_wait_seconds{cls=...}`` plus
    acquisition/contention counters), so /metrics, the exporter and the
    ``_m3_system`` self-scrape all see lock waits as first-class
    histograms — histogram_quantile over lock-wait end to end."""
    if registry is not default_registry():
        return  # lock stats are process-global; publish once, to the
        #         process registry (private test registries stay clean)
    with _stats_lock:
        deltas = []
        for cls in _lock_classes.values():
            dc = [a - b for a, b in zip(cls.hist_counts, cls._pub_counts)]
            dsum = cls.hist_sum - cls._pub_sum
            dacq = cls.acquisitions - cls._pub_acq
            dcont = cls.contended - cls._pub_contended
            if not any(dc) and dacq <= 0:
                continue
            cls._pub_counts = list(cls.hist_counts)
            cls._pub_sum = cls.hist_sum
            cls._pub_acq = cls.acquisitions
            cls._pub_contended = cls.contended
            deltas.append((cls.site, dc, dsum, dacq, dcont))
    for site, dc, dsum, dacq, dcont in deltas:
        tags = (("cls", site),)
        if any(dc):
            registry.merge_histogram("lock.wait_seconds", tags,
                                     DEFAULT_BUCKETS, dc, dsum)
        scope = Scope(registry, "lock", tags)
        if dacq > 0:
            scope.counter("acquisitions", dacq)
        if dcont > 0:
            scope.counter("contended", dcont)


register_snapshot_hook(_publish_lock_stats)


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------

class Heartbeat:
    """One registered periodic loop's handle: call ``beat()`` once per
    iteration; ``close()`` unregisters (service shutdown)."""

    __slots__ = ("name", "interval_s", "last_beat", "beats", "stalled",
                 "stalls", "recovered", "tid", "_wd")

    def __init__(self, name: str, interval_s: float, wd: "Watchdog"):
        self.name = name
        self.interval_s = float(interval_s)
        self.last_beat = wd.clock()
        self.beats = 0
        self.stalled = False
        self.stalls = 0
        self.recovered = 0
        self.tid: int | None = None
        self._wd = wd

    def beat(self) -> None:
        wd = self._wd
        with wd._lock:
            self.last_beat = wd.clock()
            self.beats += 1
            if self.tid is None:
                self.tid = threading.get_ident()
            if self.stalled:
                self.stalled = False
                self.recovered += 1
                wd._on_recover(self)

    def close(self) -> None:
        self._wd.unregister(self.name)

    def to_doc(self, now: float) -> dict:
        return {"loop": self.name, "interval_s": self.interval_s,
                "beats": self.beats,
                "last_beat_age_s": round(now - self.last_beat, 3),
                "stalled": self.stalled, "stalls": self.stalls,
                "recovered": self.recovered}


class Watchdog:
    """Flags periodic loops that miss ``miss_factor`` intervals: one
    stall event per episode (tracepoint + counter + the wedged thread's
    captured stack), recovery clears so the next wedge fires again."""

    EVENT_RING = 256

    def __init__(self, miss_factor: float = 3.0, registry=None,
                 clock=time.monotonic, check_period_s: float = 0.25):
        self.miss_factor = float(miss_factor)
        self.clock = clock
        self.check_period_s = check_period_s
        self._lock = _RAW_LOCK()
        self._loops: dict[str, Heartbeat] = {}
        # the watchdog's own evidence ring: deliberately outside the
        # saturation plane — overwriting old stall events is its design,
        # and the plane's implementation must not feed back into itself
        # m3lint: disable=inv-queue-gauge
        self._events: deque[dict] = deque(maxlen=self.EVENT_RING)
        self._registry = registry
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def _scope(self):
        return (self._registry or default_registry()).root_scope("watchdog")

    # -- registration --

    def register(self, name: str, interval_s: float) -> Heartbeat:
        """Register (or re-register: latest wins) a periodic loop."""
        hb = Heartbeat(name, interval_s, self)
        with self._lock:
            self._loops[name] = hb
        return hb

    def unregister(self, name: str) -> None:
        with self._lock:
            self._loops.pop(name, None)

    # -- checking --

    def _capture_stack(self, tid: int | None) -> str:
        if tid is None:
            return ""
        frame = sys._current_frames().get(tid)
        if frame is None:
            return ""
        return "".join(traceback.format_stack(frame))

    def _on_recover(self, hb: Heartbeat) -> None:
        # called under self._lock from Heartbeat.beat
        self._events.append({"kind": "recover", "loop": hb.name,
                             "t_unix": time.time()})

    def check_once(self, now: float | None = None) -> list[dict]:
        """One pass over registered loops; returns NEW stall events."""
        from m3_tpu.utils import trace

        now = now if now is not None else self.clock()
        fired: list[dict] = []
        with self._lock:
            loops = list(self._loops.values())
        for hb in loops:
            with self._lock:
                age = now - hb.last_beat
                # floor the interval: a 0s-interval registration (tests,
                # tick-driven monitors) must not read as instantly stalled
                if hb.stalled or \
                        age <= max(hb.interval_s, 0.1) * self.miss_factor:
                    continue
                # fires ONCE per episode: stalled stays set until a beat
                hb.stalled = True
                hb.stalls += 1
                tid = hb.tid
            ev = {"kind": "stall", "loop": hb.name, "t_unix": time.time(),
                  "age_s": round(age, 3),
                  "stack": self._capture_stack(tid)}
            with self._lock:
                self._events.append(ev)
            fired.append(ev)
            self._scope().subscope("loop", loop=hb.name).counter("stalls")
            with trace.span(trace.WATCHDOG_STALL, loop=hb.name,
                            age_s=round(age, 3)):
                pass
        return fired

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def status(self) -> dict:
        now = self.clock()
        with self._lock:
            loops = [hb.to_doc(now) for hb in self._loops.values()]
            events = list(self._events)[-32:]
        return {"armed": self._thread is not None,
                "miss_factor": self.miss_factor,
                "loops": sorted(loops, key=lambda d: d["loop"]),
                "recent_events": events}

    def reset(self) -> None:
        with self._lock:
            self._loops.clear()
            self._events.clear()

    # -- lifecycle --

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.check_period_s):
                try:
                    self.check_once()
                except Exception:  # noqa: BLE001 - the watchdog must
                    pass           # outlive anything it watches

        self._thread = threading.Thread(target=loop, name="stall-watchdog",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(2.0)
            self._thread = None


# ---------------------------------------------------------------------------
# process singletons + the /debug/profile surface
# ---------------------------------------------------------------------------

_default_profiler = SamplingProfiler()
_default_watchdog = Watchdog()


def default_profiler() -> SamplingProfiler:
    return _default_profiler


def default_watchdog() -> Watchdog:
    return _default_watchdog


def register_heartbeat(name: str, interval_s: float) -> Heartbeat:
    """Register a loop on the process watchdog (services use this)."""
    return _default_watchdog.register(name, interval_s)


def _rss_bytes() -> int:
    # the shared reader (incl. the darwin getrusage fallback): both
    # observability surfaces must report the same RSS
    from m3_tpu.utils.selfscrape import rss_bytes

    return rss_bytes()


def env_hz(value: str | None) -> float | None:
    """M3_TPU_PROFILE -> sampling rate: truthy enables at the default
    rate; a number > 1 sets the rate; falsy/None disables."""
    if not _truthy(value):
        return None
    try:
        n = float(value.strip())
    except (ValueError, AttributeError):
        return DEFAULT_HZ
    return n if n > 1 else DEFAULT_HZ


def arm_from_env(service: str = "") -> bool:
    """Service-entrypoint hook: arm the sampler + watchdog checker when
    ``M3_TPU_PROFILE`` asks for it. Idempotent; returns armed-ness."""
    hz = env_hz(os.environ.get("M3_TPU_PROFILE"))
    if hz is None:
        return False
    _default_profiler.start(hz)
    _default_watchdog.start()
    return True


def profile_payload(top_n: int = 20) -> dict:
    """The /debug/profile JSON body, shared by all four services."""
    return {
        "profiler": {**_default_profiler.status(),
                     "top": _default_profiler.top(top_n)},
        "locks": {"installed": _installed,
                  "classes": lock_classes(min_contended=1)[:top_n]},
        "watchdog": _default_watchdog.status(),
        "rss_bytes": _rss_bytes(),
    }


def handle_debug_profile(method: str, q: dict, body: bytes):
    """Shared route handler -> (status, payload, content_type).

    GET  ?format=collapsed      collapsed-stack text (flamegraph wire)
    GET  [?top=N]               JSON: profiler top-N, contended locks,
                                watchdog loops + recent stall events
    POST {"enabled": bool, "hz": f, "reset": bool}   runtime toggle
    """
    prof = _default_profiler
    if method == "POST":
        doc = json.loads(body or b"{}")
        if doc.get("reset"):
            prof.reset()
            reset_lock_stats()
        if "hz" in doc:
            prof.hz = max(0.1, float(doc["hz"]))
        if "enabled" in doc:
            if bool(doc["enabled"]):
                prof.start()
                _default_watchdog.start()
            else:
                prof.enabled = False
        return 200, json.dumps(prof.status()).encode(), "application/json"
    fmt = (q.get("format", [""])[0] if q else "").lower()
    if fmt == "collapsed":
        return 200, prof.collapsed().encode(), "text/plain; charset=utf-8"
    top_n = int(q.get("top", ["20"])[0]) if q else 20
    return (200, json.dumps(profile_payload(top_n)).encode(),
            "application/json")


class DebugServer:
    """Minimal HTTP debug surface for services without one (aggregator,
    kvd): /debug/profile, /metrics, /health. Daemon-threaded."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from urllib.parse import parse_qs, urlparse

        outer = self

        class Handler(BaseHTTPRequestHandler):
            def _do(self, method):
                u = urlparse(self.path)
                q = parse_qs(u.query)
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                try:
                    status, payload, ctype = outer._route(
                        method, u.path, q, body)
                except Exception as e:  # noqa: BLE001 - debug surface
                    status, ctype = 400, "application/json"
                    payload = json.dumps({"error": str(e)}).encode()
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):  # noqa: N802
                self._do("GET")

            def do_POST(self):  # noqa: N802
                self._do("POST")

            def log_message(self, *a):
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        self.port = self._server.server_address[1]
        threading.Thread(target=self._server.serve_forever,
                         name="debug-http", daemon=True).start()

    def _route(self, method, path, q, body):
        if path == "/debug/profile":
            return handle_debug_profile(method, q, body)
        if path == "/debug/compute":
            from m3_tpu.utils import compute_stats

            return compute_stats.handle_debug_compute(method, q, body)
        if path == "/metrics":
            return (200, default_registry().render_prometheus(),
                    "text/plain; version=0.0.4")
        if path == "/health":
            return 200, b'{"ok":true}', "application/json"
        return 404, b'{"error":"unknown path"}', "application/json"

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()  # release the listening socket fd


def serve_debug_from_env() -> DebugServer | None:
    """Start the standalone debug surface when ``M3_TPU_DEBUG_PORT`` is
    set (aggregator/kvd processes; rig arms it). Returns the server (or
    None), never raises — a busy port must not kill a service."""
    raw = os.environ.get("M3_TPU_DEBUG_PORT")
    if not raw:
        return None
    try:
        return DebugServer(port=int(raw))
    except (ValueError, OSError):
        return None

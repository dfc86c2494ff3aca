"""Self-monitoring loop: ingest the process's own metrics registry into a
`_m3_system` namespace — M3 monitors M3.

The reference deployment scrapes each component's /metrics with a separate
Prometheus and often remote-writes that back into M3. This module closes
the loop in-process: a scrape snapshots utils/instrument's registry (one
lock acquisition) and writes every sample through the normal ingest path
into a dedicated namespace, so platform health — including p99s over the
latency histograms, via histogram_quantile over the `_bucket` series — is
queryable with the platform's own PromQL (`?namespace=_m3_system` on the
query endpoints).

Series naming mirrors the Prometheus exposition exactly (name mangling,
`_bucket`/`_sum`/`_count` suffixes, `le` labels), so dashboards written
against /metrics port to PromQL over `_m3_system` unchanged.
"""

from __future__ import annotations

import math
import time

from m3_tpu.utils.instrument import (
    MetricsRegistry,
    _fmt_number,
    _prom_name,
    default_registry,
)

SELF_NAMESPACE = "_m3_system"

_PAGE_SIZE = 4096
try:
    import os as _os

    _PAGE_SIZE = _os.sysconf("SC_PAGE_SIZE")
except (ValueError, OSError, AttributeError):
    pass


def rss_bytes() -> int:
    """Process resident-set size in bytes (0 when unreadable): the one
    RSS reader both observability surfaces share — the `_m3_system`
    process_rss_bytes gauge here and /debug/profile + the rig
    trajectory (utils/profiler) must never disagree about RSS."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        try:
            import resource
            import sys as _sys

            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            # ru_maxrss is KILOBYTES on linux but BYTES on darwin — and
            # darwin is exactly where the /proc path above fails. (Peak
            # rss, not current: the best this fallback can do.)
            return peak if _sys.platform == "darwin" else peak * 1024
        except Exception:  # noqa: BLE001 - no rss source on this platform
            return 0


def record_process_gauges(registry: MetricsRegistry | None = None) -> None:
    """Compute-plane health gauges, refreshed each self-scrape tick:
    process RSS (from /proc/self/statm, getrusage fallback) and per-device
    accelerator memory in use (jax memory_stats; the service initialised
    the backend at start). CPU backends report no memory_stats and are
    skipped."""
    registry = registry or default_registry()
    scope = registry.root_scope("process")
    rss = rss_bytes()
    if rss:
        scope.gauge("rss_bytes", float(rss))
    import jax

    try:
        dev_scope = registry.root_scope("device")
        for d in jax.devices():
            stats = d.memory_stats()
            if not stats:
                continue  # CPU devices report none
            in_use = stats.get("bytes_in_use")
            if in_use is not None:
                dev_scope.subscope("mem", device=str(d.id),
                                   platform=d.platform) \
                    .gauge("bytes_in_use", float(in_use))
    except Exception:  # noqa: BLE001 - never break the scrape over a
        pass           # backend quirk


def ensure_namespace(db, namespace: str = SELF_NAMESPACE) -> bool:
    """Create the self-monitoring namespace on the LOCAL storage under
    `db` (facades unwrap to their local zone). False when there is no
    local storage to host it — a pure cluster-client coordinator
    (ClusterDatabase) routes writes to nodes that never registered the
    namespace, so self-scrape stays off there."""
    target = getattr(db, "local", db)
    create = getattr(target, "create_namespace", None)
    # a real local Database owns a block cache; client facades don't
    if create is None or getattr(target, "block_cache", None) is None:
        return False
    create(namespace)
    return True


def _entry(out: list, name: str, tags, t_ns: int, value: float,
           extra_tags: tuple = ()) -> None:
    if math.isnan(value) or math.isinf(value):
        return  # not representable as a sane sample; /metrics still has it
    fields = sorted(
        [(str(k).encode(), str(v).encode()) for k, v in tags]
        + [(str(k).encode(), str(v).encode()) for k, v in extra_tags]
    )
    out.append((_prom_name(name).encode(), fields, t_ns, float(value)))


def scrape_once(db, registry: MetricsRegistry | None = None,
                namespace: str = SELF_NAMESPACE,
                now_ns: int | None = None) -> int:
    """One self-scrape: registry snapshot -> ONE batched ingest. Every
    sample of the tick ships through db.write_batch as a single
    columnar storage pass (per-sample write_tagged only for facades
    without the batch surface). Returns the number of samples written.
    The caller created the namespace (ensure_namespace) — a missing one
    raises like any bad write."""
    registry = registry or default_registry()
    now_ns = now_ns if now_ns is not None else time.time_ns()
    # refresh compute-plane gauges (RSS, device memory) so the tick's
    # snapshot carries them alongside the seam histograms
    record_process_gauges(registry)
    counters, gauges, timers, hists = registry.snapshot()
    entries: list = []
    for (name, tags), v in counters.items():
        _entry(entries, name, tags, now_ns, v)
    for (name, tags), v in gauges.items():
        _entry(entries, name, tags, now_ns, v)
    for (name, tags), (count, total_s, max_s) in timers.items():
        _entry(entries, name + "_count", tags, now_ns, count)
        _entry(entries, name + "_total_seconds", tags, now_ns, total_s)
        _entry(entries, name + "_max_seconds", tags, now_ns, max_s)
    for (name, tags), (bounds, counts, hsum, hcount) in hists.items():
        running = 0
        for ub, c in zip(bounds, counts):
            running += c
            _entry(entries, name + "_bucket", tags, now_ns, running,
                   extra_tags=(("le", _fmt_number(ub)),))
        _entry(entries, name + "_bucket", tags, now_ns,
               running + counts[-1], extra_tags=(("le", "+Inf"),))
        _entry(entries, name + "_sum", tags, now_ns, hsum)
        _entry(entries, name + "_count", tags, now_ns, hcount)
    # device-dispatch path counters, same shape /metrics exposes them in
    # (m3_dispatch_ops_total{op,path}) so dashboards port unchanged
    try:
        from m3_tpu.utils import dispatch

        items = sorted(dispatch.counters.items())
    except Exception:  # noqa: BLE001 - never break the scrape
        items = []
    for key, v in items:
        op, _, path = key.partition("[")
        tags = (("op", op),) + ((("path", path.rstrip("]")),) if path else ())
        _entry(entries, "m3_dispatch_ops_total", tags, now_ns, v)
    write_batch = getattr(db, "write_batch", None)
    if write_batch is not None:
        results = write_batch(namespace, entries)
        bad = [r for r in results if r is not None]
        if bad:  # scrape failures must stay loud, like the old raise
            raise RuntimeError(
                f"self-scrape: {len(bad)}/{len(entries)} samples failed "
                f"(first: {bad[0]})")
        return len(entries)
    for name, fields, t_ns, v in entries:
        db.write_tagged(namespace, name, fields, t_ns, v)
    return len(entries)


class SelfMonitor:
    """Tick-driven self-scrape for a service loop: call `maybe_scrape()`
    every tick; it scrapes when `interval_s` has elapsed."""

    def __init__(self, db, interval_s: float = 10.0,
                 namespace: str = SELF_NAMESPACE, registry=None,
                 clock=time.monotonic):
        self.db = db
        self.interval_s = interval_s
        self.namespace = namespace
        self.registry = registry or default_registry()
        self._clock = clock
        self._last = 0.0
        # anchor for cadence inference: time from construction to the
        # first maybe_scrape approximates the driver's tick interval
        self._last_call = clock()
        self.samples_written = 0
        self.enabled = ensure_namespace(db, namespace)
        self._hb = None

    def maybe_scrape(self, now_ns: int | None = None) -> int:
        if not self.enabled:
            return 0
        now = self._clock()
        # stall watchdog: a wedged self-scrape means the platform has
        # silently gone blind to itself. Registered LAZILY on the first
        # call and beaten per CALL, with the interval self-tuned to the
        # observed driving cadence — this monitor is ticked by the
        # coordinator loop, which may run slower than interval_s; a 1s
        # scrape interval under a 10s tick (or the construction-to-first-
        # tick gap) must never read as a stall, while a driver that
        # stops calling entirely still flags
        gap = now - self._last_call
        self._last_call = now
        if self._hb is None:
            from m3_tpu.utils import profiler

            self._hb = profiler.register_heartbeat(
                "selfscrape", max(self.interval_s, gap))
        else:
            self._hb.interval_s = max(self.interval_s, gap)
        self._hb.beat()
        if now - self._last < self.interval_s:
            return 0
        self._last = now
        n = scrape_once(self.db, self.registry, self.namespace, now_ns)
        self.samples_written += n
        return n

    def close(self) -> None:
        """Unregister the watchdog heartbeat (service shutdown) — a
        registered loop that will never beat again is a false stall."""
        if self._hb is not None:
            self._hb.close()
            self._hb = None

"""Device-compute observability plane: per-program execute telemetry
and padding-waste accounting.

`dispatch.jit_tracker` answers ONE question per tracked call — did it
hit the executable cache — and times the compile on a miss. Everything
downstream of that (which program burns the device time, what a shape
bucket costs in padded cells, whether a mesh actually changed the FLOP
bill) was invisible. This module is the accounting ledger behind the
tracker:

- ``record_execute``/``record_compile`` land per-(op, sig) wall time in
  a process table plus ``compute.execute`` histograms, with the same
  <=64-distinct-keys + ``other`` label-cap discipline the whole-query
  compiler applies to plan-shape labels (sig cardinality is bounded by
  the half-octave bucket ladder, but the metrics registry must survive
  an adversarial shape storm anyway).
- ``record_waste`` accumulates logical-vs-padded element counts at the
  half-octave/slab padding seams (query slabs, postings tensors, ragged
  encode, windowed agg); a snapshot hook publishes them as
  ``compute.waste{site,axis}`` gauges so the ratio is fresh on every
  scrape with no refresh loop.
- ``register_device_cache`` lets device-resident caches (the hot tier,
  the per-segment postings columns) report entries+bytes without this
  module importing storage or index code: providers register when THEY
  import, the ledger only reads.
- ``debug_payload``/``handle_debug_compute`` render the whole plane as
  the ``/debug/compute`` JSON body shared by all four services,
  with the backend's platform and device list (``backend.describe``)
  and per-device memory; the plan cache is read only from an
  already-imported compiler module.

``arm(False)`` disarms the per-call paths; the table survives disarming
so ``/debug/compute`` keeps its history.
"""

from __future__ import annotations

import json
import threading

from m3_tpu.utils import backend

# ---------------------------------------------------------------------------
# arming
# ---------------------------------------------------------------------------

_armed = True


def arm(on: bool) -> None:
    """Toggle the per-call recording paths; the accumulated table is
    kept either way."""
    global _armed
    _armed = bool(on)


def armed() -> bool:
    return _armed


# ---------------------------------------------------------------------------
# per-program table + sig label cap
# ---------------------------------------------------------------------------

_lock = threading.Lock()

# (op, sig) -> mutable stat row; bounded — overflow folds to (op, "other")
_TABLE_CAP = 512
_programs: dict = {}

# metrics-label discipline: first N distinct sigs get their own label,
# the tail folds to "other" (mirrors compiler._shape_label, PR 10)
_SIG_LABEL_CAP = 64
_sig_labels_seen: set = set()

_scopes: dict = {}


def _scope(kind: str, **tags):
    key = (kind, tuple(sorted(tags.items())))
    sc = _scopes.get(key)
    if sc is None:
        from m3_tpu.utils.instrument import default_registry

        sc = default_registry().root_scope("compute").subscope(kind, **tags)
        _scopes[key] = sc
    return sc


def _sig_label(sig: str) -> str:
    if sig in _sig_labels_seen:
        return sig
    with _lock:
        if sig in _sig_labels_seen:
            return sig
        if len(_sig_labels_seen) >= _SIG_LABEL_CAP:
            return "other"
        _sig_labels_seen.add(sig)
    return sig


def _row(op: str, sig: str) -> dict:
    key = (op, sig)
    row = _programs.get(key)
    if row is None:
        if len(_programs) >= _TABLE_CAP:
            key = (op, "other")
            row = _programs.get(key)
            if row is not None:
                return row
        row = _programs[key] = {
            "op": op, "sig": key[1], "calls": 0,
            "execute_calls": 0, "execute_seconds_total": 0.0,
            "execute_seconds_last": 0.0,
            "compiles": 0, "compile_seconds_total": 0.0,
        }
    return row


def record_execute(op: str, sig: str, seconds: float) -> None:
    """One tracked cache-HIT call: the wrapped wall time is dispatch,
    execution and the wait for the result (the tracker's block holds the
    read that waits; trace/compile excluded by definition)."""
    if not _armed:
        return
    with _lock:
        row = _row(op, sig)
        row["calls"] += 1
        row["execute_calls"] += 1
        row["execute_seconds_total"] += seconds
        row["execute_seconds_last"] = seconds
    # leaf "seconds" under the compute.execute scope: the exposition
    # family is compute_execute_seconds{op,sig}
    _scope("execute", op=op, sig=_sig_label(sig)).observe("seconds", seconds)


def record_compile(op: str, sig: str, seconds: float) -> None:
    """One tracked cache-MISS call (trace+lower+compile dominates the
    wall; the jit scope's compile_seconds histogram is recorded by the
    tracker itself — this lands the table attribution)."""
    if not _armed:
        return
    with _lock:
        row = _row(op, sig)
        row["calls"] += 1
        row["compiles"] += 1
        row["compile_seconds_total"] += seconds


def record_evictions(op: str, n: int) -> None:
    """Executable-cache entries that disappeared between tracked calls
    (clear_caches, donated/evicted executables) — the ground-truth
    eviction count behind compute_jit_evictions{op}."""
    if n <= 0:
        return
    _scope("jit_cache", op=op).counter("evictions", float(n))
    with _lock:
        _evictions[op] = _evictions.get(op, 0) + n


_evictions: dict = {}


# ---------------------------------------------------------------------------
# padding-waste accounting at the half-octave / slab seams
# ---------------------------------------------------------------------------

# (site, axis) -> [logical_total, padded_total, logical_last, padded_last]
_waste: dict = {}


def record_waste(site: str, axis: str, logical: int, padded: int) -> None:
    """One padded tensor axis: ``logical`` real elements shipped in a
    ``padded``-element bucket. Sites/axes are code literals (bounded
    label set); totals feed the compute.waste{site,axis} gauges."""
    if not _armed or padded <= 0:
        return
    with _lock:
        acc = _waste.get((site, axis))
        if acc is None:
            acc = _waste[(site, axis)] = [0, 0, 0, 0]
        acc[0] += int(logical)
        acc[1] += int(padded)
        acc[2] = int(logical)
        acc[3] = int(padded)


def waste_ratio(site: str, axis: str) -> float | None:
    """Cumulative fraction of padded cells that carry no real data."""
    with _lock:
        acc = _waste.get((site, axis))
    if not acc or not acc[1]:
        return None
    return 1.0 - acc[0] / acc[1]


def _publish_waste(registry) -> None:
    # snapshot hook: gauges are fresh at every scrape, no refresh loop
    with _lock:
        items = {k: list(v) for k, v in _waste.items()}
    for (site, axis), (ltot, ptot, _ll, _pl) in items.items():
        if not ptot:
            continue
        sc = registry.root_scope("compute").subscope(
            "waste", site=site, axis=axis)
        sc.gauge("logical_elements", float(ltot))
        sc.gauge("padded_elements", float(ptot))
        sc.gauge("waste_ratio", 1.0 - ltot / ptot)


# ---------------------------------------------------------------------------
# device-resident cache providers (hot tier, postings columns, ...)
# ---------------------------------------------------------------------------

# name -> zero-arg callable returning a {"entries": int, "bytes": int,
# ...} dict; providers register when their module imports, so the
# ledger never has to import storage/index code (and a dbnode that
# never compiled a query reports nothing rather than importing the
# whole query plane to say so)
_device_caches: dict = {}


def register_device_cache(name: str, fn) -> None:
    _device_caches[name] = fn


def _device_cache_stats() -> dict:
    out = {}
    for name, fn in list(_device_caches.items()):
        try:
            out[name] = fn()
        except Exception:  # noqa: BLE001 - a provider bug must not
            pass           # break the debug surface
    return out


def _publish_device_caches(registry) -> None:
    for name, stats in _device_cache_stats().items():
        sc = registry.root_scope("compute").subscope(
            "device_cache", cache=name)
        for field, val in stats.items():
            if isinstance(val, (int, float)):
                sc.gauge(field, float(val))


def _snapshot_hook(registry) -> None:
    _publish_waste(registry)
    _publish_device_caches(registry)


def _register_hook() -> None:
    from m3_tpu.utils.instrument import register_snapshot_hook

    register_snapshot_hook(_snapshot_hook)


_register_hook()


# ---------------------------------------------------------------------------
# /debug/compute payload (shared by all four services)
# ---------------------------------------------------------------------------

def device_memory() -> list[dict]:
    """Per-device memory in use. CPU devices report no memory_stats and
    are skipped. A service initialised the backend at start
    (utils/backend.init); elsewhere this is a lazy first use."""
    import jax

    out = []
    try:
        for d in jax.devices():
            stats = d.memory_stats()
            if not stats:
                continue
            out.append({"device": int(d.id), "platform": str(d.platform),
                        "bytes_in_use": int(stats.get("bytes_in_use", 0))})
    except Exception:  # noqa: BLE001 - a backend quirk must not break
        return out      # the debug surface
    return out


def _plan_cache_stats() -> dict | None:
    # only from an already-imported compiler: the debug surface must not
    # be the importer of the whole query plane
    import sys

    compiler = sys.modules.get("m3_tpu.query.compiler")
    if compiler is None:
        return None
    try:
        return compiler.plan_cache_stats()
    except Exception:  # noqa: BLE001
        return None


def debug_payload(top_n: int = 20) -> dict:
    """The /debug/compute JSON body: top-N programs by device time,
    plan-cache occupancy, jit evictions, padding waste, device-resident
    cache bytes, per-device memory."""
    with _lock:
        rows = [dict(r) for r in _programs.values()]
        evict = dict(_evictions)
        waste = {f"{site}/{axis}": {
            "logical": acc[0], "padded": acc[1],
            "waste_ratio": round(1.0 - acc[0] / acc[1], 6) if acc[1] else 0.0,
        } for (site, axis), acc in _waste.items()}
    rows.sort(key=lambda r: r["execute_seconds_total"], reverse=True)
    return {
        "armed": _armed,
        "programs": rows[:max(top_n, 0)],
        "plan_cache": _plan_cache_stats(),
        "jit_evictions": evict,
        "waste": waste,
        "device_caches": _device_cache_stats(),
        "backend": backend.describe(),
        "device_memory": device_memory(),
    }


def handle_debug_compute(method: str, q: dict, body: bytes):
    """Shared route handler -> (status, payload, content_type) for
    GET /debug/compute[?top=N] on all four services (same signature
    contract as profiler.handle_debug_profile)."""
    if method != "GET":
        return (405, json.dumps({"error": "GET only"}).encode(),
                "application/json")
    try:
        top_n = int(q.get("top", ["20"])[0]) if q else 20
    except (TypeError, ValueError):
        top_n = 20
    return (200, json.dumps(debug_payload(top_n)).encode(),
            "application/json")


def reset() -> None:
    """Test hook: drop every accumulator (table, waste, evictions, sig
    labels) — NOT the registered cache providers."""
    global _armed
    with _lock:
        _programs.clear()
        _waste.clear()
        _evictions.clear()
        _sig_labels_seen.clear()
    _armed = True

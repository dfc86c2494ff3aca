"""Accelerator backend start-up: JAX is initialised once, where a service
starts, or the service does not start.

Every device/host choice on the serving paths (utils/dispatch) asks which
backend JAX runs on. A service — coordinator, dbnode, aggregator — calls
``init()`` before it opens a listener: that initialises the default
backend, logs what it found and places the persistent compilation cache.
If the platform ``JAX_PLATFORMS`` names cannot start, ``jax.devices()``
raises and so does the service's start-up. Which platform is wanted stays
JAX's own ``JAX_PLATFORMS``; there is no variable of ours.

Library use outside a service (tests, tools) skips ``init()`` and lets
JAX initialise lazily on first use.

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
and this module sets nothing. Where it is not, the cache lives at
``<checkout>/.jax_cache``, resolved from this package's own location —
the path is part of the cache key, so it never carries a temp name, pid
or time. No other place in the tree sets a cache.

Device trace: only the process that holds the chip can trace it, so the
profiler session belongs to the service. ``start_device_trace(dir)`` /
``stop_device_trace()`` (``POST /debug/profile/device`` on the
coordinator) run ``jax.profiler`` with the Python tracer off and the host
tracer at level 1, which is annotations: while the session runs, the
stage clock (utils/trace.py) enters a ``TraceAnnotation`` per sampled
stage, so the request's stages land in the xplane beside the device
planes, on the trace's own clock (m3_tpu/tools/trace_gaps.py reads
them). Outside a session nothing is annotated and nothing is written.
"""

from __future__ import annotations

import json
import os
import threading
import time

_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache[hit]",
    "/jax/compilation_cache/cache_misses": "compile_cache[miss]",
}

_info: dict | None = None


def compile_cache_dir() -> str | None:
    """The directory this process sets the persistent compile cache to,
    or None when ``JAX_COMPILATION_CACHE_DIR`` places it from outside."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def _on_cache_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        from m3_tpu.utils import dispatch

        dispatch.counters[key] += 1


def init(log=None) -> dict:
    """Initialise the default JAX backend (idempotent) and return what it
    is: platform, device_kind, device count, jax version, cache dir.
    Raises whatever ``jax.devices()`` raises when the backend cannot
    start — a service must not listen without one."""
    global _info
    if _info is not None:
        return _info
    import jax

    cache = compile_cache_dir()
    if cache is not None:
        jax.config.update("jax_compilation_cache_dir", cache)
        # every program the services compile is worth keeping: the
        # default 1 s floor would recompile the small ones each start
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # persistent-cache hits/misses ride /metrics beside the jit counters
    # (m3_dispatch_ops_total{op="compile_cache"}): a second start in the
    # same checkout should show hits only
    jax.monitoring.register_event_listener(_on_cache_event)
    found = describe()  # jax.devices(): the backend comes up here
    _info = {
        **found, "devices": len(found["devices"]),
        "compile_cache": cache or os.environ["JAX_COMPILATION_CACHE_DIR"],
    }
    if log is not None:
        log.info("backend initialised", **_info)
    return _info


def describe() -> dict:
    """Platform and device list for the debug surfaces; initialises the
    backend lazily like any other first use outside a service."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "jax": jax.__version__,
        "devices": [{"id": int(d.id), "platform": str(d.platform),
                     "kind": str(d.device_kind)} for d in devices],
    }


# -- the device-trace session ------------------------------------------------

_trace_lock = threading.Lock()
_trace_dir: str | None = None


def start_device_trace(trace_dir: str) -> dict:
    """Start a profiler session into `trace_dir` and switch the stage
    clock's annotations on. One session at a time."""
    global _trace_dir
    import jax

    from m3_tpu.utils import trace

    with _trace_lock:
        if _trace_dir is not None:
            raise RuntimeError(f"a device trace runs into {_trace_dir}")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        t0 = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        _trace_dir = trace_dir
        trace.default_tracer().annotate = jax.profiler.TraceAnnotation
        return {"tracing": True, "dir": trace_dir,
                "start_seconds": time.perf_counter() - t0}


def stop_device_trace() -> dict:
    """Switch the annotations off, stop the session and say where the
    xplane is and how long the stop took."""
    global _trace_dir
    import jax

    from m3_tpu.utils import trace

    with _trace_lock:
        if _trace_dir is None:
            raise RuntimeError("no device trace runs")
        trace.default_tracer().annotate = None
        t0 = time.perf_counter()
        try:
            jax.profiler.stop_trace()
        finally:
            trace_dir, _trace_dir = _trace_dir, None
        return {"tracing": False, "dir": trace_dir,
                "stop_seconds": time.perf_counter() - t0}


def handle_debug_profile_device(method: str, q: dict, body: bytes):
    """``/debug/profile/device`` -> (status, payload, content_type), the
    contract of profiler.handle_debug_profile. POST ``{"action":
    "start", "dir": ...}`` or ``{"action": "stop"}``; GET says whether a
    session runs."""
    def answer(status: int, doc: dict):
        return status, json.dumps(doc).encode(), "application/json"

    if method == "GET":
        return answer(200, {"tracing": _trace_dir is not None,
                            "dir": _trace_dir})
    if method != "POST":
        return answer(405, {"error": "GET or POST"})
    try:
        doc = json.loads(body or b"{}")
        action = doc.get("action")
        if action == "start":
            if not doc.get("dir"):
                return answer(400, {"error": "start needs a dir"})
            return answer(200, start_device_trace(str(doc["dir"])))
        if action == "stop":
            return answer(200, stop_device_trace())
    except (ValueError, RuntimeError) as e:
        return answer(409 if isinstance(e, RuntimeError) else 400,
                      {"error": str(e)})
    return answer(400, {"error": "action is start or stop"})

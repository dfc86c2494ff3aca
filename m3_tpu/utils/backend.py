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
"""

from __future__ import annotations

import os

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

#!/usr/bin/env python3
"""The benchmark's launcher for the system under test.

Does what ``m3_tpu/services/coordinator.py main()`` does
(``CoordinatorService(load_config(path)).run()``) and, because only the
process that holds the chip can trace it and the program has no hook
(PERF.md, Open questions), answers two signals from the parent:

  SIGUSR1  start ``jax.profiler`` into ``<control>/trace``; writes
           ``<control>/trace_start.json`` when tracing
  SIGUSR2  stop the trace if one runs; write the devices'
           ``memory_stats()`` to ``<control>/device_stats.json`` (and the
           same to ``trace_stop.json`` when a trace was stopped)

A traced interval is marked inside the trace itself: the launcher runs a
one-element program named ``bench_mark`` just after the profiler has
started and again just before it stops, and ``trace_reduce`` clips every
device event to the time between the two marks, on the trace's own clock.
At the same two moments it takes the program's counters in process
(the text ``/metrics`` serves), so that counts and device time are of the
same interval. The first SIGUSR2 of a run (set-up asks for the device's
statistics) compiles the mark, so nothing compiles in the window.

It also writes what the program's loader made of the configuration file
to ``<control>/loaded_config.json``, for the parent to hold against the
configuration it rendered.

The handlers only set a flag; a helper thread does the work, so nothing
of JAX runs inside a signal handler on the service's tick thread.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time


def _log(msg: str) -> None:
    print(json.dumps({"ts": time.time(), "logger": "bench-launcher",
                      "msg": msg}), file=sys.stderr, flush=True)


def _write(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def _device_stats() -> dict:
    import jax

    devices = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # a backend without memory statistics (CPU)
            stats = {}
        devices.append({"id": d.id, "platform": d.platform,
                        "kind": d.device_kind,
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "bytes_limit": stats.get("bytes_limit")})
    return {"devices": devices, "time_ns": time.time_ns()}


def bench_mark(x):
    return x + 1


def _counters() -> str:
    from m3_tpu.utils.instrument import default_registry

    return default_registry().render_prometheus().decode()


def _helper(control: str, wake: threading.Event, asked: list) -> None:
    tracing = False
    trace_dir = os.path.join(control, "trace")
    mark = zero = None
    while True:
        wake.wait()
        wake.clear()
        import jax   # the service has initialised it by now
        if mark is None:
            mark = jax.jit(bench_mark)
            zero = jax.numpy.zeros((), jax.numpy.int32)
            mark(zero).block_until_ready()
        while asked:
            what = asked.pop(0)
            if what == "start" and not tracing:
                # device planes are what trace_reduce reads. The host and
                # Python tracers cost the service its GIL and made
                # stop_trace outlast 240 s at full size (my chip run,
                # PR 24); without them it is 2 s
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                tracing = True
                mark(zero).block_until_ready()
                t_start = time.time_ns()
                counters = _counters()
                _log("trace started")
                _write(os.path.join(control, "trace_start.json"),
                       {"time_ns": t_start, "dir": trace_dir,
                        "counters": counters})
            elif what == "stop":
                doc = _device_stats()
                if tracing:
                    counters = _counters()
                    t_stop = time.time_ns()
                    mark(zero).block_until_ready()
                    _log("trace stopping")
                    jax.profiler.stop_trace()
                    tracing = False
                    _log("trace stopped")
                    doc.update(stopped_ns=t_stop, dir=trace_dir,
                               counters=counters)
                    _write(os.path.join(control, "trace_stop.json"), doc)
                _write(os.path.join(control, "device_stats.json"), doc)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-f", "--config", required=True)
    ap.add_argument("--control", required=True)
    args = ap.parse_args(argv)

    from m3_tpu.services.coordinator import CoordinatorService, load_config

    wake, asked = threading.Event(), []

    def on_signal(signum, _frame):
        asked.append("start" if signum == signal.SIGUSR1 else "stop")
        wake.set()

    signal.signal(signal.SIGUSR1, on_signal)
    signal.signal(signal.SIGUSR2, on_signal)
    threading.Thread(target=_helper, args=(args.control, wake, asked),
                     daemon=True, name="bench-launcher").start()
    config = load_config(args.config) or {}
    _write(os.path.join(args.control, "loaded_config.json"), config)
    svc = CoordinatorService(config)
    try:
        svc.run()
    except KeyboardInterrupt:
        svc.shutdown()


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    main()

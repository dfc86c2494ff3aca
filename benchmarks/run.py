#!/usr/bin/env python3
"""The benchmark's command: one cell, one run, one process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the single-node deployment (coordinator with embedded storage) as
its one child through ``harness/serve.py``, makes the cell's data and
traffic from ``--seed``, warms up (set-up), offers the cell's load for
``--seconds``, then checks what the window's own requests were answered
against the plain reference. The parent never imports JAX. The run fails,
and prints no result, when the service reports a platform other than
``tpu`` or fewer chips than the cell asks for (``--rehearse``: a tiny
scale on whatever JAX finds, for the CPU rehearsal and the tests; the
device is named in the last line as it is).

Everything that belongs to one cell, configuration, traffic kind, query
type or per-layer metric is a file found by name (``benchmarks/README.md``).
The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared beside its limit.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


# what the profiler's start takes at the most, before its first mark is in
# the trace: 0.10-0.21 s in nine traced runs on the chip, 0.31 and 0.33 s in
# two, one of which left 0.11 s of the 0.35 s asked for (my chip runs, PR 35)
TRACE_START_S = 0.3


def say(*a) -> None:
    print(*a, flush=True)


class Run:
    """What a traffic kind sees of the run."""

    def __init__(self, opts):
        from harness.client import Node

        self.opts = opts
        self.seed = int(opts.seed)
        self.say = say
        self.cell = self.load_json("workloads", opts.workload)
        self.config = self.load_json("configs", self.cell["config"])
        self.chips = int(self.cell["chips"])
        self.node = Node(self.config)
        self.params = dict(self.cell["traffic_params"])
        if opts.rehearse:
            self.config.update(self.config.get("rehearse", {}))
            self.params.update(self.cell.get("rehearse", {}))
        self.service = None
        self.port = 0
        self._client = None

    @staticmethod
    def load_json(kind: str, name: str) -> dict:
        with open(os.path.join(HERE, kind, name + ".json")) as f:
            return json.load(f)

    def metrics(self) -> dict:
        """/metrics, parsed, over a connection the run keeps."""
        from harness.client import Client, parse_metrics

        if self._client is None:
            self._client = Client(self.port)
        return parse_metrics(self._client.metrics_text())


def load_traffic(kind: str):
    path = os.path.join(HERE, "traffic", kind + ".py")
    spec = importlib.util.spec_from_file_location("traffic_" + kind, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Traffic


def read_layer_metrics(cell: str, reading) -> dict:
    """Every `layer_metrics/<name>.json` that lists the cell, read by the
    reader it names."""
    from harness.readers import READERS

    out = {}
    for path in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))):
        spec = Run.load_json("layer_metrics", path[:-len(".json")])
        if cell not in spec["cells"]:
            continue
        value = READERS[spec["reader"]](reading, **spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def reduce_trace(trace_dir: str, out_path: str) -> dict | None:
    """trace_reduce.extract in a process of its own, pinned to the CPU:
    this parent stays off JAX, and the chip's process has ended."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
         trace_dir, out_path], env=env, capture_output=True, text=True,
        timeout=240)
    if r.returncode != 0:
        say("trace_reduce failed:", r.stderr[-2000:])
        return None
    with open(out_path) as f:
        return json.load(f)


def main(argv=None, launcher: str | None = None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny scale, any platform (CPU rehearsal, tests)")
    ap.add_argument("--control", action="store_true",
                    help="also judge the cell's control (a lower precision "
                    "or a broken guarantee in the program's place) and "
                    "print its numbers on an earlier line")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (data, log, trace)")
    opts = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(CHECKOUT, "m3_tpu")):
        print(f"benchmarks/run.py: no m3_tpu package in {CHECKOUT}: "
              "nothing to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, CHECKOUT)
    if not os.path.exists(os.path.join(HERE, "workloads",
                                       opts.workload + ".json")):
        print(f"benchmarks/run.py: no cell {opts.workload!r} under "
              "benchmarks/workloads", file=sys.stderr)
        return 2

    from harness import trace_reduce
    from harness.client import BenchFailure, Client, Service, parse_metrics
    from harness.readers import Reading

    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = CHECKOUT + os.pathsep + child_env.get(
        "PYTHONPATH", "")
    if opts.rehearse:
        # the device rungs forced on whatever JAX finds, as
        # tests/test_chip_smoke.py does to a real service process
        child_env.setdefault("JAX_PLATFORMS", "cpu")   # unless it is set
        child_env.update(M3_TPU_DEVICE_OPS="1", M3_TPU_QUERY_COMPILE="1")

    run = Run(opts)
    traffic = load_traffic(run.cell["traffic"])(run)
    work = tempfile.mkdtemp(prefix="m3_bench_")
    say(f"run: cell={opts.workload} seed={opts.seed} "
        f"seconds={opts.seconds:g} trace={opts.trace} "
        f"rehearse={opts.rehearse} work={work}")

    device = None
    result = None
    try:
        run.service = svc = Service(
            work, child_env, CHECKOUT,
            launcher or os.path.join(HERE, "harness", "serve.py"), run.node)
        traffic.prepare()                 # while the service starts
        run.port, backend = svc.wait_listening(600.0)
        svc.check_loaded_config()
        svc.ask("device_stats")    # the launcher compiles its mark here
        seen = Client(run.port).get_json("/debug/compute")["backend"]
        found = {"platform": str(seen["platform"]),
                 "kind": str(seen["device_kind"]),
                 "count": len(seen["devices"])}
        say(f"service up at {time.perf_counter() - t_start:.1f}s: {found} "
            f"jax={seen['jax']} compile_cache={backend.get('compile_cache')}")
        if not opts.rehearse and (found["platform"] != "tpu"
                                  or found["count"] < run.chips):
            raise BenchFailure(
                f"the service runs on {found}, the cell needs "
                f"{run.chips} tpu chip(s)")
        device = found
        with open(os.path.join(HERE, "harness", "peaks.json")) as f:
            peaks = json.load(f)
        if found["kind"] not in peaks and not opts.rehearse:
            raise BenchFailure(f"no peaks for device {found['kind']!r} in "
                               "harness/peaks.json")

        traffic.setup()
        setup_s = time.perf_counter() - t_start
        say(f"set-up done: {setup_s:.1f}s")

        before = run.metrics()
        traffic.start_window(opts.seconds)
        t_open = time.perf_counter()
        traced = None
        if opts.trace:
            # the last seconds of the window: the trace is stopped as the
            # window closes, so collecting it costs the window nothing. The
            # profiler is asked TRACE_START_S before the slice is due, so
            # the slice is `trace_seconds` at the least
            length = min(float(run.cell.get("trace_seconds", 4.0)),
                         0.5 * opts.seconds)
            time.sleep(max(0.0, t_open + opts.seconds - length
                           - TRACE_START_S - time.perf_counter()))
            t_asked = time.perf_counter()
            started = svc.ask("trace_start")
            t_a = time.perf_counter()
            time.sleep(max(0.0, t_open + opts.seconds - time.perf_counter()))
            t_b = time.perf_counter()
            stopped = svc.ask("trace_stop", 200.0)
            # the interval between the launcher's two marks by its own
            # clock (the trace's clock, where trace_reduce finds the
            # marks), and the program's counters as taken at the marks
            traced_s = (stopped["stopped_ns"] - started["time_ns"]) / 1e9
            traced = (parse_metrics(started["counters"]),
                      parse_metrics(stopped["counters"]))
            say(f"trace: start took {t_a - t_asked:.2f}s, traced "
                f"{traced_s:.2f}s, stop took "
                f"{time.perf_counter() - t_b:.2f}s")
        outcome = traffic.end_window()
        after = run.metrics()
        missed = {k: after[k] - before.get(k, 0.0) for k in sorted(after)
                  if k.endswith("[miss]") and after[k] > before.get(k, 0.0)}
        say("cache misses in the window: " + json.dumps(missed))
        stats = svc.ask("device_stats")
        peaks_seen = [d["peak_bytes_in_use"] for d in stats["devices"]
                      if d.get("peak_bytes_in_use") is not None]
        device["memory_peak_bytes"] = max(peaks_seen) if peaks_seen else 0
        window_s = float(opts.seconds)

        checks = traffic.verify()
        if opts.control:
            ctl = traffic.control()
            say("control: " + json.dumps({
                "correct": all(c["holds"] for c in ctl),
                "checks": {c["name"]: {"value": c["value"],
                                       "limit": c["limit"]} for c in ctl}}))
        svc.stop()

        outcome["metrics"]["setup_s"] = setup_s
        units = {**traffic.e2e, "setup_s": "s"}
        e2e = {k: {"value": v, "unit": units[k]}
               for k, v in outcome["metrics"].items()}
        say("end_to_end: " + json.dumps(e2e))
        breakdown = None
        if opts.trace:
            trace = reduce_trace(os.path.join(svc.control, "trace"),
                                 os.path.join(work, "trace.json"))
            if trace is not None and trace_reduce.interval_s(trace):
                traced_s = trace_reduce.interval_s(trace)
            else:
                say("trace: no marked interval; the launcher's clock stands")
            traffic.traced(*traced)
            reading = Reading(before, after, window_s, traffic.facts,
                              peaks.get(found["kind"], {}), trace, traced_s)
            metrics = read_layer_metrics(opts.workload, reading)
            if trace is not None:
                busy = trace_reduce.busy_s(trace)
                if busy is not None:
                    device["busy_s"] = busy
                device["window_s"] = traced_s
                breakdown = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace)}
            say("facts: " + json.dumps(traffic.facts))
        else:
            metrics = e2e
        result = {"correct": all(c["holds"] for c in checks),
                  "attempted": outcome["attempted"],
                  "failed": outcome["failed"], "metrics": metrics,
                  "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = {c["name"]: {"value": c["value"],
                                        "limit": c["limit"]} for c in checks}
    except BenchFailure as e:
        print(f"benchmarks/run.py: FAILED: {e}", file=sys.stderr)
    finally:
        if run.service is not None:
            run.service.stop()
            if result is None:
                sys.stderr.write("--- service log (tail) ---\n"
                                 + run.service.log_tail() + "\n")
        if opts.keep:
            print(f"benchmarks/run.py: kept {work}", file=sys.stderr)
        else:
            shutil.rmtree(work, ignore_errors=True)
    if result is None:
        return 1
    if "jax" in sys.modules:
        print("benchmarks/run.py: FAILED: the parent imported jax",
              file=sys.stderr)
        return 1
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} {c['rule']} "
              f"{c['limit']!r}: {'holds' if c['holds'] else 'FAILS'}",
              file=sys.stderr)
    sys.stderr.flush()
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The HTTP client side and the one child process.

Copied from ``chip_smoke.py`` (PR 21): ``Client``, ``_parse_timeseries``,
``Service``, ``check_log``. The child here is ``harness/serve.py`` (the
benchmark's own launcher), so the parent can ask it for a profiler trace
and the device's memory statistics; it runs the same
``CoordinatorService(load_config(path)).run()`` as the documented start.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.parse

NS = 1_000_000_000

_UNIT_NS = {"s": NS, "m": 60 * NS, "h": 3600 * NS, "d": 86400 * NS}


def duration_ns(text: str) -> int:
    """'10m', '2h', '48h' as the coordinator's configuration writes them."""
    m = re.fullmatch(r"(\d+)([smhd])", str(text))
    if not m:
        raise ValueError(f"not a duration: {text!r}")
    return int(m.group(1)) * _UNIT_NS[m.group(2)]


def to_yaml(doc: dict, indent: int = 0) -> str:
    """Nested mappings of scalars, in the YAML subset the program reads."""
    out = []
    for key, val in doc.items():
        if isinstance(val, dict):
            out.append(f"{' ' * indent}{key}:\n" + to_yaml(val, indent + 2))
        else:
            out.append(f"{' ' * indent}{key}: {json.dumps(val)}\n")
    return "".join(out)


class Node:
    """The deployment, as the configuration's file states it: the
    coordinator's configuration is the file's ``node.coordinator`` block
    with the run's own paths filled in, and what the traffic has to know
    of it (shards, block size, ``buffer_past``) is read from that block."""

    def __init__(self, config: dict):
        self.coordinator = config["node"]["coordinator"]
        db = self.coordinator["db"]
        self.n_shards = int(db["n_shards"])
        self.namespace = str(db["namespace"])
        retention = db["options"]["retention"]
        self.block_ns = duration_ns(retention["block_size"])
        self.buffer_past_ns = duration_ns(retention["buffer_past"])

    def rendered(self, data_dir: str, kv_path: str) -> dict:
        doc = json.loads(json.dumps(self.coordinator))
        doc["db"]["path"] = data_dir
        doc.setdefault("cluster", {})["kv_path"] = kv_path
        return doc


class BenchFailure(Exception):
    """The run cannot produce a result (not: an answer was wrong)."""


class Client:
    """One keep-alive connection; not shared between threads."""

    def __init__(self, port: int, timeout: float = 300.0):
        self.port, self.timeout = port, timeout
        self._conn: http.client.HTTPConnection | None = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str,
                body: bytes | None = None) -> bytes:
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout)
            try:
                self._conn.request(method, path, body=body)
                r = self._conn.getresponse()
                data = r.read()
            except (http.client.HTTPException, ConnectionError) as e:
                # a keep-alive connection the server closed: once more on
                # a fresh one; the caller's clock keeps running
                self.close()
                if attempt:
                    raise BenchFailure(f"{method} {path[:80]}: {e!r}") from e
                continue
            if r.status != 200:
                raise BenchFailure(
                    f"{method} {path.split('?')[0]} -> HTTP {r.status}: "
                    f"{data[:400]!r}")
            return data
        raise AssertionError("unreachable")

    def get_json(self, path: str) -> dict:
        return json.loads(self.request("GET", path))

    def metrics_text(self) -> str:
        return self.request("GET", "/metrics").decode()

    def query_range_path(self, q: str, start: int, end: int, step: int) -> str:
        return "/api/v1/query_range?" + urllib.parse.urlencode({
            "query": q, "start": repr(start / NS), "end": repr(end / NS),
            "step": f"{step // NS}s"})

    def runtime(self, **options) -> None:
        """The runtime-options valve (hold flush and snapshot while a past
        block loads, as an operator's backfill does)."""
        self.request("POST", "/api/v1/runtime", json.dumps(options).encode())

    def remote_write(self, body: bytes) -> int:
        resp = json.loads(self.request(
            "POST", "/api/v1/prom/remote/write", body))
        if resp.get("status") != "success":
            raise BenchFailure(f"remote-write refused: {resp}")
        return int(resp.get("samples", 0))


def parse_metrics(text: str) -> dict[str, float]:
    """/metrics as {'name{labels}': value}; the dispatch counters also
    under their own keys, 'op' or 'op[path]'."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
        m = re.fullmatch(
            r'm3_dispatch_ops_total\{op="([^"]*)"(?:,path="([^"]*)")?\}', key)
        if m:
            op, path = m.groups()
            out[f"{op}[{path}]" if path else op] = float(val)
    return out


def check_log(log_path: str) -> None:
    with open(log_path, errors="replace") as f:
        for line in f:
            if "tick error" in line or "Traceback" in line:
                raise BenchFailure(f"service log: {line.strip()[:600]}")


class Service:
    """The one child: ``python <launcher> -f <cfg> --control <dir>``; data,
    KV, config, log, trace and control files under `work`."""

    def __init__(self, work: str, env: dict, checkout: str, launcher: str,
                 node: Node):
        self.work = work
        self.data_dir = os.path.join(work, "m3data")
        self.log_path = os.path.join(work, "coordinator.log")
        self.control = os.path.join(work, "control")
        os.makedirs(self.control)
        cfg_path = os.path.join(work, "coordinator.yml")
        self.config = node.rendered(self.data_dir,
                                    os.path.join(work, "kv.json"))
        with open(cfg_path, "w") as f:
            f.write(to_yaml(self.config))
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, launcher, "-f", cfg_path, "--control",
                 self.control], cwd=checkout, env=env, stdout=log,
                stderr=log, start_new_session=True)

    def log_tail(self, n: int = 3000) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]

    def wait_listening(self, timeout_s: float) -> tuple[int, dict]:
        """(port, backend info) from the service's own start-up lines."""
        t0 = time.perf_counter()
        backend = None
        while time.perf_counter() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"service exited with {self.proc.returncode} before "
                    f"listening:\n{self.log_tail()}")
            with open(self.log_path, errors="replace") as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if not isinstance(rec, dict):
                        continue
                    if rec.get("msg") == "backend initialised":
                        backend = rec
                    if rec.get("msg") == "http listening":
                        if backend is None:
                            raise BenchFailure("service listened before "
                                               "initialising a backend")
                        return int(rec["port"]), backend
            time.sleep(0.25)
        raise BenchFailure(f"service not listening after {timeout_s:.0f}s")

    def check_loaded_config(self) -> None:
        """The configuration the launcher handed the service (what the
        program's own loader made of the rendered file) has to be the
        configuration file's ``node.coordinator`` block."""
        with open(os.path.join(self.control, "loaded_config.json")) as f:
            loaded = json.load(f)
        if loaded != self.config:
            raise BenchFailure(
                "the service loaded another configuration than the "
                f"configuration file states: {loaded} != {self.config}")

    def ask(self, what: str, timeout_s: float = 120.0) -> dict:
        """Signal the launcher and wait for its answer file.
        `trace_start` / `trace_stop` / `device_stats` (see serve.py)."""
        sig = {"trace_start": signal.SIGUSR1, "trace_stop": signal.SIGUSR2,
               "device_stats": signal.SIGUSR2}[what]
        path = os.path.join(self.control, what + ".json")
        if os.path.exists(path):
            os.remove(path)
        os.kill(self.proc.pid, sig)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout_s:
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
            if self.proc.poll() is not None:
                raise BenchFailure(f"service died during {what}:\n"
                                   + self.log_tail())
            time.sleep(0.02)
        raise BenchFailure(f"launcher did not answer {what}")

    def stop(self) -> None:
        """Ctrl-C first (the service shuts down and closes its storage),
        then harder; returns when the child has ended."""
        for sig, wait_s in ((signal.SIGINT, 30), (signal.SIGTERM, 10),
                            (signal.SIGKILL, 30)):
            if self.proc.poll() is not None:
                return
            try:
                os.killpg(self.proc.pid, sig)
            except ProcessLookupError:
                return
            try:
                self.proc.wait(wait_s)
            except subprocess.TimeoutExpired:
                continue

"""Traffic kind ``promql_ranges``: dashboards over a fleet at TSBS's own
time ranges, across sealed blocks. ``promql_closed_loop``'s closed loop,
decks and warm-up rule (its class is loaded from its file and extended),
over a history of several whole blocks, with each query type asking for
its own ``range_s`` (TSBS ``uses/devops``: 12 h and 8 h) where the base
kind asks for the loaded span.

Each request draws from ``--seed`` its hosts (uniform without
replacement, as the base kind draws them) and its window's start:
uniform on the whole minutes after the loaded span's first sample such
that the window lies inside the span (``harness/ranges.py``). Set-up
loads the configuration's history by remote-write with flush held, lets
the tick flush every (shard, block) through the device encoder
(``coordinator_blocks_flushed`` reaches shards x blocks), and primes the
caches with one request over every series and the whole span, so that
the window opens with the decoded-block cache full and evicting; then it
warms every type as the base kind does.

What decides ``correct``: every answer of the window, and the prime's,
against the plain reference evaluated at the request's own grid
(``harness/ranges.py evaluate``), and ``verify_streams`` (series, block)
pairs drawn from the seed over every loaded block, under the scalar
decoder, bit for bit. ``control`` judges the float32 reference in the
program's place: it has to come out not correct.

Parameters beside ``promql_closed_loop``'s: ``window_facts``, for each
fact its ``keys`` (regexes over the ``/metrics`` keys) and ``scale``:
the keys' increase between the window's opening and its close, as the
kind reads them itself, times the scale; a fact whose keys the program
does not have is left out, and the metric that reads it with it.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import time

import numpy as np

from harness import compare, ranges, readers, reference, tsbs
from harness.client import BenchFailure, Client, check_log


def _load_base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "promql_closed_loop.py")
    spec = importlib.util.spec_from_file_location(
        "traffic_promql_closed_loop", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _load_base()
NS = tsbs.NS


def window_facts(spec: dict, at_open: dict, at_close: dict) -> dict:
    """For each entry of `spec` (the cell's `window_facts`) the increase
    of its keys between the two readings of /metrics, times its scale;
    one whose keys the program does not have is left out."""
    facts = {}
    for fact, entry in spec.items():
        rxs = [re.compile(rx) for rx in entry["keys"]]
        keys = [k for k in at_close if any(rx.fullmatch(k) for rx in rxs)]
        if keys:
            facts[fact] = float(entry["scale"]) * sum(
                at_close[k] - at_open.get(k, 0.0) for k in keys)
    return facts


class Requests(_base.Requests):
    """The base kind's seeded sequence of (type, hosts), each request
    with its window's start beside: (type, hosts, minutes after the
    loaded span's first sample), drawn from the same stream."""

    def __init__(self, rng, deck: list[str], types: dict, hosts: int,
                 starts: dict[str, int]):
        super().__init__(rng, deck, types, hosts)
        self.starts = starts

    def draw(self, name: str) -> tuple[str, list[int], int]:
        name, hosts = super().draw(name)
        return name, hosts, int(self.rng.integers(self.starts[name]))


class Traffic(_base.Traffic):
    e2e = {"query_p95_ms": "ms", "query_rate": "queries/s"}

    # -- made from the seed, before the service is up -----------------------

    def prepare(self) -> None:
        # the base kind's fleet, walk and decks; its `points` readings end
        # where the newest sealed block does, so whole blocks of them are
        # whole sealed blocks
        super().prepare()
        block = self.node.block_ns
        span = self.points * tsbs.INTERVAL_NS
        if span % block:
            raise BenchFailure(f"{self.points} readings are not whole "
                               "blocks")
        self.n_blocks = span // block
        self.block_starts = [int(self.times_ns[0]) + b * block
                             for b in range(self.n_blocks)]
        starts = {name: ranges.n_starts(spec, self.points)
                  for name, spec in self.types.items()
                  if name in self.run.params["mix"]}
        deck = self.window_requests.deck
        self.window_requests = Requests(
            np.random.default_rng([self.seed, 3]), deck, self.types,
            self.hosts, starts)
        self.warm_requests = Requests(
            np.random.default_rng([self.seed, 4]), deck, self.types,
            self.hosts, starts)
        self.window_requests.get(5_000)   # the rest as the window asks

    def _grid(self, spec: dict, offset: int, range_s=None) -> np.ndarray:
        return ranges.eval_ts(spec, int(self.times_ns[0]), offset, range_s)

    def _path(self, spec: dict, hosts: list[int], offset: int,
              range_s=None) -> str:
        start, end, step = ranges.grid(spec, int(self.times_ns[0]), offset,
                                       range_s)
        return self._client.query_range_path(
            reference.promql(spec, hosts), start, end, step)

    def _make(self, requests: Requests, base: int, i: int):
        name, hosts, offset = requests.get(base + i)
        return "GET", self._path(self.types[name], hosts, offset), None, name

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        run, p = self.run, self.run.params
        c = self._client = Client(run.port)
        c.runtime(flush_enabled=False, snapshot_enabled=False)
        t0 = time.perf_counter()
        n_f = len(tsbs.CPU_FIELDS)
        hosts_per, points_per = int(p["load_hosts_per_request"]), \
            int(p["load_points_per_request"])
        acked = 0
        for p0 in range(0, self.points, points_per):
            p1 = min(p0 + points_per, self.points)
            for h0 in range(0, self.hosts, hosts_per):
                h1 = min(h0 + hosts_per, self.hosts)
                body, n = tsbs.write_body(self.fleet, self.values,
                                          self.times_ns, h0 * n_f, h1 * n_f,
                                          p0, p1)
                got = c.remote_write(body)
                if got != n:
                    raise BenchFailure(f"load: acked {got} of {n}")
                acked += got
        t_load = time.perf_counter() - t0
        run.say(f"load: {acked} samples of {self.n_blocks} blocks in "
                f"{t_load:.1f}s ({acked / t_load:.0f}/s)")
        c.runtime(flush_enabled=True, snapshot_enabled=True)
        t0 = time.perf_counter()
        want = self.node.n_shards * self.n_blocks   # every (shard, block)
        while True:
            check_log(run.service.log_path)
            flushed = run.metrics().get("coordinator_blocks_flushed", 0.0)
            if flushed >= want:
                break
            if time.perf_counter() - t0 > 300:
                raise BenchFailure(f"{flushed:g} of the {want} loaded "
                                   "(shard, block) volumes were flushed")
            time.sleep(0.5)
        run.say(f"flush: {want} volumes in {time.perf_counter() - t0:.1f}s")
        self.facts["flushed_samples"] = acked
        self.facts["flushed_data_bytes"] = readers.files_bytes(
            os.path.join(run.service.data_dir, "data", self.node.namespace),
            "-data.db")
        # what the flush stored of one (series, block) stream
        self.stream_bytes = self.facts["flushed_data_bytes"] \
            / (self.fleet.n_series * self.n_blocks)
        # `prime`: one request over every series of the type and the
        # whole span, compared like the window's (the cell's `prime_why`)
        t0 = time.perf_counter()
        for q in p.get("prime", []):
            spec = dict(self.types[q["type"]], hosts=q["hosts"])
            whole = ranges.whole_span_s(spec, self.points)
            self.primed.append((spec, self._grid(spec, 0, whole), c.request(
                "GET", self._path(spec, [], 0, whole))))
        run.say(f"prime: {time.perf_counter() - t0:.1f}s")
        # every type singly, learning which plan signature serves it (as
        # the base kind does); then the base kind's rounds of the loop
        t0 = time.perf_counter()
        rx = re.compile(p["plan_launch_counter"])
        n_warm = 0
        for name in p["mix"]:
            before = run.metrics()
            for _ in range(3):
                _, hosts, offset = self.warm_requests.draw(name)
                c.request("GET", self._path(self.types[name], hosts, offset))
                n_warm += 1
            after = run.metrics()
            for key, n in after.items():
                m = rx.fullmatch(key)
                if m and n > before.get(key, 0.0):
                    if self.sig_type.setdefault(m.group(1), name) != name:
                        self.sig_type[m.group(1)] = ""   # serves two types
        run.say("plan signatures: " + json.dumps(self.sig_type))
        self.warm_rounds()
        run.say(f"warm-up: {n_warm} single requests and the rounds in "
                f"{time.perf_counter() - t0:.1f}s")

    # -- the window -----------------------------------------------------------

    def start_window(self, seconds: float) -> None:
        self._at_open = self.run.metrics()
        super().start_window(seconds)

    def end_window(self) -> dict:
        out = super().end_window()
        self.facts.update(window_facts(self.run.params["window_facts"],
                                       self._at_open, self.run.metrics()))
        return out

    def traced(self, before: dict, after: dict) -> None:
        """The base kind's facts of the traced interval, its bytes
        reckoned from each window's own points (a plan reads the series
        inside its range, not the loaded span) and each block's own
        stream (a decoded stream is one block of one series)."""
        rx = re.compile(self.run.params["plan_launch_counter"])
        launches, total, unknown = 0.0, 0.0, []
        for key, n in after.items():
            m = rx.fullmatch(key)
            d = n - before.get(key, 0.0)
            if not m or d <= 0:
                continue
            launches += d
            name = self.sig_type.get(m.group(1))
            if not name:
                unknown.append(m.group(1))
                continue
            total += d * ranges.least_bytes(self.types[name], self.hosts)
        self.facts["traced_plan_launches"] = launches
        if unknown:
            self.run.say(f"plans of no one type in the traced interval: "
                         f"{unknown}: no plan bytes reckoned")
        elif launches:
            self.facts["traced_plan_least_bytes"] = total
        key = self.run.params["decode_streams_counter"]
        streams = after.get(key, 0.0) - before.get(key, 0.0)
        self.facts["traced_decode_streams"] = streams
        if streams > 0:
            per_block = self.points // self.n_blocks
            self.facts["traced_decode_least_bytes"] = streams * (
                self.stream_bytes + per_block * 16)

    # -- what decides `correct` -----------------------------------------------

    def verify(self, served_by=None) -> list[dict]:
        """Every answer of the window (a seeded sample of `verify_max`
        where there are more) and the prime's against the reference at
        the request's own grid; `verify_streams` (series, block) pairs
        drawn from the seed over every loaded block under the scalar
        decoder. `served_by(spec, hosts, eval_ts)` exists for the
        control: it answers in the program's place."""
        run = self.run
        recs = [r for r in self.loop.records if r[4]]
        failed = len(self.loop.records) - len(recs)
        cap = int(run.params["verify_max"])
        rng = np.random.default_rng([self.seed, 5])
        if len(recs) > cap:
            idx = np.sort(rng.choice(len(recs), cap, replace=False))
            recs = [recs[i] for i in idx.tolist()]
        asked = [(f"prime {spec['name']}", spec, [], at, answer)
                 for spec, at, answer in self.primed]
        for r in recs:
            name, hosts, offset = self.window_requests.get(r[0])
            spec = self.types[name]
            asked.append((f"request {r[0]}", spec, hosts,
                          self._grid(spec, offset), r[5]))
        wrong, worst, n_values, first = 0, 0.0, 0, None
        for what, spec, hosts, at, answer in asked:
            labels, vals = ranges.evaluate(spec, self.fleet, self.values,
                                           self.times_ns, hosts, at)
            if served_by is not None:
                answer = served_by(spec, hosts, at)
            bad, gap, n = compare.matrix_gap(answer, labels, at, vals)
            if bad:
                wrong += 1
                first = first or f"{what} ({spec['name']}): {bad}"
            worst = max(worst, gap)
            n_values += n
        if first:
            run.say("first wrong answer: " + first)
        tol = float(run.cell["tolerances"]["value_rel_err"])
        checks = [
            {"name": "answers_compared", "value": len(asked), "limit": 1,
             "holds": len(recs) >= 1, "rule": ">="},
            {"name": "answers_wrong", "value": wrong, "limit": 0,
             "holds": wrong == 0, "rule": "<="},
            {"name": "answers_failed", "value": failed, "limit": 0,
             "holds": failed == 0, "rule": "<="},
            {"name": "max_rel_err", "value": worst, "limit": tol,
             "holds": worst <= tol, "rule": "<="},
        ]
        if served_by is not None:
            return checks
        return checks + self.stream_checks(rng)

    def stream_checks(self, rng) -> list[dict]:
        """`verify_streams` (series, block) pairs, drawn from the seed
        without replacement over every series and every loaded block:
        each block's volume has to hold the series' readings of that
        block, bit for bit under the scalar decoder."""
        run = self.run
        total = self.fleet.n_series * self.n_blocks
        drawn = rng.choice(total, min(int(run.params["verify_streams"]),
                                      total), replace=False).tolist()
        pairs = {(s, self.block_starts[b])
                 for s, b in (divmod(k, self.n_blocks) for k in drawn)}
        per_block = self.points // self.n_blocks
        bits = self.values.view(np.uint64)

        def expect(s: int, bs: int):
            if (s, bs) not in pairs:
                return None
            b = self.block_starts.index(bs)
            cols = slice(b * per_block, (b + 1) * per_block)
            return self.times_ns[cols], bits[s, cols]

        n_streams, n_points, n_bytes, bad_streams, fault, vols = \
            compare.volume_streams_gap(
                os.path.join(run.service.data_dir, "data"), self.fleet,
                sorted({s for s, _ in pairs}), expect, self.node.n_shards,
                self.node.namespace)
        if fault:
            run.say("first wrong stream: " + fault)
        blocks = len({bs for _, bs in pairs})
        run.say(f"verified {n_streams} streams of {len(pairs)} drawn "
                f"(series, block) pairs over {blocks} blocks, {vols} volumes "
                f"({n_bytes / max(n_points, 1):.3f} B/dp)")
        return [
            {"name": "streams_compared", "value": n_streams,
             "limit": len(pairs), "holds": n_streams >= len(pairs) > 0,
             "rule": ">="},
            {"name": "streams_wrong", "value": bad_streams, "limit": 0,
             "holds": bad_streams == 0, "rule": "<="},
        ]

    def control(self) -> list[dict]:
        """The reference computed in float32 (the nearest precision below
        the float64 the configuration states), put in the program's
        place: the same requests, answered by it and rendered as the
        program renders. It has to come out as not correct."""
        def served(spec: dict, hosts: list[int], at: np.ndarray) -> dict:
            labels, vals = ranges.evaluate(spec, self.fleet, self.values,
                                           self.times_ns, hosts, at,
                                           dtype=np.float32)
            rows = []
            for lb, row in zip(labels, vals):
                keep = ~np.isnan(row)
                rows.append({"metric": lb, "values": [
                    [t / NS, repr(float(v))]
                    for t, v in zip(at[keep].tolist(), row[keep].tolist())]})
            return {"status": "success",
                    "data": {"resultType": "matrix", "result": rows}}

        return self.verify(served_by=served)

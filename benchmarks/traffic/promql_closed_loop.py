"""Traffic kind ``promql_closed_loop``: dashboards over a fleet. A closed
loop of ``workers`` clients, each sending one ``query_range`` and waiting
for its answer (TSBS ``run_queries --workers N``). Requests come in decks:
every deck holds each query type of the cell's ``mix`` as often as its
weight says (equal weights: TSBS runs the same number of queries of every
type), shuffled from ``--seed``; a request's hosts are drawn uniformly
without replacement from ``--seed``, as TSBS draws them. Every seed thus
offers the same numbers of the same sizes, in another order and of other
hosts. Set-up loads the configuration's history by remote-write with
flush held, lets the tick flush it through the device encoder, primes the
caches and warms every type.

Parameters (the cell's ``traffic_params``): ``workers``, ``mix``
({query type: weight}), ``prime`` ([{type, hosts}]: requests that set-up
sends once each before the warm-up, compared like the window's),
``warm_s``, ``warm_clean_rounds`` and ``warm_rounds`` (the loop runs in
rounds of ``warm_s`` seconds until ``warm_clean_rounds`` rounds in a row,
1 where the cell does not say, compiled nothing, ``warm_rounds`` at the
most),
``verify_max``, ``verify_streams``, and the two program
counters the traced interval's least bytes are reckoned from:
``plan_launch_counter`` (a regex over ``/metrics`` keys whose one group is
a plan's signature) and ``decode_streams_counter``.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import threading
import time

import numpy as np

from harness import compare, readers, reference, tsbs
from harness.client import BenchFailure, Client, check_log
from harness.loadgen import FAILED_MS, ClosedLoop, percentile

NS = tsbs.NS


def warm_done(misses: list[int], want_clean: int) -> bool:
    """The warm-up's rule: its last `want_clean` rounds compiled nothing
    (`misses`: the compiles of each round so far)."""
    return len(misses) >= want_clean and not any(misses[-want_clean:])


class Requests:
    """A sequence of (type name, hosts) made from one seeded stream, deck
    by deck, as far as it is asked for."""

    def __init__(self, rng, deck: list[str], types: dict, hosts: int):
        self.rng, self.deck, self.types, self.hosts = rng, deck, types, hosts
        self._made: list[tuple[str, list[int]]] = []
        self._lock = threading.Lock()

    def draw(self, name: str) -> tuple[str, list[int]]:
        want = self.types[name]["hosts"]
        if want == "all":
            return name, []
        return name, self.rng.choice(self.hosts, int(want),
                                     replace=False).tolist()

    def get(self, i: int) -> tuple[str, list[int]]:
        with self._lock:
            while i >= len(self._made):
                for j in self.rng.permutation(len(self.deck)).tolist():
                    self._made.append(self.draw(self.deck[j]))
            return self._made[i]


class Traffic:
    e2e = {"query_p95_ms": "ms", "query_rate": "queries/s"}

    def __init__(self, run):
        self.run = run
        p = run.params
        cfg = run.config
        self.node = run.node
        self.hosts = int(cfg["scale"])
        self.points = int(cfg["history_points"])
        self.workers = int(p["workers"])
        self.types = {name: run.load_json("queries", name)
                      for name in list(p["mix"])
                      + [q["type"] for q in p.get("prime", [])]}
        self.seed = run.seed
        self.facts: dict = {}
        self.sig_type: dict[str, str] = {}
        self.primed: list[tuple] = []

    # -- made from the seed, before the service is up -----------------------

    def prepare(self) -> None:
        self.fleet = tsbs.Fleet(self.seed, self.hosts)
        self.values = tsbs.walk(self.seed, self.fleet.n_series, self.points)
        # the newest block that is already past buffer_past: sealed by
        # time, so the first tick with flush enabled writes it out
        node = self.node
        end = time.time_ns() - node.buffer_past_ns - 60 * NS
        block = end - end % node.block_ns - node.block_ns
        # the last `points` readings of the sealed block
        first = block + node.block_ns - self.points * tsbs.INTERVAL_NS
        self.times_ns = first + np.arange(self.points, dtype=np.int64) \
            * tsbs.INTERVAL_NS
        self.block_start = block
        weights = {k: int(w) for k, w in self.run.params["mix"].items()}
        unit = functools.reduce(math.gcd, weights.values())
        deck = [name for name, w in weights.items() for _ in range(w // unit)]
        self.window_requests = Requests(
            np.random.default_rng([self.seed, 3]), deck, self.types,
            self.hosts)
        self.warm_requests = Requests(
            np.random.default_rng([self.seed, 4]), deck, self.types,
            self.hosts)
        self.window_requests.get(5_000)   # the rest as the window asks

    def _path(self, spec: dict, hosts: list[int]) -> str:
        start, end, step = reference.grid(
            spec, int(self.times_ns[0]), int(self.times_ns[-1]))
        return self._client.query_range_path(
            reference.promql(spec, hosts), start, end, step)

    def _make(self, requests: Requests, base: int, i: int):
        name, hosts = requests.get(base + i)
        return "GET", self._path(self.types[name], hosts), None, name

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        run = self.run
        c = self._client = Client(run.port)
        p = run.params
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
        run.say(f"load: {acked} samples in {t_load:.1f}s "
                f"({acked / t_load:.0f}/s)")
        c.runtime(flush_enabled=True, snapshot_enabled=True)
        t0 = time.perf_counter()
        while True:
            check_log(run.service.log_path)
            m = run.metrics()
            if m.get("coordinator_blocks_flushed", 0.0) >= \
                    self.node.n_shards:
                break
            if time.perf_counter() - t0 > 300:
                raise BenchFailure("the loaded block was not flushed")
            time.sleep(0.5)
        run.say(f"flush: {time.perf_counter() - t0:.1f}s")
        self.facts["flushed_samples"] = acked
        self.facts["flushed_data_bytes"] = readers.files_bytes(
            os.path.join(run.service.data_dir, "data", self.node.namespace),
            "-data.db")
        self.stream_bytes = self.facts["flushed_data_bytes"] \
            / self.fleet.n_series
        # `prime`: requests sent once each, so that the caches hold what
        # a deployment's would after hours of dashboards (the cell's
        # `prime_why`); their answers are compared with the window's
        t0 = time.perf_counter()
        for q in p.get("prime", []):
            spec = dict(self.types[q["type"]], hosts=q["hosts"])
            self.primed.append((spec, c.request("GET", self._path(spec, []))))
        run.say(f"prime: {time.perf_counter() - t0:.1f}s")
        # warm every type, one request at a time, and learn from the
        # program's launch counter which plan signature serves it; then
        # the loop itself: the block cache and the connections
        t0 = time.perf_counter()
        rx = re.compile(p["plan_launch_counter"])
        n_warm = 0
        for name in p["mix"]:
            before = run.metrics()
            for _ in range(3):
                _, hosts = self.warm_requests.draw(name)
                c.request("GET", self._path(self.types[name], hosts))
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

    def warm_rounds(self) -> list[int]:
        """Rounds of the loop itself, `warm_s` seconds each, until
        `warm_clean_rounds` of them in a row (1 where the cell does not
        say) met no shape that the process had not compiled yet (the
        decoder's row bucket follows from what the block cache has
        dropped, so only traffic finds them), `warm_rounds` at the most.
        Says and returns the compiles of each round."""
        run, p = self.run, self.run.params
        want_clean, most = int(p.get("warm_clean_rounds", 1)), \
            int(p["warm_rounds"])
        n_burst, misses = 0, []
        while len(misses) < most and not warm_done(misses, want_clean):
            before = run.metrics()
            burst = ClosedLoop(
                run.port, self.workers,
                functools.partial(self._make, self.warm_requests, n_burst),
                False)
            burst.start(float(p["warm_s"]))
            burst.join()
            bad = [r for r in burst.records if not r[4]]
            if bad:
                raise BenchFailure(
                    f"warm-up query failed: {bad[0][5][:300]!r}")
            n_burst += len(burst.records)
            after = run.metrics()
            misses.append(int(sum(
                n - before.get(k, 0.0) for k, n in after.items()
                if k.endswith("[miss]") and n > before.get(k, 0.0))))
        ended = (f"{want_clean} clean in a row"
                 if warm_done(misses, want_clean)
                 else f"the limit of {most} rounds")
        run.say(f"warm-up rounds: {n_burst} queries in {len(misses)} rounds, "
                f"compiles a round {misses}, ended by {ended}")
        return misses

    # -- the window -----------------------------------------------------------

    def start_window(self, seconds: float) -> None:
        self.loop = ClosedLoop(
            self.run.port, self.workers,
            functools.partial(self._make, self.window_requests, 0), True)
        self.loop.start(seconds)

    def end_window(self) -> dict:
        loop = self.loop
        loop.join()
        recs = loop.records
        seconds = loop.t_close - loop.t_open
        lat = [(r[3] - r[2]) * 1e3 if r[4] else FAILED_MS for r in recs]
        done = sum(1 for r in recs if r[4] and r[3] <= loop.t_close)
        by_type: dict[str, list[float]] = {}
        for r, ms in zip(recs, lat):
            by_type.setdefault(r[1], []).append(ms)
        self.run.say("per type: " + json.dumps({
            k: {"n": len(v), "p50_ms": round(percentile(v, 50), 2),
                "p95_ms": round(percentile(v, 95), 2)}
            for k, v in sorted(by_type.items())}))
        self.run.say(f"all: n={len(lat)} p50_ms={percentile(lat, 50):.2f} "
                     f"p95_ms={percentile(lat, 95):.2f} "
                     f"max_ms={max(lat) if lat else 0:.2f}")
        return {
            "attempted": len(recs),
            "failed": sum(1 for r in recs if not r[4]),
            "metrics": {"query_p95_ms": percentile(lat, 95),
                        "query_rate": done / seconds},
        }

    def traced(self, before: dict, after: dict) -> None:
        """Facts of the traced interval, from the program's counters as the
        launcher took them at the interval's two marks: the least bytes of
        the plans launched in it (each launch counted under its signature,
        each signature the query type that the warm-up saw it serve) and
        of the streams decoded in it (what the flush stored of a series
        in, 16 B a point out)."""
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
            total += d * reference.least_bytes(self.types[name], self.hosts,
                                               self.points)
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
            self.facts["traced_decode_least_bytes"] = streams * (
                self.stream_bytes + self.points * 16)

    # -- what decides `correct` -----------------------------------------------

    def verify(self, served_by=None) -> list[dict]:
        """Every answer of the window (a seeded sample of `verify_max`
        where there are more) against the reference; a seeded sample of
        the device-written streams under the scalar decoder. `served_by`
        exists for the control: it answers in the program's place."""
        run = self.run
        recs = [r for r in self.loop.records if r[4]]
        failed = len(self.loop.records) - len(recs)
        cap = int(run.params["verify_max"])
        rng = np.random.default_rng([self.seed, 5])
        if len(recs) > cap:
            idx = np.sort(rng.choice(len(recs), cap, replace=False))
            recs = [recs[i] for i in idx.tolist()]
        wrong, worst, n_values, first = 0, 0.0, 0, None
        cache: dict = {}
        asked = [(f"prime {spec['name']}", spec, [], answer)
                 for spec, answer in self.primed]
        for r in recs:
            name, hosts = self.window_requests.get(r[0])
            asked.append((f"request {r[0]}", self.types[name], hosts, r[5]))
        for what, spec, hosts, answer in asked:
            key = (spec["name"], spec["hosts"], tuple(hosts))
            if key not in cache:
                cache[key] = reference.evaluate(
                    spec, self.fleet, self.values, self.times_ns, hosts)
            labels, eval_ts, vals = cache[key]
            if served_by is not None:
                answer = served_by(spec, hosts)
            bad, gap, n = compare.matrix_gap(answer, labels, eval_ts, vals)
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
        picks = rng.choice(self.fleet.n_series, min(
            int(run.params["verify_streams"]), self.fleet.n_series),
            replace=False).tolist()
        bits = self.values.view(np.uint64)
        n_streams, n_points, n_bytes, bad_streams, fault, vols = \
            compare.volume_streams_gap(
                os.path.join(run.service.data_dir, "data"), self.fleet, picks,
                lambda s, bs: (self.times_ns, bits[s])
                if bs == self.block_start else None, self.node.n_shards,
                self.node.namespace)
        if fault:
            run.say("first wrong stream: " + fault)
        run.say(f"verified {len(asked)} answers ({n_values} values), "
                f"{n_streams} streams of {vols} volumes "
                f"({n_bytes / max(n_points, 1):.3f} B/dp)")
        checks.append(
            {"name": "streams_wrong", "value": bad_streams, "limit": 0,
             "holds": bad_streams == 0 and n_streams > 0, "rule": "<="})
        return checks

    def control(self) -> list[dict]:
        """The reference computed in float32 (the nearest precision below
        the float64 the configuration states), put in the program's
        place: the same requests, answered by it and rendered as the
        program renders. It has to come out as not correct."""
        def served(spec: dict, hosts: list[int]) -> dict:
            labels, eval_ts, vals = reference.evaluate(
                spec, self.fleet, self.values, self.times_ns, hosts,
                dtype=np.float32)
            rows = []
            for lb, row in zip(labels, vals):
                keep = ~np.isnan(row)
                rows.append({"metric": lb, "values": [
                    [t / NS, repr(float(v))]
                    for t, v in zip(eval_ts[keep].tolist(),
                                    row[keep].tolist())]})
            return {"status": "success",
                    "data": {"resultType": "matrix", "result": rows}}

        return self.verify(served_by=served)

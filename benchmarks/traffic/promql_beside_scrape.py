"""Traffic kind ``promql_beside_scrape``: dashboards over a fleet that
keeps reporting. ``promql_closed_loop``'s closed loop of ``query_range``
clients, unchanged (its class is loaded from its file and extended), and
beside it the fleet's own scrapes by remote-write, as a Prometheus in
front of the fleet would forward them: an OPEN loop at the fleet's own
rate. The hosts are cut into groups of ``scrape_samples_per_request``
samples (Prometheus ``queue_config.max_samples_per_send``); every group
is scraped once an interval, the groups' offsets spread evenly over the
interval in an order shuffled from ``--seed`` (as Prometheus spreads its
targets by their hash), so one request is due every ``interval_s /
groups`` seconds. A request is sent at its due time whether or not the
one before it is acked: a sender with a connection of its own is started
whenever every other is waiting for an ack. A sample's timestamp is its
due time (wall clock, ms; whole seconds, the namespace's time unit), its
value the next step of its series' TSBS walk from ``--seed``. Ack latency
is counted from the due time.

The scrapes run from the warm-up's rounds (after the single warm
requests have learnt the plan signatures in quiet), so that the warm-up
meets the tick's and the encoder's shapes, through the window to its
end. The warm-up's loop then goes on until a tick cycle closes
(``tick_cycle_counter``), so that every window opens at the same point
of the tick's cycle: a cycle takes a quarter of a window here, and a
window that opens anywhere in it holds now three snapshots, now four.
``attempted`` and ``failed`` count the window's queries and scrape
requests together. A cell whose file says ``report`` reports those
end-to-end metrics only; the others are printed on a line of their own.

What decides ``correct``, beside ``promql_closed_loop``'s checks (the
sealed hour's answers do not depend on the head block the scrapes land
in): after the window, every series of ``verify_hosts`` hosts drawn from
``--seed`` is read back through remote-read over the whole live interval
and compared with what the harness itself sent and saw acked
(``harness/readback.py``): every acked sample bit for bit, nothing that
was not sent, no request refused; at least one commitlog rotation and
one snapshot lie inside the run (a run that never rotated proved
nothing; both are counted from the files on disk, so a program without
the counters is judged alike). Then the kind stops the service itself,
at once, while the newest acks are in no snapshot yet, and holds the
same record against the disk as a restart would find it: every acked
sample in a replayed commitlog or in the newest snapshot volumes (the
device encoder's streams under the scalar decoder), and nothing in a
snapshot that was not sent. ``control`` judges the float32 reference in
the program's place and both comparisons against an ack record with one
sample in ``control_alter_every`` altered: each has to come out not
correct.

Parameters beside ``promql_closed_loop``'s: ``scrape_samples_per_request``,
``scrape_rounds_max`` (how many intervals of values are made ahead),
``verify_hosts``, ``control_alter_every``, ``tick_cycle_counter`` (the
``/metrics`` key that grows by one as a tick cycle closes) and
``tick_cycle_wait_s`` (how long the warm-up waits for it at the most),
and ``window_facts``: for each fact its ``keys`` (regexes over the
``/metrics`` keys) and ``scale``; the fact is the keys' increase between
the window's opening and its close as the kind reads them itself, times
the scale. The launcher's own second reading comes after the profiler's
stop in a traced run, and the service ticks on through that; the tick's
and the write route's metrics are ``fact_ratio`` over these.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import queue
import re
import threading
import time

import numpy as np

from harness import readback, tsbs
from harness.client import BenchFailure, Client, parse_metrics
from harness.loadgen import ClosedLoop, percentile


def _load_base():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "promql_closed_loop.py")
    spec = importlib.util.spec_from_file_location(
        "traffic_promql_closed_loop", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _load_base()


def window_facts(spec: dict, at_open: dict, at_close: dict,
                 acks_ms: list[float]) -> dict:
    """The window's facts: for each entry of `spec` (the cell's
    `window_facts`) the increase of its keys between the two readings of
    /metrics, times its scale; one whose keys the program does not have
    is left out, and its metric with it. Beside them the harness's own
    clock on the write side."""
    facts = {}
    for fact, entry in spec.items():
        rxs = [re.compile(rx) for rx in entry["keys"]]
        keys = [k for k in at_close if any(rx.fullmatch(k) for rx in rxs)]
        if keys:
            facts[fact] = float(entry["scale"]) * sum(
                at_close[k] - at_open.get(k, 0.0) for k in keys)
    facts["window_write_acks"] = len(acks_ms)
    facts["window_write_ack_ms"] = float(sum(acks_ms))
    return facts


class Schedule:
    """Which hosts the j-th scrape request carries, of which interval,
    and how long after the first it is due: made from the seed alone."""

    def __init__(self, seed: int, hosts: int, samples_per_request: int,
                 interval_s: int):
        n_f = len(tsbs.CPU_FIELDS)
        self.hosts_per = samples_per_request // n_f
        if self.hosts_per * n_f != samples_per_request \
                or hosts % self.hosts_per:
            raise ValueError(
                f"{samples_per_request} samples a request do not cut "
                f"{hosts} hosts x {n_f} gauges into whole groups")
        self.groups = hosts // self.hosts_per
        self.spacing_ms, rest = divmod(interval_s * 1000, self.groups)
        if rest or self.spacing_ms % 1000:
            # the namespace's time unit is the second: the encoder would
            # truncate a timestamp off it
            raise ValueError(
                f"{self.groups} groups do not spread over {interval_s} s "
                "in whole seconds")
        self.order = np.random.default_rng([seed, 6]).permutation(
            self.groups).tolist()

    def request(self, j: int) -> tuple[int, int, int, int]:
        """(interval, first host, last host + 1, ms after the start)."""
        rnd, k = divmod(j, self.groups)
        g = self.order[k]
        return (rnd, g * self.hosts_per, (g + 1) * self.hosts_per,
                j * self.spacing_ms)


class Scraper:
    """The open loop. A record is (j, interval, host0, host1, timestamp
    ms, due, t_send, t_done, acked, error text): `due`, `t_send` and
    `t_done` on `time.perf_counter`'s clock."""

    def __init__(self, port: int, schedule: Schedule, fleet: tsbs.Fleet,
                 live: np.ndarray):
        self.port, self.schedule, self.fleet, self.live = \
            port, schedule, fleet, live
        self.records: list[tuple] = []
        self.exhausted = False
        self._close_at = float("inf")
        self._waiting = 0          # requests queued or unanswered
        self._lock = threading.Lock()
        self._queue: queue.Queue = queue.Queue()
        self._senders: list[threading.Thread] = []

    def start(self) -> None:
        # the first request is due at the next whole second of the wall
        # clock; every later one by the schedule, on perf_counter's clock
        now = time.time()
        self.t0_ms = (int(now) + 1) * 1000
        self.t0 = time.perf_counter() + (self.t0_ms / 1e3 - now)
        self.fleet.label_bytes()
        self._due = threading.Thread(target=self._schedule,
                                     name="scrape-due", daemon=True)
        self._due.start()

    def close_at(self, t_close: float) -> None:
        """Requests due before `t_close` are still sent."""
        self._close_at = t_close

    def _schedule(self) -> None:
        n_f = len(tsbs.CPU_FIELDS)
        j = 0
        while True:
            rnd, h0, h1, after_ms = self.schedule.request(j)
            due = self.t0 + after_ms / 1e3
            if due >= self._close_at:
                break
            if rnd >= self.live.shape[1]:
                self.exhausted = True
                break
            t_ms = self.t0_ms + after_ms
            body, n = tsbs.write_body(
                self.fleet, self.live[:, rnd:rnd + 1],
                np.array([t_ms * 1_000_000], np.int64),
                h0 * n_f, h1 * n_f, 0, 1)
            while True:
                wait = min(due, self._close_at) - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.2))     # close_at may move nearer
            if due >= self._close_at:
                break
            with self._lock:
                self._waiting += 1
                # open loop: never wait for a sender to come free
                if self._waiting > len(self._senders):
                    t = threading.Thread(
                        target=self._send, daemon=True,
                        name=f"scrape-{len(self._senders)}")
                    self._senders.append(t)
                    t.start()
            self._queue.put(((j, rnd, h0, h1, t_ms, due), body, n))
            j += 1
        with self._lock:
            for _ in self._senders:
                self._queue.put(None)

    def _send(self) -> None:
        client = Client(self.port)
        try:
            while True:
                item = self._queue.get()
                if item is None:
                    return
                head, body, n = item
                t_send = time.perf_counter()
                try:
                    got = client.remote_write(body)
                    acked, err = got == n, (
                        "" if got == n else f"acked {got} of {n}")
                except (BenchFailure, OSError) as e:
                    acked, err = False, str(e)
                with self._lock:
                    self._waiting -= 1
                    self.records.append(head + (
                        t_send, time.perf_counter(), acked, err))
        finally:
            client.close()

    def join(self, grace_s: float = 60.0) -> None:
        deadline = self._close_at + grace_s
        self._due.join(max(0.0, deadline - time.perf_counter()))
        for t in self._senders:
            t.join(max(0.0, deadline - time.perf_counter()))
        if self._due.is_alive() or any(t.is_alive() for t in self._senders):
            raise BenchFailure("a scrape request was still unanswered a "
                               "minute after the window closed")
        self.records.sort(key=lambda r: r[0])


def _check(name, value, limit, rule) -> dict:
    holds = value <= limit if rule == "<=" else value >= limit
    return {"name": name, "value": value, "limit": limit, "holds": holds,
            "rule": rule}


class Traffic(_base.Traffic):
    # measured as the base kind measures them; a cell's `report` says
    # which of them its result line holds
    e2e = {"query_p95_ms": "ms", "query_rate": "queries/s"}

    def prepare(self) -> None:
        super().prepare()
        p = self.run.params
        # each series' walk goes on where the loaded hour ends
        rounds = int(p["scrape_rounds_max"])
        longer = tsbs.walk(self.seed, self.fleet.n_series,
                           self.points + rounds)
        if not np.array_equal(longer[:, :self.points], self.values):
            raise BenchFailure("the longer walk does not begin as the "
                               "loaded hour does")
        self.live = np.ascontiguousarray(longer[:, self.points:])
        self.schedule = Schedule(
            self.seed, self.hosts, int(p["scrape_samples_per_request"]),
            int(self.run.config["interval_s"]))
        self.scraper = None

    def warm_rounds(self) -> list[int]:
        run = self.run
        self.scraper = Scraper(run.port, self.schedule, self.fleet,
                               self.live)
        self.scraper.start()
        run.say(f"scrapes: one request of "
                f"{self.schedule.hosts_per * len(tsbs.CPU_FIELDS)} samples "
                f"every {self.schedule.spacing_ms} ms from "
                f"{self.scraper.t0_ms} ms, {self.schedule.groups} groups")
        misses = super().warm_rounds()
        self._to_a_cycles_close()
        return misses

    def _to_a_cycles_close(self) -> None:
        """The warm-up's loop, going on until a tick cycle closes (or
        `tick_cycle_wait_s` have passed: said, not an error). Its
        requests come from a seeded stream of their own."""
        run, p = self.run, self.run.params
        key, most = p["tick_cycle_counter"], float(p["tick_cycle_wait_s"])
        client = Client(run.port)
        loop = ClosedLoop(
            run.port, self.workers, functools.partial(
                self._make, _base.Requests(
                    np.random.default_rng([self.seed, 8]),
                    self.window_requests.deck, self.types, self.hosts), 0),
            False)
        try:
            seen = parse_metrics(client.metrics_text()).get(key)
            if seen is None:
                raise BenchFailure(f"no {key} on /metrics: the service "
                                   "does not say when a tick cycle closes")
            loop.start(most)
            now = seen
            while now == seen and time.perf_counter() < loop.t_close:
                time.sleep(0.2)
                now = parse_metrics(client.metrics_text())[key]
            loop.t_close = time.perf_counter()   # no further request
            loop.join()
        finally:
            client.close()
        bad = [r for r in loop.records if not r[4]]
        if bad:
            raise BenchFailure(f"warm-up query failed: {bad[0][5][:300]!r}")
        run.say(f"warm-up went on for {loop.t_close - loop.t_open:.1f}s "
                f"({len(loop.records)} queries) until "
                + (f"tick cycle {now:g} closed" if now != seen else
                   f"its limit: no tick cycle closed in {most:g}s"))

    # -- the window -----------------------------------------------------------

    def _metrics_now(self) -> dict:
        """/metrics over a connection of its own: the close is read on a
        timer's thread, beside the launcher's."""
        client = Client(self.run.port)
        try:
            return parse_metrics(client.metrics_text())
        finally:
            client.close()

    def start_window(self, seconds: float) -> None:
        self._at_open, self._at_close = self._metrics_now(), None
        super().start_window(seconds)
        self.scraper.close_at(self.loop.t_close)

        def at_close() -> None:
            self._at_close = self._metrics_now()

        self._closer = threading.Timer(
            max(0.0, self.loop.t_close - time.perf_counter()), at_close)
        self._closer.daemon = True
        self._closer.start()

    def window_facts(self, acks_ms: list[float]) -> None:
        self._closer.join(60.0)
        if self._at_close is None:
            raise BenchFailure("no /metrics within a minute of the "
                               "window's close")
        self.facts.update(window_facts(
            self.run.params["window_facts"], self._at_open, self._at_close,
            acks_ms))

    def end_window(self) -> dict:
        out = super().end_window()
        self.scraper.join()
        if self.scraper.exhausted:
            raise BenchFailure("the run outlasted scrape_rounds_max "
                               "intervals of values")
        loop = self.loop
        mine = [r for r in self.scraper.records
                if loop.t_open <= r[5] < loop.t_close]
        acks = [(r[7] - r[5]) * 1e3 for r in mine if r[8]]
        late = [(r[6] - r[5]) * 1e3 for r in mine]
        failed = [r for r in mine if not r[8]]
        self.window_facts(acks)
        self.run.say("scrapes in the window: " + json.dumps({
            "n": len(mine), "failed": len(failed),
            "senders": len(self.scraper._senders),
            "ack_from_due_p50_ms": round(percentile(acks, 50), 2),
            "ack_from_due_p95_ms": round(percentile(acks, 95), 2),
            "ack_from_due_max_ms": round(max(acks, default=0.0), 2),
            "sent_late_max_ms": round(max(late, default=0.0), 2)}))
        if failed:
            self.run.say(f"first failed scrape: request {failed[0][0]}: "
                         f"{failed[0][9][:300]}")
        out["attempted"] += len(mine)
        out["failed"] += len(failed)
        report = self.run.cell.get("report")
        if report:
            self.run.say("measured, not reported by this cell: " + json.dumps(
                {k: v for k, v in out["metrics"].items() if k not in report}))
            out["metrics"] = {k: out["metrics"][k] for k in report}
        return out

    # -- what decides `correct` -----------------------------------------------

    def verify(self, served_by=None) -> list[dict]:
        if served_by is not None:       # the float32 control answers queries
            return super().verify(served_by)
        live = self.live_checks()       # first: the newest acks are in no
        disk = self.disk_checks()       # snapshot yet; this stops the service
        return super().verify() + live + disk

    def control(self) -> list[dict]:
        """The float32 reference in the program's place, and the read-back
        and the disk judged against an ack record with one sample in
        `control_alter_every` altered: each has to come out not correct."""
        answers = super().control()
        every = int(self.run.params["control_alter_every"])
        record = readback.altered(self._sent, every)
        _n, missing, wrong, _u, _f = readback.acked_gap(self._returned,
                                                        record)
        acks = [_check("acked_samples_missing", missing, 0, "<="),
                _check("acked_samples_wrong", wrong, 0, "<=")]
        _n, missing, wrong, _s, _b, _f = readback.durable_gap(
            *self._on_disk, record)
        disk = [_check("durable_samples_missing", missing, 0, "<="),
                _check("durable_samples_wrong", wrong, 0, "<=")]
        for what, checks in (
                ("float32 reference", answers),
                (f"one sent sample in {every} altered, read back", acks),
                (f"one sent sample in {every} altered, on disk", disk)):
            self.run.say(f"control, {what}: " + (
                "not correct" if not all(c["holds"] for c in checks)
                else "CORRECT (the comparison does not see it)"))
        return answers + acks + disk

    def live_checks(self) -> list[dict]:
        """Every series of `verify_hosts` seeded hosts read back over the
        live interval against what was sent; the run's rotations
        (commitlog files that were opened after the first scrape and are
        still there: a retired one goes once a later snapshot covers it,
        so this counts at least one per rotation still in doubt) and
        snapshots (volumes under snapshots/ that are newer than the
        first scrape). Both are counted on the disk, so a program
        without counters is held to the same."""
        from m3_tpu.storage.fileset import list_filesets

        run, p, records = self.run, self.run.params, self.scraper.records
        log_dir = os.path.join(run.service.data_dir, "commitlog",
                               self.node.namespace)

        def logs_since_start() -> int:
            # commitlog-<ns of its opening>.db
            return sum(1 for name in os.listdir(log_dir)
                       if name.startswith("commitlog-")
                       and name.endswith(".db")
                       and int(name[10:-3]) > self.scraper.t0_ms * 1_000_000)

        # a window shorter than a tick cycle (a rehearsal's) may close
        # before the tick that follows its first ack: wait for that one
        t_end = time.perf_counter() + 5.0 \
            + 3 * float(self.node.coordinator["tick_interval_s"])
        while not logs_since_start() and time.perf_counter() < t_end:
            time.sleep(0.25)
        rng = np.random.default_rng([self.seed, 7])
        self._hosts = hosts = np.sort(rng.choice(
            self.hosts, min(int(p["verify_hosts"]), self.hosts),
            replace=False)).tolist()
        self._sent = sent = readback.sent_samples(
            self.fleet, hosts, [(r[1], r[2], r[3], r[4], r[8])
                                for r in records],
            self.live.view(np.uint64))
        last_ms = max((r[4] for r in records), default=self.scraper.t0_ms)
        client = Client(run.port)
        try:
            self._returned = readback.read_hosts(client, hosts,
                                                 self.scraper.t0_ms, last_ms)
        finally:
            client.close()
        n, missing, wrong, unasked, fault = readback.acked_gap(
            self._returned, sent)
        if fault:
            run.say("first wrong sample: " + fault)
        refused = sum(1 for r in records if not r[8])
        rotations = logs_since_start()
        root = os.path.join(run.service.data_dir, "snapshots")
        snapshots = sum(
            1 for shard in range(self.node.n_shards)
            for _bs, vol in list_filesets(root, self.node.namespace, shard,
                                          all_volumes=True)
            if vol >= self.scraper.t0_ms)
        run.say(f"read back {n} acked samples of {len(hosts)} hosts "
                f"({len(records)} scrape requests since "
                f"{self.scraper.t0_ms} ms, {refused} refused); {rotations} "
                f"commitlog files newer than the first scrape, "
                f"{snapshots} snapshot volumes")
        return [
            _check("acked_samples_compared", n, 1, ">="),
            _check("acked_samples_missing", missing, 0, "<="),
            _check("acked_samples_wrong", wrong, 0, "<="),
            _check("samples_unasked_for", unasked, 0, "<="),
            _check("write_requests_refused", refused, 0, "<="),
            _check("rotations_in_run", rotations, 1, ">="),
            _check("snapshots_in_run", snapshots, 1, ">="),
        ]

    def disk_checks(self) -> list[dict]:
        """The guarantee an ack gives: the sample is on the disk. The
        kind stops the service now (Ctrl-C: it closes its storage, as
        run.py would after the comparison), before a later tick's
        snapshot covers the newest acks, and holds the ack record
        against what a restart would find: the commitlog files replayed
        and the newest snapshot volumes under the scalar decoder."""
        run = self.run
        t0 = time.perf_counter()
        run.service.stop()
        t_stop = time.perf_counter() - t0
        logged, snapshotted, n_entries = readback.on_disk(
            run.service.data_dir, self.node.namespace, self.node.n_shards,
            self.fleet, self._hosts)
        self._on_disk = (logged, snapshotted)
        n, missing, wrong, n_streams, bad_streams, fault = \
            readback.durable_gap(logged, snapshotted, self._sent)
        if fault:
            run.say("first fault on the disk: " + fault)
        in_logs_alone = sum(
            1 for key, row in self._sent.items()
            for t_ms, (_bits, acked) in row.items()
            if acked and t_ms not in snapshotted.get(key, {}))
        run.say(f"service stopped in {t_stop:.1f}s; on disk {n} acked "
                f"samples of {len(self._hosts)} hosts: {n_entries} "
                f"commitlog entries replayed, {n_streams} snapshot streams; "
                f"{in_logs_alone} of the samples in the logs alone")
        return [
            _check("durable_samples_compared", n, 1, ">="),
            _check("durable_samples_missing", missing, 0, "<="),
            _check("durable_samples_wrong", wrong, 0, "<="),
            {"name": "snapshot_streams_wrong", "value": bad_streams,
             "limit": 0, "rule": "<=",
             "holds": bad_streams == 0 and n_streams > 0},
        ]

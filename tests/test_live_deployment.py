"""The deployment ``tsbs-cpu-2k-live`` against its plain reference, and the
stage clock on the write route and the tick (ISSUE 37).

The coordinator runs in process with the configuration file's own
``node.coordinator`` block at the rehearsal's 24 hosts. A sealed hour is
loaded by remote-write and flushed; then scrape rounds by remote-write
are interleaved from the seed with ticks (snapshot and rotation on) and
with the five query types. Every query answer equals the float64 numpy
reference as the harness compares it, every acked sample comes back
through remote-read bit for bit, and again after the database is closed
and reopened from disk.
"""

import json
import os
import sys
from urllib.parse import parse_qs, urlparse

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

from harness import compare, readback, reference, tsbs  # noqa: E402
from harness.client import Client, Node  # noqa: E402
from m3_tpu.services.coordinator import CoordinatorService  # noqa: E402
from m3_tpu.utils import trace  # noqa: E402
from m3_tpu.utils.instrument import default_registry  # noqa: E402

NS = tsbs.NS
QUERY_TYPES = ["single-groupby-1-1-1", "single-groupby-1-8-1",
               "single-groupby-5-8-1", "cpu-max-all-8", "double-groupby-1"]
WRITE_STAGES = [trace.STAGE_REQUEST, trace.STAGE_WRITE_DECODE,
                trace.STAGE_WRITE_BATCH, trace.STAGE_WRITE_COMMITLOG,
                trace.STAGE_WRITE_BUFFER]
NEW_STAGES = set(WRITE_STAGES[1:]) | {
    trace.STAGE_TICK, trace.STAGE_TICK_SNAPSHOT,
    trace.STAGE_ENCODE_WAIT, trace.STAGE_TICK_FLUSH,
    trace.STAGE_TICK_ROTATE}


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = _load("configs", "tsbs-cpu-2k-live")
HOSTS = CONFIG["rehearse"]["scale"]
POINTS = CONFIG["history_points"]


class InProcess:
    """What harness/client.py Client is to a served coordinator, over
    CoordinatorAPI.handle."""

    def __init__(self, api):
        self.api = api
        self.headers = {}

    def request(self, method, path, body=None):
        u = urlparse(path)
        res = self.api.handle(method, u.path, parse_qs(u.query), body or b"")
        assert res[0] == 200, res[2][:400]
        self.headers = res[3] if len(res) == 4 else {}
        return res[2]

    def remote_write(self, body):
        return json.loads(self.request(
            "POST", "/api/v1/prom/remote/write", body))["samples"]


class Deployment:
    """The live configuration's node, a fleet and its data from a seed,
    and a clock the test moves."""

    def __init__(self, root, seed):
        self.seed, self.node = seed, Node(CONFIG)
        self.cfg = self.node.rendered(str(root / "m3data"),
                                      str(root / "kv.json"))
        self.fleet = tsbs.Fleet(seed, HOSTS)
        walk = tsbs.walk(seed, self.fleet.n_series, POINTS + 30)
        self.values, self.live = walk[:, :POINTS], \
            np.ascontiguousarray(walk[:, POINTS:])
        # the last hour of a block that is sealed and past buffer_past
        block = 1_790_000_000 * NS
        block -= block % self.node.block_ns
        self.times_ns = block + self.node.block_ns - (
            POINTS - np.arange(POINTS, dtype=np.int64)) * tsbs.INTERVAL_NS
        self.now = block + self.node.block_ns + self.node.buffer_past_ns \
            + 60 * NS
        self.start()

    def start(self):
        self.svc = CoordinatorService(self.cfg)
        self.svc.db.open(self.now)
        self.client = InProcess(self.svc.api)

    def tick(self):
        return self.svc.db.tick(self.now)

    def load_and_flush(self):
        n_f = len(tsbs.CPU_FIELDS)
        for h0 in range(0, HOSTS, 12):
            body, n = tsbs.write_body(self.fleet, self.values, self.times_ns,
                                      h0 * n_f, (h0 + 12) * n_f, 0, POINTS)
            assert self.client.remote_write(body) == n
        assert self.tick()["flushed"] == self.node.n_shards

    def scrape(self, rnd, h0, h1):
        """One remote-write request: hosts [h0, h1) of interval `rnd`, at
        the clock's time. Returns the harness's record of it."""
        n_f = len(tsbs.CPU_FIELDS)
        body, n = tsbs.write_body(
            self.fleet, self.live[:, rnd:rnd + 1],
            np.array([self.now], np.int64), h0 * n_f, h1 * n_f, 0, 1)
        assert self.client.remote_write(body) == n
        return rnd, h0, h1, self.now // 1_000_000, True

    def query(self, name, hosts):
        spec = _load("queries", name)
        start, end, step = reference.grid(
            spec, int(self.times_ns[0]), int(self.times_ns[-1]))
        answer = self.client.request("GET", Client.query_range_path(
            None, reference.promql(spec, hosts), start, end, step))
        labels, eval_ts, vals = reference.evaluate(
            spec, self.fleet, self.values, self.times_ns, hosts)
        return compare.matrix_gap(answer, labels, eval_ts, vals)

    def read_back(self, sent, t0_ms):
        returned = readback.read_hosts(
            self.client, list(range(HOSTS)), t0_ms, self.now // 1_000_000)
        return readback.acked_gap(returned, readback.sent_samples(
            self.fleet, list(range(HOSTS)), sent,
            self.live.view(np.uint64)))


def _rotations():
    return default_registry().snapshot()[0].get(
        ("storage.commitlog_rotations", ()), 0)


@pytest.mark.parametrize("seed", [36, 2**31 + 36, 2**31 + 1036])
def test_reads_beside_writes_equal_the_reference_and_survive_a_restart(
        tmp_path, seed):
    d = Deployment(tmp_path, seed)
    try:
        d.load_and_flush()
        rng = np.random.default_rng([seed, 9])
        t0_ms = d.now // 1_000_000
        rotations0 = _rotations()
        sent, stats, worst, n_answers = [], [], 0.0, 0
        for rnd in range(30):
            # the round's two host groups, a tick and two queries, in an
            # order drawn from the seed; the clock moves with each
            steps = [("scrape", 0, 12), ("scrape", 12, 24), ("tick",),
                     ("query",), ("query",)]
            for i in rng.permutation(len(steps)).tolist():
                d.now += NS
                step = steps[i]
                if step[0] == "scrape":
                    sent.append(d.scrape(rnd, step[1], step[2]))
                elif step[0] == "tick":
                    stats.append(d.tick())
                else:
                    name = QUERY_TYPES[n_answers % len(QUERY_TYPES)]
                    want = _load("queries", name)["hosts"]
                    hosts = [] if want == "all" else rng.choice(
                        HOSTS, int(want), replace=False).tolist()
                    bad, gap, n = d.query(name, hosts)
                    assert bad is None and n > 0, (name, bad)
                    worst = max(worst, gap)
                    n_answers += 1
            d.now += 5 * NS
        assert n_answers == 60 and worst <= 1e-12
        assert sum(s["snapshotted"] for s in stats) >= 25
        assert _rotations() - rotations0 >= 25
        n, missing, wrong, unasked, fault = d.read_back(sent, t0_ms)
        assert (n, missing, wrong, unasked) == (30 * 240, 0, 0, 0), fault
        # closed and reopened from disk: snapshots and commitlogs bring
        # every acked sample back
        d.svc.db.close()
        d.start()
        n, missing, wrong, unasked, fault = d.read_back(sent, t0_ms)
        assert (n, missing, wrong, unasked) == (30 * 240, 0, 0, 0), fault
        bad, gap, n = d.query("double-groupby-1", [])
        assert bad is None and gap <= 1e-12
    finally:
        d.svc.db.close()


@pytest.mark.parametrize("store", ["sound", "acks_before_it_appends"])
def test_the_disk_holds_every_acked_sample_once_the_service_has_stopped(
        tmp_path, monkeypatch, store):
    """What the live cell holds against the disk after it has stopped the
    service (harness/readback.py on_disk, durable_gap): a snapshot covers
    the first rounds, the commitlogs alone the later ones. A store whose
    every third append is lost (acked, in the buffers, never logged)
    reads back whole and is found out on the disk."""
    from m3_tpu.storage.commitlog import CommitLogWriter

    if store != "sound":
        real, calls = CommitLogWriter.write_many, {"n": 0}

        def lossy(self, *args, **kwargs):
            calls["n"] += 1
            if calls["n"] % 3:
                real(self, *args, **kwargs)

        monkeypatch.setattr(CommitLogWriter, "write_many", lossy)
    d = Deployment(tmp_path, 11)
    hosts = list(range(HOSTS))
    try:
        t0_ms = d.now // 1_000_000
        sent = []
        for rnd in range(6):
            for h0 in (0, 12):
                d.now += NS
                sent.append(d.scrape(rnd, h0, h0 + 12))
            if rnd == 2:
                d.now += NS
                assert d.tick()["snapshotted"] >= 1
        n, missing, wrong, unasked, fault = d.read_back(sent, t0_ms)
        assert (n, missing, wrong, unasked) == (6 * 240, 0, 0, 0), fault
    finally:
        d.svc.db.close()
    logged, snapshotted, n_entries = readback.on_disk(
        str(tmp_path / "m3data"), d.node.namespace, d.node.n_shards,
        d.fleet, hosts)
    record = readback.sent_samples(d.fleet, hosts, sent,
                                   d.live.view(np.uint64))
    n, missing, wrong, n_streams, bad_streams, fault = readback.durable_gap(
        logged, snapshotted, record)
    assert (n, wrong, n_streams, bad_streams) == (6 * 240, 0, 240, 0), fault
    assert all(len(row) == 3 for row in snapshotted.values())
    if store == "sound":
        assert missing == 0 and n_entries >= 3 * 240
        # an altered record is found on the disk as in the read-back
        assert readback.durable_gap(
            logged, snapshotted, readback.altered(record, 200))[2] == 7
    else:
        # appends 9 and 12 of 12 came after the snapshot: 120 samples each
        assert missing == 240 and "in no commitlog" in fault


def _stage_counts(route):
    """{stage: (observations, wall self-time sum)} of one route."""
    _c, _g, _t, hists = default_registry().snapshot()
    return {dict(tags)["stage"]: (hcount, hsum)
            for (name, tags), (_b, _cnt, hsum, hcount) in hists.items()
            if name == "query.stage.seconds"
            and dict(tags).get("route") == route}


def _delta(after, before):
    return {k: (n - before.get(k, (0, 0.0))[0], s - before.get(k, (0, 0.0))[1])
            for k, (n, s) in after.items()
            if n > before.get(k, (0, 0.0))[0]}


def _trace_spans(trace_id):
    return [s for s in trace.default_tracer().find(trace_id)
            if "self_us" in s]


@pytest.mark.parametrize("path", ["serial", "pipelined"])
def test_a_remote_write_publishes_each_write_stage_once(tmp_path,
                                                        monkeypatch, path):
    if path == "pipelined":
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "100")
    else:
        monkeypatch.setenv("M3_TPU_PIPELINE", "0")
    d = Deployment(tmp_path, 5)
    try:
        before = _stage_counts("remote_write")
        d.scrape(0, 0, 24)
        got = _delta(_stage_counts("remote_write"), before)
        # the pipelined path waits once a WAL chunk (240 samples: 3)
        want = {s: 1 for s in WRITE_STAGES}
        if path == "pipelined":
            want[trace.STAGE_WRITE_COMMITLOG] = 3
            want[trace.STAGE_WRITE_BUFFER] = 3
        assert {k: n for k, (n, _s) in got.items()} == want
        # self-times add up to the root's own wall time
        spans = {s["name"]: s for s in _trace_spans(
            d.client.headers["M3-Trace-Id"])}
        assert set(spans) == set(WRITE_STAGES)
        root_us = spans[trace.STAGE_REQUEST]["duration_us"]
        total_us = sum(s for _n, s in got.values()) * 1e6
        assert total_us == pytest.approx(root_us, abs=5.0)
    finally:
        d.svc.db.close()


@pytest.mark.parametrize("encoder", ["native", "device"])
def test_a_tick_publishes_each_tick_stage_once_a_cycle(tmp_path,
                                                       monkeypatch, encoder):
    monkeypatch.setenv("M3_TPU_DEVICE_OPS",
                       "1" if encoder == "device" else "0")
    d = Deployment(tmp_path, 6)
    try:
        d.scrape(0, 0, 24)
        d.now += NS
        before = _stage_counts("tick")
        counters0 = default_registry().snapshot()[0]
        stats = d.tick()
        got = _delta(_stage_counts("tick"), before)
        counts = {k: n for k, (n, _s) in got.items()}
        assert stats["snapshotted"] >= 1
        launches = counts.pop(trace.STAGE_ENCODE_WAIT, 0)
        # one stage an encoder launch on the device rung, none on the
        # native one
        assert (launches >= 1) if encoder == "device" else launches == 0
        assert counts == {trace.STAGE_TICK: 1, trace.STAGE_TICK_SNAPSHOT: 1,
                          trace.STAGE_TICK_FLUSH: 1,
                          trace.STAGE_TICK_ROTATE: 1}
        spans = [s for s in trace.default_tracer().recent(400)
                 if s["name"] == trace.STAGE_TICK]
        cycle = _trace_spans(spans[-1]["trace_id"])
        assert sum(s["self_us"] for s in cycle) == pytest.approx(
            spans[-1]["duration_us"], abs=5.0)
        assert sum(s for _n, s in got.values()) * 1e6 == pytest.approx(
            spans[-1]["duration_us"], abs=5.0)
        counters = default_registry().snapshot()[0]

        def grew(name, tags=()):
            return counters.get((name, tags), 0) - counters0.get(
                (name, tags), 0)

        assert grew("storage.snapshot_samples") == 240
        # a stream of one sample: the block's start, the sample, the end
        assert 240 * 8 < grew("storage.snapshot_bytes") < 240 * 40
        assert grew("storage.commitlog_rotations") == 1
    finally:
        d.svc.db.close()


def test_a_query_requests_stages_are_unchanged(tmp_path):
    d = Deployment(tmp_path, 7)
    try:
        d.load_and_flush()
        before = _stage_counts("query_range")
        bad, _gap, n = d.query("single-groupby-1-8-1", list(range(8)))
        assert bad is None and n > 0
        got = _delta(_stage_counts("query_range"), before)
        assert trace.STAGE_REQUEST in got and trace.STAGE_RENDER in got
        assert not set(got) & NEW_STAGES
        assert set(got) <= {
            trace.STAGE_REQUEST, trace.STAGE_PARSE_PLAN,
            trace.STAGE_QUERY_IDS, trace.STAGE_READ_MANY, trace.STAGE_GATHER,
            trace.STAGE_DECODE_HOST, trace.STAGE_DECODE_WAIT,
            trace.STAGE_DECODE_COMPILE, trace.STAGE_SLAB_PREP,
            trace.STAGE_PLAN_DISPATCH, trace.STAGE_PLAN_WAIT,
            trace.STAGE_PLAN_COMPILE, trace.STAGE_EVAL, trace.STAGE_RENDER}
    finally:
        d.svc.db.close()

"""The served read across sealed blocks, as ``tsbs-cpu-14h.ranges`` asks
it (ISSUE 39), in process on the CPU.

The deployment's own ``node.coordinator`` block at the rehearsal's 12
hosts, three whole 2 h blocks loaded by remote-write and flushed, and a
decoded-block cache smaller than the (series, block) streams. A
``query_range`` at 1 m and at 1 h steps over a range that starts
mid-block and crosses every block is compared with the plain numpy
reference (``benchmarks/harness/ranges.py``) and with the float64
interpreter; the three counters the cell reads add up (lookups = series x
blocks touched, ``storage_read_series`` = the ids read), the CSR
assembly is timed once a read, and a repeat of the same range is served
from the hot tier without a read. On a seven-block namespace the request
thread's timed parts of a pooled read (plan, leg wait, consume, assembly)
make up the stage ``read_many``'s self-time.
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

from harness import compare, ranges, reference, tsbs  # noqa: E402
from harness.client import Client, Node  # noqa: E402
from m3_tpu.services.coordinator import CoordinatorService  # noqa: E402
from m3_tpu.storage.cache import BlockCache  # noqa: E402
from m3_tpu.utils.instrument import default_registry  # noqa: E402

NS = tsbs.NS
BLOCKS = 3


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


CONFIG = _load("configs", "tsbs-cpu-14h")
HOSTS = CONFIG["rehearse"]["scale"]


class Deployment:
    """The deployment's node over three sealed blocks, loaded and
    flushed, its block cache cut to `cache_entries`."""

    def __init__(self, root, seed, cache_entries):
        node = self.node = Node(CONFIG)
        self.fleet = tsbs.Fleet(seed, HOSTS)
        self.points = BLOCKS * node.block_ns // tsbs.INTERVAL_NS
        self.values = tsbs.walk(seed, self.fleet.n_series, self.points)
        first = 1_790_000_000 * NS
        first -= first % node.block_ns
        self.times_ns = first + np.arange(self.points, dtype=np.int64) \
            * tsbs.INTERVAL_NS
        self.block_starts = [first + b * node.block_ns for b in range(BLOCKS)]
        self.now = first + BLOCKS * node.block_ns + node.buffer_past_ns \
            + 60 * NS
        self.svc = CoordinatorService(node.rendered(
            str(root / "m3data"), str(root / "kv.json")))
        db = self.svc.db
        db.block_cache = BlockCache(cache_entries)
        for ns in db.namespaces.values():
            for shard in ns.shards.values():
                shard.cache = db.block_cache
        db.open(self.now)
        n_f = len(tsbs.CPU_FIELDS)
        for p0 in range(0, self.points, 360):
            body, n = tsbs.write_body(self.fleet, self.values, self.times_ns,
                                      0, HOSTS * n_f, p0, p0 + 360)
            assert json.loads(self.request(
                "POST", "/api/v1/prom/remote/write", body))["samples"] == n
        assert db.tick(self.now)["flushed"] == node.n_shards * BLOCKS

    def request(self, method, path, body=b""):
        u = urlparse(path)
        res = self.svc.api.handle(method, u.path, parse_qs(u.query), body)
        assert res[0] == 200, res[2][:400]
        return res[2]

    def query(self, spec, hosts, offset, range_s):
        start, end, step = ranges.grid(spec, int(self.times_ns[0]), offset,
                                       range_s)
        return self.request("GET", Client.query_range_path(
            None, reference.promql(spec, hosts), start, end, step))

    def blocks_touched(self, spec, offset, range_s):
        """Blocks the engine's fetch range overlaps: from the first step
        less the larger of the window and the 5 m lookback, to the last."""
        at = ranges.eval_ts(spec, int(self.times_ns[0]), offset, range_s)
        t_min = int(at[0]) - max(int(spec["window_s"]), 300) * NS
        return sum(1 for bs in self.block_starts
                   if bs + self.node.block_ns > t_min and bs <= int(at[-1]))


def _counters():
    counters, _g, _t, hists = default_registry().snapshot()
    assemble = hists.get(("db.read_assemble_seconds", ()))
    return {"hit": counters.get(("storage.block_cache.hit", ()), 0),
            "miss": counters.get(("storage.block_cache.miss", ()), 0),
            "series": counters.get(("storage.read_series", ()), 0),
            "tier_hit": counters.get(("storage.hot_tier.hit", ()), 0),
            "assembled": assemble[3] if assemble else 0}


def _grew(before):
    return {k: v - before[k] for k, v in _counters().items()}


# (query type, hosts, minutes after the first sample, range): each
# starts mid-block, 67 minutes in, and ends in the last block
QUERIES = [("single-groupby-5-1-12", [3], 67, 4 * 3600),
           ("double-groupby-1.12h", [], 67, 4 * 3600)]


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    # the compiled plans and the device decoder, as on the chip
    mp.setenv("M3_TPU_QUERY_COMPILE", "1")
    mp.setenv("M3_TPU_DEVICE_OPS", "1")
    d = Deployment(tmp_path_factory.mktemp("cross_block"), 2**31 + 39,
                   cache_entries=40)
    yield d
    d.svc.db.close()
    mp.undo()


def test_the_cache_is_smaller_than_the_streams(deployment):
    assert deployment.fleet.n_series * BLOCKS > 40


@pytest.mark.parametrize("name,hosts,offset,range_s", QUERIES,
                         ids=[q[0] for q in QUERIES])
def test_a_range_across_every_block_equals_the_reference_and_the_counters_add_up(
        deployment, monkeypatch, name, hosts, offset, range_s):
    d, spec = deployment, _load("queries", name)
    at = ranges.eval_ts(spec, int(d.times_ns[0]), offset, range_s)
    labels, want = ranges.evaluate(spec, d.fleet, d.values, d.times_ns,
                                   hosts, at)
    n_series = len(reference.series_of(spec, d.fleet, hosts))
    blocks = d.blocks_touched(spec, offset, range_s)
    assert blocks == BLOCKS

    before = _counters()
    served = d.query(spec, hosts, offset, range_s)
    grew = _grew(before)
    bad, gap, n = compare.matrix_gap(served, labels, at, want)
    assert bad is None and n > 0 and gap <= 1e-12
    # one lookup a (series, block), the ids read once, one assembly
    assert grew["hit"] + grew["miss"] == n_series * blocks
    assert grew["series"] == n_series
    assert grew["assembled"] == 1 and grew["tier_hit"] == 0

    # the float64 interpreter, on the host, answers alike
    monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "0")
    monkeypatch.setenv("M3_TPU_DEVICE_OPS", "0")
    interpreted = d.query(spec, hosts, offset, range_s)
    bad, gap, _ = compare.matrix_gap(interpreted, labels, at, want)
    assert bad is None and gap <= 1e-12
    monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")
    monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")

    # the same range again: the hot tier serves it, nothing is read
    before = _counters()
    again = d.query(spec, hosts, offset, range_s)
    grew = _grew(before)
    assert grew["tier_hit"] == 1
    assert grew["series"] == grew["hit"] == grew["miss"] == 0
    assert compare.matrix_gap(again, labels, at, want)[0] is None


def test_an_evicting_cache_both_hits_and_misses(deployment):
    """Ranges that overlap, each over more streams than the cache holds:
    the later reads find some of the earlier ones' blocks, not all."""
    d = deployment
    spec = _load("queries", "cpu-max-all-1")
    before = _counters()
    for offset in (5, 65, 125):
        at = ranges.eval_ts(spec, int(d.times_ns[0]), offset, 3 * 3600)
        labels, want = ranges.evaluate(spec, d.fleet, d.values, d.times_ns,
                                       [7], at)
        served = d.query(spec, [7], offset, 3 * 3600)
        assert compare.matrix_gap(served, labels, at, want)[0] is None
    grew = _grew(before)
    assert grew["hit"] > 0 and grew["miss"] > 0
    assert grew["series"] == 3 * 10


# -- the read's parts against its stage --------------------------------------

PARTS = ("storage.read_plan_seconds", "storage.read_leg_wait_seconds",
         "storage.read_consume_seconds", "db.read_assemble_seconds")


def _seven_blocks(root, n_series=320, points=720):
    """Eight shards of seven sealed 2 h blocks, written as fileset volumes
    (the shape of a 12 h read in tsbs-cpu-14h: 56 groups)."""
    from m3_tpu.encoding.m3tsz import hostpath
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.fileset import FilesetWriter
    from m3_tpu.storage.options import (DatabaseOptions, IndexOptions,
                                        NamespaceOptions, RetentionOptions)
    from m3_tpu.utils.xtime import TimeUnit

    block = 2 * 3600 * NS
    first = 1_790_006_400 * NS
    db = Database(str(root), DatabaseOptions(n_shards=8,
                                             block_cache_entries=400))
    ns = db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(retention_ns=100 * block,
                                   block_size_ns=block),
        index=IndexOptions(enabled=False),
        writes_to_commitlog=False, snapshot_enabled=False))
    ids = [b"closure-%04d" % i for i in range(n_series)]
    by_shard: dict = {}
    for sid in ids:
        by_shard.setdefault(ns.shard_set.lookup(sid), []).append(sid)
    rng = np.random.default_rng(41)
    for shard_id, sids in by_shard.items():
        for b in range(7):
            bs = first + b * block
            times = np.broadcast_to(
                bs + np.arange(points, dtype=np.int64) * 10 * NS,
                (len(sids), points)).copy()
            values = rng.normal(50.0, 10.0, (len(sids), points))
            streams = hostpath.encode_blocks(
                times, values.view(np.uint64), np.full(len(sids), bs),
                np.full(len(sids), points, np.int32), TimeUnit.SECOND,
                False)
            w = FilesetWriter(db.fs_root, "default", shard_id, bs, block, 0)
            for sid, stream in zip(sids, streams):
                w.write_series(sid, b"", stream)
            w.close()
    db.open(first + 7 * block)
    return db, ns, ids, first, first + 7 * block


def _read_sums(route):
    counters, _g, _t, hists = default_registry().snapshot()
    out = {name: hists[(name, ())][2] if (name, ()) in hists else 0.0
           for name in PARTS}
    key = ("query.stage.seconds", (("route", route), ("stage", "read_many")))
    out["read_many"] = hists[key][2] if key in hists else 0.0
    return out


def test_the_reads_timed_parts_make_up_its_stage(tmp_path, monkeypatch):
    """Plan + leg wait + consume + assembly of seven-block reads equal the
    stage read_many's self-time within 5%: the rest is the submits, the
    span and the histograms' own bookkeeping."""
    from m3_tpu.utils import trace

    monkeypatch.setenv("M3_TPU_PIPELINE", "1")
    db, ns, ids, lo, hi = _seven_blocks(tmp_path / "seven")
    route = "t_read_closure"
    tr = trace.default_tracer()
    try:
        ns.read_many_ragged(ids, lo, hi)            # warm the readers
        before = _read_sums(route)
        with tr.activate(trace.SpanContext(trace.new_trace_id(), "", True,
                                           route)):
            for _ in range(4):
                with tr.stage(trace.STAGE_READ_MANY):
                    ns.read_many_ragged(ids, lo, hi)
        after = _read_sums(route)
    finally:
        db.close()
    grew = {k: after[k] - before[k] for k in after}
    parts = sum(grew[k] for k in PARTS)
    assert grew["read_many"] > 0
    assert parts == pytest.approx(grew["read_many"], rel=0.05)


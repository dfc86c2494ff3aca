"""Pipelined dataflow (storage/pipeline.py, ROADMAP #2).

The contract under test: with the pipeline armed (the default), every
result is IDENTICAL to the ``M3_TPU_PIPELINE=0`` serial path — read
parity (times and value bits), write parity (buffer contents, WAL entry
stream, per-entry isolation), fan-out parity (warnings, merge order) —
while the executor overlaps gather/RPC legs with decode/insert legs and
reports the overlap on the saturation and ?explain=analyze planes.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from m3_tpu.storage import commitlog, pipeline
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import (
    DatabaseOptions,
    IndexOptions,
    NamespaceOptions,
    RetentionOptions,
)
from m3_tpu.utils import faults, querystats

NS = 10**9
BLOCK = 3600 * NS
START = 1_600_000_000 * NS
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disable()
    yield
    faults.disable()


def build_multiblock_db(tmp_path, n_series=256, n_blocks=4, n_shards=4,
                        points=6, cache_entries=0):
    """Fileset-backed namespace with MANY (shard, block) groups — the
    shape the pipelined read path schedules over."""
    from m3_tpu.encoding.m3tsz import hostpath
    from m3_tpu.storage.fileset import FilesetWriter
    from m3_tpu.utils.xtime import TimeUnit

    db = Database(str(tmp_path / "db"), DatabaseOptions(
        n_shards=n_shards, block_cache_entries=cache_entries))
    ns = db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                   block_size_ns=BLOCK),
        index=IndexOptions(enabled=False),
        writes_to_commitlog=False, snapshot_enabled=False))
    ids = [b"series-%06d" % i for i in range(n_series)]
    by_shard: dict[int, list[bytes]] = {}
    for sid in ids:
        by_shard.setdefault(ns.shard_set.lookup(sid), []).append(sid)
    rng = np.random.default_rng(11)
    for shard_id, sids in by_shard.items():
        for b in range(n_blocks):
            bs = START + b * BLOCK
            B, T = len(sids), points
            times = np.broadcast_to(
                bs + np.arange(T, dtype=np.int64) * 10 * NS, (B, T)).copy()
            values = rng.normal(50.0, 10.0, (B, T))
            streams = hostpath.encode_blocks(
                times, values.view(np.uint64), np.full(B, bs, np.int64),
                np.full(B, T, np.int32), TimeUnit.SECOND, False)
            w = FilesetWriter(db.fs_root, "default", shard_id, bs, BLOCK, 0)
            for sid, stream in zip(sids, streams):
                w.write_series(sid, b"", stream)
            w.close()
    db.open(START + n_blocks * BLOCK)
    return db, ns, ids


# ---------------------------------------------------------------------------
# executor primitives
# ---------------------------------------------------------------------------


class TestExecutor:
    def test_map_ordered_preserves_order(self):
        ex = pipeline.PipelineExecutor(workers=3)
        out = list(ex.map_ordered(
            [lambda i=i: (time.sleep(0.002 * ((7 - i) % 3)), i)[1]
             for i in range(20)], depth=4))
        assert out == list(range(20))

    def test_map_ordered_raises_in_submission_order(self):
        ex = pipeline.PipelineExecutor(workers=2)

        def boom():
            raise ValueError("task 3 failed")

        fns = [lambda i=i: i for i in range(3)] + [boom] \
            + [lambda: 99] * 3
        it = ex.map_ordered(fns, depth=3)
        assert [next(it), next(it), next(it)] == [0, 1, 2]
        with pytest.raises(ValueError, match="task 3 failed"):
            next(it)

    def test_lane_is_fifo_and_exclusive(self):
        ex = pipeline.PipelineExecutor(workers=4)
        lane = ex.lane("test-wal")
        order: list[int] = []
        running = threading.Semaphore(1)

        def task(i):
            assert running.acquire(blocking=False), "lane ran concurrently"
            try:
                time.sleep(0.001)
                order.append(i)
            finally:
                running.release()

        futs = [lane.submit(lambda i=i: task(i)) for i in range(25)]
        for f in futs:
            f.result()
        assert order == list(range(25))

    def test_lane_failure_isolated_per_task(self):
        ex = pipeline.PipelineExecutor(workers=2)
        lane = ex.lane("test-wal-2")
        f1 = lane.submit(lambda: "ok-1")
        f2 = lane.submit(lambda: (_ for _ in ()).throw(OSError("disk")))
        f3 = lane.submit(lambda: "ok-3")
        assert f1.result() == "ok-1"
        with pytest.raises(OSError, match="disk"):
            f2.result()
        assert f3.result() == "ok-3"  # the lane keeps draining

    def test_nested_submission_runs_inline(self):
        """run_stages called FROM a worker degrades to the serial
        interleaving instead of waiting on the pool it occupies."""
        ex = pipeline.PipelineExecutor(workers=1)

        def nested():
            assert pipeline.in_worker()
            assert not pipeline.active()
            stats = pipeline.run_stages(
                list(range(5)), lambda i: i * 2,
                lambda i, p: consumed.append(p))
            return stats.items

        consumed: list[int] = []
        assert ex.submit(nested).result() == 5
        assert consumed == [0, 2, 4, 6, 8]

    def test_submit_fault_point_fires_on_caller(self):
        ex = pipeline.PipelineExecutor(workers=2)
        with faults.active("pipeline.task=error:n1"):
            with pytest.raises(faults.InjectedError):
                ex.submit(lambda: 1)
        assert ex.submit(lambda: 1).result() == 1

    def test_run_stages_overlap_accounting(self):
        stats = pipeline.run_stages(
            list(range(8)),
            lambda i: (time.sleep(0.004), i)[1],
            lambda i, p: time.sleep(0.004), depth=4)
        assert stats.items == 8
        assert set(stats.stages) == {"gather", "decode"}
        assert stats.wall_s > 0
        if pipeline.active():
            # stage sums exceed wall when legs genuinely overlapped
            assert sum(stats.stages.values()) > stats.wall_s

    def test_task_queues_ride_the_saturation_plane(self):
        from m3_tpu.utils.instrument import default_registry

        pipeline.default_executor()
        pipeline.client_executor()
        _c, gauges, _t, _h = default_registry().snapshot()
        names = {dict(tags).get("queue") for (name, tags) in gauges
                 if name == "queue.depth"}
        assert "pipeline_tasks_storage" in names
        assert "pipeline_tasks_client" in names


# ---------------------------------------------------------------------------
# read path
# ---------------------------------------------------------------------------


class TestPipelinedReads:
    def test_parity_with_serial_path(self, tmp_path, monkeypatch):
        db, ns, ids = build_multiblock_db(tmp_path)
        try:
            monkeypatch.setenv("M3_TPU_PIPELINE", "0")
            serial = ns.read_many(ids, START, START + 4 * BLOCK)
            monkeypatch.setenv("M3_TPU_PIPELINE", "1")
            piped = ns.read_many(ids, START, START + 4 * BLOCK)
            for (st, sv), (pt, pv) in zip(serial, piped):
                np.testing.assert_array_equal(st, pt)
                np.testing.assert_array_equal(sv, pv)
        finally:
            db.close()

    def test_buffer_overlay_parity(self, tmp_path, monkeypatch):
        """Buffered overwrites still win over flushed points (the
        filesets-then-buffer parts order survives the pipeline)."""
        db, ns, ids = build_multiblock_db(tmp_path, n_series=64)
        try:
            t_hit = START + 20 * NS
            for sid in ids[:16]:
                ns.write(sid, t_hit, int(np.float64(-7.0).view(np.uint64)))
            monkeypatch.setenv("M3_TPU_PIPELINE", "0")
            serial = ns.read_many(ids, START, START + 4 * BLOCK)
            monkeypatch.setenv("M3_TPU_PIPELINE", "1")
            piped = ns.read_many(ids, START, START + 4 * BLOCK)
            for (st, sv), (pt, pv) in zip(serial, piped):
                np.testing.assert_array_equal(st, pt)
                np.testing.assert_array_equal(sv, pv)
            row = piped[0]
            assert row[1][row[0] == t_hit].view(np.float64) == -7.0
        finally:
            db.close()

    def test_dispatch_economy_preserved(self, tmp_path):
        """No more batched decodes than (shard, block) groups (one a
        read since PR 31), cache hits never re-enter the batch — the
        PR-1 contracts, pipeline armed."""
        from m3_tpu.utils import dispatch

        db, ns, ids = build_multiblock_db(tmp_path, n_series=300,
                                          n_blocks=3,
                                          cache_entries=10_000)
        try:
            before = dispatch.counters["m3tsz_decode_batch_groups"]
            first = ns.read_many(ids, START, START + 3 * BLOCK)
            groups = dispatch.counters["m3tsz_decode_batch_groups"] - before
            assert 0 < groups <= 4 * 3
            before = dispatch.counters["m3tsz_decode_batch_groups"]
            second = ns.read_many(ids, START, START + 3 * BLOCK)
            assert dispatch.counters["m3tsz_decode_batch_groups"] == before
            for (t1, v1), (t2, v2) in zip(first, second):
                np.testing.assert_array_equal(t1, t2)
                np.testing.assert_array_equal(v1, v2)
        finally:
            db.close()

    @pytest.mark.parametrize("n_shards,n_blocks", [(8, 1), (4, 3)])
    def test_one_decode_per_read(self, tmp_path, n_shards, n_blocks):
        """The read's whole miss set is one batched decode, whatever its
        (shard, block) groups; the query record still counts the groups
        and the decode still sits in the pipeline's decode leg."""
        from m3_tpu.utils import dispatch

        db, ns, ids = build_multiblock_db(
            tmp_path, n_series=128, n_blocks=n_blocks, n_shards=n_shards,
            cache_entries=10_000)
        try:
            before = dispatch.counters["m3tsz_decode_batch_groups"]
            st = querystats.start(query="pipeline-test")
            res = ns.read_many(ids, START, START + n_blocks * BLOCK)
            querystats.finish(st)
            assert dispatch.counters["m3tsz_decode_batch_groups"] \
                - before == 1
            assert st.blocks_read == n_shards * n_blocks
            assert st.pipeline_groups == n_shards * n_blocks
            assert sum(st.decode_rungs.values()) == 1
            assert st.cache_misses == 128 * n_blocks
            assert st.pipeline_stage_s["decode"] > 0
            assert all(len(t) == 6 * n_blocks for t, _ in res)
        finally:
            db.close()

    def test_decode_follows_the_last_gather(self, tmp_path, monkeypatch):
        """No stream decodes before every group's gather has landed, and
        every shard finalizes after the decode."""
        from m3_tpu.encoding.m3tsz import hostpath
        from m3_tpu.storage.namespace import Namespace
        from m3_tpu.storage.shard import _FilesetReadGroup

        db, ns, ids = build_multiblock_db(tmp_path, n_series=64,
                                          n_blocks=3)
        events = []
        gather = _FilesetReadGroup.gather
        decode = hostpath.decode_streams_batch
        finalize = Namespace._finalize_shard_read

        def spy(name, fn):
            def wrapped(*a, **kw):
                out = fn(*a, **kw)
                events.append(name)
                return out
            return wrapped

        monkeypatch.setattr(_FilesetReadGroup, "gather",
                            spy("gather", gather))
        monkeypatch.setattr(hostpath, "decode_streams_batch",
                            spy("decode", decode))
        monkeypatch.setattr(Namespace, "_finalize_shard_read",
                            spy("finalize", finalize))
        try:
            ns.read_many(ids, START, START + 3 * BLOCK)
            assert events == ["gather"] * 12 + ["decode"] + ["finalize"] * 4
        finally:
            db.close()

    @pytest.mark.parametrize("path", ["device", "native", "scalar"])
    def test_record_and_ragged_parity_with_serial_hatch(
            self, tmp_path, monkeypatch, path):
        """read_many_ragged and the query record (blocks, bytes, cache
        counts, the rung) equal the serial hatch's on every rung."""
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", path)
        db, ns, ids = build_multiblock_db(tmp_path, n_series=96,
                                          n_blocks=3, n_shards=8,
                                          cache_entries=10_000)

        def run(pipeline_env):
            monkeypatch.setenv("M3_TPU_PIPELINE", pipeline_env)
            db.block_cache._entries.clear()
            st = querystats.start(query="pipeline-test")
            t, v, offs = ns.read_many_ragged(ids, START, START + 3 * BLOCK)
            querystats.finish(st)
            return (t, v, offs), (st.blocks_read, st.bytes_decoded,
                                  st.cache_hits, st.cache_misses,
                                  set(st.decode_rungs))

        try:
            (serial, s_rec), (piped, p_rec) = run("0"), run("1")
            for a, b in zip(serial, piped):
                np.testing.assert_array_equal(a, b)
            assert p_rec == s_rec
            assert p_rec[0] == 8 * 3 and p_rec[4] == {path}
        finally:
            db.close()

    def test_serial_hatch_pins_seed_gather(self, tmp_path, monkeypatch):
        """M3_TPU_PIPELINE=0 runs the seed read body: no group objects,
        no columnar row index on the readers (the bisection hatch)."""
        db, ns, ids = build_multiblock_db(tmp_path, n_series=64)
        try:
            monkeypatch.setenv("M3_TPU_PIPELINE", "0")
            ns.read_many(ids, START, START + 4 * BLOCK)
            readers = [r for s in ns.shards.values()
                       for r in s._filesets.values()]
            assert readers
            assert all(getattr(r, "_rows", None) is None for r in readers)
            monkeypatch.setenv("M3_TPU_PIPELINE", "1")
            ns.read_many(ids, START, START + 4 * BLOCK)
            assert any(getattr(r, "_rows", None) is not None
                       for r in readers)
        finally:
            db.close()

    def test_columnar_gather_matches_walk(self, tmp_path):
        """FilesetReader.gather_many (cached row index) returns exactly
        what the merge-join walk returns, absent ids and dups included."""
        db, ns, ids = build_multiblock_db(tmp_path, n_series=64,
                                          n_blocks=1)
        try:
            shard = next(iter(ns.shards.values()))
            reader = next(iter(shard._filesets.values()))
            want = [ids[0], b"absent-id", ids[5], ids[0], ids[63]]
            np.random.default_rng(0)
            assert reader.gather_many(want) == reader.read_many(want)
            all_plus = ids + [b"nope-%d" % i for i in range(10)]
            assert reader.gather_many(all_plus) == reader.read_many(all_plus)
        finally:
            db.close()

    def test_querystats_and_explain_report_overlap(self, tmp_path):
        db, ns, ids = build_multiblock_db(tmp_path)
        try:
            st = querystats.start(query="pipeline-test")
            ns.read_many(ids, START, START + 4 * BLOCK)
            assert st.pipeline_groups > 0
            assert set(st.pipeline_stage_s) == {"gather", "decode"}
            doc = st.to_dict()
            assert doc["pipeline"]["groups"] == st.pipeline_groups
            assert doc["pipeline"]["stage_sum_ms"] >= 0
            assert "overlap" in doc["pipeline"]
            querystats.finish(st)
        finally:
            db.close()

    def test_limit_chunking_still_bounds_decode(self, tmp_path,
                                                monkeypatch):
        from m3_tpu.storage.limits import QueryLimitError, QueryLimits
        from m3_tpu.storage.namespace import Namespace
        from m3_tpu.utils import dispatch

        db, ns, ids = build_multiblock_db(tmp_path, n_series=512,
                                          n_blocks=1)
        monkeypatch.setattr(Namespace, "READ_MANY_LIMIT_CHUNK", 64)
        try:
            db.limits = QueryLimits(max_datapoints=30)
            db.limits.start_query()
            before = dispatch.counters["m3tsz_decode_batch_groups"]
            with pytest.raises(QueryLimitError):
                ns.read_many(ids, START, START + BLOCK)
            assert dispatch.counters["m3tsz_decode_batch_groups"] \
                - before <= 1
            db.limits.end_query()
        finally:
            db.close()


# ---------------------------------------------------------------------------
# write path
# ---------------------------------------------------------------------------


def write_entries(n, repeat=5):
    return [(b"m-%d" % (i % repeat), [(b"k", b"v%d" % (i % 3))],
             START + i * NS, float(i)) for i in range(n)]


def small_db(path, flush_every=1 << 20):
    db = Database(str(path), DatabaseOptions(
        n_shards=2, commitlog_flush_every_bytes=flush_every))
    db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                   block_size_ns=BLOCK),
        index=IndexOptions(enabled=True, block_size_ns=BLOCK)))
    db.open(START)
    return db


class TestPipelinedWrites:
    def test_parity_with_serial_path(self, tmp_path, monkeypatch):
        """Chunked-lane write_batch produces the same buffers, the same
        WAL ENTRY stream (chunk framing may differ — entries never do),
        and the same index as the serial path."""
        from m3_tpu.index.query import TermQuery
        from m3_tpu.utils.ident import tags_to_id

        ents = write_entries(300)
        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "64")
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        db_p = small_db(tmp_path / "piped")
        assert db_p.write_batch("default", ents) == [None] * len(ents)
        monkeypatch.setenv("M3_TPU_PIPELINE", "0")
        db_s = small_db(tmp_path / "serial")
        assert db_s.write_batch("default", ents) == [None] * len(ents)
        for db in (db_p, db_s):
            db._commitlogs["default"].flush(fsync=True)
        sids = sorted({tags_to_id(m, t) for m, t, _ts, _v in ents})
        for sid in sids:
            for nsn in ("default",):
                a = db_p.namespaces[nsn].read(sid, START, START + BLOCK)
                b = db_s.namespaces[nsn].read(sid, START, START + BLOCK)
                np.testing.assert_array_equal(a[0], b[0])
                np.testing.assert_array_equal(a[1], b[1])
        [pp] = commitlog.log_files(db_p.commitlog_dir("default"))
        [ps] = commitlog.log_files(db_s.commitlog_dir("default"))
        ep = [(e.series_id, e.time_ns, e.value_bits, e.unit)
              for e in commitlog.replay(pp)]
        es = [(e.series_id, e.time_ns, e.value_bits, e.unit)
              for e in commitlog.replay(ps)]
        assert ep == es
        q = TermQuery(b"k", b"v0")
        got_p = db_p.namespaces["default"].query_ids(q, START,
                                                     START + BLOCK)
        got_s = db_s.namespaces["default"].query_ids(q, START,
                                                     START + BLOCK)
        assert sorted(d.series_id for d in got_p) == \
            sorted(d.series_id for d in got_s)
        db_p.close()
        db_s.close()

    def test_wal_chunk_failure_degrades_only_that_chunk(self, tmp_path,
                                                        monkeypatch):
        """An injected WAL failure on chunk 2 degrades exactly chunk 2's
        entries; chunks 1 and 3 are logged, buffered and acked — and the
        degraded entries never reach the buffers (buffered => logged)."""
        from m3_tpu.utils.ident import tags_to_id

        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "50")
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        db = small_db(tmp_path / "db")
        # distinct series per entry so buffer checks are per-entry exact
        ents = [(b"solo-%03d" % i, [(b"k", b"v")], START + i * NS, float(i))
                for i in range(150)]
        with faults.active("commitlog.write=error:n2"):
            res = db.write_batch("default", ents)
        ok = [i for i, r in enumerate(res) if r is None]
        bad = [i for i, r in enumerate(res) if r is not None]
        assert ok == list(range(0, 50)) + list(range(100, 150))
        assert bad == list(range(50, 100))
        ns = db.namespaces["default"]
        for i in ok:
            sid = tags_to_id(ents[i][0], ents[i][1])
            t, _v = ns.read(sid, START, START + BLOCK)
            assert len(t) == 1
        for i in bad:
            sid = tags_to_id(ents[i][0], ents[i][1])
            t, _v = ns.read(sid, START, START + BLOCK)
            assert len(t) == 0
        db.close()

    def test_small_batches_stay_serial(self, tmp_path, monkeypatch):
        """Batches at or under the chunk size take the serial body (no
        lane round-trips for the common small ingest batch)."""
        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "4096")
        db = small_db(tmp_path / "db")
        lane_before = len(pipeline.default_executor()._lanes)
        assert db.write_batch("default", write_entries(100)) == [None] * 100
        assert len(pipeline.default_executor()._lanes) == lane_before
        db.close()


# ---------------------------------------------------------------------------
# fan-out (session + fanout zones)
# ---------------------------------------------------------------------------


def quorum_session(tmp_path, n_nodes=3, n_shards=4):
    from m3_tpu.client.session import Session
    from m3_tpu.cluster import placement as pl
    from m3_tpu.cluster.placement import Instance
    from m3_tpu.cluster.topology import ConsistencyLevel, TopologyMap

    insts = [Instance(f"node-{i}") for i in range(n_nodes)]
    p = pl.initial_placement(insts, n_shards=n_shards, replica_factor=2)
    nodes = {}
    for inst in insts:
        db = Database(str(tmp_path / inst.id),
                      DatabaseOptions(n_shards=n_shards))
        db.create_namespace("default")
        db.open(START)
        nodes[inst.id] = db
    sess = Session(TopologyMap(p), nodes,
                   write_consistency=ConsistencyLevel.MAJORITY,
                   read_consistency=ConsistencyLevel.ONE)
    return sess, nodes


class _FailingConn:
    """read_batch-capable conn that always fails (a down node — every
    batched read surface fails, including the CSR wire path)."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def read_batch(self, *a, **kw):
        raise ConnectionError("node is down")

    def read_batch_csr(self, *a, **kw):
        raise ConnectionError("node is down")


class TestFanoutOverlap:
    def test_fetch_many_parity_and_overlap(self, tmp_path, monkeypatch):
        from m3_tpu.utils.ident import tags_to_id

        sess, nodes = quorum_session(tmp_path)
        sids = []
        for i in range(48):
            tags = [(b"i", b"%02d" % i)]
            sess.write_many("default",
                            [(b"m", tags, START + k * NS, float(k))
                             for k in range(4)])
            sids.append(tags_to_id(b"m", tags))
        monkeypatch.setenv("M3_TPU_PIPELINE", "0")
        serial = sess.fetch_many("default", sids, START, START + BLOCK)
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        piped = sess.fetch_many("default", sids, START, START + BLOCK)
        for (st, sv), (pt, pv) in zip(serial, piped):
            np.testing.assert_array_equal(st, pt)
            np.testing.assert_array_equal(sv, pv)
        for db in nodes.values():
            db.close()

    def test_partial_failure_warning_contract_holds(self, tmp_path):
        """A down node on the overlapped fan-out degrades to
        ReadWarnings once consistency is met — PR-2's partial-result
        contract, overlap enabled."""
        from m3_tpu.utils.ident import tags_to_id

        sess, nodes = quorum_session(tmp_path)
        tags = [(b"k", b"v")]
        sess.write_many("default", [(b"m", tags, START + NS, 1.0)])
        sid = tags_to_id(b"m", tags)
        # fail a node that actually REPLICATES this series' shard
        victim = sess.topology.hosts_for_shard(sess._shard(sid))[0]
        sess.connections[victim] = _FailingConn(nodes[victim])
        warnings: list = []
        out = sess.fetch_many("default", [sid],
                              START, START + BLOCK, warnings=warnings)
        assert len(out) == 1 and len(out[0][0]) == 1
        assert warnings and warnings[0].scope == "session"
        assert any(w.name == victim for w in warnings)
        for db in nodes.values():
            db.close()

    def test_armed_faults_pin_serial_fanout(self, tmp_path):
        """Under an armed fault plan the fan-out stays serial so the
        per-host injection schedule is deterministic (the legs would
        otherwise race for the per-point RNG stream)."""
        from m3_tpu.utils.ident import tags_to_id

        sess, nodes = quorum_session(tmp_path)
        tags = [(b"k", b"v")]
        sess.write_many("default", [(b"m", tags, START + NS, 1.0)])
        sid = tags_to_id(b"m", tags)
        with faults.active("session.host_call=error:p1.0", seed=3):
            with pytest.raises(Exception):
                sess.fetch_many("default", [sid], START, START + BLOCK)
        out = sess.fetch_many("default", [sid], START, START + BLOCK)
        assert len(out[0][0]) == 1
        for db in nodes.values():
            db.close()


# ---------------------------------------------------------------------------
# lock-wait before/after proof (satellite: the measured-contention story)
# ---------------------------------------------------------------------------


_LOCK_PROFILE_CHILD = r"""
import json, os, sys, threading
sys.path.insert(0, os.environ["M3_REPO"])
import numpy as np
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import (DatabaseOptions, IndexOptions,
                                    NamespaceOptions, RetentionOptions)

NS = 10**9
BLOCK = 3600 * NS
START = 1_600_000_000 * NS
db = Database(sys.argv[1], DatabaseOptions(
    n_shards=2, commitlog_flush_every_bytes=256))
db.create_namespace("default", NamespaceOptions(
    retention=RetentionOptions(retention_ns=1000 * BLOCK,
                               block_size_ns=BLOCK),
    index=IndexOptions(enabled=False)))
db.open(START)

def writer(w):
    for b in range(12):
        ents = [(b"m-%d-%d" % (w, i), [(b"k", b"v")],
                 START + (b * 64 + i) * NS, float(i))
                for i in range(64)]
        assert db.write_batch("default", ents) == [None] * len(ents)

threads = [threading.Thread(target=writer, args=(w,)) for w in range(4)]
for t in threads: t.start()
for t in threads: t.join()
from m3_tpu.utils.ident import tags_to_id
total = sum(len(db.namespaces["default"].read(
                tags_to_id(b"m-%d-%d" % (w, i), [(b"k", b"v")]),
                START, START + BLOCK)[0])
            for w in range(4) for i in range(0, 64, 16))
from m3_tpu.utils.instrument import default_registry
_c, _g, _t, hists = default_registry().snapshot()
wal_wait = 0.0
for (name, tags), (bounds, counts, hsum, count) in hists.items():
    if name == "lock.wait_seconds" and \
            "commitlog" in dict(tags).get("cls", ""):
        wal_wait += hsum
print(json.dumps({"rows": total, "wal_wait_s": wal_wait}))
"""


@pytest.mark.chaos
class TestLockWaitBeforeAfter:
    def test_wal_class_wait_shrinks_with_pipeline(self, tmp_path):
        """The before/after proof, measured: the same concurrent ingest
        load under M3_TPU_LOCK_PROFILE=1 (armed at import, hence child
        processes) shows the commitlog writer-lock class — the wait that
        brackets the WAL flush/fsync I/O — shrinking when the per-
        namespace lane serializes appends off-thread (M3_TPU_PIPELINE=1
        vs the serial path, where every ingest thread contends for the
        lock through the I/O)."""
        results = {}
        for mode in ("0", "1"):
            env = dict(os.environ)
            env.update({"M3_TPU_LOCK_PROFILE": "1", "M3_TPU_PIPELINE": mode,
                        "M3_TPU_PIPELINE_WAL_CHUNK": "16",
                        "M3_REPO": REPO, "JAX_PLATFORMS": "cpu"})
            r = subprocess.run(
                [sys.executable, "-c", _LOCK_PROFILE_CHILD,
                 str(tmp_path / f"db{mode}")],
                env=env, capture_output=True, text=True, timeout=300)
            assert r.returncode == 0, r.stderr[-2000:]
            results[mode] = json.loads(r.stdout.strip().splitlines()[-1])
        # correctness first: both modes served every sampled read
        assert results["0"]["rows"] == results["1"]["rows"] > 0
        # the serial path measurably contends on the WAL class; the
        # laned path takes it from ONE thread (near-zero wait)
        assert results["1"]["wal_wait_s"] <= results["0"]["wal_wait_s"]

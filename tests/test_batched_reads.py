"""Batched multi-series read path: one columnar fetch per (shard, block,
volume) group and ONE decode dispatch per read (per chunk and group on
the serial hatch).

Pins the claims of the batched surface:
  - dispatch economy: read_many over >=10k cold-cache series issues at
    most one batched decode per (shard, block, volume) group (counted via
    utils/dispatch counters), never one per series; on the pipelined
    path exactly one a read, cut only by the bound on a launch's output;
  - parity: batched results are identical (times AND value bits) to the
    per-series read() path on every ladder rung (native batch, vmapped
    XLA kernel, scalar loop), including int-optimized and NaN-staleness
    streams and marker-bearing streams the fast rungs reject;
  - cache semantics: hits are served without entering the batch, and the
    batch fills the decoded-block LRU so the per-series path hits it.
"""

from __future__ import annotations

import numpy as np
import pytest

from m3_tpu.encoding.m3tsz import hostpath
from m3_tpu.encoding.m3tsz.encoder import Encoder
from m3_tpu.storage.database import Database
from m3_tpu.storage.fileset import FilesetWriter
from m3_tpu.storage.options import (
    DatabaseOptions,
    IndexOptions,
    NamespaceOptions,
    RetentionOptions,
)
from m3_tpu.utils import dispatch, querystats
from m3_tpu.utils.xtime import TimeUnit

NS = 10**9
BLOCK = 3600 * NS
START = 1_600_000_000 * NS

# per-stream (non-batched) decode counters: the dispatch-economy tests
# assert these do NOT move during a batched read
PER_STREAM_COUNTERS = ("m3tsz_decode_native", "m3tsz_decode_scalar")


def build_db(tmp_path, n_series, n_blocks=2, n_shards=4, points=6,
             int_optimized=False, cache_entries=0, overrides=None):
    """A database whose fileset volumes are written directly (one batched
    encode per (shard, block)) — fast enough to set up 10k+ series.
    ``overrides`` maps (series id, block number) to the stream to store
    in place of the encoded one."""
    db = Database(
        str(tmp_path / "db"),
        DatabaseOptions(n_shards=n_shards, block_cache_entries=cache_entries),
    )
    opts = NamespaceOptions(
        retention=RetentionOptions(retention_ns=1000 * BLOCK,
                                   block_size_ns=BLOCK),
        index=IndexOptions(enabled=False),
        int_optimized=int_optimized,
        writes_to_commitlog=False,
        snapshot_enabled=False,
    )
    ns = db.create_namespace("default", opts)
    ids = [b"series-%06d" % i for i in range(n_series)]
    by_shard: dict[int, list[bytes]] = {}
    for sid in ids:
        by_shard.setdefault(ns.shard_set.lookup(sid), []).append(sid)
    rng = np.random.default_rng(7)
    for shard_id, sids in by_shard.items():
        for b in range(n_blocks):
            bs = START + b * BLOCK
            B, T = len(sids), points
            times = np.broadcast_to(
                bs + np.arange(T, dtype=np.int64) * 10 * NS, (B, T)).copy()
            values = rng.normal(100.0, 20.0, (B, T))
            if int_optimized:
                values = np.floor(values)
            streams = hostpath.encode_blocks(
                times, values.view(np.uint64), np.full(B, bs, np.int64),
                np.full(B, T, np.int32), TimeUnit.SECOND, int_optimized)
            writer = FilesetWriter(db.fs_root, "default", shard_id, bs,
                                   BLOCK, 0)
            for sid, stream in zip(sids, streams):
                writer.write_series(
                    sid, b"", (overrides or {}).get((sid, b), stream))
            writer.close()
    db.open(START + n_blocks * BLOCK)
    return db, ns, ids


def _deltas(before, names):
    return {k: dispatch.counters[k] - before.get(k, 0) for k in names}


class TestDispatchEconomy:
    N_SERIES = 10_000
    N_BLOCKS = 2
    N_SHARDS = 4

    def test_one_dispatch_per_shard_block_group(self, tmp_path):
        """>=10k cold-cache series resolve in n_shards * n_blocks batched
        dispatches — zero per-series decode dispatches."""
        db, ns, ids = build_db(tmp_path, self.N_SERIES,
                               n_blocks=self.N_BLOCKS,
                               n_shards=self.N_SHARDS, cache_entries=0)
        try:
            before = dict(dispatch.counters)
            results = ns.read_many(ids, START, START + self.N_BLOCKS * BLOCK)
            groups = dispatch.counters["m3tsz_decode_batch_groups"] \
                - before.get("m3tsz_decode_batch_groups", 0)
            assert groups <= self.N_SHARDS * self.N_BLOCKS
            assert _deltas(before, PER_STREAM_COUNTERS) == {
                k: 0 for k in PER_STREAM_COUNTERS}
            assert len(results) == self.N_SERIES
            # every series got both blocks' points
            per_series = self.N_BLOCKS * 6
            assert all(len(t) == per_series for t, _ in results)
            # spot parity vs the per-series path
            for i in range(0, self.N_SERIES, 997):
                st, sv = ns.read(ids[i], START,
                                 START + self.N_BLOCKS * BLOCK)
                np.testing.assert_array_equal(results[i][0], st)
                np.testing.assert_array_equal(results[i][1], sv)
        finally:
            db.close()

    def test_cache_hits_never_enter_the_batch(self, tmp_path):
        db, ns, ids = build_db(tmp_path, 300, cache_entries=10_000)
        try:
            first = ns.read_many(ids, START, START + 2 * BLOCK)
            before = dict(dispatch.counters)
            second = ns.read_many(ids, START, START + 2 * BLOCK)
            assert dispatch.counters["m3tsz_decode_batch_groups"] \
                == before.get("m3tsz_decode_batch_groups", 0)
            for (t1, v1), (t2, v2) in zip(first, second):
                np.testing.assert_array_equal(t1, t2)
                np.testing.assert_array_equal(v1, v2)
            # and the batch's cache fill serves the per-series path too
            st, sv = ns.read(ids[0], START, START + 2 * BLOCK)
            np.testing.assert_array_equal(st, first[0][0])
        finally:
            db.close()

    def test_limits_accounting_is_per_series_exact(self, tmp_path):
        from m3_tpu.storage.limits import QueryLimitError, QueryLimits

        db, ns, ids = build_db(tmp_path, 64, n_blocks=1, cache_entries=0)
        try:
            total = 64 * 6
            db.limits = QueryLimits(max_datapoints=total)
            db.limits.start_query()
            ns.read_many(ids, START, START + BLOCK)  # exactly at the limit
            assert db.limits._tl.datapoints == total
            db.limits.end_query()
            db.limits = QueryLimits(max_datapoints=total - 1)
            db.limits.start_query()
            with pytest.raises(QueryLimitError):
                ns.read_many(ids, START, START + BLOCK)
            db.limits.end_query()
        finally:
            db.close()

    def test_datapoint_limit_bounds_decode_work(self, tmp_path, monkeypatch):
        """With a datapoint limit configured, an over-limit read_many must
        abort after at most one chunk's decode — the limit bounds WORK,
        not just the reported total (the per-series path's property)."""
        from m3_tpu.storage.limits import QueryLimitError, QueryLimits
        from m3_tpu.storage.namespace import Namespace

        db, ns, ids = build_db(tmp_path, 1024, n_blocks=1, cache_entries=0)
        monkeypatch.setattr(Namespace, "READ_MANY_LIMIT_CHUNK", 64)
        try:
            db.limits = QueryLimits(max_datapoints=30)  # < one chunk
            db.limits.start_query()
            before = dispatch.counters["m3tsz_decode_batch_groups"]
            with pytest.raises(QueryLimitError):
                ns.read_many(ids, START, START + BLOCK)
            groups = dispatch.counters["m3tsz_decode_batch_groups"] - before
            assert groups <= 1  # stopped inside the first chunk
            db.limits.end_query()
        finally:
            db.close()

    def test_unowned_shard_still_raises(self, tmp_path):
        db, ns, ids = build_db(tmp_path, 32, n_blocks=1)
        try:
            victim = ids[0]
            ns.shards.pop(ns.shard_set.lookup(victim))
            with pytest.raises(KeyError):
                ns.read_many(ids, START, START + BLOCK)
        finally:
            db.close()


class TestForcedPathParity:
    """Every ladder rung produces bit-identical (times, vbits) to the
    per-series decode_stream path — float, int-optimized, NaN staleness."""

    def _streams(self, int_opt):
        rng = np.random.default_rng(3)
        streams = []
        for s in range(12):
            enc = Encoder(START, int_optimized=int_opt,
                          default_time_unit=TimeUnit.SECOND)
            t = START
            for i in range(int(rng.integers(1, 40))):
                t += int(rng.integers(1, 120)) * NS
                if rng.random() < 0.15:
                    v = float("nan")  # staleness marker
                elif int_opt and rng.random() < 0.5:
                    v = float(int(rng.integers(-1000, 1000)))
                else:
                    v = float(rng.normal(50, 20))
                enc.encode(t, v, TimeUnit.SECOND)
            streams.append(enc.stream())
        streams.insert(3, b"")  # empty stream mid-batch
        return streams

    @pytest.mark.parametrize("path", ["scalar", "native", "device"])
    @pytest.mark.parametrize("int_opt", [False, True])
    def test_rung_matches_per_series(self, monkeypatch, path, int_opt):
        streams = self._streams(int_opt)
        ref = [hostpath.decode_stream(s, TimeUnit.SECOND, int_opt) if s
               else (np.empty(0, np.int64), np.empty(0, np.uint64))
               for s in streams]
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", path)
        got = hostpath.decode_streams_batch(streams, TimeUnit.SECOND, int_opt)
        for (gt, gv), (rt, rv) in zip(got, ref):
            np.testing.assert_array_equal(gt, rt)
            np.testing.assert_array_equal(gv, rv)

    def test_marker_stream_degrades_per_stream_not_whole_group(self):
        """A time-unit-change marker stream (native batch rejects it) must
        not poison the group: the other streams still decode, and the
        marker stream decodes via the scalar rung."""
        enc = Encoder(START, int_optimized=False,
                      default_time_unit=TimeUnit.SECOND)
        enc.encode(START + NS, 1.0, TimeUnit.SECOND)
        enc.encode(START + NS + 10**6, 2.0, TimeUnit.MILLISECOND)
        marker = enc.stream()
        plain = Encoder(START, int_optimized=False,
                        default_time_unit=TimeUnit.SECOND)
        plain.encode(START + NS, 5.0, TimeUnit.SECOND)
        streams = [plain.stream(), marker]
        # float-mode group containing a marker stream: the native rung
        # raises for the whole batch and must fall back per stream
        got = hostpath.decode_streams_batch(streams, TimeUnit.SECOND, False)
        np.testing.assert_array_equal(got[0][0], [START + NS])
        ref = hostpath.decode_stream(marker, TimeUnit.SECOND, False)
        np.testing.assert_array_equal(got[1][0], ref[0])
        np.testing.assert_array_equal(got[1][1], ref[1])


def _read_with_stats(ns, ids, n_blocks):
    """(answers, query record) of one read_many over every block."""
    st = querystats.start(query="batched-read-test")
    try:
        return ns.read_many(ids, START, START + n_blocks * BLOCK), st
    finally:
        querystats.finish(st)


def _assert_same_answers(got, want):
    assert len(got) == len(want)
    for (gt, gv), (wt, wv) in zip(got, want):
        np.testing.assert_array_equal(gt, wt)
        np.testing.assert_array_equal(gv, wv)


def _marker_stream():
    """A stream with a time-unit-change marker: the batch rungs reject
    it, the scalar decoder reads it."""
    enc = Encoder(START, int_optimized=False,
                  default_time_unit=TimeUnit.SECOND)
    enc.encode(START + NS, 1.0, TimeUnit.SECOND)
    enc.encode(START + NS + 10**6, 2.0, TimeUnit.MILLISECOND)
    return enc.stream()


# (shards, blocks): one block over many shards is the dashboard's read,
# several blocks a long range's
SHAPES = [(8, 1), (4, 3)]


class TestOneLaunchPerRead:
    """The unit of a decoder launch is everything a read_many missed in
    the block cache, across its (shard, block) groups: one launch a
    read, cut only by the bound on one launch's padded output, with the
    answers, cache fill and query record of the serial hatch."""

    N = 96

    @pytest.mark.parametrize("n_shards,n_blocks", SHAPES)
    def test_one_device_launch_with_misses_in_every_group(
            self, tmp_path, monkeypatch, n_shards, n_blocks):
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", "device")
        db, ns, ids = build_db(tmp_path, self.N, n_blocks=n_blocks,
                               n_shards=n_shards, cache_entries=10_000)
        try:
            assert len(ns.shards) == n_shards
            for read_ids in (ids[::3], ids):  # cold, then partly warm
                before = dict(dispatch.counters)
                res, st = _read_with_stats(ns, read_ids, n_blocks)
                assert _deltas(before, ("m3tsz_decode_device_batch",
                                        "m3tsz_decode_batch_groups")) == {
                    "m3tsz_decode_device_batch": 1,
                    "m3tsz_decode_batch_groups": 1}
                # misses in every group, and every group still counted
                assert st.blocks_read == n_shards * n_blocks
                assert st.decode_rungs == {"device": 1}
                assert all(len(t) == 6 * n_blocks for t, _ in res)
        finally:
            db.close()

    @pytest.mark.parametrize("path", ["device", "native"])
    def test_launches_above_the_bound(self, tmp_path, monkeypatch, path):
        """ceil(miss rows / rows of the bound) launches, each a full row
        bucket but the last; answers unchanged."""
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", path)
        db, ns, ids = build_db(tmp_path, self.N, n_blocks=3, n_shards=4,
                               cache_entries=0)
        try:
            want = ns.read_many(ids, START, START + 3 * BLOCK)
            maxlen = max(len(s) for sh in ns.shards.values()
                         for r in sh._filesets.values()
                         for s in r.read_many(ids) if s)
            # room for 100 rows: the largest half-octave bucket is 96
            monkeypatch.setattr(
                hostpath, "_LAUNCH_OUT_BYTES",
                100 * 17 * hostpath._max_points(maxlen))
            assert hostpath._launch_rows(maxlen) == 96
            before = dict(dispatch.counters)
            got, st = _read_with_stats(ns, ids, 3)
            launches = -(-3 * self.N // 96)
            assert launches == 3
            assert _deltas(before, ("m3tsz_decode_batch_groups",)) == {
                "m3tsz_decode_batch_groups": launches}
            if path == "device":
                assert _deltas(before, ("m3tsz_decode_device_batch",)) \
                    == {"m3tsz_decode_device_batch": launches}
            assert st.decode_rungs == {path: launches}
            assert st.blocks_read == 4 * 3
            _assert_same_answers(got, want)
        finally:
            db.close()

    @pytest.mark.parametrize("maxlen,rows", [
        (1, 196608), (430, 3072), (600, 1536), (1 << 22, 1)])
    def test_launch_rows_fit_the_bound(self, maxlen, rows):
        """The cut is a half-octave bucket (padding adds no rows) within
        the bound; a 2,500-row group of hour-long streams still fits."""
        assert hostpath._launch_rows(maxlen) == rows
        assert dispatch.next_bucket(rows) == rows
        out = rows * hostpath._max_points(maxlen) * 17
        assert out <= hostpath._LAUNCH_OUT_BYTES or rows == 1

    @pytest.mark.parametrize("path", ["device", "native", "scalar"])
    @pytest.mark.parametrize("n_shards,n_blocks", SHAPES)
    def test_parity_with_serial_hatch(self, tmp_path, monkeypatch, path,
                                      n_shards, n_blocks):
        """Answers, cache contents and the query record's blocks_read /
        cache_hits / cache_misses equal M3_TPU_PIPELINE=0's, cold and
        partly warm, on every rung."""
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", path)
        db, ns, ids = build_db(tmp_path, self.N, n_blocks=n_blocks,
                               n_shards=n_shards, cache_entries=10_000)
        cache = db.block_cache

        def run(pipeline_env):
            monkeypatch.setenv("M3_TPU_PIPELINE", pipeline_env)
            cache._entries.clear()
            out = []
            for read_ids in (ids[::3], ids):
                res, st = _read_with_stats(ns, read_ids, n_blocks)
                out.append((res, st.blocks_read, st.cache_hits,
                            st.cache_misses, dict(cache._entries)))
            return out

        try:
            for serial, piped in zip(run("0"), run("1")):
                _assert_same_answers(piped[0], serial[0])
                assert piped[1:4] == serial[1:4]
                assert piped[4].keys() == serial[4].keys()
                for key, (t, v) in serial[4].items():
                    np.testing.assert_array_equal(piped[4][key][0], t)
                    np.testing.assert_array_equal(piped[4][key][1], v)
            assert len(cache) == self.N * n_blocks
        finally:
            db.close()

    @pytest.mark.parametrize("path", ["device", "native"])
    def test_marker_stream_in_one_group_degrades_alone(
            self, tmp_path, monkeypatch, path):
        """One marker-bearing stream among the misses of 8 groups: it
        alone reaches the scalar decoder, the launch stays one."""
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", path)
        victim = b"series-%06d" % 5
        db, ns, ids = build_db(
            tmp_path, self.N, n_blocks=1, n_shards=8, cache_entries=0,
            overrides={(victim, 0): _marker_stream()})
        try:
            before = dict(dispatch.counters)
            got = ns.read_many(ids, START, START + BLOCK)
            d = _deltas(before, ("m3tsz_decode_batch_groups",
                                 "m3tsz_decode_device_batch",
                                 "m3tsz_decode_scalar"))
            assert d["m3tsz_decode_batch_groups"] == 1
            assert d["m3tsz_decode_device_batch"] == (path == "device")
            assert d["m3tsz_decode_scalar"] == 1
            np.testing.assert_array_equal(
                got[5][0], [START + NS, START + NS + 10**6])
            np.testing.assert_array_equal(
                got[5][1].view(np.float64), [1.0, 2.0])
            _assert_same_answers(
                got, [ns.read(sid, START, START + BLOCK) for sid in ids])
        finally:
            db.close()

    def test_duplicate_ids(self, tmp_path, monkeypatch):
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", "device")
        db, ns, ids = build_db(tmp_path, 40, n_blocks=2, n_shards=8,
                               cache_entries=10_000)
        try:
            asked = ids + ids[:7] + [ids[3]]
            before = dict(dispatch.counters)
            got = ns.read_many(asked, START, START + 2 * BLOCK)
            assert _deltas(before, ("m3tsz_decode_device_batch",)) == {
                "m3tsz_decode_device_batch": 1}
            _assert_same_answers(
                got, [ns.read(sid, START, START + 2 * BLOCK)
                      for sid in asked])
        finally:
            db.close()

    def test_group_whose_every_series_hits_the_cache(self, tmp_path):
        """A fully cached group adds its hits and no rows to the launch,
        and is not among the blocks read."""
        db, ns, ids = build_db(tmp_path, self.N, n_blocks=1, n_shards=8,
                               cache_entries=10_000)
        try:
            warm_shard = ns.shard_set.lookup(ids[0])
            warm = [sid for sid in ids
                    if ns.shard_set.lookup(sid) == warm_shard]
            ns.read_many(warm, START, START + BLOCK)
            before = dict(dispatch.counters)
            got, st = _read_with_stats(ns, ids, 1)
            assert _deltas(before, ("m3tsz_decode_batch_groups",)) == {
                "m3tsz_decode_batch_groups": 1}
            assert st.blocks_read == 7
            assert st.cache_hits == len(warm)
            assert st.cache_misses == self.N - len(warm)
            _assert_same_answers(
                got, [ns.read(sid, START, START + BLOCK) for sid in ids])
        finally:
            db.close()

    @pytest.mark.parametrize("path", ["device", "scalar"])
    def test_int_optimized_namespace(self, tmp_path, monkeypatch, path):
        monkeypatch.setenv("M3_TPU_DECODE_BATCH_PATH", path)
        db, ns, ids = build_db(tmp_path, 48, n_blocks=2, n_shards=8,
                               int_optimized=True, cache_entries=0)
        try:
            before = dict(dispatch.counters)
            got, st = _read_with_stats(ns, ids, 2)
            assert _deltas(before, ("m3tsz_decode_batch_groups",)) == {
                "m3tsz_decode_batch_groups": 1}
            assert st.decode_rungs == {path: 1}
            monkeypatch.delenv("M3_TPU_DECODE_BATCH_PATH")
            _assert_same_answers(
                got, [ns.read(sid, START, START + 2 * BLOCK)
                      for sid in ids])
        finally:
            db.close()

    def test_shard_read_many_is_one_launch_across_blocks(self, tmp_path):
        """Direct shard callers (and the namespace's limit-chunked loop)
        decode once a call, not once a block."""
        db, ns, ids = build_db(tmp_path, 64, n_blocks=3, n_shards=2,
                               cache_entries=0)
        try:
            shard = ns.shards[0]
            sids = [s for s in ids if ns.shard_set.lookup(s) == 0]
            before = dict(dispatch.counters)
            got = shard.read_many(sids, START, START + 3 * BLOCK)
            assert _deltas(before, ("m3tsz_decode_batch_groups",)) == {
                "m3tsz_decode_batch_groups": 1}
            _assert_same_answers(
                got, [shard.read(s, START, START + 3 * BLOCK)
                      for s in sids])
        finally:
            db.close()


class TestBatchedVsBufferMerge:
    def test_buffered_writes_win_over_flushed(self, tmp_path):
        """Batched reads keep last-write-wins semantics: buffer points
        override flushed points on timestamp ties, same as read()."""
        db, ns, ids = build_db(tmp_path, 40, n_blocks=1, cache_entries=0)
        try:
            overwrite_t = START + 20 * NS  # collides with a flushed point
            for sid in ids[:10]:
                ns.write(sid, overwrite_t,
                         int(np.float64(-1.0).view(np.uint64)))
            batched = ns.read_many(ids, START, START + BLOCK)
            for i, sid in enumerate(ids):
                st, sv = ns.shards[ns.shard_set.lookup(sid)].read(
                    sid, START, START + BLOCK)
                np.testing.assert_array_equal(batched[i][0], st)
                np.testing.assert_array_equal(batched[i][1], sv)
            row = batched[0]
            at = row[1][row[0] == overwrite_t].view(np.float64)
            assert at == -1.0
        finally:
            db.close()

"""Storage engine tests: buffer, fileset, commitlog, shard, database.

Mirrors the reference's unit-test tiers for the storage path (SURVEY.md §4):
write/read round-trips, flush + bootstrap-from-fs, commitlog replay after
crash, out-of-order/duplicate resolution, retention expiry.
"""

import os

import numpy as np
import pytest

from m3_tpu.storage import commitlog
from m3_tpu.storage.buffer import ShardBuffer
from m3_tpu.storage.database import Database
from m3_tpu.storage.fileset import BloomFilter, FilesetReader, FilesetWriter, list_filesets
from m3_tpu.storage.options import (
    DatabaseOptions,
    NamespaceOptions,
    RetentionOptions,
)
from m3_tpu.utils.ident import decode_tags, encode_tags, tags_to_id

HOUR = 3600 * 10**9
START = 1_599_998_400_000_000_000  # multiple of 2h: aligned block start


def bits(v: float) -> int:
    return int(np.float64(v).view(np.uint64))


def small_opts() -> NamespaceOptions:
    return NamespaceOptions(
        retention=RetentionOptions(
            retention_ns=24 * HOUR,
            block_size_ns=2 * HOUR,
            buffer_past_ns=10 * 60 * 10**9,
        )
    )


class TestShardBuffer:
    def test_write_read(self):
        buf = ShardBuffer(2 * HOUR)
        buf.write(b"a", START + 10**9, bits(1.0))
        buf.write(b"a", START + 3 * 10**9, bits(2.0))
        buf.write(b"b", START + 10**9, bits(9.0))
        t, v = buf.read(b"a", START, START + HOUR)
        assert list(t) == [START + 10**9, START + 3 * 10**9]
        assert list(v.view(np.float64)) == [1.0, 2.0]

    def test_out_of_order_and_duplicates(self):
        buf = ShardBuffer(2 * HOUR)
        buf.write(b"a", START + 5 * 10**9, bits(5.0))
        buf.write(b"a", START + 1 * 10**9, bits(1.0))
        buf.write(b"a", START + 5 * 10**9, bits(50.0))  # dup: last wins
        t, v = buf.read(b"a", START, START + HOUR)
        assert list(t) == [START + 10**9, START + 5 * 10**9]
        assert list(v.view(np.float64)) == [1.0, 50.0]

    def test_seal_groups_and_dedupes(self):
        buf = ShardBuffer(2 * HOUR)
        buf.write(b"a", START + 2 * 10**9, bits(2.0))
        buf.write(b"b", START + 1 * 10**9, bits(1.0))
        buf.write(b"a", START + 1 * 10**9, bits(0.5))
        buf.write(b"a", START + 2 * 10**9, bits(3.0))  # dup of first
        sealed = buf.seal_csr(START)
        assert sealed.n_series == 2
        a = list(sealed.series_indices).index(buf.series_index(b"a"))
        assert sealed.n_points[a] == 2
        lo, hi = sealed.offsets[a], sealed.offsets[a + 1]
        np.testing.assert_array_equal(
            sealed.times[lo:hi], [START + 10**9, START + 2 * 10**9]
        )
        assert sealed.value_bits[hi - 1] == bits(3.0)
        # sealed window is gone from the buffer
        assert buf.points_in(START) == 0

    def test_drop_window_prefix_keeps_suffix_bit_exact(self):
        """A flush drops exactly the rows its seal covered: rows appended
        after the seal (here across a page boundary) stay readable bit
        for bit, and seal again as the whole window."""
        from m3_tpu.storage.pagepool import PAGE_ROWS

        buf = ShardBuffer(2 * HOUR)
        rng = np.random.default_rng(3)
        n = PAGE_ROWS + 300
        t = START + np.arange(n, dtype=np.int64) * 10**6
        v = rng.integers(0, 2**63, n).astype(np.uint64)
        ids = [b"a" if i % 3 else b"b" for i in range(n)]
        covered = PAGE_ROWS - 100
        buf.write_many(ids[:covered], t[:covered], v[:covered],
                       [b""] * covered)
        sealed = buf.seal_csr(START, drop=False)
        buf.write_many(ids[covered:], t[covered:], v[covered:],
                       [b""] * (n - covered))
        buf.drop_window_prefix(START, sealed.raw_count)
        assert buf.points_in(START) == n - covered
        for sid in (b"a", b"b"):
            keep = np.array([x == sid for x in ids[covered:]])
            got_t, got_v = buf.read(sid, START, START + 2 * HOUR)
            np.testing.assert_array_equal(got_t, t[covered:][keep])
            np.testing.assert_array_equal(got_v, v[covered:][keep])
        rest = buf.seal_csr(START)
        assert rest.raw_count == n - covered == len(rest.times)
        # a prefix that covers everything drops the window
        buf.write(b"a", START + 1, bits(1.0))
        buf.drop_window_prefix(START, 5)
        assert buf.block_starts() == []

    def test_multiple_block_windows(self):
        buf = ShardBuffer(2 * HOUR)
        buf.write(b"a", START + 10**9, bits(1.0))
        buf.write(b"a", START + 2 * HOUR + 10**9, bits(2.0))
        assert buf.block_starts() == [START, START + 2 * HOUR]


class TestFileset:
    def test_write_read_roundtrip(self, tmp_path):
        w = FilesetWriter(str(tmp_path), "ns", 3, START, 2 * HOUR)
        w.write_series(b"abc", encode_tags([(b"host", b"h1")]), b"STREAM-A")
        w.write_series(b"zzz", b"", b"STREAM-Z")
        w.close()
        r = FilesetReader(str(tmp_path), "ns", 3, START)
        assert r.n_series == 2
        assert r.read(b"abc") == b"STREAM-A"
        assert r.read(b"zzz") == b"STREAM-Z"
        assert r.read(b"nope") is None
        assert decode_tags(r.tags_of(b"abc")) == [(b"host", b"h1")]

    def test_missing_checkpoint_rejected(self, tmp_path):
        w = FilesetWriter(str(tmp_path), "ns", 0, START, 2 * HOUR)
        w.write_series(b"a", b"", b"x")
        w.close()
        os.remove(
            os.path.join(str(tmp_path), "ns", "0", f"fileset-{START}-0-checkpoint.db")
        )
        with pytest.raises(FileNotFoundError):
            FilesetReader(str(tmp_path), "ns", 0, START)
        assert list_filesets(str(tmp_path), "ns", 0) == []

    def test_corrupt_data_detected(self, tmp_path):
        w = FilesetWriter(str(tmp_path), "ns", 0, START, 2 * HOUR)
        w.write_series(b"a", b"", b"payload")
        w.close()
        p = os.path.join(str(tmp_path), "ns", "0", f"fileset-{START}-0-data.db")
        with open(p, "r+b") as f:
            f.write(b"X")
        with pytest.raises(ValueError, match="corrupt"):
            FilesetReader(str(tmp_path), "ns", 0, START)

    def test_bloom_filter(self):
        bf = BloomFilter(100)
        keys = [f"k{i}".encode() for i in range(100)]
        for k in keys:
            bf.add(k)
        assert all(bf.may_contain(k) for k in keys)
        fp = sum(bf.may_contain(f"other{i}".encode()) for i in range(1000))
        assert fp < 50  # ~1% expected at 10 bits/item
        bf2 = BloomFilter.from_bytes(bf.to_bytes())
        assert all(bf2.may_contain(k) for k in keys)


class TestCommitLog:
    def test_write_replay(self, tmp_path):
        p = str(tmp_path / "cl" / "commitlog-1.db")
        w = commitlog.CommitLogWriter(p)
        w.write(b"a", encode_tags([(b"x", b"y")]), START, bits(1.5), 1)
        w.write(b"a", b"", START + 10**9, bits(2.5), 1)
        w.write(b"b", b"", START, bits(9.0), 1)
        w.close()
        entries = commitlog.replay(p)
        assert len(entries) == 3
        assert entries[0].series_id == b"a"
        assert decode_tags(entries[0].encoded_tags) == [(b"x", b"y")]
        assert entries[1].value_bits == bits(2.5)
        assert entries[2].series_id == b"b"

    def test_torn_tail_ignored(self, tmp_path):
        p = str(tmp_path / "cl" / "commitlog-1.db")
        w = commitlog.CommitLogWriter(p)
        w.write(b"a", b"", START, bits(1.0), 1)
        w.flush()
        w.write(b"b", b"", START, bits(2.0), 1)
        w.close()
        raw = open(p, "rb").read()
        with open(p, "wb") as f:
            f.write(raw[:-3])  # simulate crash mid-write
        entries = commitlog.replay(p)
        assert [e.series_id for e in entries] == [b"a"]


def make_db(tmp_path, **kw) -> Database:
    db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4, **kw))
    db.create_namespace("default", small_opts())
    db.open()
    return db


class TestDatabase:
    def test_write_read_buffer_only(self, tmp_path):
        db = make_db(tmp_path)
        sid = tags_to_id(b"cpu", [(b"host", b"h1")])
        db.write("default", sid, START + 10**9, 0.5)
        db.write("default", sid, START + 2 * 10**9, 1.5)
        dps = db.read("default", sid, START, START + HOUR)
        assert [(d.timestamp_ns, d.value) for d in dps] == [
            (START + 10**9, 0.5),
            (START + 2 * 10**9, 1.5),
        ]
        db.close()

    def test_flush_and_read_from_fileset(self, tmp_path):
        db = make_db(tmp_path)
        ids = [f"series-{i}".encode() for i in range(20)]
        for i, sid in enumerate(ids):
            for j in range(10):
                db.write("default", sid, START + j * 60 * 10**9, float(i * 100 + j))
        # tick "now" far enough past the block end to trigger warm flush
        now = START + 2 * HOUR + HOUR
        stats = db.tick(now)
        assert stats["flushed"] >= 1
        # buffers are drained into filesets; reads hit the volumes
        for i, sid in enumerate(ids):
            dps = db.read("default", sid, START, START + 2 * HOUR)
            assert len(dps) == 10
            assert dps[3].value == i * 100 + 3
        db.close()

    def test_bootstrap_from_fs_after_restart(self, tmp_path):
        db = make_db(tmp_path)
        sid = b"persisted"
        for j in range(5):
            db.write("default", sid, START + j * 60 * 10**9, float(j))
        db.tick(START + 3 * HOUR)
        db.close()

        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db2.create_namespace("default", small_opts())
        db2.open(START + 3 * HOUR)
        dps = db2.read("default", sid, START, START + HOUR)
        assert [d.value for d in dps] == [0.0, 1.0, 2.0, 3.0, 4.0]
        db2.close()

    def test_commitlog_replay_recovers_unflushed(self, tmp_path):
        db = make_db(tmp_path)
        sid = b"wal-series"
        db.write("default", sid, START + 10**9, 42.0)
        # crash: no flush, no clean close; but force the log to disk
        db._commitlogs["default"].flush()
        db._commitlogs["default"]._f.close()

        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db2.create_namespace("default", small_opts())
        db2.open(START + HOUR)
        dps = db2.read("default", sid, START, START + HOUR)
        assert [(d.timestamp_ns, d.value) for d in dps] == [(START + 10**9, 42.0)]
        db2.close()

    def test_merge_buffer_and_fileset_reads(self, tmp_path):
        db = make_db(tmp_path)
        sid = b"mixed"
        db.write("default", sid, START + 10**9, 1.0)
        db.tick(START + 3 * HOUR)  # flush first point
        late = START + 2 * 10**9
        db.write("default", sid, late, 2.0)  # cold write into flushed window
        dps = db.read("default", sid, START, START + HOUR)
        assert [d.value for d in dps] == [1.0, 2.0]
        db.close()

    def test_cold_reflush_merges_volumes(self, tmp_path):
        db = make_db(tmp_path)
        sid = b"cold"
        db.write("default", sid, START + 10**9, 1.0)
        db.flush_all()
        db.write("default", sid, START + 2 * 10**9, 2.0)
        db.flush_all()  # second volume merges old + new
        shard = db.namespaces["default"].shard_for(sid)
        assert shard._filesets[START].volume == 1
        dps = db.read("default", sid, START, START + HOUR)
        assert [d.value for d in dps] == [1.0, 2.0]
        db.close()

    def test_retention_expiry(self, tmp_path):
        db = make_db(tmp_path)
        sid = b"old"
        db.write("default", sid, START + 10**9, 1.0)
        db.flush_all()
        far_future = START + 48 * HOUR
        db.tick(far_future)
        assert db.read("default", sid, START, START + HOUR) == []
        db.close()

    def test_out_of_order_across_flush_boundary(self, tmp_path):
        db = make_db(tmp_path)
        sid = b"ooo"
        db.write("default", sid, START + 5 * 10**9, 5.0)
        db.write("default", sid, START + 1 * 10**9, 1.0)
        db.write("default", sid, START + 5 * 10**9, 50.0)  # dup last wins
        db.flush_all()
        dps = db.read("default", sid, START, START + HOUR)
        assert [(d.timestamp_ns - START) // 10**9 for d in dps] == [1, 5]
        assert [d.value for d in dps] == [1.0, 50.0]
        db.close()


class TestReviewRegressions:
    """Cases found by code-review probes."""

    def test_late_write_survives_crash_after_flush(self, tmp_path):
        # post-flush write into a flushed window must replay on restart
        db = make_db(tmp_path)
        sid = b"late"
        db.write("default", sid, START + 10**9, 1.0)
        db.tick(START + 3 * HOUR)  # flush window
        db.write("default", sid, START + 2 * 10**9, 2.0)  # late write, same window
        db._commitlogs["default"].flush()
        db._commitlogs["default"]._f.close()  # crash

        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db2.create_namespace("default", small_opts())
        db2.open(START + 3 * HOUR)
        dps = db2.read("default", sid, START, START + HOUR)
        assert [d.value for d in dps] == [1.0, 2.0]
        db2.close()

    def test_retention_deletes_files_and_restart_respects_it(self, tmp_path):
        db = make_db(tmp_path)
        db.write("default", b"old", START + 10**9, 1.0)
        db.flush_all()
        far = START + 48 * HOUR
        db.tick(far)
        # files are gone from disk
        shard_dirs = os.path.join(str(tmp_path / "db"), "data", "default")
        remaining = [
            f for d in os.listdir(shard_dirs)
            for f in os.listdir(os.path.join(shard_dirs, d))
        ]
        assert remaining == []
        db.close()
        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db2.create_namespace("default", small_opts())
        db2.open(far)
        assert db2.read("default", b"old", START, START + HOUR) == []
        db2.close()

    def test_tags_to_id_no_collision(self):
        a = tags_to_id(b"m", [(b"a", b"1|b=2")])
        b = tags_to_id(b"m", [(b"a", b"1"), (b"b", b"2")])
        assert a != b

    def test_commitlogs_cleaned_after_flush(self, tmp_path):
        db = make_db(tmp_path)
        db.write("default", b"s", START + 10**9, 1.0)
        db.tick(START + 3 * HOUR)  # flush + retire + cleanup
        db.tick(START + 3 * HOUR + 1)  # second cleanup pass
        logs = commitlog.log_files(db.commitlog_dir("default"))
        assert len(logs) == 1  # only the fresh active log remains
        db.close()

    def test_unowned_shard_write_rejected_before_logging(self, tmp_path):
        from m3_tpu.storage.sharding import ShardSet

        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db.create_namespace("default", small_opts())
        db.open()
        # restrict ownership after open
        ns = db.namespaces["default"]
        ns.shard_set = ShardSet(4, shard_ids=(0,))
        ns.shards = {0: ns.shards[0]}
        sid_owned = None
        rejected = 0
        for i in range(20):
            sid = f"s{i}".encode()
            try:
                db.write("default", sid, START + 10**9, 1.0)
                sid_owned = sid
            except KeyError:
                rejected += 1
        assert rejected > 0 and sid_owned is not None
        db.close()
        # restart with full ownership: no poison in the log
        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db2.create_namespace("default", small_opts())
        db2.open(START + HOUR)
        assert db2.read("default", sid_owned, START, START + HOUR)
        db2.close()

    def test_failed_flush_keeps_buffer_and_commitlog(self, tmp_path, monkeypatch):
        # a flush that dies mid-write must not lose the buffered window
        db = make_db(tmp_path)
        sid = b"fragile"
        db.write("default", sid, START + 10**9, 1.0)
        shard = db.namespaces["default"].shard_for(sid)
        from m3_tpu.storage import fileset as fs_mod

        def boom(self_):
            raise RuntimeError("disk full")

        monkeypatch.setattr(fs_mod.FilesetWriter, "close", boom)
        with pytest.raises(RuntimeError):
            shard.flush(START)
        monkeypatch.undo()
        # buffer still holds the window; a later flush succeeds
        assert shard.buffer.points_in(START) == 1
        assert shard.flush(START)
        dps = db.read("default", sid, START, START + HOUR)
        assert [d.value for d in dps] == [1.0]
        db.close()

    def test_open_is_not_destructive(self, tmp_path):
        # expired volumes are skipped at open, deleted only by tick/expire
        db = make_db(tmp_path)
        db.write("default", b"old", START + 10**9, 1.0)
        db.flush_all()
        db.close()
        far = START + 48 * HOUR
        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db2.create_namespace("default", small_opts())
        db2.open(far)
        # not visible (expired), but still on disk
        assert db2.read("default", b"old", START, START + HOUR) == []
        data_dir = os.path.join(str(tmp_path / "db"), "data", "default")
        remaining = [f for d in os.listdir(data_dir)
                     for f in os.listdir(os.path.join(data_dir, d))]
        assert remaining  # files survived open()
        db2.tick(far)  # explicit maintenance reclaims
        remaining = [f for d in os.listdir(data_dir)
                     for f in os.listdir(os.path.join(data_dir, d))]
        assert remaining == []
        db2.close()


class TestBatchedShardRouting:
    """PR-3 satellite: read_many's series->shard routing is one
    vectorized murmur3 pass, bit-identical to the scalar path."""

    def test_batch_hash_matches_scalar(self):
        import numpy as np

        from m3_tpu.utils.hash import murmur3_32, murmur3_32_batch

        rng = np.random.default_rng(11)
        ids = [bytes(rng.integers(0, 256, int(n)).astype(np.uint8))
               for n in rng.integers(0, 48, 512)]
        ids += [b"", b"a", b"ab", b"abc", b"abcd", b"abcdefgh" * 8]
        for seed in (0, 42):
            got = murmur3_32_batch(ids, seed)
            assert got.dtype == np.uint32
            assert got.tolist() == [murmur3_32(x, seed) for x in ids]

    def test_lookup_many_matches_lookup(self):
        from m3_tpu.storage.sharding import ShardSet

        ss = ShardSet(16)
        ids = [b"series_%04d" % i for i in range(500)]
        assert ss.lookup_many(ids) == [ss.lookup(s) for s in ids]
        # small batches ride the scalar path; same answers
        assert ss.lookup_many(ids[:3]) == [ss.lookup(s) for s in ids[:3]]
        assert ss.lookup_many([]) == []


def _random_ids(seed: int, n: int) -> list[bytes]:
    rng = np.random.default_rng(seed)
    return [bytes(rng.integers(0, 256, int(k)).astype(np.uint8))
            for k in rng.integers(0, 401, n)]


def _route_counts() -> tuple[float, float]:
    from m3_tpu.utils.instrument import default_registry

    c = default_registry().counters
    return (c[("storage.shard_route.hit", ())].value,
            c[("storage.shard_route.miss", ())].value)


class TestRememberedShardRouting:
    """PR 27: a namespace hashes a series id to its shard once and
    probes a two-generation map after that (storage/sharding.py
    ShardRoutes); the answers are ShardSet.lookup's, bit for bit."""

    # under and over sharding._BATCH_MIN (64), and nothing at all
    @pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 700])
    def test_routes_match_scalar_lookup(self, n):
        from m3_tpu.storage.sharding import ShardRoutes, ShardSet

        ss = ShardSet(16)
        ids = _random_ids(27 + n, n)
        ids += ids[: n // 3]  # repeats inside one batch
        want = [ss.lookup(s) for s in ids]
        routes = ShardRoutes()
        h0, m0 = _route_counts()
        assert routes.lookup_many(ss, ids) == want  # hashed
        h1, m1 = _route_counts()
        assert routes.lookup_many(ss, ids) == want  # remembered
        h2, m2 = _route_counts()
        assert len(routes) == len(set(ids))
        # once per call, the call's totals: every id is a hit or a miss
        assert (h1 - h0) + (m1 - m0) == len(ids)
        # a repeat of an unseen id is not hashed twice
        assert m1 - m0 == len(set(ids))
        assert (h2 - h1, m2 - m1) == (len(ids), 0)
        # another (seed, n_shards) is another mapping, not a stale hit
        other = ShardSet(16, seed=7)
        assert routes.lookup_many(other, ids) == [other.lookup(s)
                                                  for s in ids]

    def test_routes_survive_assign_shards(self, tmp_path):
        db = make_db(tmp_path)
        ns = db.namespaces["default"]
        ids = [b"cpu_usage_user|hostname=host_%d" % i for i in range(200)]
        want = [ns.shard_set.lookup(s) for s in ids]
        assert ns.shards_of(ids) == want
        old_set = ns.shard_set
        db.assign_shards({0, 1}, START)
        assert ns.shard_set is not old_set
        assert ns.shards_of(ids) == want  # routing does not move
        _, m0 = _route_counts()
        kept = [s for s, sh in zip(ids, want) if sh in (0, 1)]
        gone = [s for s, sh in zip(ids, want) if sh in (2, 3)]
        assert len(ns.read_many(kept, START, START + HOUR)) == len(kept)
        assert _route_counts()[1] == m0  # ... and is not hashed again
        # ownership is tested on every call: a shard handed away is
        # refused on the next read, and per row on the next write
        with pytest.raises(KeyError, match="not owned by this node"):
            ns.read_many(kept[:2] + gone[:1], START, START + HOUR)
        by_shard, errors = ns.route_many(kept[:2] + gone[:2])
        assert sorted(np.concatenate(list(by_shard.values())).tolist()) \
            == [0, 1]
        assert set(errors) == {2, 3}
        assert all("not owned by this node" in e for e in errors.values())
        res = db.write_batch("default", [
            (b"m", [(b"k", b"%d" % i)], START + 10**9, 1.0)
            for i in range(40)])
        sids = [tags_to_id(b"m", [(b"k", b"%d" % i)]) for i in range(40)]
        for sid, err in zip(sids, res):
            if ns.shard_set.lookup(sid) in (0, 1):
                assert err is None
            else:
                assert "not owned by this node" in err
        # taken back: served again from the same remembered routes
        db.assign_shards({0, 1, 2, 3}, START)
        assert len(ns.read_many(ids, START, START + HOUR)) == len(ids)
        db.close()

    def test_routes_bounded_by_two_rotations(self, tmp_path):
        db = make_db(tmp_path)
        ns = db.namespaces["default"]
        block = ns.opts.retention.block_size_ns
        idle = [b"idle-%d" % i for i in range(100)]
        busy = [b"busy-%d" % i for i in range(100)]
        want = [ns.shard_set.lookup(s) for s in busy]
        ns.expire(START)
        ns.shards_of(idle + busy)
        assert len(ns._routes) == 200
        ns.expire(START + block // 2)  # same cutoff: no rotation
        ns.expire(START + block)       # first rotation
        assert idle[0] in ns._routes and busy[0] in ns._routes
        _, m0 = _route_counts()
        assert ns.shards_of(busy) == want  # touched in between: kept
        assert _route_counts()[1] == m0
        ns.expire(START + 2 * block)   # second rotation
        assert len(ns._routes) == 100
        assert all(s not in ns._routes for s in idle)
        assert all(s in ns._routes for s in busy)
        assert ns.shards_of(busy) == want
        assert _route_counts()[1] == m0
        # a churned-out id costs one hash when it comes back
        assert ns.shards_of(idle[:5]) == [ns.shard_set.lookup(s)
                                          for s in idle[:5]]
        assert _route_counts()[1] == m0 + 5
        # the tick is what drives it
        db.tick(START + 3 * block)
        db.tick(START + 4 * block)
        assert len(ns._routes) == 0
        db.close()

    def test_routes_under_threads_and_rotation(self):
        import threading

        from m3_tpu.storage.sharding import ShardRoutes, ShardSet

        ss = ShardSet(8)
        ids = [b"cpu_usage_user|arch=x64|hostname=host_%d|team=SF" % i
               for i in range(2000)]
        want = [ss.lookup(s) for s in ids]
        routes = ShardRoutes()
        stop = threading.Event()
        wrong: list = []

        def rotate():
            while not stop.is_set():
                routes.rotate()

        def route(k):
            for r in range(30):
                part = ids[(k * 97 + r * 131) % 1000:]
                if routes.lookup_many(ss, part) != want[-len(part):]:
                    wrong.append((k, r))

        h0, m0 = _route_counts()
        rotator = threading.Thread(target=rotate)
        workers = [threading.Thread(target=route, args=(k,))
                   for k in range(8)]
        rotator.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        stop.set()
        rotator.join()
        assert wrong == []
        h1, m1 = _route_counts()
        routed = sum(2000 - (k * 97 + r * 131) % 1000
                     for k in range(8) for r in range(30))
        assert (h1 - h0) + (m1 - m0) == routed

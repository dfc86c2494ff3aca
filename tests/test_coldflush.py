"""Warm/cold write split + cold flush (VERDICT r2 "Next round" #7).

Reference semantics matched: writes to blocks that already flushed are a
separate WriteType routed to a separate flush pass producing version-
bumped volumes (src/dbnode/storage/series/buffer.go:77-147,
storage/coldflush.go, persist/fs/merger.go) — backfill must never drag
decode+merge work into the warm flush path.
"""

import numpy as np

from m3_tpu.storage.database import Database
from m3_tpu.storage.options import (
    DatabaseOptions,
    NamespaceOptions,
    RetentionOptions,
)
from m3_tpu.utils.ident import tags_to_id

HOUR = 3600 * 10**9
MIN = 60 * 10**9
START = 1_599_998_400_000_000_000  # aligned 2h block start


def bits(v: float) -> int:
    return int(np.float64(v).view(np.uint64))


def make_db(tmp_path):
    db = Database(str(tmp_path), DatabaseOptions(n_shards=2))
    db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(
            retention_ns=48 * HOUR,
            block_size_ns=2 * HOUR,
            buffer_past_ns=10 * MIN,
        )
    ))
    db.open(START)
    return db


def write(db, name: bytes, t_ns: int, v: float):
    db.write_tagged("default", name, [(b"host", b"a")], t_ns, v)


def shard_of(db, name: bytes):
    ns = db.namespaces["default"]
    sid = tags_to_id(name, [(b"host", b"a")])
    return ns.shard_for(sid), sid


class TestWarmColdSplit:
    def test_backfill_classified_cold_and_kept_out_of_warm_pass(self, tmp_path):
        db = make_db(tmp_path)
        # warm ingest into block 0, then age it out and warm-flush it
        for i in range(20):
            write(db, b"cpu", START + i * MIN, float(i))
        now = START + 2 * HOUR + 11 * MIN  # past buffer_past
        assert db.tick(now)["flushed"] >= 1
        shard, sid = shard_of(db, b"cpu")
        assert shard._filesets[START].volume == 0
        warm_before = shard.warm_writes

        # backfill lands in the flushed block -> cold write
        write(db, b"cpu", START + 30 * MIN + 1 * MIN, 99.0)
        assert shard.cold_writes == 1
        assert shard.warm_writes == warm_before
        assert shard.cold_dirty_block_starts() == [START]
        # the warm pass must NOT pick the block up again
        assert shard.flushable_block_starts(now) == []
        assert db.namespaces["default"].flush(now) == 0
        assert shard._filesets[START].volume == 0  # untouched by warm pass

        # the cold pass merges it into a version-bumped volume
        assert db.namespaces["default"].cold_flush() == 1
        assert shard._filesets[START].volume == 1
        assert shard.cold_dirty_block_starts() == []

        # cold data queryable after its flush, merged with warm points
        t, v = shard.read(sid, START, START + 2 * HOUR)
        assert (START + 31 * MIN) in t.tolist()
        vals = v.view(np.float64)
        assert 99.0 in vals.tolist()
        db.close()

    def test_warm_flush_latency_structurally_flat_under_backfill(self, tmp_path):
        """The warm pass does no decode/merge work for backfilled blocks:
        with a cold-dirty block present, the warm pass flushes ONLY the
        new warm window (first volume), and the tick reports the cold
        merge separately."""
        db = make_db(tmp_path)
        for i in range(10):
            write(db, b"m", START + i * MIN, float(i))
        now1 = START + 2 * HOUR + 11 * MIN
        db.tick(now1)
        # sustained backfill into the flushed block + fresh warm ingest
        for i in range(50):
            write(db, b"m", START + 40 * MIN + i * MIN % (20 * MIN), float(i))
        for i in range(10):
            write(db, b"m", now1 + i * MIN, float(i))
        now2 = START + 4 * HOUR + 11 * MIN
        out = db.tick(now2)
        # warm pass: exactly the new window's first volume; cold pass
        # merged the backfill
        shard, sid = shard_of(db, b"m")
        assert out["cold_flushed"] >= 1
        assert shard._filesets[START].volume >= 1  # cold bump
        t, _ = shard.read(sid, START, START + 2 * HOUR)
        assert len(t) >= 20  # warm + backfill merged
        db.close()

    def test_cold_flush_survives_restart(self, tmp_path):
        """Version-bumped cold volumes are what bootstrap loads."""
        db = make_db(tmp_path)
        for i in range(5):
            write(db, b"r", START + i * MIN, float(i))
        db.tick(START + 2 * HOUR + 11 * MIN)
        write(db, b"r", START + 50 * MIN, 7.5)
        db.namespaces["default"].cold_flush()
        db.close()

        db2 = make_db(tmp_path)
        shard, sid = shard_of(db2, b"r")
        t, v = shard.read(sid, START, START + 2 * HOUR)
        assert (START + 50 * MIN) in t.tolist()
        assert 7.5 in v.view(np.float64).tolist()
        db2.close()

    def test_merged_series_longer_than_buffer_window_bit_exact(self, tmp_path):
        """A cold flush where the merged series (40 old points + 1 new)
        is longer than any series of the buffer window it merges (at
        most 3 rows): every stream of the version-bumped volume, merged,
        new and carried over, decodes under the scalar decoder to
        exactly the acked samples, bit for bit."""
        from m3_tpu.encoding.m3tsz import decode

        db = make_db(tmp_path)
        rng = np.random.default_rng(5)
        want: dict[bytes, dict[int, int]] = {}

        def put(name, t_ns, v):
            write(db, name, t_ns, v)
            want.setdefault(name, {})[t_ns] = bits(v)  # last write wins

        for i in range(40):
            put(b"long", START + i * MIN, float(rng.normal(50, 7)))
        for i in range(4):
            put(b"kept", START + i * MIN, float(i) + 0.25)
        assert db.tick(START + 2 * HOUR + 11 * MIN)["flushed"] >= 1
        put(b"long", START + 90 * MIN, 1.0 / 3.0)        # appended
        put(b"kept2", START + 5 * MIN, 2.5)              # new in the volume
        put(b"kept2", START + 3 * MIN, -0.0)             # out of order
        put(b"kept2", START + 5 * MIN, 7.125)            # overwrites
        assert db.namespaces["default"].cold_flush() >= 1

        for name in (b"long", b"kept", b"kept2"):
            shard, sid = shard_of(db, name)
            reader = shard._filesets[START]
            assert shard.buffer.points_in(START) == 0
            dps = decode(reader.read(sid), int_optimized=False,
                         default_time_unit=shard.opts.write_time_unit)
            got = {d.timestamp_ns: bits(d.value) for d in dps}
            assert [d.timestamp_ns for d in dps] == sorted(want[name])
            assert got == want[name], name
        shard, _sid = shard_of(db, b"long")
        assert shard._filesets[START].volume == 1
        db.close()

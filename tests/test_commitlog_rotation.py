"""A write never meets a closed commitlog writer (ISSUE 37; PERF.md
section 7, fault 1 until PR 37).

``Database.tick`` used to close the active ``CommitLogWriter`` and open
the next with no lock shared with the request threads that had fetched
it: a flush landed on the closed file, the writer poisoned itself and
every write of the batch was refused (HTTP 500 ``partial_write`` at every
rotation). Now the writer swaps its own file under its own lock
(``CommitLogWriter.rotate``) and carries the block windows it holds
entries of, so a retired log is whole and is never missing a window.
"""

import threading
import time

import numpy as np
import pytest

from m3_tpu.storage import commitlog
from m3_tpu.utils import faults
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import (DatabaseOptions, NamespaceOptions,
                                    RetentionOptions)
from m3_tpu.utils.ident import encode_tags, tags_to_id

SEC = 1_000_000_000
START = 1_790_000_000 * SEC
BLOCK = 2 * 3600 * SEC


def bits(v: float) -> int:
    return int(np.float64(v).view(np.uint64))


class TestWriterRotate:
    def test_an_append_is_whole_in_one_file_and_windows_go_with_it(
            self, tmp_path):
        w = commitlog.CommitLogWriter(str(tmp_path / "cl" / "commitlog-1.db"))
        w.write(b"a", b"", START, bits(1.0), 1, window=7)
        w.write_many([b"a", b"b"], [b"", b""],
                     np.array([START + SEC, START], np.int64),
                     np.array([bits(2.0), bits(3.0)], np.uint64), 1, [7, 9])
        old, windows = w.rotate(str(tmp_path / "cl" / "commitlog-2.db"))
        assert old.endswith("commitlog-1.db") and windows == {7, 9}
        assert w.windows == set() and w.path.endswith("commitlog-2.db")
        # the retired file is whole before the next append
        assert [(e.series_id, e.value_bits) for e in commitlog.replay(old)] \
            == [(b"a", bits(1.0)), (b"a", bits(2.0)), (b"b", bits(3.0))]
        # the series registry starts anew: `a` registers again
        w.write(b"a", b"", START + 2 * SEC, bits(4.0), 1, window=9)
        w.close()
        assert [(e.series_id, e.value_bits)
                for e in commitlog.replay(w.path)] == [(b"a", bits(4.0))]
        assert w.windows == {9}

    def test_a_poisoned_writer_is_sound_again_after_a_rotation(
            self, tmp_path):
        w = commitlog.CommitLogWriter(str(tmp_path / "cl" / "commitlog-1.db"))
        w.write(b"a", b"", START, bits(1.0), 1)
        w._f.close()                      # what the old tick did to it
        with pytest.raises(ValueError):
            w.flush()
        with pytest.raises(OSError, match="poisoned"):
            w.write(b"a", b"", START, bits(2.0), 1)
        w._f = open(w.path, "ab")         # rotate closes what is there
        w.rotate(str(tmp_path / "cl" / "commitlog-2.db"))
        w.write(b"a", b"", START, bits(3.0), 1)
        w.close()
        assert [e.value_bits for e in commitlog.replay(w.path)] == [bits(3.0)]

    def test_a_flush_that_fails_in_rotate_leaves_the_old_file_active(
            self, tmp_path, monkeypatch):
        w = commitlog.CommitLogWriter(str(tmp_path / "cl" / "commitlog-1.db"))
        w.write(b"a", b"", START, bits(1.0), 1)
        monkeypatch.setattr(commitlog, "_fsync_timed",
                            lambda fd: (_ for _ in ()).throw(OSError("disk")))
        with pytest.raises(OSError, match="disk"):
            w.rotate(str(tmp_path / "cl" / "commitlog-2.db"))
        assert w.path.endswith("commitlog-1.db")
        monkeypatch.undo()
        # the next rotation retires the poisoned file unflushed
        old, _ = w.rotate(str(tmp_path / "cl" / "commitlog-2.db"))
        assert old.endswith("commitlog-1.db")
        w.write(b"b", b"", START, bits(2.0), 1)
        w.close()
        assert [e.series_id for e in commitlog.replay(w.path)] == [b"b"]


def _database(path) -> Database:
    db = Database(str(path), DatabaseOptions(n_shards=4))
    db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(block_size_ns=BLOCK)))
    db.open(START)
    return db


@pytest.mark.parametrize("via", ["write_batch", "write_tagged", "write"])
def test_an_appender_held_at_the_seam_while_tick_rotates_loses_nothing(
        tmp_path, via):
    """The interleaving itself, not a race for it: the second append is
    held at the `commitlog.write` fault point (after the request thread
    has fetched the writer, before its append) by the plan's injected
    sleep, `Database.tick` snapshots and rotates meanwhile, then the
    append goes on. It is acked, so it has to be in the replayed logs
    and in the read. Before the repair tick closed the writer the thread
    held, and the acked entry went into a buffer nobody flushed."""
    db = _database(tmp_path / "db")
    held, rotated = threading.Event(), threading.Event()

    def hold(_seconds):
        held.set()
        assert rotated.wait(30)

    tags = [(b"h", b"0")]
    sid = tags_to_id(b"cpu", tags)
    result: list = []

    def append(t_ns, v):
        try:
            if via == "write_batch":
                result.append(db.write_batch(
                    "default", [(b"cpu", tags, t_ns, v)])[0])
            elif via == "write_tagged":
                db.write_tagged("default", b"cpu", tags, t_ns, v)
                result.append(None)
            else:
                db.write("default", sid, t_ns, v,
                         encode_tags([(b"__name__", b"cpu"), *tags]))
                result.append(None)
        except Exception as e:  # noqa: BLE001 - the fault under test
            result.append(repr(e))

    with faults.active("commitlog.write=delay:n2", sleep=hold):
        append(START + SEC, 1.0)              # the log now holds a window
        t = threading.Thread(target=append, args=(START + 2 * SEC, 2.0))
        t.start()
        assert held.wait(30)
        first = db._commitlogs["default"].path
        stats = db.tick(START + 3 * SEC)
        assert stats["snapshotted"] >= 1
        assert db._commitlogs["default"].path != first      # it rotated
        rotated.set()
        t.join(30)
    assert not t.is_alive() and result == [None, None]     # none refused
    got = db.read("default", sid, START, START + BLOCK)
    assert [(dp.timestamp_ns, bits(dp.value)) for dp in got] == [
        (START + SEC, bits(1.0)), (START + 2 * SEC, bits(2.0))]
    db.close()
    replayed = [(e.time_ns, e.value_bits)
                for path in commitlog.log_files(db.commitlog_dir("default"))
                for e in commitlog.replay(path)]
    assert sorted(replayed) == [(START + SEC, bits(1.0)),
                                (START + 2 * SEC, bits(2.0))]


@pytest.mark.parametrize("seed", [11, 2**31 + 36])
@pytest.mark.parametrize("mode", ["serial", "pipelined"])
def test_rotation_under_load_refuses_nothing_and_replays_the_acked_set(
        tmp_path, monkeypatch, mode, seed):
    """Four writer threads (two through write_batch, one through
    write_tagged, one through write) while the main thread rotates the
    log every few milliseconds, 200 times: no entry is refused, and the
    retired logs plus the active one replay to exactly the acked set,
    each log listing every window it holds a datapoint of."""
    if mode == "pipelined":
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "16")
    else:
        monkeypatch.setenv("M3_TPU_PIPELINE", "0")
    db = _database(tmp_path / "db")
    rng = np.random.default_rng(seed)
    # every (series, time) is written once; times straddle a block edge
    edge = START - START % BLOCK + BLOCK
    offsets = rng.permutation(40_000)
    stop = threading.Event()
    acked: list[list] = [[] for _ in range(4)]
    refused: list[str] = []

    def t_of(w: int, k: int) -> int:
        return edge + (int(offsets[(w * 10_000 + k) % 40_000]) - 20_000) * SEC

    def batches(w: int) -> None:
        k = 0
        while not stop.is_set():
            entries = [(b"cpu", [(b"h", b"%d" % ((k + i) % 8)),
                                 (b"w", b"%d" % w)], t_of(w, k + i),
                        float(k + i)) for i in range(64)]
            for e, err in zip(entries, db.write_batch("default", entries)):
                if err is not None:
                    refused.append(err)
                else:
                    acked[w].append((tags_to_id(e[0], e[1]), e[2],
                                     bits(e[3])))
            k += 64

    def points(w: int) -> None:
        k = 0
        while not stop.is_set():
            tags = [(b"h", b"%d" % (k % 8)), (b"w", b"%d" % w)]
            try:
                if w == 2:
                    sid = db.write_tagged("default", b"cpu", tags,
                                          t_of(w, k), float(k))
                else:
                    sid = tags_to_id(b"cpu", tags)
                    db.write("default", sid, t_of(w, k), float(k),
                             encode_tags([(b"__name__", b"cpu"), *tags]))
            except Exception as e:  # noqa: BLE001 - the fault under test
                refused.append(repr(e))
            else:
                acked[w].append((sid, t_of(w, k), bits(float(k))))
            k += 1

    threads = [threading.Thread(target=batches if w < 2 else points,
                                args=(w,)) for w in range(4)]
    for t in threads:
        t.start()
    for i in range(200):
        time.sleep(0.003)
        db._rotate_commitlog("default", START + i)
    stop.set()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)
    retired = {p: w for p, w, _ in db._retired_logs["default"]}
    active = db._commitlogs["default"]
    retired[active.path] = active.windows
    db.close()

    assert refused == []
    files = commitlog.log_files(db.commitlog_dir("default"))
    assert len(files) == 201 and set(files) == set(retired)
    replayed = []
    for path in files:
        entries = commitlog.replay(path)     # strict: whole chunks only
        replayed += [(e.series_id, e.time_ns, e.value_bits) for e in entries]
        assert {e.time_ns - e.time_ns % BLOCK for e in entries} \
            <= retired[path]
    want = [x for per in acked for x in per]
    assert len(want) > 1000 and len(set(want)) == len(want)
    assert len(replayed) == len(want) and set(replayed) == set(want)

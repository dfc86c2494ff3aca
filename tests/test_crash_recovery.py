"""Crash-safe durability: kill a writer at an arbitrary byte offset and
prove no acked write is ever lost (ISSUE 2 acceptance).

"Acked" means a commitlog flush(fsync=True) returned — the durability
promise the write path makes. Everything else (buffered datapoints,
torn chunks, half-written fileset volumes) is allowed to die with the
process; recovery = fileset bootstrap + snapshot restore + commitlog
SALVAGE replay, then optionally peer bootstrap onto a fresh node.

The deterministic cases here run in tier-1. The seeded many-iteration
loops are `chaos`-marked (excluded from tier-1; `run_tests.sh chaos`
drives them at M3_TPU_CHAOS_ITERS=200).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from m3_tpu.storage import commitlog
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import (
    DatabaseOptions,
    NamespaceOptions,
    RetentionOptions,
)
from m3_tpu.utils import faults

HOUR = 3600 * 10**9
SEC = 10**9
START = 1_599_998_400_000_000_000  # 2h-aligned block start


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.disable()
    yield
    faults.disable()


def bits(v: float) -> int:
    return int(np.float64(v).view(np.uint64))


def small_opts() -> NamespaceOptions:
    return NamespaceOptions(
        retention=RetentionOptions(
            retention_ns=24 * HOUR,
            block_size_ns=2 * HOUR,
            buffer_past_ns=10 * 60 * SEC,
        )
    )


def make_db(path: str) -> Database:
    db = Database(path, DatabaseOptions(n_shards=2))
    db.create_namespace("default", small_opts())
    return db


def hard_kill(db: Database) -> None:
    """Release a crashed database's OS resources the way process death
    would: no flush, no durability side effects (Database.close would
    flush commitlogs and fake an orderly shutdown)."""
    for log in db._commitlogs.values():
        try:
            log._f.close()
        except OSError:
            pass
    db._commitlogs.clear()
    for ns in db.namespaces.values():
        for shard in ns.shards.values():
            try:
                shard.close()
            except Exception:  # noqa: BLE001 - best-effort fd release
                pass


def read_all(db: Database, sid: bytes) -> dict[int, float]:
    t, v = db.namespaces["default"].read(sid, START, START + 24 * HOUR)
    return dict(zip(t.tolist(), v.view(np.float64).tolist()))


# ---------------------------------------------------------------------------
# commitlog salvage semantics
# ---------------------------------------------------------------------------


class TestSalvage:
    def _write_log(self, path, values):
        w = commitlog.CommitLogWriter(path)
        for i, v in enumerate(values):
            w.write(b"s", b"", START + i * SEC, bits(v), 1)
            w.flush()
        w.close()

    def test_interior_corruption_strict_raises_salvage_truncates(self, tmp_path):
        p = str(tmp_path / "cl" / "commitlog-1.db")
        self._write_log(p, [1.0, 2.0, 3.0])
        raw = bytearray(open(p, "rb").read())
        # first chunk = 12-byte header + 36-byte payload (14-byte series
        # register + 22-byte write); flip a payload byte in chunk TWO
        chunk1_end = 12 + 14 + 22
        raw[chunk1_end + 12 + 3] ^= 0xFF
        open(p, "wb").write(bytes(raw))

        with pytest.raises(ValueError):
            commitlog.replay(p)  # strict mode bricks — the inspector's job
        entries, report = commitlog.replay_salvage(p)
        assert [e.value_bits for e in entries] == [bits(1.0)]
        assert not report.clean
        assert report.truncated_at == chunk1_end
        assert report.dropped_bytes == len(raw) - report.truncated_at
        assert report.entries == 1 and report.chunks == 1

    def test_salvaged_bootstrap_recovers_prefix(self, tmp_path):
        """A corrupt interior chunk no longer bricks Database.open — the
        prefix replays and the node comes up (the round-2 brick bug)."""
        db = make_db(str(tmp_path / "db"))
        db.open(START)
        for i in range(5):
            db.write("default", b"s", START + i * SEC, float(i))
            db._commitlogs["default"].flush(fsync=True)
        hard_kill(db)
        [path] = commitlog.log_files(db.commitlog_dir("default"))
        raw = bytearray(open(path, "rb").read())
        mid = len(raw) // 2
        raw[mid] ^= 0xFF  # corrupt an interior chunk
        open(path, "wb").write(bytes(raw))

        db2 = make_db(str(tmp_path / "db"))
        db2.open(START)  # must NOT raise
        got = read_all(db2, b"s")
        assert got  # the clean prefix came back
        assert all(got[START + i * SEC] == float(i) for i, _ in
                   enumerate(range(len(got))))
        db2.close()

    def test_torn_tail_is_clean_not_truncation(self, tmp_path):
        p = str(tmp_path / "cl" / "commitlog-1.db")
        self._write_log(p, [1.0, 2.0])
        raw = open(p, "rb").read()
        open(p, "wb").write(raw[:-5])  # torn mid-final-chunk
        entries, report = commitlog.replay_salvage(p)
        assert [e.value_bits for e in entries] == [bits(1.0)]
        assert report.clean and report.torn_tail


# ---------------------------------------------------------------------------
# deterministic kill-mid-flush recovery
# ---------------------------------------------------------------------------


class TestCrashRecovery:
    def test_acked_writes_survive_torn_commitlog_flush(self, tmp_path):
        db = make_db(str(tmp_path / "db"))
        db.open(START)
        acked: dict[int, float] = {}
        db.write("default", b"s", START + SEC, 1.0)
        db._commitlogs["default"].flush(fsync=True)
        acked[START + SEC] = 1.0
        db.write("default", b"s", START + 2 * SEC, 2.0)
        with faults.active("commitlog.flush=torn", seed=4):
            with pytest.raises(faults.SimulatedCrash):
                db._commitlogs["default"].flush(fsync=True)
        hard_kill(db)

        db2 = make_db(str(tmp_path / "db"))
        db2.open(START)
        got = read_all(db2, b"s")
        for t, v in acked.items():
            assert got.get(t) == v
        db2.close()

    def test_crash_mid_fileset_flush_recovers_from_commitlog(self, tmp_path):
        """tick() dies inside the fileset persist: the volume is
        incomplete (ignored at bootstrap), the commitlog was not retired,
        and every acked write comes back."""
        db = make_db(str(tmp_path / "db"))
        db.open(START)
        acked: dict[tuple[bytes, int], float] = {}
        for i in range(20):
            sid = b"s%d" % (i % 3)
            db.write("default", sid, START + i * 60 * SEC, float(i))
            acked[(sid, START + i * 60 * SEC)] = float(i)
        db._commitlogs["default"].flush(fsync=True)
        with faults.active("fileset.persist=crash:n4", seed=2):
            with pytest.raises(faults.SimulatedCrash):
                db.tick(now_ns=START + 3 * HOUR)
        hard_kill(db)

        db2 = make_db(str(tmp_path / "db"))
        db2.open(START + 3 * HOUR)
        for (sid, t), v in acked.items():
            assert read_all(db2, sid).get(t) == v, (sid, t)
        # and the node keeps working: the interrupted flush completes
        db2.tick(now_ns=START + 3 * HOUR)
        for (sid, t), v in acked.items():
            assert read_all(db2, sid).get(t) == v, (sid, t)
        db2.close()

    def test_same_seed_reproduces_same_crash(self, tmp_path):
        spec = ("commitlog.flush=torn:p0.2;commitlog.fsync=error:p0.1;"
                "fileset.persist=crash:p0.15")

        def run(root):
            db = make_db(root)
            db.open(START)
            plan = faults.configure(spec, seed=21)
            crash_step = None
            try:
                for i in range(30):
                    db.write("default", b"s", START + i * 60 * SEC, float(i))
                    if i % 5 == 4:
                        db._commitlogs["default"].flush(fsync=True)
                    if i % 11 == 10:
                        db.tick(now_ns=START + 3 * HOUR)
            except (faults.SimulatedCrash, faults.InjectedError,
                    faults.InjectedTimeout):
                crash_step = i
            finally:
                faults.disable()
                hard_kill(db)
            return crash_step, list(plan.schedule)

        c1, s1 = run(str(tmp_path / "a"))
        c2, s2 = run(str(tmp_path / "b"))
        assert (c1, s1) == (c2, s2)
        assert s1  # the spec actually fired


# ---------------------------------------------------------------------------
# the seeded chaos loop (opt-in: run_tests.sh chaos)
# ---------------------------------------------------------------------------


CHAOS_SPEC = (
    "commitlog.flush=torn:p0.06;"
    "commitlog.fsync=error:p0.04;"
    "commitlog.write=error:p0.01;"
    "fileset.persist=crash:p0.05;"
    "fileset.write=torn:p0.03;"
    "shard.flush=crash:p0.02"
)


def _chaos_iteration(root: str, seed: int) -> tuple[bool, int]:
    """One kill-mid-anything run: returns (crashed, n_acked). Asserts the
    acked set survives restart + salvage replay, then peer-bootstraps a
    fresh node from the survivor and asserts again."""
    from m3_tpu.storage.peers import InProcessPeer, bootstrap_shard_from_peers

    db = make_db(os.path.join(root, "db"))
    db.open(START)
    acked: dict[tuple[bytes, int], float] = {}
    pending: dict[tuple[bytes, int], float] = {}
    crashed = False
    try:
        for step in range(40):
            sid = b"series-%d" % (step % 5)
            t = START + step * 90 * SEC  # 40 steps stay inside one block
            v = float(seed * 1000 + step)
            db.write("default", sid, t, v)
            pending[(sid, t)] = v
            if step % 7 == 6:
                db._commitlogs["default"].flush(fsync=True)
                acked.update(pending)
                pending.clear()
            if step % 13 == 12:
                db.tick(now_ns=START + 3 * HOUR)
    except (faults.SimulatedCrash, faults.InjectedError,
            faults.InjectedTimeout):
        crashed = True
    finally:
        faults.disable()
        hard_kill(db)

    # restart: fileset bootstrap + snapshot restore + salvage replay
    db2 = make_db(os.path.join(root, "db"))
    db2.open(START + 3 * HOUR)
    by_sid: dict[bytes, dict[int, float]] = {}
    for (sid, t), v in acked.items():
        if sid not in by_sid:
            by_sid[sid] = read_all(db2, sid)
        assert by_sid[sid].get(t) == v, \
            f"seed={seed}: acked write {(sid, t, v)} lost after recovery"

    # peer leg: a brand-new node bootstrapped from the survivor serves
    # every acked write too (flush first: peers stream fileset volumes)
    db2.flush_all()
    db3 = make_db(os.path.join(root, "peer"))
    db3.open(START + 3 * HOUR)
    for shard_id in db2.namespaces["default"].shards:
        bootstrap_shard_from_peers(db3, "default", shard_id,
                                   [InProcessPeer(db2)])
    for (sid, t), v in acked.items():
        got = read_all(db3, sid)
        assert got.get(t) == v, \
            f"seed={seed}: acked write {(sid, t, v)} lost after peer bootstrap"
    db2.close()
    db3.close()
    return crashed, len(acked)


BATCH_CHAOS_SPEC = CHAOS_SPEC + ";db.write_batch=error:p0.03"


def _chaos_iteration_batched(root: str, seed: int) -> tuple[bool, int]:
    """The batched twin of _chaos_iteration: writes arrive through
    db.write_batch (ISSUE 5), acked per batch after a commitlog fsync.
    The invariant is identical — no entry of an ACKED batch is ever lost
    after a kill mid-batch-flush + salvage replay — and per-entry
    results gate what may enter the pending set at all."""
    from m3_tpu.utils.ident import tags_to_id

    db = make_db(os.path.join(root, "db"))
    db.open(START)
    acked: dict[tuple[bytes, int], float] = {}
    pending: dict[tuple[bytes, int], float] = {}
    crashed = False
    try:
        for step in range(12):
            entries = []
            for k in range(6):
                i = step * 6 + k
                entries.append((b"m-%d" % (i % 5), [(b"k", b"v")],
                                START + i * 90 * SEC, float(seed * 1000 + i)))
            try:
                results = db.write_batch("default", entries)
            except (faults.InjectedError, faults.InjectedTimeout):
                continue  # whole batch refused: nothing pending from it
            for (m, tags, t, v), err in zip(entries, results):
                if err is None:
                    pending[(tags_to_id(m, tags), t)] = v
            if step % 3 == 2:
                db._commitlogs["default"].flush(fsync=True)
                acked.update(pending)
                pending.clear()
            if step % 5 == 4:
                db.tick(now_ns=START + 3 * HOUR)
    except (faults.SimulatedCrash, faults.InjectedError,
            faults.InjectedTimeout):
        crashed = True
    finally:
        faults.disable()
        hard_kill(db)

    db2 = make_db(os.path.join(root, "db"))
    db2.open(START + 3 * HOUR)
    by_sid: dict[bytes, dict[int, float]] = {}
    for (sid, t), v in acked.items():
        if sid not in by_sid:
            by_sid[sid] = read_all(db2, sid)
        assert by_sid[sid].get(t) == v, \
            f"seed={seed}: acked batched write {(sid, t, v)} lost"
    db2.close()
    return crashed, len(acked)


# the repair-plane spec: kills land at the cycle boundary (daemon dying
# between compare and merge) AND inside the volume write (repair killed
# mid-persist leaves .tmp leftovers / a torn volume the next cycle must
# absorb); peer partitions are injected at the peer wrapper below
REPAIR_CHAOS_SPEC = (
    "repair.cycle=crash:p0.15;"
    "fileset.persist=crash:p0.08;"
    "fileset.write=torn:p0.05"
)


def _repair_chaos_iteration(root: str, seed: int) -> tuple[int, int]:
    """One seeded anti-entropy storm (ISSUE 9): two divergent replicas
    repair each other through flaky peers while kills land mid-cycle and
    mid-volume-write and a reader thread hammers both sides across the
    volume swaps. Invariants: reads NEVER error (a repair swap must be
    invisible to serving), and once the faults heal, clean daemon cycles
    reach rollup-digest equality with every written datapoint readable
    on BOTH replicas. Returns (crashes_survived, clean_cycles_used)."""
    import random
    import threading

    from m3_tpu.storage import peers as peers_mod
    from m3_tpu.storage.repair import RepairDaemon

    rng = random.Random(f"repair-chaos:{seed}")
    a = make_db(os.path.join(root, "a"))
    a.open(START)
    b = make_db(os.path.join(root, "b"))
    b.open(START)
    expect: dict[bytes, dict[int, float]] = {}
    for i in range(30):
        sid = b"s-%d" % (i % 8)
        t = START + i * 90 * SEC
        v = float(seed * 1000 + i)
        for db in ((a,), (b,), (a, b))[rng.randrange(3)]:  # divergence
            db.write("default", sid, t, v)
        expect.setdefault(sid, {})[t] = v
    a.flush_all()
    b.flush_all()

    class FlakyPeer(peers_mod.InProcessPeer):
        """Partition mid-stream: any RPC — including between the metadata
        fetch and the stream — can drop with a seeded probability."""

        def __init__(self, db, prng, p):
            super().__init__(db)
            self._prng, self._p = prng, p

        def _maybe_drop(self):
            if self._prng.random() < self._p["p"]:
                raise ConnectionError("injected partition")

        def rollup_digests(self, *args):
            self._maybe_drop()
            return super().rollup_digests(*args)

        def block_metadata(self, *args):
            self._maybe_drop()
            return super().block_metadata(*args)

        def stream_block(self, *args):
            self._maybe_drop()
            return super().stream_block(*args)

    prng = random.Random(f"partition:{seed}")
    drop = {"p": 0.25}  # healed to 0.0 after the storm
    da = RepairDaemon(a, lambda: a.owned_shards,
                      lambda s: [FlakyPeer(b, prng, drop)])
    db_ = RepairDaemon(b, lambda: b.owned_shards,
                       lambda s: [FlakyPeer(a, prng, drop)])

    # the stale-reader swap race: reads race every repair volume swap;
    # the retire grace keeps captured readers alive, so a reader must
    # never observe an error (values may be pre- or post-repair)
    stop = threading.Event()
    read_errors: list[str] = []

    def _hammer():
        while not stop.is_set():
            try:
                for sid in list(expect):
                    read_all(a, sid)
                    read_all(b, sid)
            except Exception as e:  # noqa: BLE001 - the assertion payload
                read_errors.append(repr(e))
                return

    reader = threading.Thread(target=_hammer, name="swap-race-reader")
    reader.start()

    crashes = 0
    faults.configure(REPAIR_CHAOS_SPEC, seed=seed)
    try:
        for _ in range(6):
            for d in (da, db_):
                try:
                    d.run_cycle()
                except faults.SimulatedCrash:
                    crashes += 1  # the daemon died mid-repair; "restart"
    finally:
        faults.disable()

    # healed: faults off AND partitions closed — clean cycles must
    # converge the pair within a small budget
    drop["p"] = 0.0
    clean_cycles = 0
    converged = False
    while clean_cycles < 8 and not converged:
        da.run_cycle()
        db_.run_cycle()
        clean_cycles += 1
        converged = all(
            peers_mod.local_rollup_digests(a, "default", s)
            == peers_mod.local_rollup_digests(b, "default", s)
            for s in a.owned_shards
        )
    stop.set()
    reader.join(10.0)
    assert not read_errors, \
        f"seed={seed}: read failed during repair swaps: {read_errors[:3]}"
    assert converged, f"seed={seed}: no convergence in {clean_cycles} cycles"
    for name, db in (("a", a), ("b", b)):
        for sid, tv in expect.items():
            got = read_all(db, sid)
            for t, v in tv.items():
                assert got.get(t) == v, \
                    f"seed={seed}: {name} lost {(sid, t, v)} after repair"
    a.close()
    b.close()
    return crashes, clean_cycles


# the pipelined-dataflow spec (ISSUE 14): the batched kill/torn-write
# sweep with the WAL chunked onto the executor lane (tiny chunk so every
# batch pipelines) and submit-time task faults landing mid-pipeline.
# M3_TPU_PIPELINE=0 pins the serial path for bisection — the same seeds
# run the seed-era code body.
PIPELINE_CHAOS_SPEC = BATCH_CHAOS_SPEC + ";pipeline.task=error:p0.03"


class TestChaosQuick:
    def test_chaos_pipelined_iterations_quick(self, tmp_path, monkeypatch):
        """Kill/torn-write mid-pipeline (ISSUE 14): with the write-side
        overlap ARMED (chunked WAL lane) and pipeline.task faults firing,
        no entry of an acked batch is ever lost across restart + salvage
        replay — a chunk is buffered only after ITS WAL append, so the
        acked => durable contract holds chunk by chunk."""
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "4")
        crashes = 0
        for seed in range(6):
            faults.configure(PIPELINE_CHAOS_SPEC, seed=seed)
            crashed, _n = _chaos_iteration_batched(
                str(tmp_path / f"p{seed}"), seed)
            crashes += crashed
        assert crashes >= 1

    def test_pipeline_hatch_pins_serial_under_chaos(self, tmp_path,
                                                    monkeypatch):
        """The bisection hatch: the same seeded sweep with
        M3_TPU_PIPELINE=0 runs the serial write body (pipeline.task
        never fires — no tasks exist) and holds the same contract."""
        monkeypatch.setenv("M3_TPU_PIPELINE", "0")
        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "4")
        for seed in range(3):
            plan = faults.configure(PIPELINE_CHAOS_SPEC, seed=seed)
            _chaos_iteration_batched(str(tmp_path / f"s{seed}"), seed)
            assert not any(p == "pipeline.task"
                           for p, *_ in plan.schedule), \
                "serial path must never reach the pipeline seam"

    def test_chaos_paged_iterations_quick(self, tmp_path):
        """The batched kill/torn-write sweep over page-pool buffers and
        the ragged flush body (ISSUE 15), four seeds of its own under
        the batch spec — zero acked-write loss."""
        crashes = 0
        for seed in range(4):
            faults.configure(BATCH_CHAOS_SPEC, seed=seed)
            crashed, _n = _chaos_iteration_batched(
                str(tmp_path / f"pg{seed}"), seed)
            crashes += crashed
        assert crashes >= 1

    def test_repair_chaos_paged_iteration(self, tmp_path):
        """One seeded repair-storm iteration (seed 1): repair convergence
        (rollup-digest equality) through the ragged flush/snapshot
        bodies."""
        _c, cycles = _repair_chaos_iteration(str(tmp_path / "pg"), 1)
        assert cycles >= 1

    def test_chaos_iterations_quick(self, tmp_path):
        """A handful of seeds in tier-1 so the harness itself never rots;
        the 200-iteration sweep is the chaos lane."""
        crashes = 0
        for seed in range(6):
            faults.configure(CHAOS_SPEC, seed=seed)
            crashed, _n = _chaos_iteration(str(tmp_path / str(seed)), seed)
            crashes += crashed
        assert crashes >= 1  # the spec is hot enough to matter

    def test_chaos_batched_iterations_quick(self, tmp_path):
        crashes = 0
        for seed in range(6):
            faults.configure(BATCH_CHAOS_SPEC, seed=seed)
            crashed, _n = _chaos_iteration_batched(
                str(tmp_path / str(seed)), seed)
            crashes += crashed
        assert crashes >= 1

    def test_repair_chaos_iterations_quick(self, tmp_path):
        """Anti-entropy storm, tier-1 sized (the sweep is the chaos
        lane). The iteration arms its own spec AFTER seeding the
        divergence — setup flushes must not eat the injected kills."""
        crashes = 0
        for seed in range(4):
            c, _cycles = _repair_chaos_iteration(
                str(tmp_path / str(seed)), seed)
            crashes += c
        assert crashes >= 1  # kills actually landed mid-repair


@pytest.mark.chaos
class TestChaosFull:
    def test_chaos_kill_mid_flush_never_loses_acked_writes(self, tmp_path):
        iters = int(os.environ.get("M3_TPU_CHAOS_ITERS", "200"))
        crashes = acked_total = 0
        for seed in range(iters):
            faults.configure(CHAOS_SPEC, seed=seed)
            crashed, n = _chaos_iteration(str(tmp_path / str(seed)), seed)
            crashes += crashed
            acked_total += n
        # the sweep must actually exercise the crash paths, not no-op
        assert crashes >= iters // 10
        assert acked_total > 0

    def test_chaos_batched_kill_mid_flush_never_loses_acked_writes(
            self, tmp_path):
        """The same seeded sweep with the ISSUE-5 batched write path:
        crash-mid-batch-flush (torn WAL chunks included) never loses an
        entry of an acked batch."""
        iters = int(os.environ.get("M3_TPU_CHAOS_ITERS", "200"))
        crashes = acked_total = 0
        for seed in range(iters):
            faults.configure(BATCH_CHAOS_SPEC, seed=seed)
            crashed, n = _chaos_iteration_batched(
                str(tmp_path / str(seed)), seed)
            crashes += crashed
            acked_total += n
        assert crashes >= iters // 10
        assert acked_total > 0

    def test_chaos_pipelined_kill_mid_flush_never_loses_acked_writes(
            self, tmp_path, monkeypatch):
        """The ISSUE-14 sweep: the batched chaos iteration with the WAL
        lane armed fleet-wide (tiny chunks, pipeline.task faults) across
        M3_TPU_CHAOS_ITERS seeds — zero acked-write loss with overlap
        enabled."""
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        monkeypatch.setenv("M3_TPU_PIPELINE_WAL_CHUNK", "4")
        iters = int(os.environ.get("M3_TPU_CHAOS_ITERS", "200"))
        crashes = acked_total = 0
        for seed in range(iters):
            faults.configure(PIPELINE_CHAOS_SPEC, seed=seed)
            crashed, n = _chaos_iteration_batched(
                str(tmp_path / str(seed)), seed)
            crashes += crashed
            acked_total += n
        assert crashes >= iters // 10
        assert acked_total > 0

    def test_chaos_repair_storm_always_converges(self, tmp_path):
        """ISSUE 9's seeded daemon sweep: kill-mid-repair, peer
        partition mid-stream, and the stale-reader swap race, across
        M3_TPU_CHAOS_ITERS seeds — every storm ends with rollup-digest
        equality and both replicas serving every written datapoint."""
        iters = int(os.environ.get("M3_TPU_CHAOS_ITERS", "200")) // 4
        crashes = 0
        for seed in range(max(iters, 10)):
            c, _cycles = _repair_chaos_iteration(
                str(tmp_path / str(seed)), seed)
            crashes += c
        assert crashes >= max(iters, 10) // 10

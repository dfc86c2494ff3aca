"""Database: namespaces + commitlog + bootstrap + tick orchestration.

Role parity with the reference storage.Database
(/root/reference/src/dbnode/storage/database.go:99 — Write:795,
ReadEncoded:1068, Bootstrap:1140) and the mediator tick/flush loop
(storage/mediator.go:79-160), collapsed into explicit open/write/read/
tick calls driven by the host control plane.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from dataclasses import dataclass

import numpy as np

from m3_tpu.storage import commitlog
from m3_tpu.storage.namespace import Namespace
from m3_tpu.storage.options import DatabaseOptions, NamespaceOptions
from m3_tpu.storage.sharding import ShardSet
from m3_tpu.utils import faults
from m3_tpu.utils.instrument import default_registry

log = logging.getLogger(__name__)

# write-seam latency histogram (p50/p99 derivable from /metrics _bucket
# series); the handle pre-resolves the metric key so the per-datapoint
# hot path pays one lock + bisect per observation, nothing more
_scope = default_registry().root_scope("db")
_storage_scope = default_registry().root_scope("storage")
_observe_write = _scope.histogram_handle("write_seconds")
# the batched seam observes ONCE per batch; the points counter keeps
# throughput accounting comparable with the per-point histogram's count
_observe_write_batch = _scope.histogram_handle("write_batch_seconds")
# batch-size distribution (count-shaped bounds): whether ingest batches
# amortize the columnar pass is invisible from latency alone
from m3_tpu.utils.instrument import COUNT_BUCKETS  # noqa: E402

_observe_write_batch_size = _scope.histogram_handle(
    "write_batch_size", bounds=COUNT_BUCKETS)


@dataclass
class Datapoint:
    timestamp_ns: int
    value: float


def _f64_to_bits(v: float) -> int:
    return int(np.float64(v).view(np.uint64))


def _windows_of(times: np.ndarray, block_size_ns: int) -> list[int]:
    """The block windows a batch's timestamps fall in."""
    return np.unique(times - (times % block_size_ns)).tolist()


class Database:
    """Single-node database ("local" topology mode of the reference)."""

    def __init__(self, path: str, db_opts: DatabaseOptions | None = None):
        self.path = path
        self.opts = db_opts or DatabaseOptions()
        self.namespaces: dict[str, Namespace] = {}
        self._commitlogs: dict[str, commitlog.CommitLogWriter] = {}
        # rotated logs awaiting deletion:
        # ns -> [(path, windows-it-covers, retired_at_ns)]
        self._retired_logs: dict[str, list[tuple[str, set[int], int]]] = {}
        # (ns, window) -> time of the last snapshot covering every shard
        self._snapshot_times: dict[tuple[str, int], int] = {}
        self._open = False
        self._shard_set = ShardSet(self.opts.n_shards, self.opts.owned_shards)
        # optional storage-layer QueryLimits shared by all read paths
        self.limits = None
        from m3_tpu.storage.cache import BlockCache

        # decoded-block LRU shared by every shard (WiredList role)
        self.block_cache = BlockCache(self.opts.block_cache_entries)
        from m3_tpu.cluster.runtime import PersistRateLimiter

        # fileset write pacing shared by every shard (reference ratelimit
        # role); rate comes from runtime options (0 = unlimited)
        self.persist_limiter = PersistRateLimiter()
        # live-tunable options (set via apply_runtime; None = all defaults)
        self.runtime = None
        self._runtime_opts = None

    # -- lifecycle --

    @property
    def fs_root(self) -> str:
        return os.path.join(self.path, "data")

    @property
    def snapshots_root(self) -> str:
        return os.path.join(self.path, "snapshots")

    def commitlog_dir(self, namespace: str) -> str:
        return os.path.join(self.path, "commitlog", namespace)

    def create_namespace(self, name: str, opts: NamespaceOptions | None = None) -> Namespace:
        if name in self.namespaces:
            return self.namespaces[name]
        ns = Namespace(name, opts or NamespaceOptions(), self.opts, self._shard_set,
                       self.fs_root)
        ns.database = self
        for shard in ns.shards.values():
            shard.cache = self.block_cache
            shard.persist_limiter = self.persist_limiter
        self.namespaces[name] = ns
        if self._open:
            # a namespace created on a LIVE database (the registry-sync
            # path every cluster node takes for dynamically-added tenant
            # namespaces, and the admin API) must bootstrap its durable
            # state exactly as open() would have — filesets, snapshots,
            # then commitlog replay. Without this, a restarted node
            # re-creates the namespace EMPTY and silently abandons its
            # WAL: acked writes vanish once the other replicas restart
            # too (found by the chaos rig's zero-acked-write-loss audit).
            self._bootstrap_namespace(name, ns, time.time_ns())
        return ns

    def drop_namespace(self, name: str) -> None:
        """Remove a namespace, closing its commitlog writer and retiring
        its log-tracking state (files on disk are left for the operator)."""
        ns = self.namespaces.pop(name, None)
        if ns is None:
            return
        log = self._commitlogs.pop(name, None)
        if log is not None:
            log.close()
        self._retired_logs.pop(name, None)
        for key in [k for k in self._snapshot_times if k[0] == name]:
            del self._snapshot_times[key]

    def _commitlog_path(self, namespace: str) -> str:
        return os.path.join(self.commitlog_dir(namespace),
                            f"commitlog-{time.time_ns()}.db")

    def _open_commitlog(self, namespace: str) -> None:
        self._commitlogs[namespace] = commitlog.CommitLogWriter(
            self._commitlog_path(namespace),
            self.opts.commitlog_flush_every_bytes)

    def _rotate_commitlog(self, namespace: str, now_ns: int) -> None:
        """Retire the active log's file (recording its windows and when)
        and go on in a new one. The writer swaps files under its own
        lock, so the request threads that hold it never meet a closed
        file."""
        old_path, old_windows = self._commitlogs[namespace].rotate(
            self._commitlog_path(namespace))
        self._retired_logs.setdefault(namespace, []).append(
            (old_path, old_windows, now_ns))
        _storage_scope.counter("commitlog_rotations")

    def open(self, now_ns: int | None = None) -> None:
        """Open + bootstrap: filesets first, then commitlog replay on top
        (the fs -> commitlog bootstrapper order of the reference's default
        pipeline, storage/bootstrap/bootstrapper/README.md)."""
        self._open = True
        now_ns = now_ns if now_ns is not None else time.time_ns()
        for name, ns in self.namespaces.items():
            self._bootstrap_namespace(name, ns, now_ns)

    def _bootstrap_namespace(self, name: str, ns: Namespace,
                             now_ns: int) -> None:
        """One namespace's boot sequence (shared by open() and live
        create_namespace): index + fileset bootstrap, snapshot restore,
        commitlog replay, then a fresh commitlog writer."""
        if ns.opts.bootstrap_enabled:
            restored = set()
            if ns.index is not None:
                from m3_tpu.index import persist as index_persist

                r = ns.opts.retention
                restored = index_persist.load_index(
                    ns.index, self.fs_root, name,
                    cutoff_ns=r.block_start(now_ns - r.retention_ns),
                )
            ns.bootstrap_from_fs(now_ns, skip_index_blocks=restored)
            self._restore_snapshots(name, ns, now_ns)
            self._replay_commitlogs(name, ns, now_ns)
        if ns.opts.writes_to_commitlog:
            self._open_commitlog(name)

    def _replay_commitlogs(self, name: str, ns: Namespace,
                           now_ns: int | None = None) -> None:
        """Replay every surviving log entry into the buffers. Entries whose
        datapoints also live in a flushed volume are resolved by the normal
        last-write-wins merge (and re-merged into a higher volume on the
        next flush), so replay is safe to repeat; replayed files are retired
        and deleted once every window they cover has flushed.

        Replay runs in SALVAGE mode: a corrupt interior chunk truncates
        that log (dropping everything after it, with a warning naming the
        offset and byte count) instead of raising — a damaged WAL must
        degrade bootstrap, never brick it.

        Each surviving log replays as ONE columnar batch through
        Namespace.write_many (vectorized shard routing, one buffer lock
        per (shard, window) group, one index insert_many pass with the
        tag blobs decoded once per distinct series) instead of a
        per-point write loop; entry order is preserved per window, so
        seal-time last-write-wins resolves exactly as the per-point
        replay did. Unowned shards degrade per row (the old loop's
        silent skip)."""
        from m3_tpu.utils.ident import decode_tags

        retired = self._retired_logs.setdefault(name, [])
        cutoff = None
        r = ns.opts.retention
        if now_ns is not None:
            cutoff = r.block_start(now_ns - r.retention_ns)
        for path in commitlog.log_files(self.commitlog_dir(name)):
            entries, report = commitlog.replay_salvage(path)
            if not report.clean:
                log.warning(
                    "commitlog salvage: %s truncated at byte %d (%s): "
                    "replayed %d entries, dropped %d bytes",
                    path, report.truncated_at, report.reason,
                    report.entries, report.dropped_bytes,
                )
            sids: list[bytes] = []
            encs: list[bytes] = []
            fields_list: list = []
            t_list: list[int] = []
            v_list: list[int] = []
            tag_fields: dict[bytes, list | None] = {}  # decode once per blob
            for e in entries:
                if cutoff is not None and e.time_ns < cutoff:
                    continue  # past retention: don't resurrect
                sids.append(e.series_id)
                encs.append(e.encoded_tags)
                t_list.append(e.time_ns)
                v_list.append(e.value_bits)
                if e.encoded_tags:
                    fields = tag_fields.get(e.encoded_tags)
                    if fields is None:
                        fields = tag_fields[e.encoded_tags] = \
                            decode_tags(e.encoded_tags)
                    fields_list.append(fields)
                else:
                    fields_list.append(None)  # untagged: skip the index
            windows: set[int] = set()
            if sids:
                times = np.array(t_list, np.int64)
                vbits = np.array(v_list, np.uint64)
                errors = ns.write_many(sids, times, vbits, encs, fields_list)
                ok = np.array([err is None for err in errors], bool)
                if ok.any():  # unowned-shard rows don't pin their windows
                    t_ok = times[ok]
                    for w in np.unique(
                            t_ok - (t_ok % r.block_size_ns)).tolist():
                        windows.add(int(w))
            retired.append((path, windows, now_ns if now_ns is not None else 0))

    def _cleanup_retired_logs(self, name: str, ns: Namespace, now_ns: int) -> None:
        r = ns.opts.retention
        remaining = []
        for path, windows, retired_at in self._retired_logs.get(name, []):
            covered = all(
                (
                    w + r.block_size_ns + r.buffer_past_ns <= now_ns
                    and all(s.buffer.points_in(w) == 0 for s in ns.shards.values())
                )
                or w < r.block_start(now_ns - r.retention_ns)  # past retention
                # a snapshot taken STRICTLY after the log was retired holds
                # every datapoint the log did (same-instant snapshots race
                # concurrent writers; the next tick's snapshot covers them)
                or self._snapshot_times.get((name, w), -1) > retired_at
                for w in windows
            )
            if covered:
                try:
                    os.remove(path)
                except OSError:
                    pass
            else:
                remaining.append((path, windows, retired_at))
        self._retired_logs[name] = remaining

    # -- snapshots --

    def snapshot(self, now_ns: int) -> dict[str, int]:
        """Snapshot every open (unflushed) buffer window of every
        snapshot-enabled namespace. Returns windows snapshotted per ns."""
        from m3_tpu.storage.fileset import list_filesets

        snap_id = int(now_ns // 1_000_000)  # monotonic across restarts
        counts: dict[str, int] = {}
        for name, ns in self.namespaces.items():
            if not ns.opts.snapshot_enabled:
                continue
            # a window is COVERED only when every shard holding it either
            # snapshotted it now or was already clean since its last
            # successful snapshot — a single failed shard must not let the
            # commitlog (or that shard's previous snapshot) be reclaimed
            ok_windows: set[int] = set()
            failed_windows: set[int] = set()
            for shard in ns.shards.values():
                done_here: set[int] = set()
                for bs in shard.buffer.block_starts():
                    seq = shard.write_seq(bs)
                    if shard.snapshotted_seq(bs) == seq:
                        ok_windows.add(bs)  # unchanged since last snapshot
                        continue
                    if shard.snapshot(bs, self.snapshots_root, snap_id):
                        shard.mark_snapshotted(bs, seq)
                        ok_windows.add(bs)
                        done_here.add(bs)
                    else:
                        failed_windows.add(bs)
                # reclaim superseded volumes ONLY where this shard's new
                # snapshot landed
                for old_bs, old_vol in list_filesets(
                    self.snapshots_root, name, shard.shard_id,
                    all_volumes=True,
                ):
                    if old_bs in done_here and old_vol < snap_id:
                        self._remove_snapshot(name, shard.shard_id, old_bs,
                                              old_vol)
            covered = ok_windows - failed_windows
            for w in covered:
                self._snapshot_times[(name, w)] = now_ns
            counts[name] = len(covered)
        return counts

    def _remove_snapshot(self, name: str, shard_id: int, bs: int,
                         vol: int) -> None:
        from m3_tpu.storage.fileset import SUFFIXES, fileset_path

        # checkpoint first: a half-deleted snapshot must read as incomplete
        for suffix in ("checkpoint",) + tuple(s for s in SUFFIXES
                                              if s != "checkpoint"):
            try:
                os.remove(fileset_path(self.snapshots_root, name, shard_id,
                                       bs, vol, suffix))
            except OSError:
                pass

    def _restore_snapshots(self, name: str, ns: Namespace, now_ns: int) -> None:
        """Load the latest snapshot of each in-flight window into the
        buffers (before commitlog replay; duplicates dedup on merge)."""
        from m3_tpu.encoding.m3tsz import decode as scalar_decode
        from m3_tpu.storage.fileset import FilesetReader, list_filesets

        cutoff = ns.opts.retention.block_start(
            now_ns - ns.opts.retention.retention_ns)
        from m3_tpu.utils.ident import decode_tags

        for shard in ns.shards.values():
            for bs, vol in list_filesets(self.snapshots_root, name,
                                         shard.shard_id):
                if bs < cutoff:
                    continue
                try:
                    reader = FilesetReader(self.snapshots_root, name,
                                           shard.shard_id, bs, vol)
                except (FileNotFoundError, ValueError):
                    continue
                for i in range(reader.n_series):
                    sid, tags, stream = reader.read_at(i)
                    n_restored = 0
                    for d in scalar_decode(
                        stream, int_optimized=ns.opts.int_optimized,
                        default_time_unit=ns.opts.write_time_unit,
                    ):
                        shard.buffer.write(
                            sid, d.timestamp_ns,
                            int(np.float64(d.value).view(np.uint64)), tags,
                        )
                        n_restored += 1
                    # restored points count as writes (dirty tracking) and
                    # re-index like commitlog replay does — the persisted
                    # index segment may be corrupt/missing for this block
                    shard._write_seq[bs] = shard._write_seq.get(bs, 0) + n_restored
                    if tags and n_restored:
                        ns.index_insert_spanning(sid, decode_tags(tags), bs)
                reader.close()

    def _cleanup_snapshots(self, name: str, ns: Namespace, now_ns: int) -> None:
        """Drop snapshots whose window is flushed-and-drained or expired."""
        from m3_tpu.storage.fileset import list_filesets

        r = ns.opts.retention
        cutoff = r.block_start(now_ns - r.retention_ns)
        for shard in ns.shards.values():
            open_windows = set(shard.buffer.block_starts())
            for bs, vol in list_filesets(self.snapshots_root, name,
                                         shard.shard_id, all_volumes=True):
                if bs >= cutoff and bs in open_windows:
                    continue  # still in flight
                self._remove_snapshot(name, shard.shard_id, bs, vol)
                self._snapshot_times.pop((name, bs), None)

    def close(self) -> None:
        for log in self._commitlogs.values():
            log.close()
        self._commitlogs.clear()
        for ns in self.namespaces.values():
            for shard in ns.shards.values():
                shard.close()  # releases current + retired fileset readers
        self._open = False

    # -- shard assignment (placement-driven; storage/cluster role) --

    @property
    def owned_shards(self) -> set[int]:
        return set(self._shard_set.shard_ids)

    def assign_shards(self, shard_ids: set[int], now_ns: int | None = None) -> tuple[set[int], set[int]]:
        """Reconcile shard ownership with a placement: create newly-assigned
        shards in every namespace (bootstrapping them from local filesets if
        present) and drop unassigned ones. Returns (added, removed).

        The topology-watch -> shard-assignment flow of the reference
        (/root/reference/src/dbnode/storage/cluster/database.go)."""
        current = self.owned_shards
        added = set(shard_ids) - current
        removed = current - set(shard_ids)
        if not added and not removed:
            return added, removed
        # order matters under concurrent writes from the HTTP handlers:
        # materialize new shard objects BEFORE publishing the new shard set
        # (a routed write finds its shard), and drop old ones only after
        for ns in self.namespaces.values():
            for sid in added:
                ns.add_shard(sid, now_ns)
        new_set = ShardSet(self.opts.n_shards, tuple(sorted(shard_ids)))
        self._shard_set = new_set
        for ns in self.namespaces.values():
            ns.shard_set = new_set
            for sid in removed:
                ns.remove_shard(sid)
        return added, removed

    # -- write/read --

    def write(self, namespace: str, series_id: bytes, t_ns: int, value: float,
              encoded_tags: bytes = b"") -> None:
        t0 = time.perf_counter()
        ns = self.namespaces[namespace]
        shard = ns.shard_for(series_id)  # validate ownership BEFORE logging
        vbits = _f64_to_bits(value)
        log = self._commitlogs.get(namespace)
        if log is not None:
            log.write(series_id, encoded_tags, t_ns, vbits,
                      int(ns.opts.write_time_unit),
                      ns.opts.retention.block_start(t_ns))
        shard.write(series_id, t_ns, vbits, encoded_tags)
        if ns.index is not None and encoded_tags:
            # tagged-at-the-wire writes are index-visible like write_tagged,
            # not dependent on the fileset rebuild at restart
            from m3_tpu.utils.ident import decode_tags

            ns.index.insert(series_id, decode_tags(encoded_tags), t_ns)
        _observe_write(time.perf_counter() - t0)

    def write_tagged(self, namespace: str, metric_name: bytes,
                     tags: list[tuple[bytes, bytes]], t_ns: int, value: float) -> bytes:
        """Write + index a datapoint; returns the canonical series id."""
        from m3_tpu.utils.ident import encode_tags, tags_to_id

        t0 = time.perf_counter()
        ns = self.namespaces[namespace]
        fields = [(b"__name__", metric_name), *tags] if metric_name else list(tags)
        series_id = tags_to_id(metric_name, tags)
        shard = ns.shard_for(series_id)  # validate ownership BEFORE logging
        enc = encode_tags(fields)
        vbits = _f64_to_bits(value)
        log = self._commitlogs.get(namespace)
        if log is not None:
            log.write(series_id, enc, t_ns, vbits,
                      int(ns.opts.write_time_unit),
                      ns.opts.retention.block_start(t_ns))
        # the index first, as in Namespace.write_many: the write's version
        # bump tells a fetch that the series is matched as well as stored
        if ns.index is not None:
            ns.index.insert(series_id, fields, t_ns)
        shard.write(series_id, t_ns, vbits, enc)
        _observe_write(time.perf_counter() - t0)
        return series_id

    def write_batch(self, namespace: str, entries) -> list[str | None]:
        """Storage-side batched writes — the real surface behind dbnode
        /write_batch. entries = [(metric_name, tags, t_ns, value)], the
        session/HTTP batch shape. A batch is processed as COLUMNS, not a
        loop: one tags_to_id/encode_tags pass with a per-batch memo for
        repeated series, one vectorized shard-routing pass (ownership
        validated BEFORE logging, per-point order), ONE commitlog append
        (CommitLogWriter.write_many — byte-identical framing to the
        per-point path), one buffer lock per (shard, window) group, and
        one pre-filtered index insert_many pass. Per-entry error
        isolation: a bad entry (malformed, unowned shard) degrades that
        entry only; a commitlog failure degrades every un-acked entry in
        the batch (they were never durably logged) without touching the
        buffers. Returns per-entry error strings aligned to the input
        (None = written)."""
        from m3_tpu.utils import trace

        t0 = time.perf_counter()
        try:
            with trace.span(trace.DB_WRITE_BATCH, namespace=namespace,
                            entries=len(entries)), \
                    trace.stage(trace.STAGE_WRITE_BATCH):
                results, n_ok = self._write_batch_traced(namespace, entries)
        finally:
            _observe_write_batch(time.perf_counter() - t0)
            _observe_write_batch_size(float(len(entries)))
        _scope.counter("write_batch_points", n_ok)
        return results

    def _write_batch_traced(self, namespace, entries
                            ) -> tuple[list[str | None], int]:
        from m3_tpu.utils import trace
        from m3_tpu.utils.ident import encode_tags, tags_to_id

        ns = self.namespaces[namespace]
        n = len(entries)
        results: list[str | None] = [None] * n
        if n == 0:
            return results, 0
        # one fault-point hit per BATCH (the per-point path hits db-level
        # seams per datapoint); an injected error fails the whole call,
        # exactly like the HTTP handler's node-level faults
        faults.check("db.write_batch", namespace=namespace, entries=n)
        # identity pass: one tags_to_id/encode_tags per DISTINCT series —
        # ingest batches repeat series heavily, the memo is the point.
        # Scalars accumulate in python lists (one vectorized np.array at
        # the end: per-element ndarray stores dominate the loop otherwise)
        memo: dict = {}
        series_ids: list = [None] * n
        encs: list = [None] * n
        fields_list: list = [None] * n
        t_list: list = [0] * n
        v_list: list = [0.0] * n
        for i, e in enumerate(entries):
            try:
                metric_name, tags, t_ns, value = e
                key = (metric_name, tuple(tags))
                try:
                    got = memo.get(key)
                except TypeError:  # tags arrived as [[k, v], ...]: the
                    # tuple holds unhashable lists — normalize
                    key = (metric_name, tuple(map(tuple, tags)))
                    got = memo.get(key)
                if got is None:
                    fields = [(b"__name__", metric_name), *tags] \
                        if metric_name else list(tags)
                    got = (tags_to_id(metric_name, tags),
                           encode_tags(fields), fields)
                    memo[key] = got
                series_ids[i], encs[i], fields_list[i] = got
                t_list[i] = int(t_ns)
                v_list[i] = float(value)
            except Exception as ex:  # noqa: BLE001 - per-entry isolation
                results[i] = str(ex)
        times = np.array(t_list, np.int64)
        vbits = np.array(v_list, np.float64).view(np.uint64)
        ok0 = [i for i in range(n) if results[i] is None]
        # vectorized shard routing; ownership errors recorded BEFORE any
        # logging so an unowned row never lands in the WAL. In the common
        # all-entries-clean case the routed rows ARE entry indices; a
        # degraded batch routes the ok subset and maps rows back through it
        clean = len(ok0) == n
        route_ids = series_ids if clean else [series_ids[i] for i in ok0]
        by_shard, route_errors = ns.route_many(route_ids)
        if not clean:  # routed positions index ok0, not the entry list
            ok0_arr = np.asarray(ok0, np.intp)
            by_shard = {s: ok0_arr[rows] for s, rows in by_shard.items()}
        for k, msg in route_errors.items():
            results[k if clean else ok0[k]] = msg
        ok = [i for i in ok0 if results[i] is None] if route_errors else ok0
        if not ok:
            return results, 0
        clog = self._commitlogs.get(namespace)
        from m3_tpu.storage import pipeline

        if clog is not None and pipeline.active() \
                and len(ok) > pipeline.wal_chunk_entries():
            # pipelined write dataflow: WAL pack/flush for chunk N runs
            # on the per-namespace FIFO lane while THIS thread runs
            # chunk N-1's buffer/index inserts. Ack (returning) happens
            # only after every chunk's WAL stage completed, and a chunk
            # is buffered only AFTER its own WAL append succeeded — the
            # acked => durably-logged contract and per-entry isolation
            # are exactly the serial path's (M3_TPU_PIPELINE=0 pins it).
            return self._write_batch_pipelined(
                ns, namespace, clog, entries, series_ids, encs,
                fields_list, times, vbits, by_shard, results, ok)
        if clog is not None:
            all_ok = len(ok) == n
            ok_idx = None if all_ok else np.asarray(ok, np.intp)
            t_ok = times if all_ok else times[ok_idx]
            try:
                with trace.stage(trace.STAGE_WRITE_COMMITLOG):
                    clog.write_many(
                        series_ids if all_ok else [series_ids[i] for i in ok],
                        encs if all_ok else [encs[i] for i in ok],
                        t_ok,
                        vbits if all_ok else vbits[ok_idx],
                        int(ns.opts.write_time_unit),
                        _windows_of(t_ok, ns.opts.retention.block_size_ns))
            except faults.SimulatedCrash:
                raise  # no handler survives a kill
            except Exception as ex:  # noqa: BLE001 - WAL failure: nothing
                # past this point is acked; degrade every pending entry
                # and leave the buffers untouched (an un-logged buffered
                # write would be silently lost by a crash)
                for i in ok:
                    results[i] = str(ex)
                return results, 0
        # buffer + index: reuse the routing pass; `results` doubles as the
        # error vector so entries degraded above skip the index insert
        ns.write_many(series_ids, times, vbits, encs, fields_list,
                      routed=(by_shard, results))
        return results, len(ok)

    def _write_batch_pipelined(self, ns, namespace, clog, entries,
                               series_ids, encs, fields_list, times, vbits,
                               by_shard, results, ok
                               ) -> tuple[list[str | None], int]:
        """The overlapped tail of _write_batch_traced: the clean rows
        split into WAL chunks appended in order on the per-namespace
        lane; as each chunk's append completes (== its entries are in
        the WAL buffer/OS, the serial path's ack point), this thread
        runs its buffer + index inserts while the lane packs the next
        chunk. A chunk whose WAL append failed degrades exactly its own
        entries and never touches the buffers (buffered => logged); the
        emitted WAL entry stream is byte-identical to the serial path
        (chunk boundaries only move the flush-threshold checks, as the
        batched write_many already documents)."""
        from m3_tpu.storage import pipeline
        from m3_tpu.utils import trace

        n = len(entries)
        chunk = pipeline.wal_chunk_entries()
        unit = int(ns.opts.write_time_unit)
        block_ns = ns.opts.retention.block_size_ns
        lane = pipeline.default_executor().lane(f"wal:{namespace}")
        chunks = [ok[lo:lo + chunk] for lo in range(0, len(ok), chunk)]
        futs = []
        for ch in chunks:
            idx = np.asarray(ch, np.intp)
            futs.append(lane.submit(
                lambda s=[series_ids[i] for i in ch],
                g=[encs[i] for i in ch], t=times[idx], v=vbits[idx]:
                clog.write_many(s, g, t, v, unit, _windows_of(t, block_ns))))
        mask = np.zeros(n, bool)
        n_ok = 0
        for fut, ch in zip(futs, chunks):
            try:
                # what this thread waits for the lane's append
                with trace.stage(trace.STAGE_WRITE_COMMITLOG):
                    fut.result()
            except faults.SimulatedCrash:
                raise  # no handler survives a kill
            except Exception as ex:  # noqa: BLE001 - this chunk was never
                # durably logged: degrade exactly its entries, leave the
                # buffers untouched (the serial path's WAL-failure rule,
                # applied per chunk)
                for i in ch:
                    results[i] = str(ex)
                continue
            idx = np.asarray(ch, np.intp)
            mask[:] = False
            mask[idx] = True
            routed_chunk = {}
            for s, rows in by_shard.items():
                sub = rows[mask[np.asarray(rows, np.intp)]]
                if len(sub):
                    routed_chunk[s] = sub
            ns.write_many(series_ids, times, vbits, encs, fields_list,
                          routed=(routed_chunk, results), only_rows=ch)
            n_ok += len(ch)
        return results, n_ok

    def write_tagged_batch(self, namespace: str, entries) -> int:
        """The cluster-facade batch surface (ClusterDatabase parity) over
        write_batch: all-or-error semantics — raises naming the first
        failures instead of returning per-entry results. Lets the
        coordinator ingest path op-batch against a LOCAL database too."""
        results = self.write_batch(namespace, entries)
        bad = [r for r in results if r is not None]
        if bad:
            raise RuntimeError(
                f"write_batch: {len(bad)}/{len(results)} entries failed "
                f"(first: {bad[:3]})")
        return len(results)

    def query(self, namespace: str, matchers, start_ns: int, end_ns: int,
              limit: int | None = None):
        """Index query + per-series reads: [(series_id, fields, [Datapoint])].

        The QueryIDs -> ReadEncoded flow of the reference
        (storage/database.go:1005,1068) collapsed into one call.
        """
        from m3_tpu.utils import trace

        with trace.span(trace.DB_QUERY, namespace=namespace):
            return self._query_traced(namespace, matchers, start_ns, end_ns,
                                      limit)

    def _query_traced(self, namespace, matchers, start_ns, end_ns, limit):
        from m3_tpu.index.query import matchers_to_query

        ns = self.namespaces[namespace]
        docs = ns.query_ids(matchers_to_query(list(matchers)), start_ns, end_ns, limit)
        # one batched read for the whole match set: a fetch per (shard,
        # block, volume) group and a single decode dispatch
        results = ns.read_many([d.series_id for d in docs], start_ns, end_ns)
        out = []
        for doc, (times, vbits) in zip(docs, results):
            dps = [
                Datapoint(int(t), float(v))
                for t, v in zip(times, vbits.view(np.float64))
            ]
            out.append((doc.series_id, doc.fields, dps))
        return out

    def read(self, namespace: str, series_id: bytes, start_ns: int, end_ns: int
             ) -> list[Datapoint]:
        ns = self.namespaces[namespace]
        times, vbits = ns.read(series_id, start_ns, end_ns)
        values = vbits.view(np.float64)
        return [Datapoint(int(t), float(v)) for t, v in zip(times, values)]

    def read_batch(self, namespace: str, series_ids: list[bytes],
                   start_ns: int, end_ns: int) -> list[list[Datapoint]]:
        """Batched node-API reads (the read_batch RPC shape): one fetch
        per (shard, block, volume) group and one decode server-side, so a
        Session wired to in-process databases batches like the HTTP path."""
        ns = self.namespaces[namespace]
        results = ns.read_many(series_ids, start_ns, end_ns)
        return [
            [Datapoint(int(t), float(v))
             for t, v in zip(times, vbits.view(np.float64))]
            for times, vbits in results
        ]

    def read_batch_csr(self, namespace: str, series_ids: list[bytes],
                       start_ns: int, end_ns: int,
                       precision: str | None = None):
        """read_batch landing the ragged (times, vbits, offsets) CSR —
        the NodeConnection fast path a Session prefers over read_batch:
        an in-process leg never materializes per-sample Datapoints at
        all.  ``precision`` is the wire-quantization grant; in-process
        there is no wire, so results stay exact (quantization is a
        transport measure, not a rounding contract)."""
        del precision  # no wire to quantize in-process
        ns = self.namespaces[namespace]
        return ns.read_many_ragged(series_ids, start_ns, end_ns)

    # -- maintenance --

    def apply_runtime(self, manager) -> None:
        """Bind a RuntimeOptionsManager: query limits, tick switches, and
        persist pacing follow its updates live (kvconfig role)."""
        from m3_tpu.cluster.runtime import apply_to_query_limits
        from m3_tpu.storage.limits import QueryLimits

        self.runtime = manager

        def on_opts(opts):
            # mutate the CURRENTLY bound limits: engines rebind db.limits,
            # and storage accounting reads the binding at check time
            if self.limits is None:
                self.limits = QueryLimits()
            apply_to_query_limits(self.limits, opts)
            self.persist_limiter.set_rate(opts.persist_rate_mbps)
            self._runtime_opts = opts

        manager.register_listener(on_opts)

    def tick(self, now_ns: int | None = None) -> dict:
        """One mediator cycle: warm flush of aged-out windows, cold flush
        of backfilled (already-flushed) windows, snapshot of in-flight
        windows, retention expiry, commitlog rotation (a log retires once
        its windows are flushed OR snapshotted after it was rotated — the
        reference flush model, storage/README.md + coldflush.go).

        The cycle is one root stage of the stage clock's route `tick`
        (utils/trace.py): snapshot, flush and rotation are stages
        beneath it, and expiry and index persist and compaction are the
        root's self-time."""
        from m3_tpu.utils import trace

        ctx = dataclasses.replace(trace.start_request(),
                                  route=trace.ROUTE_TICK)
        with trace.activate(ctx), trace.stage(trace.STAGE_TICK):
            return self._tick_staged(
                now_ns if now_ns is not None else time.time_ns())

    def _tick_staged(self, now_ns: int) -> dict:
        from m3_tpu.utils import trace

        flushed = cold_flushed = expired = 0
        ropts = self._runtime_opts
        snap_on = ropts is None or ropts.snapshot_enabled
        flush_on = ropts is None or ropts.flush_enabled
        snapped = {}
        if snap_on:
            with trace.stage(trace.STAGE_TICK_SNAPSHOT):
                snapped = self.snapshot(now_ns)
        for name, ns in self.namespaces.items():
            with trace.stage(trace.STAGE_TICK_FLUSH):
                n = ns.flush(now_ns) if flush_on else 0
                # cold pass AFTER the warm pass (reference mediator
                # ordering): backfilled blocks merge into version-bumped
                # volumes without delaying first-volume warm flushes
                n_cold = ns.cold_flush() if flush_on else 0
            flushed += n
            cold_flushed += n_cold
            n += n_cold  # both make windows durable for commitlog retirement
            expired += ns.expire(now_ns)
            self._cleanup_snapshots(name, ns, now_ns)
            ns_snapped = snapped.get(name, 0)
            if ns.index is not None:
                from m3_tpu.index import persist as index_persist

                cutoff = ns.opts.retention.block_start(
                    now_ns - ns.opts.retention.retention_ns
                )
                ns.index.expire_before(cutoff)
                # sealed-by-time blocks persist as one artifact; ACTIVE
                # blocks instead get a background size-tiered compaction
                # pass (index/compaction.py planner) so per-block segment
                # count stays bounded without rewriting every doc per tick
                index_persist.persist_index(
                    ns.index, self.fs_root, name,
                    seal_before_ns=now_ns - ns.opts.retention.buffer_past_ns)
                ns.index.compact()
                index_persist.expire_index_files(
                    self.fs_root, name, cutoff, ns.opts.index.block_size_ns
                )
            clog = self._commitlogs.get(name)
            if clog is None:
                continue
            # one stage a namespace a cycle, whether or not it rotates
            with trace.stage(trace.STAGE_TICK_ROTATE):
                if (n or ns_snapped) and clog.windows:
                    # the active log's windows are durable (fileset
                    # volume or snapshot): retire its file; retirement
                    # completes in _cleanup_retired_logs
                    self._rotate_commitlog(name, now_ns)
                self._cleanup_retired_logs(name, ns, now_ns)
        return {"flushed": flushed, "cold_flushed": cold_flushed,
                "expired": expired, "snapshotted": sum(snapped.values())}

    def aggregate_tiles(self, source_ns: str, target_ns: str,
                        start_ns: int, end_ns: int, tile_ns: int,
                        agg: str = "last") -> int:
        """Server-side downsampling of historical data: re-aggregate the
        source namespace's datapoints into `tile_ns` tiles written to the
        target namespace (the AggregateTiles RPC role,
        reference storage/database.go:1284). Returns tiles written.

        The tile reduction runs as one batched pass per shard via the same
        windowed segment reductions the aggregator uses.
        """
        from m3_tpu.metrics.aggregation import AggregationType
        from m3_tpu.ops import windowed_agg

        agg_type = {
            "last": AggregationType.LAST,
            "sum": AggregationType.SUM,
            "min": AggregationType.MIN,
            "max": AggregationType.MAX,
            "mean": AggregationType.MEAN,
            "count": AggregationType.COUNT,
        }[agg]
        src = self.namespaces[source_ns]
        if target_ns not in self.namespaces:
            raise KeyError(f"target namespace {target_ns} not created")
        # align to tile boundaries: a partial boundary tile computed from a
        # sub-range would overwrite the full tile on incremental runs
        start_ns = start_ns - (start_ns % tile_ns)
        end_ns = end_ns + (-end_ns % tile_ns)
        written = 0
        for shard in src.shards.values():
            ids = sorted(shard.series_ids())
            elem_rows, t_rows, v_rows = [], [], []
            tags_by_idx = []
            for sid in ids:
                times, vbits = shard.read(sid, start_ns, end_ns)
                if len(times) == 0:
                    continue
                buf_idx = shard.buffer._series.get(sid)
                tags_blob = (
                    shard.buffer.series_tags[buf_idx] if buf_idx is not None else b""
                )
                if not tags_blob:
                    for reader in shard._filesets.values():
                        tags_blob = reader.tags_of(sid) or tags_blob
                        if tags_blob:
                            break
                elem_rows.append(np.full(len(times), len(tags_by_idx), np.int64))
                t_rows.append(times)
                v_rows.append(vbits.view(np.float64))
                tags_by_idx.append((sid, tags_blob))
            if not elem_rows:
                continue
            e = np.concatenate(elem_rows)
            t = np.concatenate(t_rows)
            v = np.concatenate(v_rows)
            w = t // tile_ns
            ge, gw, stats, vq, offsets = windowed_agg.aggregate_groups(
                e, w, v, times=t
            )
            values = windowed_agg.extract(agg_type, stats, vq, offsets)
            tgt = self.namespaces[target_ns]
            # tiles land as ONE columnar batch per source shard (the
            # write_batch shape: one commitlog append, one buffer lock per
            # (target shard, window) group, one index insert_many pass)
            # instead of a per-tile Database.write loop
            from m3_tpu.utils.ident import decode_tags

            n_tiles = len(ge)
            if n_tiles == 0:
                continue
            sids: list[bytes] = [b""] * n_tiles
            encs: list[bytes] = [b""] * n_tiles
            fields_list: list = [None] * n_tiles
            fields_of: dict[bytes, list] = {}  # decode once per tag blob
            t_arr = np.asarray(gw, np.int64) * tile_ns
            v_arr = np.asarray(values, np.float64).view(np.uint64)
            for g in range(n_tiles):
                sid, tags_blob = tags_by_idx[int(ge[g])]
                sids[g] = sid
                encs[g] = tags_blob
                if tags_blob:
                    fields = fields_of.get(tags_blob)
                    if fields is None:
                        fields = fields_of[tags_blob] = decode_tags(tags_blob)
                    fields_list[g] = fields
            clog = self._commitlogs.get(target_ns)
            if clog is not None:
                # tiles hit the commitlog like every other write into the
                # target namespace, one append for the whole shard's batch
                clog.write_many(
                    sids, encs, t_arr, v_arr, int(tgt.opts.write_time_unit),
                    _windows_of(t_arr, tgt.opts.retention.block_size_ns))
            errors = tgt.write_many(sids, t_arr, v_arr, encs, fields_list)
            written += sum(1 for err in errors if err is None)
        return written

    def flush_all(self, now_ns: int | None = None) -> int:
        """Force-flush every buffered window regardless of buffer_past."""
        flushed = 0
        for ns in self.namespaces.values():
            for shard in ns.shards.values():
                for bs in shard.buffer.block_starts():
                    if shard.flush(bs):
                        flushed += 1
        return flushed

    def flush_shard(self, shard_id: int) -> int:
        """Force-flush every buffered window of ONE shard across all
        namespaces — the donor half of shard handoff (tail handoff): the
        mutable window's acked writes become flushed volumes the target
        can stream and digest-verify before cutover."""
        flushed = 0
        for ns in self.namespaces.values():
            shard = ns.shards.get(shard_id)
            if shard is None:
                continue
            for bs in shard.buffer.block_starts():
                if shard.flush(bs):
                    flushed += 1
        return flushed

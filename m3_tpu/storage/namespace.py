"""Namespace: a retention tier owning all local shards.

Role parity with the reference dbNamespace
(/root/reference/src/dbnode/storage/namespace.go:702,736,800).
"""

from __future__ import annotations

import itertools

from m3_tpu.index.index import NamespaceIndex
from m3_tpu.index.query import Query
from m3_tpu.storage.options import DatabaseOptions, NamespaceOptions
from m3_tpu.storage.shard import Shard, run_read_groups
from m3_tpu.storage.sharding import ShardRoutes, ShardSet


class Namespace:
    # batch size per decode dispatch when a datapoint limit is active:
    # large enough to keep the batched path's dispatch economy, small
    # enough that an over-limit query stops within one chunk
    READ_MANY_LIMIT_CHUNK = 4096

    # capability marker for resolver.fetch_tagged_ragged and the hot
    # tier's fetch-version keys: ONLY local storage namespaces qualify.
    # Facades that delegate unknown attributes to a local namespace
    # (fanout) must override this with a CLASS attribute set to False —
    # hasattr probes would otherwise resolve through their __getattr__
    # and silently bypass the facade's own read path
    supports_ragged_read = True
    # local version truth (ns_uid + shard data_version counters): the
    # hot tier's fetch keys and the standing engine's incremental skip
    # require it. Split from supports_ragged_read because cluster
    # facades DO serve ragged CSR reads (over the binary wire) while
    # holding no version truth of their own
    has_version_truth = True

    def __init__(
        self,
        name: str,
        opts: NamespaceOptions,
        db_opts: DatabaseOptions,
        shard_set: ShardSet,
        fs_root: str,
    ):
        self.name = name
        self.opts = opts
        self.db_opts = db_opts
        self.shard_set = shard_set
        self.fs_root = fs_root
        self.shards: dict[int, Shard] = {
            sid: Shard(sid, name, opts, db_opts, fs_root)
            for sid in shard_set.shard_ids
        }
        self.index = (
            NamespaceIndex(opts.index.block_size_ns) if opts.index.enabled else None
        )
        # set by Database.create_namespace; carries the shared QueryLimits
        self.database = None
        # process-unique instance id: hot-tier keys must never collide
        # across two Namespace objects that happen to share a name and
        # fresh data-version counters (test fixtures, re-created tenants)
        self.ns_uid = next(self._UID)
        # bumped by every shard add/remove (see data_version)
        self._placement_epoch = 0
        # series -> shard, hashed once per id (see shards_of); expire
        # rotates it each time the retention cutoff moves on a block
        self._routes = ShardRoutes()
        self._routes_cutoff = None

    _UID = itertools.count()

    @property
    def limits(self):
        return getattr(self.database, "limits", None)

    def data_version(self) -> tuple:
        """Content-version fingerprint of the whole namespace: changes
        whenever any owned shard's readable content could have changed,
        in any block. The standing engine keys a rule's evaluation on it
        (query/standing.py: its rules read the newest windows, where the
        head block's writes are what matters); a selector fetch keys on
        `data_version_in` of its own range. The placement epoch (bumped
        by every add/remove_shard) rides along because a remove+add swap
        can return the version SUM to a previously-seen value with
        different readable content — a sum alone would alias and serve
        stale pages."""
        shards = list(self.shards.values())  # placement changes mutate
        # the dict concurrently; iterate a snapshot
        return (self._placement_epoch, len(shards),
                sum(s.data_version for s in shards))

    def data_version_in(self, t_min: int, t_max: int) -> tuple:
        """Content-version fingerprint of what a fetch over
        `[t_min, t_max)` can return, for the device-resident hot tier
        (storage/hottier.py): the samples lie in the blocks from
        `block_start(t_min)` to `block_start(t_max - 1)`, so a write,
        flush, repair or bootstrap of any other block leaves it as it
        was and sealed history stays warm beside the head block's
        writes. It cannot alias — two different readable contents of the
        range never share a fingerprint: every shard's term is a sum of
        counters that only ever grow and are never reset or pruned (its
        structural counter, which expiry bumps, and the version of each
        block of the range, 0 for one never written or loaded:
        `Shard.data_version_in`), so the sum over a fixed set of shards
        never returns to a value it had; and the set of shards changes
        only with the placement epoch, which rides along as it does in
        `data_version`. A series is indexed before its first sample
        bumps a version (`write_many`), so the fingerprint that holds a
        sample also matches its series."""
        r = self.opts.retention
        first = r.block_start(t_min)
        last = r.block_start(max(t_max - 1, t_min))
        shards = list(self.shards.values())  # as in data_version
        return (self._placement_epoch, len(shards),
                sum(s.data_version_in(first, last) for s in shards))

    def add_shard(self, shard_id: int, now_ns: int | None = None) -> Shard:
        """Start owning a shard (placement assignment). Local fileset data
        for the shard is bootstrapped if present; peer bootstrap is the
        caller's job (services layer) since it needs the topology."""
        shard = self.shards.get(shard_id)
        if shard is None:
            shard = Shard(shard_id, self.name, self.opts, self.db_opts,
                          self.fs_root)
            if self.database is not None:
                shard.cache = self.database.block_cache
                shard.persist_limiter = self.database.persist_limiter
            self.shards[shard_id] = shard
            self._placement_epoch += 1
            shard.bootstrap_from_fs(now_ns)
            shard.bootstrapped = True
        return shard

    def remove_shard(self, shard_id: int) -> None:
        """Stop owning a shard. Buffered (unflushed) windows are force-
        flushed to fileset volumes first so a handoff never discards the
        only copy of recent writes — background repair can reconcile the
        new owner from disk later (reference keeps LEAVING donors serving
        until cutover for the same reason)."""
        shard = self.shards.pop(shard_id, None)
        if shard is None:
            return
        self._placement_epoch += 1
        for bs in shard.buffer.block_starts():
            try:
                shard.flush(bs)
            except Exception:  # noqa: BLE001 - best effort on the way out
                pass

    def shard_for(self, series_id: bytes) -> Shard:
        sid = self.shard_set.lookup(series_id)
        shard = self.shards.get(sid)
        if shard is None:
            raise KeyError(f"shard {sid} not owned by this node")
        return shard

    def write(self, series_id: bytes, t_ns: int, value_bits: int,
              encoded_tags: bytes = b"") -> None:
        self.shard_for(series_id).write(series_id, t_ns, value_bits, encoded_tags)

    def write_tagged(self, series_id: bytes, tags: list[tuple[bytes, bytes]],
                     t_ns: int, value_bits: int, encoded_tags: bytes = b"") -> None:
        """Write + reverse-index the series in the datapoint's index block
        (the writeAndIndex path, reference storage/shard.go:869-896)."""
        # the index first: the write's version bump then tells a fetch
        # that the series is matched as well as stored (see write_many)
        shard = self.shard_for(series_id)
        if self.index is not None:
            self.index.insert(series_id, tags, t_ns)
        shard.write(series_id, t_ns, value_bits, encoded_tags)

    def shards_of(self, series_ids: list[bytes]) -> list[int]:
        """The shard of each id, == shard_set.lookup_many(series_ids):
        a probe of the remembered routes, with one murmur3 pass over
        the ids not routed before. Ownership is the caller's test, on
        every call: the routes do not move with a placement change."""
        return self._routes.lookup_many(self.shard_set, series_ids)

    def route_many(self, series_ids: list[bytes]
                   ) -> tuple[dict[int, "object"], dict[int, str]]:
        """Series->shard routing for a batch (shards_of), then one
        row-index gather per distinct shard. Returns ({owned shard id: row
        index ndarray}, {row index: error} for rows landing on unowned
        shards — sparse, so the clean path allocates nothing per row).
        Split from write_many so Database.write_batch can validate
        ownership BEFORE logging, the per-point write order."""
        import numpy as np

        shards_arr = np.asarray(self.shards_of(series_ids), np.int64)
        by_shard: dict[int, object] = {}
        errors: dict[int, str] = {}
        for s in np.unique(shards_arr).tolist():
            rows = np.nonzero(shards_arr == s)[0]
            if s in self.shards:
                by_shard[s] = rows
            else:
                msg = f"shard {s} not owned by this node"
                for i in rows.tolist():
                    errors[i] = msg
        return by_shard, errors

    def write_many(self, series_ids: list[bytes], times, value_bits,
                   tags_list: list[bytes], fields_list: list | None = None,
                   routed: tuple | None = None,
                   only_rows: list | None = None) -> list[str | None]:
        """Storage-side batched writes (the write half of read_many's
        contract): rows route in one pass (route_many — pass `routed`
        to reuse its result), each owned shard takes its rows through
        ONE buffer lock per (shard, window) group (Shard.write_many),
        and the reverse index sees one pre-filtered insert_many pass.
        Rows landing on unowned shards degrade per entry — the batch
        never fails wholesale. Returns per-row error strings (None =
        written).

        ``only_rows`` (with ``routed``) restricts the pass to those row
        indices — the pipelined write path's per-WAL-chunk call shape:
        the routed dict is already chunk-filtered, and the index insert
        must not re-insert other chunks' rows."""
        import numpy as np

        n = len(series_ids)
        if routed is not None:
            by_shard, errors = routed
        else:
            by_shard, err_map = self.route_many(series_ids)
            errors = [err_map.get(i) for i in range(n)] if err_map \
                else [None] * n
        from m3_tpu.utils import trace

        # the index before the buffers: a shard bumps its block's version
        # as its rows land, and a fetch that samples the new version must
        # match a series first seen in this batch, or the hot tier would
        # keep an answer without it under the version that has it. (A
        # fetch in between matches a series with no sample yet, which it
        # drops, under the old version.)
        if self.index is not None and fields_list is not None:
            cand = only_rows if only_rows is not None else range(n)
            ok = [i for i in cand
                  if errors[i] is None and fields_list[i] is not None]
            if ok:
                self.index.insert_many([series_ids[i] for i in ok],
                                       [fields_list[i] for i in ok],
                                       times[np.asarray(ok, np.intp)])
        with trace.stage(trace.STAGE_WRITE_BUFFER):
            for shard_id, rows in by_shard.items():
                ridx = np.asarray(rows, np.intp)
                rows_l = rows.tolist() if hasattr(rows, "tolist") \
                    else list(rows)
                self.shards[shard_id].write_many(
                    [series_ids[i] for i in rows_l], times[ridx],
                    value_bits[ridx], [tags_list[i] for i in rows_l])
        return errors

    def query_ids(self, query: Query, start_ns: int, end_ns: int, limit=None):
        """Matched index docs for the time range (storage QueryIDs role).

        Limits are accounted HERE — the shared storage read path — so every
        caller (PromQL, Graphite, remote read) draws from one budget, the
        way the reference enforces storage/limits below the query engines
        (/root/reference/src/dbnode/storage/limits/types.go:37)."""
        if self.index is None:
            raise RuntimeError(f"namespace {self.name} has no index enabled")
        docs = self.index.query(query, start_ns, end_ns, limit)
        if self.limits is not None:
            self.limits.add_series(len(docs))
        return docs

    def read(self, series_id: bytes, start_ns: int, end_ns: int):
        times, vbits = self.shard_for(series_id).read(series_id, start_ns, end_ns)
        if self.limits is not None:
            self.limits.add_datapoints(len(times))
        return times, vbits

    def read_many(self, series_ids: list[bytes], start_ns: int, end_ns: int):
        """Batch-read surface shared with the cluster facade (which turns
        it into one request per storage node).

        First-class batched operation: series group by owning shard,
        each shard fetches once per (block, volume) group, and what the
        groups missed in the block cache decodes in ONE dispatch for the
        whole call (shard.decode_misses) — cache hits never enter the
        batch. Limits accounting stays EXACT: one add_datapoints per
        series, same as the per-series path; with a datapoint limit
        configured, shard batches are chunked (one decode a chunk) so
        the limit still bounds decode WORK (an over-limit query aborts
        after at most one chunk of extra decode, not after materializing
        the whole match set)."""
        from m3_tpu.utils import trace
        from m3_tpu.utils.instrument import default_registry

        with trace.span(trace.READ_MANY, namespace=self.name,
                        series=len(series_ids)), \
                default_registry().root_scope("db") \
                .histogram("read_many_seconds"):
            return self._read_many_traced(series_ids, start_ns, end_ns)

    def read_many_ragged(self, series_ids: list[bytes], start_ns: int,
                         end_ns: int):
        """Batch read returning the RAGGED (times, vbits, offsets) CSR
        aligned to `series_ids` (ROADMAP #3): the per-shard finalize
        hands its merged columns straight through — no per-series tuple
        materialization — and the resolver/engine feed the CSR directly
        into `RaggedSeries`, which is exactly what the whole-query
        compiler's `_slab_cuts`/`_fill_slabs` slab prep consumes.  Same
        results, limits accounting and warnings contract as read_many
        (per-row slices are element-identical); paths the batched finalize
        doesn't cover (datapoint-limit chunking, serial hatch) assemble
        the CSR from the per-series views in one pass."""
        from m3_tpu.ops import ragged
        from m3_tpu.utils import trace
        from m3_tpu.utils.instrument import default_registry

        with trace.span(trace.READ_MANY, namespace=self.name,
                        series=len(series_ids)), \
                default_registry().root_scope("db") \
                .histogram("read_many_seconds"):
            res = self._read_many_traced(series_ids, start_ns, end_ns,
                                         want_ragged=True)
        if isinstance(res, tuple):
            return res
        return ragged.pairs_to_csr(res)

    def _read_many_traced(self, series_ids, start_ns, end_ns,
                          want_ragged: bool = False):
        from m3_tpu.storage import pipeline

        by_shard: dict[int, list[int]] = {}
        for i, shard_id in enumerate(self.shards_of(series_ids)):
            if shard_id not in self.shards:
                raise KeyError(f"shard {shard_id} not owned by this node")
            by_shard.setdefault(shard_id, []).append(i)
        limits = self.limits
        chunk = len(series_ids) or 1
        if limits is not None and getattr(limits, "max_datapoints", 0):
            chunk = min(chunk, self.READ_MANY_LIMIT_CHUNK)
        out: list = [None] * len(series_ids)
        if pipeline.active() and chunk >= len(series_ids):
            # pipelined dataflow (no datapoint-limit chunking): ONE
            # flattened schedule of per-(shard, block) gather legs
            # across every shard, then one decode of what they missed
            return self._read_many_pipelined(series_ids, by_shard,
                                             start_ns, end_ns, out,
                                             want_ragged=want_ragged)
        for shard_id, idxs in by_shard.items():
            shard = self.shards[shard_id]
            for lo in range(0, len(idxs), chunk):
                part = idxs[lo : lo + chunk]
                results = shard.read_many(
                    [series_ids[i] for i in part], start_ns, end_ns)
                for i, (times, vbits) in zip(part, results):
                    if limits is not None:
                        limits.add_datapoints(len(times))
                    out[i] = (times, vbits)
        return out

    def _read_many_pipelined(self, series_ids, by_shard, start_ns, end_ns,
                             out, want_ragged: bool = False):
        """Per-(shard, block) groups through the executor seam
        (shard.run_read_groups): the fileset gathers of every shard run
        on the pool while this thread lands the cache hits of those that
        have arrived; when the last has, ONE batched decode takes what
        all groups missed, and then the shards' series FINALIZE (buffer
        merge + limits accounting, the partial columns downstream host
        prep consumes). Results are identical to the serial path: the
        groups' hits and decoded misses land in the same nested order,
        and per-series parts keep the filesets-then-buffer order
        merge_dedup resolves last-write-wins.
        """
        from m3_tpu.ops import ragged

        # fragments of the namespace-level ragged combine (one merged
        # per-shard CSR each) are only tracked when the caller asked for
        # the CSR back
        frags: list | None = [] if want_ragged else None
        groups = []
        plans = []
        for shard_id, idxs in by_shard.items():
            shard = self.shards[shard_id]
            sids = [series_ids[i] for i in idxs]
            parts: list[list] = [[] for _ in idxs]
            plans.append((shard, idxs, sids, parts))
            groups.extend(shard.plan_read_groups(sids, start_ns, end_ns,
                                                 parts))
        run_read_groups(groups)
        for plan in plans:
            self._finalize_shard_read(plan, start_ns, end_ns, out, frags)
        if frags is not None:
            # pure O(N) scatter: each fragment is already merged and
            # filtered, and every row lives in exactly one fragment —
            # the combine just lands rows at their query-order positions
            return ragged.combine_fragments(frags, len(series_ids))
        return out

    def _finalize_shard_read(self, plan, start_ns, end_ns, out,
                             frags: list | None = None) -> None:
        """Batched ragged finalize (ROADMAP #3): ONE merge pass over the
        shard's series; out[] carries zero-copy row slices of the shard
        CSR."""
        import numpy as np

        shard, idxs, sids, parts = plan
        limits = self.limits
        t, v, offs = shard.finish_read_many(sids, parts, start_ns, end_ns)
        for j, i in enumerate(idxs):
            a, b = int(offs[j]), int(offs[j + 1])
            if limits is not None:
                limits.add_datapoints(b - a)
            out[i] = (t[a:b], v[a:b])
        if frags is not None:
            frags.append((np.asarray(idxs, np.int64), t, v, offs))

    def flush(self, now_ns: int) -> int:
        """WARM flush: first volume for aged-out buffered windows."""
        if not self.opts.flush_enabled:
            return 0
        n = 0
        for shard in self.shards.values():
            for bs in shard.flushable_block_starts(now_ns):
                if shard.flush(bs):
                    n += 1
        return n

    def cold_flush(self) -> int:
        """COLD flush: version-bumped volumes for blocks that took writes
        after their warm flush (backfill/out-of-retention-order ingest).
        Separate pass so its decode+merge cost never sits in the warm
        path (reference storage/coldflush.go)."""
        if not self.opts.flush_enabled:
            return 0
        n = 0
        for shard in self.shards.values():
            for bs in shard.cold_dirty_block_starts():
                if shard.cold_flush(bs):
                    n += 1
        return n

    def expire(self, now_ns: int) -> int:
        r = self.opts.retention
        cutoff = r.block_start(now_ns - r.retention_ns)
        if cutoff != self._routes_cutoff:
            # a block period has passed: forget the routes of series
            # not touched since the one before it did
            self._routes.rotate()
            self._routes_cutoff = cutoff
        return sum(s.expire(now_ns) for s in self.shards.values())

    def _spanned_index_starts(self, data_block_start: int) -> range:
        """Index block starts a data block overlaps (single source of the
        spanning rule for insert AND bootstrap-skip checks)."""
        idx_bs = self.opts.index.block_size_ns
        data_bs = self.opts.retention.block_size_ns
        first = data_block_start - (data_block_start % idx_bs)
        return range(first, data_block_start + data_bs, idx_bs)

    def index_insert_spanning(self, series_id: bytes, fields,
                              data_block_start: int) -> None:
        """Insert a doc into EVERY index block its data block overlaps (a
        data block can span several smaller index blocks)."""
        if self.index is None:
            return
        for t in self._spanned_index_starts(data_block_start):
            self.index.insert(series_id, fields, t)

    def bootstrap_from_fs(self, now_ns: int | None = None,
                          skip_index_blocks: set[int] | None = None) -> int:
        from m3_tpu.utils.ident import decode_tags

        n = sum(s.bootstrap_from_fs(now_ns) for s in self.shards.values())
        if self.index is not None:
            # rebuild the reverse index from fileset tag blobs, EXCEPT for
            # index blocks already restored from persisted segments
            skip = skip_index_blocks or set()
            for s in self.shards.values():
                for bs, reader in s._filesets.items():
                    # skip only if every overlapping index block was restored
                    if set(self._spanned_index_starts(bs)) <= skip:
                        continue
                    for i in range(reader.n_series):
                        sid, tags_blob = reader.entry_at(i)
                        if tags_blob:
                            self.index_insert_spanning(sid, decode_tags(tags_blob), bs)
        for s in self.shards.values():
            s.bootstrapped = True
        return n

    def series_ids(self) -> set[bytes]:
        out: set[bytes] = set()
        for s in self.shards.values():
            out |= s.series_ids()
        return out

"""Fanout storage: merge the local zone with remote-zone coordinators.

The reference coordinator composes its local m3 storage with remote gRPC
storages behind one Storage interface and merges series results
(/root/reference/src/query/storage/fanout/storage.go; remote client
query/remote/client.go). This facade does the same for this framework's
storage contract — `namespaces[ns].query_ids / read / read_many` plus the
label APIs — so the PromQL/Graphite engines and the HTTP API run unchanged
over a multi-zone deployment.

Semantics:
- reads UNION series across zones; duplicate series ids merge their
  samples timestamp-deduped (local zone wins ties — it is authoritative
  for its own writes, matching the reference's local-preferred merge).
- writes stay zone-local: cross-zone replication is a deployment concern
  (the reference fanout likewise only fans out reads).
- a remote zone failing closed is either skipped (default, recorded via a
  warning counter — the reference's warn-on-partial-results mode) or
  fatal (strict=True, its fail mode).
"""

from __future__ import annotations

import logging

import numpy as np

from m3_tpu.storage.buffer import merge_dedup
from m3_tpu.utils import faults
from m3_tpu.utils.instrument import default_registry
from m3_tpu.utils.warnings import ReadWarning

log = logging.getLogger(__name__)
_scope = default_registry().root_scope("fanout")


class FanoutError(RuntimeError):
    """A remote zone failed and the fanout is configured strict."""


class FanoutNamespace:
    """One namespace viewed across the local db + remote zones."""

    # resolver.fetch_tagged threads its per-query warnings list through
    # the warnings= out-param (thread-safe) instead of draining the
    # shared last_warnings field
    supports_read_warnings = True
    # CLASS attribute, deliberately False: __getattr__ below delegates
    # unknown names to the LOCAL namespace, so without this shadow the
    # ragged fast path / hot-tier version probes would resolve to the
    # local namespace's methods and silently skip the remote zones
    supports_ragged_read = False
    has_version_truth = False

    def __init__(self, fdb: "FanoutDatabase", name: str):
        self._fdb = fdb
        self.name = name
        # partial-result contract (non-strict mode): zones skipped by the
        # last read/query call, as structured ReadWarnings — callers that
        # must distinguish "complete" from "served degraded" read this
        # instead of scraping logs/counters
        self.last_warnings: list[ReadWarning] = []

    @property
    def _local(self):
        """The local namespace, or None when this namespace exists only in
        a remote zone — callers skip the local leg then (the remote-only
        union semantics _Namespaces.__missing__ promises)."""
        try:
            return self._fdb.local.namespaces[self.name]
        except KeyError:
            return None

    # -- index scatter --

    def _zone_call(self, zone, fn, *args, warnings: list | None = None):
        import time as _time

        from m3_tpu.utils import querystats

        t0 = _time.perf_counter()
        try:
            faults.check("fanout.zone", zone=zone.name)
            return fn(*args)
        except Exception as e:  # noqa: BLE001 - per-zone failure policy
            if self._fdb.strict:
                raise FanoutError(f"remote zone {zone.name}: {e}") from e
            _scope.subscope("zone", zone=zone.name).counter("errors")
            log.warning("fanout: skipping zone %s: %s", zone.name, e)
            if warnings is not None:
                warnings.append(ReadWarning("fanout", zone.name, str(e)))
            return None
        finally:
            # per-zone share of this read, onto the active query record
            # (EXPLAIN ANALYZE shows one plan leg per remote zone)
            querystats.record_node_leg(f"zone:{zone.name}",
                                       _time.perf_counter() - t0)

    def query_ids(self, query, start_ns: int, end_ns: int, limit=None,
                  warnings: list | None = None):
        from m3_tpu.index.query import query_to_json

        warns: list[ReadWarning] = []
        local = self._local
        docs = list(local.query_ids(query, start_ns, end_ns, limit)) if local else []
        seen = {d.series_id for d in docs}
        qj = query_to_json(query)
        from m3_tpu.index.segment import Document

        for zone in self._fdb.zones:
            rows = self._zone_call(
                zone, zone.query_ids, self.name, qj, start_ns, end_ns, limit,
                warnings=warns)
            if not rows:
                continue
            for sid, fields in rows:
                if sid not in seen:
                    seen.add(sid)
                    docs.append(Document(0, sid, fields))
        docs.sort(key=lambda d: d.series_id)
        if limit is not None:
            docs = docs[:limit]
        self.last_warnings = warns
        if warnings is not None:
            warnings.extend(warns)
        return docs

    # -- reads (replica-style sample merge across zones) --

    def read_many(self, series_ids: list[bytes], start_ns: int, end_ns: int,
                  warnings: list | None = None):
        """One BATCHED read per zone: the local leg is the namespace's
        batched read (one fetch per (shard, block, volume) group, one
        decode dispatch) and each remote leg is one read_many RPC, so a
        fan-out over N series costs one batched request per node, not N.

        Partial-result contract (non-strict): a zone failing closed yields
        the surviving zones' merge plus one ReadWarning per skipped zone
        (self.last_warnings / the warnings out-param) — never an
        exception."""
        from m3_tpu.utils import trace

        with trace.span(trace.FANOUT_READ, namespace=self.name,
                        series=len(series_ids),
                        zones=len(self._fdb.zones)):
            return self._read_many_traced(series_ids, start_ns, end_ns,
                                          warnings)

    def _read_many_traced(self, series_ids, start_ns, end_ns, warnings):
        from m3_tpu.storage import pipeline

        warns: list[ReadWarning] = []
        local = self._local
        zones = self._fdb.zones
        # pipelined fan-out: every remote zone's read_many RPC goes in
        # flight BEFORE the local leg's fused fetch+decode runs on this
        # thread, so cross-zone network legs overlap the local decode
        # rung. Serial is pinned under the hatch or an armed fault plan
        # (the fanout.zone injection schedule must stay deterministic).
        futs = None
        if zones and series_ids and pipeline.active() \
                and not faults.enabled():
            futs = self._fly_zone_reads(zones, series_ids, start_ns, end_ns)
        if local is not None:
            merged = list(local.read_many(series_ids, start_ns, end_ns))
        else:
            empty_t = np.array([], dtype=np.int64)
            empty_v = np.array([], dtype=np.uint64)
            merged = [(empty_t, empty_v) for _ in series_ids]
        for k, zone in enumerate(zones):
            if futs is not None:
                remote = self._reap_zone_read(zone, futs[k], warns)
            else:
                remote = self._zone_call(
                    zone, zone.read_many, self.name, series_ids, start_ns,
                    end_ns, warnings=warns)
            if remote is None:
                continue
            for i, (rt, rv) in enumerate(remote):
                if len(rt) == 0:
                    continue
                lt, lv = merged[i]
                if len(lt) == 0:
                    merged[i] = (rt, rv)
                else:
                    # merge_dedup is last-write-wins on timestamp ties, so
                    # remote samples go FIRST and the local zone wins
                    merged[i] = merge_dedup(
                        np.concatenate([rt, lt]), np.concatenate([rv, lv]))
        self.last_warnings = warns
        if warnings is not None:
            warnings.extend(warns)
        return merged

    def _fly_zone_reads(self, zones, series_ids, start_ns, end_ns):
        """Submit every remote zone's read_many through the shared leg
        policy (pipeline.submit_client_leg: trace context re-activated
        per worker, timed, exceptions as values); `_reap_zone_read`
        applies the per-zone failure policy in zone order, so
        warnings/merge order match the serial loop."""
        from m3_tpu.storage import pipeline
        from m3_tpu.utils import trace

        tracer = trace.default_tracer()
        ctx = tracer.current()
        return [pipeline.submit_client_leg(
            lambda zone=zone: zone.read_many(self.name, series_ids,
                                             start_ns, end_ns),
            tracer, ctx, point_ctx="fanout_zone") for zone in zones]

    def _reap_zone_read(self, zone, fut, warns: list):
        """Consume one overlapped zone leg with _zone_call's exact
        policy: strict mode raises, otherwise the zone is skipped with a
        counter + ReadWarning; the leg rides EXPLAIN ANALYZE either way."""
        from m3_tpu.utils import querystats

        rows, err, dt = fut.result()
        querystats.record_node_leg(f"zone:{zone.name}", dt)
        if err is None:
            return rows
        if isinstance(err, faults.SimulatedCrash):
            raise err  # our own injected death, never a zone failure
        if self._fdb.strict:
            raise FanoutError(f"remote zone {zone.name}: {err}") from err
        _scope.subscope("zone", zone=zone.name).counter("errors")
        log.warning("fanout: skipping zone %s: %s", zone.name, err)
        warns.append(ReadWarning("fanout", zone.name, str(err)))
        return None

    def read(self, series_id: bytes, start_ns: int, end_ns: int):
        [(t, v)] = self.read_many([series_id], start_ns, end_ns)
        return t, v

    # -- label APIs --

    class _IndexFacade:
        def __init__(self, ns: "FanoutNamespace"):
            self._ns = ns

        def aggregate_field_names(self, start_ns, end_ns):
            ns = self._ns
            local = ns._local
            out = set(local.index.aggregate_field_names(start_ns, end_ns)) \
                if local else set()
            for zone in ns._fdb.zones:
                vals = ns._zone_call(
                    zone, zone.label_names, ns.name, start_ns, end_ns)
                if vals:
                    out.update(vals)
            return sorted(out)

        def aggregate_field_values(self, field, start_ns, end_ns):
            ns = self._ns
            local = ns._local
            out = set(local.index.aggregate_field_values(
                field, start_ns, end_ns)) if local else set()
            for zone in ns._fdb.zones:
                vals = ns._zone_call(
                    zone, zone.label_values, ns.name, field, start_ns, end_ns)
                if vals:
                    out.update(vals)
            return sorted(out)

    @property
    def index(self):
        return FanoutNamespace._IndexFacade(self)

    # passthrough attributes the engines occasionally consult (options,
    # limits); the LOCAL zone is authoritative for both
    def __getattr__(self, item):
        local = self._fdb.local.namespaces
        if self.name not in local:
            # a remote-only namespace has no local attributes to offer;
            # AttributeError (not KeyError) so getattr(ns, x, default) works
            raise AttributeError(
                f"namespace {self.name!r} has no local attribute {item!r}")
        return getattr(local[self.name], item)


class _Namespaces(dict):
    """Facade mapping that MIRRORS the local db's namespace listing
    (iteration/membership), while __getitem__ materializes a fanout view
    for any name — a namespace existing only in a remote zone is still
    queryable, matching the reference fanout's union semantics."""

    def __init__(self, fdb: "FanoutDatabase"):
        super().__init__()
        self._fdb = fdb

    def __missing__(self, name: str) -> FanoutNamespace:
        ns = FanoutNamespace(self._fdb, name)
        self[name] = ns
        return ns

    def _local_names(self):
        return list(self._fdb.local.namespaces)

    def __contains__(self, name) -> bool:  # type: ignore[override]
        return name in self._fdb.local.namespaces

    def __iter__(self):
        return iter(self._local_names())

    def __len__(self) -> int:
        return len(self._fdb.local.namespaces)

    def keys(self):
        return self._local_names()

    def items(self):
        return [(n, self[n]) for n in self._local_names()]

    def values(self):
        return [self[n] for n in self._local_names()]


class FanoutDatabase:
    """Database facade: local zone + remote read fanout. Write/lifecycle
    calls delegate to the local database untouched."""

    def __init__(self, local, zones, strict: bool = False):
        self.local = local
        self.zones = list(zones)
        self.strict = strict
        self.namespaces = _Namespaces(self)

    # local-zone passthroughs (writes, admin, lifecycle, limits)
    def __getattr__(self, item):
        return getattr(self.local, item)

    @property
    def limits(self):
        return getattr(self.local, "limits", None)

    @limits.setter
    def limits(self, v) -> None:
        self.local.limits = v

    def close(self) -> None:
        for z in self.zones:
            z.close()
        self.local.close()

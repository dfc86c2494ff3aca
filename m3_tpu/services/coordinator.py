"""Coordinator service: HTTP APIs + embedded downsampler + carbon ingest.

Role parity with the reference coordinator assembly
(/root/reference/src/query/server/query.go:201 Run — storage, downsampler
wiring at :500-530, ingest servers, HTTP). One process serves Prometheus
remote read/write, PromQL, Graphite render/find, carbon ingest, and flushes
rule-matched aggregations into per-policy namespaces.

Run: python -m m3_tpu.services.coordinator -f config/coordinator.yml
"""

from __future__ import annotations

import argparse
import threading

from m3_tpu.aggregator.downsample import Downsampler, DownsamplerAndWriter
from m3_tpu.metrics.rules import RuleSet
from m3_tpu.query.api import CoordinatorAPI
from m3_tpu.query.graphite import CarbonIngester
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import DatabaseOptions, NamespaceOptions, RetentionOptions
from m3_tpu.utils import backend
from m3_tpu.utils.config import load_config
from m3_tpu.utils.instrument import Logger, default_registry


def ruleset_from_config(doc: dict | None) -> RuleSet:
    """Build mapping/rollup rules from the config's `rules:` section (the
    same doc shape the KV rule store uses — one parser for both)."""
    from m3_tpu.metrics.rules_store import ruleset_from_doc

    return ruleset_from_doc(doc)


_TIME_UNITS = {"s": "SECOND", "ms": "MILLISECOND", "us": "MICROSECOND",
               "ns": "NANOSECOND", "m": "MINUTE", "h": "HOUR"}


def parse_time_unit(name: str):
    """Namespace time-unit config ("s", "ms", "us", "ns", ...) -> the
    encoder TimeUnit. Sub-unit timestamp precision is TRUNCATED at
    encode (reference-compatible lossiness), so namespaces ingesting
    irregular/high-frequency timestamps must declare a fine unit or a
    snapshot/flush-restore cycle silently collapses their datapoints —
    the chaos rig's zero-acked-write-loss audit is what surfaced this."""
    from m3_tpu.encoding.m3tsz.constants import TimeUnit

    try:
        return TimeUnit[_TIME_UNITS[str(name).strip().lower()]]
    except KeyError:
        raise ValueError(f"unknown time_unit {name!r} "
                         f"(want one of {sorted(_TIME_UNITS)})") from None


def namespace_options(doc: dict | None) -> NamespaceOptions:
    if not doc:
        return NamespaceOptions()
    from m3_tpu.metrics.policy import parse_go_duration as dur

    r = doc.get("retention", {}) or {}
    res = doc.get("resolution")  # set on downsampled (aggregated) tiers
    tu = doc.get("time_unit")
    kwargs = {}
    if tu:
        kwargs["write_time_unit"] = parse_time_unit(tu)
    return NamespaceOptions(
        retention=RetentionOptions(
            retention_ns=dur(r.get("period", "48h")),
            block_size_ns=dur(r.get("block_size", "2h")),
            buffer_past_ns=dur(r.get("buffer_past", "10m")),
            buffer_future_ns=dur(r.get("buffer_future", "2m")),
        ),
        int_optimized=bool(doc.get("int_optimized", False)),
        aggregated_resolution_ns=dur(res) if res else 0,
        aggregated_complete=bool(doc.get("complete", False)),
        **kwargs,
    )


class CoordinatorService:
    def __init__(self, config: dict, kv=None):
        self.config = config
        self.log = Logger("coordinator")
        db_cfg = config.get("db", {}) or {}
        cl_cfg = config.get("cluster", {}) or {}
        self.kv = kv
        self._placement_version = -1
        self._registry_ns: set[str] = set()  # names synced from the registry
        self._divergence_reporter = None  # set in cluster mode only
        if self.kv is None:
            from m3_tpu.cluster.kv import kv_from_config

            self.kv = kv_from_config(cl_cfg)
        self._cluster_mode = bool(cl_cfg.get("enabled"))
        if self._cluster_mode:
            # cluster mode: all reads/writes go through the quorum session
            # to the placement's storage nodes (reference query/server
            # wiring m3.NewStorage over client sessions). A KV without
            # enabled=true serves the KV-backed features (rules, runtime,
            # admin) over local storage.
            if self.kv is None:
                raise RuntimeError("cluster.enabled needs a KV (kv_path or kv_addr)")
            self.db = self._build_cluster_db(cl_cfg)
            self._sync_namespace_options()  # tier metadata before first tick
        else:
            self.db = Database(
                db_cfg.get("path", "./m3data"),
                DatabaseOptions(n_shards=db_cfg.get("n_shards", 8)),
            )
            self.db.create_namespace(
                db_cfg.get("namespace", "default"),
                namespace_options(db_cfg.get("options")),
            )
        # cross-zone remote read fanout (reference query/storage/fanout +
        # query/remote): serve this zone's storage over gRPC and/or merge
        # remote zones into the local query surface
        rm_cfg = config.get("remote", {}) or {}
        self.remote_server = None
        if rm_cfg.get("listen"):
            from m3_tpu.query.remote import RemoteQueryServer

            self.remote_server = RemoteQueryServer(self.db, rm_cfg["listen"])
        if rm_cfg.get("zones"):
            from m3_tpu.query.fanout import FanoutDatabase
            from m3_tpu.query.remote import RemoteZone

            zones = [
                RemoteZone(z["name"], z["target"],
                           timeout_s=float(z.get("timeout_s", 10.0)))
                for z in rm_cfg["zones"]
            ]
            self.db = FanoutDatabase(self.db, zones,
                                     strict=bool(rm_cfg.get("strict")))
        ruleset = ruleset_from_config(config.get("rules"))
        self.downsampler = (
            self._make_downsampler(ruleset)
            if (ruleset.mapping_rules or ruleset.rollup_rules
                or ruleset.standing_rules)
            else None
        )
        self.writer = DownsamplerAndWriter(
            self.db, self.downsampler, db_cfg.get("namespace", "default")
        )
        if self.kv is not None:
            # KV-managed rules (R2 service / matcher-watch role): updates
            # through /api/v1/rules apply to the live ingest path without
            # a restart; config-file rules are only the boot value
            from m3_tpu.metrics.rules_store import watch_ruleset

            self._rules_unwatch = watch_ruleset(self.kv, self._apply_ruleset)
        lim_cfg = config.get("limits", {}) or {}
        from m3_tpu.query.engine import QueryLimits

        limits = QueryLimits(
            max_series=int(lim_cfg.get("max_series", 0)),
            max_datapoints=int(lim_cfg.get("max_datapoints", 0)),
            max_steps=int(lim_cfg.get("max_steps", 0)),
        )
        from m3_tpu.cluster.runtime import (
            RuntimeOptions,
            RuntimeOptionsManager,
            apply_to_query_limits,
        )

        # seed the runtime manager from the config-file limits so wiring
        # the listener re-applies (not resets) them; KV updates override
        self.runtime = RuntimeOptionsManager(RuntimeOptions(
            max_series=limits.max_series,
            max_datapoints=limits.max_datapoints,
            max_steps=limits.max_steps,
        ))
        self.runtime.register_listener(
            lambda opts: apply_to_query_limits(limits, opts))
        if hasattr(self.db, "apply_runtime"):  # local-storage mode
            self.db.apply_runtime(self.runtime)
        if self.kv is not None:
            self.runtime.watch_kv(self.kv)
        # whole-query compilation (ROADMAP #2): `query: compile: true`
        # fuses covered PromQL plans into one XLA program per plan shape;
        # M3_TPU_QUERY_COMPILE=1/0 overrides at runtime
        query_cfg = config.get("query", {}) or {}
        self.api = CoordinatorAPI(self.db, db_cfg.get("namespace", "default"),
                                  limits=limits,
                                  query_compile=bool(
                                      query_cfg.get("compile", False)))
        self.api.writer = self.writer  # ingest fans out through downsampler
        # per-tenant admission control (utils/tenantlimits): quotas from
        # the config's `tenants:` section, cardinality ceilings read from
        # the live storage, runtime-retunable through the m3_tpu.tenants
        # KV key — a noisy tenant is throttled live, without a restart
        from m3_tpu.storage import limits as storage_limits
        from m3_tpu.utils import tenantlimits

        self.admission = tenantlimits.from_config(
            config.get("tenants"),
            cardinality_source=lambda ns: storage_limits.live_series(
                self.db, ns),
        )
        self.api.admission = self.admission
        if self.admission is not None and self.kv is not None:
            self.admission.watch_kv(self.kv)
            self.log.info("tenant admission armed",
                          tenants=self.admission.known_tenants())
        from m3_tpu.query.admin import AdminAPI

        self.api.admin = AdminAPI(
            self.db, kv=self.kv,
            placement_key=cl_cfg.get("placement_key"),
        )
        self.carbon: CarbonIngester | None = None
        # M3-monitors-M3: optional self-scrape loop ingesting this
        # process's metrics registry into the `_m3_system` namespace so
        # platform p99s are queryable with the platform's own PromQL
        # (?namespace=_m3_system on the query endpoints)
        sm_cfg = config.get("self_monitor", {}) or {}
        self.self_monitor = None
        if sm_cfg.get("enabled"):
            from m3_tpu.utils.selfscrape import SELF_NAMESPACE, SelfMonitor

            self.self_monitor = SelfMonitor(
                self.db,
                interval_s=float(sm_cfg.get("interval_s", 10.0)),
                namespace=sm_cfg.get("namespace", SELF_NAMESPACE),
            )
            if not self.self_monitor.enabled:
                self.log.info("self-monitor disabled: no local storage "
                              "namespace available")
        # OTLP-style telemetry export: background drainer shipping this
        # process's span ring + metrics registry to the configured
        # collector (config `export:` section / M3_TPU_EXPORT_* env);
        # None when unconfigured — no thread, no overhead
        from m3_tpu.utils.export import exporter_from_config

        self.exporter = exporter_from_config(config, "coordinator")
        if self.exporter is not None:
            self.exporter.start()
            self.log.info("telemetry exporter started",
                          sink=type(self.exporter.sink).__name__)
        # always-on profiling plane: M3_TPU_PROFILE arms the sampling
        # profiler + stall watchdog (POST /debug/profile toggles live)
        from m3_tpu.utils import profiler

        profiler.arm_from_env("coordinator")
        self._stop = threading.Event()

    def _make_downsampler(self, ruleset) -> Downsampler:
        db_cfg = self.config.get("db", {}) or {}
        return Downsampler(
            self.db, ruleset,
            source_namespace=db_cfg.get("namespace", "default"),
            register_namespace=(self._register_tier_namespace
                                if self.kv is not None else None),
        )

    def _register_tier_namespace(self, name: str, policy, complete: bool
                                 ) -> None:
        """Registry-sync leg of on-demand tier creation: the aggregated
        namespace the downsampler just created locally must also land in
        the KV namespace registry, so dbnodes (and a restarted
        coordinator) re-create it BEFORE opening storage and its WAL
        replays instead of being abandoned."""
        from m3_tpu.query.admin import update_namespace_registry

        sec = 10**9
        doc = {
            "retention": {
                "period": f"{policy.retention_ns // sec}s",
                "block_size":
                    f"{max(policy.resolution_ns * 720, 2 * 3600 * sec) // sec}s",
            },
            "resolution": f"{policy.resolution_ns // sec}s",
        }
        if complete:
            doc["complete"] = True

        def add(registry):
            registry.setdefault(name, doc)
            return registry

        try:
            update_namespace_registry(self.kv, add)
        except Exception as e:  # noqa: BLE001 - registry contention/outage
            # must not fail the flush; the next namespace_for retries
            self.downsampler._registered.discard(name)
            self.log.info("tier namespace registry sync failed",
                          namespace=name, error=str(e))

    def _apply_ruleset(self, rs) -> None:
        """KV rules watcher: swap the live matcher's ruleset (its version
        bump invalidates the match cache), creating the downsampler on
        first rules if the node booted without any."""
        if not (rs.mapping_rules or rs.rollup_rules or rs.standing_rules) \
                and self.downsampler is None:
            return
        if self.downsampler is None:
            self.downsampler = self._make_downsampler(rs)
            self.writer.downsampler = self.downsampler
            self.log.info("downsampler created from KV rules",
                          version=rs.version)
            return
        old = self.downsampler.aggregator.matcher.ruleset
        # the KV version can collide with the boot ruleset's (both start
        # at 1); the cache invalidates on CHANGE, so force a distinct one
        rs.version = max(rs.version, old.version + 1)
        self.downsampler.set_ruleset(rs)
        self.log.info("ruleset reloaded", version=rs.version,
                      mapping=len(rs.mapping_rules),
                      rollup=len(rs.rollup_rules),
                      standing=len(rs.standing_rules))

    def _build_cluster_db(self, cl_cfg: dict):
        from m3_tpu.client.cluster_db import ClusterDatabase
        from m3_tpu.client.http_conn import HTTPNodeConnection
        from m3_tpu.client.session import Session
        from m3_tpu.cluster import placement as pl
        from m3_tpu.cluster.topology import ConsistencyLevel, TopologyMap

        key = cl_cfg.get("placement_key") or pl.PLACEMENT_KEY
        loaded = pl.load_placement(self.kv, key)
        if loaded is None:
            raise RuntimeError(f"cluster mode but no placement at {key!r}")
        # change detection keys on the KV version: placement edits that do
        # not bump the embedded document version must still be observed
        p, self._placement_version = loaded
        self._placement_key = key
        connections = {
            iid: HTTPNodeConnection(inst.endpoint)
            for iid, inst in p.instances.items() if inst.endpoint
        }
        session = Session(
            TopologyMap(p), connections,
            write_consistency=ConsistencyLevel(
                cl_cfg.get("write_consistency", "majority")),
            read_consistency=ConsistencyLevel(
                cl_cfg.get("read_consistency", "one")),
        )
        # read-path divergence detection closes its loop here: a quorum
        # read whose replicas disagree hands the (namespace, shard, range)
        # to this reporter, which forwards it to the replicas' repair
        # daemons out of band (POST /repair/enqueue) — detection inline,
        # repair never on the read path
        from m3_tpu.client.session import DivergenceReporter

        self._divergence_reporter = DivergenceReporter(session)
        session.divergence_sink = self._divergence_reporter.submit
        cdb = ClusterDatabase(session)
        # placement hot-swap: the shared watcher owns change detection and
        # connection reconcile; the coordinator's tick drives poll()
        self._placement_watcher = cdb.watch_placement(
            self.kv, key=key, connection_factory=HTTPNodeConnection)
        self._placement_watcher.version = self._placement_version
        return cdb

    def _sync_namespace_options(self) -> None:
        """Mirror the KV namespace registry's options into the cluster
        facade so retention-tier read resolution has each tier's
        retention/resolution in cluster mode (nodes sync data namespaces
        from the same registry). Namespaces REMOVED from the registry are
        pruned so the resolver stops fanning out to deleted tiers."""
        from m3_tpu.query.admin import load_namespace_registry

        set_opts = getattr(self.db, "set_namespace_options", None)
        if set_opts is None:
            return
        registry = load_namespace_registry(self.kv)
        for name, doc in registry.items():
            try:
                set_opts(name, namespace_options(doc))
            except Exception as e:  # noqa: BLE001 - one bad doc must not
                # block the rest, but it must be VISIBLE (validated at
                # registration; an out-of-band writer can bypass that)
                self.log.info("bad namespace registry doc; skipping",
                              namespace=name, error=str(e))
        # prune only names THIS sync previously sourced from the registry
        # (the embedded downsampler registers its tier namespaces directly
        # on the facade; those must survive)
        drop = getattr(self.db, "drop_namespace", None)
        if drop is not None:
            for name in self._registry_ns - set(registry):
                drop(name)
        self._registry_ns = set(registry)

    def _refresh_topology(self) -> None:
        """Pick up placement changes (node add/remove/endpoint) between
        ticks via the shared watcher (client/topology_watch.py) — one
        version-gated check, atomic map swap, lazy connection reconcile."""
        if self._placement_watcher.poll():
            self._placement_version = self._placement_watcher.version
            self.log.info("topology refreshed",
                          version=self._placement_version)

    def run(self) -> None:
        # the backend is initialised here, once, before anything listens:
        # flush encode, cold decode, compiled plans and the index program
        # all choose device or host from it (utils/dispatch)
        backend.init(self.log)
        if not self.db._open:
            self.db.open()  # bootstrap filesets + commitlog replay + WAL
            self.log.info("bootstrapped")
        http_cfg = self.config.get("http", {}) or {}
        port = self.api.serve(
            host=http_cfg.get("host", "0.0.0.0"),
            port=http_cfg.get("port", 7201),
        )
        self.log.info("http listening", port=port)
        carbon_cfg = self.config.get("carbon", {}) or {}
        if carbon_cfg.get("enabled", False):
            db_cfg = self.config.get("db", {}) or {}
            self.carbon = CarbonIngester(
                self.db,
                namespace=db_cfg.get("namespace", "default"),
                port=carbon_cfg.get("port", 7204),
                writer=self.writer,  # carbon goes through the same rules
            )
            self.log.info("carbon listening", port=self.carbon.port)
        tick_every = float(self.config.get("tick_interval_s", 10.0))
        scope = default_registry().root_scope("coordinator")
        from m3_tpu.utils import profiler

        hb = profiler.register_heartbeat("coordinator.tick", tick_every)
        try:
            while not self._stop.is_set():
                self._stop.wait(tick_every)
                if self._stop.is_set():
                    break
                hb.beat()
                try:
                    with scope.timer("tick"):
                        if self.kv is not None and hasattr(self.kv, "refresh"):
                            # cross-process KV (file-backed): pick up other
                            # processes' writes and fire local watches
                            self.kv.refresh()
                        if self.kv is not None and self._cluster_mode:
                            self._refresh_topology()
                            self._sync_namespace_options()
                        if self.downsampler is not None:
                            flushed = self.downsampler.flush()
                            scope.counter("downsample_flushed", flushed)
                        stats = self.db.tick()
                        scope.counter("blocks_flushed", stats["flushed"])
                        if self.self_monitor is not None:
                            self.self_monitor.maybe_scrape()
                except Exception as e:  # noqa: BLE001 - a transient KV/IO
                    # error must not kill the long-running coordinator
                    # (but an armed SimulatedCrash must — the rig watches)
                    from m3_tpu.utils import faults

                    faults.escalate(e)
                    self.log.error("tick error; continuing",
                                   error=f"{type(e).__name__}: {e}")
        finally:
            self.shutdown()

    def shutdown(self) -> None:
        self._stop.set()
        from m3_tpu.utils import profiler

        profiler.default_watchdog().unregister("coordinator.tick")
        if self.self_monitor is not None:
            self.self_monitor.close()
        self.api.shutdown()
        if self.carbon:
            self.carbon.close()
        if self.remote_server is not None:
            self.remote_server.close()
        if self.exporter is not None:
            self.exporter.close()  # final best-effort flush
        if self._divergence_reporter is not None:
            self._divergence_reporter.close()
        self.db.close()
        self.log.info("coordinator stopped")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-f", "--config", required=True)
    args = ap.parse_args(argv)
    svc = CoordinatorService(load_config(args.config) or {})
    try:
        svc.run()
    except KeyboardInterrupt:
        svc.shutdown()


if __name__ == "__main__":
    main()

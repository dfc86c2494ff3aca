"""Storage node service.

Role parity with the reference node assembly
(/root/reference/src/dbnode/server/server.go:171: config -> topology ->
storage opts -> servers -> db.Open -> bootstrap -> mediator loop). Serves
the node API over HTTP (the TChannel/Thrift role: writes, reads, peer
block streaming for bootstrap/repair) and runs the tick loop.

Run: python -m m3_tpu.services.dbnode -f config/dbnode.yml
"""

from __future__ import annotations

import argparse
import base64
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from m3_tpu.services.coordinator import namespace_options
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import DatabaseOptions
from m3_tpu.utils import backend, faults, trace
from m3_tpu.utils.config import load_config
from m3_tpu.utils.instrument import Logger, default_registry


class NodeAPI:
    """The node RPC surface (write/read/blocks-metadata/blocks-stream)."""

    # the routed surface; unknown paths share one histogram label so a
    # port scanner cannot grow metric cardinality without bound
    KNOWN_PATHS = frozenset({
        "/health", "/bootstrapped", "/metrics", "/debug/traces", "/write",
        "/write_batch", "/read_batch", "/read", "/query_ids",
        "/label_names", "/label_values", "/blocks/starts",
        "/blocks/metadata", "/blocks/stream", "/blocks/rollup",
        "/debug/repair", "/repair/enqueue", "/debug/flush",
        "/debug/profile", "/debug/compute", "/debug/placement",
        "/shards/flush",
    })

    def __init__(self, db: Database):
        self.db = db
        # the node's RepairDaemon (set by DBNodeService; None standalone):
        # /debug/repair and /repair/enqueue surface it
        self.repair = None
        # the node's HandoffController + placement summary callable (set
        # by DBNodeService on placement-driven nodes): /debug/placement
        self.handoff = None
        self.placement_status = None
        self._server: ThreadingHTTPServer | None = None
        scope = default_registry().root_scope("dbnode")
        # per-path latency histograms, pre-resolved (bounded set)
        self._observe_handle = {
            p: scope.subscope("handle", path=p).histogram_handle("seconds")
            for p in self.KNOWN_PATHS
        }
        self._observe_other = scope.subscope(
            "handle", path="other").histogram_handle("seconds")

    def handle(self, method, path, q, body, headers=None):
        """One node RPC. A propagated `traceparent` header joins this
        node's spans (request handling, storage read, decode rung) to the
        coordinator's trace; the per-path latency histogram feeds the
        node's /metrics."""
        import time as _time

        ctx = trace.start_request(headers)
        observe = self._observe_handle.get(path, self._observe_other)
        t0 = _time.perf_counter()
        try:
            with trace.activate(ctx), \
                    trace.span(trace.DBNODE_HANDLE, path=path):
                return self._handle_traced(method, path, q, body, headers)
        finally:
            observe(_time.perf_counter() - t0)

    def _handle_traced(self, method, path, q, body, headers=None):
        try:
            if path in ("/health", "/bootstrapped"):
                # exempt from injection so orchestrators can still see the
                # process is alive under a fault plan
                return 200, json.dumps({"ok": True}).encode()
            if path == "/debug/profile":
                # also exempt: the saturation plane exists to observe a
                # SICK node — a fault plan that error-injects the handler
                # must not blind the stall/contention telemetry the rig's
                # trajectory recorder scrapes mid-outage
                from m3_tpu.utils import profiler

                status, payload, ctype = profiler.handle_debug_profile(
                    method, q, body)
                return status, payload, ctype
            if path == "/debug/compute":
                # same exemption: the compute-plane ledger must stay
                # readable while a fault plan sickens the node
                from m3_tpu.utils import compute_stats

                status, payload, ctype = compute_stats.handle_debug_compute(
                    method, q, body)
                return status, payload, ctype
            # node-level request faults: clients see a 5xx, driving their
            # breaker/consistency paths like a real sick node
            faults.check("dbnode.handle", path=path)
            if path == "/metrics":
                from m3_tpu.query.api import _render_metrics

                # exemplar-capable OpenMetrics under content negotiation,
                # same contract (incl. Content-Type) as the coordinator
                # /metrics: a 3-tuple carries the negotiated type to the
                # HTTP handler
                status, ctype, payload = _render_metrics(q, headers)
                return status, payload, ctype
            if path == "/debug/traces":
                return self._debug_traces(method, q, body)
            if path == "/write" and method == "POST":
                doc = json.loads(body)
                if "tags_b64" in doc:  # binary-safe wire (tags are bytes)
                    tags = [(base64.b64decode(k), base64.b64decode(v))
                            for k, v in doc["tags_b64"]]
                    metric = base64.b64decode(doc.get("metric_b64", ""))
                else:
                    tags = [(k.encode(), v.encode()) for k, v in
                            sorted(doc.get("tags", {}).items())]
                    metric = doc.get("metric", "").encode()
                self.db.write_tagged(
                    doc.get("namespace", "default"), metric, tags,
                    int(doc["timestamp_ns"]), float(doc["value"]),
                )
                return 200, b'{"ok":true}'
            if path == "/write_batch" and method == "POST":
                # op-batched writes (the host-queue batching role,
                # reference client/host_queue.go): the wire parses per
                # entry, then the STORAGE side runs as ONE columnar pass
                # (db.write_batch) — no per-entry write loop. Per-entry
                # error isolation is preserved end to end: a malformed
                # wire entry or a storage-rejected one degrades that
                # entry's result slot, never the batch.
                doc = json.loads(body)
                namespace = doc.get("namespace", "default")
                entries: list = []
                parse_err: dict[int, str] = {}
                for k, e in enumerate(doc["entries"]):
                    try:
                        tags = [(base64.b64decode(kk), base64.b64decode(v))
                                for kk, v in e["tags_b64"]]
                        entries.append((
                            base64.b64decode(e.get("metric_b64", "")), tags,
                            int(e["timestamp_ns"]), float(e["value"]),
                        ))
                    except Exception as ex:  # noqa: BLE001 - per-entry error
                        parse_err[k] = str(ex)
                        entries.append(None)
                good = [e for e in entries if e is not None]
                try:
                    batch_res = iter(self.db.write_batch(namespace, good))
                except (faults.SimulatedCrash, faults.InjectedError,
                        faults.InjectedTimeout):
                    raise  # node-level fault semantics stay 503/kill
                except Exception as ex:  # noqa: BLE001 - a whole-batch
                    # storage failure (e.g. unknown namespace) degrades
                    # every entry, NOT the request: a 4xx/5xx here would
                    # feed the client's breaker and shed a healthy node
                    # over a misconfigured namespace
                    batch_res = iter([str(ex)] * len(good))
                results = [parse_err[k] if entries[k] is None
                           else next(batch_res)
                           for k in range(len(entries))]
                return 200, json.dumps({"results": results}).encode()
            if path == "/read_batch" and method == "POST":
                from m3_tpu.utils import querystats, wire

                doc = json.loads(body)
                # one batched storage read for the whole request: a fetch
                # per (shard, block, volume) group and a single decode
                # dispatch instead of one decode per series. The storage
                # counters the read accrues (blocks/bytes/cache/rungs)
                # ride the response envelope back to the coordinator's
                # QueryStats record — in cluster mode they live HERE, and
                # without the envelope the coordinator reports zeros.
                packed = wire.packed_enabled()
                if packed and wire.accepts_packed(headers):
                    # binary sample frame (utils/wire): the rows go out
                    # as a ragged CSR with m3tsz-re-encoded columns —
                    # or bf16 value columns under the client's
                    # propagated ?precision=bf16 grant — never as
                    # per-sample JSON text
                    from m3_tpu.ops import ragged

                    ns = self.db.namespaces[doc.get("namespace", "default")]
                    with querystats.collect() as st:
                        results = ns.read_many(
                            [base64.b64decode(s)
                             for s in doc["series_ids"]],
                            int(doc["start_ns"]), int(doc["end_ns"]))
                    times, vbits, offsets = ragged.pairs_to_csr(results)
                    frame = wire.pack_samples(
                        times, vbits, offsets,
                        precision=doc.get("precision"),
                        stats=querystats.storage_counters(st))
                    return 200, frame, wire.CONTENT_TYPE
                if packed:
                    # packed-capable node, JSON-only client (mixed-
                    # version fleet): counted, served transparently
                    wire.count_fallback("client_json")
                with querystats.collect() as st:
                    rows = self.db.read_batch(
                        doc.get("namespace", "default"),
                        [base64.b64decode(s) for s in doc["series_ids"]],
                        int(doc["start_ns"]), int(doc["end_ns"]),
                    )
                out = [[[d.timestamp_ns, d.value] for d in dps]
                       for dps in rows]
                return 200, json.dumps(
                    {"rows": out,
                     "stats": querystats.storage_counters(st)}).encode()
            if path == "/read":
                dps = self.db.read(
                    q["namespace"][0], base64.b64decode(q["series_id"][0]),
                    int(q["start_ns"][0]), int(q["end_ns"][0]),
                )
                return 200, json.dumps(
                    [[d.timestamp_ns, d.value] for d in dps]
                ).encode()
            if path == "/query_ids" and method == "POST":
                # index query (the fetchTagged/query RPC role,
                # reference rpc.thrift:51 service Node query/fetchTagged)
                from m3_tpu.index.query import query_from_json

                doc = json.loads(body)
                ns = self.db.namespaces[doc.get("namespace", "default")]
                docs = ns.query_ids(
                    query_from_json(doc["query"]),
                    int(doc["start_ns"]), int(doc["end_ns"]),
                    doc.get("limit"),
                )
                out = [
                    {
                        "series_id": base64.b64encode(d.series_id).decode(),
                        "fields": [
                            [base64.b64encode(k).decode(),
                             base64.b64encode(v).decode()]
                            for k, v in d.fields
                        ],
                    }
                    for d in docs
                ]
                return 200, json.dumps(out).encode()
            if path == "/label_names":
                ns = self.db.namespaces[q["namespace"][0]]
                names = ns.index.aggregate_field_names(
                    int(q["start_ns"][0]), int(q["end_ns"][0]))
                return 200, json.dumps(
                    [base64.b64encode(n).decode() for n in names]).encode()
            if path == "/label_values":
                ns = self.db.namespaces[q["namespace"][0]]
                vals = ns.index.aggregate_field_values(
                    base64.b64decode(q["field"][0]),
                    int(q["start_ns"][0]), int(q["end_ns"][0]))
                return 200, json.dumps(
                    [base64.b64encode(v).decode() for v in vals]).encode()
            if path == "/blocks/starts":
                # flushed block starts per shard (peer bootstrap discovery)
                ns = self.db.namespaces[q["namespace"][0]]
                shard = ns.shards.get(int(q["shard"][0]))
                starts = sorted(shard._filesets) if shard else []
                return 200, json.dumps(starts).encode()
            if path == "/blocks/metadata":
                # repair/bootstrap support: per-series stream checksums
                import zlib

                ns = self.db.namespaces[q["namespace"][0]]
                shard = ns.shards[int(q["shard"][0])]
                bs = int(q["block_start"][0])
                out = {}
                reader = shard._filesets.get(bs)
                if reader is not None:
                    for i in range(reader.n_series):
                        sid, _tags, stream = reader.read_at(i)
                        out[base64.b64encode(sid).decode()] = {
                            "checksum": zlib.adler32(stream),
                            "size": len(stream),
                        }
                return 200, json.dumps(out).encode()
            if path == "/blocks/stream":
                from m3_tpu.utils import wire

                ns = self.db.namespaces[q["namespace"][0]]
                shard = ns.shards[int(q["shard"][0])]
                bs = int(q["block_start"][0])
                sid = base64.b64decode(q["series_id"][0])
                reader = shard._filesets.get(bs)
                stream = reader.read(sid) if reader else None
                tags = (reader.tags_of(sid) or b"") if reader else b""
                if wire.packed_enabled() and wire.accepts_packed(headers):
                    # the stream is ALREADY m3tsz-compressed — the frame
                    # just drops the base64+JSON wrapping (~33% + quotes)
                    return (200,
                            wire.pack_blobs(wire.KIND_BLOCK,
                                            [stream or b"", tags]),
                            wire.CONTENT_TYPE)
                return 200, json.dumps(
                    {
                        "stream": base64.b64encode(stream or b"").decode(),
                        "tags": base64.b64encode(tags).decode(),
                    }
                ).encode()
            if path == "/blocks/rollup":
                # the repair plane's digest exchange: the whole shard's
                # per-block rollup table as ONE packed binary payload
                # (peers.ROLLUP_DTYPE — in-sync blocks cost 20 bytes on
                # the wire, not per-series JSON)
                from m3_tpu.storage.peers import (
                    local_rollup_digests,
                    pack_rollup,
                )

                digests = local_rollup_digests(
                    self.db, q["namespace"][0], int(q["shard"][0]))
                from m3_tpu.utils import wire

                if wire.packed_enabled() and wire.accepts_packed(headers):
                    return (200,
                            wire.pack_blobs(wire.KIND_ROLLUP,
                                            [pack_rollup(digests)]),
                            wire.CONTENT_TYPE)
                return 200, json.dumps({
                    "rollup_b64": base64.b64encode(
                        pack_rollup(digests)).decode(),
                }).encode()
            if path == "/repair/enqueue" and method == "POST":
                # out-of-band repair hint from a quorum read that saw
                # replica checksums disagree (client/session.py)
                if self.repair is None:
                    return 200, b'{"ok":false,"queued":false}'
                doc = json.loads(body)
                queued = self.repair.enqueue_range(
                    doc.get("namespace", "default"), int(doc["shard"]),
                    int(doc["start_ns"]), int(doc["end_ns"]),
                )
                return 200, json.dumps(
                    {"ok": True, "queued": queued}).encode()
            if path == "/debug/repair":
                if self.repair is None:
                    return 200, b'{"enabled":false}'
                return 200, json.dumps(self.repair.status()).encode()
            if path == "/debug/flush" and method == "POST":
                # ops/audit surface: persist every buffered block NOW so
                # rollup digests cover current data (the rig's convergence
                # audit flushes both replicas before comparing; blocks
                # normally wait for their window to complete)
                self.db.flush_all()
                return 200, b'{"ok":true}'
            if path == "/shards/flush" and method == "POST":
                # donor buffer/WAL tail handoff: flush ONE shard's buffered
                # windows so the joining replica's digest verification (and
                # catch-up stream) covers this node's acked-but-unflushed
                # writes before cutover reclaims the LEAVING shard
                doc = json.loads(body or b"{}")
                flushed = self.db.flush_shard(int(doc["shard"]))
                return 200, json.dumps(
                    {"ok": True, "flushed": flushed}).encode()
            if path == "/debug/placement":
                # per-shard handoff state/progress/last-error + this node's
                # placement view (the rig's elasticity episode polls it)
                out = dict(self.placement_status()
                           if self.placement_status is not None else {})
                out["handoff"] = (self.handoff.status()
                                  if self.handoff is not None
                                  else {"enabled": False})
                return 200, json.dumps(out).encode()
            return 404, b'{"error":"unknown path"}'
        except faults.SimulatedCrash:
            # a simulated crash must NOT be served as an error response —
            # no handler survives a SIGKILL. With M3_TPU_FAULTS_EXIT=1
            # (chaos rig) the WHOLE PROCESS dies here (_exit 137); else
            # propagate so the request thread dies mid-flight (the client
            # sees a torn connection) and any partially-written
            # durability state stays exactly as the kill left it.
            faults.escalate()
            raise
        except (faults.InjectedError, faults.InjectedTimeout) as e:
            return 503, json.dumps({"error": str(e)}).encode()
        except Exception as e:
            return 400, json.dumps({"error": str(e)}).encode()

    def _debug_traces(self, method, q, body):
        """Node half of the distributed-trace surface: the coordinator's
        /debug/traces?trace_id= gathers these to stitch the full tree.
        POST toggles recording ({"enabled": bool, "sample_every": int})."""
        tracer = trace.default_tracer()
        if method == "POST":
            doc = json.loads(body or b"{}")
            if "enabled" in doc:
                tracer.enabled = bool(doc["enabled"])
            if "sample_every" in doc:
                tracer.sample_every = max(1, int(doc["sample_every"]))
            return 200, json.dumps(
                {"enabled": tracer.enabled,
                 "sample_every": tracer.sample_every}).encode()
        trace_id = q.get("trace_id", [None])[0]
        if trace_id:
            return 200, json.dumps({"spans": tracer.find(trace_id)}).encode()
        limit = int(q.get("limit", ["200"])[0])
        return 200, json.dumps({"spans": tracer.recent(limit)}).encode()

    def serve(self, host="0.0.0.0", port=9000) -> int:
        api = self

        class Handler(BaseHTTPRequestHandler):
            def _do(self, method):
                u = urlparse(self.path)
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                status, payload, *rest = api.handle(
                    method, u.path, parse_qs(u.query), body,
                    headers=self.headers)
                self.send_response(status)
                # routes may return a negotiated content type as a third
                # element (/metrics OpenMetrics exposition)
                self.send_header("Content-Type",
                                 rest[0] if rest else "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):  # noqa: N802
                self._do("GET")

            def do_POST(self):  # noqa: N802
                self._do("POST")

            def log_message(self, *a):
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        threading.Thread(target=self._server.serve_forever, daemon=True).start()
        return self._server.server_address[1]

    def shutdown(self):
        if self._server:
            self._server.shutdown()


class DBNodeService:
    """Storage node: optionally placement-driven.

    With a `cluster:` config section the node reads its shard assignment
    from the KV placement, peer-bootstraps INITIALIZING shards from the
    replicas that own them, CASes them AVAILABLE, and keeps watching the
    placement every tick — the topology-watch -> shard-assignment flow of
    the reference (dbnode/storage/cluster/database.go, placement shard
    states driving elastic add/remove)."""

    def __init__(self, config: dict, kv=None):
        self.config = config
        self.log = Logger("dbnode")
        db_cfg = config.get("db", {}) or {}
        cl_cfg = config.get("cluster", {}) or {}
        self.instance_id = cl_cfg.get("instance_id", "")
        self.placement_key = cl_cfg.get("placement_key")
        self.kv = kv
        if self.kv is None:
            from m3_tpu.cluster.kv import kv_from_config

            self.kv = kv_from_config(cl_cfg)
        self._placement_version = -1
        if self.kv is not None:
            # placement-driven node: own NOTHING until the placement says
            # otherwise (sync_placement assigns once one appears)
            owned = self._owned_from_placement() or set()
            owned_arg = tuple(sorted(owned))
        else:
            owned_arg = None  # standalone node: owns every shard
        self.db = Database(
            db_cfg.get("path", "./m3data"),
            DatabaseOptions(
                n_shards=db_cfg.get("n_shards", 8),
                owned_shards=owned_arg,
                # WAL flush threshold: how many acked bytes may sit in the
                # user-space buffer (lost on SIGKILL before replication
                # recovers them). 1 = flush every append — the chaos rig
                # runs nodes this way so "acked" means "in the OS"
                commitlog_flush_every_bytes=int(db_cfg.get(
                    "commitlog_flush_every_bytes", 1 << 20)),
            ),
        )
        for ns in db_cfg.get("namespaces", [{"name": "default"}]) or []:
            self.db.create_namespace(ns["name"], namespace_options(ns.get("options")))
        # pipelined-dataflow sizing (storage/pipeline.py): `pipeline:`
        # config section {workers, depth, wal_chunk} — env vars win, so
        # M3_TPU_PIPELINE* still overrides per process (and =0 disables)
        from m3_tpu.storage import pipeline as storage_pipeline

        pl_cfg = config.get("pipeline", {}) or {}
        storage_pipeline.configure(
            workers=pl_cfg.get("workers"), depth=pl_cfg.get("depth"),
            wal_chunk=pl_cfg.get("wal_chunk"))
        from m3_tpu.cluster.runtime import RuntimeOptionsManager

        # live-tunable options: query limits, tick switches, persist pacing
        # follow the kvconfig runtime key when a cluster KV is attached
        self.runtime = RuntimeOptionsManager()
        self.db.apply_runtime(self.runtime)
        if self.kv is not None:
            self.runtime.watch_kv(self.kv)
        self.api = NodeAPI(self.db)
        # the anti-entropy repair plane (storage/repair.py): peers come
        # from the placement, tuning from the `repair:` config section
        # and the m3_tpu.repair KV key. Built unconditionally — a
        # standalone node has no peers and idles — so /debug/repair and
        # the read path's /repair/enqueue hints always have a home.
        from m3_tpu.storage.repair import RepairDaemon, RepairOptions

        self.repair = RepairDaemon(
            self.db, lambda: self.db.owned_shards,
            self._repair_peers_for_shard,
            opts=RepairOptions.from_config(config.get("repair")),
            seed=self.instance_id or "standalone",
        )
        self.api.repair = self.repair
        # placement snapshot for repair peer discovery, refreshed at most
        # every TTL so a cycle over many shards is one KV load, not one
        # per shard
        self._repair_placement_ttl_s = 5.0
        self._repair_placement: tuple[float, object] = (-1e18, None)
        self._repair_placement_lock = threading.Lock()
        # the off-tick shard handoff controller (services/handoff.py):
        # sync_placement only ENQUEUES newly-INITIALIZING shards; the
        # paced stream + donor tail handoff + digest-verified cutover run
        # on the pipeline's handoff lane, paying into the repair plane's
        # rate budget. Shards a placement change takes AWAY keep serving
        # one grace tick (donor-side cutover safety) before dropping.
        self._shard_grace: set[int] = set()
        if self.kv is not None:
            from m3_tpu.services.handoff import HandoffController

            self.handoff = HandoffController(
                self.db, self.kv, self.instance_id, self._load_placement,
                self._peer_for_instance,
                placement_key=self.placement_key,
                pacer=self.repair.pacer,
            )
            self.api.handoff = self.handoff
            self.api.placement_status = self._placement_status
        else:
            self.handoff = None
        # OTLP-style telemetry export (config `export:` / M3_TPU_EXPORT_*
        # env): storage nodes ship their span rings + seam histograms to
        # the same collector as the coordinator, so exported traces stitch
        from m3_tpu.utils.export import exporter_from_config

        self.exporter = exporter_from_config(config, "dbnode")
        if self.exporter is not None:
            self.exporter.start()
        # always-on profiling plane: M3_TPU_PROFILE arms the sampling
        # profiler + stall-watchdog checker (POST /debug/profile toggles
        # at runtime either way)
        from m3_tpu.utils import profiler

        profiler.arm_from_env("dbnode")
        self._stop = threading.Event()

    # -- placement plumbing --

    def _load_placement(self):
        """(placement, kv_version) or (None, -1). Change detection uses the
        KV VERSION — placement edits that don't bump the embedded document
        version (e.g. endpoint updates) must still be observed."""
        from m3_tpu.cluster import placement as pl

        key = self.placement_key or pl.PLACEMENT_KEY
        loaded = pl.load_placement(self.kv, key)
        return loaded if loaded else (None, -1)

    def _owned_from_placement(self) -> set[int] | None:
        p, version = self._load_placement()
        if p is None:
            return None
        self._placement_version = version
        inst = p.instances.get(self.instance_id)
        return set(inst.shards) if inst else set()

    def _peer_for_instance(self, inst):
        """HTTP peer for one placement instance (the handoff controller's
        transport half), under the repair plane's tunable peer timeout."""
        from m3_tpu.storage.peers import HTTPPeer

        if not inst.endpoint:
            return None
        return HTTPPeer(inst.endpoint,
                        timeout_s=self.repair.opts.peer_timeout_s)

    def _placement_status(self) -> dict:
        """This node's placement view for /debug/placement."""
        return {
            "instance_id": self.instance_id,
            "placement_version": self._placement_version,
            "owned_shards": sorted(self.db.owned_shards),
            "grace_shards": sorted(self._shard_grace),
        }

    def _repair_peers_for_shard(self, shard_id: int) -> list:
        """Replica peers for the repair daemon, from a TTL-cached
        placement snapshot (one KV load per cycle, not per shard) with
        the runtime-tunable peer timeout applied."""
        if self.kv is None:
            return []
        import time as _time

        with self._repair_placement_lock:
            ts, p = self._repair_placement
            stale = _time.monotonic() - ts > self._repair_placement_ttl_s
        if stale:
            try:
                p, _version = self._load_placement()
            except Exception:  # noqa: BLE001 - KV hiccup: cache the miss
                # for the TTL too, so a KV outage costs ONE failing load
                # per cycle, not one per shard; a later cycle retries
                p = None
            with self._repair_placement_lock:
                self._repair_placement = (_time.monotonic(), p)
        if p is None:
            return []
        from m3_tpu.cluster.placement import ShardState
        from m3_tpu.storage.peers import HTTPPeer

        timeout_s = self.repair.opts.peer_timeout_s
        peers = []
        for iid, inst in p.instances.items():
            if iid == self.instance_id or not inst.endpoint:
                continue
            sh = inst.shards.get(shard_id)
            if sh is not None and sh.state in (ShardState.AVAILABLE,
                                               ShardState.LEAVING):
                peers.append(HTTPPeer(inst.endpoint, timeout_s=timeout_s))
        return peers

    def sync_placement(self) -> None:
        """Reconcile shard ownership with the current placement and hand
        newly-INITIALIZING shards to the off-tick handoff controller
        (services/handoff.py): the paced peer stream, donor tail flush and
        digest-verified `mark_available` cutover all run on the pipeline's
        handoff lane, never inside this tick.

        Donor-side cutover safety: a shard the placement takes away keeps
        serving ONE extra sync (grace tick) before `assign_shards` drops
        it — clients still draining in-flight ops off a pre-swap topology
        map read the old owner meanwhile."""
        from m3_tpu.cluster.placement import ShardState

        # the kill-mid-sync seam: chaos sweeps crash a node here to prove
        # a placement change interrupted between load and assign resumes
        faults.check("placement.sync")
        p, version = self._load_placement()
        if p is None:
            return
        inst = p.instances.get(self.instance_id)
        owned = set(inst.shards) if inst else set()
        leaving_now = (self.db.owned_shards - owned) - self._shard_grace
        added, removed = self.db.assign_shards(owned | leaving_now)
        if leaving_now:
            self.log.info("shards leaving; serving one grace tick",
                          shards=sorted(leaving_now))
        self._shard_grace = leaving_now
        if added or removed:
            self.log.info("placement reassignment",
                          added=sorted(added), removed=sorted(removed))
        self._placement_version = version
        if inst is None or self.handoff is None:
            return
        initializing = [
            s.id for s in inst.shards.values()
            if s.state == ShardState.INITIALIZING
        ]
        self.handoff.request(initializing)

    def _placement_changed(self) -> bool:
        p, version = self._load_placement()
        return p is not None and version != self._placement_version

    def sync_namespaces(self) -> None:
        """Reconcile local namespaces with the KV registry (the dynamic
        namespace-registry watch, reference dbnode/namespace/dynamic):
        admin-created namespaces appear on every node without restarts."""
        from m3_tpu.cluster.kv import KeyNotFound
        from m3_tpu.query.admin import NAMESPACE_KEY, load_namespace_registry

        try:
            version = self.kv.get(NAMESPACE_KEY).version
        except KeyNotFound:
            return
        if version == getattr(self, "_ns_registry_version", -1):
            return
        registry = load_namespace_registry(self.kv)
        created = getattr(self, "_registry_namespaces", set())
        for name, opts_doc in registry.items():
            if name in self.db.namespaces:
                # pre-existing (config-declared or already synced): do NOT
                # claim it for the registry — a later registry delete must
                # not drop a config-declared namespace
                continue
            try:
                opts = namespace_options(opts_doc)
            except Exception as e:  # noqa: BLE001 - a malformed registry
                # entry (admin validates, but defense in depth) must not
                # crash-loop every storage node
                self.log.info("ignoring malformed registry namespace",
                              name=name, error=str(e))
                continue
            self.db.create_namespace(name, opts)
            created.add(name)
            self.log.info("namespace created from registry", name=name)
        # only drop namespaces the REGISTRY created — config-declared ones
        # (e.g. the default) are not the registry's to delete
        for name in list(created):
            if name not in registry and name in self.db.namespaces:
                self.db.drop_namespace(name)
                created.discard(name)
                self.log.info("namespace dropped from registry", name=name)
        self._registry_namespaces = created
        self._ns_registry_version = version

    def run(self) -> None:
        backend.init(self.log)  # once, before anything listens
        self.db.open()
        self.log.info("bootstrapped")
        if self.kv is not None:
            try:
                self.sync_namespaces()
                self.sync_placement()
            except faults.SimulatedCrash:
                faults.escalate()
                raise
            except Exception as e:  # noqa: BLE001 - a KV hiccup at boot
                # must not kill the node; the tick loop retries
                self.log.info("initial cluster sync failed; will retry",
                              error=str(e))
        http_cfg = self.config.get("http", {}) or {}
        port = self.api.serve(http_cfg.get("host", "0.0.0.0"),
                              http_cfg.get("port", 9000))
        self.log.info("node api listening", port=port)
        # continuous anti-entropy: the daemon runs for the node's whole
        # life (NOT test-invoked), paced + jittered, following the
        # m3_tpu.repair KV key for live retuning
        if self.kv is not None:
            self.repair.watch_kv(self.kv)
        self.repair.start()
        tick_every = float(self.config.get("tick_interval_s", 10.0))
        scope = default_registry().root_scope("dbnode")
        from m3_tpu.utils import profiler

        hb = profiler.register_heartbeat("dbnode.tick", tick_every)
        try:
            while not self._stop.is_set():
                self._stop.wait(tick_every)
                if self._stop.is_set():
                    break
                hb.beat()
                try:
                    # the tick-wedge seam: a delay fault here models a
                    # loop stuck mid-cycle (the rig's partition plans use
                    # it to drill the stall watchdog on a live node)
                    faults.check("dbnode.tick")
                    if self.kv is not None:
                        if hasattr(self.kv, "refresh"):
                            # cross-process KV: fire local watches (runtime
                            # options, rules) for other processes' writes
                            self.kv.refresh()
                        self.sync_namespaces()
                        if self._placement_changed() or self._shard_grace \
                                or (self.handoff is not None
                                    and self.handoff.pending()):
                            # re-sync without a version bump too: deferred
                            # handoffs retry, and grace-tick shards drop
                            self.sync_placement()
                    with scope.timer("tick"):
                        stats = self.db.tick()
                    scope.counter("blocks_flushed", stats["flushed"])
                except Exception as e:  # noqa: BLE001 - a transient KV/IO
                    # error must not kill the long-running node (but an
                    # armed SimulatedCrash must — the rig is watching)
                    faults.escalate(e)
                    self.log.error("tick error; continuing",
                                   error=f"{type(e).__name__}: {e}")
        finally:
            self.shutdown()

    def shutdown(self) -> None:
        self._stop.set()
        from m3_tpu.utils import profiler

        profiler.default_watchdog().unregister("dbnode.tick")
        if self.handoff is not None:
            self.handoff.stop()
        self.repair.stop()
        self.api.shutdown()
        if self.exporter is not None:
            self.exporter.close()  # final best-effort flush
        self.db.close()
        self.log.info("dbnode stopped")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-f", "--config", required=True)
    args = ap.parse_args(argv)
    svc = DBNodeService(load_config(args.config) or {})
    try:
        svc.run()
    except KeyboardInterrupt:
        svc.shutdown()


if __name__ == "__main__":
    main()

"""Coordinator HTTP API.

Role parity with the reference coordinator surface
(/root/reference/src/query/api/v1/httpd/handler.go:175-247): Prometheus
remote write (snappy+protobuf), query/query_range, labels, label values,
series, plus a JSON debug-write endpoint and health/ready. Runs on the
stdlib threading HTTP server; each ingest batch lands through the same
Database write path the TPU ingest pipeline uses.
"""

from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import numpy as np

from m3_tpu.index.query import Matcher, MatchType, matchers_to_query
from m3_tpu.query.engine import Engine, QueryLimitError, Scalar, Vector
from m3_tpu.query.windows import NS
from m3_tpu.utils import faults, protowire, snappy
from m3_tpu.utils.tenantlimits import TenantShedError

_MATCH_TYPE_BY_PROM = {
    0: MatchType.EQUAL,
    1: MatchType.NOT_EQUAL,
    2: MatchType.REGEXP,
    3: MatchType.NOT_REGEXP,
}

def _parse_time(s: str) -> int:
    """Prometheus API time (unix seconds float or RFC3339) -> ns."""
    try:
        return int(float(s) * NS)
    except ValueError:
        pass
    import datetime as dt

    t = dt.datetime.fromisoformat(s.replace("Z", "+00:00"))
    return int(t.timestamp() * NS)


def _parse_graphite_time(s: str, now_ns: int) -> int:
    """Graphite from/until: epoch seconds, 'now', or relative '-1h'."""
    if s == "now":
        return now_ns
    if s.startswith("-") or s.startswith("+"):
        from m3_tpu.metrics.policy import parse_go_duration

        mag = parse_go_duration(s.lstrip("+-"))
        return now_ns - mag if s.startswith("-") else now_ns + mag
    return _parse_time(s)


def _parse_step(s: str) -> int:
    try:
        return int(float(s) * NS)
    except ValueError:
        from m3_tpu.query.promql import parse_duration

        return parse_duration(s)


def _parse_series_selector(sel: str) -> list[Matcher]:
    """'metric{a="b",c!~"d"}' -> matchers (for /series and remote read)."""
    from m3_tpu.query.promql import Parser

    p = Parser(sel)
    vs = p.parse_atom()
    from m3_tpu.query.promql import VectorSelector

    if not isinstance(vs, VectorSelector) or p.peek().kind != "EOF":
        raise ValueError(f"invalid series selector {sel!r}")
    return vs.matchers


def _parse_influx_line(line: bytes):
    """'measurement,tag=v field=1.5,other=2i 1600000000000000000' ->
    (measurement, [(k, v)], [(field, float)], t_ns|None), or None."""
    try:
        # split on unescaped spaces: sections = ident, fields, [timestamp]
        sections = _split_unescaped(line, b" ")
        if len(sections) < 2:
            return None
        ident_parts = _split_unescaped(sections[0], b",")
        measurement = _influx_unescape(ident_parts[0])
        tags = []
        for part in ident_parts[1:]:
            k, _, v = part.partition(b"=")
            tags.append((_influx_unescape(k), _influx_unescape(v)))
        fields = []
        field_errors = 0
        for part in _split_unescaped(sections[1], b","):
            k, _, v = part.partition(b"=")
            try:
                if v.endswith(b"i") or v.endswith(b"u"):
                    fv = float(int(v[:-1]))
                elif v in (b"t", b"T", b"true", b"True"):
                    fv = 1.0
                elif v in (b"f", b"F", b"false", b"False"):
                    fv = 0.0
                elif v.startswith(b'"'):
                    continue  # string fields have no numeric representation
                else:
                    fv = float(v)
            except ValueError:
                field_errors += 1  # one bad field must not drop the line
                continue
            fields.append((_influx_unescape(k), fv))
        if not fields:
            return None
        t_ns = int(sections[2]) if len(sections) > 2 else None
        return measurement, sorted(tags), fields, t_ns, field_errors
    except (ValueError, IndexError):
        return None


def _split_unescaped(raw: bytes, sep: bytes) -> list[bytes]:
    """Split on sep outside escapes AND outside double-quoted strings
    (string field values may contain commas/spaces)."""
    out = []
    cur = bytearray()
    i = 0
    in_quotes = False
    while i < len(raw):
        c = raw[i:i + 1]
        if c == b"\\" and i + 1 < len(raw):
            cur += raw[i:i + 2]
            i += 2
            continue
        if c == b'"':
            in_quotes = not in_quotes
            cur += c
        elif c == sep and not in_quotes:
            out.append(bytes(cur))
            cur = bytearray()
        else:
            cur += c
        i += 1
    out.append(bytes(cur))
    return [p for p in out if p]


def _influx_unescape(raw: bytes) -> bytes:
    return raw.replace(b"\\,", b",").replace(b"\\ ", b" ").replace(b"\\=", b"=")


def _fmt_value(v: float) -> str:
    if np.isnan(v):
        return "NaN"
    if np.isposinf(v):
        return "+Inf"
    if np.isneginf(v):
        return "-Inf"
    return repr(float(v))


def _wants_openmetrics(q, headers) -> bool:
    """Scrape-format selection shared by the coordinator and node
    /metrics endpoints: EXPLICIT `?format=openmetrics` only. The
    exemplar exposition keeps the PR-4 family names (counters without
    the `_total` suffix OpenMetrics mandates) so `_m3_system` series and
    dashboards line up across formats — which means a stock Prometheus
    scraper, whose default Accept header advertises openmetrics-text,
    must keep getting the always-valid text/plain 0.0.4 render unless an
    operator opts this scrape in."""
    fmt = (q.get("format", [""])[0] if q else "").lower()
    return fmt in ("openmetrics", "openmetrics-text")


# path -> the `route` label of the stage metrics (utils/trace.py);
# every other path is trace.ROUTE_OTHER
_ROUTES = {
    "/api/v1/query_range": "query_range",
    "/api/v1/query": "query",
    "/api/v1/prom/remote/write": "remote_write",
    "/api/v1/prom/remote/read": "remote_read",
}
_QUERY_ROUTES = ("query_range", "query")


def _render_metrics(q, headers):
    """(status, content_type, payload) for a /metrics scrape: OpenMetrics
    with exemplars when negotiated, strict Prometheus text otherwise."""
    from m3_tpu.utils.instrument import default_registry

    reg = default_registry()
    if _wants_openmetrics(q, headers):
        return (200,
                "application/openmetrics-text; version=1.0.0; charset=utf-8",
                reg.render_openmetrics())
    return 200, "text/plain; version=0.0.4", reg.render_prometheus()


class CoordinatorAPI:
    """HTTP facade over a Database + PromQL Engine."""

    def __init__(self, db, namespace: str = "default", limits=None,
                 query_compile: bool = False):
        self.db = db
        self.namespace = namespace
        # whole-query compilation default for every engine this API
        # builds (config `query: compile:`; M3_TPU_QUERY_COMPILE is the
        # per-process escape hatch either way)
        self.query_compile = bool(query_compile)
        self.engine = Engine(db, namespace, limits=limits,
                             query_compile=self.query_compile)
        self._server: ThreadingHTTPServer | None = None
        # optional DownsamplerAndWriter: ingest then fans out through the
        # embedded downsampler (coordinator service wiring)
        self.writer = None
        # optional AdminAPI (namespace/placement/topic CRUD; query/admin.py)
        self.admin = None
        # optional per-tenant admission controller (utils/tenantlimits,
        # coordinator service wiring): None = no quotas, zero overhead
        self.admission = None
        # per-tenant request-latency observer handles, keyed by BOUNDED
        # label (configured tenants + the default namespace + "other")
        self._tenant_observers: dict[str, object] = {}
        # per-namespace engine cache for ?namespace= query routing (the
        # self-monitoring loop's _m3_system namespace is queried this way)
        self._engines: dict[str, Engine] = {namespace: self.engine}
        self._engines_lock = threading.Lock()
        from m3_tpu.utils.instrument import default_registry

        self._scope = default_registry().root_scope("coordinator")

    # bound on cached per-namespace engines: namespaces are operator-
    # created (bounded), but the ?namespace= value is client-supplied
    MAX_ENGINES = 64

    def _engine_for(self, namespace: str) -> Engine:
        # validate before caching: an unknown namespace must not grow the
        # cache (fanout facades union remote zones, so remote-only names
        # are only checkable there at query time — they still pass)
        if namespace != self.namespace \
                and namespace not in self.db.namespaces \
                and not getattr(self.db, "zones", None):
            raise ValueError(f"unknown namespace {namespace!r}")
        with self._engines_lock:
            eng = self._engines.get(namespace)
            if eng is None:
                if len(self._engines) >= self.MAX_ENGINES:
                    # drop an arbitrary non-default entry (engines are
                    # cheap to rebuild; correctness never depends on one)
                    for key in list(self._engines):
                        if key != self.namespace:
                            del self._engines[key]
                            break
                eng = self._engines[namespace] = Engine(
                    self.db, namespace, query_compile=self.query_compile)
        return eng

    def _write(self, name: bytes, tags, t_ns: int, value: float):
        if self.writer is not None:
            from m3_tpu.metrics.aggregation import MetricType

            return self.writer.write(MetricType.GAUGE, name, tags, t_ns, value)
        return self.db.write_tagged(self.namespace, name, list(tags), t_ns, value)

    # -- request handling --

    def handle(self, method: str, path: str, query: dict, body: bytes,
               headers=None):
        """Returns (status, content_type, payload, headers) — routes may
        return the legacy 3-tuple; headers default to {}.

        Trace ingress: the head-based sampling decision for the whole
        request is made HERE (or honored from a propagated `traceparent`
        in `headers`), every downstream hop — engine, session, storage
        nodes — follows it, and the response echoes the trace id in an
        `M3-Trace-Id` header so a slow query is one /debug/traces lookup
        away."""
        import dataclasses
        import math
        import time as _time

        from m3_tpu.utils import querystats, trace

        # one resource budget per request, enforced in the storage read
        # path (covers PromQL, Graphite render, and remote read alike)
        limits = getattr(self.db, "limits", None)
        route = _ROUTES.get(path, trace.ROUTE_OTHER)
        ctx = dataclasses.replace(trace.start_request(headers), route=route)
        # a query route's record lives as long as the request, so that
        # /debug/slow_queries holds every stage down to `render`; the
        # engine's own start() nests under it
        stats = querystats.start(
            query=(query.get("query") or [""])[0] if query else "",
            namespace=self._tenant_of(query)) \
            if route in _QUERY_ROUTES else None
        t0 = _time.perf_counter()
        try:
            if limits is not None:
                limits.start_query()
            with trace.activate(ctx), \
                    trace.stage(trace.STAGE_REQUEST, path=path,
                                method=method), \
                    self._scope.histogram("request_seconds"):
                res = self._route(method, path, query, body, headers)
            status, ctype, payload, hdrs = res if len(res) == 4 \
                else (*res, {})
        except TenantShedError as e:
            # per-tenant admission shed: 429 + Retry-After, the
            # degrade-THIS-tenant contract (clients treat it as
            # backpressure, never as a node failure)
            status, ctype, payload, hdrs = 429, "application/json", json.dumps(
                {"status": "error", "errorType": "tenant_limit",
                 "tenant": e.namespace, "kind": e.kind,
                 "retry_after_s": round(e.retry_after_s, 3),
                 "error": str(e)}
            ).encode(), {"Retry-After": str(max(1, math.ceil(e.retry_after_s)))}
        except faults.SimulatedCrash:
            # crash semantics match the node API: never served as an
            # error envelope — the request thread dies (and with
            # M3_TPU_FAULTS_EXIT=1 armed, the whole process does)
            faults.escalate()
            raise
        except QueryLimitError as e:
            status, ctype, payload, hdrs = 422, "application/json", json.dumps(
                {"status": "error", "errorType": "query_limit", "error": str(e)}
            ).encode(), {}
        except Exception as e:  # surface as prometheus-style error envelope
            status, ctype, payload, hdrs = 400, "application/json", json.dumps(
                {"status": "error", "errorType": "bad_data", "error": str(e)}
            ).encode(), {}
        finally:
            if limits is not None:
                limits.end_query()
            if stats is not None:
                querystats.finish(stats)
        if path.startswith("/api/v1/") or path == "/render":
            # bytes-on-wire ledger for the coordinator's egress (the
            # `response` flow of net_bytes_{sent,recv}): only query-serving
            # routes — a /metrics scrape reporting its own response bytes
            # would feed back into itself
            from m3_tpu.utils import wire

            wire.account("response", sent=len(payload),
                         recv=len(body) if body else 0)
            if self.admission is not None:
                # only tenant-billable routes feed the per-tenant latency
                # histogram: /metrics scrapes, health polls and /debug would
                # dilute the p99 the isolation SLO is asserted against
                self._observe_tenant(query, _time.perf_counter() - t0)
        if trace.default_tracer().enabled:
            hdrs = {**hdrs, "M3-Trace-Id": ctx.trace_id}
        return status, ctype, payload, hdrs

    # -- per-tenant admission plumbing --

    def _tenant_of(self, q) -> str:
        """The tenant (== namespace) a request bills to: ?namespace= on
        query routes, the configured ingest namespace otherwise."""
        return (q.get("namespace", [self.namespace])[0] if q
                else self.namespace)

    def _observe_tenant(self, q, seconds: float) -> None:
        """Per-tenant request-latency histogram (the PR-4 family,
        namespace-labelled): the substrate for isolation SLOs — tenant
        B's p99 must hold while tenant A is being shed. Cardinality is
        bounded: only configured tenants and the default namespace get
        their own label, everything else shares "other"."""
        ns = self._tenant_of(q)
        if ns != self.namespace and not self.admission.is_configured(ns):
            ns = "other"
        obs = self._tenant_observers.get(ns)
        if obs is None:
            obs = self._scope.subscope("tenant", namespace=ns) \
                .histogram_handle("request_seconds")
            self._tenant_observers[ns] = obs
        obs(seconds)

    def _admit_write(self, datapoints: int) -> None:
        """Ingest gate: raises TenantShedError (-> 429) when the tenant
        is over its datapoints/sec rate or live-cardinality ceiling."""
        if self.admission is not None and datapoints:
            self.admission.admit_write(self.namespace, datapoints)

    def _admit_query(self, ns: str) -> None:
        """Query gate: queries/sec bucket + post-paid cost budget."""
        if self.admission is not None:
            self.admission.admit_query(ns)

    def _charge_query(self, ns: str, engine) -> None:
        """Bill the finished query's QueryStats against the tenant's
        cost budget (post-paid; never raises)."""
        if self.admission is not None:
            self.admission.charge_query_cost(
                ns, getattr(engine, "last_stats", None))

    def _warning_headers(self, engine=None) -> dict:
        """PR-2 partial-result contract, threaded out to HTTP: one
        M3-Warnings header value per degraded read leg (failed session
        host, skipped fanout zone) recorded by the engine for THIS query.
        An absent header means the result is complete."""
        warns = getattr(engine or self.engine, "last_warnings", None)
        if not warns:
            return {}
        return {"M3-Warnings": ",".join(str(w) for w in warns)}

    def _route(self, method, path, q, body, headers=None):
        if path == "/health":
            return 200, "application/json", b'{"ok":true}'
        if path == "/ready":
            # ready == the storage below is open/bootstrapped
            ready = bool(getattr(self.db, "_open", True))
            return (200 if ready else 503), "application/json", json.dumps(
                {"ready": ready}
            ).encode()
        if self.admin is not None and (
            path.startswith("/api/v1/services/")
            or path.startswith("/api/v1/database/")
            or path.startswith("/api/v1/topic")
            or path == "/api/v1/runtime"
            or path == "/api/v1/rules"
            or path.startswith("/api/v1/rules/")
        ):
            res = self.admin.handle(method, path, q, body)
            if res is not None:
                status, payload = res
                return status, "application/json", payload
        if path == "/metrics":
            return _render_metrics(q, headers)
        if path == "/debug/dump":
            return self._debug_dump()
        if path == "/debug/profile":
            # the always-on profiling & saturation plane: sampling
            # profiler top-N / collapsed stacks, contended-lock table,
            # stall-watchdog status (utils/profiler; POST toggles live)
            from m3_tpu.utils import profiler

            status, payload, ctype = profiler.handle_debug_profile(
                method, q, body)
            return status, ctype, payload
        if path == "/debug/profile/device":
            # the service's device-trace session (utils/backend): the
            # stage clock's spans as annotations beside the device planes
            from m3_tpu.utils import backend

            status, payload, ctype = backend.handle_debug_profile_device(
                method, q, body)
            return status, ctype, payload
        if path == "/debug/compute":
            # the device-compute observability plane: top-N programs by
            # device time, plan-cache occupancy, padding-waste ledger,
            # device-resident cache bytes (utils/compute_stats)
            from m3_tpu.utils import compute_stats

            status, payload, ctype = compute_stats.handle_debug_compute(
                method, q, body)
            return status, ctype, payload
        if path == "/debug/traces":
            return self._debug_traces(method, q, body)
        if path == "/debug/explain":
            from m3_tpu.query import explain as explain_mod

            trace_id = q.get("trace_id", [None])[0]
            if trace_id:
                return 200, "application/json", json.dumps(
                    {"plans": explain_mod.find(trace_id)}).encode()
            limit = int(q.get("limit", ["20"])[0])
            return 200, "application/json", json.dumps(
                {"plans": explain_mod.recent(limit)}).encode()
        if path == "/debug/standing":
            # per-rule standing-query evaluation state (watermarks, eval/
            # skip tallies, matched shards, last error) — the rig's
            # standing_rules episode audits recovery through this surface
            standing = getattr(getattr(self.writer, "downsampler", None),
                               "standing", None)
            if standing is None:
                return 404, "application/json", json.dumps(
                    {"status": "error", "error": "no standing rules"}
                ).encode()
            return 200, "application/json", json.dumps(
                standing.status()).encode()
        if path == "/debug/slow_queries":
            from m3_tpu.utils import querystats

            limit = int(q.get("limit", ["50"])[0])
            return 200, "application/json", json.dumps(
                {"queries": querystats.slow_queries(limit),
                 "threshold_ms": round(querystats.threshold_s() * 1e3, 3)}
            ).encode()
        if path == "/api/v1/prom/remote/write" and method == "POST":
            return self._remote_write(body)
        if path == "/api/v1/prom/remote/read" and method == "POST":
            return self._remote_read(body)
        if path == "/api/v1/json/write" and method == "POST":
            return self._json_write(body)
        if path == "/api/v1/influxdb/write" and method == "POST":
            return self._influx_write(q, body)
        if path == "/api/v1/query_range":
            return self._query_range(q)
        if path == "/api/v1/m3ql/query_range":
            return self._m3ql_query_range(q)
        if path == "/api/v1/query":
            return self._query_instant(q)
        if path == "/api/v1/labels":
            return self._labels(q)
        m = re.fullmatch(r"/api/v1/label/([^/]+)/values", path)
        if m:
            return self._label_values(m.group(1), q)
        if path == "/api/v1/series":
            return self._series(q)
        if path == "/render":
            return self._graphite_render(q)
        if path == "/metrics/find":
            return self._graphite_find(q)
        return 404, "application/json", json.dumps(
            {"status": "error", "error": f"unknown path {path}"}
        ).encode()

    def _debug_traces(self, method, q, body: bytes):
        """GET: recent spans, or — with ?trace_id= — the ONE stitched
        cross-process tree for that trace: local ring spans merged with
        every storage node's (cluster session connections expose
        /debug/traces on the node API). POST: runtime toggle
        ({"enabled": bool, "sample_every": int})."""
        from m3_tpu.utils import trace

        tracer = trace.default_tracer()
        if method == "POST":
            doc = json.loads(body or b"{}")
            if "enabled" in doc:
                tracer.enabled = bool(doc["enabled"])
            if "sample_every" in doc:
                tracer.sample_every = max(1, int(doc["sample_every"]))
            return 200, "application/json", json.dumps(
                {"enabled": tracer.enabled,
                 "sample_every": tracer.sample_every}
            ).encode()
        trace_id = q.get("trace_id", [None])[0]
        if not trace_id:
            limit = int(q.get("limit", ["200"])[0])
            return 200, "application/json", json.dumps(
                {"spans": tracer.recent(limit)}
            ).encode()
        spans = tracer.find(trace_id)
        # cluster mode: gather the nodes' halves of the trace (their spans
        # live in their own process rings)
        session = getattr(self.db, "session", None)
        for host, conn in (getattr(session, "connections", None) or {}).items():
            fetch = getattr(conn, "debug_traces", None)
            if fetch is None:
                continue
            try:
                spans.extend(fetch(trace_id))
            except Exception:  # noqa: BLE001 - a dead node must not hide
                continue      # the rest of the trace
        # dedupe by span id: in-process test topologies (and co-located
        # services) share one ring, so the same span can arrive twice
        seen: set[str] = set()
        unique = []
        for s in spans:
            sid = s.get("span_id") or ""
            if sid and sid in seen:
                continue
            seen.add(sid)
            unique.append(s)
        spans = sorted(unique, key=lambda s: s.get("start_unix_ns", 0))
        return 200, "application/json", json.dumps(
            {"trace_id": trace_id, "count": len(spans), "spans": spans,
             "tree": trace.build_tree(spans)}
        ).encode()

    def _debug_dump(self):
        """Thread stacks + namespace stats (the x/debug zip-dump role)."""
        import sys
        import traceback

        stacks = {}
        for tid, frame in sys._current_frames().items():
            stacks[str(tid)] = traceback.format_stack(frame)
        ns_stats = {}
        for name, ns in list(self.db.namespaces.items()):
            shards = getattr(ns, "shards", None)
            if shards is None:  # cluster facade: nodes own the storage
                ns_stats[name] = {"remote": True}
                continue
            ns_stats[name] = {
                "shards": len(shards),
                "series": sum(s.buffer.n_series for s in shards.values()),
                "flushed_blocks": sum(
                    len(s._filesets) for s in shards.values()
                ),
            }
        return 200, "application/json", json.dumps(
            {"threads": stacks, "namespaces": ns_stats}
        ).encode()

    # -- graphite --

    def _graphite_render(self, q):
        from m3_tpu.query.graphite import GraphiteEngine

        self._admit_query(self.namespace)
        now = time.time_ns()
        start = _parse_graphite_time(q["from"][0], now) if "from" in q else now - 24 * 3600 * NS
        end = _parse_graphite_time(q["until"][0], now) if "until" in q else now
        step = 60 * NS
        if "maxDataPoints" in q:
            mdp = max(int(q["maxDataPoints"][0]), 1)
            step = max((end - start) // mdp, 10 * NS)
            step -= step % (10 * NS) or 0
            step = max(step, 10 * NS)
        eng = GraphiteEngine(self.db, self.namespace)
        out = []
        for target in q.get("target", []):
            for s in eng.render(target, start, end, step):
                out.append(
                    {
                        "target": s.name.decode(),
                        "datapoints": [
                            [None if np.isnan(v) else float(v), int(t // NS)]
                            for t, v in zip(s.times, s.values)
                        ],
                    }
                )
        return 200, "application/json", json.dumps(out).encode()

    def _graphite_find(self, q):
        from m3_tpu.query.graphite import path_prefix_query

        pattern = q["query"][0]
        ns, start, end = self._time_range(q)
        parts = pattern.split(".")
        depth = len(parts) - 1
        docs = ns.query_ids(path_prefix_query(pattern), start, end)
        name_tag = f"__g{depth}__".encode()
        deeper_tag = f"__g{depth + 1}__".encode()
        # a node can be BOTH a leaf (series ends here) and a branch
        nodes: dict[bytes, set] = {}
        for doc in docs:
            fields = dict(doc.fields)
            text = fields.get(name_tag)
            if text is None:
                continue
            kind = "branch" if deeper_tag in fields else "leaf"
            nodes.setdefault(text, set()).add(kind)
        out = []
        prefix = ".".join(parts[:-1])
        for text in sorted(nodes):
            node_id = (prefix + "." if prefix else "") + text.decode()
            for kind in sorted(nodes[text]):
                is_branch = kind == "branch"
                out.append(
                    {
                        "text": text.decode(),
                        "id": node_id,
                        "leaf": 0 if is_branch else 1,
                        "expandable": 1 if is_branch else 0,
                        "allowChildren": 1 if is_branch else 0,
                    }
                )
        return 200, "application/json", json.dumps(out).encode()

    # -- ingest --

    def _remote_write(self, body: bytes):
        from m3_tpu.utils import trace

        with trace.stage(trace.STAGE_WRITE_DECODE):
            payload = snappy.decompress(body)
            series = protowire.decode_write_request(payload)
            entries = []
            for ts in series:
                name = b""
                tags = []
                for k, v in ts.labels:
                    if k == b"__name__":
                        name = v
                    else:
                        tags.append((k, v))
                for ts_ms, value in ts.samples:
                    entries.append((name, tags, ts_ms * 1_000_000, value))
        self._admit_write(len(entries))
        batch = getattr(self.db, "write_batch", None)
        if batch is not None and (
                self.writer is None or self.writer.downsampler is None):
            # no downsampler rules to run per-sample: one op-batched
            # request per storage node (host-queue batching role) with
            # PER-ENTRY results — one sub-consistency sample degrades its
            # own slot, and the response names the shortfall instead of
            # failing (or silently acking) the whole batch
            results = batch(self.namespace, entries)
            bad = [r for r in results if r is not None]
            n = len(results) - len(bad)
            if bad:
                return 500, "application/json", json.dumps(
                    {"status": "error", "errorType": "partial_write",
                     "samples": n, "failed": len(bad),
                     "error": f"{len(bad)}/{len(results)} samples failed "
                              f"(first: {bad[0]})"}
                ).encode()
        else:
            for name, tags, t_ns, value in entries:
                self._write(name, tags, t_ns, value)
            n = len(entries)
        return 200, "application/json", json.dumps({"status": "success", "samples": n}).encode()

    def _json_write(self, body: bytes):
        doc = json.loads(body)
        tags = [(k.encode(), v.encode()) for k, v in sorted(doc.get("tags", {}).items())]
        name = doc.get("metric", "").encode()
        t_ns = int(doc["timestamp"] * NS) if "timestamp" in doc else None
        if t_ns is None:
            import time

            t_ns = time.time_ns()
        self._admit_write(1)
        self._write(name, tags, t_ns, float(doc["value"]))
        return 200, "application/json", b'{"status":"success"}'

    def _influx_write(self, q, body: bytes):
        """InfluxDB line protocol ingest (the reference influxdb handler,
        api/v1/handler/influxdb/write.go): each field of a line becomes a
        series named measurement_field, tags become labels."""
        import gzip

        if body[:2] == b"\x1f\x8b":
            body = gzip.decompress(body)
        precision = q.get("precision", ["ns"])[0]
        mult = {"ns": 1, "u": 10**3, "us": 10**3, "ms": 10**6,
                "s": 10**9, "m": 60 * 10**9, "h": 3600 * 10**9}.get(precision)
        if mult is None:
            return 400, "application/json", json.dumps(
                {"status": "error", "error": f"invalid precision {precision!r}"}
            ).encode()
        n = 0
        errors = 0
        # parse the whole payload BEFORE writing: the admission gate needs
        # the datapoint count, and a shed must reject the batch without
        # having half-applied it
        writes = []
        for line in body.splitlines():
            line = line.strip()
            if not line or line.startswith(b"#"):
                continue
            parsed = _parse_influx_line(line)
            if parsed is None:
                errors += 1
                continue
            measurement, tags, fields, t_ns, field_errors = parsed
            errors += field_errors
            if t_ns is None:
                t_ns = time.time_ns()
            else:
                t_ns *= mult
            for fname, fval in fields:
                name = measurement + b"_" + fname if fname != b"value" else measurement
                writes.append((name, tags, t_ns, fval))
        self._admit_write(len(writes))
        for name, tags, t_ns, fval in writes:
            self._write(name, tags, t_ns, fval)
            n += 1
        if errors:
            # influx-style partial-write semantics: good points ARE
            # written; the client still learns something was dropped
            return 400, "application/json", json.dumps(
                {"status": "error",
                 "error": f"partial write: {errors} unparseable "
                          f"lines/fields, {n} points written"}
            ).encode()
        return 204, "application/json", b""

    # -- read --

    def _remote_read(self, body: bytes):
        self._admit_query(self.namespace)
        queries = protowire.decode_read_request(snappy.decompress(body))
        results = []
        for q in queries:
            matchers = [
                Matcher(_MATCH_TYPE_BY_PROM[m.type], m.name, m.value)
                for m in q.matchers
            ]
            res = self.db.query(
                self.namespace, matchers, q.start_ms * 1_000_000,
                q.end_ms * 1_000_000 + 1,
            )
            out = []
            for sid, fields, dps in res:
                out.append(
                    protowire.PromTimeSeries(
                        labels=sorted(fields),
                        samples=[(d.timestamp_ns // 1_000_000, d.value) for d in dps],
                    )
                )
            results.append(out)
        payload = snappy.compress(protowire.encode_read_response(results))
        return 200, "application/x-protobuf", payload

    def _query_engine(self, q) -> Engine:
        """Engine for the request's ?namespace= (default: the configured
        one) — how PromQL reaches the `_m3_system` self-monitoring tier."""
        ns = q.get("namespace", [self.namespace])[0]
        return self._engine_for(ns)

    @staticmethod
    def _explain_mode(q) -> bool | None:
        """?explain= → None (off), False (plan only), True (analyze)."""
        raw = (q.get("explain", [""])[0] or "").lower()
        if not raw:
            return None
        if raw == "analyze":
            return True
        if raw in ("plan", "true", "1"):
            return False
        raise ValueError(f"explain must be 'plan' or 'analyze', got {raw!r}")

    @staticmethod
    def _precision_of(q) -> str | None:
        """?precision=bf16 — the per-query grant for the hot tier's
        reduced-precision value mirror (storage/hottier). Anything else
        than the explicit opt-in keeps full precision."""
        raw = (q.get("precision", [""])[0] or "").lower()
        if not raw:
            return None
        if raw == "bf16":
            return "bf16"
        raise ValueError(f"precision must be 'bf16', got {raw!r}")

    def _run_explained(self, q, engine, run):
        """Run one engine evaluation, collecting its plan tree when
        ?explain= asks for one. Returns ((result, eval_ts), plan_doc) —
        plan_doc is None without explain; with it, the finished record
        (tree + trace id + envelope-parity stats) also lands in the
        /debug/explain ring."""
        from m3_tpu.storage import hottier

        base_run = run
        precision = self._precision_of(q)
        if precision is not None:
            def run():  # noqa: F811 - deliberate wrap
                with hottier.negotiated_precision(precision):
                    return base_run()
        mode = self._explain_mode(q)
        if mode is None:
            return run(), None
        from m3_tpu.query import explain as explain_mod

        with explain_mod.collect(analyze=mode) as col:
            out = run()
        doc = col.to_dict()
        st = engine.last_stats
        if st is not None:
            doc["query"] = st.query
            doc["trace_id"] = st.trace_id
            if mode:
                doc["stats"] = st.to_dict()
        explain_mod.remember(doc)
        return out, doc

    def _query_range(self, q):
        expr = q["query"][0]
        start = _parse_time(q["start"][0])
        end = _parse_time(q["end"][0])
        step = _parse_step(q["step"][0])
        self._admit_query(self._tenant_of(q))
        engine = self._query_engine(q)
        (result, eval_ts), plan = self._run_explained(
            q, engine, lambda: engine.query_range(expr, start, end, step))
        self._charge_query(self._tenant_of(q), engine)
        return (200, "application/json",
                self._render(result, eval_ts, matrix=True, engine=engine,
                             explain_doc=plan),
                self._warning_headers(engine))

    def _m3ql_query_range(self, q):
        """M3QL pipe-syntax range query (the reference's experimental
        /api/v1/m3ql endpoint role): parse with query.m3ql into the SAME
        AST and evaluate on the shared engine."""
        from m3_tpu.query import m3ql

        raw = q["query"][0]
        expr = m3ql.parse(raw)
        start = _parse_time(q["start"][0])
        end = _parse_time(q["end"][0])
        step = _parse_step(q["step"][0])
        self._admit_query(self._tenant_of(q))
        engine = self._query_engine(q)
        (result, eval_ts), plan = self._run_explained(
            q, engine, lambda: engine.query_range_expr(
                expr, start, end, step, query_text=raw))
        self._charge_query(self._tenant_of(q), engine)
        return (200, "application/json",
                self._render(result, eval_ts, matrix=True, engine=engine,
                             explain_doc=plan),
                self._warning_headers(engine))

    def _query_instant(self, q):
        expr = q["query"][0]
        t = _parse_time(q["time"][0]) if "time" in q else None
        if t is None:
            import time as _time

            t = _time.time_ns()
        self._admit_query(self._tenant_of(q))
        engine = self._query_engine(q)
        (result, eval_ts), plan = self._run_explained(
            q, engine, lambda: engine.query_instant(expr, t))
        self._charge_query(self._tenant_of(q), engine)
        return (200, "application/json",
                self._render(result, eval_ts, matrix=False, engine=engine,
                             explain_doc=plan),
                self._warning_headers(engine))

    def _render(self, result, eval_ts, matrix: bool, engine=None,
                explain_doc=None):
        from m3_tpu.utils import trace

        with trace.stage(trace.STAGE_RENDER):
            return self._render_json(result, eval_ts, matrix, engine,
                                     explain_doc)

    def _render_json(self, result, eval_ts, matrix: bool, engine,
                     explain_doc):
        ts_sec = eval_ts.astype(np.float64) / NS
        if isinstance(result, Scalar):
            if matrix:
                data = {
                    "resultType": "matrix",
                    "result": [
                        {
                            "metric": {},
                            "values": [
                                [t, _fmt_value(v)]
                                for t, v in zip(ts_sec, result.values)
                                if not np.isnan(v)
                            ],
                        }
                    ],
                }
            else:
                data = {
                    "resultType": "scalar",
                    "result": [ts_sec[0], _fmt_value(result.values[0])],
                }
        elif isinstance(result, Vector):
            if matrix:
                out = []
                for i, lb in enumerate(result.labels):
                    values = [
                        [t, _fmt_value(v)]
                        for t, v in zip(ts_sec, result.values[i])
                        if not np.isnan(v)
                    ]
                    if values:
                        out.append(
                            {
                                "metric": {
                                    k.decode(): v.decode() for k, v in lb.items()
                                },
                                "values": values,
                            }
                        )
                data = {"resultType": "matrix", "result": out}
            else:
                out = []
                for i, lb in enumerate(result.labels):
                    v = result.values[i, 0]
                    if not np.isnan(v):
                        out.append(
                            {
                                "metric": {
                                    k.decode(): val.decode() for k, val in lb.items()
                                },
                                "value": [ts_sec[0], _fmt_value(v)],
                            }
                        )
                data = {"resultType": "vector", "result": out}
        else:
            data = {"resultType": "string", "result": [ts_sec[0], result.value]}
        doc = {"status": "success", "data": data}
        engine = engine or self.engine
        # prometheus envelope convention: a top-level "warnings" list
        # accompanies a SUCCEEDING partial result (mirrors M3-Warnings)
        warns = getattr(engine, "last_warnings", None)
        if warns:
            doc["warnings"] = [str(w) for w in warns]
        # per-query stats (series matched, blocks read, bytes decoded,
        # cache hit/miss, decode rungs, stage timings) ride the envelope
        stats = getattr(engine, "last_stats", None)
        if stats is not None:
            doc["stats"] = stats.to_dict()
        # ?explain= : the resolved plan tree (with per-stage timings,
        # dispatch rungs and per-node legs under analyze) rides along
        if explain_doc is not None:
            doc["explain"] = explain_doc
        return json.dumps(doc).encode()

    def _time_range(self, q):
        ns = self.db.namespaces[self.namespace]
        start = _parse_time(q["start"][0]) if "start" in q else 0
        end = _parse_time(q["end"][0]) if "end" in q else (1 << 62)
        return ns, start, end

    def _labels(self, q):
        ns, start, end = self._time_range(q)
        names = [n.decode() for n in ns.index.aggregate_field_names(start, end)]
        return 200, "application/json", json.dumps(
            {"status": "success", "data": names}
        ).encode()

    def _label_values(self, name, q):
        ns, start, end = self._time_range(q)
        vals = [
            v.decode()
            for v in ns.index.aggregate_field_values(name.encode(), start, end)
        ]
        return 200, "application/json", json.dumps(
            {"status": "success", "data": vals}
        ).encode()

    def _series(self, q):
        ns, start, end = self._time_range(q)
        out = []
        for sel in q.get("match[]", []):
            matchers = _parse_series_selector(sel)
            for doc in ns.query_ids(matchers_to_query(matchers), start, end):
                out.append({k.decode(): v.decode() for k, v in doc.fields})
        return 200, "application/json", json.dumps(
            {"status": "success", "data": out}
        ).encode()

    # -- server lifecycle --

    def serve(self, host: str = "127.0.0.1", port: int = 7201) -> int:
        # arm percentile-based slow-query admission: the bar follows the
        # live p99 of THIS coordinator's request-latency histogram (with
        # M3_TPU_SLOW_QUERY_MS as floor, and as the sole bar until the
        # histogram holds enough samples to trust)
        from m3_tpu.utils import querystats
        from m3_tpu.utils.instrument import default_registry

        reg = default_registry()
        # .get, not [..]: the defaultdict must not grow outside its lock
        self._adaptive_source = \
            lambda: reg.histograms.get(("coordinator.request_seconds", ()))
        querystats.set_adaptive_source(self._adaptive_source)
        api = self

        class Handler(BaseHTTPRequestHandler):
            def _do(self, method):
                u = urlparse(self.path)
                q = parse_qs(u.query)
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                if method == "POST" and self.headers.get(
                    "Content-Type", ""
                ).startswith("application/x-www-form-urlencoded"):
                    try:
                        q = {**parse_qs(body.decode()), **q}
                    except UnicodeDecodeError:
                        pass  # mislabeled binary body; routes read it raw
                status, ctype, payload, headers = api.handle(
                    method, u.path, q, body, headers=self.headers)
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                for k, v in headers.items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):  # noqa: N802
                self._do("GET")

            def do_POST(self):  # noqa: N802
                self._do("POST")

            def do_DELETE(self):  # noqa: N802
                self._do("DELETE")

            def do_PUT(self):  # noqa: N802
                self._do("PUT")

            def log_message(self, *a):  # quiet
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        thread = threading.Thread(target=self._server.serve_forever, daemon=True)
        thread.start()
        return self._server.server_address[1]

    def shutdown(self):
        from m3_tpu.utils import querystats

        # identity-scoped: only disarm the bar if WE registered it — a
        # sibling CoordinatorAPI's registration must survive our shutdown
        src = getattr(self, "_adaptive_source", None)
        if src is not None:
            querystats.clear_adaptive_source(src)
        if self._server:
            self._server.shutdown()
            self._server = None

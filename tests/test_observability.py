"""PR-4 observability plane: distributed tracing, histograms, per-query
stats, and the M3-monitors-M3 self-scrape loop.

Covers the acceptance criteria: a query_range through coordinator ->
session fan-out -> two dbnodes stitches into ONE trace (id echoed in a
response header, /debug/traces?trace_id= returns the cross-process tree
including the decode-rung span); /metrics exposes _bucket/_sum/_count for
the write / read_many / consensus seams; the `_m3_system` namespace
answers PromQL over the platform's own p99; and the Prometheus text
exposition survives a strict parser round-trip.
"""

from __future__ import annotations

import json
import math
import re
import urllib.request

import pytest

from m3_tpu.utils import querystats, trace
from m3_tpu.utils.instrument import MetricsRegistry, default_registry
from m3_tpu.utils.trace import SpanContext, Tracer, parse_traceparent

START = 1_600_000_000_000_000_000
NS = 10**9


# ---------------------------------------------------------------------------
# strict Prometheus text parser (the round-trip half of the exposition test)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?"
    r" (?P<value>[^ ]+)$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    return v.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")


def parse_exposition(text: str):
    """Strict parse: returns (types, samples) where samples maps
    (name, frozenset(labels)) -> float. Raises on any malformed line."""
    types: dict[str, str] = {}
    samples: dict = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(" ", 3)
            assert parts[0] == "#" and parts[1] == "TYPE", f"bad meta: {line}"
            assert parts[2] not in types, f"duplicate TYPE for {parts[2]}"
            assert parts[3] in ("counter", "gauge", "histogram", "untyped",
                                "summary"), line
            types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        labels = {}
        raw = m.group("labels")
        if raw:
            consumed = 0
            for lm in _LABEL_RE.finditer(raw):
                labels[lm.group(1)] = _unescape(lm.group(2))
                consumed = lm.end()
            rest = raw[consumed:].strip(", ")
            assert not rest, f"unparsed label residue {rest!r} in {line!r}"
        val = m.group("value")
        if val == "NaN":
            fv = math.nan
        elif val == "+Inf":
            fv = math.inf
        elif val == "-Inf":
            fv = -math.inf
        else:
            fv = float(val)
        samples[(m.group("name"), frozenset(labels.items()))] = fv
    return types, samples


class TestExposition:
    def test_round_trip_strict(self):
        reg = MetricsRegistry()
        s = reg.root_scope("svc")
        s.counter("reqs", 3)
        s.gauge("temp", float("nan"))
        s.gauge("ceiling", float("inf"))
        tagged = s.subscope("api", path='/q"x"', note="a\\b\nc")
        tagged.counter("hits")
        with s.timer("tick"):
            pass
        for v in (0.0001, 0.004, 0.004, 2.5):
            s.observe("lat_seconds", v)
        types, samples = parse_exposition(reg.render_prometheus().decode())
        assert types["svc_reqs"] == "counter"
        assert types["svc_lat_seconds"] == "histogram"
        assert samples[("svc_reqs", frozenset())] == 3
        assert math.isnan(samples[("svc_temp", frozenset())])
        assert math.isinf(samples[("svc_ceiling", frozenset())])
        # escaped label values survive the round trip
        key = frozenset({"path": '/q"x"', "note": "a\\b\nc"}.items())
        assert samples[("svc_api_hits", key)] == 1  # noqa: F841 - presence
        # histogram contract: cumulative monotone, +Inf == count, sum right
        buckets = sorted(
            ((dict(k[1])["le"], v) for k, v in samples.items()
             if k[0] == "svc_lat_seconds_bucket"),
            key=lambda p: math.inf if p[0] == "+Inf" else float(p[0]),
        )
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), "bucket counts must be cumulative"
        assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 4
        assert samples[("svc_lat_seconds_count", frozenset())] == 4
        assert samples[("svc_lat_seconds_sum", frozenset())] == \
            pytest.approx(0.0001 + 0.004 + 0.004 + 2.5)
        # p99 interpolates into the top occupied bucket
        h = reg.histograms[("svc.lat_seconds", ())]
        assert 2.0 <= h.quantile(0.99) <= 4.0

    def test_every_live_registry_line_parses(self):
        # whatever other tests put in the default registry must render
        # parseable too (this is what a real scraper sees)
        default_registry().root_scope("probe").counter("alive")
        types, samples = parse_exposition(
            default_registry().render_prometheus().decode())
        assert samples  # non-empty and fully parsed


class TestTraceCore:
    def test_traceparent_round_trip(self):
        ctx = SpanContext("ab" * 16, "cd" * 8, True)
        assert parse_traceparent(ctx.to_traceparent()) == ctx
        off = SpanContext("ab" * 16, "cd" * 8, False)
        assert parse_traceparent(off.to_traceparent()) == off
        assert parse_traceparent("garbage") is None
        assert parse_traceparent("00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None
        assert parse_traceparent(None) is None

    def test_span_identity_and_nesting(self):
        tr = Tracer(capacity=16)
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_span_id == outer.span_id
        spans = tr.recent()
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert spans[1]["parent_span_id"] is None

    def test_head_sampling_honored_downstream(self):
        tr = Tracer()
        # a propagated UNSAMPLED context silences every tracepoint below
        with tr.activate(SpanContext("ab" * 16, "cd" * 8, False)):
            with tr.span("quiet") as sp:
                assert sp is None
        assert tr.recent() == []
        # a SAMPLED context joins the remote trace with correct parentage
        with tr.activate(SpanContext("ab" * 16, "cd" * 8, True)):
            with tr.span("joined") as sp:
                assert sp.trace_id == "ab" * 16
                assert sp.parent_span_id == "cd" * 8

    def test_unsampled_root_silences_descendants(self):
        # a negative head decision at the root must install a not-sampled
        # context: nested tracepoints follow it instead of drawing their
        # own decisions (which would record orphan bottom-half trees)
        tr = Tracer(sample_every=2)
        for _ in range(6):
            with tr.span("root") as root:
                with tr.span("child") as child:
                    assert (child is None) == (root is None)
        names = [s["name"] for s in tr.recent()]
        assert names.count("root") == 3
        assert names.count("child") == 3

    def test_lock_free_sampler_is_exact_under_threads(self):
        import threading

        tr = Tracer(capacity=100_000, sample_every=10)
        n_threads, per_thread = 8, 1000

        def run():
            for _ in range(per_thread):
                with tr.span("s"):
                    pass

        threads = [threading.Thread(target=run) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the old racy `_counter += 1` could lose increments and oversample;
        # itertools.count hands out each tick exactly once
        assert len(tr.recent(100_000)) == n_threads * per_thread // 10

    def test_env_override(self, monkeypatch):
        from m3_tpu.utils.trace import _env_sample

        monkeypatch.setenv("M3_TPU_TRACE_SAMPLE", "0")
        assert _env_sample() == (1, False)
        monkeypatch.setenv("M3_TPU_TRACE_SAMPLE", "7")
        assert _env_sample() == (7, True)
        monkeypatch.delenv("M3_TPU_TRACE_SAMPLE")
        assert _env_sample() == (1, True)


def _local_api(tmp_path, n_shards=2):
    from m3_tpu.query.api import CoordinatorAPI
    from m3_tpu.storage.database import Database
    from m3_tpu.storage.options import DatabaseOptions

    db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=n_shards))
    db.create_namespace("default")
    db.open(START)
    return db, CoordinatorAPI(db)


class TestQueryStatsAndSlowLog:
    def test_envelope_stats_and_slow_query_ring(self, tmp_path):
        querystats.clear()
        db, api = _local_api(tmp_path)
        port = api.serve(port=0)
        # this test pins the FLOOR admission path; serve() armed the
        # adaptive p99 bar against the suite-global request histogram,
        # which other tests may already have filled past min_count —
        # disarm it here (the adaptive path has its own virtual-clock
        # test below)
        querystats.set_adaptive_source(None)
        try:
            for j in range(20):
                db.write_tagged("default", b"m", [(b"k", b"v")],
                                START + j * NS, float(j))
            db.flush_all()  # flushed data so the read decodes streams
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/v1/query_range?query=m"
                f"&start={START // NS}&end={START // NS + 60}&step=15",
                timeout=10).read())
            st = doc["stats"]
            assert st["query"] == "m"
            assert st["series_matched"] >= 1
            assert st["blocks_read"] >= 1
            assert st["bytes_decoded"] > 0
            assert st["decode_rungs"]  # which rung served is attributed
            assert "read_many" in st["stages_ms"]
            assert st["duration_ms"] > 0
            slow = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/slow_queries",
                timeout=10).read())
            assert any(q["query"] == "m" for q in slow["queries"])
        finally:
            api.shutdown()
            db.close()

    def test_threshold_filters(self):
        querystats.clear()
        querystats.set_threshold_ms(10_000)
        try:
            st = querystats.start(query="cheap")
            querystats.finish(st)
            assert querystats.slow_queries() == []
        finally:
            querystats.set_threshold_ms(0)
        st = querystats.start(query="kept")
        querystats.finish(st)
        assert any(q["query"] == "kept" for q in querystats.slow_queries())


class TestDebugTraceToggle:
    def test_post_toggle(self, tmp_path):
        db, api = _local_api(tmp_path)
        port = api.serve(port=0)
        tracer = trace.default_tracer()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/debug/traces",
                data=json.dumps({"enabled": False, "sample_every": 3}).encode(),
                method="POST")
            doc = json.loads(urllib.request.urlopen(req, timeout=10).read())
            assert doc == {"enabled": False, "sample_every": 3}
            assert tracer.enabled is False and tracer.sample_every == 3
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/debug/traces",
                data=json.dumps({"enabled": True, "sample_every": 1}).encode(),
                method="POST")
            urllib.request.urlopen(req, timeout=10).read()
            assert tracer.enabled is True and tracer.sample_every == 1
        finally:
            tracer.enabled = True
            tracer.sample_every = 1
            api.shutdown()
            db.close()


class TestSelfMonitoring:
    def test_self_scrape_answers_promql_p99(self, tmp_path):
        from m3_tpu.utils import selfscrape

        db, api = _local_api(tmp_path)
        port = api.serve(port=0)
        try:
            reg = MetricsRegistry()
            s = reg.root_scope("probe")
            # a distribution whose p99 lands in the (0.25, 0.5] bucket:
            # rank 99 of 100 falls among the 0.3s observations
            for _ in range(10):
                s.observe("lat_seconds", 0.01)
            for _ in range(90):
                s.observe("lat_seconds", 0.3)
            assert selfscrape.ensure_namespace(db)
            n = selfscrape.scrape_once(db, reg, now_ns=START + 30 * NS)
            assert n > 0
            q = ("histogram_quantile(0.99,probe_lat_seconds_bucket)"
                 f"&time={START // NS + 30}&namespace=_m3_system")
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/v1/query?query={q}",
                timeout=10).read())
            assert doc["status"] == "success"
            [res] = doc["data"]["result"]
            p99 = float(res["value"][1])
            assert 0.25 <= p99 <= 0.5, p99
        finally:
            api.shutdown()
            db.close()

    def test_self_monitor_tick(self, tmp_path):
        from m3_tpu.utils.selfscrape import SelfMonitor

        db, _api = _local_api(tmp_path)
        try:
            clock = [0.0]
            mon = SelfMonitor(db, interval_s=10.0, clock=lambda: clock[0])
            assert mon.enabled
            clock[0] = 11.0
            assert mon.maybe_scrape(now_ns=START + NS) > 0
            assert mon.maybe_scrape(now_ns=START + NS) == 0  # interval gate
            clock[0] = 22.0
            assert mon.maybe_scrape(now_ns=START + 2 * NS) > 0
        finally:
            db.close()


class TestTwoNodeFanoutTrace:
    """The acceptance-criteria path: coordinator -> client session ->
    two dbnode HTTP servers, one stitched trace."""

    @pytest.fixture
    def cluster(self, tmp_path):
        from m3_tpu.client.cluster_db import ClusterDatabase
        from m3_tpu.client.http_conn import HTTPNodeConnection
        from m3_tpu.client.session import Session
        from m3_tpu.cluster import placement as pl
        from m3_tpu.cluster.kv import KVStore
        from m3_tpu.cluster.placement import Instance, initial_placement
        from m3_tpu.cluster.topology import ConsistencyLevel, TopologyMap
        from m3_tpu.query.api import CoordinatorAPI
        from m3_tpu.services.dbnode import DBNodeService

        kv = KVStore()
        p = initial_placement(
            [Instance("node0", isolation_group="g0"),
             Instance("node1", isolation_group="g1")],
            n_shards=4, replica_factor=1,
        )
        for inst in p.instances.values():
            p = pl.mark_available(p, inst.id)
        pl.store_placement(kv, p)
        nodes = {}
        for nid in ("node0", "node1"):
            svc = DBNodeService(
                {"db": {"path": str(tmp_path / nid), "n_shards": 4,
                        "namespaces": [{"name": "default"}]},
                 "cluster": {"instance_id": nid}},
                kv=kv,
            )
            svc.db.open(START)
            svc.sync_placement()
            node_port = svc.api.serve(host="127.0.0.1", port=0)

            def set_endpoint(cur, nid=nid, port=node_port):
                cur.instances[nid].endpoint = f"http://127.0.0.1:{port}"
                return cur

            pl.cas_update_placement(kv, set_endpoint)
            nodes[nid] = svc
        p, _ = pl.load_placement(kv)
        conns = {iid: HTTPNodeConnection(inst.endpoint)
                 for iid, inst in p.instances.items()}
        session = Session(TopologyMap(p), conns,
                          write_consistency=ConsistencyLevel.ONE,
                          read_consistency=ConsistencyLevel.ONE)
        cdb = ClusterDatabase(session)
        api = CoordinatorAPI(cdb)
        coord_port = api.serve(port=0)
        yield nodes, cdb, api, coord_port
        api.shutdown()
        for svc in nodes.values():
            svc.api.shutdown()
            svc.db.close()

    def test_stitched_cross_node_trace(self, cluster):
        nodes, cdb, api, port = cluster
        trace.default_tracer().clear()
        # spread series across both nodes, flushed so reads hit the
        # fileset -> decode-rung path
        for i in range(32):
            cdb.write_tagged("default", b"m", [(b"i", b"%02d" % i)],
                             START + NS, float(i))
        for svc in nodes.values():
            svc.db.flush_all()
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v1/query_range?query=m"
            f"&start={START // NS}&end={START // NS + 60}&step=15",
            timeout=10)
        resp.read()
        trace_id = resp.headers["M3-Trace-Id"]
        assert trace_id and len(trace_id) == 32
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces?trace_id={trace_id}",
            timeout=10).read())
        assert doc["trace_id"] == trace_id
        spans = doc["spans"]
        assert spans and all(s["trace_id"] == trace_id for s in spans)
        names = [s["name"] for s in spans]
        for expected in (trace.STAGE_REQUEST, trace.STAGE_EVAL,
                         trace.SESSION_FETCH, trace.DBNODE_HANDLE,
                         trace.READ_MANY, trace.STAGE_DECODE_HOST):
            assert expected in names, f"missing {expected} in {names}"
        # one batched /read_batch per node -> two dbnode read spans, each
        # parented by the coordinator's session fetch span
        fetch = [s for s in spans if s["name"] == trace.SESSION_FETCH]
        assert len(fetch) == 1
        node_reads = [s for s in spans if s["name"] == trace.DBNODE_HANDLE
                      and s.get("tags", {}).get("path") == "/read_batch"]
        assert len(node_reads) == 2
        for s in node_reads:
            assert s["parent_span_id"] == fetch[0]["span_id"]
        # ONE stitched tree: every span hangs off the single request root
        tree = doc["tree"]
        assert len(tree) == 1 and tree[0]["name"] == trace.STAGE_REQUEST

        def count(node):
            return 1 + sum(count(c) for c in node["children"])

        assert count(tree[0]) == len(spans)

    def test_seam_histograms_on_metrics(self, cluster):
        nodes, cdb, api, port = cluster
        cdb.write_tagged("default", b"h", [(b"k", b"v")], START + NS, 1.0)
        _ = cdb.namespaces["default"].read_many([b"x"], START, START + NS)
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        types, samples = parse_exposition(text)
        for fam in ("db_write_seconds", "db_read_many_seconds",
                    "session_host_call_seconds", "dbnode_handle_seconds"):
            assert types.get(fam) == "histogram", fam
            assert any(k[0] == fam + "_bucket" for k in samples), fam
            assert any(k[0] == fam + "_count" for k in samples), fam
            assert any(k[0] == fam + "_sum" for k in samples), fam


class TestConsensusSeamHistogram:
    def test_append_histogram_and_commit_counter(self):
        # a 3-node virtual-clock raft plane: replication drives the
        # append-handling histogram and the commit counter (the
        # submit->majority-commit histogram rides KvdServer._propose on
        # the same path)
        from m3_tpu.cluster.consensus import LocalRaftCluster

        reg = default_registry()
        before_append = reg.histograms[("consensus.append_seconds", ())].count
        before_commits = reg.counters[("consensus.commits", ())].value
        cluster = LocalRaftCluster(
            ["a", "b", "c"], lambda nid: (lambda idx, cmd: {"ok": True}))
        assert cluster.run_until(
            lambda: any(n.role == "leader" for n in cluster.nodes.values()))
        cluster.submit_and_commit(b"x")
        after_append = reg.histograms[("consensus.append_seconds", ())].count
        after_commits = reg.counters[("consensus.commits", ())].value
        assert after_append > before_append
        assert after_commits > before_commits
        types, samples = parse_exposition(reg.render_prometheus().decode())
        assert types.get("consensus_append_seconds") == "histogram"
        # the commit seam is pre-registered at import, so its
        # _bucket/_sum/_count exposition is present from process start
        # (observations come from RaftNode.wait on live planes)
        assert types.get("consensus_commit_seconds") == "histogram"
        assert any(k[0] == "consensus_commit_seconds_bucket"
                   for k in samples)


# ---------------------------------------------------------------------------
# PR-6 introspection plane: exemplars, EXPLAIN/ANALYZE, exporter, p99 bar
# ---------------------------------------------------------------------------


def _strip_exemplars(text: str) -> tuple[str, dict]:
    """Split OpenMetrics text into (plain exposition, exemplars keyed by
    the full sample-line prefix). Drops the # EOF terminator."""
    plain: list[str] = []
    exemplars: dict[str, tuple[str, float]] = {}
    for line in text.splitlines():
        if line == "# EOF":
            continue
        if " # {" in line:
            base, _, ex = line.partition(" # ")
            m = re.match(r'\{trace_id="([^"]+)"\} ([^ ]+) ', ex + " ")
            assert m, f"malformed exemplar: {line!r}"
            exemplars[base[: base.rfind(" ")]] = (m.group(1),
                                                 float(m.group(2)))
            plain.append(base)
        else:
            plain.append(line)
    return "\n".join(plain) + "\n", exemplars


class TestExemplars:
    def test_openmetrics_exemplar_round_trip(self):
        reg = MetricsRegistry()
        s = reg.root_scope("seam")
        handle = s.histogram_handle("hot_seconds")
        trace.default_tracer().clear()
        with trace.span("req") as sp:
            s.observe("lat_seconds", 0.3)      # Scope.observe path
            handle(0.0021)                     # hot-path closure path
        s.observe("lat_seconds", 0.4)          # OUTSIDE a trace: no exemplar
        text = reg.render_openmetrics().decode()
        assert text.endswith("# EOF\n")
        plain, exemplars = _strip_exemplars(text)
        # base exposition (exemplars stripped) still parses strictly and
        # matches the Prometheus render byte-for-byte
        types, samples = parse_exposition(plain)
        assert types["seam_lat_seconds"] == "histogram"
        assert plain == reg.render_prometheus().decode()
        # both entry points pinned this trace's id to the bucket they hit
        by_metric = {}
        for prefix, (tid, val) in exemplars.items():
            by_metric.setdefault(prefix.split("{")[0], []).append((tid, val))
        assert any(tid == sp.trace_id and val == 0.3
                   for tid, val in by_metric["seam_lat_seconds_bucket"])
        assert any(tid == sp.trace_id and val == 0.0021
                   for tid, val in by_metric["seam_hot_seconds_bucket"])
        # the 0.4 observation landed in a different bucket than 0.3 and
        # carried no trace: its bucket must have NO exemplar
        import bisect as _bisect

        from m3_tpu.utils.instrument import DEFAULT_BUCKETS
        b_03 = _bisect.bisect_left(DEFAULT_BUCKETS, 0.3)
        b_04 = _bisect.bisect_left(DEFAULT_BUCKETS, 0.4)
        if b_03 != b_04:  # (they do differ: 0.3 <= 2^-2 < 0.4 <= 2^-1)
            vals = [v for _t, v in by_metric["seam_lat_seconds_bucket"]]
            assert 0.4 not in vals

    def test_unsampled_trace_pins_no_exemplar(self):
        from m3_tpu.utils.trace import SpanContext

        reg = MetricsRegistry()
        s = reg.root_scope("seam")
        tr = trace.default_tracer()
        with tr.activate(SpanContext("ab" * 16, "cd" * 8, False)):
            s.observe("lat_seconds", 0.1)
        assert b"# {" not in reg.render_openmetrics()


class TestExplain:
    def test_plan_mode_local(self, tmp_path):
        from m3_tpu.query import explain as explain_mod

        explain_mod.clear()
        db, api = _local_api(tmp_path)
        port = api.serve(port=0)
        try:
            for j in range(10):
                db.write_tagged("default", b"pm", [(b"k", b"v")],
                                START + j * NS, float(j))
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/v1/query_range"
                f"?query=sum(rate(pm[1m]))&start={START // NS}"
                f"&end={START // NS + 60}&step=15&explain=plan",
                timeout=10).read())
            plan = doc["explain"]
            assert plan["mode"] == "plan"
            [root] = plan["tree"]
            assert root["node"] == "aggregate" and root["detail"] == "sum"
            [rate] = root["children"]
            assert rate["node"] == "range_fn" and rate["detail"] == "rate()"
            [sel] = rate["children"]
            assert sel["node"] == "selector"
            assert "pm" in sel["detail"] and "[60s]" in sel["detail"]
            # plan mode carries structure only, no timings
            assert "duration_ms" not in root
            # the record also landed in the /debug/explain ring
            ring = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/explain",
                timeout=10).read())
            assert any(p.get("query") == "sum(rate(pm[1m]))"
                       for p in ring["plans"])
        finally:
            api.shutdown()
            db.close()

    def test_bad_explain_mode_is_an_error(self, tmp_path):
        db, api = _local_api(tmp_path)
        try:
            status, _ctype, payload, _h = api.handle(
                "GET", "/api/v1/query_range",
                {"query": ["x"], "start": ["0"], "end": ["60"],
                 "step": ["15"], "explain": ["bogus"]}, b"")
            assert status == 400
            assert b"explain" in payload
        finally:
            api.shutdown()
            db.close()


class TestExplainAnalyzeFanout(TestTwoNodeFanoutTrace):
    """EXPLAIN ANALYZE over the 2-node fan-out topology: ONE stitched
    plan tree whose per-stage timings, dispatch rungs, and per-node legs
    line up with the envelope stats — and whose exemplars link back to
    the stitched trace (the acceptance-criteria path)."""

    def test_stitched_plan_tree_parity(self, cluster):
        nodes, cdb, api, port = cluster
        trace.default_tracer().clear()
        for i in range(32):
            cdb.write_tagged("default", b"m", [(b"i", b"%02d" % i)],
                             START + NS, float(i))
        for svc in nodes.values():
            svc.db.flush_all()
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v1/query_range"
            f"?query=sum(rate(m[2m]))&start={START // NS}"
            f"&end={START // NS + 60}&step=15&explain=analyze",
            timeout=10)
        doc = json.loads(resp.read())
        trace_id = resp.headers["M3-Trace-Id"]
        stats = doc["stats"]
        plan = doc["explain"]
        assert plan["mode"] == "analyze"
        assert plan["trace_id"] == trace_id == stats["trace_id"]
        # ONE stitched tree: sum -> rate -> selector -> one rpc leg/node
        [root] = plan["tree"]
        assert root["node"] == "aggregate"
        [rate] = root["children"]
        assert rate["node"] == "range_fn"
        [sel] = rate["children"]
        assert sel["node"] == "selector"
        legs = [c for c in sel["children"] if c["node"] == "rpc"]
        assert {leg["detail"] for leg in legs} == {"node0", "node1"}
        # per-stage timings nest: child wall time within parent's, every
        # stage within the envelope total
        for node, child in ((root, rate), (rate, sel)):
            assert child["duration_ms"] <= node["duration_ms"] + 0.5
        assert root["duration_ms"] <= stats["duration_ms"] + 0.5
        # node legs fly CONCURRENTLY on the pipelined fan-out
        # (storage/pipeline.py), so their SUM may exceed the selector
        # stage's wall time — each individual leg still nests within it
        for leg in legs:
            assert leg["duration_ms"] <= sel["duration_ms"] + 0.5
        assert sum(leg.get("rows", 0) for leg in legs) == 32
        # dispatch-rung attribution: the selector stage carries exactly
        # the rungs the envelope reports (decode happened ON THE NODES;
        # the counters rode the /read_batch stats envelope back)
        assert sel["rungs"] == stats["decode_rungs"]
        assert sum(sel["rungs"].values()) >= 2  # both nodes decoded
        assert sel["series"] == stats["series_matched"] == 32
        assert sel["bytes"] == stats["bytes_decoded"] > 0
        # /debug/explain?trace_id= finds the same plan
        ring = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/explain?trace_id={trace_id}",
            timeout=10).read())
        assert len(ring["plans"]) == 1

    def test_exemplar_links_to_stitched_trace(self, cluster):
        nodes, cdb, api, port = cluster
        trace.default_tracer().clear()
        for i in range(8):
            cdb.write_tagged("default", b"ex", [(b"i", b"%02d" % i)],
                             START + NS, float(i))
        for svc in nodes.values():
            svc.db.flush_all()
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v1/query_range?query=ex"
            f"&start={START // NS}&end={START // NS + 60}&step=15",
            timeout=10)
        resp.read()
        trace_id = resp.headers["M3-Trace-Id"]
        # the coordinator's request histogram pinned this trace as the
        # exemplar of the bucket the query's latency landed in
        om = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics?format=openmetrics",
            timeout=10).read().decode()
        _plain, exemplars = _strip_exemplars(om)
        req_ex = {tid for prefix, (tid, _v) in exemplars.items()
                  if prefix.startswith("coordinator_request_seconds_bucket")}
        assert trace_id in req_ex
        # the decode seam ON THE STORAGE NODES pinned the same trace
        # (propagated traceparent), so a node's p99 decode bucket links
        # to the same stitched tree
        node_ex = set()
        for svc in nodes.values():
            _status, payload, ctype = svc.api.handle(
                "GET", "/metrics", {"format": ["openmetrics"]}, b"")
            assert ctype.startswith("application/openmetrics-text")
            _p, node_exemplars = _strip_exemplars(payload.decode())
            node_ex |= {tid for prefix, (tid, _v) in node_exemplars.items()
                        if prefix.startswith("decode_batch_seconds_bucket")}
        assert trace_id in node_ex
        # ...and that trace id resolves via /debug/traces to the stitched
        # cross-process tree for THIS query
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces?trace_id={trace_id}",
            timeout=10).read())
        assert doc["count"] > 0
        names = [s["name"] for s in doc["spans"]]
        assert trace.STAGE_REQUEST in names
        assert trace.STAGE_DECODE_HOST in names
        assert len(doc["tree"]) == 1


class TestTelemetryExporter:
    def _tracer_with_spans(self, n):
        from m3_tpu.utils.trace import Tracer

        tr = Tracer()
        for i in range(n):
            with tr.span(f"s{i}"):
                pass
        return tr

    def test_file_sink_drain_and_cursor(self, tmp_path):
        from m3_tpu.utils.export import FileSink, TelemetryExporter

        reg = MetricsRegistry()
        reg.root_scope("svc").counter("boot")
        tr = self._tracer_with_spans(3)
        path = str(tmp_path / "out.jsonl")
        exp = TelemetryExporter("dbnode", FileSink(path), registry=reg,
                                tracer=tr)
        assert exp.tick() == 1
        with tr.span("later"):
            pass
        assert exp.tick() == 1
        lines = [json.loads(l) for l in open(path).read().splitlines()]
        assert len(lines) == 2
        assert lines[0]["resource"]["service.name"] == "dbnode"
        # cursor semantics: each span ships exactly once
        assert [s["name"] for s in lines[0]["scopeSpans"]] == \
            ["s0", "s1", "s2"]
        assert [s["name"] for s in lines[1]["scopeSpans"]] == ["later"]
        assert any(m["name"] == "svc.boot"
                   for m in lines[0]["scopeMetrics"])
        # histograms ship with bounds+counts (the collector can rebuild
        # quantiles)
        reg.root_scope("svc").observe("lat_seconds", 0.2)
        exp.tick()
        last = json.loads(open(path).read().splitlines()[-1])
        [h] = [m for m in last["scopeMetrics"]
               if m["name"] == "svc.lat_seconds"]
        assert h["type"] == "histogram" and h["count"] == 1

    def test_drop_counter_under_full_queue(self, tmp_path):
        from m3_tpu.utils.export import FileSink, TelemetryExporter

        class DeadSink:
            def ship(self, payload):
                raise OSError("collector down")

        reg = MetricsRegistry()
        tr = self._tracer_with_spans(1)
        exp = TelemetryExporter("agg", DeadSink(), registry=reg, tracer=tr,
                                queue_max=2)
        for i in range(5):
            with tr.span(f"tick{i}"):
                pass
            exp.tick()
        counters, gauges, _t, _h = reg.snapshot()
        c = {k[0]: v for (k, v) in counters.items()}
        # queue bounded at 2: 5 payloads enqueued, 3 dropped oldest-first,
        # every failed ship counted — the hot path never blocked
        assert c["exporter.svc.dropped_payloads"] == 3
        assert c["exporter.svc.ship_errors"] == 5
        assert c["exporter.svc.dropped_spans"] >= 3
        assert exp.queue_depth == 2
        assert gauges[("exporter.svc.queue_depth",
                       (("service", "agg"),))] == 2
        # collector recovers: the surviving queue drains in order
        path = str(tmp_path / "out.jsonl")
        exp.sink = FileSink(path)
        assert exp.tick() >= 2
        assert exp.queue_depth == 0

    def test_exporter_from_config(self, tmp_path, monkeypatch):
        from m3_tpu.utils.export import (
            FileSink,
            HTTPSink,
            exporter_from_config,
        )

        assert exporter_from_config({}, "kvd") is None
        exp = exporter_from_config(
            {"export": {"file": str(tmp_path / "f"), "interval_s": 1.5,
                        "queue_max": 7}}, "coordinator")
        assert isinstance(exp.sink, FileSink)
        assert exp.interval_s == 1.5 and exp.queue_max == 7
        exp = exporter_from_config(
            {"export": {"endpoint": "http://127.0.0.1:9/v1"}}, "dbnode")
        assert isinstance(exp.sink, HTTPSink)
        # env overrides config, and arms config-less processes (kvd)
        monkeypatch.setenv("M3_TPU_EXPORT_FILE", str(tmp_path / "env"))
        exp = exporter_from_config(None, "kvd")
        assert isinstance(exp.sink, FileSink)

    def test_dbnode_service_registers_exporter(self, tmp_path, monkeypatch):
        from m3_tpu.services.dbnode import DBNodeService

        out = tmp_path / "tel.jsonl"
        monkeypatch.setenv("M3_TPU_EXPORT_FILE", str(out))
        svc = DBNodeService({"db": {"path": str(tmp_path / "db"),
                                    "n_shards": 2}})
        try:
            assert svc.exporter is not None
            svc.db.open(START)
            svc.db.write_tagged("default", b"m", [(b"k", b"v")],
                                START + NS, 1.0)
            svc.exporter.tick()
            lines = out.read_text().splitlines()
            assert lines
            doc = json.loads(lines[0])
            assert doc["resource"]["service.name"] == "dbnode"
            assert any(m["name"] == "db.write_seconds"
                       for m in doc["scopeMetrics"])
        finally:
            svc.shutdown()


class TestAdaptiveSlowQueryBar:
    def test_p99_admission_with_virtual_clock(self):
        querystats.clear()
        reg = MetricsRegistry()
        s = reg.root_scope("coordinator")
        # 50/50 split at 0.01s and 1.0s: interpolated p99 lands just
        # under 1.0s in the (0.5, 1.0] bucket
        for _ in range(50):
            s.observe("request_seconds", 0.01)
        for _ in range(50):
            s.observe("request_seconds", 1.0)
        querystats.set_adaptive_source(
            lambda: reg.histograms.get(("coordinator.request_seconds", ())))
        try:
            bar = querystats.threshold_s()
            assert 0.5 <= bar <= 1.0
            clock = [0.0]

            def run(query: str, duration_s: float):
                st = querystats.start(query=query, clock=lambda: clock[0])
                clock[0] += duration_s
                querystats.finish(st)

            run("below-bar", 0.05)   # would have been kept at floor=0
            run("above-bar", 5.0)
            kept = {q["query"] for q in querystats.slow_queries()}
            assert "above-bar" in kept and "below-bar" not in kept
            # duration stamped from the virtual clock, not wall time
            [rec] = [q for q in querystats.slow_queries()
                     if q["query"] == "above-bar"]
            assert rec["duration_ms"] == pytest.approx(5000.0)
        finally:
            querystats.set_adaptive_source(None)
            querystats.clear()

    def test_floor_and_thin_histogram_fallback(self):
        querystats.clear()
        reg = MetricsRegistry()
        s = reg.root_scope("coordinator")
        for _ in range(3):  # far below min_count: p99 not armed yet
            s.observe("request_seconds", 0.001)
        querystats.set_adaptive_source(
            lambda: reg.histograms.get(("coordinator.request_seconds", ())))
        try:
            # fallback: the env floor (0) governs alone -> everything kept
            assert querystats.threshold_s() == 0.0
            clock = [0.0]
            st = querystats.start(query="thin", clock=lambda: clock[0])
            clock[0] += 0.002
            querystats.finish(st)
            assert any(q["query"] == "thin"
                       for q in querystats.slow_queries())
            # the floor RAISES the armed bar, never lowers it
            for _ in range(100):
                s.observe("request_seconds", 0.001)
            querystats.set_threshold_ms(50.0)
            assert querystats.threshold_s() == pytest.approx(0.05)
        finally:
            querystats.set_threshold_ms(0.0)
            querystats.set_adaptive_source(None)
            querystats.clear()

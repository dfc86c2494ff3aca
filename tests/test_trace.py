"""Tracepoints + span recorder (SURVEY §5 tracing role)."""

from __future__ import annotations

import json
import urllib.request

from m3_tpu.utils import trace
from m3_tpu.utils.trace import Tracer


class TestTracer:
    def test_nesting_and_ring(self):
        tr = Tracer(capacity=8)
        with tr.span("outer"):
            with tr.span("inner", shard=3):
                pass
        spans = tr.recent()
        assert [s["name"] for s in spans] == ["inner", "outer"]
        assert spans[0]["parent"] == "outer"
        assert spans[0]["tags"] == {"shard": 3}
        assert spans[1]["parent"] is None
        for _ in range(20):
            with tr.span("x"):
                pass
        assert len(tr.recent(100)) == 8  # bounded ring

    def test_sampling_and_disable(self):
        tr = Tracer(sample_every=2)
        for _ in range(10):
            with tr.span("s"):
                pass
        assert len(tr.recent()) == 5
        tr.enabled = False
        with tr.span("off"):
            pass
        assert all(s["name"] != "off" for s in tr.recent())


class TestEndToEndSpans:
    def test_query_path_produces_spans(self, tmp_path):
        from m3_tpu.query.api import CoordinatorAPI
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        trace.default_tracer().clear()
        START = 1_600_000_000_000_000_000
        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=2))
        db.create_namespace("default")
        db.open(START)
        api = CoordinatorAPI(db)
        port = api.serve(port=0)
        try:
            for j in range(10):
                db.write_tagged("default", b"m", [(b"k", b"v")],
                                START + j * 10**9, float(j))
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/v1/query_range?query=m"
                f"&start={START // 10**9}&end={START // 10**9 + 60}&step=15",
                timeout=10,
            ).read()
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/traces", timeout=10).read())
            names = [s["name"] for s in doc["spans"]]
            assert trace.STAGE_EVAL in names
            assert trace.INDEX_QUERY in names
            # index query nests under the engine's eval stage, inside
            # the resolver's query_ids stage
            idx = next(s for s in doc["spans"] if s["name"] == trace.INDEX_QUERY)
            assert idx["parent"] == trace.STAGE_QUERY_IDS
            by_id = {s["span_id"]: s for s in doc["spans"]}
            assert by_id[idx["parent_span_id"]]["parent"] == trace.STAGE_EVAL
        finally:
            api.shutdown()
            db.close()

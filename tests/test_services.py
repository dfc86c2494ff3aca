"""Service assembly tests: config loading, coordinator/dbnode/aggregator
lifecycle, node API, and the leader/follower flush control."""

import base64
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from m3_tpu.cluster.kv import KVStore
from m3_tpu.services.aggregator import AggregatorService, encode_metric
from m3_tpu.services.coordinator import CoordinatorService
from m3_tpu.services.dbnode import DBNodeService
from m3_tpu.utils.config import expand_env, load_config, parse_yaml
from m3_tpu.utils.instrument import Logger, MetricsRegistry

SEC = 10**9
START = 1_599_998_400_000_000_000


class TestConfig:
    def test_yaml_subset(self):
        doc = parse_yaml(
            "a: 1\nb:\n  c: hello  # comment\n  d: true\nlist:\n  - x\n  - y\n"
            "maps:\n  - name: n1\n    port: 1\n  - name: n2\n    port: 2\n"
        )
        assert doc == {
            "a": 1,
            "b": {"c": "hello", "d": True},
            "list": ["x", "y"],
            "maps": [{"name": "n1", "port": 1}, {"name": "n2", "port": 2}],
        }

    def test_env_expansion(self):
        assert expand_env("p: ${FOO:fallback}", {}) == "p: fallback"
        assert expand_env("p: ${FOO:fallback}", {"FOO": "real"}) == "p: real"
        with pytest.raises(KeyError):
            expand_env("p: ${NO_DEFAULT}", {})

    def test_sample_configs_parse(self):
        import pathlib

        root = pathlib.Path(__file__).resolve().parents[1] / "config"
        for f in ("coordinator.yml", "dbnode.yml", "aggregator.yml"):
            doc = load_config(str(root / f))
            assert isinstance(doc, dict) and doc


class TestInstrument:
    def test_scope_and_prometheus(self):
        reg = MetricsRegistry()
        s = reg.root_scope("svc").subscope("api", endpoint="write")
        s.counter("requests")
        s.counter("requests", 2)
        s.gauge("inflight", 5)
        with s.timer("latency"):
            pass
        text = reg.render_prometheus().decode()
        assert 'svc_api_requests{endpoint="write"} 3' in text
        assert 'svc_api_inflight{endpoint="write"} 5' in text
        assert "svc_api_latency_count" in text
        assert "# TYPE svc_api_requests counter" in text

    def test_logger_json(self, capsys):
        import io

        buf = io.StringIO()
        log = Logger("t", stream=buf).with_fields(node="n1")
        log.info("hello", x=1)
        log.debug("hidden")
        rec = json.loads(buf.getvalue())
        assert rec["msg"] == "hello" and rec["node"] == "n1" and rec["x"] == 1
        assert buf.getvalue().count("\n") == 1  # debug filtered


class TestDBNodeService:
    def test_node_api_write_read_metadata(self, tmp_path):
        svc = DBNodeService({
            "db": {"path": str(tmp_path / "n1"), "n_shards": 4,
                   "namespaces": [{"name": "default"}]},
        })
        svc.db.open(START)
        port = svc.api.serve(host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{port}"
        try:
            body = json.dumps({
                "namespace": "default", "metric": "cpu",
                "tags": {"host": "h1"}, "timestamp_ns": START + SEC,
                "value": 4.5,
            }).encode()
            req = urllib.request.Request(f"{base}/write", data=body, method="POST")
            with urllib.request.urlopen(req) as r:
                assert json.loads(r.read())["ok"]
            from m3_tpu.utils.ident import tags_to_id

            sid = base64.b64encode(tags_to_id(b"cpu", [(b"host", b"h1")])).decode()
            with urllib.request.urlopen(
                f"{base}/read?namespace=default&series_id={sid}"
                f"&start_ns={START}&end_ns={START + 3600 * SEC}"
            ) as r:
                dps = json.loads(r.read())
            assert dps == [[START + SEC, 4.5]]
            # flush then fetch block metadata (repair surface)
            svc.db.flush_all()
            shard = svc.db.namespaces["default"].shard_for(
                base64.b64decode(sid))
            bs = shard.flushed_block_starts[0]
            with urllib.request.urlopen(
                f"{base}/blocks/metadata?namespace=default"
                f"&shard={shard.shard_id}&block_start={bs}"
            ) as r:
                md = json.loads(r.read())
            assert sid in md and md[sid]["size"] > 0
            with urllib.request.urlopen(
                f"{base}/blocks/stream?namespace=default"
                f"&shard={shard.shard_id}&block_start={bs}&series_id={sid}"
            ) as r:
                st = json.loads(r.read())
            assert len(base64.b64decode(st["stream"])) == md[sid]["size"]
        finally:
            svc.api.shutdown()
            svc.db.close()


class TestAggregatorService:
    def test_leader_follower_flush(self, tmp_path):
        kv = KVStore()
        cfg = {
            "instance_id": "a1", "n_shards": 2,
            "rules": {"mapping": [
                {"name": "m", "filter": "__name__:*", "policies": ["10s:2d"]}
            ]},
        }
        leader = AggregatorService({**cfg, "instance_id": "a1"}, kv=kv)
        follower = AggregatorService({**cfg, "instance_id": "a2"}, kv=kv)
        payload = encode_metric(1, b"c", [(b"__name__", b"c")], START + SEC, 5.0)
        leader._on_message(0, payload)
        follower._on_message(0, payload)
        t = START + 60 * SEC
        assert leader.flush_once(t) == 1  # wins election, emits
        assert follower.flush_once(t) == 0  # follower: shadow only
        # leader dies; follower takes over after lease expiry and emits its
        # shadow-aggregated window
        t2 = t + int(30e9)
        assert follower.flush_once(t2) == 1
        leader.shutdown()
        follower.shutdown()


class TestCoordinatorService:
    def test_end_to_end_with_downsampling(self, tmp_path):
        cfg = {
            "db": {"path": str(tmp_path / "db"), "n_shards": 4,
                   "namespace": "default"},
            "http": {"host": "127.0.0.1", "port": 0},
            "rules": {"mapping": [
                {"name": "r", "filter": "__name__:cpu",
                 "policies": ["10s:2d"]}
            ]},
        }
        svc = CoordinatorService(cfg)
        svc.db.open(START)
        port = svc.api.serve(host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{port}"
        try:
            for i in range(4):
                body = json.dumps({
                    "metric": "cpu", "tags": {"h": "1"},
                    "timestamp": (START // SEC) + i * 2, "value": float(i),
                }).encode()
                req = urllib.request.Request(
                    f"{base}/api/v1/json/write", data=body, method="POST")
                urllib.request.urlopen(req).read()
            svc.downsampler.flush(START + 60 * SEC)
            ns_name = "aggregated_10s_2d"
            assert ns_name in svc.db.namespaces
            from m3_tpu.utils.ident import tags_to_id

            dps = svc.db.read(ns_name, tags_to_id(b"cpu", [(b"h", b"1")]),
                              START, START + 60 * SEC)
            assert len(dps) == 1 and dps[0].value == 3.0  # gauge last
            # /metrics endpoint serves prometheus text
            with urllib.request.urlopen(f"{base}/metrics") as r:
                assert r.status == 200
            # /debug/dump serves thread + namespace stats
            with urllib.request.urlopen(f"{base}/debug/dump") as r:
                doc = json.loads(r.read())
            assert "namespaces" in doc and "default" in doc["namespaces"]
        finally:
            svc.api.shutdown()
            svc.db.close()


class TestConfigRegressions:
    def test_list_scalar_with_colon(self):
        # '- 10s:2d' is a scalar, not an inline mapping
        doc = parse_yaml("policies:\n  - 10s:2d\n  - 1m:30d\nm:\n  - k: v\n")
        assert doc["policies"] == ["10s:2d", "1m:30d"]
        assert doc["m"] == [{"k": "v"}]

    def test_empty_flow_collections(self):
        # `rollup: []` used to load as the string "[]" and crash the
        # documented `coordinator -f config/coordinator.yml` start
        assert parse_yaml("a: []\nb: {}\nc: x\n") == \
            {"a": [], "b": {}, "c": "x"}

    def test_shipped_configs_build_their_services(self, tmp_path,
                                                  monkeypatch):
        """Every config under config/ constructs the service it is for
        (constructed, not run: nothing listens, nothing touches jax)."""
        import os

        from m3_tpu.utils.config import load_config

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.setenv("M3_DATA_PATH", str(tmp_path / "data"))
        monkeypatch.chdir(tmp_path)
        svc = CoordinatorService(
            load_config(os.path.join(repo, "config", "coordinator.yml")))
        assert svc.downsampler is not None  # the default-rollup rule
        svc.db.close()
        node = DBNodeService(
            load_config(os.path.join(repo, "config", "dbnode.yml")))
        node.db.close()
        agg = AggregatorService(
            load_config(os.path.join(repo, "config", "aggregator.yml")))
        agg.shutdown()

    def test_same_indent_list_under_key(self):
        doc = parse_yaml("namespaces:\n- name: default\n- name: agg\nk: 1\n")
        assert doc == {"namespaces": [{"name": "default"}, {"name": "agg"}],
                       "k": 1}

    def test_commented_env_ref_ignored(self, tmp_path):
        p = tmp_path / "c.yml"
        p.write_text("a: 1\n# path: ${NOT_SET_ANYWHERE}\n")
        assert load_config(str(p)) == {"a": 1}


class TestAggregatorThreadSafety:
    def test_concurrent_add_and_flush(self):
        from m3_tpu.aggregator.engine import Aggregator
        from m3_tpu.metrics.aggregation import MetricType
        from m3_tpu.metrics.filters import TagFilter
        from m3_tpu.metrics.policy import StoragePolicy
        from m3_tpu.metrics.rules import MappingRule, RuleSet

        rs = RuleSet(mapping_rules=[MappingRule(
            "m", TagFilter.parse("__name__:*"),
            (StoragePolicy.parse("10s:2d"),))])
        agg = Aggregator(rs, buffer_past_ns=0)
        N_THREADS, PER = 4, 500
        errors = []

        def writer(k):
            try:
                for i in range(PER):
                    agg.add(MetricType.COUNTER, f"c{k}".encode(),
                            [(b"__name__", f"c{k}".encode())],
                            START + (i % 50) * SEC, 1.0)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(N_THREADS)]
        for t in threads:
            t.start()
        collected = []
        for _ in range(20):
            collected.extend(agg.flush(START + 3600 * SEC))
            time.sleep(0.002)
        for t in threads:
            t.join()
        collected.extend(agg.flush(START + 7200 * SEC))
        assert not errors
        # conservation under concurrency: every sample is either aggregated
        # exactly once or counted as a late drop (the flush watermark moves
        # ahead of the writers on purpose here) — nothing lost or doubled
        total = sum(m.value for m in collected)
        assert total + agg.num_late_dropped == N_THREADS * PER
        assert agg.num_dropped == 0


class TestInspectTools:
    def test_list_read_verify(self, tmp_path, capsys):
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions
        from m3_tpu.tools import inspect as tools

        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=2))
        db.create_namespace("default")
        db.open(START)
        db.write_tagged("default", b"cpu", [(b"h", b"1")], START + SEC, 7.5)
        db.flush_all()
        db.close()
        root = str(tmp_path / "db" / "data")
        assert tools.main(["list", root, "default"]) == 0
        lines = [json.loads(line) for line in
                 capsys.readouterr().out.strip().splitlines()]
        assert lines and lines[0]["n_series"] == 1
        bs = lines[0]["block_start"]
        shard = lines[0]["shard"]
        assert tools.main(["read", root, "default", str(shard), str(bs)]) == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[0])
        assert doc["tags"] == {"__name__": "cpu", "h": "1"}
        assert doc["datapoints"] == [[START + SEC, 7.5]]
        assert tools.main(["verify", root, "default"]) == 0
        summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert summary == {"filesets": 1, "corrupt": 0}

    def test_verify_detects_corruption(self, tmp_path, capsys):
        import os as _os

        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions
        from m3_tpu.tools import inspect as tools

        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=1))
        db.create_namespace("default")
        db.open(START)
        db.write_tagged("default", b"x", [], START + SEC, 1.0)
        db.flush_all()
        db.close()
        root = str(tmp_path / "db" / "data")
        victim = None
        for dirpath, _dirs, files in _os.walk(root):
            for f in files:
                if f.endswith("-data.db"):
                    victim = _os.path.join(dirpath, f)
        with open(victim, "r+b") as f:
            f.write(b"CORRUPT!")
        assert tools.main(["verify", root, "default"]) == 1
        out = capsys.readouterr().out.strip().splitlines()
        assert json.loads(out[-1])["corrupt"] == 1

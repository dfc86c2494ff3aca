"""Device-compute observability plane (ISSUE 19, utils/compute_stats +
dispatch.jit_tracker).

The contract under test: tracked cache-HIT calls land EXACT execute
wall time in the per-program ledger and the compute_execute_seconds
histogram (fake clock — no tolerance); evictions are counted from the
executable-cache ground truth (a clear-then-retrace is a miss plus an
eviction, never a hit); sig labels and the program table are bounded
with an ``other`` overflow; the /debug/compute surface answers on all
four services (fault-exempt on dbnode, like /debug/profile) and NEVER
initializes a jax backend; the ?explain=analyze ``device`` block is
present and consistent at 1 and 8 virtual mesh devices; and the whole
plane flows through the _m3_system self-scrape.
"""

from __future__ import annotations

import json
import math
import time

import numpy as np
import pytest

from m3_tpu.utils import compute_stats, dispatch
from m3_tpu.utils.instrument import default_registry


NS = 10**9
MIN = 60 * NS
START = 1_599_998_400_000_000_000


@pytest.fixture(autouse=True)
def fresh_ledger():
    compute_stats.reset()
    yield
    compute_stats.reset()


class FakeJit:
    """Stands in for a jax.jit'd callable: a private executable cache
    whose size the test scripts directly."""

    def __init__(self):
        self.n = 0

    def __call__(self):
        return None

    def _cache_size(self):
        return self.n


@pytest.fixture
def clock(monkeypatch):
    """Settable perf_counter: the test moves time, nothing else does.
    Anchored near the real clock so a heartbeat recorded while patched
    doesn't read as a giant stall after the test unpatches."""
    state = {"t": float(math.floor(time.perf_counter()))}
    monkeypatch.setattr(time, "perf_counter", lambda: state["t"])

    def advance(dt: float) -> None:
        state["t"] += dt

    return advance


# ---------------------------------------------------------------------------
# tracker attribution: exact execute/compile seconds under a fake clock
# ---------------------------------------------------------------------------

class TestTrackerAttribution:
    def test_exact_execute_and_compile_seconds(self, clock):
        fn = FakeJit()
        # miss: cache grows across the call; the whole wall is compile
        with dispatch.jit_tracker("fakeop", fn, sig="S1") as tr:
            fn.n = 1
            clock(0.5)
        assert tr.miss is True and tr.seconds == 0.5
        # hit: cache size unchanged; the wall is execute
        with dispatch.jit_tracker("fakeop", fn, sig="S1") as tr:
            clock(0.25)
        assert tr.miss is False and tr.seconds == 0.25

        [row] = compute_stats.debug_payload()["programs"]
        assert row["op"] == "fakeop" and row["sig"] == "S1"
        assert row["calls"] == 2
        assert row["compiles"] == 1
        assert row["compile_seconds_total"] == 0.5
        assert row["execute_calls"] == 1
        assert row["execute_seconds_total"] == 0.25
        assert row["execute_seconds_last"] == 0.25

        # the histogram family is compute_execute_seconds{op,sig}, sum
        # EXACTLY the fake-clock delta
        _c, _g, _t, hists = default_registry().snapshot()
        key = ("compute.execute.seconds", (("op", "fakeop"), ("sig", "S1")))
        bounds, counts, hsum, hcount = hists[key]
        assert hcount == 1 and hsum == 0.25

    def test_eviction_ground_truth_counts_and_retrace_is_a_miss(self, clock):
        fn = FakeJit()
        with dispatch.jit_tracker("evop", fn, sig="S1"):
            fn.n = 1
            clock(0.5)
        # simulate jax.clear_caches(): the executable vanishes between
        # tracked calls
        fn.n = 0
        with dispatch.jit_tracker("evop", fn, sig="S1") as tr:
            fn.n = 1
            clock(0.5)
        assert tr.miss is True  # the re-trace is a miss, not a hit
        payload = compute_stats.debug_payload()
        assert payload["jit_evictions"] == {"evop": 1}
        [row] = payload["programs"]
        assert row["compiles"] == 2 and row["execute_calls"] == 0
        counters, *_ = default_registry().snapshot()
        assert counters[
            ("compute.jit_cache.evictions", (("op", "evop"),))] == 1.0

    def test_raising_call_is_not_attributed(self, clock):
        fn = FakeJit()
        with pytest.raises(RuntimeError):
            with dispatch.jit_tracker("boomop", fn, sig="S"):
                clock(0.5)
                raise RuntimeError("kernel failed")
        assert compute_stats.debug_payload()["programs"] == []

    def test_disarmed_records_nothing(self, clock):
        compute_stats.arm(False)
        fn = FakeJit()
        fn.n = 1
        with dispatch.jit_tracker("offop", fn, sig="S"):
            clock(0.25)
        assert compute_stats.debug_payload()["programs"] == []
        assert compute_stats.debug_payload()["armed"] is False


# ---------------------------------------------------------------------------
# bounded labels and table
# ---------------------------------------------------------------------------

class TestCardinalityBounds:
    def test_sig_label_overflow_folds_to_other(self):
        n = compute_stats._SIG_LABEL_CAP + 6
        for i in range(n):
            compute_stats.record_execute("capop", f"sig{i:03d}", 0.001)
        _c, _g, _t, hists = default_registry().snapshot()
        labels = {dict(tags)["sig"] for (name, tags) in hists
                  if name == "compute.execute.seconds"
                  and dict(tags).get("op") == "capop"}
        assert len(labels) == compute_stats._SIG_LABEL_CAP + 1
        assert "other" in labels
        # a capped sig keeps its own label on repeat calls
        compute_stats.record_execute("capop", "sig000", 0.001)
        # while the TABLE keeps every distinct row until its own cap
        assert len(compute_stats.debug_payload(top_n=1000)["programs"]) == n

    def test_program_table_overflow_folds_to_other(self, monkeypatch):
        monkeypatch.setattr(compute_stats, "_TABLE_CAP", 8)
        for i in range(12):
            compute_stats.record_execute("tblop", f"t{i}", 0.001)
        rows = compute_stats.debug_payload(top_n=1000)["programs"]
        assert len(rows) == 9  # 8 distinct + the shared overflow row
        other = [r for r in rows if r["sig"] == "other"]
        assert len(other) == 1 and other[0]["execute_calls"] == 4

    def test_top_n_ranks_by_execute_time(self):
        compute_stats.record_execute("cold", "s", 0.001)
        compute_stats.record_execute("hot", "s", 5.0)
        [top] = compute_stats.debug_payload(top_n=1)["programs"]
        assert top["op"] == "hot"


# ---------------------------------------------------------------------------
# padding-waste ledger + gauges
# ---------------------------------------------------------------------------

class TestWasteLedger:
    def test_ratio_and_gauges(self):
        compute_stats.record_waste("wsite", "wax", 3, 4)
        assert compute_stats.waste_ratio("wsite", "wax") == 0.25
        compute_stats.record_waste("wsite", "wax", 3, 4)
        assert compute_stats.waste_ratio("wsite", "wax") == 0.25  # cumulative
        # the snapshot hook publishes fresh gauges at every snapshot
        _c, gauges, _t, _h = default_registry().snapshot()
        tags = (("axis", "wax"), ("site", "wsite"))
        assert gauges[("compute.waste.waste_ratio", tags)] == 0.25
        assert gauges[("compute.waste.logical_elements", tags)] == 6.0
        assert gauges[("compute.waste.padded_elements", tags)] == 8.0
        w = compute_stats.debug_payload()["waste"]["wsite/wax"]
        assert w == {"logical": 6, "padded": 8, "waste_ratio": 0.25}

    def test_unrecorded_site_is_none(self):
        assert compute_stats.waste_ratio("nope", "nope") is None


# ---------------------------------------------------------------------------
# device-resident cache providers
# ---------------------------------------------------------------------------

class TestDeviceCaches:
    def test_provider_flows_to_payload_and_gauges(self):
        compute_stats.register_device_cache(
            "unit_cache", lambda: {"entries": 2, "bytes": 640})
        try:
            assert compute_stats.debug_payload()["device_caches"][
                "unit_cache"] == {"entries": 2, "bytes": 640}
            _c, gauges, _t, _h = default_registry().snapshot()
            assert gauges[("compute.device_cache.bytes",
                           (("cache", "unit_cache"),))] == 640.0
        finally:
            del compute_stats._device_caches["unit_cache"]

    def test_broken_provider_never_breaks_the_surface(self):
        def boom():
            raise RuntimeError("provider bug")

        compute_stats.register_device_cache("broken_cache", boom)
        try:
            caches = compute_stats.debug_payload()["device_caches"]
            assert "broken_cache" not in caches
        finally:
            del compute_stats._device_caches["broken_cache"]

    def test_hot_tier_bf16_mirror_bytes(self):
        from m3_tpu.storage.hottier import HotTier

        tier = HotTier(max_bytes=1000)
        tier.put("a", {"precision": "bf16"}, 100)
        tier.put("b", {"precision": "fp64"}, 50)
        assert tier.stats()["bytes"] == 150
        assert tier.stats()["bf16_bytes"] == 100
        # replacing a bf16 entry with full precision releases its share
        tier.put("a", {"precision": "fp64"}, 100)
        assert tier.stats()["bf16_bytes"] == 0
        # LRU: the re-put refreshed "a", so "b" is the eviction victim
        tier.put("c", {"precision": "bf16"}, 900)
        s = tier.stats()
        assert s["entries"] == 2
        assert s["bytes"] == 1000 and s["bf16_bytes"] == 900
        assert s["evictions"] == 1
        tier.clear()
        assert tier.stats()["bytes"] == 0
        assert tier.stats()["bf16_bytes"] == 0
        # the module registered the default tier as a provider on import
        assert "hot_tier" in compute_stats.debug_payload()["device_caches"]

    def test_postings_columns_tracked_and_released_with_segment(self):
        import gc

        from m3_tpu.index import packed
        from m3_tpu.index.segment import Document

        docs = [Document(i, b"s-%04d" % i,
                         [(b"host", b"h%d" % (i % 3))]) for i in range(64)]
        seg = packed.build(docs)
        # segments that earlier tests of this worker left as garbage
        # release their share now, not at the collect below
        gc.collect()
        before = dict(packed._dev_cols)
        col = seg.device_postings()
        nbytes = int(col.nbytes)
        after = dict(packed._dev_cols)
        assert after["entries"] == before["entries"] + 1
        assert after["bytes"] == before["bytes"] + nbytes
        # cached forever on the segment: a second call adds nothing
        seg.device_postings()
        assert dict(packed._dev_cols) == after
        assert "postings_columns" in \
            compute_stats.debug_payload()["device_caches"]
        # a GC'd segment releases its share (weakref.finalize)
        del seg, col
        gc.collect()
        released = dict(packed._dev_cols)
        assert released["entries"] == before["entries"]
        assert released["bytes"] == before["bytes"]


# ---------------------------------------------------------------------------
# /debug/compute surface: the shared handler + all four services
# ---------------------------------------------------------------------------

class TestDebugComputeSurface:
    def test_handler_get_only_and_top_param(self):
        compute_stats.record_execute("cold", "s", 0.001)
        compute_stats.record_execute("hot", "s", 5.0)
        status, payload, ctype = compute_stats.handle_debug_compute(
            "GET", {"top": ["1"]}, b"")
        assert status == 200 and ctype == "application/json"
        doc = json.loads(payload)
        assert [r["op"] for r in doc["programs"]] == ["hot"]
        assert set(doc) >= {"armed", "programs", "plan_cache",
                            "jit_evictions", "waste", "device_caches",
                            "device_memory"}
        status, _p, _ct = compute_stats.handle_debug_compute(
            "POST", {}, b"{}")
        assert status == 405

    def test_started_service_reports_platform_and_devices(self, tmp_path,
                                                          monkeypatch):
        """The start-up contract: a service initialises the backend
        before it listens, so /debug/compute on a started service names
        the platform and lists every device."""
        import threading
        import urllib.request

        import jax

        from m3_tpu.services.coordinator import CoordinatorService

        # this process's compile cache stays where it was (off)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        svc = CoordinatorService({
            "db": {"path": str(tmp_path / "db"), "n_shards": 2},
            "http": {"host": "127.0.0.1", "port": 0},
            "carbon": {"enabled": False},
        })
        t = threading.Thread(target=svc.run, daemon=True)
        t.start()
        try:
            deadline = time.time() + 60
            while svc.api._server is None and time.time() < deadline:
                time.sleep(0.02)
            port = svc.api._server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/compute") as r:
                doc = json.loads(r.read())
        finally:
            svc._stop.set()
            t.join(30)
        assert not t.is_alive()
        assert doc["backend"]["platform"] == jax.default_backend() == "cpu"
        assert doc["backend"]["jax"] == jax.__version__
        assert [d["id"] for d in doc["backend"]["devices"]] == \
            [d.id for d in jax.devices()]
        assert doc["device_memory"] == []  # CPU devices report none

    def test_dbnode_route_fault_exempt(self, tmp_path):
        """A fault plan error-injecting dbnode.handle must not blind the
        compute plane: /debug/compute still answers mid-outage, exactly
        like /debug/profile."""
        from m3_tpu.services.dbnode import NodeAPI
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions
        from m3_tpu.utils import faults

        compute_stats.record_execute("nodeop", "s", 0.5)
        db = Database(str(tmp_path / "d"), DatabaseOptions(n_shards=2))
        db.create_namespace("default")
        db.open()
        try:
            api = NodeAPI(db)
            status, payload, ctype = api.handle(
                "GET", "/debug/compute", {}, b"")
            assert status == 200 and ctype == "application/json"
            assert json.loads(payload)["programs"][0]["op"] == "nodeop"
            with faults.active("dbnode.handle=error"):
                status, payload, _ct = api.handle(
                    "GET", "/debug/compute", {}, b"")
                assert status == 200
                status, _p, *_ = api.handle(
                    "GET", "/blocks/starts",
                    {"namespace": ["default"], "shard": ["0"]}, b"")
                assert status == 503  # the plan does bite everything else
        finally:
            db.close()

    def test_coordinator_route(self, tmp_path):
        from m3_tpu.query.api import CoordinatorAPI
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        compute_stats.record_execute("coordop", "s", 0.5)
        db = Database(str(tmp_path / "c"), DatabaseOptions(n_shards=2))
        db.create_namespace("default")
        db.open()
        try:
            api = CoordinatorAPI(db)
            status, ctype, payload, _h = api.handle(
                "GET", "/debug/compute", {"top": ["3"]}, b"")
            assert status == 200 and ctype == "application/json"
            doc = json.loads(payload)
            assert doc["programs"][0]["op"] == "coordop"
        finally:
            db.close()

    def test_debug_server_route(self):
        """The profiler DebugServer carries /debug/compute for the two
        services without a request router of their own (aggregator,
        kvd)."""
        import urllib.request

        from m3_tpu.utils import profiler

        compute_stats.record_execute("aggop", "s", 0.5)
        srv = profiler.DebugServer(port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/debug/compute?top=5",
                    timeout=5) as r:
                doc = json.loads(r.read().decode())
            assert doc["programs"][0]["op"] == "aggop"
            assert "waste" in doc and "device_caches" in doc
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# ?explain=analyze device block on the compiled query path, 1 and 8 devices
# ---------------------------------------------------------------------------

class TestExplainDeviceBlock:
    @pytest.fixture(scope="class")
    def engine(self, tmp_path_factory):
        from m3_tpu.query.engine import Engine
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        db = Database(str(tmp_path_factory.mktemp("cstat") / "db"),
                      DatabaseOptions(n_shards=4))
        db.create_namespace("default")
        db.open(START)
        rng = np.random.default_rng(11)
        # 23 series: a distinct Sp shape bucket from the other test
        # files, so THIS file's warm run pays the miss
        for i in range(23):
            tags = [(b"host", b"h%02d" % (i % 5)), (b"i", b"%02d" % i)]
            t = START
            for _ in range(40):
                t += int(rng.integers(10, 50)) * NS
                db.write_tagged("default", b"reqs", tags, t,
                                float(rng.integers(0, 9)))
        yield Engine(db, resolve_tiers=False)
        db.close()

    Q = "sum by (host) (sum_over_time(reqs[4m]))"

    def _run(self, engine, collect):
        from m3_tpu.query import explain

        if not collect:
            v, _ = engine.query_range(self.Q, START, START + 12 * MIN, MIN)
            return v, None
        with explain.collect(analyze=True) as col:
            v, _ = engine.query_range(self.Q, START, START + 12 * MIN, MIN)
        return v, col.to_dict()

    def test_device_block_single_device(self, engine, monkeypatch):
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        self._run(engine, collect=False)  # warm: the miss
        _v, doc = self._run(engine, collect=True)
        assert doc["compiled"]["ran"] is True
        dev = doc["compiled"]["device"]
        assert dev["program"] == "query_plan"
        assert dev["sig"] == doc["compiled"]["cache_key"]
        assert dev["cache"] == "hit" and dev["execute_seconds"] >= 0.0
        assert dev["mesh_devices"] == 1
        pad = dev["padding"]
        assert pad["series"]["logical"] == 23
        assert pad["series"]["padded"] >= 23
        assert pad["time"]["padded"] >= pad["time"]["logical"]
        assert 0.0 <= dev["waste_ratio"] < 1.0
        # the same program ranks in the /debug/compute table
        ops = {r["op"] for r in
               compute_stats.debug_payload()["programs"]}
        assert "query_plan" in ops
        # and the padding ledger carries this query's seams
        waste = compute_stats.debug_payload()["waste"]
        assert "query_slabs/series" in waste
        assert "query_slabs/samples" in waste

    def test_device_block_parity_1_vs_8_mesh_devices(self, engine,
                                                     monkeypatch):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")
        docs = {}
        vals = {}
        for n_dev in (1, 8):
            monkeypatch.setenv("M3_TPU_QUERY_SHARD", str(n_dev))
            self._run(engine, collect=False)  # warm this mesh width
            v, doc = self._run(engine, collect=True)
            docs[n_dev], vals[n_dev] = doc["compiled"]["device"], v
        assert docs[1]["mesh_devices"] == 1
        assert docs[8]["mesh_devices"] == 8
        for n_dev in (1, 8):
            d = docs[n_dev]
            assert d["cache"] == "hit" and "execute_seconds" in d
            # the logical shape is mesh-independent; only padding may
            # differ (series pads to a multiple of the mesh width)
            assert d["padding"]["series"]["logical"] == 23
            assert d["padding"]["time"] == \
                docs[1]["padding"]["time"]
        assert docs[8]["padding"]["series"]["padded"] % 8 == 0
        # numerics: device-count independent within the documented
        # reassociation envelope
        a, b = vals[1], vals[8]
        assert a.labels == b.labels
        assert np.array_equal(np.isnan(a.values), np.isnan(b.values))
        assert np.allclose(a.values, b.values, rtol=1e-9, atol=0,
                           equal_nan=True)
        # plan-cache occupancy/evictions surface alongside the programs
        pc = compute_stats.debug_payload()["plan_cache"]
        assert pc is not None and pc["entries"] >= 2  # one per mesh width


# ---------------------------------------------------------------------------
# M3-monitors-M3: the compute plane flows through _m3_system
# ---------------------------------------------------------------------------

class TestSelfScrapeIngestion:
    def test_execute_histogram_and_waste_gauge_queryable(self, tmp_path):
        from m3_tpu.query.engine import Engine
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions
        from m3_tpu.utils import selfscrape

        compute_stats.record_execute("scrapeop", "Ssig", 0.25)
        compute_stats.record_waste("scrapesite", "ax", 3, 4)
        db = Database(str(tmp_path / "m"), DatabaseOptions(n_shards=2))
        db.open()
        try:
            mon = selfscrape.SelfMonitor(db, interval_s=0.0)
            assert mon.enabled
            assert mon.maybe_scrape(now_ns=10**15) > 0
            eng = Engine(db, selfscrape.SELF_NAMESPACE)
            start, end = 10**15 - NS, 10**15 + NS
            v, _w = eng.query_range("compute_execute_seconds_count",
                                    start, end, NS)
            by_op = {labels.get(b"op"): float(np.nanmax(row))
                     for labels, row in zip(v.labels, v.values)}
            assert by_op.get(b"scrapeop") == 1.0, by_op
            v, _w = eng.query_range("compute_waste_waste_ratio",
                                    start, end, NS)
            by_site = {labels.get(b"site"): float(np.nanmax(row))
                       for labels, row in zip(v.labels, v.values)}
            assert by_site.get(b"scrapesite") == 0.25, by_site
            mon.close()
        finally:
            db.close()

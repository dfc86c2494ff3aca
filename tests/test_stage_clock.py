"""The stage clock of the served query path (ISSUE 26, utils/trace.py).

One clock per layer boundary: ``trace.stage`` keeps wall and thread-CPU
time for every request whatever the sampling, knows what the stages
beneath it covered, feeds ``query_stage_seconds{route,stage}`` with wall
self-time and ``query_stage_cpu_seconds`` with CPU self-time, writes its
whole time to ``QueryStats.stages``, and enters the device trace as an
annotation while a session runs. The last class drives a real service
process on the CPU with the device rungs forced, as
tests/test_chip_smoke.py does.
"""

import glob
import json
import os
import re
import sys
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from m3_tpu.utils import dispatch, querystats, trace  # noqa: E402
from m3_tpu.utils.instrument import default_registry  # noqa: E402
from m3_tpu.utils.trace import SpanContext, Tracer  # noqa: E402

STAGE_TABLE = [
    trace.STAGE_REQUEST, trace.STAGE_PARSE_PLAN, trace.STAGE_QUERY_IDS,
    trace.STAGE_READ_MANY, trace.STAGE_GATHER, trace.STAGE_DECODE_HOST,
    trace.STAGE_DECODE_WAIT, trace.STAGE_SLAB_PREP,
    trace.STAGE_PLAN_DISPATCH, trace.STAGE_PLAN_WAIT, trace.STAGE_EVAL,
    trace.STAGE_RENDER]


def _busy(seconds: float) -> None:
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        sum(range(500))


def _stage_metrics(route: str) -> dict:
    """{stage: (wall self-time sum, observations, CPU self-time)} of one
    route, from the default registry."""
    counters, _g, _t, hists = default_registry().snapshot()
    out = {}
    for (name, tags), (_b, _c, hsum, hcount) in hists.items():
        tags = dict(tags)
        if name == "query.stage.seconds" and tags.get("route") == route:
            cpu = counters.get(("query.stage.cpu_seconds",
                                (("route", route),
                                 ("stage", tags["stage"]))), 0.0)
            out[tags["stage"]] = (hsum, hcount, cpu)
    return out


def _ctx(route: str, sampled: bool = True) -> SpanContext:
    return SpanContext(trace.new_trace_id(), "", sampled, route)


class TestSelfTime:
    def test_nested_self_times_add_up_to_the_roots_wall(self):
        tr = Tracer()
        with tr.activate(_ctx("t_nest")):
            with tr.stage("t.root") as root:
                _busy(0.004)
                with tr.stage("t.wait") as wait:
                    time.sleep(0.02)
                    with tr.span("t.plain"):     # transparent to the sum
                        _busy(0.002)
                        with tr.stage("t.leaf") as leaf:
                            _busy(0.006)
                with tr.stage("t.leaf"):         # a second span, same name
                    _busy(0.003)
        m = _stage_metrics("t_nest")
        assert set(m) == {"t.root", "t.wait", "t.leaf"}
        assert m["t.leaf"][1] == 2 and m["t.root"][1] == 1
        total = sum(v[0] for v in m.values())
        assert total == pytest.approx(root.wall_s, abs=1e-6)
        # what a frame's stages covered is on the frame
        assert root.covered_ns >= wait.wall_ns + 3_000_000
        assert wait.covered_ns == leaf.wall_ns
        # CPU <= wall per span and, within clock slack, per stage's self
        for fr in (root, wait, leaf):
            assert 0 <= fr.cpu_ns <= fr.wall_ns
        for wall_self, _n, cpu_self in m.values():
            assert cpu_self <= wall_self + 1e-3
        # the sleeper waited, the leaf computed
        assert m["t.wait"][2] < 0.012 < 0.02 <= m["t.wait"][0]
        assert m["t.leaf"][2] >= 0.008
        # the ring carries the same numbers on the span
        spans = {s["name"]: s for s in tr.recent()}
        assert spans["t.root"]["self_us"] == pytest.approx(
            m["t.root"][0] * 1e6, abs=1.0)
        assert spans["t.root"]["cpu_us"] >= spans["t.root"]["cpu_self_us"]
        assert "self_us" not in spans["t.plain"]

    @pytest.mark.parametrize("how", ["unsampled_request", "tracer_disabled",
                                     "unsampled_root"])
    def test_metrics_are_fed_whatever_the_sampling(self, how):
        tr = Tracer(sample_every=2)
        route = "t_" + how
        if how == "tracer_disabled":      # M3_TPU_TRACE_SAMPLE=0
            tr.enabled = False
            ctx = _ctx(route)
        elif how == "unsampled_request":  # the head decision said no
            ctx = _ctx(route, sampled=False)
        else:                             # a root that draws the decision
            ctx = None
            tr.sample_head()              # the next root is the 1-in-2 miss
            route = trace.ROUTE_OTHER
        before = _stage_metrics(route).get("t.fed", (0.0, 0, 0.0))
        with tr.activate(ctx):
            with tr.stage("t.fed") as fr:
                with tr.span("t.inner") as sp:
                    assert sp is None
                _busy(0.002)
            assert fr.span is None
            assert tr.current() is ctx    # the context is put back
        after = _stage_metrics(route)["t.fed"]
        assert after[1] == before[1] + 1
        assert after[0] - before[0] == pytest.approx(fr.wall_s, abs=1e-6)
        assert after[2] - before[2] >= 0.0015
        assert tr.recent() == []          # sampling governs the ring only

    def test_an_unmetered_stage_keeps_time_and_passes_through(self):
        tr = Tracer()
        with tr.activate(_ctx("t_unmetered")):
            with tr.stage("t.outer") as outer:
                with tr.stage("t.leg", metered=False) as leg:
                    _busy(0.002)
                    with tr.stage("t.inner") as inner:
                        _busy(0.002)
        m = _stage_metrics("t_unmetered")
        assert set(m) == {"t.outer", "t.inner"}
        assert leg.wall_ns > inner.wall_ns > 0
        assert outer.covered_ns == inner.wall_ns
        assert m["t.outer"][0] == pytest.approx(
            outer.wall_s - inner.wall_s, abs=1e-6)
        assert [s["name"] for s in tr.recent()] == \
            ["t.inner", "t.leg", "t.outer"]

    def test_the_name_is_taken_at_close(self):
        tr = Tracer()
        with tr.activate(_ctx("t_rename")):
            with tr.stage("t.device_wait") as fr:
                fr.name = "t.compile"     # what a jit miss does
                fr.tag(sig="S1")
        assert set(_stage_metrics("t_rename")) == {"t.compile"}
        [sp] = tr.recent()
        assert sp["name"] == "t.compile" and sp["tags"] == {"sig": "S1"}

    def test_the_route_rides_the_context_into_child_spans(self):
        tr = Tracer()
        with tr.activate(_ctx("t_route")):
            with tr.stage("t.a"):
                assert tr.current().route == "t_route"
                assert tr.current().sampled and tr.current().span_id
        # the wire form does not carry it
        assert "t_route" not in _ctx("t_route").to_traceparent()


class TestQueryStatsStages:
    def test_stages_hold_inclusive_wall_and_the_clock_is_the_spans(self):
        assert not hasattr(querystats, "stage")
        tr = Tracer()
        st = querystats.start(query="q")
        try:
            with tr.stage("t.eval") as ev:
                with tr.stage("t.read") as rd:
                    _busy(0.002)
                with tr.stage("t.read"):
                    pass
        finally:
            querystats.finish(st)
        assert st.stages["t.eval"] == ev.wall_s
        assert st.stages["t.eval"] > st.stages["t.read"] >= rd.wall_s
        assert set(st.to_dict()["stages_ms"]) == {"t.eval", "t.read"}

    def test_a_nested_start_names_the_query_and_stamps_the_time(self):
        ticks = iter([10.0, 10.5, 12.0])
        outer = querystats.start(clock=lambda: next(ticks))
        try:
            inner = querystats.start(query="up", namespace="default")
            assert inner is outer and outer.query == "up"
            querystats.finish(inner)      # the engine's: time so far
            assert outer.duration_s == 0.5
            assert querystats.current() is outer
        finally:
            querystats.finish(outer)
        assert outer.duration_s == 2.0 and querystats.current() is None


class _FakeAnnotation:
    seen: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.seen.append(("enter", self.name))

    def __exit__(self, *exc):
        self.seen.append(("exit", self.name))


class TestAnnotations:
    def test_only_sampled_stages_of_a_running_session_are_annotated(self):
        tr = Tracer()
        _FakeAnnotation.seen = seen = []
        with tr.activate(_ctx("t_ann")):
            with tr.stage("t.before"):
                pass
        tr.annotate = _FakeAnnotation     # start_device_trace does this
        with tr.activate(_ctx("t_ann")):
            with tr.stage("t.a"):
                with tr.stage("t.b", metered=False):
                    pass
        with tr.activate(_ctx("t_ann", sampled=False)):
            with tr.stage("t.unsampled"):
                pass
        tr.annotate = None                # stop_device_trace does this
        with tr.activate(_ctx("t_ann")):
            with tr.stage("t.after"):
                pass
        assert seen == [("enter", "t.a"), ("enter", "t.b"),
                        ("exit", "t.b"), ("exit", "t.a")]


# -- the tracker times completion ------------------------------------------


class _Blocks:
    """A result whose read waits, as a device array's does."""

    def __init__(self, value, seconds=0.05):
        self.value, self.seconds = value, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return np.asarray(self.value, dtype)


class _FakeProgram:
    def __init__(self, result):
        self.result, self.size = result, 1

    def _cache_size(self):
        return self.size

    def __call__(self, *a, **kw):
        return self.result


def _execute_seconds(op: str) -> float:
    _c, _g, _t, hists = default_registry().snapshot()
    return sum(h[2] for (name, tags), h in hists.items()
               if name == "compute.execute.seconds"
               and dict(tags).get("op") == op)


class TestTrackerIncludesTheWait:
    def test_a_fake_program_whose_result_blocks(self):
        prog = _FakeProgram(_Blocks(np.zeros(3)))
        before = _execute_seconds("t_fake_op")
        with dispatch.jit_tracker("t_fake_op", prog, sig="S") as tracker:
            out = np.asarray(prog())
            assert not tracker.missed()
        assert out.shape == (3,) and not tracker.miss
        assert tracker.seconds >= 0.05
        assert _execute_seconds("t_fake_op") - before >= 0.05

    def test_a_miss_is_seen_inside_the_block(self):
        prog = _FakeProgram(np.zeros(1))
        with dispatch.jit_tracker("t_fake_miss", prog) as tracker:
            prog.size += 1                # the call compiled
            assert tracker.missed()
        assert tracker.miss

    def test_the_decode_site_times_the_wait(self, monkeypatch):
        from m3_tpu.encoding.m3tsz import hostpath, tpu
        from m3_tpu.utils.xtime import TimeUnit

        def blocking_decode(words, unit, **kw):
            real = real_decode(words, unit, **kw)
            return real._replace(times=_Blocks(np.asarray(real.times)))

        real_decode = tpu.decode
        t0 = 1_600_000_000 * 10**9
        times = t0 + np.arange(1, 9, dtype=np.int64)[None, :] * 10**10
        vbits = np.arange(8, dtype=np.float64)[None, :].view(np.uint64)
        streams = hostpath.encode_blocks(times, vbits, np.array([t0]),
                                         np.array([8]), TimeUnit.SECOND,
                                         False)
        hostpath._decode_streams_device(streams, TimeUnit.SECOND, False)
        monkeypatch.setattr(tpu, "decode", blocking_decode)
        before = _execute_seconds("m3tsz_decode")
        tr = trace.default_tracer()
        with tr.activate(_ctx("t_decode_site")):
            [(t, v)] = hostpath._decode_streams_device(
                streams, TimeUnit.SECOND, False)
        assert np.array_equal(t, times[0]) and np.array_equal(v, vbits[0])
        assert _execute_seconds("m3tsz_decode") - before >= 0.05
        wall, n, cpu = _stage_metrics("t_decode_site")[
            trace.STAGE_DECODE_WAIT]
        assert n == 1 and wall >= 0.05 and cpu < wall - 0.03

    def test_the_plan_site_times_the_wait(self, tmp_path, monkeypatch):
        from m3_tpu.query import compiler
        from m3_tpu.query.engine import Engine
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        start = 1_600_000_000 * 10**9
        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=2))
        db.create_namespace("default")
        db.open(start)
        try:
            for i in range(5):
                for j in range(20):
                    db.write_tagged("default", b"m", [(b"i", b"%d" % i)],
                                    start + j * 10**10, float(i + j))
            monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")
            eng = Engine(db, resolve_tiers=False)
            args = ("sum(sum_over_time(m[1m]))", start + 6 * 10**10,
                    start + 18 * 10**10, 6 * 10**10)
            want, _ = eng.query_range(*args)      # compiles the real one
            real_program = compiler._program

            def blocking_program(sig, mesh=None):
                real = real_program(sig, mesh)

                class Prog(_FakeProgram):
                    def __call__(self, *a, **kw):
                        return _Blocks(np.asarray(real(*a, **kw)))

                return Prog(None)

            monkeypatch.setattr(compiler, "_program", blocking_program)
            before = _execute_seconds("query_plan")
            tr = trace.default_tracer()
            with tr.activate(_ctx("t_plan_site")):
                got, _ = eng.query_range(*args)
            assert np.array_equal(got.values, want.values)
            assert _execute_seconds("query_plan") - before >= 0.05
            m = _stage_metrics("t_plan_site")
            assert m[trace.STAGE_PLAN_WAIT][0] >= 0.05
            assert m[trace.STAGE_PLAN_DISPATCH][1] == 1
            assert trace.STAGE_PLAN_COMPILE not in m
        finally:
            db.close()


class TestProgramNames:
    def test_what_the_benchmarks_trace_patterns_match(self):
        """plan_roofline matches ^jit_run( and decode_roofline
        ^jit__decode_jit(: the plan program is `run`, the decoder
        `_decode_jit`, and the postings program is neither."""
        from m3_tpu.encoding.m3tsz import tpu
        from m3_tpu.index import device
        from m3_tpu.query import compiler

        plan = compiler._program(("sum_over_time", ()), None)
        postings = device._program(1, 0, True, None)
        assert plan.__name__ == "run"
        assert tpu._decode_jit.__name__ == "_decode_jit"
        assert postings.__name__ == "postings_run"
        assert postings.__name__ not in (plan.__name__,
                                         tpu._decode_jit.__name__)

    def test_the_phases_carry_named_scopes(self):
        import jax.numpy as jnp

        from m3_tpu.encoding.m3tsz import tpu
        from m3_tpu.utils.xtime import TimeUnit

        text = tpu._decode_jit.lower(
            jnp.zeros((2, 4), jnp.uint64), TimeUnit.SECOND, 16,
            "scatter", jnp.int32(2)).as_text(debug_info=True)
        assert "m3.decode.scan" in text


# -- pipeline legs ----------------------------------------------------------


class TestPipelineLegs:
    def test_a_gather_leg_hangs_under_the_request(self, monkeypatch):
        from m3_tpu.storage import pipeline

        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        tr = trace.default_tracer()
        tr.clear()
        ctx = _ctx("t_legs")
        threads = set()

        def produce(item):
            threads.add(pipeline.in_worker())
            _busy(0.002)
            return item * 2

        got = []
        with tr.activate(ctx), tr.stage(trace.STAGE_READ_MANY) as parent:
            stats = pipeline.run_stages(
                [1, 2, 3], produce, lambda it, p: got.append(p))
            one = pipeline.run_stages([4], produce, lambda it, p: None)
        assert got == [2, 4, 6] and threads == {True, False}
        spans = tr.find(ctx.trace_id)
        legs = [s for s in spans if s["name"] == trace.STAGE_GATHER]
        assert len(legs) == 4
        for s in legs:                    # the request's id, and a parent
            assert s["trace_id"] == ctx.trace_id
            assert s["parent_span_id"] == parent.span.span_id
        assert len([s for s in spans
                    if s["name"] == trace.PIPELINE_CONSUME]) == 4
        # the per-leg seconds are the spans'
        assert stats.stages["gather"] == pytest.approx(
            sum(s["duration_us"] for s in legs[:3]) / 1e6, abs=1e-5)
        assert stats.stages["decode"] > 0 and one.stages["gather"] >= 0.002
        # three worker legs are the stage read_many.gather; the one run
        # inline is the caller's own (read_many's self-time)
        m = _stage_metrics("t_legs")
        assert m[trace.STAGE_GATHER][1] == 3
        assert m[trace.STAGE_READ_MANY][0] >= 0.002


# -- a served query_range on the forced device rungs -------------------------


def _get(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=120) as r:
        return r.read(), r.headers


def _post(port: int, path: str, doc: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(doc).encode(),
        method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _metric_keys(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            out[key] = float(val)
    return out


def _stage_key(family: str, stage: str, route: str = "query_range") -> str:
    return f'{family}{{route="{route}",stage="{stage}"}}'


def _walk(node):
    yield node
    for child in node["children"]:
        yield from _walk(child)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One service process (CPU, device rungs forced), a flushed block of
    30 hosts, ten query_range requests inside a device-trace session
    started and stopped through /debug/profile/device."""
    work = tmp_path_factory.mktemp("stage_clock")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "M3_TPU_DEVICE_OPS": "1",
                "M3_TPU_QUERY_COMPILE": "1",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    env.pop("M3_TPU_TRACE_SAMPLE", None)
    svc = chip_smoke.Service(str(work), env, REPO)
    try:
        wl = chip_smoke.Workload(
            5, 30, 40, chip_smoke.block_start_for(time.time_ns()))
        port, _backend = svc.wait_listening(120.0)
        client = chip_smoke.Client(f"http://127.0.0.1:{port}")
        client.request("POST", "/api/v1/runtime", json.dumps(
            {"flush_enabled": False, "snapshot_enabled": False}).encode())
        chip_smoke.phase_ingest(client, wl, 15, 20)
        client.request("POST", "/api/v1/runtime", json.dumps(
            {"flush_enabled": True, "snapshot_enabled": True}).encode())
        chip_smoke.wait_for_flush(client, svc.log_path, 120.0)
        trace_dir = str(work / "device_trace")
        started = _post(port, "/debug/profile/device",
                        {"action": "start", "dir": trace_dir})
        start, end, step = chip_smoke.query_grid(wl)
        answers = []
        fields = ("idle", "user", "system", "nice", "idle")
        for field in fields + fields:     # the repeats hit the hot tier
            qs = urllib.parse.urlencode({
                "query": f"avg by (region) (avg_over_time("
                         f"cpu_usage_{field}[5m]))",
                "start": repr(start / chip_smoke.NS),
                "end": repr(end / chip_smoke.NS),
                "step": f"{step // chip_smoke.NS}s"})
            body, headers = _get(port, "/api/v1/query_range?" + qs)
            answers.append((json.loads(body), headers["M3-Trace-Id"]))
        stopped = _post(port, "/debug/profile/device", {"action": "stop"})
        status = json.loads(_get(port, "/debug/profile/device")[0])
        # one more request, after the session
        answers.append((json.loads(_get(
            port, "/api/v1/query_range?" + qs)[0]), None))
        yield {
            "port": port, "answers": answers, "trace_dir": trace_dir,
            "started": started, "stopped": stopped, "status": status,
            "metrics": _metric_keys(_get(port, "/metrics")[0].decode()),
            "spans": json.loads(_get(
                port, "/debug/traces?limit=2048")[0])["spans"],
            "slow": json.loads(_get(
                port, "/debug/slow_queries?limit=50")[0])["queries"],
        }
    finally:
        svc.stop()


class TestServedQueryRange:
    def test_every_stage_is_on_metrics_and_the_sum_is_the_roots(self, served):
        m = served["metrics"]
        n = len(served["answers"])
        assert all(a["status"] == "success" for a, _ in served["answers"])
        for stage in STAGE_TABLE + [trace.STAGE_DECODE_COMPILE,
                                    trace.STAGE_PLAN_COMPILE]:
            assert m[_stage_key("query_stage_seconds_count", stage)] >= 1, \
                stage
            assert _stage_key("query_stage_cpu_seconds", stage) in m, stage
        # the root's count is the requests sent, on this route alone
        assert m[_stage_key("query_stage_seconds_count", "request")] == n
        assert _stage_key("query_stage_seconds_count", "eval",
                          "other") not in m
        assert m[_stage_key("query_stage_seconds_count", "request",
                            "remote_write")] >= 1
        # coordinator_request_seconds keeps no route label
        assert "coordinator_request_seconds_count" in m
        # the request thread's self-times add up to the roots' durations
        self_sum = sum(
            v for k, v in m.items()
            if k.startswith('query_stage_seconds_sum{route="query_range"')
            and f'stage="{trace.STAGE_GATHER}"' not in k)
        roots = [s for s in served["spans"]
                 if s["name"] == trace.STAGE_REQUEST
                 and s["tags"]["path"] == "/api/v1/query_range"]
        assert len(roots) == n
        root_sum = sum(s["duration_us"] for s in roots) / 1e6
        assert self_sum == pytest.approx(root_sum, rel=0.02)
        # CPU is under wall, over the route
        cpu = sum(v for k, v in m.items() if k.startswith(
            'query_stage_cpu_seconds{route="query_range"'))
        wall = self_sum + m[_stage_key("query_stage_seconds_sum",
                                       trace.STAGE_GATHER)]
        assert 0 < cpu <= wall
        # the handler's own histogram bounds the same extent
        assert m["coordinator_request_seconds_sum"] >= root_sum

    def test_the_same_stages_in_stats_and_in_the_requests_tree(self, served):
        first, trace_id = served["answers"][0]
        on_thread = [s for s in STAGE_TABLE if s != trace.STAGE_GATHER]
        # the envelope is rendered inside `render`, under `request`
        in_envelope = set(first["stats"]["stages_ms"])
        assert in_envelope >= {trace.STAGE_QUERY_IDS, trace.STAGE_READ_MANY,
                               trace.STAGE_EVAL, trace.STAGE_PARSE_PLAN,
                               trace.STAGE_DECODE_HOST,
                               trace.STAGE_SLAB_PREP, trace.STAGE_PLAN_WAIT}
        assert first["stats"]["duration_ms"] > 0
        # /debug/slow_queries holds every request-thread stage; the worker
        # legs are the pipeline block's
        seen = set()
        fetched = 0
        for rec in served["slow"]:
            stages = rec["stages_ms"]
            assert stages["request"] >= stages["eval"] > 0
            if trace.STAGE_READ_MANY in stages:
                fetched += 1
                assert stages["eval"] >= stages["read_many"] > 0
            else:
                # a repeat the hot tier served: nothing matched, read
                # or prepared
                assert not set(stages) & {trace.STAGE_QUERY_IDS,
                                          trace.STAGE_SLAB_PREP,
                                          trace.STAGE_DECODE_HOST}
            assert rec["duration_ms"] >= stages["eval"]
            assert rec["query"].startswith("avg by (region)")
            seen |= set(stages)
        assert len(served["slow"]) == len(served["answers"])
        # four distinct queries were fetched once each; their seven
        # repeats were not (a tick that bumps the version costs a fetch)
        assert 4 <= fetched < len(served["slow"])
        assert seen >= set(on_thread)
        assert any(r.get("pipeline", {}).get("stage_ms", {}).get("gather")
                   for r in served["slow"])
        # the request's tree
        doc = json.loads(_get(
            served["port"], f"/debug/traces?trace_id={trace_id}")[0])
        [root] = doc["tree"]
        assert root["name"] == trace.STAGE_REQUEST
        names = {n["name"] for n in _walk(root)}
        assert names >= set(STAGE_TABLE) - {trace.STAGE_DECODE_WAIT,
                                            trace.STAGE_PLAN_DISPATCH}
        assert names & {trace.STAGE_DECODE_WAIT, trace.STAGE_DECODE_COMPILE}
        assert names & {trace.STAGE_PLAN_DISPATCH, trace.STAGE_PLAN_COMPILE}
        # a pipeline gather leg has the request's trace id and a parent
        by_id = {n["span_id"]: n for n in _walk(root)}
        legs = [n for n in _walk(root) if n["name"] == trace.STAGE_GATHER]
        assert legs
        for leg in legs:
            assert leg["trace_id"] == trace_id
            assert by_id[leg["parent_span_id"]]["name"] in (
                trace.STAGE_READ_MANY, trace.READ_MANY)

    def test_the_session_leaves_an_xplane_with_the_stage_names(self, served):
        from jax.profiler import ProfileData

        assert served["started"]["tracing"] is True
        assert served["stopped"]["tracing"] is False
        assert served["stopped"]["stop_seconds"] >= 0
        assert served["status"] == {"tracing": False, "dir": None}
        [path] = glob.glob(os.path.join(
            served["trace_dir"], "plugins", "profile", "*", "*.xplane.pb"))
        host = [p for p in ProfileData.from_file(path).planes
                if p.name.startswith("/host:")]
        counts: dict = {}
        for plane in host:
            for line in plane.lines:
                for ev in line.events:
                    counts[ev.name] = counts.get(ev.name, 0) + 1
        for stage in STAGE_TABLE:
            assert counts.get(stage, 0) >= 1, stage
        # ten requests ran inside the session; the one after it left
        # nothing (the xplane was closed) and started no second file
        assert counts[trace.STAGE_REQUEST] == len(served["answers"]) - 1
        # a second stop is refused, nothing runs
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(served["port"], "/debug/profile/device",
                  {"action": "stop"})
        assert e.value.code == 409

    def test_trace_gaps_reads_that_trace(self, served, capsys, monkeypatch):
        from m3_tpu.tools import trace_gaps

        doc = trace_gaps.reduce(served["trace_dir"])
        assert doc["on_device"] is False and doc["programs"] > 0
        assert doc["idle_s"] > 0
        named = {trace.STAGE_REQUEST, trace.STAGE_EVAL,
                 trace.STAGE_READ_MANY, trace.STAGE_DECODE_HOST,
                 trace.STAGE_SLAB_PREP}
        assert named & set(doc["by_stage"])
        assert sum(doc["by_stage"].values()) == pytest.approx(doc["idle_s"])
        for gap in doc["longest"]:
            assert sum(gap["stages"].values()) == pytest.approx(
                gap["seconds"])
        # the printed form, of the same reduction (one read of the file)
        monkeypatch.setattr(
            trace_gaps, "reduce",
            lambda d, top: {**doc, "longest": doc["longest"][:top]})
        assert trace_gaps.main([served["trace_dir"], "--top", "3"]) == 0
        out = capsys.readouterr().out
        assert "no device plane" in out and "longest 3 gaps" in out
        assert f"{trace.STAGE_DECODE_WAIT:<28}" in out


NEW_METRICS = ["parse_plan_ms", "index_match_ms", "fetch_ms",
               "decode_host_ms", "decode_device_wait_ms", "slab_prep_ms",
               "plan_device_wait_ms", "render_ms", "query_cpu_ms"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_layer_metrics_regexes_match_the_rendered_metrics(served, name):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "prom_ratio" and spec["args"]["scale"] == 1000
    keys = list(served["metrics"])
    for side in ("num", "den"):
        for pattern in spec["args"][side]:
            rx = re.compile(pattern)
            hits = [k for k in keys if rx.fullmatch(k)]
            assert hits, (side, pattern)
            assert all('route="query_range"' in k for k in hits)
    # label order does not matter to them
    [den] = spec["args"]["den"]
    assert re.fullmatch(den, 'query_stage_seconds_count'
                             '{stage="request",route="query_range"}')
    assert not re.fullmatch(den, 'query_stage_seconds_count'
                                 '{route="query",stage="request"}')

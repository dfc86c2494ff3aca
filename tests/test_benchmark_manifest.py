"""BENCHMARK.json against the files it names, in tier 1 (ISSUE 35 asked
for it, a `benchmark` PR could add nothing here; ISSUE 37).

The cases are those of ``benchmarks/tests/test_manifest.py``, imported so
that each counts in the driver's run, and what the live cell needs held
beside them: its thirteen ``.live`` twins read exactly what their
originals read (``api_query_ms.live`` alone by another reader: its
original's histogram has no route label), its queries are
``tsbs-cpu-2k.dash``'s, its scrapes offer the fleet's own rate, and
every one of its twenty-six metrics comes back as a number from a sound
window (a traced line that lacks one is refused); and the read
pipeline's parts and the launch-gap share that ``tsbs-cpu-2k.dash`` and
``tsbs-cpu-14h.ranges`` read come back as numbers from a sound window and
are left out where the program lacks their families. Pure JSON and the
harness's readers, no JAX.
"""

import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks", "tests"))

import test_manifest as manifest  # noqa: E402
from test_manifest import *  # noqa: E402,F401,F403  (the cases themselves)

BENCH, MANIFEST = manifest.BENCH, manifest.MANIFEST
LIVE, DASH = "tsbs-cpu-2k-live.dash", "tsbs-cpu-2k.dash"
QUERY_PARAMS = ("workers", "mix", "prime", "warm_s", "warm_clean_rounds",
                "warm_rounds", "verify_max", "verify_streams",
                "load_hosts_per_request", "load_points_per_request",
                "plan_launch_counter", "decode_streams_counter")
LIVE_METRICS = [m["name"] for m in MANIFEST["per_layer"]
                if LIVE in m["workloads"]]
TWINS = [("hot_tier_hit_pct.live", "hot_tier_hit_pct"),
         ("fetch_ms.live", "fetch_ms"),
         ("query_cpu_ms.live", "query_cpu_ms"),
         ("compiles_in_window.live", "compiles_in_window.dash"),
         ("device_idle_pct.live", "device_idle_pct.dash")] + [
    (name + ".live", name) for name in (
        "index_match_ms", "slab_prep_ms", "render_ms", "parse_plan_ms",
        "decode_host_ms", "decode_device_wait_ms", "plan_device_wait_ms",
        "decode_streams_per_launch")]


def _metric(name):
    return manifest._load(BENCH, "layer_metrics", name + ".json")


def _cell():
    return manifest._load(BENCH, "workloads", LIVE + ".json")


def _kind():
    from run import load_traffic   # the kind's module, found by name

    return load_traffic("promql_beside_scrape").prepare.__globals__


def test_the_live_cell_lists_its_metrics_and_no_roofline():
    # twelve of the write route and the tick, fourteen of the read path
    assert len(LIVE_METRICS) == 26
    assert sorted(n for n in LIVE_METRICS if n.endswith(".live")) == sorted(
        [live for live, _orig in TWINS] + ["api_query_ms.live"])
    assert not [n for n in LIVE_METRICS if "roofline" in n]
    assert all(m["workloads"] == [LIVE] and m["moves"] == "query_p95_ms"
               for m in MANIFEST["per_layer"] if LIVE in m["workloads"])
    # no metric of the cell is read kernel by kernel from the slice: only
    # the idle share reads the trace, and any program in it will do
    assert [n for n in LIVE_METRICS
            if _metric(n)["reader"].startswith("trace_")] \
        == ["device_idle_pct.live"]


@pytest.mark.parametrize("live,original", TWINS)
def test_a_live_twin_reads_what_its_original_reads(live, original):
    twin, spec = _metric(live), _metric(original)
    assert (twin["reader"], twin.get("args", {})) == \
        (spec["reader"], spec.get("args", {}))
    assert (twin["unit"], twin["layer"], twin["source"], twin["better"]) \
        == (spec["unit"], spec["layer"], spec["source"], spec["better"])
    assert live in LIVE_METRICS


def test_the_live_cells_queries_are_the_dash_cells():
    live = _cell()
    dash = manifest._load(BENCH, "workloads", DASH + ".json")
    for key in QUERY_PARAMS:
        assert live["traffic_params"][key] == dash["traffic_params"][key]
    assert live["tolerances"] == dash["tolerances"]
    assert live["chips"] == dash["chips"] == 1
    # the cell reports the end-to-end metrics its file says, and no other
    assert sorted(live["report"]) == sorted(
        m["name"] for m in MANIFEST["end_to_end"]
        if LIVE in m.get("workloads", []))
    assert live["report"] == ["query_p95_ms"]


def test_the_live_configuration_is_the_dash_cells_with_the_fleet_reporting():
    live = manifest._load(BENCH, "configs", "tsbs-cpu-2k-live.json")
    base = manifest._load(BENCH, "configs", "tsbs-cpu-2k.json")
    for key in ("scale", "series", "interval_s", "history_points",
                "query_range_h", "node", "rehearse"):
        assert live[key] == base[key]
    # no guarantee is weaker: the base's, word for word, and two more
    assert live["guarantees"][:len(base["guarantees"])] == base["guarantees"]
    assert len(live["guarantees"]) == len(base["guarantees"]) + 2
    assert sorted(live["reduced"]) == sorted(base["reduced"])
    assert live["source"] != base["source"] and len(live["source"]) <= 200


def test_the_scrapes_offer_the_fleets_own_rate():
    cell = _cell()
    cfg = manifest._load(BENCH, "configs", cell["config"] + ".json")
    params = cell["traffic_params"]
    per_request = params["scrape_samples_per_request"]
    samples_per_s = cfg["scale"] * 10 / cfg["interval_s"]
    assert cfg["series"] == cfg["scale"] * 10
    # one request a second: whole groups, due at whole seconds
    assert cfg["series"] % per_request == 0
    groups = cfg["series"] // per_request
    requests_per_s = groups / cfg["interval_s"]
    assert per_request * requests_per_s == samples_per_s == 2000
    assert (cfg["interval_s"] * 1000 // groups) % 1000 == 0
    # the rehearsal's cut keeps whole groups at whole seconds too
    small = cfg["rehearse"]["series"] // cell["rehearse"][
        "scrape_samples_per_request"]
    assert cfg["rehearse"]["series"] % small == 0
    assert (cfg["interval_s"] * 1000 // small) % 1000 == 0


def test_the_schedule_is_made_from_the_seed_alone():
    Schedule = _kind()["Schedule"]
    made = [[Schedule(seed, 2000, 2000, 10).request(j) for j in range(50)]
            for seed in (2**31 + 9, 2**31 + 9, 10)]
    assert made[0] == made[1] and made[0] != made[2]
    s = Schedule(2**31 + 9, 2000, 2000, 10)
    assert (s.groups, s.hosts_per, s.spacing_ms) == (10, 200, 1000)
    for rnd in range(3):
        reqs = [s.request(rnd * 10 + k) for k in range(10)]
        assert [r[0] for r in reqs] == [rnd] * 10
        assert [r[3] for r in reqs] == [(rnd * 10 + k) * 1000
                                        for k in range(10)]
        assert sorted(h for r in reqs for h in range(r[1], r[2])) \
            == list(range(2000))
    for hosts, per_request in ((2000, 1500), (24, 80), (2000, 300)):
        with pytest.raises(ValueError):
            Schedule(1, hosts, per_request, 10)


# -- every metric of the cell, read from a sound window --------------------


def _stage_keys(route, stages, n, seconds, cpu):
    """What /metrics holds of one route after `n` root spans."""
    out = {}
    for stage, per in stages.items():
        labels = f'{{route="{route}",stage="{stage}"}}'
        out["query_stage_seconds_count" + labels] = float(n * per)
        out["query_stage_seconds_sum" + labels] = seconds * n * per
        out["query_stage_cpu_seconds" + labels] = cpu * n * per
    return out


def _window(cycles=2, writes=51, queries=600):
    """(before, after): /metrics as parsed at the window's two ends, for
    a window of `cycles` tick cycles."""
    def at(k):
        m = {}
        m.update(_stage_keys("tick", {
            "tick": 1, "tick.snapshot.host": 1, "encode.device_wait": 16,
            "tick.flush": 1, "tick.rotate": 1}, k * cycles, 0.5, 0.2))
        m.update(_stage_keys("remote_write", {
            "request": 1, "write.decode": 1, "write.batch": 1,
            "write.commitlog": 1, "write.buffer": 1}, k * writes, 0.03,
            0.02))
        m.update(_stage_keys("query_range", {
            "request": 1, "parse_plan": 1, "query_ids": 0.8,
            "read_many": 0.8, "read_many.gather": 0.8, "decode.host": 0.5,
            "decode.device_wait": 0.5, "slab_prep": 0.8,
            "plan.device_wait": 1, "render": 1}, k * queries, 0.05, 0.01))
        m['decode_batch_streams{path="device"}'] = k * queries * 0.5 * 20
        m["storage_snapshot_samples"] = k * cycles * 300_000.0
        m["storage_snapshot_bytes"] = k * cycles * 1_400_000.0
        m["storage_commitlog_rotations"] = float(k * cycles)
        m["storage_hot_tier_hit"] = k * 10.0
        m["storage_hot_tier_miss"] = k * 590.0
        m["jit_m3tsz_encode[miss]"] = k * 2.0
        return m

    return at(1), at(2)


def _trace():
    """The reduced form of a trace that holds the launcher's two marks
    and one program between them."""
    return {"devices": [{"plane": "/device:TPU:0", "op_seconds": {},
                         "lines": {"XLA Modules": [
                             ["jit_run(7)", 1_200_000_000, 400_000]]}}],
            "interval_ns": [1_000_000_000, 3_200_000_000]}


@pytest.fixture(scope="module")
def reading():
    from harness.readers import Reading

    before, after = _window()
    facts = _kind()["window_facts"](
        _cell()["traffic_params"]["window_facts"], before, after,
        [400.0] * 51)
    return Reading(before, after, 51.0, facts, {"hbm_bytes_per_s": 819e9},
                   _trace(), 2.2)


@pytest.mark.parametrize("name", LIVE_METRICS)
def test_a_live_metric_reads_a_number_from_a_two_cycle_window(name, reading):
    from harness.readers import READERS

    spec = _metric(name)
    value = READERS[spec["reader"]](reading, **spec.get("args", {}))
    assert isinstance(value, float) and value >= 0.0
    want = {"tick_ms": 20 * 500.0, "snapshot_device_wait_ms": 16 * 500.0,
            "snapshot_samples_per_tick": 300_000.0, "write_ack_ms": 400.0,
            "write_decode_ms": 30.0, "tick_cpu_ms": 20 * 200.0,
            "compiles_in_window.live": 2.0,
            "hot_tier_hit_pct.live": 100 * 10 / 600,
            "device_idle_pct.live": 100 * (1 - 0.0004 / 2.2),
            "render_ms.live": 50.0, "index_match_ms.live": 0.8 * 50.0,
            "decode_streams_per_launch.live": 20.0,
            # every stage of the request's own thread: 7.4 a request
            "api_query_ms.live": 7.4 * 50.0}
    if name in want:
        assert value == pytest.approx(want[name])
    if spec["unit"] == "%":
        assert value <= 100.0


@pytest.mark.parametrize("name", LIVE_METRICS)
def test_a_live_metric_is_left_out_where_the_program_lacks_its_source(name):
    """The parent's program has neither the stages nor the counters this
    PR adds: the reader returns nothing and does not raise."""
    from harness.readers import READERS, Reading

    before, after = _window()
    new = re.compile(r'route="tick"|stage="write\.|storage_snapshot_'
                     r'|storage_commitlog_rotations')
    before, after = ({k: v for k, v in m.items() if not new.search(k)}
                     for m in (before, after))
    facts = _kind()["window_facts"](
        _cell()["traffic_params"]["window_facts"], before, after,
        [400.0] * 51)
    spec = _metric(name)
    value = READERS[spec["reader"]](
        Reading(before, after, 51.0, facts, {"hbm_bytes_per_s": 819e9},
                _trace(), 2.2), **spec.get("args", {}))
    old = {"write_ack_ms"} | {n for n in LIVE_METRICS if n.endswith(".live")}
    assert (value is not None) == (name in old)


@pytest.mark.parametrize("name", [n for n in LIVE_METRICS
                                  if not n.endswith(".live")])
def test_a_write_or_tick_metric_reads_a_stage_or_counter_the_program_has(
        name):
    """Every regex of the metric names a stage constant of utils/trace.py
    or a counter the program increments (read as text: no import of the
    program)."""
    spec = _metric(name)
    with open(os.path.join(REPO, "m3_tpu", "utils", "trace.py")) as f:
        stages = set(re.findall(r'^(?:STAGE|ROUTE)_\w+ = "([^"]+)"',
                                f.read(), re.M))
    with open(os.path.join(REPO, "m3_tpu", "storage", "shard.py")) as f:
        shard = f.read()
    args = spec["args"]
    # facts of the window that the kind takes itself: a program without
    # the stage leaves the metric out, and a traced run's service ticks
    # on while the profiler stops
    assert spec["reader"] == "fact_ratio"
    facts = _cell()["traffic_params"]["window_facts"]
    regexes = [rx for fact in (args["num"], args["den"])
               for rx in facts.get(fact, {"keys": []})["keys"]]
    assert regexes or name == "write_ack_ms"        # the harness's clock
    for rx in regexes:
        counter = re.fullmatch(r"storage_(snapshot_\w+)", rx)
        if counter:
            assert f'scope.counter("{counter.group(1)}"' in shard
            continue
        route = re.search(r'route="(\w+)"', rx)
        assert route and route.group(1) in stages | {"remote_write"}
        named = re.search(r'stage="([^"]+)"', rx)
        if named:
            assert named.group(1).replace("\\.", ".") in stages


# -- tsbs-cpu-14h.ranges (ISSUE 39) -------------------------------------------

RANGES = "tsbs-cpu-14h.ranges"
RANGES_METRICS = [m["name"] for m in MANIFEST["per_layer"]
                  if RANGES in m["workloads"]]
RANGES_TWINS = [("hot_tier_hit_pct.ranges", "hot_tier_hit_pct"),
                ("compiles_in_window.ranges", "compiles_in_window.dash"),
                ("device_idle_pct.ranges", "device_idle_pct.dash")] + [
    (name + ".ranges", name) for name in (
        "api_query_ms", "fetch_ms", "index_match_ms", "decode_host_ms",
        "decode_device_wait_ms", "decode_streams_per_launch", "slab_prep_ms",
        "plan_device_wait_ms", "render_ms", "query_cpu_ms")]
# the read pipeline's parts and the launch-gap ledger's share, one of
# each in .dash and in .ranges
READ_PARTS = ["fetch_legs_per_read", "fetch_plan_ms", "fetch_leg_queue_ms",
              "fetch_leg_wait_ms", "fetch_leg_handover_ms", "fetch_consume_ms"]
DASH_NEW = READ_PARTS + ["device_unlaunched_pct.dash"]
RANGES_NEW = ["block_cache_hit_pct.ranges", "blocks_per_series.ranges",
              "fetch_assemble_ms.ranges"] + [
    name + ".ranges" for name in READ_PARTS] + [
    "device_unlaunched_pct.ranges"]
# TSBS uses/devops: (range, step and window, hosts, fields) of each type
TSBS_RANGES = {"single-groupby-1-1-12": (43200, 60, 1, 1),
               "single-groupby-5-1-12": (43200, 60, 1, 5),
               "cpu-max-all-1": (28800, 3600, 1, 10),
               "cpu-max-all-8.8h": (28800, 3600, 8, 10),
               "double-groupby-1.12h": (43200, 3600, "all", 1)}


def _ranges_cell():
    return manifest._load(BENCH, "workloads", RANGES + ".json")


def _ranges_kind():
    from run import load_traffic

    return load_traffic("promql_ranges").prepare.__globals__


def test_the_ranges_cell_lists_its_metrics_and_no_roofline():
    assert sorted(RANGES_METRICS) == sorted(
        [twin for twin, _orig in RANGES_TWINS] + RANGES_NEW)
    assert not [n for n in RANGES_METRICS if "roofline" in n or "mfu" in n]
    assert all(m["workloads"] == [RANGES] for m in MANIFEST["per_layer"]
               if RANGES in m["workloads"])
    # only the idle share reads the slice, and any program in it will do
    assert [n for n in RANGES_METRICS
            if _metric(n)["reader"].startswith("trace_")] \
        == ["device_idle_pct.ranges"]
    # the cell reports both end-to-end metrics the kind measures
    reported = sorted(m["name"] for m in MANIFEST["end_to_end"]
                      if RANGES in m.get("workloads", []))
    assert reported == sorted(_ranges_kind()["Traffic"].e2e)
    assert "report" not in _ranges_cell()


@pytest.mark.parametrize("twin,original", RANGES_TWINS)
def test_a_ranges_twin_reads_what_its_original_reads(twin, original):
    got, spec = _metric(twin), _metric(original)
    assert (got["reader"], got.get("args", {})) == \
        (spec["reader"], spec.get("args", {}))
    assert (got["unit"], got["layer"], got["source"], got["better"]) \
        == (spec["unit"], spec["layer"], spec["source"], spec["better"])
    assert got["cells"] == [RANGES]


@pytest.mark.parametrize("qtype", sorted(TSBS_RANGES))
def test_a_ranges_query_type_carries_tsbs_own_range_and_step(qtype):
    spec = manifest._load(BENCH, "queries", qtype + ".json")
    range_s, step_s, hosts, fields = TSBS_RANGES[qtype]
    assert (spec["range_s"], spec["step_s"], spec["window_s"]) == \
        (range_s, step_s, step_s)
    assert (spec["hosts"], len(spec["fields"])) == (hosts, fields)
    assert qtype in _ranges_cell()["traffic_params"]["mix"]
    # no `__name__` under `by` and no comparison (PERF.md section 7)
    assert "__name__" not in spec.get("by", []) and ">" not in spec["promql"]


def test_the_ranges_configuration_is_seven_whole_blocks_of_tsbs_cpu_2k():
    cfg = manifest._load(BENCH, "configs", "tsbs-cpu-14h.json")
    base = manifest._load(BENCH, "configs", "tsbs-cpu-2k.json")
    assert cfg["node"] == base["node"]
    assert cfg["guarantees"] == base["guarantees"]
    assert (cfg["scale"], cfg["series"], cfg["interval_s"],
            cfg["history_points"]) == (167, 1670, 10, 5040)
    # 14 h of 10 s readings: seven 2 h blocks, 8.42 M samples
    assert cfg["history_points"] * cfg["interval_s"] == 7 * 2 * 3600
    assert cfg["series"] * cfg["history_points"] == 8_416_800
    assert sorted(cfg["reduced"]) == ["history_points", "scale"]
    assert cfg["rehearse"]["scale"] >= 8 + 4      # cpu-max-all-8.8h draws 8
    assert _ranges_cell()["chips"] == 1


def test_the_window_starts_are_made_from_the_seed_alone_and_fit_the_span():
    from harness import ranges

    kind = _ranges_kind()
    types = {q: manifest._load(BENCH, "queries", q + ".json")
             for q in TSBS_RANGES}
    points = 5040
    starts = {q: ranges.n_starts(spec, points) for q, spec in types.items()}
    # whole minutes on which a 12 h (8 h) window fits 14 h less 10 s
    assert starts["double-groupby-1.12h"] == 120
    assert starts["cpu-max-all-1"] == 360
    deck = sorted(types)

    def made(seed):
        r = kind["Requests"](np.random.default_rng([seed, 3]), deck, types,
                             167, starts)
        return [r.get(i) for i in range(500)]

    a, b, c = made(2**31 + 39), made(2**31 + 39), made(39)
    assert a == b and a != c
    t_first = 1_790_006_400 * ranges.NS
    t_last = t_first + ranges.span_ns(points)
    seen = {q: set() for q in types}
    for name, hosts, offset in a:
        spec = types[name]
        at = ranges.eval_ts(spec, t_first, offset)
        start = int(at[0]) - spec["step_s"] * ranges.NS
        assert t_first <= start and int(at[-1]) <= t_last
        assert (start - t_first) % (60 * ranges.NS) == 0
        assert len(at) == spec["range_s"] // spec["step_s"]
        assert len(set(hosts)) == len(hosts)
        seen[name].add(offset)
    # uniform: a hundred windows of a type start at many minutes
    assert all(len(offsets) > 40 for offsets in seen.values())


def _ranges_window(queries=500):
    """(before, after): /metrics as parsed at the window's two ends."""
    def at(k):
        m = _stage_keys("query_range", {
            "request": 1, "parse_plan": 1, "query_ids": 0.9,
            "read_many": 0.9, "read_many.gather": 0.9, "decode.host": 0.3,
            "decode.device_wait": 0.3, "slab_prep": 0.9,
            "plan.device_wait": 1, "render": 1}, k * queries, 0.05, 0.02)
        m['decode_batch_streams{path="device"}'] = k * queries * 0.3 * 60
        m["storage_hot_tier_hit"] = k * 50.0
        m["storage_hot_tier_miss"] = k * 450.0
        m["storage_block_cache_hit"] = k * 450 * 54 * 0.9
        m["storage_block_cache_miss"] = k * 450 * 54 * 0.1
        m["storage_read_series"] = k * 450 * 9.0
        m["db_read_assemble_seconds_sum"] = k * 450 * 0.02
        m["db_read_assemble_seconds_count"] = k * 450.0
        m["coordinator_request_seconds_sum"] = k * queries * 0.3
        m["coordinator_request_seconds_count"] = float(k * queries)
        m["jit_run[miss]"] = 3.0
        m.update(_read_parts(k * 450, 56))
        return m

    return at(1), at(2)


def _read_parts(reads, legs):
    """What /metrics holds of the read pipeline's histograms after
    `reads` pooled reads of `legs` legs, and of the launch-gap ledger."""
    m = {}
    per_read = {"legs": legs, "plan_seconds": 0.002,
                "leg_queue_seconds": 0.001, "leg_wait_seconds": 0.15,
                "leg_handover_seconds": 0.1, "consume_seconds": 0.02}
    for fam, v in per_read.items():
        m[f"storage_read_{fam}_sum"] = reads * v
        m[f"storage_read_{fam}_count"] = float(reads)
        m[f'storage_read_{fam}_bucket{{le="+Inf"}}'] = float(reads)
    m['compute_launch_gap_seconds{stage="read_many"}'] = reads * 0.03
    m['compute_launch_gap_seconds{stage="(no stage open)"}'] = reads * 0.01
    m["compute_launch_outstanding_seconds"] = reads * 0.01
    return m


# the numbers _read_parts gives, as the readers should read them
READ_PARTS_WANT = {"fetch_plan_ms": 2.0, "fetch_leg_queue_ms": 1.0,
                   "fetch_leg_wait_ms": 150.0, "fetch_leg_handover_ms": 100.0,
                   "fetch_consume_ms": 20.0, "device_unlaunched_pct": 80.0}
_LACKS_READ_PARTS = re.compile(r"storage_read_(?!series)|compute_launch_")


def _ranges_reading(before, after):
    from harness.readers import Reading

    facts = _ranges_kind()["window_facts"](
        _ranges_cell()["traffic_params"]["window_facts"], before, after)
    return Reading(before, after, 51.0, facts, {"hbm_bytes_per_s": 819e9},
                   _trace(), 2.2)


@pytest.mark.parametrize("name", RANGES_METRICS)
def test_a_ranges_metric_reads_a_number_from_a_sound_window(name):
    from harness.readers import READERS

    spec = _metric(name)
    value = READERS[spec["reader"]](_ranges_reading(*_ranges_window()),
                                    **spec.get("args", {}))
    assert isinstance(value, float) and value >= 0.0
    want = {"block_cache_hit_pct.ranges": 90.0,
            "blocks_per_series.ranges": 6.0,
            "fetch_assemble_ms.ranges": 1000 * 450 * 0.02 / 500,
            "hot_tier_hit_pct.ranges": 10.0,
            "api_query_ms.ranges": 300.0, "render_ms.ranges": 50.0,
            "fetch_ms.ranges": 0.9 * 50.0,
            "decode_streams_per_launch.ranges": 60.0,
            "compiles_in_window.ranges": 0.0,
            "fetch_legs_per_read.ranges": 56.0}
    want.update({k + ".ranges": v for k, v in READ_PARTS_WANT.items()})
    if name in want:
        assert value == pytest.approx(want[name])
    if spec["unit"] == "%":
        assert value <= 100.0


@pytest.mark.parametrize("name", RANGES_METRICS)
def test_a_ranges_metric_is_left_out_where_the_program_lacks_its_source(
        name):
    """A program without the block cache's counters, the series
    counter, the assembly's histogram, the read pipeline's histograms or
    the launch-gap ledger: the new metrics' readers return nothing and
    do not raise; the twins read."""
    from harness.readers import READERS

    new = re.compile(r"storage_block_cache_|storage_read_"
                     r"|db_read_assemble_seconds|compute_launch_")
    before, after = ({k: v for k, v in m.items() if not new.search(k)}
                     for m in _ranges_window())
    spec = _metric(name)
    value = READERS[spec["reader"]](_ranges_reading(before, after),
                                    **spec.get("args", {}))
    assert (value is None) == (name in RANGES_NEW)


@pytest.mark.parametrize("name", RANGES_NEW)
def test_a_new_ranges_metric_reads_a_counter_or_histogram_the_program_has(
        name):
    """Each name the new metrics read is a counter or a histogram of
    storage/ or of the launch-gap ledger (read as text: no import of the
    program)."""
    spec = _metric(name)
    regexes = list(spec["args"].get("num", [])) + list(
        spec["args"].get("den", []))
    if spec["reader"] == "fact_ratio":
        facts = _ranges_cell()["traffic_params"]["window_facts"]
        regexes = [rx for fact in (spec["args"]["num"], spec["args"]["den"])
                   for rx in facts[fact]["keys"]]
    if spec["reader"] == "prom_hist_mean":
        regexes = [spec["args"]["family"]]
    text = "".join(open(os.path.join(REPO, "m3_tpu", "storage", f)).read()
                   for f in ("shard.py", "namespace.py"))
    with open(os.path.join(REPO, "m3_tpu", "utils", "dispatch.py")) as f:
        ledger = f.read()
    for rx in regexes:
        m = re.fullmatch(r"storage_(\w+?)_(hit|miss|series)", rx)
        if m:
            assert f'"storage.{m.group(1)}.{m.group(2)}"' in text \
                or f'"storage.{m.group(1)}_{m.group(2)}"' in text
        elif rx.startswith("storage_read_"):
            assert f'"storage.{rx[len("storage_"):]}"' in text
        elif rx.startswith("compute_launch_"):
            leaf = re.match(r"compute_(launch_\w+)", rx).group(1)
            assert f'"compute.{leaf}"' in ledger
        elif rx.startswith("db_"):
            leaf = rx[len("db_"):-len("_sum")]
            assert f'.histogram("{leaf}")' in text
        else:
            assert "query_stage_seconds_count" in rx


# -- the read pipeline's parts in tsbs-cpu-2k.dash ----------------------------


def test_the_dash_cells_new_metrics_are_listed_for_it_alone():
    dash = [m["name"] for m in MANIFEST["per_layer"]
            if DASH in m["workloads"]]
    assert [n for n in dash if n in DASH_NEW] == DASH_NEW
    assert all(m["workloads"] == [DASH] for m in MANIFEST["per_layer"]
               if m["name"] in DASH_NEW)
    # each reads what its .ranges twin reads
    for name in DASH_NEW:
        twin = name.replace(".dash", "") + ".ranges"
        got, spec = _metric(name), _metric(twin)
        assert (got["reader"], got["args"], got["unit"], got["source"],
                got["layer"], got["moves"]) == (
            spec["reader"], spec["args"], spec["unit"], spec["source"],
            spec["layer"], spec["moves"])


def _dash_window():
    """(before, after): the read pipeline's families over a window of
    300 pooled reads of 8 legs."""
    return _read_parts(300, 8), _read_parts(600, 8)


@pytest.mark.parametrize("name", DASH_NEW)
def test_a_new_dash_metric_reads_a_number_from_a_sound_window(name):
    from harness.readers import READERS, Reading

    spec = _metric(name)
    value = READERS[spec["reader"]](
        Reading(*_dash_window(), 51.0, {}, {}), **spec.get("args", {}))
    assert isinstance(value, float)
    want = {"fetch_legs_per_read": 8.0, **READ_PARTS_WANT}
    assert value == pytest.approx(want[name.replace(".dash", "")])


@pytest.mark.parametrize("name", DASH_NEW)
def test_a_new_dash_metric_is_left_out_where_the_program_lacks_it(name):
    from harness.readers import READERS, Reading

    before, after = ({k: v for k, v in m.items()
                      if not _LACKS_READ_PARTS.search(k)}
                     for m in _dash_window())
    spec = _metric(name)
    assert READERS[spec["reader"]](Reading(before, after, 51.0, {}, {}),
                                   **spec.get("args", {})) is None

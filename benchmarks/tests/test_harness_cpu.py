"""The harness, checked where there is no chip.

    python -m pytest benchmarks/tests -q

The generator is seeded (what the data files name: test_manifest.py); the
plain reference agrees with the program's own float64 interpreter; a
``--rehearse`` run of each cell ends in a well-formed last line; each
cell's control comes out not correct; and a run with the timed path
broken underneath comes out not correct.
"""

import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

from harness import compare, reference, tsbs  # noqa: E402

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
MEASURED = [w["name"] for w in MANIFEST["workloads"]]


def _files(kind):
    return sorted(glob.glob(os.path.join(BENCH, kind, "*.json")))


def _load(path):
    with open(path) as f:
        return json.load(f)


CELLS = [_load(p)["name"] for p in _files("workloads")]


class TestGenerator:
    def test_same_seed_same_bytes_other_seed_other_bytes(self):
        times = (1_790_000_000 + np.arange(5) * 10) * tsbs.NS
        bodies = []
        for seed in (2**31 + 11, 2**31 + 11, 12):
            fleet = tsbs.Fleet(seed, 7)
            vals = tsbs.walk(seed, fleet.n_series, 5)
            bodies.append(tsbs.write_body(fleet, vals, times, 0, 70, 1, 4))
        assert bodies[0] == bodies[1]
        assert bodies[0][0] != bodies[2][0]
        assert bodies[0][1] == 70 * 3

    def test_bodies_are_valid_remote_write(self):
        from m3_tpu.utils import protowire, snappy

        fleet = tsbs.Fleet(3, 4)
        vals = tsbs.walk(3, fleet.n_series, 6)
        times = (1_790_000_000 + np.arange(6) * 10) * tsbs.NS
        body, n = tsbs.write_body(fleet, vals, times, 10, 30, 2, 5)
        series = protowire.decode_write_request(snappy.decompress(body))
        assert sum(len(ts.samples) for ts in series) == n == 60
        for s, ts in zip(range(10, 30), series):
            assert tuple(ts.labels) == tuple(sorted(fleet.labels(s).items()))
            assert [v for _, v in ts.samples] == vals[s, 2:5].tolist()
            assert [t for t, _ in ts.samples] == \
                (times[2:5] // 1_000_000).tolist()

    def test_walk_is_tsbs_shaped(self):
        v = tsbs.walk(9, 50, 40)
        assert ((v >= 0) & (v <= 100)).all()
        assert np.array_equal(v, np.floor(v))
        assert np.abs(np.diff(v, axis=1)).max() <= 6


class TestTraffic:
    """promql_closed_loop's requests: decks by weight, hosts uniform and
    distinct, all from the seed."""

    @staticmethod
    def _requests(seed, weights=(1, 1, 1), hosts=50):
        from run import load_traffic   # the kind's module, found by name

        kind = load_traffic("promql_closed_loop")
        types = {"a": {"hosts": 1}, "b": {"hosts": 8}, "c": {"hosts": "all"}}
        deck = [n for n, w in zip(types, weights) for _ in range(w)]
        return kind.__init__.__globals__["Requests"](
            np.random.default_rng([seed, 3]), deck, types, hosts)

    def test_same_seed_same_requests_other_seed_others(self):
        a, b, c = (self._requests(s) for s in (2**31 + 7, 2**31 + 7, 8))
        made = [[r.get(i) for i in range(300)] for r in (a, b, c)]
        assert made[0] == made[1] and made[0] != made[2]
        # whatever the seed: the same numbers of the same sizes
        for seq in made:
            assert sorted(n for n, _ in seq) == ["a"] * 100 + ["b"] * 100 \
                + ["c"] * 100

    def test_every_deck_holds_its_weights_and_hosts_are_distinct(self):
        r = self._requests(5, weights=(2, 1, 1))
        seen = set()
        for d in range(200):
            deck = [r.get(4 * d + j) for j in range(4)]
            assert sorted(n for n, _ in deck) == ["a", "a", "b", "c"]
            for name, hosts in deck:
                assert len(hosts) == {"a": 1, "b": 8, "c": 0}[name]
                assert len(set(hosts)) == len(hosts)
                seen.update(hosts)
        assert seen == set(range(50))    # uniform: every host is drawn


class TestNode:
    def test_the_rendered_yaml_loads_as_the_files_node_block(self):
        from harness.client import Node, duration_ns, to_yaml
        from m3_tpu.utils.config import parse_yaml

        for path in _files("configs"):
            cfg = _load(path)
            node = Node(cfg)
            doc = node.rendered("/w/m3data", "/w/kv.json")
            assert parse_yaml(to_yaml(doc)) == doc
            want = json.loads(json.dumps(cfg["node"]["coordinator"]))
            want["db"]["path"] = "/w/m3data"
            want.setdefault("cluster", {})["kv_path"] = "/w/kv.json"
            assert doc == want
            r = cfg["node"]["coordinator"]["db"]["options"]["retention"]
            assert node.block_ns == duration_ns(r["block_size"])
            assert node.n_shards == \
                cfg["node"]["coordinator"]["db"]["n_shards"]
        assert duration_ns("10m") == 600 * tsbs.NS
        with pytest.raises(ValueError):
            duration_ns("soon")


@pytest.mark.parametrize("path", _files("queries"), ids=os.path.basename)
class TestQueryTypes:
    HAND = {  # least bytes at 3 hosts, 360 loaded points, counted by hand
        "single-groupby-1-1-1": 1 * 360 * 16 + 1 * 60 * 8,
        "single-groupby-1-8-1": 8 * 360 * 16 + 8 * 60 * 8,
        "single-groupby-5-8-1": 40 * 360 * 16 + 40 * 60 * 8,
        "cpu-max-all-8": 80 * 360 * 16 + 80 * 1 * 8,
        "double-groupby-1": 3 * 360 * 16 + 3 * 1 * 8,
    }

    def test_template_parses_and_least_bytes_match_a_hand_count(self, path):
        from m3_tpu.query import promql

        spec = _load(path)
        assert NAME.fullmatch(spec["name"])
        assert os.path.basename(path) == spec["name"] + ".json"
        n = 3 if spec["hosts"] == "all" else spec["hosts"]
        promql.parse(reference.promql(spec, list(range(n))))
        if spec["name"] in self.HAND:
            assert reference.least_bytes(spec, 3, 360) == \
                self.HAND[spec["name"]]
        else:
            assert reference.least_bytes(spec, 3, 360) > 0

    def test_reference_agrees_with_the_programs_interpreter(self, path,
                                                            monkeypatch):
        """The plain reference imports nothing of the program; here the
        program's float64 numpy interpreter is its witness."""
        for k in ("M3_TPU_DEVICE_OPS", "M3_TPU_NATIVE_OPS",
                  "M3_TPU_QUERY_COMPILE"):
            monkeypatch.setenv(k, "0")
        from m3_tpu.query.engine import Engine
        from m3_tpu.query.windows import RaggedSeries

        spec = _load(path)
        fleet = tsbs.Fleet(4, 12)
        vals = tsbs.walk(4, fleet.n_series, 360)
        times = 1_790_000_000 * tsbs.NS + np.arange(360) * tsbs.INTERVAL_NS
        hosts = [] if spec["hosts"] == "all" else list(range(
            2, 2 + spec["hosts"]))
        labels, eval_ts, want = reference.evaluate(spec, fleet, vals, times,
                                                   hosts)
        rows = reference.series_of(spec, fleet, hosts)

        class OverArrays(Engine):
            def _fetch(self, sel, eval_ts, range_ns):
                shifted = self._resolve_ts(sel, eval_ts)
                t_min = int(shifted[0]) - max(range_ns, self.lookback_ns)
                cols = np.nonzero((times >= t_min)
                                  & (times < int(shifted[-1]) + 1))[0]
                return ([fleet.labels(s) for s in rows], RaggedSeries(
                    np.tile(times[cols], len(rows)),
                    vals[np.ix_(rows, cols)].reshape(-1),
                    np.arange(len(rows) + 1, dtype=np.int64) * len(cols)))

        start, end, step = reference.grid(spec, int(times[0]),
                                          int(times[-1]))
        vec, ts = OverArrays(None, "default", resolve_tiers=False) \
            .query_range(reference.promql(spec, hosts), start, end, step)
        assert np.array_equal(ts, eval_ts)
        served = {"status": "success", "data": {"result": [
            {"metric": {k.decode(): v.decode() for k, v in lb.items()},
             "values": [[t / tsbs.NS, repr(float(v))]
                        for t, v in zip(ts.tolist(), row.tolist())
                        if not np.isnan(v)]}
            for lb, row in zip(vec.labels, vec.values)]}}
        bad, gap, n = compare.matrix_gap(served, labels, eval_ts, want)
        assert bad is None and n > 0
        assert gap <= 1e-15


class TestCompare:
    LABELS = [{"hostname": "a"}, {"hostname": "b"}]
    TS = np.array([60, 120, 180], np.int64) * tsbs.NS
    REF = np.array([[1.0, np.nan, 3.0], [np.nan] * 3])

    def _served(self, values, metric=None):
        return {"status": "success", "data": {"result": [
            {"metric": metric or {"hostname": "a"}, "values": values}]}}

    def test_equal_within_the_gap_reported(self):
        bad, gap, n = compare.matrix_gap(
            self._served([[60.0, "1.0"], [180.0, "3.0000000003"]]),
            self.LABELS, self.TS, self.REF)
        assert bad is None and n == 2
        assert 0.9e-10 < gap < 1.1e-10

    @pytest.mark.parametrize("values,metric", [
        ([[60.0, "1.0"]], None),                                   # a step missing
        ([[60.0, "1.0"], [120.0, "2.0"], [180.0, "3.0"]], None),   # NaN step filled
        ([[60.0, "1.0"], [180.0, "3.0"]], {"hostname": "c"}),      # another series
    ])
    def test_shape_differences_are_wrong(self, values, metric):
        bad, _, _ = compare.matrix_gap(self._served(values, metric),
                                       self.LABELS, self.TS, self.REF)
        assert bad is not None

    def test_repeated_label_sets_pair_in_value_order(self):
        labels = [{"hostname": "a"}, {"hostname": "a"}]
        ref = np.array([[5.0, 6.0, 7.0], [1.0, 2.0, 3.0]])
        rows = [[[60.0, "1.0"], [120.0, "2.0"], [180.0, "3.0"]],
                [[60.0, "5.0"], [120.0, "6.0"], [180.0, "7.0"]]]
        served = {"status": "success", "data": {"result": [
            {"metric": {"hostname": "a"}, "values": r} for r in rows]}}
        bad, gap, n = compare.matrix_gap(served, labels, self.TS, ref)
        assert bad is None and gap == 0.0 and n == 6


def _run(cell, *extra, launcher=None):
    code = ("import sys; sys.path.insert(0, %r); import run; "
            "sys.exit(run.main(%r, launcher=%r))" % (
                BENCH, ["--workload", cell, "--seed", str(2**31 + 5),
                        "--seconds", "3", "--trace", "0", "--rehearse",
                        *extra], launcher))
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r


@pytest.mark.parametrize("cell", CELLS)
class TestRehearsedRun:
    def test_last_line_is_well_formed_and_the_control_fails(self, cell):
        r = _run(cell, "--control")
        lines = r.stdout.strip().splitlines()
        doc = json.loads(lines[-1])
        assert list(doc)[:5] == ["correct", "attempted", "failed",
                                 "metrics", "device"]
        assert list(doc)[-1] == "checks"
        assert doc["correct"] is True and doc["failed"] == 0
        assert doc["attempted"] > 0
        assert doc["device"]["platform"] == "cpu"
        assert set(doc["device"]) >= {"platform", "kind", "count",
                                      "memory_peak_bytes"}
        if cell in MEASURED:
            want = {m["name"] for m in MANIFEST["end_to_end"]
                    if cell in m.get("workloads", [cell])}
            assert set(doc["metrics"]) == want
        assert "setup_s" in doc["metrics"] and len(doc["metrics"]) >= 2
        for m in doc["metrics"].values():
            assert m["value"] > 0 and UNIT.fullmatch(m["unit"])
        for name, c in doc["checks"].items():
            assert f"check {name}:" in r.stderr
        control = json.loads(next(
            ln for ln in lines if ln.startswith("control: "))[9:])
        assert control["correct"] is False

    def test_a_fault_where_answers_are_produced_is_not_correct(self, cell):
        r = _run(cell, launcher=os.path.join(BENCH, "tests",
                                             "faulty_serve.py"))
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert doc["correct"] is False
        assert "FAILS" in r.stderr


def test_off_tpu_there_is_no_result():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         MEASURED[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        assert not line.startswith('{"correct"')

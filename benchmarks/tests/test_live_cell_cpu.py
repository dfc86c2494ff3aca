"""The live cell ``tsbs-cpu-2k-live.dash`` and its traffic kind
``promql_beside_scrape``, checked where there is no chip.

    python -m pytest benchmarks/tests/test_live_cell_cpu.py -q

A ``--rehearse`` run comes out ``correct`` with at least one rotation and
one snapshot inside it, and none of its controls does (the float32
reference, an ack record with one sample in two hundred altered, held
against the read-back and against the disk); a traced line's metric
names are the manifest's for the cell (the driver's rule, before the
driver); a service that drops one acked sample in a thousand
(``faulty_live_serve.py``) comes out not correct, by the read-back's
count and the disk's, and by nothing of the read path. A store that
acks without appending is held against the disk in tier 1
(``tests/test_live_deployment.py``). The scrape schedule and the
readers are held in tier 1 (``tests/test_benchmark_manifest.py``).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

from harness import readback, tsbs  # noqa: E402

CELL = "tsbs-cpu-2k-live.dash"
LIVE_CHECKS = ["acked_samples_compared", "acked_samples_missing",
               "acked_samples_wrong", "samples_unasked_for",
               "write_requests_refused", "durable_samples_missing",
               "durable_samples_wrong", "snapshot_streams_wrong",
               "rotations_in_run", "snapshots_in_run",
               "durable_samples_compared"]


class TestReadBack:
    FLEET = tsbs.Fleet(3, 4)
    BITS = np.arange(40 * 3, dtype=np.uint64).reshape(40, 3) + 1000

    def _sent(self, acked=True):
        return readback.sent_samples(
            self.FLEET, [1, 2],
            [(0, 0, 2, 5000, True), (1, 0, 2, 15000, acked),
             (0, 2, 4, 6000, True)], self.BITS)

    def _returned(self, sent):
        return {k: {t: b for t, (b, _a) in row.items()}
                for k, row in sent.items()}

    def test_what_was_sent_is_what_is_wanted(self):
        sent = self._sent()
        assert len(sent) == 20                    # hosts 1 and 2 only
        row = sent[(b"cpu_usage_user", b"host_1")]
        assert row == {5000: (1030, True), 15000: (1031, True)}
        assert readback.acked_gap(self._returned(sent), sent)[:4] == \
            (30, 0, 0, 0)

    def test_missing_wrong_and_unasked_are_counted_apart(self):
        sent = self._sent()
        got = self._returned(sent)
        del got[(b"cpu_usage_user", b"host_1")][5000]
        got[(b"cpu_usage_idle", b"host_2")][6000] += 1
        got[(b"cpu_usage_idle", b"host_2")][7000] = 5
        n, missing, wrong, unasked, fault = readback.acked_gap(got, sent)
        assert (n, missing, wrong, unasked) == (30, 1, 1, 1) and fault

    def test_an_altered_record_differs_in_each_altered_sample(self):
        sent = self._sent()
        altered = readback.altered(sent, 7)
        n, missing, wrong, unasked, fault = readback.acked_gap(
            self._returned(sent), altered)
        assert (n, missing, wrong, unasked) == (30, 0, 30 // 7, 0) and fault
        assert readback.altered(sent, 31) == sent

    def test_a_sample_of_a_refused_request_may_come_back_or_not(self):
        sent = self._sent(acked=False)
        got = self._returned(sent)
        assert readback.acked_gap(got, sent)[:4] == (20, 0, 0, 0)
        for row in got.values():
            row.pop(15000, None)
        assert readback.acked_gap(got, sent)[:4] == (20, 0, 0, 0)


def _run(*extra, launcher=None, seconds="8", trace="0"):
    code = ("import sys; sys.path.insert(0, %r); import run; "
            "sys.exit(run.main(%r, launcher=%r))" % (
                BENCH, ["--workload", CELL, "--seed", str(2**31 + 36),
                        "--seconds", seconds, "--trace", trace, "--rehearse",
                        *extra], launcher))
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r


def test_a_rehearsed_run_is_correct_across_a_rotation_and_its_controls_are_not():
    r = _run("--control", seconds="12")
    lines = r.stdout.strip().splitlines()
    doc = json.loads(lines[-1])
    assert doc["correct"] is True and doc["failed"] == 0
    checks = doc["checks"]
    assert set(LIVE_CHECKS) <= set(checks) and "streams_wrong" in checks
    assert checks["rotations_in_run"]["value"] >= 1
    assert checks["snapshots_in_run"]["value"] >= 1
    assert checks["acked_samples_compared"]["value"] >= 240
    for name in LIVE_CHECKS[1:8]:
        assert checks[name]["value"] == 0
    assert checks["durable_samples_compared"]["value"] == \
        checks["acked_samples_compared"]["value"]
    assert any(ln.startswith("service stopped in ") for ln in lines)
    # an untraced line holds the end-to-end metrics the cell reports
    assert set(doc["metrics"]) == {"query_p95_ms", "setup_s"}
    assert any(ln.startswith("measured, not reported by this cell: "
                             '{"query_rate"') for ln in lines)
    scrapes = json.loads(next(
        ln for ln in lines if ln.startswith("scrapes in the window: "))[23:])
    assert scrapes["n"] >= 1 and scrapes["failed"] == 0
    # queries and scrape requests of the window together
    n_queries = int(next(ln for ln in lines if ln.startswith("all: n="))
                    .split()[1][2:])
    assert doc["attempted"] == n_queries + scrapes["n"]
    control = json.loads(next(
        ln for ln in lines if ln.startswith("control: "))[9:])
    assert control["correct"] is False
    assert control["checks"]["max_rel_err"]["value"] > 1e-12
    assert control["checks"]["acked_samples_wrong"]["value"] >= 1
    assert control["checks"]["durable_samples_wrong"]["value"] >= 1
    assert "control, float32 reference: not correct" in lines
    for where in ("read back", "on disk"):
        assert (f"control, one sent sample in 200 altered, {where}: "
                "not correct") in lines


def test_a_traced_lines_metric_names_are_the_manifests_for_the_cell():
    """The driver refuses a traced line whose metric names are not
    exactly those that list the cell. On the CPU a trace has no device
    plane, so the one metric that reads it is left out, and the
    rehearsal's 240 series all fit the decoded-block cache, so no decoder
    launch is counted; every other one has to be there, and nothing
    else."""
    r = _run(seconds="12", trace="1")
    lines = r.stdout.strip().splitlines()
    doc = json.loads(lines[-1])
    assert doc["correct"] is True
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]
                  if CELL in m["workloads"]}
    assert len(listed) == 26
    device_only = {n for n, m in listed.items()
                   if m["source"] == "device_trace"}
    assert device_only == {"device_idle_pct.live"}
    assert doc["metrics"]["decode_device_wait_ms.live"]["value"] == 0
    assert set(doc["metrics"]) == set(listed) - device_only - {
        "decode_streams_per_launch.live"}
    for name, m in doc["metrics"].items():
        assert m["unit"] == listed[name]["unit"] and m["value"] >= 0
    facts = json.loads(next(
        ln for ln in lines if ln.startswith("facts: "))[7:])
    assert facts["window_tick_cycles"] >= 1
    assert doc["metrics"]["tick_ms"]["value"] == pytest.approx(
        facts["window_tick_ms"] / facts["window_tick_cycles"])
    assert doc["metrics"]["snapshot_device_wait_ms"]["value"] > 0
    assert any(ln.startswith("warm-up went on for ") and " closed" in ln
               for ln in lines)


def test_a_service_that_drops_an_acked_sample_is_not_correct():
    # 240 samples a round: the thousandth is in the fifth interval
    r = _run(launcher=os.path.join(BENCH, "tests", "faulty_live_serve.py"),
             seconds="45")
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["correct"] is False
    checks = doc["checks"]
    assert checks["acked_samples_missing"]["value"] >= 1
    assert checks["durable_samples_missing"]["value"] >= 1
    assert "check acked_samples_missing:" in r.stderr and "FAILS" in r.stderr
    # nothing else of the run is at fault
    for name in ("answers_wrong", "answers_failed", "streams_wrong",
                 "acked_samples_wrong", "samples_unasked_for",
                 "write_requests_refused"):
        assert checks[name]["value"] == 0
    assert checks["max_rel_err"]["value"] <= 1e-12

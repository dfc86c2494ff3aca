"""The cell ``tsbs-cpu-14h.ranges`` and its traffic kind ``promql_ranges``,
checked where there is no chip.

    python -m pytest benchmarks/tests/test_ranges_cell_cpu.py -q

A ``--rehearse`` run (12 hosts, the seven blocks) comes out ``correct``
with every answer compared and 200 (series, block) streams drawn over
all seven blocks, and its float32 control does not; a traced line holds
the manifest's metrics for the cell but those the CPU cannot give; a
service whose flush writes one value of an older block's streams one
unit in the last place off (``faulty_block_serve.py``) comes out not
correct by the stream check alone, which shows that it covers the older
blocks. The reference, the windows and the readers are held in tier 1
(``tests/test_benchmark_manifest.py``, ``tests/test_cross_block_reads.py``).
"""

import json
import os
import re
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "tsbs-cpu-14h.ranges"


def _run(*extra, launcher=None, trace="0"):
    code = ("import sys; sys.path.insert(0, %r); import run; "
            "sys.exit(run.main(%r, launcher=%r))" % (
                BENCH, ["--workload", CELL, "--seed", str(2**31 + 39),
                        "--seconds", "8", "--trace", trace, "--rehearse",
                        *extra], launcher))
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    return r, lines, json.loads(lines[-1])


def _blocks_verified(lines):
    line = next(ln for ln in lines if ln.startswith("verified "))
    return int(re.search(r"over (\d+) blocks", line).group(1))


def test_a_rehearsed_run_is_correct_over_seven_blocks_and_its_control_is_not():
    _r, lines, doc = _run("--control")
    assert doc["correct"] is True and doc["failed"] == 0
    checks = doc["checks"]
    assert checks["streams_compared"]["value"] == 200
    assert checks["streams_wrong"]["value"] == 0
    assert _blocks_verified(lines) == 7
    assert checks["max_rel_err"]["value"] <= 1e-12
    # every answer of the window and the prime's
    assert checks["answers_compared"]["value"] == doc["attempted"] + 1
    assert any(ln.startswith("flush: 56 volumes") for ln in lines)
    control = json.loads(next(
        ln for ln in lines if ln.startswith("control: "))[9:])
    assert control["correct"] is False
    assert control["checks"]["max_rel_err"]["value"] > 1e-12


def test_a_traced_lines_metric_names_are_the_manifests_for_the_cell():
    """On the CPU a trace has no device plane, so the idle share is left
    out, and the rehearsal's 840 streams all fit the decoded-block cache,
    so no decoder launch is counted; every other metric is there."""
    _r, lines, doc = _run(trace="1")
    assert doc["correct"] is True
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]
                  if CELL in m["workloads"]}
    assert len(listed) == 16
    assert set(doc["metrics"]) == set(listed) - {
        "device_idle_pct.ranges", "decode_streams_per_launch.ranges"}
    for name, m in doc["metrics"].items():
        assert m["unit"] == listed[name]["unit"] and m["value"] >= 0
    # the read spans the blocks: 5.3 at 12 hosts, about 6 at 167
    assert doc["metrics"]["blocks_per_series.ranges"]["value"] > 4.5
    assert doc["metrics"]["fetch_assemble_ms.ranges"]["value"] > 0


def test_an_older_blocks_altered_stream_is_not_correct():
    r, lines, doc = _run(launcher=os.path.join(BENCH, "tests",
                                               "faulty_block_serve.py"))
    assert doc["correct"] is False
    checks = doc["checks"]
    assert checks["streams_wrong"]["value"] >= 1
    assert "check streams_wrong:" in r.stderr and "FAILS" in r.stderr
    assert any(ln.startswith("first wrong stream: ") for ln in lines)
    # one unit in the last place: the answers cannot see it
    assert checks["answers_wrong"]["value"] == 0
    assert checks["max_rel_err"]["value"] <= 1e-12

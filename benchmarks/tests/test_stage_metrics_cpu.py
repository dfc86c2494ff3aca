"""The per-layer metrics that read the program's stage clock
(``query_stage_seconds{route,stage}``, ``query_stage_cpu_seconds``), in a
rehearsed traced run on the CPU.

    python -m pytest benchmarks/tests -q

One ``run.py --rehearse --trace 1``: its last line carries the nine
metrics beside every older one that a run without a device plane can
carry (the two rooflines and the idle share read the device's trace).
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "tsbs-cpu-2k.dash"
STAGE_METRICS = ["parse_plan_ms", "index_match_ms", "fetch_ms",
                 "decode_host_ms", "decode_device_wait_ms", "slab_prep_ms",
                 "plan_device_wait_ms", "render_ms"]

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


@pytest.fixture(scope="module")
def traced_line():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 26), "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_line_carries_the_new_metrics_beside_the_old(traced_line):
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]
                 if CELL in m["workloads"]}
    # how many: the manifest's own count (test_manifest.py holds it to the
    # files), not a number written here (it read 17 while they grew to 20)
    off_device = {n for n, m in per_layer.items()
                  if m["source"] != "device_trace"}
    assert off_device >= set(STAGE_METRICS) | {"query_cpu_ms"}
    metrics = traced_line["metrics"]
    # the rehearsal's 240 series all fit the block cache: a window that
    # launches no decoder has no streams a launch to report
    assert off_device - {"decode_streams_per_launch"} <= set(metrics) \
        <= off_device
    assert traced_line["correct"] is True and traced_line["failed"] == 0
    for name in metrics:
        assert metrics[name]["unit"] == per_layer[name]["unit"]
        assert metrics[name]["value"] >= 0


def test_the_stages_add_up_to_the_handlers_own_clock(traced_line):
    """Eight stage metrics and the `request` and `eval` self-times make
    the mean wall time of a query_range; `api_query_ms` times the same
    extent from outside, the harness's few /metrics scrapes with it."""
    metrics = {k: v["value"] for k, v in traced_line["metrics"].items()}
    stages = sum(metrics[n] for n in STAGE_METRICS)
    assert 0 < stages <= metrics["api_query_ms"] * 1.05
    assert stages >= 0.5 * metrics["api_query_ms"]
    # one thread's CPU cannot pass its wall time
    assert 0 < metrics["query_cpu_ms"] <= metrics["api_query_ms"] * 1.5

"""BENCHMARK.json against the files it names, one case a name so that each
counts: pure JSON (and the names of harness/readers.py), no JAX, a second
on any machine.

    python -m pytest benchmarks/tests/test_manifest.py -q

ISSUE 35 asked for this file as ``tests/test_benchmark_manifest.py`` (tier
1); a `benchmark` PR may add nothing outside ``benchmarks/``, so it stands
here until a PR of another kind gives ``tests/`` a file that imports it.
"""

import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness.readers import READERS  # noqa: E402  (imports no JAX)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = _load(REPO, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("entry", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_entry_has_its_file_and_its_reader(entry):
    spec = _load(BENCH, "layer_metrics", entry["name"] + ".json")
    assert spec["name"] == entry["name"]
    assert (spec["unit"], spec["layer"], spec["source"], spec["moves"],
            spec["better"]) == (entry["unit"], entry["layer"],
                                entry["source"], entry["moves"],
                                entry["better"])
    assert spec["cells"] == entry["workloads"]
    assert spec["reader"] in READERS
    assert NAME.fullmatch(entry["name"]) and UNIT.fullmatch(entry["unit"])
    assert entry["source"] in SOURCES
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # it moves an end-to-end metric that each of its cells reports
    moved = E2E[entry["moves"]]
    for cell in entry["workloads"]:
        assert cell in CELLS and cell in moved.get("workloads", CELLS)
    # a roofline or an mfu is a share of a peak, in %
    if entry["name"].endswith("_roofline") or "mfu" in entry["name"]:
        assert entry["unit"] == "%" and entry["better"] == "higher"


@pytest.mark.parametrize("entry", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_entry_is_bounded_and_its_workloads_are_cells(entry):
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    assert NAME.fullmatch(entry["name"]) and UNIT.fullmatch(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("host_clock", "device_trace")
    assert 0.01 <= entry["bound"] <= 0.25
    for cell in entry.get("workloads", []):
        assert cell in CELLS
    if entry["name"] == "setup_s":
        assert "workloads" not in entry     # every cell reports it


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_workload_has_its_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    cell = _load(BENCH, "workloads", w["name"] + ".json")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (w["name"], w["config"], w["traffic"], w["chips"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["config"] in [c["name"] for c in MANIFEST["configs"]]
    assert os.path.exists(os.path.join(BENCH, "traffic",
                                       w["traffic"] + ".py"))
    # a traced slice is the window's end: at most half of it
    assert 0 < cell["trace_seconds"] <= 0.5 * MANIFEST["run_seconds"]
    # every cell reports setup_s, another end-to-end metric and a layer's
    assert any(w["name"] in m.get("workloads", CELLS)
               for m in MANIFEST["end_to_end"] if m["name"] != "setup_s")
    assert any(w["name"] in m["workloads"] for m in MANIFEST["per_layer"])
    params = cell["traffic_params"]
    assert 1 <= params.get("warm_clean_rounds", 1) <= params["warm_rounds"]


def _query_types():
    out = []
    for w in MANIFEST["workloads"]:
        params = _load(BENCH, "workloads",
                       w["name"] + ".json")["traffic_params"]
        for q in list(params.get("mix", {})) + [
                q["type"] for q in params.get("prime", [])]:
            if (w["name"], q) not in out:
                out.append((w["name"], q))
    return out


@pytest.mark.parametrize("cell,qtype", _query_types())
def test_query_type_named_by_a_cell_has_its_file(cell, qtype):
    spec = _load(BENCH, "queries", qtype + ".json")
    assert spec["name"] == qtype and NAME.fullmatch(qtype)
    assert "promql" in spec and spec["fn"] in ("max_over_time",
                                               "avg_over_time")


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_has_its_file_and_a_cell(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    doc = _load(REPO, cfg["file"])
    assert (doc["name"], doc["source"]) == (cfg["name"], cfg["source"])
    assert sorted(doc["reduced"]) == sorted(cfg["reduced"])
    assert len(cfg["reduced"]) <= 16 and 1 <= len(cfg["source"]) <= 200
    assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])
    assert doc["guarantees"]        # the deployment states what it promises


def test_the_manifest_keeps_the_contracts_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in MANIFEST["end_to_end"]
             + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS)) <= 24
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) \
        <= max(1, len(CELLS) // 2)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def _files(kind):
    return sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(BENCH, kind)) if f.endswith(".json"))


@pytest.mark.parametrize("name", _files("layer_metrics"))
def test_layer_metric_file_names_what_exists(name):
    """From the file's side: also one that only an unmeasured cell lists."""
    spec = _load(BENCH, "layer_metrics", name + ".json")
    assert spec["name"] == name and NAME.fullmatch(name)
    assert UNIT.fullmatch(spec["unit"]) and spec["source"] in SOURCES
    assert spec["better"] in ("lower", "higher")
    assert spec["reader"] in READERS
    for cell in spec["cells"]:
        kind = _load(BENCH, "workloads", cell + ".json")["traffic"]
        traffic = open(os.path.join(BENCH, "traffic", kind + ".py")).read()
        assert f'"{spec["moves"]}"' in traffic      # the kind reports it
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    # in the manifest exactly where a measured cell reads it
    assert (name in listed) == any(c in CELLS for c in spec["cells"])


@pytest.mark.parametrize("name", _files("workloads"))
def test_cell_file_names_files_that_exist(name):
    cell = _load(BENCH, "workloads", name + ".json")
    assert cell["name"] == name
    for kind, named, ext in (("configs", cell["config"], ".json"),
                             ("traffic", cell["traffic"], ".py")):
        assert os.path.exists(os.path.join(BENCH, kind, named + ext))
    params = cell["traffic_params"]
    for q in list(params.get("mix", {})) + [
            q["type"] for q in params.get("prime", [])]:
        assert os.path.exists(os.path.join(BENCH, "queries", q + ".json"))


def test_peaks_are_the_published_ones():
    peaks = _load(BENCH, "harness", "peaks.json")
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9

"""chip_smoke.py, checked where there is no chip.

The script itself must fail here (no TPU). Its pieces, imported, drive
the same phases against a service on the CPU at 50 hosts: the generator's
remote-write bodies are acked, the flushed volumes decode under the
scalar decoder, every sample reads back bit-for-bit, and the served
query answers equal the script's own float64 reference.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


class TestWorkload:
    def test_seeded_and_tsbs_shaped(self):
        a = chip_smoke.Workload(7, 12, 9, 7200 * chip_smoke.NS)
        b = chip_smoke.Workload(7, 12, 9, 7200 * chip_smoke.NS)
        c = chip_smoke.Workload(8, 12, 9, 7200 * chip_smoke.NS)
        assert a.n_series == 120 and a.values.shape == (120, 9)
        assert np.array_equal(a.values, b.values)
        assert a.host_tags == b.host_tags
        assert not np.array_equal(a.values, c.values)
        assert np.array_equal(np.diff(a.times_ns),
                              np.full(8, 10 * chip_smoke.NS))
        assert ((a.values >= 0) & (a.values <= 100)).all()
        assert np.array_equal(a.values, np.floor(a.values))
        lb = a.labels(13)
        assert lb[b"__name__"] == b"cpu_usage_nice"
        assert lb[b"hostname"] == b"host_1"
        assert set(lb) == {
            b"__name__", b"hostname", b"region", b"datacenter", b"rack",
            b"os", b"arch", b"team", b"service", b"service_version",
            b"service_environment"}

    def test_write_bodies_are_valid_remote_write(self):
        """The numpy-laid-out bodies parse under the repo's own prompb
        decoder to exactly the generated samples."""
        from m3_tpu.utils import protowire, snappy

        wl = chip_smoke.Workload(1, 5, 7, 1_758_000_000 * chip_smoke.NS)
        seen = {}
        total = 0
        for body, n in wl.write_requests(2, 3):
            series = protowire.decode_write_request(snappy.decompress(body))
            assert sum(len(ts.samples) for ts in series) == n
            total += n
            for ts in series:
                seen.setdefault(tuple(ts.labels), []).extend(ts.samples)
        assert total == wl.n_series * wl.points
        for s in range(wl.n_series):
            got = seen[tuple(sorted(wl.labels(s).items()))]
            assert [t for t, _ in got] == \
                (wl.times_ns // 1_000_000).tolist()
            assert [v for _, v in got] == wl.values[s].tolist()

    def test_block_is_sealed_by_time(self):
        now = 1_758_000_123 * chip_smoke.NS
        bs = chip_smoke.block_start_for(now)
        assert bs % chip_smoke.BLOCK_NS == 0
        assert bs + chip_smoke.BLOCK_NS + chip_smoke.BUFFER_PAST_NS <= now
        assert bs + 2 * chip_smoke.BLOCK_NS + chip_smoke.BUFFER_PAST_NS > now


class TestCompare:
    def _ref(self):
        from m3_tpu.query.engine import Vector

        return Vector([{b"region": b"a"}, {b"region": b"b"}],
                      np.array([[1.0, np.nan, 3.0], [np.nan] * 3]))

    def _served(self, values):
        return {"status": "success", "data": {"resultType": "matrix",
                "result": [{"metric": {"region": "a"}, "values": values}]}}

    def test_equal_within_tolerance_passes(self):
        ts = np.array([60, 120, 180], np.int64) * chip_smoke.NS
        res = chip_smoke.compare_matrix(
            self._served([[60.0, "1.0"], [180.0, "3.0000000000001"]]),
            self._ref(), ts, 1e-12, "t")
        assert res["series"] == 1 and res["values"] == 2

    @pytest.mark.parametrize("values", [
        [[60.0, "1.0"], [180.0, "3.1"]],                 # value off
        [[60.0, "1.0"]],                                 # a step missing
        [[60.0, "1.0"], [120.0, "2.0"], [180.0, "3.0"]],  # NaN step filled
    ])
    def test_differences_fail(self, values):
        ts = np.array([60, 120, 180], np.int64) * chip_smoke.NS
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.compare_matrix(self._served(values), self._ref(),
                                      ts, 1e-12, "t")


class TestReferenceRunsWithoutJax:
    def test_reference_never_imports_jax(self):
        """The parent computes every reference answer itself and must stay
        off JAX: one process per chip."""
        code = (
            "import sys, time\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "import chip_smoke\n"
            "chip_smoke.pin_reference_rungs()\n"
            "wl = chip_smoke.Workload(1, 12, 40, 7200 * chip_smoke.NS)\n"
            "ref = chip_smoke.reference_engine(wl)\n"
            "start, end, step = chip_smoke.query_grid(wl)\n"
            "for spec in chip_smoke.queries(wl):\n"
            "    vec, _ = ref.query_range(spec['q'], start, end, step)\n"
            "    assert len(vec.labels), spec\n"
            "assert 'jax' not in sys.modules\n"
            "print('JAX-FREE')\n")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("M3_TPU_")}
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        assert "JAX-FREE" in r.stdout


class TestServedOnCpu:
    def test_phases_agree_at_50_hosts(self, tmp_path, monkeypatch):
        """The whole of run_phases against the real service process on the
        CPU, its device rungs forced so the same counters are checked."""
        import time

        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "M3_TPU_DEVICE_OPS": "1",
                    "M3_TPU_QUERY_COMPILE": "1",
                    "PYTHONPATH": REPO + os.pathsep
                    + env.get("PYTHONPATH", "")})
        for k in ("M3_TPU_DEVICE_OPS", "M3_TPU_NATIVE_OPS",
                  "M3_TPU_QUERY_COMPILE"):
            monkeypatch.setenv(k, "0")  # what pin_reference_rungs() sets
        svc = chip_smoke.Service(str(tmp_path), env, REPO)
        try:
            wl = chip_smoke.Workload(
                3, 50, 40, chip_smoke.block_start_for(time.time_ns()))
            port, backend = svc.wait_listening(120.0)
            assert backend["platform"] == "cpu"
            client = chip_smoke.Client(f"http://127.0.0.1:{port}")
            report = chip_smoke.run_phases(
                client, wl, svc.data_dir, svc.log_path, 1,
                hosts_per_request=20, points_per_request=16,
                hosts_per_read=25, scalar_sample=40, flush_timeout_s=120.0)
        finally:
            svc.stop()
        assert report["ingest"]["samples_acked"] == 500 * 40
        assert report["flush"]["blocks_flushed"] >= chip_smoke.N_SHARDS
        assert report["filesets"]["series"] == 500
        assert report["readback"]["bit_exact"]
        assert report["readback"]["decode_groups_on_device"] > 0
        assert set(report["queries_cold"]["queries"]) == {
            "narrow", "wide_grouped", "rate", "minmax", "heavy_matcher"}
        assert not any(report["queries_warm"]["jit_misses"].values())
        assert report["rungs"]["m3tsz_encode_device"] > 0
        assert svc.proc.poll() is not None  # the child is gone


class TestResultLine:
    def test_exactly_the_contract_keys(self):
        """The driver refuses a last line with any other key."""
        doc = json.loads(chip_smoke.result_line(True, {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "extra": "dropped"}))
        assert doc == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
        assert list(doc) == ["ok", "device"]
        assert "\n" not in chip_smoke.result_line(False, doc["device"])

    def test_main_prints_summary_then_result(self, monkeypatch, capsys):
        """A passing run on an accelerator: summary (ending `"claim":
        null`) on the line before the last, the bare result last."""
        class FakeService:
            data_dir = log_path = "unused"

            def __init__(self, work, env, checkout):
                pass

            def wait_listening(self, timeout_s):
                return 1, {"compile_cache": "x"}

            def stop(self):
                pass

            def log_tail(self, n=3000):
                return ""

        class FakeClient:
            def __init__(self, base):
                pass

            def get_json(self, path):
                return {"backend": {
                    "platform": "tpu", "device_kind": "TPU v5 lite",
                    "jax": "0.9.0", "devices": [{"id": 0}]}}

        monkeypatch.setattr(chip_smoke, "Service", FakeService)
        monkeypatch.setattr(chip_smoke, "Client", FakeClient)
        monkeypatch.setattr(chip_smoke, "pin_reference_rungs", lambda: None)
        monkeypatch.setattr(chip_smoke, "run_phases",
                            lambda *a, **k: {"phases": "ran"})
        monkeypatch.delitem(sys.modules, "jax", raising=False)
        assert chip_smoke.main(["--hosts", "2", "--points", "40"]) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert json.loads(lines[-1]) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
        assert lines[-2].endswith('"claim": null}')
        assert json.loads(lines[-2])["report"] == {"phases": "ran"}

        def boom(*a, **k):
            raise chip_smoke.SmokeFailure("a phase failed")

        monkeypatch.setattr(chip_smoke, "run_phases", boom)
        assert chip_smoke.main(["--hosts", "2", "--points", "40"]) != 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert json.loads(lines[-1])["ok"] is False
        assert set(json.loads(lines[-1])) == {"ok", "device"}


class TestScriptFailsWithoutAChip:
    def test_cpu_platform_exits_nonzero_and_says_so(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--hosts", "5", "--points", "40"],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode != 0
        assert "platform 'cpu'" in r.stderr
        assert "JAX_PLATFORMS='cpu'" in r.stderr
        # no result line: nothing on stdout parses as the summary object
        for line in r.stdout.splitlines():
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            assert not (isinstance(doc, dict) and "ok" in doc)

    def test_alone_in_a_directory_exits_nonzero(self, tmp_path):
        import shutil

        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode != 0
        assert r.stdout.strip() == ""

"""Backend start-up (utils/backend): initialised once where a service
starts, before it listens; dispatch follows jax.default_backend(); the
compile cache is placed from outside or at <checkout>/.jax_cache."""

import os
import subprocess
import sys
import threading
import time

import pytest

from m3_tpu.utils import backend, dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def init_calls(monkeypatch):
    """Replace backend.init with a recorder that notes, at call time,
    whether the service under test already listens."""
    calls = []
    probe = {"listening": lambda: None}

    def fake_init(log=None):
        calls.append(probe["listening"]())
        return {"platform": "cpu"}

    monkeypatch.setattr(backend, "init", fake_init)
    return calls, probe


def _run_until_listening(svc, listening, stop):
    t = threading.Thread(target=svc.run, daemon=True)
    t.start()
    deadline = time.time() + 60
    while not listening() and time.time() < deadline:
        time.sleep(0.02)
    assert listening(), "service never listened"
    stop()
    t.join(30)
    assert not t.is_alive()


class TestServicesInitialiseBeforeListening:
    def test_coordinator(self, tmp_path, init_calls):
        from m3_tpu.services.coordinator import CoordinatorService

        calls, probe = init_calls
        svc = CoordinatorService({
            "db": {"path": str(tmp_path / "db"), "n_shards": 2},
            "http": {"host": "127.0.0.1", "port": 0},
            "carbon": {"enabled": False}})
        probe["listening"] = lambda: svc.api._server is not None
        _run_until_listening(svc, probe["listening"], svc._stop.set)
        assert calls == [False]  # once, and before the listener opened

    def test_dbnode(self, tmp_path, init_calls):
        from m3_tpu.services.dbnode import DBNodeService

        calls, probe = init_calls
        svc = DBNodeService({
            "db": {"path": str(tmp_path / "n"), "n_shards": 2,
                   "namespaces": [{"name": "default"}]},
            "http": {"host": "127.0.0.1", "port": 0}})
        probe["listening"] = lambda: svc.api._server is not None
        _run_until_listening(svc, probe["listening"], svc._stop.set)
        assert calls == [False]

    def test_aggregator(self, init_calls):
        from m3_tpu.services.aggregator import AggregatorService

        calls, probe = init_calls
        svc = AggregatorService({"instance_id": "a1", "n_shards": 2,
                                 "ingest": {"host": "127.0.0.1", "port": 0}})
        probe["listening"] = lambda: svc.consumer is not None
        _run_until_listening(svc, probe["listening"], svc._stop.set)
        assert calls == [False]


class TestInit:
    def test_reports_what_jax_reports_and_is_idempotent(self, monkeypatch,
                                                        tmp_path):
        import jax

        # this process's compile cache stays where it was (off)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(backend, "_info", None)
        logged = []

        class Log:
            def info(self, msg, **fields):
                logged.append((msg, fields))

        first = backend.init(Log())
        assert first["platform"] == jax.devices()[0].platform == "cpu"
        assert first["device_kind"] == jax.devices()[0].device_kind
        assert first["devices"] == len(jax.devices())
        assert first["jax"] == jax.__version__
        assert backend.init(Log()) is first
        assert [m for m, _ in logged] == ["backend initialised"]
        assert logged[0][1]["platform"] == "cpu"

    def test_a_backend_that_cannot_start_raises(self):
        """No fallback: the platform JAX_PLATFORMS names must come up."""
        code = ("from m3_tpu.utils import backend\n"
                "backend.init()\n")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "no_such_platform"
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode != 0
        assert "no_such_platform" in r.stderr


class TestDispatchFollowsDefaultBackend:
    @pytest.mark.parametrize("name,want", [("tpu", True), ("gpu", True),
                                           ("cpu", False)])
    def test_accelerator_present(self, monkeypatch, name, want):
        import jax

        monkeypatch.setattr(dispatch, "_accel_cache", None)
        monkeypatch.setattr(jax, "default_backend", lambda: name)
        monkeypatch.delenv("M3_TPU_DEVICE_OPS", raising=False)
        assert dispatch._accelerator_present() is want
        assert dispatch.use_device(1 << 20) is want
        assert dispatch.use_device(8) is False  # under the threshold


class TestCompileCachePlacement:
    def test_env_set_means_the_code_sets_nothing(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert backend.compile_cache_dir() is None

    def test_unset_resolves_to_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert backend.compile_cache_dir() == os.path.join(REPO,
                                                           ".jax_cache")

    def _cache_dir_after_init(self, tmp_path, env_dir):
        code = ("import jax\n"
                "from m3_tpu.utils import backend\n"
                "info = backend.init()\n"
                "print(jax.config.jax_compilation_cache_dir)\n"
                "print(info['compile_cache'])\n")
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        env["PYTHONPATH"] = REPO
        r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 0, r.stderr
        return r.stdout.split()

    def test_init_leaves_an_outside_cache_alone(self, tmp_path):
        outside = str(tmp_path / "outside")
        assert self._cache_dir_after_init(tmp_path, outside) == \
            [outside, outside]

    def test_init_sets_the_checkout_cache_from_any_cwd(self, tmp_path):
        want = os.path.join(REPO, ".jax_cache")
        assert self._cache_dir_after_init(tmp_path, None) == [want, want]

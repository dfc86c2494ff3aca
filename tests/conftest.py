"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths
(pjit/shard_map over jax.sharding.Mesh) compile and execute without TPU
hardware; the driver separately dry-runs the multi-chip path and benches on
a real chip.
"""

import os

# Tests run on the CPU: set before anything imports jax.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Two-lane split (SURVEY §4): `run_tests.sh fast` deselects these
# wall-clock-heavy files (multi-process clusters with real timeouts, XLA
# codec-parity sweeps) via the `slow` marker; the full lane runs all.
SLOW_FILES = {
    "test_multinode.py",      # quorum tests ride real client timeouts
    "test_tpu_int_codec.py",  # XLA int-codec parity sweep (many compiles)
    "test_m3tsz_tpu.py",      # XLA codec parity sweep
    "test_em_dtest.py",       # spawns a node cluster via the em agent
    "test_kvd.py",            # lease TTL / failover wall-clock waits
    "test_race_stress.py",    # thread storms
}


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: excluded from the fast lane")
    config.addinivalue_line(
        "markers",
        "chaos: seeded long-loop fault-injection runs; excluded from tier-1 "
        "(implies slow), opt-in via `run_tests.sh chaos`",
    )


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in SLOW_FILES:
            item.add_marker(pytest.mark.slow)
        if item.get_closest_marker("chaos") is not None:
            # chaos loops ride the slow marker too, so every existing
            # `-m 'not slow'` lane (tier-1 included) skips them
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def rng():
    return np.random.default_rng(42)

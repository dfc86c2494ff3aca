"""The native libraries are built to a name of the builder's own and
renamed into place (PERF.md section 7 until PR 37: built in place, one of
six xdist workers on a fresh checkout could ``ctypes.CDLL`` a half-written
file, pin ``None`` for its life and fail or skip every native test it
held). Several processes start at once against a copy of ``native/``
that holds the sources and no library; every one of them has to load
both libraries and get a right answer from each.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
from m3_tpu.encoding.m3tsz import native
from m3_tpu.ops import native_hostops
native._SRC = os.path.join({copy!r}, "m3tsz.cpp")
native._SO = os.path.join({copy!r}, "libm3tsz.so")
native_hostops._SRC = os.path.join({copy!r}, "hostops.cpp")
native_hostops._SO = os.path.join({copy!r}, "libm3hostops.so")
assert native.load() is not None, "m3tsz"
assert native_hostops.load() is not None, "hostops"
from m3_tpu.utils.xtime import TimeUnit
t = 1_790_000_000_000_000_000 + np.arange(5, dtype=np.int64) * 10**10
v = np.arange(5, dtype=np.float64)
got_t, got_v = native.decode_series(
    native.encode_series(t, v, int(t[0]), TimeUnit.SECOND), TimeUnit.SECOND)
assert np.array_equal(got_t, t) and np.array_equal(got_v, v), (got_t, got_v)
print("loaded")
"""


@pytest.mark.skipif(shutil.which("g++") is None, reason="no compiler")
def test_processes_that_start_together_all_load_a_whole_library(tmp_path):
    copy = tmp_path / "native"
    copy.mkdir()
    for name in ("m3tsz.cpp", "hostops.cpp"):
        shutil.copy(os.path.join(REPO, "native", name), copy / name)
    code = CHILD.format(repo=REPO, copy=str(copy))
    env = {k: v for k, v in os.environ.items()
           if k not in ("M3TSZ_SO", "M3HOSTOPS_SO")}
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True) for _ in range(6)]
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0 and out.strip() == "loaded", err[-2000:]
    # nothing is left beside the two libraries and their sources
    assert sorted(os.listdir(copy)) == [
        "hostops.cpp", "libm3hostops.so", "libm3tsz.so", "m3tsz.cpp"]

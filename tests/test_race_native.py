"""ThreadSanitizer race detection for the native layer (SURVEY §5 race
detection; the `go test -race` equivalent the Python-side stress tests
can't provide for GIL-free native threads)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tsan_available() -> bool:
    try:
        out = subprocess.run(["g++", "-print-file-name=libtsan.so"],
                             capture_output=True, text=True, timeout=30)
        path = out.stdout.strip()
        return bool(path) and os.path.exists(path)
    except (OSError, subprocess.SubprocessError):
        return False


pytestmark = pytest.mark.skipif(not _tsan_available(),
                                reason="no libtsan on this toolchain")


def test_harness_detects_a_planted_race(tmp_path):
    """Sensitivity check: the TSan setup must flag a known race (else a
    clean run of the real libraries proves nothing)."""
    src = tmp_path / "racy.cpp"
    src.write_text(
        '#include <thread>\n'
        'extern "C" long racy_sum(int iters) {\n'
        '    long counter = 0;\n'
        '    std::thread a([&]{ for (int i = 0; i < iters; i++) counter++; });\n'
        '    std::thread b([&]{ for (int i = 0; i < iters; i++) counter++; });\n'
        '    a.join(); b.join();\n'
        '    return counter;\n'
        '}\n')
    so = tmp_path / "libracy.so"
    subprocess.run(["g++", "-O1", "-g", "-fsanitize=thread", "-shared",
                    "-fPIC", "-pthread", "-o", str(so), str(src)],
                   check=True, timeout=120)
    libtsan = subprocess.run(["g++", "-print-file-name=libtsan.so"],
                             capture_output=True, text=True,
                             check=True).stdout.strip()
    env = dict(os.environ)
    env.update({"LD_PRELOAD": libtsan, "TSAN_OPTIONS": "exitcode=66",
                "JAX_PLATFORMS": "cpu"})
    r = subprocess.run(
        [sys.executable, "-c",
         f"import ctypes; lib = ctypes.CDLL({str(so)!r}); "
         "lib.racy_sum.restype = ctypes.c_long; lib.racy_sum(100000)"],
        env=env, capture_output=True, timeout=120)
    assert r.returncode == 66, "TSan failed to flag the planted race"


def test_native_libraries_are_race_free():
    """The real check: threaded codec + hostops workloads under TSan."""
    # budget covers race_check's own worst case: two cold TSan builds
    # (180s each) plus the 600s instrumented-child limit
    r = subprocess.run([sys.executable, "-m", "m3_tpu.tools.race_check"],
                       cwd=_REPO, capture_output=True, text=True,
                       timeout=1000)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])

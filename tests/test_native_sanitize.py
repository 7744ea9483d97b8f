"""Sanitizer lane for the native C++ (neighbor list + partitioner).

The reference ships no TSAN/ASAN configs (SURVEY §5 'race detection:
none'); here the address-sanitized build of the OpenMP 2-pass
prefix-sum/fill and atomic-CAS border detection runs the full native test
files in a subprocess (LD_PRELOAD of libasan into an uninstrumented
python; leak checking off — CPython itself 'leaks' at exit). `make tsan`
in neighbors/src builds the thread-sanitized variant for manual runs.
"""

import os
import shutil
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "distmlip_tpu", "neighbors", "src")


def _libasan():
    gxx = shutil.which("g++")
    if gxx is None:
        return None
    out = subprocess.run([gxx, "-print-file-name=libasan.so"],
                         capture_output=True, text=True)
    path = out.stdout.strip()
    return path if os.path.isabs(path) and os.path.exists(path) else None


def test_native_suite_clean_under_asan():
    lib = _libasan()
    if lib is None:
        pytest.skip("libasan not available")
    build = subprocess.run(["make", "-s", "-C", _SRC, "asan"],
                           capture_output=True, text=True)
    assert build.returncode == 0, build.stderr
    asan_so = os.path.join(_REPO, "distmlip_tpu", "neighbors",
                           "_native_asan.so")
    # FIRST assert the instrumented lib is the one that loads — otherwise
    # a broken LD_PRELOAD would make this test vacuously green.
    env_prefix = (f"DISTMLIP_TPU_NATIVE_LIB={asan_so} LD_PRELOAD={lib} "
                  f"ASAN_OPTIONS=detect_leaks=0:halt_on_error=1:exitcode=66 ")
    check = subprocess.run(
        ["bash", "-c",
         env_prefix + f"{sys.executable} -c \"from "
         f"distmlip_tpu.neighbors.native import _build_and_load, _LIB_PATH;"
         f" _build_and_load();"
         f" assert _LIB_PATH.endswith('_native_asan.so'), _LIB_PATH\""],
        cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert check.returncode == 0, (check.stdout[-1000:], check.stderr[-1000:])
    r = subprocess.run(
        ["bash", "-c",
         env_prefix + f"{sys.executable} -m pytest tests/test_neighbors.py "
         f"tests/test_partition.py -q -x"],
        cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "ERROR: AddressSanitizer" not in r.stderr

"""Drive the C++ unit-test tier (reference tests/cpp, run via `make test`).

Builds src/cc/test_io from source and runs it; the binary asserts
RecordIO framing, threaded batcher ordering/sharding, image decode
pipeline behavior (corrupt-record skip, CHW layout, epoch mechanics).
"""
import shutil
import subprocess
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src" / "cc"


@pytest.mark.skipif(shutil.which("make") is None or shutil.which("g++") is None,
                    reason="native toolchain unavailable")
def test_native_io_cpp_suite(tmp_path):
    build = subprocess.run(["make", "-B", "-C", str(SRC), "test_io"],
                           capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr
    run = subprocess.run([str(SRC / "test_io"), str(tmp_path)],
                         capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "ALL NATIVE IO TESTS PASSED" in run.stdout

"""CLI smoke tests for bench.py on the smoke corpus under
JAX_PLATFORMS=cpu (tests/conftest.py), so it cannot rot between chip
runs."""

import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("args", [["--tpu-child"], [], ["--scale"]])
def test_bench_refuses_cpu(args):
    """bench.py measures the chip or nothing: without a TPU it exits
    non-zero naming the platform, and prints no result line."""
    import os

    env = dict(os.environ, MRI_TPU_BENCH_CORPUS=str(
        REPO_ROOT / "tests" / "fixtures" / "smoke" / "docs"))
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "bench.py"), *args],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(REPO_ROOT))
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"metric"' not in proc.stdout

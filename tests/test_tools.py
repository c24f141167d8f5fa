"""CLI smoke tests for the measurement tools (tools/measure_tpu.py,
bench.py): end to end on the smoke corpus under JAX_PLATFORMS=cpu
(tests/conftest.py), so they cannot rot between chip runs."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent


def test_measure_tpu_cli_smoke_on_cpu():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "measure_tpu.py"),
         "--quick",
         "--corpus", str(REPO_ROOT / "tests" / "fixtures" / "smoke" / "docs")],
        capture_output=True, text=True, timeout=420, cwd=str(REPO_ROOT))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    header, engines = lines[0], lines[1:]
    assert header["device"]["platform"] == "cpu"
    labels = [e["engine"] for e in engines]
    assert labels == ["cpu_native", "overlap_0.5", "overlap_0.5_1win",
                      "device_tokenize_oneshot"]
    for e in engines:
        assert e["e2e_ms"] > 0
        assert e["phases_ms"]
    # non-reference corpus: every tpu engine is cross-checked against
    # the cpu backend's md5
    assert all(e["md5_ok"] for e in engines if "md5_ok" in e)
    assert sum("md5_ok" in e for e in engines) == 3


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


def test_profile_stream_stages_smoke_on_cpu():
    """The stream-stage profiler replicates DeviceStreamEngine.feed's
    staging by hand; this smoke run is the drift guard — if feed()'s
    staging changes and the serialized replication desynchronizes, the
    tool crashes or its pair count diverges from the generator's
    ground truth."""
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "profile_stream_stages.py"),
         "--docs", "3000", "--vocab", "500",
         "--chunk", "1000"],
        capture_output=True, text=True, timeout=420, cwd=str(REPO_ROOT))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    full = lines[-1]
    assert full["windows"] == 3
    assert full["serialized_wall_s"] > 0 and full["pipelined_feed_wall_s"] > 0
    for k in ("host_prep_s", "upload_s", "window_rows_s", "merge_s",
              "finalize_s"):
        assert k in full
    # ground truth from the same deterministic generator
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        synthetic_manifest,
    )

    m = synthetic_manifest(num_docs=3000, vocab_size=500, tokens_per_doc=40,
                           seed=11)
    pairs = {(w, i) for i in range(3000)
             for w in m.read_doc(i).split()}
    assert full["unique_pairs"] == len(pairs)

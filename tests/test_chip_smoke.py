"""chip_smoke.py on the CPU: its phases at a tiny size, its checks, and
the pieces of the program it leans on (compile-cache placement, device
engines that fail loudly and answer exactly)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO_ROOT

import chip_smoke as cs

PKG = "parallel_computation_of_an_inverted_index_using_map_reduce_tpu"

#: the children see one device, as on a one-chip machine
ONE_DEVICE = "--xla_force_host_platform_device_count=1"


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """Every one-chip build plan, once, on a tiny corpus."""
    wd = tmp_path_factory.mktemp("chip_smoke")
    lp = cs.write_build_corpus(wd, num_docs=40, vocab_size=500,
                               tokens_per_doc=200, seed=7)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XLA_FLAGS", ONE_DEVICE)
        return cs.phase_build(wd, lp, cs.BUILD_PLANS, clock=cs._Clock(600))


@pytest.mark.parametrize("plan", list(cs.BUILD_PLANS))
def test_build_plan_matches_oracle(built, plan):
    rec = next(r for r in built if r["plan"] == plan)
    assert rec["md5"] == rec["md5_oracle"]
    assert rec["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert rec["compile_s"] is not None


def test_serve_answers_equal_host_engine(built, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)
    art = next(r["out_dir"] for r in built if r["plan"] == "pipelined")
    rec = cs.phase_serve(cs.Path(art), clock=cs._Clock(300))
    assert rec["engine"] == "device" and rec["requests"] == 20
    assert rec["mismatches"] == 0
    assert rec["device"]["platform"] == "cpu"


def test_stream_engines_match_cpu_backend(tmp_path, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)
    recs = cs.phase_stream(tmp_path, clock=cs._Clock(300), num_docs=3000,
                           vocab_size=2000, tokens_per_doc=40, seed=11,
                           chunk_docs=1000)
    assert [r["engine"] for r in recs] == ["host_stream", "device_stream"]
    for r in recs:
        assert r["md5"] == r["md5_cpu"] and r["stream_windows"] == 3


def _rec(platform="tpu", kind="TPU v5 lite", count=1, **kw):
    return {"phase": "build", "device": {"platform": platform, "kind": kind,
                                         "count": count}, **kw}


def test_check_device_refuses_cpu():
    with pytest.raises(cs.PhaseError, match="'cpu', not 'tpu'"):
        cs.check_device([_rec(platform="cpu", kind="cpu")], 1)


@pytest.mark.parametrize("records,count,match", [
    ([_rec(), _rec(kind="TPU v4")], 1, "disagree"),
    ([_rec(count=4)], 1, "expected 1"),
])
def test_check_device_refuses_mismatch(records, count, match):
    with pytest.raises(cs.PhaseError, match=match):
        cs.check_device(records, count)


def test_check_device_accepts_one_tpu():
    assert cs.check_device([_rec(), _rec()], 1) == _rec()["device"]


def test_check_dedup_compiled_needs_the_compiled_kernel():
    recs = [_rec(plan="pipelined", dedup_kernel=None),
            _rec(plan="stream", dedup_kernel="pallas-interpret")]
    with pytest.raises(cs.PhaseError, match="compiled Pallas"):
        cs.check_dedup_compiled(recs)
    recs[1]["dedup_kernel"] = "pallas"
    cs.check_dedup_compiled(recs)


def test_check_spread_refuses_one_device():
    cs.check_spread([_rec(plan="m", device_shards=4, device_spread=4),
                     {"phase": "serve", "shard_devices": 4}], 4)
    with pytest.raises(cs.PhaseError, match="spread over 1"):
        # the count asked for is not where the arrays live
        cs.check_spread([_rec(plan="m", device_shards=4, device_spread=1)],
                        4)


@pytest.fixture(scope="module")
def mesh_built(tmp_path_factory):
    """The --chips 4 build plans on four virtual devices, tiny corpus."""
    wd = tmp_path_factory.mktemp("chip_smoke_mesh")
    lp = cs.write_build_corpus(wd, num_docs=40, vocab_size=500,
                               tokens_per_doc=200, seed=7)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        return cs.phase_build(wd, lp, cs.MESH_PLANS, clock=cs._Clock(600))


@pytest.mark.parametrize("plan", list(cs.MESH_PLANS))
def test_mesh_build_spreads_over_four_devices(mesh_built, plan):
    rec = next(r for r in mesh_built if r["plan"] == plan)
    assert rec["md5"] == rec["md5_oracle"]
    assert rec["device_shards"] == 4 and rec["device_spread"] == 4
    cs.check_spread([rec], 4)


def test_sharded_serve_spreads_over_four_devices(mesh_built, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    art = next(r["out_dir"] for r in mesh_built
               if r["plan"] == "mesh_pipelined")
    rec = cs.phase_serve(cs.Path(art), clock=cs._Clock(300),
                         env={"MRI_SERVE_SHARDS": "4"})
    assert rec["mismatches"] == 0 and rec["shard_devices"] == 4
    cs.check_spread([rec], 4)


def test_script_refuses_cpu():
    """The whole script on the CPU: non-zero, names the platform, and
    prints no result line."""
    proc = subprocess.run([sys.executable, str(REPO_ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=600,
                          cwd=str(REPO_ROOT))
    assert proc.returncode != 0
    assert "'cpu', not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo the script fails and prints no
    result line."""
    shutil.copy(REPO_ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


# -- compile cache -------------------------------------------------------


def test_cache_dir_default_and_env(monkeypatch):
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.utils import (
        device,
    )

    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    assert device.cache_dir() == str(REPO_ROOT / ".jax_cache")
    monkeypatch.setenv(device.CACHE_ENV, "/some/where")
    assert device.cache_dir() == "/some/where"


@pytest.mark.parametrize("from_env", [True, False])
def test_setup_places_cache(tmp_path, from_env):
    """With the variable set, JAX uses it and setup() sets no other
    path; without it, the cache goes to <checkout>/.jax_cache."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = ("import jax; from " + PKG + ".utils import device; "
            "device.setup(); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=str(REPO_ROOT), check=True).stdout.split()[-1]
    want = tmp_path / "cc" if from_env else REPO_ROOT / ".jax_cache"
    assert out == str(want)


def test_cli_build_writes_cache_to_env_dir(tmp_path):
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
        write_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        write_corpus, zipf_corpus,
    )

    docs = zipf_corpus(num_docs=8, vocab_size=100, tokens_per_doc=30, seed=3)
    write_manifest(tmp_path / "list.txt", write_corpus(tmp_path / "d", docs))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    subprocess.run([sys.executable, "-m", PKG, "1", "1",
                    str(tmp_path / "list.txt"), "--backend=tpu",
                    f"--output-dir={tmp_path / 'out'}"], env=env,
                   cwd=str(REPO_ROOT), check=True, timeout=300)
    assert any(p.name.endswith("-cache") for p in (tmp_path / "cc").iterdir())


# -- the program pieces the smoke run leans on --------------------------


def test_build_report_names_the_device(built):
    rec = next(r for r in built if r["plan"] == "stream")
    assert rec["dedup_kernel"] == "xla"  # the CPU takes the XLA dedup


def test_dedup_kernel_names_only_this_runs_trace(tmp_path):
    """A run that traces no dedup does not report an earlier run's."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, InvertedIndexModel,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        synthetic_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
        engine,
    )

    m = synthetic_manifest(num_docs=50, vocab_size=300, tokens_per_doc=20,
                           seed=5)
    engine.dedup_kernel = "pallas"  # as an earlier run's trace leaves it
    # the default plan's sort takes pre-deduplicated keys: no dedup
    report = InvertedIndexModel(IndexConfig(backend="tpu")).run(
        m, str(tmp_path / "o"))
    assert "dedup_kernel" not in report and engine.dedup_kernel is None


@pytest.fixture(scope="module")
def artifact_dir(built):
    return cs.Path(next(r["out_dir"] for r in built
                        if r["plan"] == "pipelined"))


def test_auto_engine_raises_when_device_engine_fails(artifact_dir,
                                                     monkeypatch):
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve import (
        device_engine, engine,
    )

    def refuse(*a, **kw):
        raise RuntimeError("TPU held by another process")

    monkeypatch.setattr(device_engine.DeviceEngine, "__init__", refuse)
    monkeypatch.setenv("MRI_SERVE_CROSSOVER", "1")
    auto = engine.AutoEngine(artifact_dir)
    try:
        with pytest.raises(RuntimeError, match="held by another"):
            auto.df(auto.encode_batch(["a"]))
    finally:
        auto.close()


@pytest.mark.parametrize("planner", ["exhaustive", "bmw", "maxscore"])
def test_device_bm25_is_byte_equal_to_host(artifact_dir, monkeypatch,
                                           planner):
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.device_engine import (
        DeviceEngine,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.engine import (
        Engine,
    )

    monkeypatch.setenv("MRI_SERVE_NATIVE", "0")
    monkeypatch.setenv("MRI_SERVE_PLANNER", planner)
    host, dev = Engine(artifact_dir), DeviceEngine(artifact_dir)
    try:
        terms = cs._pick_terms(host)
        queries = [[terms["hot"][i], terms["mid"][i], terms["rare"][i]]
                   for i in range(4)]
        queries += [[terms["hot"][0], terms["hot"][0]], [terms["rare"][1]]]
        for q in queries:
            for k in (1, 10, 50):
                want = host.top_k_scored(host.encode_batch(q), k)
                got = dev.top_k_scored(dev.encode_batch(q), k)
                assert json.dumps(got) == json.dumps(want), (q, k)
    finally:
        dev.close()
        host.close()


def test_cpu_backend_reads_whole_virtual_documents(tmp_path):
    """A virtual corpus's sizes are estimates: the cpu backend's window
    reader must not truncate documents to them."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, InvertedIndexModel, oracle_index,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        synthetic_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.text.formatter import (
        letters_md5,
    )

    m = synthetic_manifest(num_docs=3000, vocab_size=2000, tokens_per_doc=40,
                           seed=11)
    oracle_index(m, tmp_path / "oracle")
    InvertedIndexModel(IndexConfig(backend="cpu")).run(m, str(tmp_path / "c"))
    assert letters_md5(tmp_path / "c") == letters_md5(tmp_path / "oracle")


def test_window_reader_generates_virtual_documents_once():
    """Each virtual document is generated once per window read, whole."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.io import (
        WindowArena, read_window_into,
    )

    class Virtual:
        paths = ("v0", "v1", "v2")
        sizes = (1, 1, 1)  # estimates, shorter than the documents
        calls = []

        def __len__(self):
            return 3

        def doc_id(self, i):
            return i + 1

        def read_doc(self, i):
            self.calls.append(i)
            return b"doc %d words" % i

    v = Virtual()
    arena = read_window_into(v, 0, 3, WindowArena())
    assert v.calls == [0, 1, 2]
    assert arena.contents() == [b"doc 0 words", b"doc 1 words",
                                b"doc 2 words"]

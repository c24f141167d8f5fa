"""Program spans on the profiler's clock (``obs/timing.py``): the device
engine's ops and steps, its BM25 memo counters, the daemon's dispatcher
spans, the build's pack span and the device programs' names, read back
from a JAX profiler trace with the benchmark's own reader
(``benchmark.devtrace.load_events``).

On the CPU backend a jitted program shows in the trace as the host event
``PjitFunction(<name>)``; on a TPU the same name, ``jit_<name>``, labels
the device plane's ``XLA Modules`` line."""

import glob
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark.devtrace import load_events
from conftest import REPO_ROOT
from test_daemon import Client, serving
from test_serve import build_corpus

from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
    IndexConfig, InvertedIndexModel, read_manifest, write_manifest,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
    zipf_corpus,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.parallel.mesh import (
    make_mesh,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve import (
    device_engine as de,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.artifact import (
    artifact_path,
)

PKG = "parallel_computation_of_an_inverted_index_using_map_reduce_tpu"
#: every op the device engine times (``describe()["ops"]``)
OPS = {"df", "postings", "and", "or", "top_k", "top_k_scored"}


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """A v2.1 artifact over a small Zipf corpus."""
    docs = zipf_corpus(num_docs=150, vocab_size=600, tokens_per_doc=120,
                       seed=5)
    return build_corpus(tmp_path_factory.mktemp("spans"), docs)


def _frequent(eng, n: int) -> list[bytes]:
    """The ``n`` terms of highest df."""
    order = np.argsort(-np.asarray(eng._h_df), kind="stable")[:n]
    return [eng.artifact.term(int(i)) for i in order]


def _traced(tmp_path, fn) -> list[tuple]:
    """``fn()`` under a JAX profiler session; the trace's events."""
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    return load_events(path)


def test_engine_spans_and_programs_reach_the_profiler(built, tmp_path):
    """A fresh engine's first ranked and postings calls: the op, both
    steps and the named programs land in the trace (each first call
    compiles, so every span is well over the reader's 100 us floor)."""
    eng = de.DeviceEngine(artifact_path(built))
    try:
        terms = eng.encode_batch(_frequent(eng, 4))

        def calls():
            assert eng.top_k_scored(terms, 5)
            assert all(r is not None for r in eng.postings(terms))

        names = {e[2] for e in _traced(tmp_path, calls)}
    finally:
        eng.close()
    assert {"serve.op.top_k_scored", "serve.op.postings",
            "serve.step.device", "serve.step.rescore"} <= names
    assert any("serve_bm25" in n for n in names), sorted(names)
    assert any("serve_decode" in n for n in names), sorted(names)
    assert any("serve_lookup" in n for n in names), sorted(names)


def test_engine_ops_keep_their_names_and_hold_the_steps(built):
    """``describe()["ops"]`` names exactly the ops (no step among them,
    since ``serve.engine_ms`` sums every entry), and the device step,
    which runs inside the ops, totals no more than they do."""
    eng = de.DeviceEngine(artifact_path(built))
    try:
        terms = eng.encode_batch(_frequent(eng, 3))
        eng.df(terms)
        eng.postings(terms)
        eng.query_and(terms)
        eng.query_or(terms)
        eng.top_k(eng.artifact.term(0)[:1].decode(), 3)
        eng.top_k_scored(terms, 5)
        d = eng.describe()
    finally:
        eng.close()
    assert set(d["ops"]) == OPS
    assert set(d["steps"]) == {"device", "rescore"}
    ops_ms = sum(v["total_ms"] for v in d["ops"].values())
    assert 0 < d["steps"]["device"]["total_ms"] <= ops_ms
    assert d["steps"]["device"]["calls"] >= len(OPS)


def test_bm25_memo_counts_and_a_repeat_hits(built):
    eng = de.DeviceEngine(artifact_path(built))
    try:
        terms = eng.encode_batch(_frequent(eng, 3))
        first = eng.top_k_scored(terms, 5)
        d1 = eng.describe()
        assert eng.top_k_scored(terms, 5) == first
        d2 = eng.describe()
    finally:
        eng.close()
    m1, m2 = d1["bm25_memo"], d2["bm25_memo"]
    assert m1["misses"] >= 3  # every term decoded once on the host
    assert m2["misses"] == m1["misses"]
    assert m2["hits"] >= m1["hits"] + 3
    for step in ("device", "rescore"):
        assert d2["steps"][step]["calls"] > d1["steps"][step]["calls"]


@pytest.mark.daemon
@pytest.mark.serve
def test_daemon_batch_and_reply_spans_and_uptime(built):
    """Each engine-answered request is one ``reply``; a result-cache hit,
    answered on the reader thread, is none.  ``uptime_s`` grows."""
    with serving(built, engine="device") as daemon, Client(daemon) as c:
        s0 = c.rpc(op="stats")["stats"]
        reqs = [{"op": "df", "terms": ["qzx"]},
                {"op": "postings", "terms": ["qzx"]},
                {"op": "or", "terms": ["qzx", "zzq"]},
                {"op": "top_k", "terms": ["qzx"], "k": 3, "score": "bm25"}]
        for i, r in enumerate(reqs):
            assert c.rpc(id=i, **r)["ok"]
        assert c.rpc(id=9, **reqs[0])["ok"]  # a result-cache hit
        s1 = c.rpc(op="stats")["stats"]
    assert s0["steps"] == {}
    assert s1["steps"]["reply"]["calls"] == len(reqs)
    assert 1 <= s1["steps"]["batch"]["calls"] <= len(reqs)
    assert s1["steps"]["batch"]["total_ms"] \
        >= s1["steps"]["reply"]["total_ms"] > 0
    assert s1["uptime_s"] > s0["uptime_s"] > 0
    assert s1["result_cache"]["hits"] == 1


def test_build_pack_span_inside_emit(tmp_path):
    """The ``index.mri`` pack is the ``build.pack`` span, inside the
    ``build.emit`` phase, and the report's ``artifact_build_ms``."""
    docs = zipf_corpus(num_docs=40, vocab_size=300, tokens_per_doc=80,
                       seed=3)
    paths = []
    for i, blob in enumerate(docs):
        p = tmp_path / f"d{i}.txt"
        p.write_bytes(blob)
        paths.append(str(p))
    write_manifest(tmp_path / "list.txt", paths)
    model = InvertedIndexModel(IndexConfig(
        num_mappers=1, num_reducers=1, backend="tpu", artifact=True))
    out = tmp_path / "out"
    report = {}

    def build():
        report.update(model.run(read_manifest(tmp_path / "list.txt"),
                                str(out)))

    names = {e[2] for e in _traced(tmp_path, build)}
    assert {"build.emit", "build.pack"} <= names
    assert 0 < report["artifact_build_ms"] <= report["phases_ms"]["emit"]
    assert (out / "index.mri").stat().st_size == report["artifact_bytes"]


def test_obs_import_leaves_jax_out():
    """``obs`` never imports JAX: its spans are plain timers in a process
    without it (the package's own ``__init__`` imports the build, so obs
    is loaded as a subpackage alone)."""
    code = f"""
import importlib, sys, types
pkg = types.ModuleType({PKG!r})
pkg.__path__ = [{str(REPO_ROOT / PKG)!r}]
sys.modules[{PKG!r}] = pkg
obs = importlib.import_module({PKG!r} + ".obs")
t = obs.OpTimer(span="serve.op")
with t.time("df"):
    pass
p = obs.PhaseTimer()
with p.phase("emit"):
    pass
assert t.stats()["df"]["calls"] == 1 and "emit" in p.phases
print("jax" in sys.modules)
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"


#: each program factory, its arguments (``mesh`` stands for a one-device
#: mesh) and the name its jitted body carries
PROGRAMS = [
    ("_make_lookup", ("mesh", 4, 1), "serve_lookup"),
    ("_make_decode", ("mesh", 8), "serve_decode"),
    ("_make_decode_v2", ("mesh", 8, 128), "serve_decode"),
    ("_make_bool", ("and", 8), "serve_bool_and"),
    ("_make_bool", ("or", 8), "serve_bool_or"),
    ("_make_bool_v2", ("and", 8, 128), "serve_bool_and"),
    ("_make_bool_v2", ("or", 8, 128), "serve_bool_or"),
    ("_make_bm25", (8, 3), "serve_bm25"),
    ("_make_bm25_v2", (8, 3, 128), "serve_bm25"),
    ("_make_bm25_blocks", (3, 128), "serve_bm25_blocks"),
    ("_make_topk", (3,), "serve_topk_df"),
]


@pytest.mark.parametrize("factory,args,name", PROGRAMS,
                         ids=[f"{f}-{n}" for f, _, n in PROGRAMS])
def test_device_program_names(factory, args, name):
    """The jitted program's name, which XLA's module takes
    (``jit_<name>``), says which family it is."""
    mesh = make_mesh(1)
    fn = getattr(de, factory)(*(mesh if a == "mesh" else a for a in args))
    assert fn.__name__ == name

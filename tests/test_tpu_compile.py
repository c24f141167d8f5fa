"""The chip's compiler accepts the main path's device programs.

Each case compiles one program for a described TPU v5e (no chip needed)
and fails where the chip's compiler would refuse it: a Pallas block
that breaks the tiling, too much fast memory, a program that does not
fit.  Nothing runs, so these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.  The persistent compile cache is off around the compiles (an
entry written for a described chip cannot be read back here).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import engine
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops.pallas import kernels as pk
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.parallel.mesh import SHARD_AXIS
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve import device_engine as de

KEYS = 1 << 20  # the dedup kernel's size at test_in scale

pytestmark = pytest.mark.filterwarnings("ignore:Some donated buffers")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pallas_dedup_kernel_compiles(one_chip):
    keys = _spec((KEYS // 128, 128), jnp.int32, one_chip)
    limit = _spec((1, 1), jnp.int32, one_chip)
    hlo = pk._unique_call.lower(keys, limit, interpret=False).compile()
    assert "tpu_custom_call" in hlo.as_text()


def test_pallas_histogram_kernel_compiles(one_chip):
    vals = _spec((KEYS // 128, 128), jnp.int32, one_chip)
    hlo = pk._hist_call.lower(vals, num_buckets=26,
                              interpret=False).compile()
    assert "tpu_custom_call" in hlo.as_text()


def test_prededuped_sort_compiles(one_chip):
    # XLA's TPU sort compiles slowly past 2^14 keys (2^20: ~16 s); the
    # full size is the builder's rehearsal, recorded in CHANGES.md
    n = 1 << 14
    feed = _spec((2 * n,), jnp.uint16, one_chip)
    hlo = engine.index_prededuped_u16.lower(
        feed, max_doc_id=355, out_size=n).compile()
    assert hlo.memory_analysis() is not None


def test_pipelined_window_sort_compiles(one_chip):
    # one int32 window and one half-bandwidth uint16 window, as the
    # pipelined plan uploads them
    n = 1 << 13
    chunks = [_spec((n,), jnp.int32, one_chip),
              _spec((2 * n,), jnp.uint16, one_chip)]
    hlo = engine.sort_prov_chunks.lower(
        chunks, stride=357, out_size=n).compile()
    assert hlo.memory_analysis() is not None


@pytest.fixture(scope="module")
def serve_cols(tmp_path_factory):
    """Device columns of a test_in-shaped artifact (real widths)."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, InvertedIndexModel,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
        read_manifest, write_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        write_corpus, zipf_corpus,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve import (
        artifact as artifact_mod,
    )

    d = tmp_path_factory.mktemp("serve_cols")
    docs = zipf_corpus(num_docs=355, vocab_size=33_000, tokens_per_doc=2900,
                       seed=7)
    write_manifest(d / "list.txt", write_corpus(d / "docs", docs))
    InvertedIndexModel(IndexConfig(
        backend="cpu", artifact=True, output_dir=str(d / "out"))).run(
            read_manifest(d / "list.txt"))
    art = artifact_mod.load_artifact(d / "out")
    cols = artifact_mod.device_columns(art)
    cols["doc_lens"] = artifact_mod.bm25_corpus(art)[0]
    yield cols
    art.close()


@pytest.fixture(scope="module")
def mesh1(topo):
    return Mesh(np.array(topo.devices[:1]), (SHARD_AXIS,))


def test_device_engine_lookup_compiles(serve_cols, mesh1):
    c = serve_cols
    rep = NamedSharding(mesh1, P())
    lanes = NamedSharding(mesh1, P(SHARD_AXIS))
    V, width, batch = c["vocab"], c["width"], 1024
    fn = de._make_lookup(mesh1, V.bit_length() + 1, c["max_prefix_group"])
    hlo = fn.lower(
        _spec((V,), c["key_hi"].dtype, rep),
        _spec((V,), c["key_lo"].dtype, rep),
        _spec((V, width), c["rows"].dtype, rep),
        _spec((V,), c["df"].dtype, rep),
        _spec((batch,), jnp.uint32, lanes),
        _spec((batch,), jnp.uint32, lanes),
        _spec((batch, width), jnp.uint8, lanes)).compile()
    assert hlo.memory_analysis() is not None


def test_device_engine_bm25_compiles(serve_cols, one_chip):
    c = serve_cols
    # 4 query terms, the decode tier that covers df <= 355, and the
    # candidate count DeviceEngine._exact_top_k asks for at k=10
    terms, width, k = 4, 512, 32
    fn = de._make_bm25_v2(width, k, c["block_size"])
    cols = [_spec(c[name].shape, c[name].dtype, one_chip) for name in (
        "term_block_off", "blk_first", "blk_width", "blk_woff",
        "post_words", "blk_tf_width", "blk_tf_woff", "tf_words")]
    hlo = fn.lower(
        *cols,
        _spec((terms,), jnp.int32, one_chip),
        _spec((terms,), jnp.int32, one_chip),
        _spec((terms,), jnp.bool_, one_chip),
        _spec(c["doc_lens"].shape, jnp.float32, one_chip),
        _spec((), jnp.float32, one_chip),
        _spec((), jnp.float32, one_chip)).compile()
    assert hlo.memory_analysis() is not None

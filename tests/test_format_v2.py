"""Format v2 suite: block-bitpacked postings must be invisible.

Three guarantees, each checked against format v1 built from the SAME
corpus through the real cpu pipeline:

* round-trip parity — every existing op (df, postings, AND/OR, top-k
  by df) answers byte-identically on v1 and v2 artifacts, on both the
  host Engine and the DeviceEngine;
* block-boundary fuzz — terms whose document frequency lands exactly
  on, just under, and just over multiples of the 128-doc block size
  (plus single-doc terms) decode exactly; partial last blocks and
  width-0 blocks are the edges that matter;
* BM25 ranked top-k — ``top_k_scored`` matches a pure-Python scoring
  oracle (tf from a brute-force re-tokenize, the documented idf and
  length norm) in both document order and score, and the device path
  agrees with the host path.
"""

import collections
import math
import os

import numpy as np
import pytest

from test_serve import _C_WHITESPACE, build_corpus, naive_index

from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
    zipf_corpus,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve import (
    Engine, load_artifact,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.artifact import (
    DEFAULT_BLOCK_SIZE, FORMAT_ENV, VERSION, VERSION_V2, artifact_path,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.device_engine import (
    DeviceEngine,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.engine import (
    BM25_B, BM25_K1,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.text.tokenizer import (
    clean_token,
)

pytestmark = pytest.mark.serve


def build_corpus_fmt(tmp_path, docs, fmt: int):
    """build_corpus pinned to one artifact format via the env knob."""
    old = os.environ.get(FORMAT_ENV)
    os.environ[FORMAT_ENV] = str(fmt)
    try:
        return build_corpus(tmp_path, docs)
    finally:
        if old is None:
            os.environ.pop(FORMAT_ENV, None)
        else:
            os.environ[FORMAT_ENV] = old


def word(i: int) -> str:
    """Deterministic alphabetic term (tokenizer drops digits)."""
    i += 26 ** 3  # always 4+ letters so terms stay distinct
    s = ""
    while i:
        i, r = divmod(i, 26)
        s = chr(ord("a") + r) + s
    return s


@pytest.fixture(scope="module")
def both_built(tmp_path_factory):
    docs = zipf_corpus(num_docs=60, vocab_size=900, tokens_per_doc=150,
                       seed=23)
    out1 = build_corpus_fmt(tmp_path_factory.mktemp("fmt_v1"), docs, 1)
    out2 = build_corpus_fmt(tmp_path_factory.mktemp("fmt_v2"), docs, 2)
    return out1, out2, naive_index(docs)


@pytest.fixture(scope="module")
def boundary_built(tmp_path_factory):
    """One corpus whose term dfs bracket every block-size edge: 1, 2,
    B-1, B, B+1, 2B-1, 2B, 2B+1, 2B+44 (B = 128).  Term k appears in
    docs 1..df — doc i holds every term whose target df >= i."""
    B = DEFAULT_BLOCK_SIZE
    targets = {word(k): d for k, d in enumerate(
        (1, 2, B - 1, B, B + 1, 2 * B - 1, 2 * B, 2 * B + 1, 2 * B + 44))}
    ndocs = max(targets.values())
    docs = [" ".join(t for t, d in targets.items() if d >= i).encode()
            for i in range(1, ndocs + 1)]
    out1 = build_corpus_fmt(tmp_path_factory.mktemp("bnd_v1"), docs, 1)
    out2 = build_corpus_fmt(tmp_path_factory.mktemp("bnd_v2"), docs, 2)
    return out1, out2, targets, naive_index(docs)


# -- artifact shape -----------------------------------------------------


def test_versions_and_shared_fields(both_built):
    out1, out2, naive = both_built
    a1 = load_artifact(artifact_path(out1))
    a2 = load_artifact(artifact_path(out2))
    try:
        assert a1.version == VERSION
        assert a2.version == VERSION_V2
        assert a2.block_size == DEFAULT_BLOCK_SIZE
        assert a1.vocab == a2.vocab == len(naive)
        assert a1.num_postings == a2.num_postings
        assert a1.max_doc_id == a2.max_doc_id
        # term tables are byte-identical across formats
        assert a1.term_blob.tobytes() == a2.term_blob.tobytes()
        assert a1.df.tolist() == a2.df.tolist()
        # every df-derived block count is represented in the skip table
        bpt = -(-a2.df.astype(np.int64) // a2.block_size)
        assert int(bpt.sum()) == len(a2.blk_max)
    finally:
        a1.close()
        a2.close()


# -- host round-trip parity ---------------------------------------------


def test_host_engine_v1_v2_parity(both_built):
    out1, out2, naive = both_built
    terms = sorted(naive) + ["zzzzabsent"]
    with Engine(artifact_path(out1)) as e1, \
            Engine(artifact_path(out2)) as e2:
        b1, b2 = e1.encode_batch(terms), e2.encode_batch(terms)
        assert e1.df(b1).tolist() == e2.df(b2).tolist()
        for p1, p2, t in zip(e1.postings(b1), e2.postings(b2), terms):
            if p1 is None:
                assert p2 is None, t
            else:
                assert p1.tolist() == p2.tolist() == naive[t], t
        # boolean ops over every adjacent vocab pair
        pairs = [[terms[i], terms[i + 1]] for i in range(0, 40, 2)]
        for pair in pairs:
            assert e1.query_and(e1.encode_batch(pair)).tolist() == \
                e2.query_and(e2.encode_batch(pair)).tolist()
            assert e1.query_or(e1.encode_batch(pair)).tolist() == \
                e2.query_or(e2.encode_batch(pair)).tolist()
        for li in range(26):
            assert e1.top_k(li, k=10) == e2.top_k(li, k=10)
        # v2 actually exercised the block decoder
        dec = e2.decode_stats()
        assert dec["blocks_decoded"] > 0
        assert dec["bytes_decoded"] > 0


def test_device_engine_v1_v2_parity(both_built):
    out1, out2, naive = both_built
    terms = sorted(naive)[:128] + ["zzzzabsent"]
    d1 = DeviceEngine(artifact_path(out1))
    d2 = DeviceEngine(artifact_path(out2))
    try:
        assert d1.describe()["format"] == VERSION
        assert d2.describe()["format"] == VERSION_V2
        b1, b2 = d1.encode_batch(terms), d2.encode_batch(terms)
        assert d1.df(b1).tolist() == d2.df(b2).tolist()
        for p1, p2, t in zip(d1.postings(b1), d2.postings(b2), terms):
            if p1 is None:
                assert p2 is None, t
            else:
                assert p1.tolist() == p2.tolist(), t
        for pair in ([terms[0], terms[1]], [terms[4], terms[40]],
                     [terms[7], "zzzzabsent"]):
            assert d1.query_and(d1.encode_batch(pair)).tolist() == \
                d2.query_and(d2.encode_batch(pair)).tolist()
            assert d1.query_or(d1.encode_batch(pair)).tolist() == \
                d2.query_or(d2.encode_batch(pair)).tolist()
    finally:
        d1.close()
        d2.close()


# -- block-boundary fuzz ------------------------------------------------


def test_block_boundary_dfs_decode_exactly(boundary_built):
    out1, out2, targets, naive = boundary_built
    with Engine(artifact_path(out1)) as e1, \
            Engine(artifact_path(out2)) as e2:
        terms = sorted(targets)
        b1, b2 = e1.encode_batch(terms), e2.encode_batch(terms)
        assert e1.df(b1).tolist() == [len(naive[t]) for t in terms]
        assert e2.df(b2).tolist() == [len(naive[t]) for t in terms]
        for p1, p2, t in zip(e1.postings(b1), e2.postings(b2), terms):
            assert p1.tolist() == naive[t], t
            assert p2.tolist() == naive[t], t
        # AND between a rare and a block-straddling term forces the
        # skip path through a partial last block
        for pair in ([terms[0], terms[-1]], [terms[1], terms[2]]):
            assert e1.query_and(e1.encode_batch(pair)).tolist() == \
                e2.query_and(e2.encode_batch(pair)).tolist()
        dec = e2.decode_stats()
        assert dec["blocks_decoded"] > 0


def test_block_boundary_device_parity(boundary_built):
    out1, out2, targets, naive = boundary_built
    d2 = DeviceEngine(artifact_path(out2))
    try:
        terms = sorted(targets)
        batch = d2.encode_batch(terms)
        for post, t in zip(d2.postings(batch), terms):
            assert post.tolist() == naive[t], t
    finally:
        d2.close()


def test_single_doc_corpus_round_trip(tmp_path):
    """Degenerate geometry: one doc, every term df=1, every delta run
    empty — all blocks are width-0 and post_data may be empty."""
    docs = [b"lonely little document of one"]
    out = build_corpus_fmt(tmp_path, docs, 2)
    naive = naive_index(docs)
    with Engine(artifact_path(out)) as eng:
        assert eng.artifact.version == VERSION_V2
        batch = eng.encode_batch(sorted(naive))
        for post, t in zip(eng.postings(batch), sorted(naive)):
            assert post.tolist() == naive[t], t


# -- audit / verify coverage --------------------------------------------


def test_verify_manifest_covers_v2_artifact(tmp_path):
    """--audit runs put the v2 ``index.mri`` in index.manifest.json and
    --verify re-checks it: a clean dir passes, a torn v2 artifact fails
    exactly like a torn letter file."""
    import json

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.cli import (
        main,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (  # noqa: E501
        write_manifest,
    )

    ddir = tmp_path / "docs"
    ddir.mkdir()
    paths = []
    for i, blob in enumerate([b"alpha beta gamma", b"beta delta",
                              b"alpha epsilon zeta"]):
        p = ddir / f"d{i}.txt"
        p.write_bytes(blob)
        paths.append(str(p))
    listfile = tmp_path / "list.txt"
    write_manifest(listfile, paths)
    out = tmp_path / "out"
    old = os.environ.get(FORMAT_ENV)
    os.environ[FORMAT_ENV] = "2"
    try:
        assert main(["1", "1", str(listfile), "--backend", "cpu",
                     "--output-dir", str(out), "--artifact",
                     "--audit"]) == 0
    finally:
        if old is None:
            os.environ.pop(FORMAT_ENV, None)
        else:
            os.environ[FORMAT_ENV] = old
    art = artifact_path(out)
    assert load_artifact(art).version == VERSION_V2
    manifest = json.loads((out / "index.manifest.json").read_text())
    assert "index.mri" in manifest["files"]
    assert manifest["files"]["index.mri"]["bytes"] == art.stat().st_size
    assert main(["--verify", str(out)]) == 0
    # tear the v2 artifact: verify must reject the directory
    art.write_bytes(art.read_bytes()[:128])
    assert main(["--verify", str(out)]) == 2


# -- BM25 ranked top-k ---------------------------------------------------


def _bm25_oracle(docs, query_terms, k):
    """Brute-force BM25 in pure Python, mirroring the documented
    semantics: tf re-counted from text, doc length = kept tokens,
    avgdl over non-empty docs, duplicate query terms accumulate."""
    tf = collections.defaultdict(collections.Counter)
    doc_lens = collections.Counter()
    for doc_id, blob in enumerate(docs, start=1):
        for raw in _C_WHITESPACE.split(blob):
            w = clean_token(raw)
            if w:
                tf[w][doc_id] += 1
                doc_lens[doc_id] += 1
    ndocs = len(doc_lens)
    avgdl = sum(doc_lens.values()) / ndocs if ndocs else 1.0
    scores = collections.defaultdict(float)
    for t in query_terms:
        postings = tf.get(t)
        if not postings:
            continue
        df = len(postings)
        idf = math.log(1.0 + (ndocs - df + 0.5) / (df + 0.5))
        for doc, f in postings.items():
            denom = f + BM25_K1 * (
                1.0 - BM25_B + BM25_B * doc_lens[doc] / avgdl)
            scores[doc] += idf * f * (BM25_K1 + 1.0) / denom
    ranked = sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))
    return ranked[:k]


def test_bm25_host_matches_oracle(both_built):
    out1, out2, naive = both_built
    docs = zipf_corpus(num_docs=60, vocab_size=900, tokens_per_doc=150,
                       seed=23)
    vocab = sorted(naive)
    queries = [
        [vocab[0]],
        [vocab[0], vocab[1]],
        [vocab[3], vocab[50], vocab[200]],
        [vocab[5], vocab[5]],              # duplicate term accumulates
        [vocab[2], "zzzzabsent"],
        ["zzzzabsent"],
    ]
    with Engine(artifact_path(out2)) as eng:
        for q in queries:
            got = eng.top_k_scored(eng.encode_batch(q), k=10)
            want = _bm25_oracle(docs, q, 10)
            assert [d for d, _ in got] == [d for d, _ in want], q
            for (_, gs), (_, ws) in zip(got, want):
                assert gs == pytest.approx(ws, rel=1e-9), q


def test_bm25_v1_fallback_is_self_consistent(both_built):
    """v1 carries no term frequencies: the documented fallback scores
    with tf=1 and lengths reconstructed from the postings.  The result
    must be deterministic, positive, and rank-sane (all returned docs
    contain at least one query term)."""
    out1, out2, naive = both_built
    vocab = sorted(naive)
    q = [vocab[0], vocab[1]]
    with Engine(artifact_path(out1)) as eng:
        got = eng.top_k_scored(eng.encode_batch(q), k=10)
        assert got == eng.top_k_scored(eng.encode_batch(q), k=10)
        members = set(naive[q[0]]) | set(naive[q[1]])
        assert got and all(d in members and s > 0 for d, s in got)


def test_bm25_device_matches_host(both_built):
    out1, out2, naive = both_built
    vocab = sorted(naive)
    queries = [[vocab[0], vocab[1]], [vocab[3], vocab[50], vocab[200]],
               [vocab[5], vocab[5]], ["zzzzabsent"]]
    with Engine(artifact_path(out2)) as host:
        dev = DeviceEngine(artifact_path(out2))
        try:
            for q in queries:
                h = host.top_k_scored(host.encode_batch(q), k=10)
                d = dev.top_k_scored(dev.encode_batch(q), k=10)
                assert [x for x, _ in h] == [x for x, _ in d], q
                for (_, hs), (_, ds) in zip(h, d):
                    assert ds == pytest.approx(hs, rel=1e-4), q
        finally:
            dev.close()


# -- whole-array packer round trip --------------------------------------


def test_vectorized_pack_round_trip_at_wiki_shape(tmp_path):
    """An artifact from the whole-array ``pack`` at a 200,000-doc shape
    answers postings, AND, top-k by df and BM25 top-k on the host Engine
    and the DeviceEngine exactly as the loop oracle's artifact does."""
    from pack_oracle import lex_arrays, oracle_pack_v2, word, zipf_runs

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.artifact import (  # noqa: E501
        pack,
    )

    runs = zipf_runs(21, vocab=1_500, ndocs=200_000, top_df=8_000, a=0.9)
    args = lex_arrays(runs, max_doc_id=200_000)
    new, old = tmp_path / "new.mri", tmp_path / "oracle.mri"
    pack(new, fmt=3, **args)
    oracle_pack_v2(old, fmt=3, **args)
    stride = 26 ** 5 // len(runs) - 1
    by_df = np.argsort(-args["df"], kind="stable")
    terms = [word(int(k) * stride).decode() for k in
             (*by_df[:4], by_df[len(runs) // 2], by_df[-1])]
    pairs = [terms[:2], [terms[0], terms[4]], [terms[2], terms[5]]]
    with Engine(new) as host, Engine(old) as ref:
        dev = DeviceEngine(new)
        try:
            for eng in (host, dev):
                got = eng.postings(eng.encode_batch(terms))
                want = ref.postings(ref.encode_batch(terms))
                for g, w, t in zip(got, want, terms):
                    assert g.tolist() == w.tolist(), t
                for pair in pairs:
                    assert eng.query_and(eng.encode_batch(pair)).tolist() \
                        == ref.query_and(ref.encode_batch(pair)).tolist()
                for letter in "aemz":
                    assert eng.top_k(letter, k=5) == ref.top_k(letter, k=5)
                for q in (terms[:3], pairs[2]):
                    g = eng.top_k_scored(eng.encode_batch(q), k=10)
                    w = ref.top_k_scored(ref.encode_batch(q), k=10)
                    assert [d for d, _ in g] == [d for d, _ in w], q
                    for (_, gs), (_, ws) in zip(g, w):
                        assert gs == pytest.approx(ws, rel=1e-4), q
            # the runs went in as drawn, so the oracle itself is right too
            for t, k in zip(terms[:2], by_df[:2]):
                post, = ref.postings(ref.encode_batch([t]))
                assert post.tolist() == runs[k].tolist()
        finally:
            dev.close()

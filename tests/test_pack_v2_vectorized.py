"""``pack_v2`` packs every block in whole-array passes; the per-block
loop packer in ``pack_oracle`` is its byte oracle.

Each case writes a whole ``index.mri`` both ways and compares the files
byte for byte, header and checksums included, across the shapes that a
vectorised packer most often gets wrong: terms with df 0 (the last one
too, whose offset equals the postings length), dfs on every block edge,
runs of consecutive ids (width 0), tf above 1, doc lengths that
saturate both score widths, 200,000-doc ids (18-bit deltas), block size
2 and 128, formats 2 and 3, score bits 8 and 16, terms with no
postings at all, and no terms at all.
"""

import functools

import numpy as np
import pytest

from pack_oracle import _pack_bits, lex_arrays, oracle_pack_v2, zipf_runs

from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.artifact import (  # noqa: E501
    _bit_length, _pack_blocks, load_artifact, pack_v2,
)

pytestmark = pytest.mark.serve

WIKI_DOCS = 200_000


@functools.lru_cache(maxsize=None)
def _shape(name: str) -> dict:
    """``pack_v2`` arguments for one named shape (built once per run)."""
    if name == "testin":
        # ~27k terms over 355 docs, df up to every doc
        return lex_arrays(zipf_runs(11, vocab=27_000, ndocs=355,
                                    top_df=355, a=0.4))
    if name == "wiki":
        # ~3k terms over 200,000 docs, df in the thousands, ~270k postings
        return lex_arrays(zipf_runs(12, vocab=3_000, ndocs=WIKI_DOCS,
                                    top_df=20_000, a=0.9),
                          max_doc_id=WIKI_DOCS)
    if name == "wiki_tf":
        return lex_arrays(zipf_runs(13, vocab=3_000, ndocs=WIKI_DOCS,
                                    top_df=20_000, a=0.9),
                          tf=True, max_doc_id=WIKI_DOCS, seed=13)
    if name == "edges":
        rng = np.random.default_rng(14)
        B = 128
        spread = [np.sort(rng.choice(WIKI_DOCS, size=n, replace=False)) + 1
                  for n in (B - 1, B, B + 1, 2 * B + 1)]
        runs = [np.zeros(0, dtype=np.int32),            # df 0 first
                np.array([5]),                          # df 1
                *spread,                                # block edges
                np.zeros(0, dtype=np.int32),            # df 0 inside
                np.arange(1_000, 1_000 + 2 * B + 1),    # width 0
                np.array([1, WIKI_DOCS]),               # 18-bit delta
                np.cumsum(rng.integers(1, 3, size=300)),
                np.array([2, 3]),                       # df 2, width 0
                np.zeros(0, dtype=np.int32)]            # df 0 last
        # lengths past 255 and past 65,535 saturate both score widths
        doc_lens = rng.integers(0, 70_000, size=WIKI_DOCS + 1,
                                dtype=np.int32)
        doc_lens[1_000:1_100] = rng.integers(256, 65_535, size=100)
        return lex_arrays(runs, tf=True, doc_lens=doc_lens,
                          max_doc_id=WIKI_DOCS, seed=14)
    if name == "edges_default_lens":
        # the device build's call: no tf, doc_lens from the postings
        args = dict(_shape("edges"))
        del args["tf"], args["doc_lens"]
        return args
    if name == "empty":
        return lex_arrays([])
    if name == "no_postings":
        return lex_arrays([np.zeros(0, dtype=np.int32)] * 3)
    raise KeyError(name)


#: (shape, fmt, score_bits, block_size)
CASES = [
    *[(s, 3, 8, 128) for s in ("testin", "wiki", "wiki_tf", "edges",
                               "edges_default_lens", "empty")],
    *[(s, 3, 16, 128) for s in ("testin", "wiki_tf", "edges", "empty")],
    *[(s, 2, 8, 128) for s in ("testin", "wiki", "edges", "empty")],
    *[(s, 3, 8, 2) for s in ("edges", "edges_default_lens", "empty")],
    *[(s, 3, 16, 2) for s in ("edges", "empty")],
    ("edges", 2, 8, 2),
    ("no_postings", 3, 8, 128),
    ("no_postings", 2, 8, 2),
]


@pytest.mark.parametrize("shape,fmt,bits,block", CASES,
                         ids=[f"{s}-v{f}-s{b}-B{k}" for s, f, b, k in CASES])
def test_pack_v2_bytes_match_loop_oracle(tmp_path, shape, fmt, bits, block):
    args = _shape(shape)
    got, want = tmp_path / "new.mri", tmp_path / "oracle.mri"
    n_got = pack_v2(got, fmt=fmt, score_bits=bits, block_size=block, **args)
    n_want = oracle_pack_v2(want, fmt=fmt, score_bits=bits,
                            block_size=block, **args)
    assert n_got == n_want
    assert got.read_bytes() == want.read_bytes()
    art = load_artifact(got)
    try:
        assert art.num_postings == int(args["post_offsets"][-1]
                                       if len(args["post_offsets"]) else 0)
        assert art.num_blocks == int(
            (-(-np.asarray(args["df"]) // block)).sum())
    finally:
        art.close()


def test_edge_shape_reaches_every_edge():
    """The edge shape really holds what its cases claim to cover."""
    args = _shape("edges")
    df = np.asarray(args["df"])
    assert df[0] == 0 and df[-1] == 0
    assert args["post_offsets"][-1] == len(args["postings"])
    assert {1, 127, 128, 129, 257} <= set(df.tolist())
    assert (args["tf"] > 1).any()
    lens = args["doc_lens"][args["postings"]]
    assert (lens > 255).any() and (lens > 65_535).any()
    assert (lens <= 255).any()


@pytest.mark.parametrize("v", [0, 1, 2, 3, 4, 255, 256, 65_535, 65_536,
                               199_999, 2**31 - 1, 2**31, 2**62 - 1,
                               2**62, 2**63 - 1, -1, -(2**40)])
def test_bit_length_is_exact(v):
    want = v.bit_length() if v > 0 else 0
    assert int(_bit_length(np.array([v], dtype=np.int64))[0]) == want


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pack_blocks_matches_per_block_packing(seed):
    """Every width 0-32, counts 0-70, values at each width's top bit and
    negative values (packed by their low bits, as the loop did)."""
    rng = np.random.default_rng(seed)
    widths = np.concatenate([np.arange(33), rng.integers(0, 33, 40)])
    counts = rng.integers(0, 71, size=len(widths))
    counts[-3:] = 0
    parts, want = [], []
    for c, w in zip(counts, widths):
        hi = (1 << int(w)) - 1
        vals = rng.integers(0, hi + 1, size=c, dtype=np.int64)
        if c:
            vals[0] = hi
            if w == 32 and c > 1:
                vals[1] = -1  # wraps to 0xFFFFFFFF, as the u32 view does
        parts.append(vals)
        want.append(_pack_bits(vals, int(w)))
    got = _pack_blocks(np.concatenate(parts), counts, widths)
    assert got.tobytes() == np.concatenate(want).tobytes()

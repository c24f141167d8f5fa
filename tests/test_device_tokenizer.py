"""All-device engine (ops/device_tokenizer.py + device_tokenize=True):
the entire map phase as one XLA program over raw corpus bytes.

Exactness contract: byte-identical to the oracle for every corpus whose
cleaned tokens fit ``device_tokenize_width``; anything longer trips
WidthOverflow and falls back to the host-scan path — so output is
byte-identical ALWAYS, and the engine never silently truncates."""

import numpy as np
import pytest

from conftest import read_letter_files

from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
    IndexConfig,
    InvertedIndexModel,
    build_index,
    oracle_index,
    read_manifest,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
    write_manifest,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
    write_corpus,
    zipf_corpus,
)
from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
    device_tokenizer as DT,
)


def _cfg(**kw):
    kw.setdefault("backend", "tpu")
    kw.setdefault("device_tokenize", True)
    kw.setdefault("pad_multiple", 256)
    kw.setdefault("device_shards", 1)  # 8 virtual devices otherwise -> dist
    return IndexConfig(**kw)


def test_matches_goldens_smoke(smoke_fixture, tmp_path):
    m = read_manifest(smoke_fixture / "manifest.txt", base_dir=smoke_fixture)
    report = InvertedIndexModel(_cfg()).run(m, output_dir=tmp_path)
    assert "host_views" in report["phases_ms"]  # really took the device engine
    assert "load" in report["phases_ms"]
    assert read_letter_files(tmp_path) == read_letter_files(smoke_fixture / "golden")


@pytest.mark.parametrize("seed", [2, 9])
def test_property_random_corpus_vs_oracle(tmp_path, seed):
    docs = zipf_corpus(num_docs=37, vocab_size=800, tokens_per_doc=60, seed=seed)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    build_index(m, _cfg(), output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


def test_tokenizer_edge_cases(tmp_path):
    """The §2.3 contract cases through the device byte classifier."""
    docs = [b"don't foo-bar x1y2z3 I.Loomings cafe\xcc\x81 42 --- UPPER",
            b"a  b\tc\nd\ve\ff\rg", b"", b"ab ab\x00 ab"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    build_index(m, _cfg(), output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


def test_width_overflow_falls_back_exactly(tmp_path):
    """A cleaned token longer than the row width must abort to the host
    path and still produce byte-identical output."""
    docs = [b"short words here", b"a" * 30 + b" tail", b"end doc"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    report = InvertedIndexModel(
        _cfg(device_tokenize_width=16)).run(m, output_dir=tmp_path / "dev")
    assert "device_tokenize_fallback" in report
    assert "aborted_device_tokenize" in report["phases_ms"]
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


def test_over_299_letter_token_falls_back(tmp_path):
    """Tokens past the reference's own 299-letter cap (main.c:105) can
    never be represented in a device row; the guard must fire."""
    docs = [b"x" * 400 + b" normal words"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    report = InvertedIndexModel(_cfg()).run(m, output_dir=tmp_path / "dev")
    assert "device_tokenize_fallback" in report
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


def test_empty_and_allspace_corpus(tmp_path):
    docs = [b"", b"  \t \r\n "]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    InvertedIndexModel(_cfg()).run(m, output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == b""


def test_numbers_only_corpus(tmp_path):
    docs = [b"123 456", b"--- !!!"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    InvertedIndexModel(_cfg()).run(m, output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == b""


def test_config_validation():
    with pytest.raises(ValueError, match="backend"):
        IndexConfig(backend="cpu", device_tokenize=True)
    with pytest.raises(ValueError, match="host-scan"):
        IndexConfig(device_tokenize=True, overlap_tail_fraction=0.4)
    # device_tokenize + stream_chunk_docs is the STREAMING all-device
    # engine — single-chip (ops/device_streaming.py) or mesh
    # (parallel/dist_device_streaming.py)
    IndexConfig(device_tokenize=True, stream_chunk_docs=10)
    IndexConfig(device_tokenize=True, stream_chunk_docs=10, device_shards=4)
    with pytest.raises(ValueError, match="skew"):
        IndexConfig(device_tokenize=True, collect_skew_stats=True)
    with pytest.raises(ValueError, match="device_tokenize_width"):
        IndexConfig(device_tokenize_width=30)  # not a multiple of 4
    with pytest.raises(ValueError, match="device_tokenize_width"):
        IndexConfig(device_tokenize_width=300)  # could hide the 299 cap


def test_tiny_docs_tok_cap_bound(tmp_path):
    # One-byte docs: up to one token per byte (doc boundaries split
    # tokens) -- the review-found tok_cap crash regression test.
    docs = [b"a"] * 64
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    build_index(m, _cfg(pad_multiple=64), output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


# -- mesh variant (parallel/dist_device_tokenizer.py) ---------------------


def _dist_cfg(**kw):
    kw.setdefault("device_shards", None)  # all 8 virtual devices
    return _cfg(**kw)


def _needs_mesh():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("mesh device tokenizer needs >= 2 devices")


def test_dist_matches_goldens_smoke(smoke_fixture, tmp_path):
    _needs_mesh()
    m = read_manifest(smoke_fixture / "manifest.txt", base_dir=smoke_fixture)
    report = InvertedIndexModel(_dist_cfg()).run(m, output_dir=tmp_path)
    assert report["device_shards"] > 1  # really took the mesh engine
    assert "exchange_capacity" in report
    assert read_letter_files(tmp_path) == read_letter_files(smoke_fixture / "golden")


@pytest.mark.parametrize("seed", [4, 13])
def test_dist_property_vs_oracle(tmp_path, seed):
    _needs_mesh()
    docs = zipf_corpus(num_docs=41, vocab_size=700, tokens_per_doc=55, seed=seed)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    build_index(m, _dist_cfg(), output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


def test_dist_matches_single_chip(tmp_path):
    _needs_mesh()
    docs = zipf_corpus(num_docs=29, vocab_size=400, tokens_per_doc=45, seed=21)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    build_index(m, _cfg(), output_dir=tmp_path / "one")
    build_index(m, _dist_cfg(), output_dir=tmp_path / "mesh")
    assert read_letter_files(tmp_path / "mesh") == read_letter_files(tmp_path / "one")


def test_dist_fewer_docs_than_chips(tmp_path):
    _needs_mesh()
    docs = [b"alpha beta", b"beta gamma"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    build_index(m, _dist_cfg(), output_dir=tmp_path / "dev")
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


@pytest.mark.parametrize("seed", [6, 17])
def test_dist_letter_emit_vs_oracle(tmp_path, seed):
    """Letter-ownership emit on the mesh device engine: owners hold
    whole letter ranges (main.c:129-150 at raw-text level) and emit
    their own files — no global merge anywhere."""
    _needs_mesh()
    docs = zipf_corpus(num_docs=31, vocab_size=600, tokens_per_doc=50,
                       seed=seed)
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    report = InvertedIndexModel(
        _dist_cfg(emit_ownership="letter")).run(m, output_dir=tmp_path / "dev")
    assert report.get("emit_ownership") == "letter"
    assert "letter_owners" in report
    assert read_letter_files(tmp_path / "dev") == read_letter_files(
        tmp_path / "oracle")


def test_dist_letter_emit_single_chip_rejected(tmp_path):
    docs = [b"alpha beta"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    with pytest.raises(ValueError, match="multi-chip"):
        build_index(m, _cfg(emit_ownership="letter"),
                    output_dir=tmp_path / "dev")


def test_dist_width_overflow_falls_back(tmp_path):
    _needs_mesh()
    docs = [b"regular words", b"a" * 40 + b" tail"]
    paths = write_corpus(tmp_path / "docs", docs)
    write_manifest(tmp_path / "list.txt", paths)
    m = read_manifest(tmp_path / "list.txt")
    oracle_index(m, tmp_path / "oracle")
    report = InvertedIndexModel(
        _dist_cfg(device_tokenize_width=16)).run(m, output_dir=tmp_path / "dev")
    assert "device_tokenize_fallback" in report
    assert read_letter_files(tmp_path / "dev") == read_letter_files(tmp_path / "oracle")


def _pad_concat(docs, multiple=256):
    total = sum(len(d) for d in docs)
    padded = -(-max(total, 1) // multiple) * multiple
    buf = np.full(padded, 0x20, np.uint8)
    if total:
        buf[:total] = np.frombuffer(b"".join(docs), np.uint8)
    ends = np.cumsum([len(d) for d in docs]).astype(np.int32)
    return buf, ends


@pytest.mark.parametrize("docs", [
    [b"don't foo-bar x1y2z3 I.Loomings supercalifragilistic"],
    [b"a"] * 7 + [b"bb ccc"],
    [b"", b"   ", b"42 --- !!!"],
    [b"x" * 400 + b" tail", b"mid"],
    [b"word\tword\nword\vword\fword\rword"],
    [b"abc", b"", b"de"],  # zero-length doc between others
])
def test_max_cleaned_token_len_matches_python_reference(docs):
    """Host helper vs a trivially-correct per-doc Python scan (the
    reference's clean loop, main.c:105-111: letters-only length)."""
    expect = 0
    for d in docs:
        for tok in d.split():
            expect = max(expect, sum(1 for b in tok if
                                     (65 <= b <= 90) or (97 <= b <= 122)))
    buf, ends = _pad_concat(docs)
    assert DT.max_cleaned_token_len(buf, ends) == expect


def test_sort_cols_pass_skipping_is_exact(tmp_path):
    """index_bytes_device with the host-measured sort_cols bound must
    produce identical outputs to the full 13-pass sort."""
    import jax

    docs = [b"gamma beta alpha alpha", b"delta beta longishword here"]
    buf, ends = _pad_concat(docs)
    ids = np.arange(1, len(docs) + 1, dtype=np.int32)
    tok_cap = 256
    width = 48
    max_len = DT.max_cleaned_token_len(buf, ends)
    full = DT.index_bytes_device(
        jax.device_put(buf), jax.device_put(ends), jax.device_put(ids),
        width=width, tok_cap=tok_cap, num_docs=len(docs))
    skip = DT.index_bytes_device(
        jax.device_put(buf), jax.device_put(ends), jax.device_put(ids),
        width=width, tok_cap=tok_cap, num_docs=len(docs),
        sort_cols=-(-max_len // 4))
    for k in ("counts", "df", "postings"):
        np.testing.assert_array_equal(np.asarray(full[k]), np.asarray(skip[k]))
    for (ah, al), (bh, bl) in zip(full["unique_groups"],
                                  skip["unique_groups"]):
        np.testing.assert_array_equal(np.asarray(ah), np.asarray(bh))
        np.testing.assert_array_equal(np.asarray(al), np.asarray(bl))


@pytest.mark.parametrize("width", [40, 48, 64])
@pytest.mark.parametrize("docs", [
    [b"don't foo-bar x1y2z3 I.Loomings tail42", b"", b"  42 ",
     b"pack my box with five dozen liquor jugs"],
    [b"supercalifragilisticexpialidocious antidisestablishmentarianism",
     b"zz top zz top aa"],
    # 39- and 37-letter words reach into the partial last group at
    # width 40 (chars 36-39 of a 36..41 window)
    [b"a" * 39 + b" zz " + b"q" * 37, b"mid"],
])
def test_tokenize_groups_matches_pack_of_tokenize_rows(docs, width):
    """The 5-bit group frontend must emit EXACTLY
    pack_groups(tokenize_rows(x)) padded with zero pairs — the
    property that lets tokenize_rows stand as the directly-
    byte-addressed reference implementation.  Widths 40 and 64 are NOT
    multiples of 12, so the last group's window reaches past the row
    and the width cap in tokenize_groups' mask is what keeps the two
    frontends identical there."""
    import jax

    buf, ends = _pad_concat(docs)
    ids = np.arange(1, len(docs) + 1, dtype=np.int32)
    kw = dict(width=width, tok_cap=256, num_docs=len(docs))
    args = (jax.device_put(buf), jax.device_put(ends), jax.device_put(ids))
    max_len = DT.max_cleaned_token_len(buf, ends)
    sort_cols = -(-max_len // 4)

    cols, doc_r, len_r, cnt_r = jax.jit(
        lambda *a: DT.tokenize_rows(*a, **kw))(*args)
    nsort = DT.clamp_sort_cols(sort_cols, len(cols))
    ref_groups = DT.pack_groups(
        DT.zero_tail_cols(cols, nsort, 256), nsort)

    groups, doc_g, len_g, cnt_g = jax.jit(
        lambda *a: DT.tokenize_groups(*a, **kw, sort_cols=sort_cols))(*args)
    assert len(groups) == DT.num_groups_for(width)
    assert int(len_r) == int(len_g)
    assert int(cnt_r) == int(cnt_g)
    np.testing.assert_array_equal(np.asarray(doc_r), np.asarray(doc_g))
    for g, (hi, lo) in enumerate(groups):
        if g < len(ref_groups):
            eh, el = ref_groups[g]
        else:
            eh = el = np.zeros(256, np.int32)
        np.testing.assert_array_equal(np.asarray(hi), np.asarray(eh))
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(el))


def test_device_program_has_no_token_scale_scatter():
    """Design guard: TPU scatter lowers to a serial per-update loop
    (~75 ns/update measured), so the device program must stay
    scatter-free at token scale — only the two num_docs-sized
    doc-boundary scatters are allowed.  Lower the jit and count."""
    import re

    import jax

    num_docs, tok_cap, n = 4, 256, 1024
    lowered = jax.jit(
        lambda d, e, i: DT.index_bytes_device(
            d, e, i, width=48, tok_cap=tok_cap, num_docs=num_docs)
    ).lower(
        jax.ShapeDtypeStruct((n,), np.uint8),
        jax.ShapeDtypeStruct((num_docs,), np.int32),
        jax.ShapeDtypeStruct((num_docs,), np.int32),
    )
    text = lowered.as_text()
    # exactly the three num_docs-sized doc-boundary scatters survive:
    # doc_starts .at[ends].set / .at[0].set, and the doc-slot
    # scatter-max — every one carries <= num_docs-1 updates
    scatters = re.findall(r'= "stablehlo\.scatter"', text)
    assert len(scatters) == 3, (
        f"{len(scatters)} scatter ops in the device program (expected "
        "the 3 tiny doc-boundary ones) — token-scale compactions must "
        "stay sort/gather formulations")
    # and NO loops: jnp.searchsorted's default method='scan' lowers to
    # a sequential log2(n) while-loop of dynamic slices, the round-3
    # regression's root cause (702 ms at 2^20 queries into 5.7M on the
    # v5e, round 3) — the program must stay loop-free
    assert 'stablehlo.while' not in text, (
        "a while loop appeared in the device program — most likely a "
        "scan-lowered searchsorted crept back in; use "
        "segment.searchsorted_device / segment.set_bit_positions")


def test_decode_word_groups_roundtrip():
    """Host decoder vs pack_groups on hand-built byte columns: the
    5-bit group pairs must decode back to the original words."""
    words = [b"cat", b"aardvark", b"z" * 12, b"q" * 16]
    width = 16
    rows = np.zeros((len(words), width), np.uint8)
    for i, w in enumerate(words):
        rows[i, : len(w)] = np.frombuffer(w, np.uint8)
    r32 = rows.reshape(len(words), width // 4, 4).astype(np.int64)
    cols = [
        ((r32[:, c, 0] << 24) | (r32[:, c, 1] << 16)
         | (r32[:, c, 2] << 8) | r32[:, c, 3]).astype(np.int32)
        for c in range(width // 4)
    ]
    import jax.numpy as jnp

    groups = DT.pack_groups([jnp.asarray(c) for c in cols], width // 4)
    decoded = DT.decode_word_groups(
        [(np.asarray(h), np.asarray(l)) for h, l in groups], width)
    assert [w.rstrip(b"\x00") for w in decoded.tolist()] == words


def test_two_key_letter_compaction_branch_matches(monkeypatch):
    """The n >= 2^24 letter-compaction fallback (the (flag, position)
    key no longer fits in one int32, tokenize_rows) must agree exactly
    with the one-key path — forced here by dropping the module
    threshold so both branches run on the same small buffer."""
    import jax

    docs = [b"don't foo-bar x1y2z3 I.Loomings tail42", b"", b"  42 ",
            b"pack my box with five dozen liquor jugs"]
    buf, ends = _pad_concat(docs)
    ids = np.arange(1, len(docs) + 1, dtype=np.int32)
    kw = dict(width=48, tok_cap=256, num_docs=len(docs))
    args = (jax.device_put(buf), jax.device_put(ends), jax.device_put(ids))

    one = jax.jit(lambda *a: DT.tokenize_rows(*a, **kw))(*args)
    monkeypatch.setattr(DT, "_ONE_KEY_COMPACTION_LIMIT", 0)
    two = jax.jit(lambda *a: DT.tokenize_rows(*a, **kw))(*args)

    one_cols, one_doc, one_len, one_cnt = one
    two_cols, two_doc, two_len, two_cnt = two
    assert int(one_len) == int(two_len)
    assert int(one_cnt) == int(two_cnt)
    np.testing.assert_array_equal(np.asarray(one_doc), np.asarray(two_doc))
    for a, b in zip(one_cols, two_cols):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tokenize_rows_buffer_ending_in_letter():
    """A buffer whose LAST byte is a letter (no trailing pad) must
    tokenize exactly: the compaction tail and the final token's length
    come from the clamped start-byte gather, which must not read past
    the exclusive-cumsum array.  (This input guarded the removed
    searchsorted compaction variant; kept for the sort path.)"""
    import jax

    docs = [b"don't foo-bar x1y2z3 I.Loomings tail42", b"", b"  42 ",
            b"pack my box with five dozen liquor jugz"]  # ends in a letter
    buf, ends = _pad_concat(docs)
    buf = buf[: int(ends[-1])]  # no trailing pad: last byte IS a letter
    ids = np.arange(1, len(docs) + 1, dtype=np.int32)
    kw = dict(width=48, tok_cap=256, num_docs=len(docs))
    args = (jax.device_put(buf), jax.device_put(ends), jax.device_put(ids))

    trunc = jax.jit(lambda *a: DT.tokenize_rows(*a, **kw))(*args)
    pad_buf, _ = _pad_concat(docs)  # same docs, space-padded tail
    padded = jax.jit(lambda *a: DT.tokenize_rows(*a, **kw))(
        jax.device_put(pad_buf), jax.device_put(ends), jax.device_put(ids))

    t_cols, t_doc, t_len, t_cnt = trunc
    p_cols, p_doc, p_len, p_cnt = padded
    assert int(t_len) == int(p_len)
    assert int(t_cnt) == int(p_cnt)
    np.testing.assert_array_equal(np.asarray(t_doc), np.asarray(p_doc))
    for a, b in zip(t_cols, p_cols):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_native_token_stats_matches_numpy_mirror():
    """mri_token_stats (SIMD masks) vs the numpy mirror on edge cases:
    inner doc boundaries splitting runs, letter as the last byte,
    non-space bytes past the last doc end, zero-length docs, padded
    equal ends, empty input."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        native,
    )

    if not native.available():
        pytest.skip("native tokenizer unavailable")

    def both(buf, ends):
        got = native.token_stats(buf, ends)
        want = DT._host_token_stats_numpy(buf, ends)
        assert got == want, (got, want, bytes(buf), ends.tolist())
        return got

    cases = []
    # handcrafted edges
    b = np.frombuffer(b"abXcd ef", np.uint8).copy()
    cases.append((b, np.array([4, 8], np.int64)))       # boundary mid-token
    cases.append((b, np.array([8], np.int64)))          # single doc
    cases.append((b, np.array([2, 2, 8], np.int64)))    # zero-length doc
    cases.append((b, np.array([3], np.int64)))          # bytes past last end
    cases.append((np.frombuffer(b"  42!  ", np.uint8).copy(),
                  np.array([7], np.int64)))             # letterless token
    cases.append((np.frombuffer(b"z", np.uint8).copy(),
                  np.array([1], np.int64)))             # last byte a letter
    # padded-ends shape the streaming feed uses
    pad = np.full(64, 0x20, np.uint8)
    pad[:11] = np.frombuffer(b"hello world", np.uint8)
    cases.append((pad, np.array([5, 11, 64, 64], np.int64)))
    # randomized sweep incl. >64-byte tokens spanning mask words
    rng = np.random.default_rng(9)
    alphabet = np.frombuffer(b"ab XY.9\t\n-z", np.uint8)
    for _ in range(25):
        n = int(rng.integers(1, 400))
        buf = rng.choice(alphabet, n).astype(np.uint8)
        k = int(rng.integers(1, 6))
        ends = np.sort(rng.integers(0, n + 1, k)).astype(np.int64)
        ends[-1] = int(rng.integers(0, n + 1))
        ends = np.sort(ends)
        cases.append((buf, ends))
    cases.append((np.frombuffer(b"a" * 200 + b" " + b"b" * 70, np.uint8).copy(),
                  np.array([271], np.int64)))           # long tokens
    for buf, ends in cases:
        both(buf, ends)

    # non-monotonic / negative ends: the native path refuses (None) so
    # host_token_stats falls back to the numpy mirror instead of
    # double-scanning or reading out of bounds
    assert native.token_stats(b, np.array([9, 3, 11], np.int64)) is None
    assert native.token_stats(b, np.array([-1, 8], np.int64)) is None


@pytest.mark.parametrize("k,narrow", [(1, True), (3, True), (1, False)])
def test_fetch_pack_roundtrip(k, narrow):
    """fetch_pack's transfer set must reconstruct exactly the dense
    df/postings/unique_groups prefixes: packed postings (3 doc ids per
    int32 when they fit 10 bits / uint16 otherwise) and the SPARSE
    tail-group form (indices + values for >12-char words only)."""
    import jax

    docs = [b"short words here on every line",
            b"supercalifragilisticexpialidocious floccinaucinihilipilification",
            b"medium sized tokens xyz pneumonoultramicroscopicsilicovolcanoconiosis"]
    buf, ends = _pad_concat(docs)
    ids = np.arange(1, len(docs) + 1, dtype=np.int32)
    width, tok_cap = 48, 256
    max_len = DT.max_cleaned_token_len(buf, ends)
    sort_cols = -(-max_len // 4)
    out = DT.index_bytes_device(
        jax.device_put(buf), jax.device_put(ends), jax.device_put(ids),
        width=width, tok_cap=tok_cap, num_docs=len(docs),
        sort_cols=sort_cols)
    num_words, num_pairs, _, _, num_long = (
        int(v) for v in np.asarray(out["counts"]))
    assert num_long == 3  # the three >12-char words above
    live = DT.live_groups_for(sort_cols, width)
    nu = npairs = tok_cap
    nlong = 64
    packed = DT.fetch_pack(out, nu=nu, npairs=npairs, nlong=nlong,
                           k=k, live=live, narrow=narrow)

    dense_df = np.asarray(out["df"])[:num_words]
    dense_post = np.asarray(out["postings"])[:num_pairs]
    np.testing.assert_array_equal(
        np.asarray(packed["df"])[:num_words].astype(np.int32), dense_df)
    if not narrow:  # wide path must NOT narrow the dtypes
        assert np.asarray(packed["df"]).dtype == np.int32
        assert np.asarray(packed["post"]).dtype == np.int32
    np.testing.assert_array_equal(
        DT.unpack_postings(packed["post"], num_pairs, k), dense_post)

    # rebuild dense tails from the sparse transfer and compare
    idx = np.asarray(packed["long_idx"])[:num_long]
    for g in range(1, live):
        eh = np.asarray(out["unique_groups"][g][0])[:num_words]
        el = np.asarray(out["unique_groups"][g][1])[:num_words]
        h = np.zeros(num_words, np.int32)
        l = np.zeros(num_words, np.int32)
        th, tl = packed["tail"][g - 1]
        h[idx] = np.asarray(th)[:num_long]
        l[idx] = np.asarray(tl)[:num_long]
        np.testing.assert_array_equal(h, eh)
        np.testing.assert_array_equal(l, el)


@pytest.mark.parametrize("npairs", [1, 2, 3, 7, 4096])
def test_pack_unpack_postings_boundary_values(npairs):
    """pack_postings/unpack_postings at the 10-bit field boundary:
    doc ids up to 1023 (doc_pack_width's k=3 threshold is < 1024) and
    lengths not divisible by k must round-trip exactly."""
    import jax.numpy as jnp

    rng = np.random.default_rng(npairs)
    post = rng.integers(0, 1024, npairs).astype(np.int32)
    post[0] = 1023  # field-boundary value
    packed = np.asarray(DT.pack_postings(jnp.asarray(post), 3))
    assert packed.shape[0] == -(-npairs // 3)
    np.testing.assert_array_equal(
        DT.unpack_postings(packed, npairs, 3), post)
    # k=1 passthrough
    np.testing.assert_array_equal(
        DT.unpack_postings(post, npairs, 1), post)
    # the k selector: packing only when ids fit 10 bits
    assert DT.doc_pack_width(1023) == 3
    assert DT.doc_pack_width(1024) == 1
    assert DT.doc_pack_width(70000) == 1

"""The per-block loop packer for ``index.mri`` v2/v2.1, kept as the byte
oracle for :func:`serve.artifact.pack_v2`, plus generators of lex-order
index arrays at the benchmark's shapes.

The oracle walks every term and every block one Python iteration at a
time and packs each block with ``unpackbits``/``packbits``: slow, but
each step reads straight off the format description, so the
whole-array packer must write the same bytes.
"""

from __future__ import annotations

import numpy as np

from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.artifact import (  # noqa: E501
    VERSION, VERSION_V21, _layout_v2, _write, resolve_block_size,
    resolve_format, resolve_score_bits,
)


def _pack_bits(vals: np.ndarray, w: int) -> np.ndarray:
    """Pack ``vals`` (< 2**w each) at ``w`` bits LSB-first into a
    word-aligned little-endian uint8 array (width 0 packs to nothing)."""
    if w == 0 or not len(vals):
        return np.zeros(0, dtype=np.uint8)
    bits = np.unpackbits(
        np.ascontiguousarray(vals, dtype="<u4").view(np.uint8).reshape(-1, 4),
        axis=1, bitorder="little")[:, :w].ravel()
    pad = (-len(bits)) % 32
    if pad:
        bits = np.concatenate([bits, np.zeros(pad, dtype=np.uint8)])
    return np.packbits(bits, bitorder="little")


def oracle_pack_v2(path, *, term_blob, term_offsets, df, post_offsets,
                   postings, df_order, max_doc_id, width=None, tf=None,
                   doc_lens=None, block_size=None, fmt=None,
                   score_bits=None) -> int:
    """``pack_v2``'s contract, one block per Python iteration."""
    fmt = resolve_format(fmt)
    if fmt == VERSION:
        raise ValueError("pack_v2 writes formats 2 and 3, not 1")
    bits = resolve_score_bits(score_bits) if fmt == VERSION_V21 else 0
    B = resolve_block_size(block_size)
    term_offsets = np.ascontiguousarray(term_offsets, dtype=np.int64)
    post_offsets = np.ascontiguousarray(post_offsets, dtype=np.int64)
    term_blob = np.ascontiguousarray(term_blob, dtype=np.uint8)
    df = np.ascontiguousarray(df, dtype=np.int32)
    df_order = np.ascontiguousarray(df_order, dtype=np.int32)
    postings = np.asarray(postings, dtype=np.int32)
    vocab = len(df)
    num_postings = int(post_offsets[-1]) if len(post_offsets) else 0
    blob_bytes = int(term_offsets[-1]) if len(term_offsets) else 0
    if width is None:
        lens = np.diff(term_offsets)
        width = int(lens.max()) if vocab else 1
    if tf is None:
        tf = np.ones(num_postings, dtype=np.int32)
    tf = np.ascontiguousarray(tf, dtype=np.int32)
    if doc_lens is None:
        doc_lens = np.bincount(
            postings, weights=tf,
            minlength=max_doc_id + 1).astype(np.int32)
    doc_lens = np.ascontiguousarray(doc_lens, dtype=np.int32)
    if len(doc_lens) != max_doc_id + 1:
        out = np.zeros(max_doc_id + 1, dtype=np.int32)
        out[:len(doc_lens)] = doc_lens[:max_doc_id + 1]
        doc_lens = out

    blk_max: list[int] = []
    blk_first: list[int] = []
    blk_width: list[int] = []
    blk_tf_width: list[int] = []
    blk_max_tf: list[int] = []
    blk_min_dl: list[int] = []
    post_parts: list[np.ndarray] = []
    tf_parts: list[np.ndarray] = []
    cap = (1 << bits) - 1 if bits else 0
    for t in range(vocab):
        lo, hi = int(post_offsets[t]), int(post_offsets[t + 1])
        for b0 in range(lo, hi, B):
            b1 = min(b0 + B, hi)
            docs = postings[b0:b1].astype(np.int64)
            tfs = tf[b0:b1].astype(np.int64)
            blk_first.append(int(docs[0]))
            blk_max.append(int(docs[-1]))
            deltas = np.diff(docs) - 1
            w = int(deltas.max()).bit_length() if len(deltas) and \
                deltas.max() > 0 else 0
            tw = int(tfs.max() - 1).bit_length() if tfs.max() > 1 else 0
            blk_width.append(w)
            blk_tf_width.append(tw)
            post_parts.append(_pack_bits(deltas, w))
            tf_parts.append(_pack_bits(tfs - 1, tw))
            if bits:
                blk_max_tf.append(min(int(tfs.max()), cap))
                blk_min_dl.append(min(int(doc_lens[docs].min()), cap))
    post_data = (np.concatenate(post_parts) if post_parts
                 else np.zeros(0, dtype=np.uint8))
    tf_data = (np.concatenate(tf_parts) if tf_parts
               else np.zeros(0, dtype=np.uint8))
    num_blocks = len(blk_max)

    layout, total = _layout_v2(vocab, blob_bytes, num_blocks,
                               len(post_data), len(tf_data), max_doc_id,
                               score_bits=bits)
    buf = np.zeros(total, dtype=np.uint8)

    def put(name: str, arr: np.ndarray) -> None:
        off, nbytes = layout[name]
        buf[off:off + nbytes] = np.frombuffer(arr.tobytes(), dtype=np.uint8)

    first_bytes = term_blob[term_offsets[:-1]] if vocab else term_blob[:0]
    letter_dir = np.searchsorted(
        first_bytes, np.arange(ord("a"), ord("a") + 27)).astype(np.int64)
    put("letter_dir", letter_dir)
    put("term_offsets", term_offsets)
    put("term_blob", term_blob)
    put("df", df)
    put("blk_max", np.asarray(blk_max, dtype=np.int32))
    put("blk_first", np.asarray(blk_first, dtype=np.int32))
    put("blk_width", np.asarray(blk_width, dtype=np.uint8))
    put("blk_tf_width", np.asarray(blk_tf_width, dtype=np.uint8))
    if bits:
        sdt = "<u1" if bits == 8 else "<u2"
        put("blk_max_tf", np.asarray(blk_max_tf, dtype=sdt))
        put("blk_min_dl", np.asarray(blk_min_dl, dtype=sdt))
    put("post_data", post_data)
    put("tf_data", tf_data)
    put("doc_lens", doc_lens)
    put("df_order", df_order)

    return _write(path, buf, width=width, vocab=vocab,
                  num_postings=num_postings, max_doc_id=max_doc_id,
                  blob_bytes=blob_bytes, version=fmt,
                  v2={"block_size": B, "num_blocks": num_blocks,
                      "score_bits": bits,
                      "post_data_bytes": len(post_data),
                      "tf_data_bytes": len(tf_data)})


# -- lex-order index arrays ----------------------------------------------


def word(i: int) -> bytes:
    """The i-th of a lex-ascending run of distinct 5-letter terms."""
    out = bytearray()
    for _ in range(5):
        i, r = divmod(i, 26)
        out.append(ord("a") + r)
    return bytes(reversed(out))


def _doc_ids(rng: np.random.Generator, df: int, ndocs: int) -> np.ndarray:
    """``df`` distinct ascending doc ids drawn from ``[1, ndocs]``."""
    if 4 * df >= ndocs:
        ids = rng.choice(ndocs, size=df, replace=False)
    else:
        ids = np.unique(rng.integers(0, ndocs, size=2 * df + 8))
        ids = rng.choice(ids, size=df, replace=False)
    return np.sort(ids).astype(np.int32) + 1


def lex_arrays(runs: list[np.ndarray], *, tf: bool = False,
               doc_lens: np.ndarray | None = None,
               max_doc_id: int | None = None,
               seed: int = 0) -> dict:
    """``pack_v2`` keyword arguments for one doc-id run per term (term k
    is ``word(k * stride)``, spread over the 26 letters).  ``tf`` draws
    term frequencies above 1 (so tf widths are non-zero)."""
    rng = np.random.default_rng(seed)
    V = len(runs)
    stride = max(1, 26 ** 5 // max(V, 1) - 1)
    words = [word(k * stride) for k in range(V)]
    term_offsets = np.zeros(V + 1, dtype=np.int64)
    np.cumsum([len(w) for w in words], out=term_offsets[1:])
    df = np.array([len(r) for r in runs], dtype=np.int64)
    post_offsets = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(df, out=post_offsets[1:])
    postings = (np.concatenate(runs).astype(np.int32) if V
                else np.zeros(0, dtype=np.int32))
    if max_doc_id is None:
        max_doc_id = int(postings.max()) if len(postings) else 0
    out = dict(
        term_blob=np.frombuffer(b"".join(words), dtype=np.uint8),
        term_offsets=term_offsets, df=df, post_offsets=post_offsets,
        postings=postings, df_order=np.argsort(-df, kind="stable"),
        max_doc_id=max_doc_id)
    if tf:
        out["tf"] = (rng.geometric(0.3, size=len(postings))
                     .astype(np.int32))
    if doc_lens is not None:
        out["doc_lens"] = doc_lens
    return out


def zipf_runs(seed: int, *, vocab: int, ndocs: int, top_df: int,
              a: float = 1.1) -> list[np.ndarray]:
    """Term k gets df ~ top_df / (k+1)**a (at least 1), its ids spread
    over ``[1, ndocs]``; ranks are shuffled so high-df terms fall
    anywhere in lex order."""
    rng = np.random.default_rng(seed)
    dfs = np.maximum(1, (top_df / np.arange(1, vocab + 1) ** a)
                     .astype(np.int64))
    rng.shuffle(dfs)
    return [_doc_ids(rng, int(min(d, ndocs)), ndocs) for d in dfs]

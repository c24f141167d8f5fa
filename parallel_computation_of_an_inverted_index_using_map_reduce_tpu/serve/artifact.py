"""``index.mri`` — the compact, memory-mappable serving artifact.

The letter files are the conformance surface (byte-exact against the
reference); this is the *serving* surface: one columnar file the query
engine mmaps and reads with zero-copy numpy views, so a process serving
lookups never re-parses text.

Format v1, little-endian throughout:

    header (96 bytes)
      magic            8s   b"MRIIDX01"
      version          u32  1
      width            u32  fixed term-row width (max term length)
      vocab            i64  V — number of terms
      num_postings     i64  P — total (term, doc) pairs
      max_doc_id       i64
      term_blob_bytes  i64
      payload_bytes    i64  everything after the header
      payload_adler32  u32  over the payload bytes
      reserved         32 zero bytes
      header_adler32   u32  over header bytes [0, 92)

    payload — fixed section order, each section 16-byte aligned:
      letter_dir    i64[27]   lex term-index bounds per first letter
      term_offsets  i64[V+1]  exclusive prefix into term_blob
      term_blob     u8[...]   term bytes, lex order, no separators
      df            i32[V]    document frequency per term
      post_offsets  i64[V+1]  exclusive prefix into postings
      postings      i32[P]    per-term runs, delta-encoded: first doc id
                              absolute, the rest diffs (>= 1 — ids are
                              strictly ascending within a term)
      df_order      i32[V]    emit-order permutation over lex indices
                              (letter asc, df desc, word asc); its
                              letter bounds are letter_dir too, since
                              both orders are letter-contiguous

Terms are in lexicographic order — the engine's binary-search key — and
``df_order`` gives O(k) top-k-by-df per letter.  Writes are atomic
(tmp + rename), loads verify both checksums before any answer is
served: a torn artifact raises :class:`ArtifactError`, never garbage.

Format v2 (``$MRI_SERVE_FORMAT``, the default) keeps the header
discipline and the term sections but stores postings as fixed-size
blocks of ``block_size`` doc ids (``$MRI_SERVE_BLOCK_SIZE``, default
128, power of two).  The reserved header bytes gain, at offset 60:

      block_size       u32
      reserved0        u32
      num_blocks       i64  NB — total blocks over all terms
      post_data_bytes  i64
      tf_data_bytes    i64

and the payload becomes (same alignment discipline):

      letter_dir    i64[27]   as v1
      term_offsets  i64[V+1]  as v1
      term_blob     u8[...]   as v1
      df            i32[V]    as v1
      blk_max       i32[NB]   skip table: last doc id per block
      blk_first     i32[NB]   first doc id per block (absolute, so any
                              block decodes without its predecessors)
      blk_width     u8[NB]    bit width of the block's packed deltas
      blk_tf_width  u8[NB]    bit width of the block's packed tf
      post_data     u8[...]   per block: (count-1) values of
                              (delta - 1) at blk_width bits, LSB-first
                              little-endian, zero-padded to a 4-byte
                              boundary per block (width 0 => 0 bytes)
      tf_data       u8[...]   per block: count values of (tf - 1) at
                              blk_tf_width bits, same packing
      doc_lens      i32[max_doc_id + 1]  tokens per document (BM25
                              length norm; 0 = absent doc)
      df_order      i32[V]    as v1

Nothing else is stored: block counts per term derive from ``df``, and
each block's byte offset derives from the width/count columns — the
loader reconstructs both prefix sums vectorized at load time.

Format v2.1 (version 3, the default) is v2 plus two per-block
max-score columns for dynamic pruning (Block-Max WAND / MaxScore):

      blk_max_tf    u8/u16[NB]  max tf in the block, saturated at
                                2**score_bits - 1 (saturation means
                                "assume the tf->inf BM25 limit")
      blk_min_dl    u8/u16[NB]  min doc length in the block, saturated
                                the same way (a saturated/short length
                                only loosens the bound, never unsafe)

inserted between ``blk_tf_width`` and ``post_data``.  ``score_bits``
(8 or 16, ``$MRI_SERVE_SCORE_BITS``) lives in the v2 header's
reserved0 slot, which v2 writers always zeroed.  Integer columns —
not quantized floats — keep the native C++ exporter and the
pure-Python packer bit-identical; the engines derive the float BM25
upper bound ``idf * (k1+1) * mtf / (mtf + k1*(1-b+b*mdl/avgdl))``
from them at query time.  v1 and v2 stay readable forever; engines
fall back to exhaustive scoring when the columns are absent.
"""

from __future__ import annotations

import mmap
import os
import struct
import zlib
from pathlib import Path

import numpy as np

from ..utils import envknobs
from ..utils.checksum import file_checksum

#: Written next to a.txt..z.txt by ``--artifact`` runs.
ARTIFACT_NAME = "index.mri"

MAGIC = b"MRIIDX01"
VERSION = 1
VERSION_V2 = 2
VERSION_V21 = 3
HEADER_BYTES = 96
_ALIGN = 16
_HEADER_FMT = "<8sIIqqqqqI"  # ... + 32 reserved + u32 header_adler32
_HEADER_V2_FMT = "<IIqqq"    # v2+: packed into the 32 reserved bytes
_HEADER_V2_OFF = struct.calcsize(_HEADER_FMT)  # 60

#: Artifact format written by the builders (1, 2 or 3; older versions
#: stay readable forever), the v2+ postings block size (power of two
#: >= 2), and the v2.1 max-score column width (8 or 16 bits).
FORMAT_ENV = "MRI_SERVE_FORMAT"
BLOCK_ENV = "MRI_SERVE_BLOCK_SIZE"
SCORE_BITS_ENV = "MRI_SERVE_SCORE_BITS"

DEFAULT_BLOCK_SIZE = 128
DEFAULT_SCORE_BITS = 8


class ArtifactError(RuntimeError):
    """The artifact is missing, torn, or not an artifact at all."""


def _align(n: int) -> int:
    return (n + _ALIGN - 1) & ~(_ALIGN - 1)


def _layout(vocab: int, num_postings: int, blob_bytes: int):
    """Section name -> (file offset, byte length), plus total file size.

    Deterministic from the three header scalars, so the loader never
    stores per-section offsets in the file.
    """
    sections = [
        ("letter_dir", 27 * 8),
        ("term_offsets", (vocab + 1) * 8),
        ("term_blob", blob_bytes),
        ("df", vocab * 4),
        ("post_offsets", (vocab + 1) * 8),
        ("postings", num_postings * 4),
        ("df_order", vocab * 4),
    ]
    out: dict[str, tuple[int, int]] = {}
    cur = HEADER_BYTES
    for name, nbytes in sections:
        cur = _align(cur)
        out[name] = (cur, nbytes)
        cur += nbytes
    return out, _align(cur)


def _layout_v2(vocab: int, blob_bytes: int, num_blocks: int,
               post_data_bytes: int, tf_data_bytes: int, max_doc_id: int,
               score_bits: int = 0):
    """v2/v2.1 section name -> (file offset, byte length), plus total
    size — deterministic from the header scalars, like :func:`_layout`.
    ``score_bits`` 0 is plain v2; 8/16 inserts the v2.1 max-score
    columns (every section offset before them is unchanged)."""
    sections = [
        ("letter_dir", 27 * 8),
        ("term_offsets", (vocab + 1) * 8),
        ("term_blob", blob_bytes),
        ("df", vocab * 4),
        ("blk_max", num_blocks * 4),
        ("blk_first", num_blocks * 4),
        ("blk_width", num_blocks),
        ("blk_tf_width", num_blocks),
        ("post_data", post_data_bytes),
        ("tf_data", tf_data_bytes),
        ("doc_lens", (max_doc_id + 1) * 4),
        ("df_order", vocab * 4),
    ]
    if score_bits:
        sections[8:8] = [
            ("blk_max_tf", num_blocks * (score_bits // 8)),
            ("blk_min_dl", num_blocks * (score_bits // 8)),
        ]
    out: dict[str, tuple[int, int]] = {}
    cur = HEADER_BYTES
    for name, nbytes in sections:
        cur = _align(cur)
        out[name] = (cur, nbytes)
        cur += nbytes
    return out, _align(cur)


def resolve_format(fmt: int | None = None) -> int:
    """The artifact version the builders should write: the explicit
    argument, else ``$MRI_SERVE_FORMAT`` (default 3)."""
    fmt = int(envknobs.get(FORMAT_ENV) if fmt is None else fmt)
    if fmt not in (VERSION, VERSION_V2, VERSION_V21):
        raise ValueError(f"unsupported artifact format {fmt}")
    return fmt


def resolve_score_bits(bits: int | None = None) -> int:
    """The v2.1 max-score column width: the explicit argument, else
    ``$MRI_SERVE_SCORE_BITS``.  Must be 8 or 16."""
    b = int(envknobs.get(SCORE_BITS_ENV) if bits is None else bits)
    if b not in (8, 16):
        raise ValueError(f"{SCORE_BITS_ENV}={b} is not 8 or 16")
    return b


def resolve_block_size(block_size: int | None = None) -> int:
    """The v2 postings block size: the explicit argument, else
    ``$MRI_SERVE_BLOCK_SIZE``.  Must be a power of two >= 2."""
    b = int(envknobs.get(BLOCK_ENV) if block_size is None else block_size)
    if b < 2 or b > (1 << 20) or b & (b - 1):
        raise ValueError(
            f"{BLOCK_ENV}={b} is not a power of two in [2, 2**20]")
    return b


def artifact_path(index_dir: str | Path) -> Path:
    return Path(index_dir) / ARTIFACT_NAME


#: Present in a directory that the incremental-indexing layer manages
#: (mirrors segments.manifest.MANIFEST_NAME; duplicated here so the
#: serve stack can detect segmented directories without importing the
#: build-side segments package).
SEGMENTS_MANIFEST_NAME = "segments.manifest.json"


def is_segment_managed(path) -> bool:
    """Whether ``path`` is a directory whose live truth is a segment
    manifest rather than its (possibly stale) root ``index.mri``.
    Engines refuse to open such a directory as a single artifact.  A
    path to the root ``index.mri`` file itself is equally stale, so it
    is judged by its parent directory (segment artifacts live one level
    down, under ``segments/``, and stay openable)."""
    p = Path(path)
    if p.is_dir():
        return (p / SEGMENTS_MANIFEST_NAME).exists()
    return (p.name == ARTIFACT_NAME
            and (p.parent / SEGMENTS_MANIFEST_NAME).exists())


def pack(path, *, term_blob: np.ndarray, term_offsets: np.ndarray,
         df: np.ndarray, post_offsets: np.ndarray, postings: np.ndarray,
         df_order: np.ndarray, max_doc_id: int, width: int | None = None,
         fmt: int | None = None, tf: np.ndarray | None = None,
         doc_lens: np.ndarray | None = None, block_size: int | None = None
         ) -> int:
    """Write the artifact from lex-order arrays; returns bytes written.

    ``postings`` arrives ABSOLUTE (ascending per term) — the wire
    encoding (v1 deltas or v2 bitpacked blocks, per ``fmt`` /
    ``$MRI_SERVE_FORMAT``) happens here.  ``tf``/``doc_lens`` only
    matter for v2; absent, every tf is 1 and doc lengths fall back to
    the per-doc posting count — self-consistent BM25 stats for builders
    that never saw token-level frequencies.
    """
    fmt = resolve_format(fmt)
    if fmt != VERSION:
        return pack_v2(
            path, term_blob=term_blob, term_offsets=term_offsets, df=df,
            post_offsets=post_offsets, postings=postings, df_order=df_order,
            max_doc_id=max_doc_id, width=width, tf=tf, doc_lens=doc_lens,
            block_size=block_size, fmt=fmt)
    path = Path(path)
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

    deltas = postings.copy()
    if num_postings:
        deltas[1:] -= postings[:-1]
        starts = post_offsets[:-1][np.diff(post_offsets) > 0]
        deltas[starts] = postings[starts]

    layout, total = _layout(vocab, num_postings, blob_bytes)
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
    put("post_offsets", post_offsets)
    put("postings", deltas)
    put("df_order", df_order)

    return _write(path, buf, width=width, vocab=vocab,
                  num_postings=num_postings, max_doc_id=max_doc_id,
                  blob_bytes=blob_bytes)


def _header(*, width: int, vocab: int, num_postings: int, max_doc_id: int,
            blob_bytes: int, payload_len: int, payload_crc: int,
            version: int = VERSION, v2: dict | None = None) -> bytes:
    header = struct.pack(
        _HEADER_FMT, MAGIC, version, int(max(width, 1)), vocab,
        num_postings, int(max_doc_id), blob_bytes, payload_len,
        payload_crc)
    if v2 is not None:
        header += struct.pack(
            _HEADER_V2_FMT, v2["block_size"], v2.get("score_bits", 0),
            v2["num_blocks"], v2["post_data_bytes"], v2["tf_data_bytes"])
    header = header + b"\0" * (HEADER_BYTES - 4 - len(header))
    return header + struct.pack("<I", zlib.adler32(header))


def _write(path, buf: np.ndarray, *, width: int, vocab: int,
           num_postings: int, max_doc_id: int, blob_bytes: int,
           version: int = VERSION, v2: dict | None = None) -> int:
    """Checksum + header a filled file buffer, write atomically."""
    path = Path(path)
    payload = buf[HEADER_BYTES:]
    header = _header(width=width, vocab=vocab, num_postings=num_postings,
                     max_doc_id=max_doc_id, blob_bytes=blob_bytes,
                     payload_len=len(payload),
                     payload_crc=zlib.adler32(payload),
                     version=version, v2=v2)
    buf[:HEADER_BYTES] = np.frombuffer(header, dtype=np.uint8)

    tmp = path.with_name(path.name + ".tmp")
    # mrilint: allow(fault-boundary) atomic tmp+rename publish; a crash leaves only the .tmp
    with open(tmp, "wb") as f:
        f.write(memoryview(buf))
    os.replace(tmp, path)
    return len(buf)


#: 2**0 .. 2**62: an int64's bit length is how many of these it reaches.
_POW2 = np.left_shift(1, np.arange(63, dtype=np.int64))


def _bit_length(v: np.ndarray) -> np.ndarray:
    """``int.bit_length`` of each int64, exactly (0 for v <= 0)."""
    return np.searchsorted(_POW2, v, side="right").astype(np.uint8)


def _pack_blocks(vals: np.ndarray, counts: np.ndarray,
                 widths: np.ndarray) -> np.ndarray:
    """Bit-pack consecutive blocks of ``vals`` in whole-array passes.

    Block b is the next ``counts[b]`` values, each kept to its low
    ``widths[b]`` bits and packed LSB-first into little-endian u32
    words from its own word boundary (the C++ BitPacker's wire form;
    width 0 packs to nothing).  A value lands in one word, or spills
    into the next when ``shift + width > 32``; no two pieces share a
    bit, so summing them per word is an OR (exact in float64, since
    every word stays below 2**32).
    """
    counts = counts.astype(np.int64)
    widths = widths.astype(np.int64)
    nwords = (counts * widths + 31) >> 5
    total = int(nwords.sum())
    if not total:
        return np.zeros(0, dtype=np.uint8)
    woff = np.cumsum(nwords) - nwords
    voff = np.cumsum(counts) - counts
    w = np.repeat(widths, counts)
    # value i of the run sits at bit woff*32 + (i - voff) * w
    bit = np.repeat((woff << 5) - voff * widths, counts)
    bit += np.arange(len(w), dtype=np.int64) * w
    word = bit >> 5
    shift = (bit & 31).astype(np.uint64)
    del bit
    w = w.astype(np.uint64)
    v = vals.astype(np.uint64)
    v &= (np.uint64(1) << w) - np.uint64(1)
    out = np.bincount(word, weights=(v << shift) & np.uint64(0xFFFFFFFF),
                      minlength=total + 1)
    spill = shift + w > np.uint64(32)
    out += np.bincount(
        word[spill] + 1,
        weights=v[spill] >> (np.uint64(32) - shift[spill]),
        minlength=total + 1)
    return out[:total].astype("<u4").view(np.uint8)


def pack_v2(path, *, term_blob: np.ndarray, term_offsets: np.ndarray,
            df: np.ndarray, post_offsets: np.ndarray, postings: np.ndarray,
            df_order: np.ndarray, max_doc_id: int, width: int | None = None,
            tf: np.ndarray | None = None,
            doc_lens: np.ndarray | None = None,
            block_size: int | None = None, fmt: int | None = None,
            score_bits: int | None = None) -> int:
    """Write a format-v2/v2.1 artifact from lex-order ABSOLUTE postings
    (whole-array numpy passes over every block at once — the cpu
    backend's merge handle has a one-pass native equivalent in
    :func:`build_from_merge`).

    ``tf`` aligns with ``postings`` (defaults to all-ones); ``doc_lens``
    defaults to each doc's tf sum, so scoring stays self-consistent for
    builders without token-level data.  ``fmt`` 3 (the default) adds
    the per-block saturated max-tf / min-doc-length columns.
    """
    path = Path(path)
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

    # block geometry: term t's k-th block covers postings [bs, be)
    nb_t = -(-np.diff(post_offsets) // B)
    num_blocks = int(nb_t.sum())
    k = np.arange(num_blocks) - np.repeat(np.cumsum(nb_t) - nb_t, nb_t)
    bs = np.repeat(post_offsets[:-1], nb_t) + k * B
    be = np.minimum(bs + B, np.repeat(post_offsets[1:], nb_t))
    cnt = be - bs
    docs = postings[:num_postings]
    tfs = tf[:num_postings]
    blk_first = docs[bs]
    blk_max = docs[be - 1]
    # gap-1 to the previous doc id, 0 at each block's first posting
    dz = np.zeros(num_postings, dtype=np.int64)
    np.subtract(docs[1:], docs[:-1], out=dz[1:], dtype=np.int64)
    dz[1:] -= 1
    dz[bs] = 0
    blk_width = _bit_length(np.maximum.reduceat(dz, bs))
    keep = np.ones(num_postings, dtype=bool)
    keep[bs] = False
    post_data = _pack_blocks(dz[keep], cnt - 1, blk_width)
    del dz, keep
    mtf = np.maximum.reduceat(tfs, bs)
    blk_tf_width = _bit_length(mtf - 1)
    tf_data = _pack_blocks(tfs - 1, cnt, blk_tf_width)

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
    put("blk_max", blk_max)
    put("blk_first", blk_first)
    put("blk_width", blk_width)
    put("blk_tf_width", blk_tf_width)
    if bits:
        # saturated integer columns (never floats: the native exporter
        # must reproduce these bytes exactly)
        cap = (1 << bits) - 1
        sdt = "<u1" if bits == 8 else "<u2"
        put("blk_max_tf", np.minimum(mtf, cap).astype(sdt))
        min_dl = np.minimum.reduceat(doc_lens[docs], bs)
        put("blk_min_dl", np.minimum(min_dl, cap).astype(sdt))
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


class Artifact:
    """Zero-copy numpy views over a verified, mmapped ``index.mri``.

    Both format versions present the same decode API; v2 additionally
    exposes the block skip table (``blk_max``/``blk_first``/widths), the
    derived block geometry (``term_block_off``, ``blk_cnt``, word-offset
    prefix sums) and the BM25 columns (``decode_tf``, ``doc_lens``).
    """

    _VIEW_NAMES = ("letter_dir", "term_offsets", "term_blob", "df",
                   "post_offsets", "postings", "df_order",
                   "blk_max", "blk_first", "blk_width", "blk_tf_width",
                   "blk_max_tf", "blk_min_dl",
                   "post_words", "tf_words", "doc_lens")

    def __init__(self, path: Path, mm: mmap.mmap, meta: dict,
                 views: dict[str, np.ndarray]):
        self.path = path
        self._mm = mm
        self.version = meta.get("version", VERSION)
        self.vocab = meta["vocab"]
        self.num_postings = meta["num_postings"]
        self.max_doc_id = meta["max_doc_id"]
        self.width = meta["width"]
        self.nbytes = meta["nbytes"]
        for name in self._VIEW_NAMES:
            setattr(self, name, views.get(name))
        # v2 derived block geometry (computed by the loader, vectorized)
        self.block_size = meta.get("block_size", 0)
        self.num_blocks = meta.get("num_blocks", 0)
        self.score_bits = meta.get("score_bits", 0)
        self.term_block_off = meta.get("term_block_off")
        self.blk_cnt = meta.get("blk_cnt")
        self.blk_woff = meta.get("blk_woff")
        self.blk_tf_woff = meta.get("blk_tf_woff")

    @property
    def has_block_scores(self) -> bool:
        """True when the v2.1 per-block max-score columns are present
        (the planner's precondition for Block-Max WAND / MaxScore)."""
        return self.blk_max_tf is not None

    def term(self, idx: int) -> bytes:
        lo, hi = self.term_offsets[idx], self.term_offsets[idx + 1]
        return self.term_blob[lo:hi].tobytes()

    def _gather_packed(self, sel: np.ndarray, words: np.ndarray,
                       woff: np.ndarray, widths: np.ndarray,
                       nvals: np.ndarray) -> np.ndarray:
        """Decode variable-width packed values for the selected blocks.

        ``sel`` indexes blocks; block i holds ``nvals[i]`` values at
        ``widths[i]`` bits starting at word ``woff[i]`` of ``words``.
        Returns an (len(sel), max(nvals)) int64 matrix; entries past a
        block's count are 0.  Fully vectorized: one word gather, one
        unpackbits, one broadcast bit-gather, one matmul.
        """
        n = len(sel)
        J = int(nvals.max()) if n else 0
        out = np.zeros((n, max(J, 1)), dtype=np.int64)
        if not n or not J:
            return out
        W = int(widths.max())
        wlen = (nvals * widths + 31) >> 5
        total = int(wlen.sum())
        if not W or not total:
            return out
        starts = np.zeros(n, dtype=np.int64)
        np.cumsum(wlen[:-1], out=starts[1:])
        word_src = np.repeat(woff - starts, wlen) + np.arange(total)
        bits = np.unpackbits(
            np.ascontiguousarray(words[word_src]).view(np.uint8),
            bitorder="little")
        j = np.arange(J)
        k = np.arange(W)
        bitpos = (starts * 32)[:, None] + j[None, :] * widths[:, None]
        idx3 = bitpos[:, :, None] + k[None, None, :]
        np.clip(idx3, 0, bits.size - 1, out=idx3)
        valid = (j[None, :, None] < nvals[:, None, None]) & \
                (k[None, None, :] < widths[:, None, None])
        g = np.where(valid, bits[idx3], 0)
        out[:, :J] = g @ (np.int64(1) << k)
        return out

    def decode_blocks(self, sel: np.ndarray) -> tuple[np.ndarray,
                                                      np.ndarray]:
        """v2: absolute doc ids of the selected (global) block indices.

        Returns ``(ids, cnt)`` — an (len(sel), block_size) int32 matrix
        (entries past ``cnt[i]`` are garbage; mask with
        ``arange(block_size) < cnt[:, None]``) and the per-block counts.
        """
        sel = np.asarray(sel, dtype=np.int64)
        cnt = self.blk_cnt[sel].astype(np.int64)
        w = self.blk_width[sel].astype(np.int64)
        deltas = self._gather_packed(sel, self.post_words,
                                     self.blk_woff[sel], w, cnt - 1)
        B = self.block_size
        out = np.zeros((len(sel), B), dtype=np.int64)
        out[:, 0] = self.blk_first[sel]
        out[:, 1:deltas.shape[1] + 1] = np.where(
            np.arange(deltas.shape[1])[None, :] < (cnt - 1)[:, None],
            deltas + 1, 0)
        np.cumsum(out, axis=1, out=out)
        return out.astype(np.int32), cnt

    def decode_tf_blocks(self, sel: np.ndarray) -> tuple[np.ndarray,
                                                         np.ndarray]:
        """v2: per-doc term frequencies of the selected (global) block
        indices, aligned row-for-row with :meth:`decode_blocks` — an
        (len(sel), block_size) int64 matrix plus the per-block counts
        (entries past ``cnt[i]`` are meaningless; mask like
        ``decode_blocks``)."""
        sel = np.asarray(sel, dtype=np.int64)
        cnt = self.blk_cnt[sel].astype(np.int64)
        tw = self.blk_tf_width[sel].astype(np.int64)
        vals = self._gather_packed(sel, self.tf_words,
                                   self.blk_tf_woff[sel], tw, cnt)
        B = self.block_size
        tfm = (vals + 1)[:, :B]
        if tfm.shape[1] < B:
            tfm = np.pad(tfm, ((0, 0), (0, B - tfm.shape[1])))
        return tfm, cnt

    def decode_postings(self, idx: int) -> np.ndarray:
        """One term's absolute ascending doc ids (a fresh array)."""
        if self.version == VERSION:
            lo, hi = self.post_offsets[idx], self.post_offsets[idx + 1]
            return np.cumsum(self.postings[lo:hi], dtype=np.int64).astype(
                np.int32)
        b0, b1 = self.term_block_off[idx], self.term_block_off[idx + 1]
        if b0 == b1:
            return np.zeros(0, dtype=np.int32)
        ids, cnt = self.decode_blocks(np.arange(b0, b1))
        return ids[np.arange(self.block_size)[None, :] < cnt[:, None]]

    def decode_tf(self, idx: int) -> np.ndarray:
        """One term's per-document term frequencies, aligned with
        :meth:`decode_postings` (v1 artifacts carry no tf: all ones)."""
        if self.version == VERSION:
            df = int(self.post_offsets[idx + 1] - self.post_offsets[idx])
            return np.ones(df, dtype=np.int32)
        b0, b1 = self.term_block_off[idx], self.term_block_off[idx + 1]
        if b0 == b1:
            return np.zeros(0, dtype=np.int32)
        sel = np.arange(b0, b1)
        cnt = self.blk_cnt[sel].astype(np.int64)
        tw = self.blk_tf_width[sel].astype(np.int64)
        vals = self._gather_packed(sel, self.tf_words,
                                   self.blk_tf_woff[sel], tw, cnt)
        tfm = (vals + 1)[:, :self.block_size]
        if tfm.shape[1] < self.block_size and len(sel) > 1:
            tfm = np.pad(tfm, ((0, 0),
                               (0, self.block_size - tfm.shape[1])))
        return tfm[np.arange(tfm.shape[1])[None, :]
                   < cnt[:, None]].astype(np.int32)

    def close(self) -> None:
        # drop the views before the mmap: numpy holds buffer references
        for name in self._VIEW_NAMES:
            setattr(self, name, None)
        self.term_block_off = self.blk_cnt = None
        self.blk_woff = self.blk_tf_woff = None
        if self._mm is not None:
            try:
                self._mm.close()
            except BufferError:
                # a caller still holds a view (e.g. an engine's df
                # column): the map frees when the last view dies
                pass
            self._mm = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def load_artifact(path: str | Path) -> Artifact:
    """mmap + verify an artifact (a directory means its ``index.mri``).

    Every structural and checksum violation raises :class:`ArtifactError`
    with a one-line reason — the contract the CLI maps to exit 2.
    """
    path = Path(path)
    if path.is_dir():
        path = path / ARTIFACT_NAME
    try:
        f = open(path, "rb")
    except OSError as e:
        msg = f"{path}: cannot open artifact ({e})"
        # A letter-file index next to a missing index.mri means the
        # build ran without --artifact: name the remediation instead of
        # leaving the operator to diff the two output formats.
        if path.name == ARTIFACT_NAME and not path.exists() \
                and (path.parent / "a.txt").exists():
            msg += ("; directory holds a letter-file index built "
                    "without --artifact — rebuild with --artifact "
                    "to pack index.mri")
        raise ArtifactError(msg) from e
    with f:
        try:
            size = os.fstat(f.fileno()).st_size
            if size < HEADER_BYTES:
                raise ArtifactError(
                    f"{path}: {size} bytes is smaller than the "
                    f"{HEADER_BYTES}-byte header")
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except (OSError, ValueError) as e:
            raise ArtifactError(f"{path}: cannot map artifact ({e})") from e
    try:
        head = bytes(mm[:HEADER_BYTES])
        (want_crc,) = struct.unpack_from("<I", head, HEADER_BYTES - 4)
        if zlib.adler32(head[:HEADER_BYTES - 4]) != want_crc:
            raise ArtifactError(f"{path}: header checksum mismatch")
        (magic, version, width, vocab, num_postings, max_doc_id,
         blob_bytes, payload_bytes, payload_crc) = struct.unpack_from(
            _HEADER_FMT, head)
        if magic != MAGIC:
            raise ArtifactError(
                f"{path}: bad magic {magic!r} (not an index.mri)")
        if version not in (VERSION, VERSION_V2, VERSION_V21):
            raise ArtifactError(
                f"{path}: unsupported artifact version {version} "
                f"(this reader knows versions {VERSION}-{VERSION_V21})")
        v2 = None
        score_bits = 0
        if version >= VERSION_V2:
            (block_size, score_bits, num_blocks, post_data_bytes,
             tf_data_bytes) = struct.unpack_from(
                _HEADER_V2_FMT, head, _HEADER_V2_OFF)
            if block_size < 2 or block_size & (block_size - 1):
                raise ArtifactError(
                    f"{path}: invalid v2 block size {block_size}")
            if version == VERSION_V2:
                score_bits = 0  # v2 writers zeroed this slot
            elif score_bits not in (8, 16):
                raise ArtifactError(
                    f"{path}: invalid v2.1 score_bits {score_bits}")
            v2 = (block_size, num_blocks, post_data_bytes, tf_data_bytes)
            layout, total = _layout_v2(
                vocab, blob_bytes, num_blocks, post_data_bytes,
                tf_data_bytes, max_doc_id, score_bits=score_bits)
        else:
            layout, total = _layout(vocab, num_postings, blob_bytes)
        if total != size or payload_bytes != size - HEADER_BYTES:
            raise ArtifactError(
                f"{path}: truncated artifact — header promises "
                f"{total} bytes, file has {size}")
        if zlib.adler32(mm[HEADER_BYTES:]) != payload_crc:
            raise ArtifactError(f"{path}: payload checksum mismatch")

        raw = np.frombuffer(mm, dtype=np.uint8)
        dtypes = {"letter_dir": np.int64, "term_offsets": np.int64,
                  "term_blob": np.uint8, "df": np.int32,
                  "post_offsets": np.int64, "postings": np.int32,
                  "df_order": np.int32,
                  "blk_max": np.int32, "blk_first": np.int32,
                  "blk_width": np.uint8, "blk_tf_width": np.uint8,
                  "blk_max_tf": "<u1" if score_bits == 8 else "<u2",
                  "blk_min_dl": "<u1" if score_bits == 8 else "<u2",
                  "post_words": np.uint32, "tf_words": np.uint32,
                  "doc_lens": np.int32}
        names = {"post_data": "post_words", "tf_data": "tf_words"}
        views = {}
        for name, (off, nbytes) in layout.items():
            name = names.get(name, name)
            views[name] = raw[off:off + nbytes].view(dtypes[name])
        meta = {"version": version, "vocab": vocab,
                "num_postings": num_postings,
                "max_doc_id": max_doc_id, "width": width, "nbytes": size}
        if v2 is not None:
            block_size, num_blocks, post_data_bytes, tf_data_bytes = v2
            df = views["df"].astype(np.int64)
            bpt = -(-df // block_size)  # ceil(df / B); 0 for df == 0
            term_block_off = np.zeros(vocab + 1, dtype=np.int64)
            np.cumsum(bpt, out=term_block_off[1:])
            if term_block_off[-1] != num_blocks:
                raise ArtifactError(
                    f"{path}: v2 geometry mismatch — df implies "
                    f"{int(term_block_off[-1])} blocks, header says "
                    f"{num_blocks}")
            blk_cnt = np.full(num_blocks, block_size, dtype=np.int32)
            last = term_block_off[1:][bpt > 0] - 1
            blk_cnt[last] = (df[bpt > 0]
                             - (bpt[bpt > 0] - 1) * block_size)
            cnt64 = blk_cnt.astype(np.int64)
            pw = (np.maximum(cnt64 - 1, 0)
                  * views["blk_width"].astype(np.int64) + 31) >> 5
            tw = (cnt64 * views["blk_tf_width"].astype(np.int64)
                  + 31) >> 5
            blk_woff = np.zeros(num_blocks + 1, dtype=np.int64)
            np.cumsum(pw, out=blk_woff[1:])
            blk_tf_woff = np.zeros(num_blocks + 1, dtype=np.int64)
            np.cumsum(tw, out=blk_tf_woff[1:])
            if blk_woff[-1] * 4 != post_data_bytes \
                    or blk_tf_woff[-1] * 4 != tf_data_bytes:
                raise ArtifactError(
                    f"{path}: v2 geometry mismatch — widths imply "
                    f"{int(blk_woff[-1]) * 4}/{int(blk_tf_woff[-1]) * 4} "
                    f"packed bytes, header says "
                    f"{post_data_bytes}/{tf_data_bytes}")
            meta.update(block_size=block_size, num_blocks=num_blocks,
                        score_bits=score_bits,
                        term_block_off=term_block_off, blk_cnt=blk_cnt,
                        blk_woff=blk_woff, blk_tf_woff=blk_tf_woff)
        return Artifact(path, mm, meta, views)
    except ArtifactError:
        mm.close()
        raise
    except Exception:
        mm.close()
        raise


def term_table(art: Artifact) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Materialize the engines' term-resolution columns from the blob.

    Returns ``(rows, terms, key8)``:

    - ``rows``   (max(V,1), width) uint8 — NUL-padded fixed-width term
      rows, scattered from the compact blob in two vectorized ops
    - ``terms``  (V,) ``S{width}`` view of those rows (exact-match gathers)
    - ``key8``   (V, 8) uint8 — each term's NUL-padded 8-byte prefix;
      viewed big-endian, numeric order == lexicographic term order, so
      it is THE binary-search key column (host: one ``>u8`` view;
      device: a (hi, lo) ``u32`` pair, x64-free)
    """
    V, width = art.vocab, max(art.width, 1)
    lens = np.diff(art.term_offsets)
    rows = np.zeros((max(V, 1), width), dtype=np.uint8)
    if V:
        rows[np.arange(width) < lens[:, None]] = art.term_blob
    terms = rows.view(f"S{width}").ravel()[:V]
    pad = rows if width >= 8 else np.pad(rows, ((0, 0), (0, 8 - width)))
    key8 = np.ascontiguousarray(pad[:, :8])[:V]
    return rows, terms, key8


def device_columns(art: Artifact) -> dict:
    """Host-side staging of every column the device engine uploads.

    All integer columns are narrowed to 32-bit (jax default, x64 off):
    the 8-byte prefix key becomes a big-endian ``(key_hi, key_lo)``
    uint32 pair whose pairwise lexicographic order equals the u64
    numeric order, and ``post_offsets`` drops to int32 — guarded, since
    an artifact with >= 2**31 postings can't be addressed that way.
    ``max_prefix_group`` is the largest set of vocabulary terms sharing
    one 8-byte prefix: the static trip count of the device lookup's
    collision-fixup loop.
    """
    if art.num_postings >= 2 ** 31 or art.vocab >= 2 ** 31:
        raise ArtifactError(
            f"{art.path}: {art.num_postings} postings / {art.vocab} terms "
            f"exceed the device engine's int32 addressing")
    rows, _, key8 = term_table(art)
    V = art.vocab
    if V:
        key_hi = np.ascontiguousarray(key8[:, :4]).view(">u4").ravel()
        key_lo = np.ascontiguousarray(key8[:, 4:]).view(">u4").ravel()
        groups = np.unique(key8.view(">u8").ravel(), return_counts=True)[1]
        max_group = int(groups.max())
    else:
        key_hi = key_lo = np.zeros(0, dtype=np.uint32)
        max_group = 1
    cols = {
        "format": art.version,
        "rows": rows[:V],
        "key_hi": key_hi.astype(np.uint32),
        "key_lo": key_lo.astype(np.uint32),
        "df": np.ascontiguousarray(art.df, dtype=np.int32),
        "df_order": np.ascontiguousarray(art.df_order, dtype=np.int32),
        "letter_dir": np.ascontiguousarray(art.letter_dir, dtype=np.int32),
        "max_prefix_group": max_group,
        "vocab": V,
        "width": max(art.width, 1),
        "max_doc_id": art.max_doc_id,
    }
    if art.version == VERSION:
        cols["post_offsets"] = np.ascontiguousarray(
            art.post_offsets, dtype=np.int32)
        cols["postings"] = np.ascontiguousarray(
            art.postings, dtype=np.int32)
        return cols
    # v2: blocked layout.  All word offsets must fit int32 addressing;
    # one zero pad word past each packed stream lets the unaligned
    # two-word bit-window gather read words[i + 1] unconditionally.
    if art.blk_woff[-1] >= 2 ** 31 - 1 \
            or art.blk_tf_woff[-1] >= 2 ** 31 - 1:
        raise ArtifactError(
            f"{art.path}: packed postings exceed the device engine's "
            f"int32 word addressing")
    pad = np.zeros(1, dtype=np.uint32)
    cols.update({
        "block_size": art.block_size,
        "term_block_off": np.ascontiguousarray(
            art.term_block_off, dtype=np.int32),
        "blk_first": np.ascontiguousarray(art.blk_first, dtype=np.int32),
        "blk_width": np.ascontiguousarray(art.blk_width, dtype=np.int32),
        "blk_tf_width": np.ascontiguousarray(
            art.blk_tf_width, dtype=np.int32),
        "blk_woff": np.ascontiguousarray(art.blk_woff, dtype=np.int32),
        "blk_tf_woff": np.ascontiguousarray(
            art.blk_tf_woff, dtype=np.int32),
        "post_words": np.concatenate([art.post_words, pad]),
        "tf_words": np.concatenate([art.tf_words, pad]),
        "doc_lens": np.ascontiguousarray(art.doc_lens, dtype=np.int32),
    })
    return cols


def serve_columns(art: Artifact) -> dict:
    """Zero-copy column views handed to the native serve kernels.

    Unlike ``device_columns`` this makes NO padded copies: every entry
    is a view straight into the artifact mmap (or a derived geometry
    array the loader already materialized), so the dict is valid only
    while the artifact stays open.  The native unpack kernel reads one
    u32 word past each block payload unconditionally; that over-read is
    always in-file because the v2 layout places ``tf_data`` /
    ``doc_lens`` / ``df_order`` after ``post_data`` and ``doc_lens`` /
    ``df_order`` after ``tf_data`` (both 16-byte aligned), so no pad
    word is appended here.  ``blk_max_tf`` / ``blk_min_dl`` are exposed
    as raw bytes (``None`` on plain v2): C picks u8 vs u16-LE off
    ``score_bits`` itself.
    """
    if art.version < VERSION_V2:
        raise ArtifactError(
            f"{art.path}: native serve kernels need a v2+ artifact "
            f"(got version {art.version})")
    has_scores = art.score_bits != 0
    return {
        "blk_max": art.blk_max,
        "blk_first": art.blk_first,
        "blk_width": art.blk_width,
        "blk_tf_width": art.blk_tf_width,
        "blk_max_tf": art.blk_max_tf.view(np.uint8) if has_scores
        else None,
        "blk_min_dl": art.blk_min_dl.view(np.uint8) if has_scores
        else None,
        "post_words": art.post_words,
        "tf_words": art.tf_words,
        "term_block_off": art.term_block_off,
        "blk_cnt": art.blk_cnt,
        "blk_woff": art.blk_woff,
        "blk_tf_woff": art.blk_tf_woff,
        "vocab": art.vocab,
        "num_blocks": art.num_blocks,
        "block_size": art.block_size,
        "score_bits": art.score_bits,
    }


def bm25_corpus(art: Artifact) -> tuple[np.ndarray, int, float]:
    """``(doc_lens float64, ndocs, avgdl)`` for BM25 scoring.

    v2 reads the packed doc-length column; v1 carries no lengths, so
    they are reconstructed from the postings themselves (each stored
    pair counts 1 — the same tf=1 fallback the scorer uses).  Shared by
    both engines so their corpus statistics agree exactly.
    """
    if art.version >= VERSION_V2:
        doc_lens = art.doc_lens.astype(np.float64)
    elif art.num_postings:
        flat = art.postings.astype(np.int64)
        starts = art.post_offsets[:-1]
        csum = np.cumsum(flat)
        # undo the per-term delta encoding in one pass: subtract each
        # term's cumulative base, re-anchor at its first absolute id
        base = np.repeat(
            csum[starts] - flat[starts], np.diff(art.post_offsets))
        doc_lens = np.bincount(
            (csum - base).astype(np.int64),
            minlength=art.max_doc_id + 1).astype(np.float64)
    else:
        doc_lens = np.zeros(art.max_doc_id + 1, dtype=np.float64)
    ndocs = int(np.count_nonzero(doc_lens))
    avgdl = float(doc_lens[doc_lens > 0].mean()) if ndocs else 1.0
    return doc_lens, ndocs, avgdl


def checksum(path: str | Path) -> tuple[str, int]:
    """``(adler32_hex, size)`` of the artifact file — the audit
    manifest's fingerprint, same scheme as the letter files.  Shim
    over :func:`..utils.checksum.file_checksum`."""
    return file_checksum(path)


# -- builders: lex arrays from each engine family's native shapes --------


def build_from_merge(path, merge, *, fmt: int | None = None,
                     block_size: int | None = None) -> int:
    """Pack straight off a live :class:`native.HostIndexMerge`: one C++
    pass fills every payload section of the final file buffer at the
    layout's offsets — compact blob, delta-encoded postings and all —
    leaving only checksums, the header, and the atomic write here.  The
    cpu backend's fast path: the two-step ``export_arrays`` +
    :func:`build_from_export` route costs ~2x more on the pack-time
    budget (<= 10 % of the unaudited e2e).

    ``fmt``/``block_size`` default to the ``MRI_SERVE_FORMAT`` /
    ``MRI_SERVE_BLOCK_SIZE`` knobs; format 2 runs the native two-call
    v2 export (prepare sizes the packed streams, payload fills them).
    """
    vocab, width, num_pairs, blob_bytes, max_doc_id = merge.export_info()
    fmt = resolve_format(fmt)
    if fmt >= VERSION_V2:
        block_size = resolve_block_size(block_size)
        bits = resolve_score_bits() if fmt == VERSION_V21 else 0
        num_blocks, post_bytes, tf_bytes = \
            merge.export_v2_prepare(block_size, bits)
        layout, total = _layout_v2(vocab, blob_bytes, num_blocks,
                                   post_bytes, tf_bytes, max_doc_id,
                                   score_bits=bits)
        buf = np.zeros(total, dtype=np.uint8)
        merge.export_v2_payload(
            buf, {n: off for n, (off, _) in layout.items()})
        return _write(path, buf, width=width, vocab=vocab,
                      num_postings=num_pairs, max_doc_id=max_doc_id,
                      blob_bytes=blob_bytes, version=fmt,
                      v2={"block_size": block_size,
                          "num_blocks": num_blocks,
                          "score_bits": bits,
                          "post_data_bytes": post_bytes,
                          "tf_data_bytes": tf_bytes})
    layout, total = _layout(vocab, num_pairs, blob_bytes)
    buf = np.zeros(total, dtype=np.uint8)
    merge.export_payload(buf, {n: off for n, (off, _) in layout.items()})
    return _write(path, buf, width=width, vocab=vocab,
                  num_postings=num_pairs, max_doc_id=max_doc_id,
                  blob_bytes=blob_bytes)


def build_from_export(path, export: dict) -> int:
    """Pack from :meth:`native.HostIndexMerge.export_arrays` output —
    the cpu backend's no-text-round-trip path."""
    vocab_packed = export["vocab_packed"]
    word_lens = np.asarray(export["word_lens"], dtype=np.int64)
    term_offsets = np.zeros(len(word_lens) + 1, dtype=np.int64)
    np.cumsum(word_lens, out=term_offsets[1:])
    if len(word_lens):
        # trim the NUL padding out of the fixed-width rows, vectorized:
        # keep column j of row i when j < word_lens[i]
        width = vocab_packed.shape[1]
        mask = np.arange(width) < word_lens[:, None]
        term_blob = vocab_packed[mask]
    else:
        term_blob = np.zeros(0, dtype=np.uint8)
    return pack(
        path, term_blob=term_blob, term_offsets=term_offsets,
        df=export["df"], post_offsets=export["offsets"],
        postings=export["postings"], df_order=export["df_order"],
        max_doc_id=export["max_doc_id"], width=export["width"])


def build_from_emit_arrays(path, *, vocab: np.ndarray, order: np.ndarray,
                           df: np.ndarray, offsets: np.ndarray,
                           postings: np.ndarray, max_doc_id: int) -> int:
    """Pack from ``formatter.emit_index``'s argument shapes (the device
    engines' host-side arrays): 'S' terms in ANY order (re-sorted to
    the lex invariant here), ``order`` the emit permutation over those
    indices, ``offsets``/``df`` addressing absolute postings in a
    possibly oversized buffer (gaps re-compacted here)."""
    vocab = np.asarray(vocab)
    df = np.asarray(df, dtype=np.int64)
    order = np.asarray(order, dtype=np.int64)
    offsets = np.asarray(offsets, dtype=np.int64)
    postings = np.asarray(postings, dtype=np.int32)
    V = len(vocab)
    # original index -> lex rank (identity when vocab arrives sorted,
    # e.g. from the one-shot device engine's sorted-unique output)
    perm = np.argsort(vocab, kind="stable")
    inv = np.empty(V, dtype=np.int64)
    inv[perm] = np.arange(V)
    vocab = vocab[perm]
    df_lex = df[perm]
    starts_lex = offsets[perm]
    lens = np.char.str_len(vocab).astype(np.int64) if V else \
        np.zeros(0, dtype=np.int64)
    term_offsets = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(lens, out=term_offsets[1:])
    if V:
        width = vocab.dtype.itemsize
        rows = np.ascontiguousarray(vocab).view(np.uint8).reshape(V, width)
        term_blob = rows[np.arange(width) < lens[:, None]]
    else:
        term_blob = np.zeros(0, dtype=np.uint8)
    post_offsets = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(df_lex, out=post_offsets[1:])
    P = int(post_offsets[-1])
    flat = np.zeros(0, dtype=np.int32)
    if P:
        src = (np.repeat(starts_lex, df_lex)
               + (np.arange(P) - np.repeat(post_offsets[:-1], df_lex)))
        flat = postings[src]
    return pack(
        path, term_blob=term_blob, term_offsets=term_offsets, df=df_lex,
        post_offsets=post_offsets, postings=flat,
        df_order=inv[order], max_doc_id=int(max_doc_id))


def build_from_grouped(path, per_letter: dict) -> int:
    """Pack from the oracle/empty-path grouped form: per-letter lists of
    ``(word_bytes, ids)`` already in emit order."""
    words: list[bytes] = []
    ids: list[list[int]] = []
    for letter in sorted(per_letter):
        for word, docs in per_letter[letter]:
            words.append(word)
            ids.append(list(docs))
    emit_to_lex = np.argsort(np.array(words, dtype="S") if words
                             else np.zeros(0, dtype="S1"), kind="stable")
    lex_words = [words[i] for i in emit_to_lex]
    # df_order[emit position] = lex index: the argsort's inverse
    df_order = np.empty(len(words), dtype=np.int64)
    df_order[emit_to_lex] = np.arange(len(words))
    term_blob = np.frombuffer(b"".join(lex_words), dtype=np.uint8)
    term_offsets = np.zeros(len(words) + 1, dtype=np.int64)
    np.cumsum([len(w) for w in lex_words], out=term_offsets[1:])
    df = np.array([len(ids[i]) for i in emit_to_lex], dtype=np.int64)
    post_offsets = np.zeros(len(words) + 1, dtype=np.int64)
    np.cumsum(df, out=post_offsets[1:])
    flat = (np.concatenate([np.asarray(ids[i], dtype=np.int32)
                            for i in emit_to_lex])
            if words else np.zeros(0, dtype=np.int32))
    max_doc_id = int(flat.max()) if len(flat) else 0
    return pack(
        path, term_blob=term_blob, term_offsets=term_offsets, df=df,
        post_offsets=post_offsets, postings=flat, df_order=df_order,
        max_doc_id=max_doc_id)

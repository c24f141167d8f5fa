"""Device-side tokenizer: the ENTIRE map phase as one XLA program.

Every other engine in this package keeps the reference's split: host
scans text (main.c:102-117 re-expressed in C++/numpy), device sorts
integers.  This module removes the host from the compute path entirely:
raw corpus bytes go up, the finished index comes down.

    bytes (uint8, N) ──► classify: space/letter as fused compares
            (256-entry table gathers cost ~100 ms at 5.7M bytes on the
            v5e; the compare chain is free — round-3 attribution)
        ──► token segmentation: start mask, letter-count cumsum
        ──► letter compaction: ONE position-keyed ``lax.sort`` moves
            every cleaned letter to the front in byte order (the
            byte stream with non-letters deleted, main.c:105-111),
            carrying the lowered bytes as a sort payload
        ──► per-token offsets/lengths: token start bytes via a second
            single-key sort (set-bit positions), then F = one gather
            of the exclusive letter cumsum — no token-scale
            searchsorted (its scan lowering was the round-2 program's
            dominant cost: 702 ms for 2^20 queries into 5.7M)
        ──► word rows: windowed gathers off the compacted letter
            stream pack big-endian int32 columns (cleaned bytes are
            a-z < 0x80, so signed int32 ascending == byte-
            lexicographic ascending)
        ──► LSD radix ``lax.sort`` passes over (word columns…, doc)
        ──► boundary-diff word/pair dedup ► df ► postings ► unique rows

    Why sorts/gathers and never large scatters: XLA lowers TPU scatter
    to a serial per-update loop (~75 ns/update measured on v5e — a
    single 1M-update scatter costs ~75 ms, 5x a whole 1M stable-sort
    pass).  The first cut of this module scattered letters into rows
    and compacted results with scatters; every token-scale scatter is
    now a sort/cumsum/gather formulation.  Scatters are kept only at
    trivial sizes (the num_docs-entry doc-boundary marker).

Exactness without strings-on-host: rows are the *actual cleaned bytes*
(no hashing, no collisions); sorted-row order IS strcmp order because
rows are zero-padded (0x00 < any letter, so shorter words sort first —
the same argument as the C side's prefix keys, native/tokenizer.cc
SortedOrder).  Words longer than ``width`` cleaned letters cannot be
represented exactly; the program returns the global max cleaned length
and the caller MUST fall back to a host path when it exceeds ``width``
(``WidthOverflow``).  The reference's own cap is 299 (main.c:105), and
its corpus maxes at 38, so ``width=48`` covers real text with margin.

This is the TPU-first endpoint of the design space: on hardware where
the host<->device link is ~free (local PCIe), the whole pipeline runs
at device sort throughput; on a high-RTT link the host-scan engines
win end-to-end (bench.py records both, labeled).  Reference seams
re-expressed: mapper tokenize+emit (main.c:85-124) and reducer
dedup/sort (main.c:126-242) become one fused program with no
intermediate materialization at all — not even the (term, doc) pair
array the other engines feed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import segment

INT32_MAX = np.iinfo(np.int32).max

# Byte-buffer size above which the letter compaction's (flag, position)
# key no longer fits in one int32 and tokenize_rows switches to a
# two-key sort.  Module-level so tests can force the two-key branch on
# small inputs and compare it against the one-key path.
_ONE_KEY_COMPACTION_LIMIT = 1 << 24

# The round-2 MRI_TPU_LETTER_COMPACTION=searchsorted variant was
# removed after the round-3 on-chip A/B: the cumsum-rank binary-search
# compaction measured 2150.8 ms device_index vs 1156.6 ms for the
# position-keyed sort on the v5e (round-3 record,
# letter_compaction_ab), and the sort formulation then absorbed the
# letter payload for free.


class WidthOverflow(Exception):
    """A cleaned token exceeded the row width — the device rows would be
    truncated (inexact); the caller must fall back to a host tokenizer."""


@functools.lru_cache(maxsize=1)
def _byte_tables():
    """(space, lower) 256-entry tables — the exact C-locale contract of
    the native scan (native/tokenizer.cc ByteTables).  Cached as numpy
    (NOT device arrays: an lru-cached jnp value created inside a trace
    would leak that trace's tracers into later calls); jit closes over
    them as constants."""
    space = np.zeros(256, np.bool_)
    for b in b" \t\n\v\f\r":
        space[b] = True
    lower = np.zeros(256, np.uint8)
    for b in range(ord("a"), ord("z") + 1):
        lower[b] = b
    for b in range(ord("A"), ord("Z") + 1):
        lower[b] = b + 32
    return space, lower


def _tokenize_front(data, doc_ends, doc_id_values, *, tok_cap: int,
                    num_docs: int):
    """Shared front half of both tokenizer frontends: byte classify,
    token segmentation, letter compaction, per-token offsets/lengths
    and doc ids.  Returns ``(letters, F0, tok_len, max_word_len,
    doc_of_tok, valid_tok, num_tokens, n)`` — everything the word-row
    packers (:func:`tokenize_rows`, :func:`tokenize_groups`) need."""
    n = data.shape[0]
    # byte classifiers as arithmetic, not 256-entry table gathers: a
    # token-scale gather costs ~7 ms/2^20 rows on the v5e where the
    # compare chain fuses for free (round-3 attribution on the v5e:
    # the two table lookups were ~100 ms of the program).  Exact
    # C-locale contract of
    # native/tokenizer.cc ByteTables: space = {0x20, 0x09..0x0D};
    # A-Z|0x20 lands in [a-z] and no non-letter byte does (the only
    # preimages of [0x61,0x7A] under |0x20 are the two letter ranges).
    is_space = (data == 0x20) | ((data >= 0x09) & (data <= 0x0D))
    lc = data | jnp.uint8(0x20)
    is_letter = (lc >= 0x61) & (lc <= 0x7A)
    lowered = jnp.where(is_letter, lc, jnp.uint8(0)).astype(jnp.int32)

    pos = jnp.arange(n, dtype=jnp.int32)
    # first byte of each document forces a token break (tokens never
    # span documents — the per-doc scan loop of every host frontend).
    # num_docs-entry scatters: trivially small, the only ones kept.
    doc_starts = jnp.zeros(n, jnp.bool_).at[doc_ends[:-1]].set(
        True, mode="drop").at[0].set(True)
    # manifest slot per byte: scatter-max doc slots at their start
    # bytes (max resolves zero-length-doc collisions the same way as
    # searchsorted side="right": the last doc starting there owns the
    # byte), then cummax propagates slots forward
    doc_slot_of_byte = lax.cummax(
        jnp.zeros(n, jnp.int32).at[doc_ends[:-1]].max(
            jnp.arange(1, num_docs, dtype=jnp.int32), mode="drop"))
    nonspace = ~is_space
    prev_space = jnp.concatenate([jnp.ones(1, jnp.bool_), is_space[:-1]])
    token_start = nonspace & (prev_space | doc_starts)

    cs = jnp.cumsum(is_letter.astype(jnp.int32))

    # letter compaction: ONE sort on (non-letter flag, byte position)
    # packed into a single key moves every cleaned letter to the front
    # in byte order — the reference's delete-non-letters pass
    # (main.c:105-111) with no scatter.  Position fits the key's low
    # bits; the flag rides above them, so ascending key order is
    # "letters first, each group in byte order".  ``lowered`` rides
    # along as a payload of the SAME sort, so the compacted letter
    # stream needs no n-scale gather afterwards (round-3 on-chip
    # attribution: each such gather is ~40 ms at 5.7M bytes).
    if n < _ONE_KEY_COMPACTION_LIMIT:
        key = jnp.where(is_letter, pos, pos + jnp.int32(1 << 24))
        _, letters = lax.sort((key, lowered), num_keys=1, is_stable=True)
    else:  # buffers >= 16 MiB per program: flag no longer fits beside
        # the position in an int32 (and int64 needs jax_enable_x64),
        # so sort on (flag, position) as two keys instead
        _, _, letters = lax.sort(
            ((~is_letter).astype(jnp.int32), pos, lowered), num_keys=2,
            is_stable=True)
    # compacted letter stream: past num_letters every payload is 0
    # (non-letters carry lowered == 0), but no consumer may rely on
    # the tail: every unmasked window read below stays inside its own
    # token's letters (masktab[nbytes]).

    # per-token letter offsets/lengths WITHOUT a token-scale
    # searchsorted (the round-2 formulation's dominant cost): token
    # start bytes move to the front with the shared set-bit sort
    # (segment.set_bit_positions), and F[t] = letters strictly before
    # start byte t = one gather of the exclusive letter-count cumsum.
    # Every letter between token t's start byte and token t+1's start
    # byte belongs to token t (the gap is spaces / non-letters), so F
    # is exactly "first compacted slot of token t's letters"; a token
    # with no letters (e.g. "42", skipped at main.c:113) gets
    # F[t] == F[t+1] => length 0 => masked invalid below.  Slots past
    # num_tokens hold INT32_MAX -> clamp to n -> F = total letters =>
    # length 0.
    sb = segment.set_bit_positions(token_start, tok_cap + 1)
    sbc = jnp.minimum(sb, jnp.int32(n))
    cse = jnp.concatenate([jnp.zeros(1, jnp.int32), cs])  # exclusive
    F = cse[sbc]
    tok_len = F[1:] - F[:-1]
    F0 = F[:-1]
    # true cleaned length, NO width clip (the exactness guard; the
    # reference's own cap is 299, enforced by the caller)
    max_word_len = tok_len.max() if tok_cap else jnp.int32(0)

    # doc id per token: start byte -> manifest slot -> 1-based id
    # (tokens never span docs, so the start byte's doc is the token's)
    slot = doc_slot_of_byte[jnp.clip(sb[:-1], 0, n - 1)]
    doc_of_tok = doc_id_values[jnp.clip(slot, 0, num_docs - 1)]

    num_tokens = jnp.int32(0) + jnp.sum(token_start.astype(jnp.int32))
    valid_tok = (tok_len > 0) & (jnp.arange(tok_cap) < num_tokens)
    return (letters, F0, tok_len, max_word_len, doc_of_tok, valid_tok,
            num_tokens, n)


def tokenize_rows(data, doc_ends, doc_id_values, *, width: int,
                  tok_cap: int, num_docs: int):
    """bytes -> packed word-row byte columns + doc column (device,
    traceable).

    The byte-column frontend: ``width // 4`` big-endian int32 columns
    per word row.  :func:`tokenize_groups` (the 5-bit compressed
    frontend the engines run) supersedes it on the hot paths — this
    one is kept as the directly-byte-addressed reference whose output
    the group frontend is property-tested against
    (pack_groups(tokenize_rows(x)) == tokenize_groups(x)).  Returns
    ``(cols, doc_col, max_word_len, num_tokens)``: ``cols[0]`` carries
    INT32_MAX on empty/padding rows (sorts last), ``doc_col``
    likewise.
    """
    (letters, F0, tok_len, max_word_len, doc_of_tok, valid_tok,
     num_tokens, n) = _tokenize_front(
        data, doc_ends, doc_id_values, tok_cap=tok_cap,
        num_docs=num_docs)

    # big-endian int32 word columns via windowed gathers: 4-byte packs
    # of the letter stream at every alignment (elementwise shifts of
    # padded slices), then one gather per column at F[t] + 4c, masked
    # by how many of the window's 4 bytes belong to the token.  Mask
    # values are uint32 byte prefixes viewed as int32.
    lp = jnp.concatenate([letters, jnp.zeros(3, jnp.int32)])
    l4 = ((lp[0:n] << 24) | (lp[1:n + 1] << 16)
          | (lp[2:n + 2] << 8) | lp[3:n + 3])
    masktab = jnp.array([0, -16777216, -65536, -256, -1], jnp.int32)
    ncols = width // 4
    cols = []
    for c in range(ncols):
        idx = jnp.clip(F0 + 4 * c, 0, n - 1)
        nbytes = jnp.clip(tok_len - 4 * c, 0, 4)
        cols.append(l4[idx] & masktab[nbytes])

    # valid rows (>= 1 letter) have column 0's top byte in [a-z] =>
    # positive int32; empty/padding rows get INT32_MAX in column 0 so
    # they sort after every real word
    col0 = jnp.where(valid_tok, cols[0], INT32_MAX)
    doc_col = jnp.where(valid_tok, doc_of_tok, INT32_MAX)

    return (col0, *cols[1:]), doc_col, max_word_len, num_tokens


def num_groups_for(width: int) -> int:
    """Total (hi, lo) group pairs a ``width``-byte word row packs into
    (12 chars per group — see :func:`pack_groups`)."""
    return (width // 4 + 2) // 3


def live_groups_for(sort_cols: int | None, width: int) -> int:
    """Group pairs that can be non-constant given the host-exact
    ``sort_cols`` byte-column bound (the :func:`clamp_sort_cols`
    discipline, lifted to groups)."""
    return (clamp_sort_cols(sort_cols, width // 4) + 2) // 3


def tokenize_groups(data, doc_ends, doc_id_values, *, width: int,
                    tok_cap: int, num_docs: int,
                    sort_cols: int | None = None):
    """bytes -> 5-bit-compressed word-row group pairs + doc column.

    The frontend both device engines run: word rows come out directly
    as the ``(hi, lo)`` 30-bit code pairs of :func:`pack_groups`
    (12 chars per pair, order-preserving, injective), built by TWO
    windowed gathers per group off a 6-char packed letter stream —
    instead of 12 byte-column gathers then an elementwise repack.
    Groups past the host-exact ``sort_cols`` bound are constant zeros
    (XLA dead-code-eliminates their gathers), mirroring
    :func:`zero_tail_cols`.  Group 0 pins INT32_MAX on empty/padding
    rows so they sort last; ``doc_col`` likewise.

    Returns ``(groups, doc_col, max_word_len, num_tokens)`` with
    ``groups`` a list of ``num_groups_for(width)`` pairs, exactly
    ``pack_groups(tokenize_rows(...), nsort)`` padded with zero pairs
    (property-tested).
    """
    (letters, F0, tok_len, max_word_len, doc_of_tok, valid_tok,
     num_tokens, n) = _tokenize_front(
        data, doc_ends, doc_id_values, tok_cap=tok_cap,
        num_docs=num_docs)

    # 6-char packed stream: l6[i] = letters[i..i+5] as 5-bit codes
    # (byte & 31: pad 0, a=1 .. z=26 — order-preserving), char k at
    # shift 25-5k.  One gather at F[t]+12g yields group g's hi half,
    # one at F[t]+12g+6 its lo half; the mask keeps only the token's
    # own chars (the compacted stream runs straight into the next
    # token's letters).
    codes = letters & 31
    cp = jnp.concatenate([codes, jnp.zeros(5, jnp.int32)])
    l6 = ((cp[0:n] << 25) | (cp[1:n + 1] << 20) | (cp[2:n + 2] << 15)
          | (cp[3:n + 3] << 10) | (cp[4:n + 4] << 5) | cp[5:n + 5])
    full = (1 << 30) - 1
    masktab6 = jnp.array(
        [0] + [full ^ ((1 << (30 - 5 * m)) - 1) for m in range(1, 7)],
        jnp.int32)

    def half(char_off):
        idx = jnp.clip(F0 + char_off, 0, n - 1)
        # cap at width too: when 12 * num_groups_for(width) > width
        # (width not divisible by 12), the last group's window reaches
        # past the row — the byte-column reference drops those chars
        # (it only builds width//4 columns), so the mask must as well
        nchars = jnp.clip(
            jnp.minimum(tok_len, jnp.int32(width)) - char_off, 0, 6)
        return l6[idx] & masktab6[nchars]

    total = num_groups_for(width)
    live = live_groups_for(sort_cols, width)
    groups = []
    for g in range(live):
        hi, lo = half(12 * g), half(12 * g + 6)
        if g == 0:
            hi = jnp.where(valid_tok, hi, INT32_MAX)
            lo = jnp.where(valid_tok, lo, INT32_MAX)
        groups.append((hi, lo))
    zero = jnp.zeros(tok_cap, jnp.int32)
    groups.extend((zero, zero) for _ in range(total - live))

    doc_col = jnp.where(valid_tok, doc_of_tok, INT32_MAX)
    return tuple(groups), doc_col, max_word_len, num_tokens


def clamp_sort_cols(sort_cols: int | None, ncols: int) -> int:
    """The ONE clamp every consumer of ``sort_cols`` must share: the
    number of leading word columns that can be non-constant.  Sorting,
    exchange, and fetch all rely on the same bound — a desynchronized
    copy would silently drop live columns."""
    return ncols if sort_cols is None else max(1, min(sort_cols, ncols))


def zero_tail_cols(cols, nsort: int, n: int):
    """Splice constant zeros for the provably-all-zero trailing columns
    (valid rows have no letters there; padding rows carry 0 in every
    column but 0) so XLA dead-code-eliminates whatever built them."""
    if nsort >= len(cols):
        return tuple(cols)
    zero = jnp.zeros(n, jnp.int32)
    return (*cols[:nsort], *([zero] * (len(cols) - nsort)))


def pack_groups(cols, nsort: int):
    """Radix compression of word-row byte columns: cleaned bytes are
    only 0 or a..z, and ``byte & 31`` maps them order-preservingly to
    5-bit codes (pad 0, a=1 .. z=26).  Three byte columns (12 chars)
    repack into one 30-bit (hi, lo) int32 pair — a 2-key stable pass
    over the pair replaces three single-key passes (int64 keys would
    halve again but need jax_enable_x64).  Returns ``ceil(nsort/3)``
    pairs; group 0 pins INT32_MAX padding rows so they sort last.
    The mapping is injective on the charset, so group equality ==
    column equality (see :func:`unpack_groups` for the exact inverse).
    """
    col0 = cols[0]

    def _codes(c):
        return ((c >> 24) & 31, (c >> 16) & 31, (c >> 8) & 31, c & 31)

    zero_col = jnp.zeros_like(col0)
    groups = []
    for g in range((nsort + 2) // 3):
        ga = cols[3 * g]
        gb = cols[3 * g + 1] if 3 * g + 1 < nsort else zero_col
        gc = cols[3 * g + 2] if 3 * g + 2 < nsort else zero_col
        a0, a1, a2, a3 = _codes(ga)
        b0, b1, b2, b3 = _codes(gb)
        c0, c1, c2, c3 = _codes(gc)
        hi = (a0 << 25) | (a1 << 20) | (a2 << 15) | (a3 << 10) | (b0 << 5) | b1
        lo = (b2 << 25) | (b3 << 20) | (c0 << 15) | (c1 << 10) | (c2 << 5) | c3
        if g == 0:
            pad = col0 == INT32_MAX
            hi = jnp.where(pad, INT32_MAX, hi)
            lo = jnp.where(pad, INT32_MAX, lo)
        groups.append((hi, lo))
    return groups


def unpack_groups(groups, ncols: int):
    """Exact inverse of :func:`pack_groups` for non-padding rows:
    (hi, lo) code pairs back to big-endian byte columns.  Callers mask
    padding rows (their codes decode to garbage bytes) — every consumer
    already filters by a validity mask before using columns."""
    zero = jnp.zeros_like(groups[0][0])

    def _byte(code):
        return jnp.where(code > 0, code + 96, 0)

    cols = []
    for c in range(ncols):
        g, r = divmod(c, 3)
        if g >= len(groups):
            cols.append(zero)
            continue
        hi, lo = groups[g]
        if r == 0:
            codes = (hi >> 25, hi >> 20, hi >> 15, hi >> 10)
        elif r == 1:
            codes = (hi >> 5, hi, lo >> 25, lo >> 20)
        else:
            codes = (lo >> 15, lo >> 10, lo >> 5, lo)
        b = [_byte(x & 31) for x in codes]
        cols.append((b[0] << 24) | (b[1] << 16) | (b[2] << 8) | b[3])
    return tuple(cols)


def groups_sort_perm(groups, doc_col, cap: int):
    """Sort permutation for lexicographic ((group pairs…), doc) order:
    LSD from the least-significant segment — doc rides as a third key
    of the most-minor group's pass (perm starts as the identity so the
    first pass gathers nothing), then one 2-key stable pass per
    remaining group.  Wide comparators blow up TPU AOT compile time
    (~80x — measured: 1403 s AOT-compiling a 13-key comparator sort vs
    17.8 s for 13 single-key passes at 2^21); 2-3-key ones are cheap."""
    perm = jnp.arange(cap, dtype=jnp.int32)
    hi, lo = groups[-1]
    _, _, _, perm = lax.sort((hi, lo, doc_col, perm), num_keys=3,
                             is_stable=True)
    for hi, lo in reversed(groups[:-1]):
        _, _, perm = lax.sort((hi[perm], lo[perm], perm), num_keys=2,
                              is_stable=True)
    return perm


def sort_dedup_groups(groups, doc_col, cap: int, live: int):
    """Sorted/deduped index from 5-bit group pairs (device, traceable).

    The reduce stage, operating natively on the compressed
    representation :func:`tokenize_groups` emits — no byte columns
    ever materialize at token scale.  Lexicographic ((group pairs…),
    doc) order via the LSD radix passes of :func:`groups_sort_perm`;
    INT32_MAX rows (padding / empty) sort last and are dropped by the
    validity mask.  ``live``: group pairs
    that can be non-constant (:func:`live_groups_for`); constant-zero
    tail pairs are excluded from the radix passes (a stable pass over
    a constant key is the identity) and returned as zeros.

    Returns ``(num_words, num_pairs, df, postings, unique_groups)``
    with ``unique_groups`` shaped like ``groups``.
    """
    live_pairs = list(groups[:max(1, live)])
    perm = groups_sort_perm(live_pairs, doc_col, cap)
    s_groups = [(hi[perm], lo[perm]) for hi, lo in live_pairs]
    s_docs = doc_col[perm]

    def neq_prev(a):
        return jnp.concatenate(
            [jnp.ones(1, jnp.bool_), a[1:] != a[:-1]])

    word_valid = s_groups[0][0] != INT32_MAX
    first_word = word_valid & functools.reduce(
        jnp.logical_or,
        (neq_prev(h) for pair in s_groups for h in pair))
    first_pair = word_valid & (first_word | neq_prev(s_docs))

    num_words = first_word.sum(dtype=jnp.int32)
    num_pairs = first_pair.sum(dtype=jnp.int32)

    # Compaction WITHOUT scatters: the shared set-bit sort
    # (segment.set_bit_positions) — one cap-sized 1-key sort per
    # compaction, cheaper than the rank-cumsum searchsorted it
    # replaced (round 3 on-chip).
    pair_rank = jnp.cumsum(first_pair.astype(jnp.int32)) - 1
    slots = jnp.arange(cap, dtype=jnp.int32)
    W = jnp.concatenate([
        jnp.minimum(segment.set_bit_positions(first_word, cap), cap),
        jnp.full(1, cap, jnp.int32)])
    P = jnp.minimum(segment.set_bit_positions(first_pair, cap), cap)
    word_live = slots < num_words
    pair_live = slots < num_pairs
    Wg = jnp.clip(W[:-1], 0, cap - 1).astype(jnp.int32)
    Pg = jnp.clip(P, 0, cap - 1).astype(jnp.int32)

    pair_excl = jnp.concatenate(
        [pair_rank + 1 - first_pair.astype(jnp.int32),
         jnp.full(1, num_pairs, jnp.int32)])
    df = jnp.where(
        word_live, pair_excl[jnp.minimum(W[1:], cap)] - pair_excl[Wg], 0)
    postings = jnp.where(pair_live, s_docs[Pg], 0)
    zero = jnp.zeros(cap, jnp.int32)
    unique_groups = tuple(
        [(jnp.where(word_live, hi[Wg], 0),
          jnp.where(word_live, lo[Wg], 0)) for hi, lo in s_groups]
        + [(zero, zero)] * (len(groups) - len(live_pairs)))
    return num_words, num_pairs, df, postings, unique_groups


@functools.partial(
    jax.jit,
    static_argnames=("width", "tok_cap", "num_docs", "sort_cols"),
)
def index_bytes_device(data, doc_ends, doc_id_values, *, width: int,
                       tok_cap: int, num_docs: int,
                       sort_cols: int | None = None):
    """bytes -> sorted/deduped index, entirely on device (single chip).

    ``data``: uint8 (N,) — concatenated documents, padded with spaces
    (0x20) to a static length.  ``doc_ends``: int32 (num_docs,)
    exclusive end offsets.  ``doc_id_values``: int32 (num_docs,)
    1-based ids.  ``width``: word-row bytes, multiple of 4.
    ``tok_cap``: static token capacity — must be > the true token count
    (callers compute it exactly with vectorized masks; note doc
    boundaries split tokens, so up to one token per byte can exist).

    Returns a dict of fixed-shape arrays; valid prefixes are bounded by
    ``num_words`` / ``num_pairs`` (see caller).  ``max_word_len`` must
    be checked against ``width`` host-side (WidthOverflow contract).
    ``sort_cols``: optional static radix-pass bound from the host-exact
    :func:`max_cleaned_token_len`.  Word rows live and return as the
    5-bit ``unique_groups`` pairs (:func:`tokenize_groups`) — the
    host decodes them at vocab scale (:func:`decode_word_groups`),
    and the fetch rides 2 int32 per 12 chars instead of 3.
    """
    groups, doc_col, max_word_len, num_tokens = tokenize_groups(
        data, doc_ends, doc_id_values, width=width, tok_cap=tok_cap,
        num_docs=num_docs, sort_cols=sort_cols)
    num_words, num_pairs, df, postings, unique_groups = sort_dedup_groups(
        groups, doc_col, tok_cap, live_groups_for(sort_cols, width))
    # words needing any tail group (cleaned length > 12): group 1's hi
    # is nonzero iff char 13 exists.  The count rides with the other
    # counts so the fetch can size a SPARSE tail-group transfer
    # (long words are rare in real text; see fetch_pack).
    slots = jnp.arange(tok_cap, dtype=jnp.int32)
    if len(unique_groups) > 1:
        long_mask = (slots < num_words) & (unique_groups[1][0] != 0)
        num_long = long_mask.sum(dtype=jnp.int32)
    else:
        num_long = jnp.int32(0)
    return {
        # one 5-scalar array: ONE host sync fetches all counts (each
        # scalar fetched separately would pay the link RTT per scalar);
        # num_tokens lets the caller verify its tok_cap bound held
        "counts": jnp.stack([num_words, num_pairs, max_word_len,
                             num_tokens, num_long]),
        "df": df,                    # (tok_cap,) valid prefix num_words
        "postings": postings,        # (tok_cap,) valid prefix num_pairs
        # num_groups_for(width) x (hi, lo), valid prefix num_words
        "unique_groups": unique_groups,
    }


def doc_pack_width(max_doc_id: int) -> int:
    """Doc ids per packed int32 for the postings fetch: 3 when ids fit
    10 bits, else 1 (below 2^16 the uint16 cast already covers
    2-per-4-bytes and packing would only add shifts for the same
    transfer size; above it ids must ride int32 untouched)."""
    return 3 if 0 < max_doc_id < (1 << 10) else 1


def pack_postings(post, k: int):
    """Traceable postings packer: ``k`` doc ids per int32 in 10-bit
    fields (``k == 1`` passes through).  The ONE pack implementation —
    the single-chip tail (:func:`fetch_pack`) and the mesh prefix
    slice both call it, and :func:`unpack_postings` is its pinned
    inverse; a second copy could silently drift from the decoder."""
    if k == 1:
        return post
    npairs = post.shape[0]
    pad = (-npairs) % k
    p = jnp.concatenate([post, jnp.zeros(pad, post.dtype)]).reshape(-1, k)
    return (p[:, 0] | (p[:, 1] << 10) | (p[:, 2] << 20)
            if k == 3 else p[:, 0])


def gather_long_tails(halves, nu: int, nlong: int):
    """Traceable sparse tail-group gather: set-bit indices of the
    >12-char rows (group 1's hi is nonzero exactly there; tail halves
    are zero past ``num_words``, so padding never matches) and every
    tail half gathered at them.  Returns ``(idx, gathered_halves)``
    with ``idx`` INT32_MAX past the true long count — callers slice by
    the count they carried in their counts array."""
    long_mask = halves[0][:nu] != 0
    idx = segment.set_bit_positions(long_mask, nlong)
    gi = jnp.clip(idx, 0, nu - 1)
    return idx, tuple(h[:nu][gi] for h in halves)


@functools.partial(jax.jit,
                   static_argnames=("nu", "npairs", "nlong", "k", "live",
                                    "narrow"))
def fetch_pack(out, *, nu: int, npairs: int, nlong: int, k: int,
               live: int, narrow: bool):
    """Device-side fetch packer for the single-chip engines' tail.

    Returns the minimal transfer set (everything int32/uint16, every
    array dispatched before any is read by the caller):

    - ``df``: valid prefix, uint16 when ``narrow`` (df <= max_doc_id,
      so the same bound governs both; packing further would save
      little — df is the smallest array), int32 otherwise;
    - ``post``: postings packed ``k`` ids per int32 (10-bit fields,
      :func:`doc_pack_width`), else the uint16 cast when ``narrow``,
      else untouched int32 (doc ids >= 2^16 MUST ride wide —
      truncation here would silently corrupt the index);
    - ``g0``: group 0's (hi, lo) prefix — every word's first 12 chars;
    - ``long_idx`` + ``tail``: row indices and tail-group halves for
      ONLY the words longer than 12 chars (``num_long`` of them, from
      the program's counts) — the dense tail arrays are provably zero
      everywhere else, so the host rebuilds them by scatter at vocab
      scale.  Real-text corpora put ~1-5% of the vocab here, cutting
      the dominant group transfer ~(live-1)/live.
    """
    df = out["df"][:nu]
    post = out["postings"][:npairs]
    if narrow:
        df = df.astype(jnp.uint16)
    if k > 1:
        post = pack_postings(post, k)
    elif narrow:
        post = post.astype(jnp.uint16)
    hi0, lo0 = out["unique_groups"][0]
    res = {"df": df, "post": post, "g0": (hi0[:nu], lo0[:nu])}
    if live > 1 and nlong > 0:
        halves = [h for pair in out["unique_groups"][1:live]
                  for h in pair]
        idx, gathered = gather_long_tails(halves, nu, nlong)
        res["long_idx"] = idx  # INT32_MAX past num_long; caller slices
        res["tail"] = tuple(
            (gathered[2 * g], gathered[2 * g + 1])
            for g in range(live - 1))
    return res


def rebuild_tail_groups(num_words: int, ngroups_fetch: int, *,
                        idx=None, tails=(), num_long: int = 0):
    """Host-side inverse of the sparse tail-group transfer
    (:func:`gather_long_tails`): dense (hi, lo) pairs for groups
    1..ngroups_fetch-1, zeros everywhere except the ``num_long`` long
    words' rows scattered back at ``idx``.  The ONE rebuild
    implementation — the single-chip tail and the mesh owner fetch
    both call it (same anti-drift rationale as
    :func:`unpack_postings`)."""
    out = []
    for g in range(ngroups_fetch - 1):
        h = np.zeros(num_words, np.int32)
        l = np.zeros(num_words, np.int32)
        if num_long:
            h[idx] = np.asarray(tails[g][0])[:num_long]
            l[idx] = np.asarray(tails[g][1])[:num_long]
        out.append((h, l))
    return out


def unpack_postings(packed: np.ndarray, num_pairs: int,
                    k: int) -> np.ndarray:
    """Host-side inverse of :func:`fetch_pack`'s postings packing —
    kept next to the pack so field width and ``k`` can never drift
    apart.  ``k == 1`` input is the uint16/int32 passthrough."""
    if k == 1:
        return np.asarray(packed)[:num_pairs].astype(np.int32)
    pw = np.asarray(packed).astype(np.int64)
    return np.stack(
        [pw & 1023, (pw >> 10) & 1023, (pw >> 20) & 1023],
        axis=1).reshape(-1)[:num_pairs].astype(np.int32)


def _host_start_mask(buf: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Token-start mask, host side.  MUST mirror the device classifier
    in :func:`tokenize_rows` byte for byte (same whitespace set, same
    doc-boundary break rule); divergence is asserted loudly by callers.
    Vectorized whole-array compares, not a scan."""
    sp = ((buf == 0x20) | (buf == 0x09) | (buf == 0x0A)
          | (buf == 0x0B) | (buf == 0x0C) | (buf == 0x0D))
    prev_sp = np.empty_like(sp)
    prev_sp[0] = True
    prev_sp[1:] = sp[:-1]
    start = ~sp & prev_sp
    start[0] = not sp[0]
    de = ends[:-1][ends[:-1] < buf.shape[0]]
    start[de] |= ~sp[de]
    return start


def host_token_stats(buf: np.ndarray, ends: np.ndarray) -> tuple[int, int]:
    """``(token_count, max_cleaned_len)`` in ONE pass over the buffer.

    The count sizes the static ``tok_cap`` (the device's reported
    ``num_tokens`` is asserted against it, so classifier divergence
    fails loudly instead of silently dropping tokens).  The exact max
    cleaned (letters-only) length lets callers raise
    :class:`WidthOverflow` before paying for a doomed launch and pass a
    tight ``sort_cols`` bound (skipping radix passes and fetch bytes
    over provably all-zero word columns); the device's own
    ``max_word_len`` output is asserted equal by callers.

    Delegates to the native SIMD scan when available (~10x the numpy
    mirror below, which stays as the portable fallback and the
    cross-check reference in tests).
    """
    from .. import native

    res = native.token_stats(buf, ends)
    if res is not None:
        return res
    return _host_token_stats_numpy(buf, ends)


def _host_token_stats_numpy(buf: np.ndarray, ends: np.ndarray) -> tuple[int, int]:
    """Portable numpy mirror of ``mri_token_stats`` (the cross-check
    reference in tests)."""
    start = _host_start_mask(buf, ends)
    count = int(np.count_nonzero(start))
    if count == 0:
        return 0, 0
    _, lower_np = _byte_tables()
    is_letter = lower_np[buf] > 0
    excl = np.cumsum(is_letter, dtype=np.int64) - is_letter
    total = int(excl[-1]) + int(is_letter[-1])
    lens = np.diff(np.append(excl[np.flatnonzero(start)], total))
    return count, int(lens.max())


def count_token_starts(buf: np.ndarray, ends: np.ndarray) -> int:
    """Exact host-side token count (see :func:`host_token_stats`)."""
    return int(np.count_nonzero(_host_start_mask(buf, ends)))


def max_cleaned_token_len(buf: np.ndarray, ends: np.ndarray) -> int:
    """Exact max cleaned token length (see :func:`host_token_stats`)."""
    return host_token_stats(buf, ends)[1]


def decode_word_groups(groups, width: int) -> np.ndarray:
    """Fetched (hi, lo) 5-bit group pairs -> numpy 'S(width)' word
    array — the host-side inverse of :func:`tokenize_groups`'s packing
    (same layout as :func:`unpack_groups`, but in numpy at vocab
    scale).  Padding rows must already be sliced off by the caller
    (their codes decode to garbage) — the valid-prefix contract of the
    engines' fetch tails."""
    u = np.asarray(groups[0][0]).shape[0]
    out = np.zeros((u, width), np.uint8)
    for g, (hi, lo) in enumerate(groups):
        for half_idx, arr in ((0, hi), (1, lo)):
            a = np.asarray(arr).astype(np.int64)
            for k in range(6):
                ch = 12 * g + 6 * half_idx + k
                if ch >= width:
                    break
                code = (a >> (25 - 5 * k)) & 31
                out[:, ch] = np.where(code > 0, code + 96, 0)
    return np.ascontiguousarray(out).view(f"S{width}").reshape(u)

"""Device engine: the whole reduce phase as one XLA program.

The reference's reduce phase — re-parse spill text, linear-scan dict
dedup, qsort by (df desc, word asc), bubble-sort postings, format
(main.c:126-242) — becomes one jitted program over integer arrays:

    sort packed (term, doc) keys          ->  lax.sort (radix under XLA)
    per-(term, doc) dedup                 ->  boundary diff on sorted keys
    document frequency                    ->  run-edge cumsum differences
    postings lists (ascending, compact)   ->  rank searchsorted + gather
    final emit order (letter, -df, term)  ->  second key sort

Everything is fixed-shape; padding keys sort to the tail and fall out of
the searchsorted edges (ops/segment.py — scatter-free by design: TPU
scatter serializes per update).  Control crosses host<->device exactly twice
(feed pairs, fetch postings) vs. the reference's per-token lock/IO
crossing (SURVEY.md §3.5).

The post-sort tail (:func:`postings_from_sorted`) is shared with the
multi-chip engine in ``parallel/dist_engine.py``, which reaches the same
sorted state via a hash-bucket ``all_to_all`` instead of one local sort.
"""

from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import keys as K
from .segment import compact, first_occurrence_mask, sorted_segment_counts
from ..utils import envknobs


def _quiet_donation(fn):
    """Silence JAX's unusable-donation warning around one jitted entry.

    Feed buffers are donated so XLA reuses their device memory as sort
    scratch; the programs' *outputs* are deliberately narrower than the
    feeds, so no output can alias them and JAX warns at lowering time.
    Scoped per call so user code importing this library keeps the
    diagnostic for its own donations.
    """

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn(*args, **kwargs)

    for attr in ("clear_cache", "lower", "trace", "eval_shape"):  # jit API
        if hasattr(fn, attr):
            setattr(wrapper, attr, getattr(fn, attr))
    return wrapper

# Fused Pallas kernel for the dedup mask (ops/pallas/kernels.py):
#   "auto"  — compiled kernel on TPU, XLA elsewhere (default)
#   "force" — always (interpret mode off-TPU; used by tests)
#   "off"   — XLA everywhere
_PALLAS_MODE = envknobs.get("MRI_TPU_PALLAS")

#: which dedup the last trace took: "pallas" (compiled kernel),
#: "pallas-interpret" or "xla".  ``InvertedIndexModel.run`` clears it
#: first and reports it as ``dedup_kernel`` only if its own run traced a
#: dedup (a jit-cache hit traces nothing), so a run can show the kernel
#: really ran on the chip and never names an earlier run's choice.
dedup_kernel: str | None = None


def _dedup_mask(keys_s, valid_limit: int):
    """(mask, count) over ascending keys: first-occurrence & validity.

    Via the fused Pallas kernel when eligible (trace-time choice),
    which also yields the unique count for free; the XLA path returns
    ``count=None`` and callers reduce the mask instead.
    """
    global dedup_kernel
    if _PALLAS_MODE != "off":
        from .pallas import kernels as pk

        if pk.supports(keys_s.shape[0]) and (
            _PALLAS_MODE == "force" or jax.default_backend() == "tpu"
        ):
            dedup_kernel = ("pallas-interpret" if pk._should_interpret()
                            else "pallas")
            return pk.unique_mask_count(keys_s, valid_limit)
    dedup_kernel = "xla"
    return first_occurrence_mask(keys_s) & (keys_s < valid_limit), None


def emit_order_keys(letter_of_term, df, max_doc_id: int):
    """Sort keys giving the reference's output order (main.c:55-64).

    Within a letter file: df descending, then word ascending — and term
    ids are assigned in sorted-vocab order, so ``term id asc == word
    asc`` and no strings are needed on device.
    """
    neg_df = (max_doc_id + 1) - df  # df <= max_doc_id + 1 always
    return letter_of_term, neg_df


def emit_order(letter_of_term, df, vocab_size: int, max_doc_id: int):
    """Term ids ordered (letter asc, df desc, term asc)."""
    letter, neg_df = emit_order_keys(letter_of_term, df, max_doc_id)
    stride = max_doc_id + 2
    terms = jnp.arange(vocab_size, dtype=jnp.int32)
    if 26 * stride * (vocab_size + 1) < np.iinfo(np.int32).max:
        emit_key = (letter * stride + neg_df) * vocab_size + terms
        _, order = lax.sort_key_val(emit_key, terms)
    else:
        # stable two-key sort; stability supplies the term-asc tiebreak
        _, _, order = lax.sort((letter, neg_df, terms), num_keys=2)
    return order


def host_order_offsets(letter_of_term, df):
    """Emit order + postings offsets computed on host from fetched df.

    Cheaper than fetching the device-computed versions over a slow
    device->host link: both are vocab-sized and derive from df alone.
    ``np.lexsort`` is stable, so full ties fall back to term id ascending
    == word ascending, matching main.c:55-64.
    """
    df64 = np.asarray(df).astype(np.int64)
    order = np.lexsort((-df64, np.asarray(letter_of_term)))
    offsets = np.cumsum(df64) - df64
    return order.astype(np.int64), offsets


def dedup_df_postings(keys_s, *, vocab_size: int, max_doc_id: int):
    """Shared post-sort block: per-(term, doc) dedup, document frequency,
    compacted postings — from an ascending packed-key array (may contain
    ``K.INT32_MAX`` padding, which sorts last and is dropped).

    Returns ``(first, df, postings, num_unique)``; the unique count
    comes fused from the Pallas kernel when it ran."""
    valid_limit = vocab_size * (max_doc_id + 2)
    term_s, doc_s = K.unpack_pairs(keys_s, max_doc_id)
    first, count = _dedup_mask(keys_s, valid_limit)
    df = sorted_segment_counts(term_s, first.astype(jnp.int32), vocab_size)
    postings = compact(doc_s, first, keys_s.shape[0], jnp.int32(0))
    num_unique = count if count is not None else first.astype(jnp.int32).sum()
    return first, df, postings, num_unique


def postings_from_sorted(keys_s, letter_of_term, *, vocab_size: int, max_doc_id: int):
    """Postings/df/order from an ascending packed-key array."""
    _, df, postings, num_unique = dedup_df_postings(
        keys_s, vocab_size=vocab_size, max_doc_id=max_doc_id)
    order = emit_order(letter_of_term, df, vocab_size, max_doc_id)
    offsets = jnp.cumsum(df) - df
    return {
        "postings": postings,
        "df": df,
        "order": order,
        "offsets": offsets,
        "num_unique": num_unique,
    }


@_quiet_donation
@functools.partial(jax.jit, static_argnames=("vocab_size", "max_doc_id"), donate_argnums=(0,))
def index_packed(keys, letter_of_term, *, vocab_size: int, max_doc_id: int):
    """Index a batch of packed (term, doc) int32 keys.

    ``keys`` may be padded with ``K.INT32_MAX`` (sorts after every valid
    key since ``can_pack`` guarantees headroom).
    """
    return postings_from_sorted(
        lax.sort(keys), letter_of_term, vocab_size=vocab_size, max_doc_id=max_doc_id)


def pack_u16_feed(terms, docs, padded: int) -> np.ndarray:
    """Host-side encode of the half-bandwidth uint16 feed buffer:
    ``[terms | docs]``, each half ``padded`` long, 0xFFFF padding —
    the layout :func:`_u16_feed_to_keys` decodes on device."""
    buf = np.full(2 * padded, 0xFFFF, dtype=np.uint16)
    n = len(terms)
    buf[:n] = terms
    buf[padded : padded + n] = docs
    return buf


def _u16_feed_to_keys(feed_u16, max_doc_id: int):
    """[terms | docs] uint16 buffer (0xFFFF padding) -> packed int32 keys."""
    pad = jnp.uint16(0xFFFF)
    stride = max_doc_id + 2
    half = feed_u16.shape[0] // 2
    term_u16, doc_u16 = feed_u16[:half], feed_u16[half:]
    return jnp.where(
        term_u16 == pad, K.INT32_MAX,
        term_u16.astype(jnp.int32) * stride + doc_u16.astype(jnp.int32))


@_quiet_donation
@functools.partial(jax.jit, static_argnames=("max_doc_id", "out_size"), donate_argnums=(0,))
def index_prededuped_u16(feed_u16, *, max_doc_id: int, out_size: int | None = None):
    """Minimal device program for a combiner-deduped feed.

    When the host map phase already emitted each (term, doc) pair once
    (native tokenizer's combiner), the reduce phase is exactly one sort:
    postings = doc component of the ascending pair keys.  df, order and
    offsets all derive from the deduped term ids on host (np.bincount +
    lexsort, vocab-sized).  One upload, one download — ``out_size``
    (static) limits the download to the valid prefix so the D2H
    transfer never includes padding beyond the rounding granule.
    """
    keys = _u16_feed_to_keys(feed_u16, max_doc_id)
    sorted_docs = (lax.sort(keys) % (max_doc_id + 2)).astype(jnp.uint16)
    return sorted_docs if out_size is None else sorted_docs[:out_size]


@_quiet_donation
@functools.partial(jax.jit, static_argnames=("stride", "out_size"), donate_argnums=(0,))
def sort_prov_chunks(chunks, *, stride: int, out_size: int):
    """Pipelined path: sort packed *provisional*-id keys fed per chunk.

    Each element of ``chunks`` is one upload window, asynchronously
    DMA'd while the host tokenizer was still scanning later documents —
    possible because provisional ids are first-occurrence ids, stable
    the moment a chunk is scanned, so this program never depends on the
    final sorted vocab.  A window is either an int32 array of
    ``prov_id * stride + doc`` keys (INT32_MAX padding) or, when its
    prov ids still fit, a half-bandwidth uint16 ``[terms | docs]``
    buffer (0xFFFF padding) packed into the same keys on device.
    Postings only need *grouping* by term and docs ascending, which the
    prov-key sort already gives; the host resolves emit order / offsets
    in prov space from vocab-sized arrays (models/inverted_index.py),
    leaving exactly one device->host round-trip on the critical path
    after tokenization ends.

    Combiner-deduped feeds only (each (term, doc) at most once).
    Returns the doc component of the ascending keys — the concatenated
    postings lists in prov-id order — as uint16 (callers guarantee
    ``stride <= 0x10000``); padding sorts last and is cut by
    ``out_size``.
    """
    as_keys = [
        _u16_feed_to_keys(c, stride - 2) if c.dtype == jnp.uint16 else c
        for c in chunks
    ]
    keys = as_keys[0] if len(as_keys) == 1 else jnp.concatenate(as_keys)
    return (lax.sort(keys)[:out_size] % stride).astype(jnp.uint16)


@_quiet_donation
@functools.partial(jax.jit, static_argnames=("vocab_size", "max_doc_id"),
                   donate_argnums=(0,))
def index_u16(feed_u16, *, vocab_size: int, max_doc_id: int):
    """Transfer-minimized path for corpora with vocab_size <= 65535 and
    max_doc_id <= 65534 (covers the reference's whole envelope,
    MAX_FILES=360 at main.c:8).

    The device<->host link has a large per-transfer fixed cost, so input
    is ONE uint16 buffer: term ids in the first half, doc ids in the
    second, 0xFFFF padding; keys are packed on device.  Output postings
    and df are uint16 — halving the bytes fetched — and
    ``order``/``offsets``/``num_unique`` are left for the host to derive
    from df (engine.host_order_offsets), saving further transfers.
    (Feeds already deduped by the combiner skip this entirely —
    :func:`index_prededuped_u16` is one sort and one download.)
    """
    keys = _u16_feed_to_keys(feed_u16, max_doc_id)
    _, df, postings, _ = dedup_df_postings(
        lax.sort(keys), vocab_size=vocab_size, max_doc_id=max_doc_id)
    # single output [df | postings]: callers slice host-side, so the fetch
    # is at most two download ops (df prefix, then valid postings prefix)
    return {"combined": jnp.concatenate(
        [df.astype(jnp.uint16), postings.astype(jnp.uint16)])}


@_quiet_donation
@functools.partial(jax.jit, static_argnames=("vocab_size", "max_doc_id"), donate_argnums=(0, 1))
def index_pairs(term_ids, doc_ids, letter_of_term, *, vocab_size: int, max_doc_id: int):
    """General path for corpora too large to pack into one int32 key.

    Two-key variadic sort; otherwise identical semantics to
    :func:`index_packed`.  Padding: term = doc = INT32_MAX.
    """
    term_s, doc_s = lax.sort((term_ids, doc_ids), num_keys=2)
    valid = term_s < vocab_size
    first = (first_occurrence_mask(term_s) | first_occurrence_mask(doc_s)) & valid
    df = sorted_segment_counts(jnp.where(valid, term_s, vocab_size), first.astype(jnp.int32), vocab_size)
    postings = compact(doc_s, first, term_s.shape[0], jnp.int32(0))
    order = emit_order(letter_of_term, df, vocab_size, max_doc_id)
    offsets = jnp.cumsum(df) - df
    return {
        "postings": postings,
        "df": df,
        "order": order,
        "offsets": offsets,
        "num_unique": first.astype(jnp.int32).sum(),
    }

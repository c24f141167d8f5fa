"""Segmented primitives over sorted key arrays.

These replace the reference reducer's O(tokens x unique_words) linear
dictionary scan and O(n^2) bubble sort (main.c:172-187, 217-226) with
O(n) boundary diffs, cumsums and sort/gather compactions over a sorted
array — the shapes XLA vectorizes well on TPU.  None of them scatters:
XLA lowers TPU scatter to a serial per-update loop (~75 ns/update
measured on v5e — one 1M-update scatter costs more than five
1M-element stable-sort passes), so every compaction here is a
set-bit-position ``lax.sort`` plus a gather (:func:`set_bit_positions`;
the round-2 cumsum-rank + searchsorted formulation lost the round-3
on-chip A/B — see :func:`searchsorted_device`, kept for run-edge
lookups where the sought values are not mask positions).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .keys import INT32_MAX as _INT32_MAX


def searchsorted_device(a, v):
    """``searchsorted(a, v, side='left')`` for NONDECREASING queries
    ``v``, formulated for TPU (both inputs same int dtype).

    CONTRACT: ``v`` must be nondecreasing — the formulation takes each
    query's index as its rank among queries, so unsorted queries get
    silently wrong edges (failure mode pinned by
    tests/test_segment.py::test_searchsorted_device_requires_monotone_
    queries).  Every in-tree caller passes an ``arange``.

    ``jnp.searchsorted``'s default ``method='scan'`` binary search
    lowers to a sequential log2(n)-step loop of dynamic slices —
    measured on the v5e (round 3):
    173 ms for 2^20 sorted queries into a 2^20 array, 702 ms into a
    5.7M array.  Three of those per run dominated the all-device
    engine's 1157 ms device_index regression.

    This is the co-sort formulation instead: stable-sort
    ``concat([v, a])`` (ties put queries first = side='left'), invert
    the permutation, and subtract each query's own rank — for
    nondecreasing ``v`` that rank is just its index.  The inverse is a
    second ``argsort`` rather than the iota-scatter
    ``jnp.searchsorted(method='sort')`` uses, which keeps the device
    program scatter-free (the design guard in
    tests/test_device_tokenizer.py) AND measures faster: 72 ms / 90 ms
    on the shapes above vs 88 / 135 for ``method='sort'`` (the
    permutation scatter is not the serial per-update worst case, but
    it still loses to the sort).
    """
    m = v.shape[0]
    idx = jnp.argsort(jnp.concatenate([v, a]), stable=True)
    inv = jnp.argsort(idx)
    return inv[:m] - jnp.arange(m, dtype=inv.dtype)


def set_bit_positions(mask, out_len: int):
    """Positions of ``mask``'s True slots, in order, as an
    ``out_len``-long int32 array padded with INT32_MAX.

    ONE single-key ``lax.sort`` of (slot where set, INT32_MAX
    elsewhere) front-compacts the positions; set bits past ``out_len``
    are dropped.  This is the shared core of every compaction in the
    device programs (``segment.compact``, the streaming row compactor,
    and the W/P word/pair-start lookups of both dedup tails) — cheaper
    on TPU than the rank-cumsum searchsorted it replaced (round-3
    on-chip measurement, see :func:`searchsorted_device`).
    """
    n = mask.shape[0]
    kept = lax.sort(
        jnp.where(mask, jnp.arange(n, dtype=jnp.int32), _INT32_MAX))
    if out_len <= n:
        return kept[:out_len]
    return jnp.concatenate(
        [kept, jnp.full(out_len - n, _INT32_MAX, jnp.int32)])


def first_occurrence_mask(sorted_keys):
    """mask[i] = sorted_keys[i] is the first of its run.

    On a sorted pair array this is exactly the reference's per-(word, doc)
    dedup (main.c:176-184): one True per unique pair.
    """
    prev = jnp.concatenate([sorted_keys[:1] - 1, sorted_keys[:-1]])
    return sorted_keys != prev


def sorted_segment_counts(segment_ids, weights, num_segments: int):
    """Sum ``weights`` per segment id over a NONDECREASING id array;
    ids >= num_segments are dropped.  The name carries the precondition:
    the searchsorted run edges are silently wrong on unsorted ids (the
    scatter-based formulation this replaced accepted any order).

    Used for document frequency: df[t] = number of unique (t, doc) pairs
    (the count the reference accumulates per dictionary entry at
    main.c:176-187 and sorts by at main.c:55-64).  Every caller passes
    term ids taken from an already-sorted key array, so each segment is
    one contiguous run and its sum is a cumsum difference at the run's
    searchsorted edges — no scatter.
    """
    wext = jnp.concatenate(
        [jnp.zeros(1, weights.dtype), jnp.cumsum(weights)])
    edges = searchsorted_device(
        segment_ids, jnp.arange(num_segments + 1, dtype=segment_ids.dtype))
    return wext[edges[1:]] - wext[edges[:-1]]


def bucket_edges(sorted_bucket_ids, num_buckets: int):
    """``(counts, offsets)`` of each bucket's run in a sorted id array.

    The exchange cores sort rows by destination bucket and then need
    each bucket's count and start offset; both fall out of one
    searchsorted over the sorted column (ids >= num_buckets — the
    padding bucket — land past the last edge and are dropped).
    """
    edges = searchsorted_device(
        sorted_bucket_ids,
        jnp.arange(num_buckets + 1, dtype=jnp.int32)).astype(jnp.int32)
    return edges[1:] - edges[:-1], edges[:-1]


def compact(values, keep_mask, out_size: int, fill):
    """Stable-compact ``values[keep_mask]`` into a fixed-size array.

    The result's first ``keep_mask.sum()`` slots are the kept values in
    order, remaining slots are ``fill`` (kept values past ``out_size``
    are dropped): :func:`set_bit_positions` then a plain gather — no
    scatter.
    """
    n = values.shape[0]
    if n == 0:
        return jnp.full((out_size,), fill, dtype=values.dtype)
    kept = set_bit_positions(keep_mask, out_size)
    live = kept != _INT32_MAX
    return jnp.where(live, values[jnp.clip(kept, 0, n - 1)], fill)

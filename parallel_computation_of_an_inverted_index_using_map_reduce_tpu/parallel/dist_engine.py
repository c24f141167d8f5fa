"""Multi-chip engine: hash-bucket ``all_to_all`` shuffle over the mesh.

This is the TPU-native replacement for the reference's shuffle — 26
shared spill files written by every mapper under implicit stdio locks
and re-read by letter-owning reducers (main.c:116, 332-341, 135-137):

- pairs are sharded over chips (data parallelism over documents,
  main.c:307-328's file ranges);
- each chip buckets its pairs by ``term % n_chips`` — a uniform hash
  partition, unlike the reference's ~1000x-skewed first-letter
  partition (SURVEY.md §2.3) — and exchanges them with one
  ``lax.all_to_all`` over ICI;
- each chip dedups its owned terms locally (sorted boundary diff — the
  global dedup, since a term's pairs all land on its owner) and keeps
  its survivors *sharded*;
- only vocab-sized aggregates cross chips after the exchange: document
  frequency via one ``psum``, from which the emit order is computed
  replicated.  The deduped pair shards go straight to the host, which
  merges n sorted runs during emit (emit is host-bound regardless) —
  per-chip work and memory stay O(N/n log N/n), never O(N).

The exchange uses a fixed per-bucket capacity (static shapes for XLA);
a returned overflow flag triggers one retry at the provably-safe
capacity.  Every step is a collective or a fused elementwise/scan —
no host round-trips inside the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from ..ops import keys as K
from ..ops.engine import emit_order
from ..ops.segment import (
    bucket_edges,
    compact,
    first_occurrence_mask,
    sorted_segment_counts,
)
from ..utils.rounding import round_up as _round_up
from .mesh import SHARD_AXIS, make_mesh, replicated_spec, shard_spec, sharding


def default_capacity(local_size: int, num_shards: int, factor: float = 2.0) -> int:
    """Per-(src, dst) bucket capacity.

    Expected load is ``local_size / num_shards``; ``factor`` covers hash
    imbalance.  Capped at ``local_size`` (the provably-safe value: one
    source cannot send more pairs than it holds).
    """
    if num_shards == 1:
        return local_size
    return min(local_size, _round_up(int(math.ceil(local_size / num_shards * factor)), 8))


def _bucket_exchange(keys_local, valid_limit, *, num_shards: int,
                     capacity: int, stride: int, owner_of_term=None):
    """Shared exchange core: partition packed keys and run one ICI
    ``all_to_all``.

    Default bucketing is ``term % num_shards`` (uniform, unlike the
    reference's ~1000x-skewed first-letter partition); passing
    ``owner_of_term`` (a replicated term->owner map) buckets by it
    instead — the letter-ownership partition of the per-owner emit mode.
    Keys ``>= valid_limit`` go to the padding bucket.  Returns
    ``(recv, overflow_local)`` where row b of the fixed-shape send
    buffer went to device b.
    """
    local = keys_local.shape[0]
    term = keys_local // stride
    owner = (term % num_shards if owner_of_term is None
             else owner_of_term[jnp.clip(term, 0, owner_of_term.shape[0] - 1)])
    bucket = jnp.where(keys_local < valid_limit, owner, num_shards)
    bucket_s, keys_s = lax.sort((bucket.astype(jnp.int32), keys_local), num_keys=2)
    counts, offsets = bucket_edges(bucket_s, num_shards)
    overflow_local = (counts > capacity).any()

    # fixed-shape send buffer (num_shards, capacity)
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    gather_idx = jnp.clip(offsets[:, None] + slot, 0, local - 1)
    in_bucket = slot < counts[:, None]
    send = jnp.where(in_bucket, keys_s[gather_idx], K.INT32_MAX)
    return lax.all_to_all(send, SHARD_AXIS, 0, 0, tiled=True), overflow_local


def _shuffle_body(keys_local, letter_of_term, *, num_shards: int, capacity: int,
                  vocab_size: int, max_doc_id: int):
    """shard_map body: runs per-device with collectives over SHARD_AXIS."""
    stride = max_doc_id + 2
    valid_limit = vocab_size * stride
    recv, overflow_local = _bucket_exchange(
        keys_local, valid_limit, num_shards=num_shards, capacity=capacity,
        stride=stride)

    # --- owner-side global dedup of this device's terms.
    recv_s = lax.sort(recv.reshape(-1))
    first = first_occurrence_mask(recv_s) & (recv_s < valid_limit)
    uniq = compact(recv_s, first, recv_s.shape[0], K.INT32_MAX)

    # --- vocab-sized aggregates only: df by psum, emit order replicated.
    owned_term = recv_s // stride  # nondecreasing: recv_s is sorted
    df_local = sorted_segment_counts(owned_term, first.astype(jnp.int32), vocab_size)
    df = lax.psum(df_local, SHARD_AXIS)
    order = emit_order(letter_of_term, df, vocab_size, max_doc_id)
    offsets = jnp.cumsum(df) - df
    return {
        "uniq_sharded": uniq,
        "df": df,
        "order": order,
        "offsets": offsets,
        "num_unique": lax.psum(first.astype(jnp.int32).sum(), SHARD_AXIS),
        "overflow": lax.psum(overflow_local.astype(jnp.int32), SHARD_AXIS),
    }


@functools.lru_cache(maxsize=64)
def _build(mesh: Mesh, num_shards: int, capacity: int, vocab_size: int,
           max_doc_id: int, donate: bool):
    def body(keys_local, letters):
        return _shuffle_body(
            keys_local, letters, num_shards=num_shards, capacity=capacity,
            vocab_size=vocab_size, max_doc_id=max_doc_id)

    out_specs = {
        "uniq_sharded": shard_spec(),
        "df": replicated_spec(),
        "order": replicated_spec(),
        "offsets": replicated_spec(),
        "num_unique": replicated_spec(),
        "overflow": replicated_spec(),
    }
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(shard_spec(), replicated_spec()),
            out_specs=out_specs,
            check_vma=False,
        ),
        # Donation frees the input keys' HBM during the exchange, but the
        # overflow retry re-feeds the same buffer, so only donate when no
        # retry can follow (capacity already at the provably-safe bound).
        donate_argnums=(0,) if donate else (),
    )


def assemble_postings(uniq_sharded, max_doc_id: int, valid_limit: int,
                      offsets: np.ndarray, num_pairs: int) -> np.ndarray:
    """O(N) host-side merge of the sharded deduped pair keys into the
    global term-major postings array (runs during emit, which is
    host-bound).

    Each shard's keys are ascending (owner-side sort, INT32_MAX padding
    packed at the tail) and every term's pairs live on exactly one
    owner, so scattering each shard's term runs at the replicated
    global ``offsets`` is a complete, collision-free merge — no
    token-scale re-sort anywhere in the dist tails."""
    stride = max_doc_id + 2
    shards = uniq_sharded.addressable_shards
    if len(shards) < uniq_sharded.sharding.num_devices:
        raise RuntimeError(
            "global postings assembly needs every shard addressable; in a "
            "multi-host run use emit_ownership='letter' so each host emits "
            "only its own owners' letters")
    postings = np.empty(max(num_pairs, 1), dtype=np.int32)
    for s in shards:  # overlap the D2H transfers before the serial reads
        s.data.copy_to_host_async()
    for s in shards:
        keys = np.asarray(s.data)
        keys = keys[: np.searchsorted(keys, valid_limit)]
        if keys.size:
            _scatter_run(keys // stride, keys % stride, offsets, postings)
    return postings[:num_pairs]


def _prov_shuffle_body(window_locals, *, num_shards: int, capacity: int,
                       stride: int, owner_of_term=None):
    """shard_map body for the pipelined (provisional-key) dist path.

    Unlike :func:`_shuffle_body`, the feed is already combiner-deduped
    and emit order is resolved host-side from the combiner's df counts
    (models/inverted_index.py), so the program is pure data movement:
    concat this device's slice of every upload window, bucket by term
    hash — or by ``owner_of_term`` (the letter-ownership partition of
    the per-owner emit mode, the reference's reducer letter ranges
    main.c:129-130) — one ``all_to_all`` over ICI, owner-side sort.
    The owner sort makes each device's slice ascending and
    term-grouped, so the host assembles postings with one valid-prefix
    merge instead of a re-sort.  ``valid`` (per-owner count of real
    keys) lets the host fetch only the valid prefix instead of the
    2x-overprovisioned capacity buffer (VERDICT r1 #7).
    """
    keys_local = jnp.concatenate(list(window_locals))
    recv, overflow_local = _bucket_exchange(
        keys_local, K.INT32_MAX, num_shards=num_shards, capacity=capacity,
        stride=stride, owner_of_term=owner_of_term)
    recv_s = lax.sort(recv.reshape(-1))
    valid = (recv_s < K.INT32_MAX).sum(dtype=jnp.int32)
    return {
        "owned_sorted": recv_s,
        "valid": valid[None],
        # replicated global max -> every process computes the same
        # fetch-slice shape without seeing the other hosts' counts
        "max_valid": lax.pmax(valid, SHARD_AXIS),
        "overflow": lax.psum(overflow_local.astype(jnp.int32), SHARD_AXIS),
    }


@functools.lru_cache(maxsize=64)
def _build_prov(mesh: Mesh, num_windows: int, window_local: tuple,
                num_shards: int, capacity: int, stride: int, donate: bool,
                with_owner: bool = False):
    """Compiled exchange program; ``with_owner`` prepends a replicated
    term->owner map argument (letter-ownership mode)."""
    if with_owner:
        def body(owner_of_term, *window_locals):
            return _prov_shuffle_body(
                window_locals, num_shards=num_shards, capacity=capacity,
                stride=stride, owner_of_term=owner_of_term)

        in_specs = (replicated_spec(),) + tuple(
            shard_spec() for _ in range(num_windows))
        donate_argnums = tuple(range(1, num_windows + 1))
    else:
        def body(*window_locals):
            return _prov_shuffle_body(
                window_locals, num_shards=num_shards, capacity=capacity,
                stride=stride)

        in_specs = tuple(shard_spec() for _ in range(num_windows))
        donate_argnums = tuple(range(num_windows))

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=in_specs,
            out_specs={"owned_sorted": shard_spec(),
                       "valid": shard_spec(),
                       "max_valid": replicated_spec(),
                       "overflow": replicated_spec()},
            check_vma=False,
        ),
        donate_argnums=donate_argnums if donate else (),
    )


def _exchange_and_fetch_rows(windows, *, stride: int, mesh: Mesh,
                             capacity_factor: float,
                             owner_of_prov: np.ndarray | None,
                             stats: dict | None) -> dict[int, np.ndarray]:
    """Shared tail of both dist paths: run the (possibly letter-keyed)
    exchange with the capacity-overflow retry, then fetch each owner's
    valid prefix — per-owner counts, then one device-side slice at the
    replicated global max count rounded to a reuse granule, so fetched
    bytes track unique pairs, not the overprovisioned capacity
    (VERDICT r1 #7).

    Returns ``{owner_id: keys}`` for every *addressable* owner: in a
    multi-host (multi-controller) run each process sees only its local
    devices' shards — exactly what the per-owner emit needs, and why
    the slice shape comes from the device-computed ``max_valid``
    (replicated) rather than a host-side max over counts this process
    cannot see.
    """
    n = mesh.devices.size
    local_total = sum(w.shape[0] for w in windows) // n
    capacity = default_capacity(local_total, n, capacity_factor)
    shapes = tuple(w.shape[0] for w in windows)
    with_owner = owner_of_prov is not None
    args = tuple(windows)
    if with_owner:
        owner_dev = jax.device_put(
            np.ascontiguousarray(owner_of_prov, dtype=np.int32),
            sharding(mesh, replicated_spec()))
        args = (owner_dev,) + args
    # donate the window buffers only when no retry can re-feed them
    # (the owner map, arg 0 in owner mode, is never donated)
    out = _build_prov(mesh, len(windows), shapes, n, capacity, stride,
                      capacity >= local_total, with_owner)(*args)
    if capacity < local_total and int(out["overflow"]) > 0:
        out = _build_prov(mesh, len(windows), shapes, n, local_total, stride,
                          True, with_owner)(*args)
    # shard.index[0].start is None for a full-span shard (1-device mesh)
    counts = {
        (s.index[0].start or 0): int(np.asarray(s.data)[0])
        for s in out["valid"].addressable_shards
    }
    local_len = int(out["owned_sorted"].shape[0]) // n
    nfetch = min(local_len,
                 _round_up(max(int(out["max_valid"]), 1), 1 << 13))
    sliced = _build_prefix_slice(mesh, local_len, nfetch)(out["owned_sorted"])
    rows = {}
    fetched = 0
    for s in sliced.addressable_shards:  # overlap the D2H transfers
        s.data.copy_to_host_async()
    for s in sliced.addressable_shards:
        owner = (s.index[0].start or 0) // nfetch
        row = np.asarray(s.data)
        rows[owner] = row[: counts[owner]]
        fetched += row.nbytes
    if stats is not None:
        # the devices the exchange's output really lives on (not the
        # mesh size asked for): a run can show it spread over the mesh
        stats["device_spread"] = len(out["owned_sorted"].sharding.device_set)
        stats["dist_fetched_bytes"] = fetched + 4 * len(counts)
        stats["dist_valid_pairs"] = int(sum(counts.values()))
    return rows


def dist_letter_windows(windows, owner_of_prov: np.ndarray, *, stride: int,
                        mesh: Mesh, capacity_factor: float = 2.0,
                        stats: dict | None = None) -> dict[int, np.ndarray]:
    """Per-owner-emit tail of the pipelined path: exchange the sharded
    upload windows by letter owner (the reference's reducer letter
    ranges, main.c:129-130, via corpus/scheduler.plan_letter_ranges);
    returns ``{owner: keys}`` (prov-grouped ascending, docs ascending
    inside each term) for every addressable owner.  The letter
    partition is skewed by construction (SURVEY.md §2.3); the
    capacity-overflow retry at the provably-safe bound absorbs it.

    In the multi-host regime each process receives only its own local
    owners' rows and emits just those letter files; a single-controller
    run receives all of them.
    """
    return _exchange_and_fetch_rows(
        windows, stride=stride, mesh=mesh, capacity_factor=capacity_factor,
        owner_of_prov=owner_of_prov, stats=stats)


@functools.lru_cache(maxsize=64)
def _build_prefix_slice(mesh: Mesh, local_len: int, nfetch: int):
    """Per-shard valid-prefix slice, compiled once per (len, nfetch)
    bucket: the owner sort packs real keys first, so ``x[:nfetch]`` on
    each shard drops the INT32_MAX padding *before* the D2H transfer."""
    return jax.jit(jax.shard_map(
        lambda x: x[:nfetch], mesh=mesh,
        in_specs=shard_spec(), out_specs=shard_spec(), check_vma=False))


def _scatter_run(term: np.ndarray, doc: np.ndarray,
                 offsets_prov: np.ndarray, postings: np.ndarray) -> None:
    """Scatter one owner's (term-grouped ascending) run into the global
    prov-grouped postings array — vectorized, collision-free because
    every term lives on exactly one owner."""
    change = np.empty(term.shape[0], dtype=bool)
    change[0] = True
    np.not_equal(term[1:], term[:-1], out=change[1:])
    starts = np.flatnonzero(change)
    run_of_elem = np.cumsum(change) - 1
    within = np.arange(term.shape[0], dtype=np.int64) - starts[run_of_elem]
    postings[offsets_prov[term] + within] = doc


def merge_owner_runs(rows, stride: int, offsets_prov: np.ndarray,
                     num_pairs: int) -> np.ndarray:
    """O(N) host merge of per-owner sorted *packed-key* runs into the
    global prov-grouped postings array.

    Each ``rows[d]`` is owner d's valid keys, ascending — grouped by
    prov term with docs ascending inside each group — and every term's
    pairs live on exactly one owner, so scattering each group to its
    term's global slot (``offsets_prov``) is a complete, collision-free
    merge: no token-scale sort anywhere, just vectorized index math.
    """
    postings = np.empty(max(num_pairs, 1), dtype=np.int32)
    for row in rows:
        if row.size:
            _scatter_run(row // stride, row % stride, offsets_prov, postings)
    return postings[:num_pairs]


def merge_owner_pair_runs(rows, offsets_prov: np.ndarray,
                          num_pairs: int) -> np.ndarray:
    """Pair-mode variant of :func:`merge_owner_runs`: each ``rows[d]``
    is ``(terms, docs)`` sorted by (term, doc)."""
    postings = np.empty(max(num_pairs, 1), dtype=np.int32)
    for term, doc in rows:
        if term.size:
            _scatter_run(term.astype(np.int64), doc, offsets_prov, postings)
    return postings[:num_pairs]


def dist_sort_prov_windows(windows, *, stride: int, mesh: Mesh,
                           offsets_prov: np.ndarray, num_pairs: int,
                           capacity_factor: float = 2.0,
                           stats: dict | None = None) -> np.ndarray:
    """Distributed tail of the pipelined path: shuffle + sort the
    sharded provisional-key upload windows; returns the host-assembled
    postings array (docs grouped by prov term id, ascending).

    Each element of ``windows`` is an int32 device array sharded over
    ``mesh`` (padded with ``K.INT32_MAX`` to a multiple of the mesh
    size).  Overflow of the per-bucket capacity triggers one retry at
    the provably-safe bound, exactly like :func:`dist_index`.

    ``offsets_prov`` (prov-space postings offsets from the combiner's
    df counts) drives the O(N) :func:`merge_owner_runs`; only the
    valid prefix of each owner's sorted buffer crosses the D2H link —
    the padded capacity tail never leaves the device.  ``stats`` (if
    given) records ``dist_fetched_bytes`` for observability.
    """
    rows = _exchange_and_fetch_rows(
        windows, stride=stride, mesh=mesh, capacity_factor=capacity_factor,
        owner_of_prov=None, stats=stats)
    if len(rows) < mesh.devices.size:
        raise RuntimeError(
            "merged postings assembly needs every shard addressable; in a "
            "multi-host run use emit_ownership='letter' so each host emits "
            "only its own owners' letters")
    return merge_owner_runs(rows.values(), stride, offsets_prov, num_pairs)


def dist_index(keys, letter_of_term, *, vocab_size: int, max_doc_id: int,
               mesh: Mesh | None = None, capacity_factor: float = 2.0):
    """Distributed index of packed pair keys sharded over the mesh.

    ``keys`` length must be a multiple of the mesh size (pad with
    ``K.INT32_MAX``).  Returns the single-chip engine's dict interface;
    ``postings`` is assembled on host from the sharded unique keys, the
    vocab-sized outputs (df/order/offsets) are replicated device arrays.
    If the hash partition overflows the default capacity, the exchange
    is re-run once at the provably-safe capacity.
    """
    mesh = mesh if mesh is not None else make_mesh()
    n = mesh.devices.size
    if keys.shape[0] % n:
        raise ValueError(f"keys length {keys.shape[0]} not divisible by mesh size {n}")
    local = keys.shape[0] // n
    capacity = default_capacity(local, n, capacity_factor)
    out = _build(mesh, n, capacity, vocab_size, max_doc_id, capacity >= local)(
        keys, letter_of_term)
    if capacity < local and int(out["overflow"]) > 0:
        out = _build(mesh, n, local, vocab_size, max_doc_id, True)(keys, letter_of_term)
    out.pop("overflow", None)
    uniq = out.pop("uniq_sharded")
    out["postings"] = assemble_postings(
        uniq, max_doc_id, vocab_size * (max_doc_id + 2),
        np.asarray(out["offsets"]), int(out["num_unique"]))
    return out

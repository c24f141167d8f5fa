"""Mesh variant of the all-device engine: sharded bytes in, index out.

Completes the engine matrix — {host scan, device scan} x {single chip,
multi chip}.  The single-chip all-device engine
(ops/device_tokenizer.py) removes the host from the compute path; this
module removes the single-chip limit: each chip receives a contiguous
doc range's raw bytes, tokenizes/cleans them locally with the SAME
traceable stages, and one ``all_to_all`` exchanges whole word rows
(the live 5-bit (hi, lo) group halves + doc, carried side by side)
bucketed by a word-content hash, so every term is deduped/counted by
exactly one owner — the
reference's reducer ownership (main.c:129-150) re-keyed from its
~1000x-skewed letters to a near-uniform hash, at the level of raw
text rather than pre-tokenized pairs.

Per chip, as one ``shard_map`` program:

    rows   <- tokenize_groups(bytes_shard)          # local scans/sorts
    owner  <- mix32(word columns) % n
    recv   <- all_to_all(bucket(rows, owner))       # ICI, 2*live+1 rows
    index  <- sort_dedup_groups(recv)               # owner-side radix

Static exchange capacity with a provably-safe overflow retry
(psum-reduced flag), the same discipline as the integer-pair engines
(parallel/dist_engine.py).  Exactness story is inherited:
byte-identical output or WidthOverflow fallback, never truncation.

Multi-controller contract: :func:`index_bytes_dist` feeds each
process's local mesh positions via
``make_array_from_single_device_arrays`` and fetches ONLY addressable
shards — per-owner counts from the sharded counts array, data through
a device-side prefix slice shaped by device-replicated count maxima
(so every process compiles the same fetch program).  In a
single-process run every owner is addressable and behavior is
unchanged; on a multi-host pod each process gets exactly its local
owners' blocks, the same discipline as parallel/dist_engine
(exercised cross-process by tests/test_distributed.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from ..ops.device_tokenizer import (
    INT32_MAX,
    doc_pack_width,
    gather_long_tails,
    live_groups_for,
    num_groups_for,
    pack_postings,
    rebuild_tail_groups,
    sort_dedup_groups,
    tokenize_groups,
    unpack_postings,
)
from ..ops.segment import bucket_edges
from ..utils.rounding import round_up as _round_up
from .dist_engine import default_capacity
from .mesh import SHARD_AXIS, replicated_spec, shard_spec, sharding


def _mix32(cols):
    """Deterministic word-content hash from the packed columns (uint32
    mul-xor mix; identical rows always hash identically)."""
    h = cols[0].astype(jnp.uint32)
    for c in cols[1:]:
        h = (h ^ c.astype(jnp.uint32)) * jnp.uint32(0x9E3779B1)
    h ^= h >> 16
    h *= jnp.uint32(0x85EBCA6B)
    h ^= h >> 13
    return h


def _body(data_l, ends_l, ids_l, *, width: int, tok_cap: int, num_docs: int,
          num_shards: int, capacity: int, sort_cols: int | None,
          owner_of_letter: tuple | None):
    groups, doc_col, max_len, num_tokens = tokenize_groups(
        data_l, ends_l, ids_l, width=width, tok_cap=tok_cap,
        num_docs=num_docs, sort_cols=sort_cols)
    live = live_groups_for(sort_cols, width)
    # group pairs past the host-exact sort_cols bound are all zero for
    # every row (valid AND padding): don't build, exchange, or sort
    # them — XLA dead-code-eliminates their windowed gathers, and the
    # all_to_all payload shrinks proportionally
    rows = (*(h for pair in groups[:live] for h in pair), doc_col)
    nrows = len(rows)

    valid = groups[0][0] != INT32_MAX
    if owner_of_letter is None:  # near-uniform content-hash ownership
        dest = (_mix32(rows[:-1]) % num_shards).astype(jnp.int32)
    else:
        # letter ownership (the reference's reducer letter ranges,
        # main.c:129-130, re-keyed at raw-text level): each owner
        # receives whole letters and can emit its own letter files
        # with no global merge — the multi-host emit mode.  Skewed by
        # construction (SURVEY.md §2.3); the provably-safe capacity
        # retry absorbs it.  First char's 5-bit code sits at group 0
        # hi's top field (pad 0, a=1 .. z=26).
        letter = ((groups[0][0] >> 25) & 31) - 1
        dest = jnp.asarray(np.asarray(owner_of_letter, np.int32))[
            jnp.clip(letter, 0, 25)]
    owner = jnp.where(valid, dest, num_shards)
    # bucket rows by owner: stable sort of (owner, perm), then windowed
    # gather per destination (the integer engines' exchange shape,
    # dist_engine._bucket_exchange, carrying the live columns side by
    # side)
    b_s, perm = lax.sort(
        (owner, jnp.arange(tok_cap, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    counts, offsets = bucket_edges(b_s, num_shards)
    overflow_local = (counts > capacity).any()
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    gather_idx = jnp.clip(offsets[:, None] + slot, 0, tok_cap - 1)
    in_bucket = slot < counts[:, None]
    pg = perm[gather_idx]  # compose the two gathers once, not per row
    send = jnp.concatenate(
        [jnp.where(in_bucket, r[pg], INT32_MAX) for r in rows],
        axis=1)  # (num_shards, nrows * capacity)
    recv = lax.all_to_all(send, SHARD_AXIS, 0, 0, tiled=True)
    recv = recv.reshape(num_shards, nrows, capacity)
    recv_rows = [recv[:, r, :].reshape(-1) for r in range(nrows)]

    # un-exchanged tail group pairs are reconstructed as the constants
    # they provably are (tokenize_groups' zero-tail contract)
    zero = jnp.zeros(num_shards * capacity, jnp.int32)
    recv_groups = tuple(
        [(recv_rows[2 * g], recv_rows[2 * g + 1]) for g in range(live)]
        + [(zero, zero)] * (num_groups_for(width) - live))
    num_words, num_pairs, df, postings, unique_groups = sort_dedup_groups(
        recv_groups, recv_rows[-1], num_shards * capacity, live)
    # per-owner >12-char word count for the sparse tail-group fetch
    # (ops/device_tokenizer.fetch_pack discipline, per owner here);
    # unique_groups are already zero past num_words, so the nonzero
    # count IS the long-word count
    num_long = ((unique_groups[1][0] != 0).sum(dtype=jnp.int32)
                if len(unique_groups) > 1 else jnp.int32(0))
    return {
        # per-owner counts, sharded (n, 3) once stacked over the mesh
        "counts": jnp.stack([num_words, num_pairs, num_long])[None, :],
        # replicated health scalars: [global max word len, overflow,
        # max per-shard token count, max owner words, max owner pairs,
        # max owner long-words] — the maxima size the prefix-slice
        # fetch identically on every process (a host-side max over
        # counts would only see the local shards in a
        # multi-controller run)
        "globals": jnp.stack([
            lax.pmax(max_len, SHARD_AXIS),
            lax.psum(overflow_local.astype(jnp.int32), SHARD_AXIS),
            lax.pmax(num_tokens, SHARD_AXIS),
            lax.pmax(num_words, SHARD_AXIS),
            lax.pmax(num_pairs, SHARD_AXIS),
            lax.pmax(num_long, SHARD_AXIS),
        ]),
        "df": df,
        "postings": postings,
        "unique_groups": unique_groups,
    }


@functools.lru_cache(maxsize=32)
def _build(mesh: Mesh, width: int, tok_cap: int, num_docs: int,
           capacity: int, sort_cols: int | None,
           owner_of_letter: tuple | None):
    n = mesh.devices.size
    body = functools.partial(
        _body, width=width, tok_cap=tok_cap, num_docs=num_docs,
        num_shards=n, capacity=capacity, sort_cols=sort_cols,
        owner_of_letter=owner_of_letter)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(shard_spec(),) * 3,
        out_specs={"counts": shard_spec(), "globals": replicated_spec(),
                   "df": shard_spec(), "postings": shard_spec(),
                   "unique_groups": ((shard_spec(), shard_spec()),)
                   * num_groups_for(width)},
        check_vma=False,
    ))


@functools.lru_cache(maxsize=32)
def _build_prefix_slice(mesh: Mesh, nu: int, npairs: int, live: int,
                        narrow: bool, k: int, nlong: int):
    """Per-owner valid-prefix slice with the single-chip tail's
    transfer trimming (ops/device_tokenizer.fetch_pack), device side,
    so the D2H transfer tracks unique counts — the fetch discipline of
    dist_engine._dist_prov_exchange (VERDICT r1 #7).  Per owner:
    postings pack ``k`` doc ids per int32 / narrow to uint16; group 0
    rides dense; tail groups ride sparsely (set-bit indices + values
    for the ``nlong``-capped >12-char words).  Output order:
    ``(df, post, g0_hi, g0_lo[, long_idx, *tail_halves])``."""
    def body(df, postings, *halves):
        dfp, pp = df[:nu], postings[:npairs]
        if narrow:
            dfp = dfp.astype(jnp.uint16)
        if k > 1:
            pp = pack_postings(pp, k)
        elif narrow:
            pp = pp.astype(jnp.uint16)
        out = [dfp, pp, halves[0][:nu], halves[1][:nu]]
        if nlong:
            idx, gathered = gather_long_tails(
                halves[2:2 * live], nu, nlong)
            out.append(idx)
            out.extend(gathered)
        return tuple(out)

    nout = 4 + ((1 + 2 * (live - 1)) if nlong else 0)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(shard_spec(),) * (2 + 2 * live),
        out_specs=(shard_spec(),) * nout,
        check_vma=False,
    ))


def _local_mesh_positions(mesh: Mesh):
    """mesh position -> device for THIS process's devices (multi-
    process device ids are sparse; never index by device.id)."""
    me = jax.process_index()
    return {i: d for i, d in enumerate(mesh.devices.flat)
            if d.process_index == me}


def index_bytes_dist(shard_bufs, shard_ends, shard_ids, *, width: int,
                     tok_cap: int, mesh: Mesh, stats: dict | None = None,
                     sort_cols: int | None = None,
                     max_doc_id: int | None = None,
                     owner_of_letter: np.ndarray | None = None):
    """Sharded raw bytes -> per-owner index rows, over the mesh.

    ``shard_bufs``: list of n equal-length uint8 buffers (space-padded
    contiguous doc ranges).  ``shard_ends`` / ``shard_ids``: per-shard
    int32 arrays, equal lengths across shards (pad ends with the buffer
    length — padding spaces produce no tokens).  ``tok_cap``: per-shard
    token capacity (callers bound it exactly per shard and take the
    max).  Returns ``(owners, globals)`` where ``owners`` maps owner ->
    dict(num_words, num_pairs, df, postings, unique_groups) with valid
    prefixes already cut, and ``globals`` is ``(max_word_len,
    exchange_retries)``.

    Multi-controller contract: feed and fetch touch only THIS
    process's addressable devices — each process uploads its local
    mesh positions' shards and ``owners`` contains exactly the owners
    whose device is local (all of them in a single-process run).  The
    prefix-slice shape comes from the device-replicated count maxima,
    so every process compiles the same fetch program.
    """
    n = mesh.devices.size
    local_pos = _local_mesh_positions(mesh)
    ref = min(local_pos)  # any local position: shapes are uniform
    num_docs = shard_ends[ref].shape[0]
    sh = sharding(mesh, shard_spec())

    def _feed(parts):
        # only THIS process's positions are read — a pod host may pass
        # None for shards it did not load
        arrays = [jax.device_put(parts[i], d) for i, d in local_pos.items()]
        shape = (n * parts[ref].shape[0],)
        return jax.make_array_from_single_device_arrays(shape, sh, arrays)

    data = _feed(shard_bufs)
    ends = _feed(shard_ends)
    ids = _feed(shard_ids)
    owner_key = (tuple(int(x) for x in owner_of_letter)
                 if owner_of_letter is not None else None)
    capacity = default_capacity(tok_cap, n)
    retries = 0
    while True:
        out = _build(mesh, width, tok_cap, num_docs, capacity, sort_cols,
                     owner_key)(data, ends, ids)
        g = np.asarray(out["globals"])
        if int(g[1]) > 0 and capacity < tok_cap:
            capacity = tok_cap  # provably safe: a shard holds <= tok_cap rows
            retries += 1
            continue
        break
    max_len = int(g[0])
    max_shard_tokens = int(g[2])
    if max_shard_tokens + 1 > tok_cap:
        raise AssertionError(
            f"device token count {max_shard_tokens} exceeded tok_cap "
            f"{tok_cap}: host mask count diverged from the device "
            "classifier (bug)")

    # per-owner counts from THIS process's shards only (the (n, 3)
    # counts array is device-sharded; a whole-array np.asarray would
    # need every shard addressable and break multi-controller)
    owners = fetch_owner_blocks(
        out, mesh=mesh, local_len=n * capacity, width=width,
        sort_cols=sort_cols, max_doc_id=max_doc_id, max_words=int(g[3]),
        max_pairs=int(g[4]), max_long=int(g[5]), stats=stats)
    if stats is not None:
        # the devices the program's output really lives on
        stats["device_spread"] = len(out["counts"].sharding.device_set)
        stats["exchange_retries"] = retries
        stats["exchange_capacity"] = capacity
    return owners, (max_len, retries)


def fetch_owner_blocks(out, *, mesh: Mesh, local_len: int, width: int,
                       sort_cols: int | None, max_doc_id: int | None,
                       max_words: int, max_pairs: int, max_long: int,
                       stats: dict | None = None):
    """Addressable-shard fetch of per-owner index blocks — the shared
    tail of the mesh device engines (one-shot and streaming).

    ``out`` must carry device-sharded ``counts`` ((n, 3): words,
    pairs, >12-char words per owner), ``df``, ``postings`` and
    ``unique_groups``; ``max_words`` / ``max_pairs`` / ``max_long``
    are the device-REPLICATED per-owner maxima (identical prefix-slice
    shapes on every process).  Transfer trimming matches the
    single-chip tail (ops/device_tokenizer.fetch_pack): fetched bytes
    track unique counts, postings pack 3 doc ids per int32 when they
    fit 10 bits (uint16 under 2^16, untouched int32 above), and tail
    group pairs ride sparsely — indices + values for each owner's
    long words, the dense arrays rebuilt by host scatter.
    """
    counts = {
        (s.index[0].start or 0): np.asarray(s.data).reshape(3)
        for s in out["counts"].addressable_shards
    }
    ngroups_fetch = min(len(out["unique_groups"]),
                        live_groups_for(sort_cols, width))
    narrow = max_doc_id is not None and max_doc_id < (1 << 16)
    k = doc_pack_width(max_doc_id) if max_doc_id else 1
    # 1k granule: tight enough that fetched bytes track the max owner's
    # unique counts, coarse enough that slice programs reuse across
    # similar corpora
    nu = min(local_len, _round_up(max(max_words, 1), 1 << 10))
    npairs = min(local_len, _round_up(max(max_pairs, 1), 1 << 10))
    nlong = (min(nu, _round_up(max_long, 1 << 10))
             if ngroups_fetch > 1 and max_long else 0)
    halves = [h for pair in out["unique_groups"][:ngroups_fetch]
              for h in pair]
    sliced = _build_prefix_slice(
        mesh, nu, npairs, ngroups_fetch, narrow, k, nlong)(
        out["df"], out["postings"], *halves)
    for arr in sliced:
        for s in arr.addressable_shards:
            s.data.copy_to_host_async()

    owners = {}
    fetched = 0

    def _per_owner(arr, stride_len):
        return {(s.index[0].start or 0) // stride_len: np.asarray(s.data)
                for s in arr.addressable_shards}

    df_sh = _per_owner(sliced[0], nu)
    post_sh = _per_owner(sliced[1], (npairs + k - 1) // k if k > 1
                         else npairs)
    g0_sh = (_per_owner(sliced[2], nu), _per_owner(sliced[3], nu))
    if nlong:
        idx_sh = _per_owner(sliced[4], nlong)
        tails_sh = [_per_owner(h, nlong) for h in sliced[5:]]
    for o, cnt in counts.items():
        num_words, num_pairs, num_long = (int(v) for v in cnt)
        fetched += df_sh[o].nbytes + post_sh[o].nbytes \
            + g0_sh[0][o].nbytes + g0_sh[1][o].nbytes
        if nlong:
            fetched += idx_sh[o].nbytes + sum(
                t[o].nbytes for t in tails_sh)
        groups = (
            [(g0_sh[0][o][:num_words], g0_sh[1][o][:num_words])]
            + rebuild_tail_groups(
                num_words, ngroups_fetch,
                idx=idx_sh[o][:num_long] if nlong else None,
                tails=[(tails_sh[2 * g][o], tails_sh[2 * g + 1][o])
                       for g in range(ngroups_fetch - 1)] if nlong
                else (),
                num_long=num_long if nlong else 0))
        owners[o] = {
            "num_words": num_words, "num_pairs": num_pairs,
            "df": df_sh[o][:num_words].astype(np.int32),
            "postings": unpack_postings(post_sh[o], num_pairs, k),
            "unique_groups": groups,
        }
    if stats is not None:
        stats["dist_fetched_bytes"] = fetched
    return owners

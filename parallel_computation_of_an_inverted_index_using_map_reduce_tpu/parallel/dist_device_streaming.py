"""Mesh streaming all-device engine: sharded raw byte windows in,
bounded per-owner row accumulators on every chip.

Completes the engine matrix's last cell — {device scan} x {mesh} x
{streaming}.  Combines the three scale mechanisms the other engines
prove separately:

- **device scan** (ops/device_tokenizer.py): the whole map phase as
  array ops over raw bytes — no host tokenizer anywhere;
- **streaming** (ops/device_streaming.py): the device carries only
  the unique (word, doc) rows seen so far, as compressed 30-bit
  (hi, lo) code pairs + doc, bounded by output size not stream length;
- **multi-chip** (parallel/dist_device_tokenizer.py): word rows are
  content-hash-partitioned over the mesh with one ``all_to_all`` per
  window, so each chip's accumulator holds only its owned terms —
  per-chip memory is O(unique / n) and the shuffle rides ICI.

Per window, as ONE ``shard_map`` program per chip:

    rows   <- tokenize_groups(local byte shard)   # 5-bit pairs direct
    recv   <- all_to_all(bucket(rows, mix32 % n))          # ICI
    acc_o  <- compact(unique(sort(acc_o ++ recv)))         # owner merge

Reference seams re-expressed: the mappers' shared spill-file shuffle
(main.c:116, 332-341) is the per-window ``all_to_all``; the reducer's
per-(word, doc) dedup (main.c:176-184) is the owner merge's
boundary-diff — with the strict map->reduce barrier (main.c:367-369)
dissolved into a window pipeline that never materializes the full
token stream anywhere.

Like the pair-mode mesh streaming engine (parallel/dist_streaming.py),
a per-owner bound cannot be derived host-side without assuming hash
uniformity, so each merge returns the replicated max per-owner count
(one scalar sync per window, amortized over large windows) and an
overflowing merge retries against the PRESERVED previous accumulator
at a doubled capacity — no data loss, no uniformity assumption.

Exactness contract is the family's: rows are actual cleaned bytes
under an injective code map; the caller rejects over-width windows
host-side BEFORE feeding (WidthOverflow -> host fallback), and every
window's device stats are re-checked against the host classifier at
finalize.  Finalize runs ops/device_streaming.finalize_rows_body per
owner inside ``shard_map`` and hands the per-owner blocks to the
shared addressable-shard fetch
(dist_device_tokenizer.fetch_owner_blocks), so the multi-controller
contract matches the one-shot mesh engine's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from ..ops.device_streaming import _compact_rows, _row_first_mask, finalize_rows_body
from ..ops.device_tokenizer import (
    INT32_MAX,
    groups_sort_perm,
    live_groups_for,
    num_groups_for,
    tokenize_groups,
)
from ..ops.segment import bucket_edges
from ..utils.rounding import round_up
from .dist_device_tokenizer import _local_mesh_positions, _mix32, fetch_owner_blocks
from .dist_engine import default_capacity
from .mesh import SHARD_AXIS, replicated_spec, shard_spec, sharding


def _window_merge_body(acc_and_window, *, width: int, tok_cap: int,
                       num_docs: int, num_shards: int, cap: int,
                       exchange_capacity: int, sort_cols: int,
                       live_groups: int, num_groups: int):
    """Per-chip: tokenize the local byte shard, exchange rows by
    content hash, fold received rows into this owner's accumulator."""
    nrows_acc = 2 * num_groups + 1
    acc = acc_and_window[:nrows_acc]
    data_l, ends_l, ids_l = acc_and_window[nrows_acc:]

    groups_all, doc_col, max_len, num_tokens = tokenize_groups(
        data_l, ends_l, ids_l, width=width, tok_cap=tok_cap,
        num_docs=num_docs, sort_cols=sort_cols)
    live = groups_all[:live_groups]
    send_rows = tuple(g for pair in live for g in pair) + (doc_col,)
    nrows = len(send_rows)

    valid = groups_all[0][0] != INT32_MAX
    # STABLE ownership across the whole stream: live_groups grows as
    # longer words appear, so the hash folds a FIXED number of columns
    # (all num_groups pairs — tokenize_groups emits the un-exchanged
    # tails as the constant zeros they provably are) — hashing only
    # the live columns would re-home a word mid-stream and split its
    # postings across owners
    hash_cols = tuple(g for pair in groups_all for g in pair)
    owner = jnp.where(
        valid, (_mix32(hash_cols) % num_shards).astype(jnp.int32),
        num_shards)
    b_s, perm = lax.sort(
        (owner, jnp.arange(tok_cap, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    counts, offsets = bucket_edges(b_s, num_shards)
    overflow_ex = (counts > exchange_capacity).any()
    slot = jnp.arange(exchange_capacity, dtype=jnp.int32)[None, :]
    gather_idx = jnp.clip(offsets[:, None] + slot, 0, tok_cap - 1)
    in_bucket = slot < counts[:, None]
    pg = perm[gather_idx]
    send = jnp.concatenate(
        [jnp.where(in_bucket, r[pg], INT32_MAX) for r in send_rows],
        axis=1)
    recv = lax.all_to_all(send, SHARD_AXIS, 0, 0, tiled=True)
    recv = recv.reshape(num_shards, nrows, exchange_capacity)
    recv_rows = [recv[:, r, :].reshape(-1) for r in range(nrows)]

    # splice the un-exchanged all-zero tail groups back, then fold
    zero = jnp.zeros(num_shards * exchange_capacity, jnp.int32)
    lg = len(live)
    recv_full = (tuple(recv_rows[:-1])
                 + tuple([zero] * (2 * (num_groups - lg)))
                 + (recv_rows[-1],))
    cat = tuple(jnp.concatenate([a, w]) for a, w in zip(acc, recv_full))
    doc = cat[-1]
    sort_groups = [(cat[2 * g], cat[2 * g + 1]) for g in range(max(lg, 1))]
    s_perm = groups_sort_perm(sort_groups, doc, doc.shape[0])
    s_rows = tuple(r[s_perm] for r in cat)
    first = _row_first_mask(s_rows)
    count = first.sum(dtype=jnp.int32)
    new_acc = _compact_rows(s_rows, first, cap)
    return {
        "acc": new_acc,
        # replicated health: [max per-owner unique count, exchange
        # overflow, global max word len, max per-shard token count]
        "globals": jnp.stack([
            lax.pmax(count, SHARD_AXIS),
            lax.psum(overflow_ex.astype(jnp.int32), SHARD_AXIS),
            lax.pmax(max_len, SHARD_AXIS),
            lax.pmax(num_tokens, SHARD_AXIS),
        ]),
    }


@functools.lru_cache(maxsize=64)
def _build_merge(mesh: Mesh, width: int, tok_cap: int, num_docs: int,
                 cap: int, exchange_capacity: int, sort_cols: int,
                 live_groups: int, num_groups: int):
    n = mesh.devices.size
    nrows_acc = 2 * num_groups + 1
    body = functools.partial(
        _window_merge_body, width=width, tok_cap=tok_cap,
        num_docs=num_docs, num_shards=n, cap=cap,
        exchange_capacity=exchange_capacity, sort_cols=sort_cols,
        live_groups=live_groups, num_groups=num_groups)

    def wrapper(*args):
        return body(args)

    # no donation: an overflowing merge retries against the same
    # accumulator and window at a larger capacity
    return jax.jit(jax.shard_map(
        wrapper, mesh=mesh,
        in_specs=(shard_spec(),) * (nrows_acc + 3),
        out_specs={"acc": (shard_spec(),) * nrows_acc,
                   "globals": replicated_spec()},
        check_vma=False,
    ))


@functools.lru_cache(maxsize=64)
def _build_regrow(mesh: Mesh, old_cap: int, new_cap: int, nrows: int):
    def body(*acc):
        def one(a):
            out = jnp.full((new_cap,), INT32_MAX, jnp.int32)
            return lax.dynamic_update_slice(out, a, (0,))
        return tuple(one(a) for a in acc)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(shard_spec(),) * nrows,
        out_specs=(shard_spec(),) * nrows, check_vma=False))


@functools.lru_cache(maxsize=64)
def _build_finalize(mesh: Mesh, cap: int, num_groups: int):
    def body(*acc):
        out = finalize_rows_body(acc, num_groups=num_groups)
        c = out["counts"]
        return {
            "counts": c[None, :],  # (n, 3) once stacked
            # replicated per-owner maxima [words, pairs, long] so every
            # process sizes the same prefix-slice fetch (the one-shot
            # mesh engine's globals discipline); one pmax over the
            # counts vector
            "maxima": lax.pmax(c, SHARD_AXIS),
            "df": out["df"],
            "postings": out["postings"],
            "unique_groups": out["unique_groups"],
        }

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(shard_spec(),) * (2 * num_groups + 1),
        out_specs={"counts": shard_spec(), "maxima": replicated_spec(),
                   "df": shard_spec(), "postings": shard_spec(),
                   "unique_groups": ((shard_spec(), shard_spec()),)
                   * num_groups},
        check_vma=False,
    ))


class DistDeviceStreamEngine:
    """Hash-sharded bounded row accumulators over a raw byte-window
    stream.  ``initial_capacity`` is *per owner*.  The caller guards
    WidthOverflow per window BEFORE feeding and supplies per-window
    host stats (host_token_stats per byte shard)."""

    def __init__(self, *, width: int, mesh: Mesh,
                 window_pad: int = 1 << 13,
                 initial_capacity: int = 1 << 15):
        self._width = width
        self._num_groups = num_groups_for(width)
        self._mesh = mesh
        self._n = mesh.devices.size
        self._window_pad = window_pad
        self._cap = initial_capacity
        self._acc = None
        self._count = 0          # last observed max per-owner count
        self._live_groups = 1
        self.windows_fed = 0
        self.max_word_len = 0
        self.merge_retries = 0
        self._window_checks = []  # (device max_len, tok_cap, host stats)

    @property
    def capacity(self) -> int:
        """Per-owner accumulator capacity."""
        return self._cap

    def _empty(self, cap: int):
        # built per addressable device (not one global device_put) so a
        # multi-host pod process can create the accumulator without
        # seeing the other hosts' devices — the same multi-controller
        # contract as _feed_arr and the addressable-shard fetch
        pad = np.full(cap, INT32_MAX, np.int32)
        sh = sharding(self._mesh, shard_spec())
        local_pos = _local_mesh_positions(self._mesh)

        def one():
            arrays = [jax.device_put(pad, d) for d in local_pos.values()]
            return jax.make_array_from_single_device_arrays(
                (self._n * cap,), sh, arrays)

        return tuple(one() for _ in range(2 * self._num_groups + 1))

    def _regrow(self, old_cap: int) -> None:
        if self._acc is not None and old_cap < self._cap:
            self._acc = _build_regrow(
                self._mesh, old_cap, self._cap,
                2 * self._num_groups + 1)(*self._acc)

    def feed(self, shard_bufs, shard_ends, shard_ids, *, tok_count: int,
             max_len: int) -> None:
        """Tokenize + exchange + fold one sharded byte window.

        ``tok_count`` / ``max_len``: max per-shard token count and max
        cleaned length over the window's shards (host-exact); the
        caller has already rejected ``max_len > width``."""
        if tok_count == 0:
            return
        self.max_word_len = max(self.max_word_len, max_len)
        # sort_cols tracks the stream's RUNNING max length, so the
        # window's live group count below equals self._live_groups --
        # the exchange payload never carries zero pairs past it
        sort_cols = -(-max(self.max_word_len, 1) // 4)
        self._live_groups = max(self._live_groups,
                                live_groups_for(sort_cols, self._width))
        tok_cap = round_up(tok_count + 1, self._window_pad)
        exchange_cap = default_capacity(tok_cap, self._n)

        local_pos = _local_mesh_positions(self._mesh)
        # only THIS process's positions are read (a pod host may pass
        # None for shards it did not load — the one-shot mesh engine's
        # multi-controller contract)
        num_docs = shard_ends[min(local_pos)].shape[0]
        sh = sharding(self._mesh, shard_spec())

        def _feed_arr(parts):
            arrays = [jax.device_put(parts[i], d)
                      for i, d in local_pos.items()]
            return jax.make_array_from_single_device_arrays(
                (self._n * parts[min(local_pos)].shape[0],), sh, arrays)

        data = _feed_arr(shard_bufs)
        ends = _feed_arr(shard_ends)
        ids = _feed_arr(shard_ids)
        if self._acc is None:
            self._acc = self._empty(self._cap)

        while True:
            out = _build_merge(
                self._mesh, self._width, tok_cap, num_docs, self._cap,
                exchange_cap, sort_cols, self._live_groups,
                self._num_groups)(*self._acc, data, ends, ids)
            g = np.asarray(out["globals"])  # one scalar sync per window
            if int(g[1]) > 0 and exchange_cap < tok_cap:
                exchange_cap = tok_cap  # provably safe: <= tok_cap rows
                self.merge_retries += 1
                continue
            if int(g[0]) > self._cap:
                old = self._cap
                while self._cap < int(g[0]):
                    self._cap *= 2
                self.merge_retries += 1
                self._regrow(old)
                continue
            break
        self._acc = out["acc"]
        self._count = int(g[0])
        self._window_checks.append((int(g[2]), tok_cap, int(g[3]),
                                    max_len))
        # grow ahead of the next window once 3/4 full (amortized)
        if self._count * 4 > self._cap * 3:
            old = self._cap
            self._cap *= 2
            self._regrow(old)
        self.windows_fed += 1

    def finalize(self, *, sort_cols: int | None, max_doc_id: int,
                 stats: dict | None = None):
        """Per-owner index blocks via the shared addressable fetch
        (``{owner: dict}``, the one-shot mesh engine's contract).
        Re-checks every window's device stats against the host
        classifier first, like the single-chip streaming engine."""
        if self._acc is None:
            raise ValueError("no windows fed")
        for dev_max_len, tok_cap, dev_tokens, host_max_len in (
                self._window_checks):
            if dev_tokens + 1 > tok_cap:
                raise AssertionError(
                    f"device token count {dev_tokens} exceeded tok_cap "
                    f"{tok_cap}: host mask count diverged from the "
                    "device classifier (bug)")
            if dev_max_len != host_max_len:
                raise AssertionError(
                    f"device max word len {dev_max_len} != host "
                    f"{host_max_len}: classifier divergence (bug)")
        out = _build_finalize(
            self._mesh, self._cap, self._num_groups)(*self._acc)
        self._acc = None
        self._window_checks = []
        mx = np.asarray(out["maxima"])
        owners = fetch_owner_blocks(
            out, mesh=self._mesh, local_len=self._cap, width=self._width,
            sort_cols=sort_cols, max_doc_id=max_doc_id,
            max_words=int(mx[0]), max_pairs=int(mx[1]),
            max_long=int(mx[2]), stats=stats)
        if stats is not None:
            stats["merge_retries"] = self.merge_retries
            stats["accumulator_capacity_per_owner"] = self._cap
        return owners

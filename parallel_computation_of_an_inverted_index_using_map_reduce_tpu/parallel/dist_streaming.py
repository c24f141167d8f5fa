"""Distributed streaming engine: bounded per-chip accumulators over an
unbounded pair stream (BASELINE.json config 5's regime — "streaming
host->device token batches" on a mesh).

Combines the two scale axes the single-chip engines cover separately:

- **streaming** (ops/streaming.py): the device carries only the sorted
  unique pairs seen so far, bounded by output size, not stream length;
- **multi-chip** (parallel/dist_engine.py): pairs are hash-partitioned
  over the mesh with one ``all_to_all`` per window, so each chip's
  accumulator holds only its own terms — per-chip memory is
  O(unique / n), the shuffle rides ICI, and the map→reduce spill files
  of the reference (main.c:332-341) never exist.

Per window, as one ``shard_map`` program:

    recv   <- all_to_all(bucket(window, term % n))        # ICI shuffle
    acc_d  <- compact(unique(sort(acc_d ++ recv)))        # owner merge

Like the single-chip engine, two accumulator representations are
switched automatically mid-stream: **packed** (one int32
``term * stride + doc`` key) while the growing vocabulary still packs
(K.can_pack), and **pairs** (separate term/doc arrays, a three-key
bucket sort for the exchange and a two-key merge sort) once it
outgrows int32 — so the mesh path handles the same 10^6-doc corpora
single-chip streaming does.

The window feed is combiner-deduped per document by the tokenizer, but
cross-window duplicates (the numpy fallback tokenizer emits them) fold
into the accumulator exactly like the reference reducer's dedup
(main.c:176-184).

Unlike the single-chip engine's host-side bound (unique <= fed), a
per-owner bound cannot be derived host-side without assuming hash
uniformity, so each feed returns the replicated max per-owner count
(one scalar fetch per window — amortized over 10^5-doc windows) and an
overflowing merge is *retried* against the preserved previous
accumulator at a doubled capacity: no data loss, no uniformity
assumption.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from ..ops import keys as K
from ..ops.segment import bucket_edges, compact, first_occurrence_mask
from ..utils.rounding import round_up
from .dist_engine import _bucket_exchange, _build_prefix_slice, default_capacity
from .mesh import SHARD_AXIS, replicated_spec, shard_spec, sharding


def _pair_bucket_exchange(term, doc, *, num_shards: int, capacity: int):
    """Pair-mode exchange: bucket (term, doc) rows by ``term % n`` and
    run one ``all_to_all`` carrying both halves side by side
    (``[terms | docs]`` per destination row)."""
    local = term.shape[0]
    valid = term < K.INT32_MAX
    bucket = jnp.where(valid, term % num_shards, num_shards)
    b_s, t_s, d_s = lax.sort(
        (bucket.astype(jnp.int32), term, doc), num_keys=3)
    counts, offsets = bucket_edges(b_s, num_shards)
    overflow_local = (counts > capacity).any()
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    gather_idx = jnp.clip(offsets[:, None] + slot, 0, local - 1)
    in_bucket = slot < counts[:, None]
    send = jnp.concatenate([
        jnp.where(in_bucket, t_s[gather_idx], K.INT32_MAX),
        jnp.where(in_bucket, d_s[gather_idx], K.INT32_MAX),
    ], axis=1)  # (num_shards, 2 * capacity)
    recv = lax.all_to_all(send, SHARD_AXIS, 0, 0, tiled=True)
    recv = recv.reshape(num_shards, 2, capacity)
    return recv[:, 0, :].reshape(-1), recv[:, 1, :].reshape(-1), overflow_local


def _merge_body(acc_local, window_local, *, num_shards: int, cap: int,
                exchange_capacity: int, stride: int):
    recv, overflow_ex = _bucket_exchange(
        window_local, K.INT32_MAX, num_shards=num_shards,
        capacity=exchange_capacity, stride=stride)
    s = lax.sort(jnp.concatenate([acc_local, recv.reshape(-1)]))
    first = first_occurrence_mask(s) & (s < K.INT32_MAX)
    count = first.sum(dtype=jnp.int32)
    return {
        "acc": compact(s, first, cap, K.INT32_MAX),
        "max_count": lax.pmax(count, SHARD_AXIS),
        "exchange_overflow": lax.psum(
            overflow_ex.astype(jnp.int32), SHARD_AXIS),
    }


def _merge_body_pairs(acc_t, acc_d, win_t, win_d, *, num_shards: int,
                      cap: int, exchange_capacity: int):
    recv_t, recv_d, overflow_ex = _pair_bucket_exchange(
        win_t, win_d, num_shards=num_shards, capacity=exchange_capacity)
    t = jnp.concatenate([acc_t, recv_t])
    d = jnp.concatenate([acc_d, recv_d])
    t_s, d_s = lax.sort((t, d), num_keys=2)
    first = (first_occurrence_mask(t_s) | first_occurrence_mask(d_s)) & (
        t_s < K.INT32_MAX)
    count = first.sum(dtype=jnp.int32)
    return {
        "acc_t": compact(t_s, first, cap, K.INT32_MAX),
        "acc_d": compact(d_s, first, cap, K.INT32_MAX),
        "max_count": lax.pmax(count, SHARD_AXIS),
        "exchange_overflow": lax.psum(
            overflow_ex.astype(jnp.int32), SHARD_AXIS),
    }


@functools.lru_cache(maxsize=64)
def _build_merge(mesh: Mesh, window_local: int, num_shards: int, cap: int,
                 exchange_capacity: int, stride: int):
    def body(acc_local, window_local_arr):
        return _merge_body(
            acc_local, window_local_arr, num_shards=num_shards, cap=cap,
            exchange_capacity=exchange_capacity, stride=stride)

    # no donation: an overflowing merge is retried against the same
    # accumulator and window at a larger capacity
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(shard_spec(), shard_spec()),
        out_specs={"acc": shard_spec(),
                   "max_count": replicated_spec(),
                   "exchange_overflow": replicated_spec()},
        check_vma=False,
    ))


@functools.lru_cache(maxsize=64)
def _build_merge_pairs(mesh: Mesh, window_local: int, num_shards: int,
                       cap: int, exchange_capacity: int):
    def body(acc_t, acc_d, win_t, win_d):
        return _merge_body_pairs(
            acc_t, acc_d, win_t, win_d, num_shards=num_shards, cap=cap,
            exchange_capacity=exchange_capacity)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(shard_spec(),) * 4,
        out_specs={"acc_t": shard_spec(), "acc_d": shard_spec(),
                   "max_count": replicated_spec(),
                   "exchange_overflow": replicated_spec()},
        check_vma=False,
    ))


@functools.lru_cache(maxsize=64)
def _build_regrow(mesh: Mesh, old_cap: int, new_cap: int):
    def body(acc_local):
        out = jnp.full((new_cap,), K.INT32_MAX, jnp.int32)
        return lax.dynamic_update_slice(out, acc_local, (0,))

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=shard_spec(), out_specs=shard_spec(),
        check_vma=False))


@functools.lru_cache(maxsize=64)
def _build_unpack(mesh: Mesh, cap: int, stride: int):
    """Packed sharded accumulator -> (term, doc) pair accumulators."""
    def body(acc_local):
        valid = acc_local < K.INT32_MAX
        term = jnp.where(valid, acc_local // stride, K.INT32_MAX)
        doc = jnp.where(valid, acc_local % stride, K.INT32_MAX)
        return term, doc

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=shard_spec(),
        out_specs=(shard_spec(), shard_spec()), check_vma=False))


class DistStreamingIndexEngine:
    """Hash-sharded bounded accumulator over a provisional-id pair stream.

    One per-owner sorted-unique buffer per chip; each :meth:`feed`
    shuffles a window over ICI and folds it in.  ``initial_capacity``
    is *per owner*.  Starts in packed mode and switches permanently to
    pair mode the first time ``vocab_size_so_far`` stops packing into
    int32 keys (exactly like ops/streaming.StreamingIndexEngine).
    """

    def __init__(self, *, max_doc_id: int, mesh: Mesh,
                 window_pad: int = 1 << 16,
                 initial_capacity: int = 1 << 16):
        self._stride = max_doc_id + 2
        self._max_doc_id = max_doc_id
        self._mesh = mesh
        self._n = mesh.devices.size
        self._window_pad = window_pad
        self._cap = initial_capacity
        self._acc = None        # packed mode
        self._acc_pair = None   # pair mode: (terms, docs)
        self._count = 0         # last observed max per-owner count
        self.windows_fed = 0
        self.merge_retries = 0

    @property
    def capacity(self) -> int:
        """Per-owner accumulator capacity (total device memory is
        ``capacity * mesh size`` int32s per buffer)."""
        return self._cap

    @property
    def mode(self) -> str:
        return "pairs" if self._acc_pair is not None else "packed"

    def _empty(self, cap: int):
        return jax.device_put(
            np.full(self._n * cap, K.INT32_MAX, np.int32),
            sharding(self._mesh, shard_spec()))

    def _switch_to_pairs(self) -> None:
        if self._acc is None:
            self._acc_pair = (self._empty(self._cap), self._empty(self._cap))
        else:
            self._acc_pair = _build_unpack(
                self._mesh, self._cap, self._stride)(self._acc)
            self._acc = None

    def _upload(self, host: np.ndarray):
        return jax.device_put(host, sharding(self._mesh, shard_spec()))

    def feed(self, prov_term_ids: np.ndarray, doc_ids: np.ndarray,
             vocab_size_so_far: int) -> None:
        """Shuffle + fold one window of (provisional term, doc) pairs."""
        n_pairs = int(prov_term_ids.shape[0])
        if n_pairs == 0:
            return
        if self.mode == "packed" and not K.can_pack(vocab_size_so_far,
                                                    self._max_doc_id):
            self._switch_to_pairs()
        padded = round_up(n_pairs, max(self._window_pad, self._n))
        padded = round_up(padded, self._n)
        window_local = padded // self._n
        exchange_cap = default_capacity(window_local, self._n)

        if self.mode == "packed":
            if self._acc is None:
                self._acc = self._empty(self._cap)
            host = np.full(padded, K.INT32_MAX, np.int32)
            np.multiply(prov_term_ids, self._stride, out=host[:n_pairs])
            host[:n_pairs] += doc_ids
            window = (self._upload(host),)
        else:
            ht = np.full(padded, K.INT32_MAX, np.int32)
            hd = np.full(padded, K.INT32_MAX, np.int32)
            ht[:n_pairs] = prov_term_ids
            hd[:n_pairs] = doc_ids
            window = (self._upload(ht), self._upload(hd))

        while True:
            if self.mode == "packed":
                out = _build_merge(
                    self._mesh, window_local, self._n, self._cap,
                    exchange_cap, self._stride)(self._acc, *window)
            else:
                out = _build_merge_pairs(
                    self._mesh, window_local, self._n, self._cap,
                    exchange_cap)(*self._acc_pair, *window)
            max_count = int(out["max_count"])  # one scalar sync per window
            if int(out["exchange_overflow"]) > 0:
                exchange_cap = window_local  # provably safe
                self.merge_retries += 1
                continue
            if max_count > self._cap:
                # grow and retry against the preserved accumulator
                while self._cap < max_count:
                    self._cap *= 2
                self.merge_retries += 1
                self._regrow_acc()
                continue
            break
        if self.mode == "packed":
            self._acc = out["acc"]
        else:
            self._acc_pair = (out["acc_t"], out["acc_d"])
        self._count = max_count
        # grow ahead of the next window once 3/4 full (amortized)
        if self._count * 4 > self._cap * 3:
            self._cap *= 2
            self._regrow_acc()
        self.windows_fed += 1

    def _regrow_acc(self) -> None:
        """Pad the live accumulator buffers up to the current capacity."""
        if self._acc is not None:
            old = self._acc.shape[0] // self._n
            if old < self._cap:
                self._acc = _build_regrow(self._mesh, old, self._cap)(self._acc)
        if self._acc_pair is not None:
            old = self._acc_pair[0].shape[0] // self._n
            if old < self._cap:
                grow = _build_regrow(self._mesh, old, self._cap)
                self._acc_pair = (grow(self._acc_pair[0]),
                                  grow(self._acc_pair[1]))

    def finalize(self, stats: dict | None = None):
        """``(mode, {owner: rows})`` for every addressable owner, valid
        prefix only — the capacity tail never crosses the D2H link,
        mirroring dist_engine's multi-host fetch contract.  Packed
        mode: rows are sorted packed keys.  Pair mode: rows are
        ``(terms, docs)`` tuples sorted by (term, doc)."""
        mode = self.mode
        if self._acc is None and self._acc_pair is None:
            return mode, {}
        nfetch = min(self._cap, round_up(max(self._count, 1), 1 << 13))
        slicer = _build_prefix_slice(self._mesh, self._cap, nfetch)

        def fetch_rows(arr):
            rows, fetched = {}, 0
            for s in slicer(arr).addressable_shards:
                owner = (s.index[0].start or 0) // nfetch
                row = np.asarray(s.data)
                rows[owner] = row
                fetched += row.nbytes
            return rows, fetched

        if mode == "packed":
            rows, fetched = fetch_rows(self._acc)
            rows = {o: r[r < K.INT32_MAX] for o, r in rows.items()}
        else:
            rows_t, f1 = fetch_rows(self._acc_pair[0])
            rows_d, f2 = fetch_rows(self._acc_pair[1])
            fetched = f1 + f2
            rows = {}
            for o, t in rows_t.items():
                valid = t < K.INT32_MAX
                rows[o] = (t[valid], rows_d[o][valid])
        if stats is not None:
            stats["dist_fetched_bytes"] = fetched
        self._acc = self._acc_pair = None
        return mode, rows

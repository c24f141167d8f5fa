"""Device-resident batched query engine over ``index.mri``.

The host engine (:mod:`.engine`) answers batches with numpy over mmap
views; this engine uploads the artifact's columns to device memory ONCE
and answers large batches as jitted XLA programs, the batch dimension
sharded across devices with ``jax.shard_map`` (DrJAX's
broadcast/map/reduce shape, arxiv 2403.07128: columns replicated,
queries mapped, results concatenated).

Per batch the pipeline is

  1. term resolution — a fixed-step vectorized bisect over the 8-byte
     big-endian term-prefix key column.  jax runs x64-free here, so the
     u64 key is carried as a big-endian ``(hi, lo)`` uint32 pair whose
     pairwise lexicographic order equals the u64 numeric order; the
     bisect is ``ceil(log2 V)`` masked ``jnp.where`` steps (the shape
     ``jnp.searchsorted`` lowers to, spelled out for the pair dtype).
     Shared-prefix collisions resolve in a static ``max_prefix_group``-
     step gather-compare over the full fixed-width term rows, fused
     with the df gather.
  2. postings decode — segment-gather of each hit's delta run into a
     fixed-width tier (powers of 4, statically bucketed so steady-state
     serving never recompiles) and one int32 row-cumsum; invalid lanes
     carry ``_SENTINEL``.
  3. compound ops — AND/OR as sorted-set intersection/union over the
     sentinel-padded posting windows (membership via vectorized
     ``jnp.searchsorted`` probes; union via sort + neighbor-compare
     dedup), and top-k as a ``df_order`` gather.

Every answer is byte-identical to the host engine — the parity suite
(tests/test_serve_device.py) fuzzes both engines against each other at
batches {1, 32, 1024, 8192} under ``JAX_PLATFORMS=cpu``.

Shape discipline: batches pad to power-of-two buckets (multiples of the
shard count), posting tiers are powers of 4, and compound ops pad their
term count to powers of two — so the jit cache stays O(log) in every
dimension and ``compile_stats()`` can assert a zero-recompile steady
state after warmup.
"""

from __future__ import annotations

import os

import numpy as np

from . import artifact as artifact_mod
from . import planner as planner_mod
from .cache import LRUCache
from .engine import (
    BM25_B, BM25_K1, OpTimer, bm25_contrib, bm25_idf, encode_terms,
    letter_index,
)

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs import attribution as obs_attrib
from ..obs import metrics as obs_metrics
from ..parallel.mesh import SHARD_AXIS, make_mesh
from ..utils import device, envknobs

#: pad value in posting windows: larger than any doc id (guarded at
#: load), so sentinel lanes sort after every real doc.
_SENTINEL = np.int32(2 ** 31 - 1)

SHARDS_ENV = "MRI_SERVE_SHARDS"
#: soft cap on decode-window elements per call (B * W); oversize
#: batches loop in bucket-sized chunks instead of materializing one
#: giant (B, W) window.
DECODE_BUDGET_ENV = "MRI_SERVE_DEVICE_DECODE_BUDGET"  # default: envknobs

#: smallest per-shard batch bucket: tiny batches all share one compile.
_MIN_LANES = 8

#: bound on the relative error of a float32 device BM25 score against
#: its float64 value (a few ulps per term; generous on purpose)
_F32_REL = 1e-4


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def _pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n >= 1 else 1


def _make_lookup(mesh, nsteps: int, group: int):
    """Jitted fused resolve: (idx, found, df) per query lane."""

    def body(key_hi, key_lo, rows, df, q_hi, q_lo, q_rows):
        V = key_hi.shape[0]

        def bisect(right: bool):
            lo = jnp.zeros(q_hi.shape, jnp.int32)
            hi = jnp.full(q_hi.shape, V, jnp.int32)
            for _ in range(nsteps):
                active = lo < hi
                mid = (lo + hi) >> 1
                m = jnp.minimum(mid, V - 1)
                kh, kl = key_hi[m], key_lo[m]
                go = (kh < q_hi) | ((kh == q_hi)
                                    & ((kl <= q_lo) if right
                                       else (kl < q_lo)))
                lo = jnp.where(active & go, mid + 1, lo)
                hi = jnp.where(active & ~go, mid, hi)
            return lo

        lo_i, hi_i = bisect(right=False), bisect(right=True)
        at = jnp.minimum(lo_i, V - 1)
        found = jnp.zeros(q_hi.shape, bool)
        # Shared-prefix fixup: up to `group` vocabulary terms share one
        # 8-byte key; compare full fixed-width rows at each candidate.
        for j in range(group):
            cand = jnp.minimum(lo_i + j, V - 1)
            ok = ((lo_i + j) < hi_i) & jnp.all(
                rows[cand] == q_rows, axis=1)
            at = jnp.where(ok & ~found, cand, at)
            found = found | ok
        found = found & ((q_hi | q_lo) != 0)
        dfv = jnp.where(found, df[at], 0)
        return at.astype(jnp.int32), found, dfv

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(),
                  P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False))


def _decode_window(post_offsets, postings, idx, n, *, width: int):
    """(len(idx), width) sentinel-padded absolute doc ids: segment
    gather of the delta runs + one row cumsum."""
    Ptot = postings.shape[0]
    start = post_offsets[idx]
    lane = jnp.arange(width, dtype=jnp.int32)
    pos = start[:, None] + lane[None, :]
    valid = lane[None, :] < n[:, None]
    d = jnp.where(valid, postings[jnp.clip(pos, 0, max(Ptot - 1, 0))], 0)
    docs = jnp.cumsum(d, axis=1, dtype=jnp.int32)
    return jnp.where(valid, docs, _SENTINEL)


def _make_decode(mesh, width: int):
    def body(post_offsets, postings, idx, n):
        return _decode_window(post_offsets, postings, idx, n, width=width)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(SHARD_AXIS), check_vma=False))


def _bit_window(words, word_ix, off, nbits):
    """Per-lane unaligned read of a ``nbits``-bit little-endian value
    starting ``off`` bits into word ``word_ix``: two word gathers + a
    fixed shift-or (``words`` carries one zero pad word so ``+ 1`` never
    reads past the stream)."""
    r = (off & 31).astype(jnp.uint32)
    w0 = words[word_ix]
    w1 = words[word_ix + 1]
    val = (w0 >> r) | jnp.where(
        r == 0, jnp.uint32(0), w1 << ((jnp.uint32(32) - r) & 31))
    nb = nbits.astype(jnp.uint32)
    mask = jnp.where(nb == 0, jnp.uint32(0), (jnp.uint32(1) << nb)
                     - jnp.uint32(1))
    return (val & mask).astype(jnp.int32)


def _decode_window_v2(term_block_off, blk_first, blk_width, blk_woff,
                      post_words, idx, n, *, width: int,
                      block_size: int):
    """v2 mirror of :func:`_decode_window`: (len(idx), width) sentinel-
    padded absolute doc ids straight from the blocked bitpacked layout.

    Lane j of a term maps statically to block ``j // block_size`` slot
    ``j % block_size``; slot 0 reads the skip table's absolute
    ``blk_first``, every other slot bit-extracts its (delta - 1).  The
    cumsum then runs PER BLOCK (blocks re-anchor absolutely), so a
    partially-filled block's trailing garbage never contaminates the
    next block — and invalid lanes are sentinel-masked exactly as v1.
    """
    lane = jnp.arange(width, dtype=jnp.int32)
    s = lane & (block_size - 1)
    qb = lane >> (block_size.bit_length() - 1)
    bl = term_block_off[idx][:, None] + qb[None, :]
    w = blk_width[bl]
    off = jnp.maximum(s - 1, 0)[None, :] * w
    delta = _bit_window(post_words, blk_woff[bl] + (off >> 5),
                        off, w) + 1
    vals = jnp.where(s[None, :] == 0, blk_first[bl], delta)
    if width <= block_size:
        docs = jnp.cumsum(vals, axis=1, dtype=jnp.int32)
    else:
        T = vals.shape[0]
        docs = jnp.cumsum(
            vals.reshape(T, width // block_size, block_size),
            axis=2, dtype=jnp.int32).reshape(T, width)
    valid = lane[None, :] < n[:, None]
    return jnp.where(valid, docs, _SENTINEL)


def _tf_window_v2(term_block_off, blk_tf_width, blk_tf_woff, tf_words,
                  idx, n, *, width: int, block_size: int):
    """(len(idx), width) term frequencies aligned with
    :func:`_decode_window_v2` (slot s reads packed value s; no cumsum —
    tf entries are independent).  Invalid lanes carry 0."""
    lane = jnp.arange(width, dtype=jnp.int32)
    s = lane & (block_size - 1)
    qb = lane >> (block_size.bit_length() - 1)
    bl = term_block_off[idx][:, None] + qb[None, :]
    tw = blk_tf_width[bl]
    off = s[None, :] * tw
    tf = _bit_window(tf_words, blk_tf_woff[bl] + (off >> 5),
                     off, tw) + 1
    valid = lane[None, :] < n[:, None]
    return jnp.where(valid, tf, 0)


def _make_decode_v2(mesh, width: int, block_size: int):
    def body(term_block_off, blk_first, blk_width, blk_woff, post_words,
             idx, n):
        return _decode_window_v2(
            term_block_off, blk_first, blk_width, blk_woff, post_words,
            idx, n, width=width, block_size=block_size)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(),
                  P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(SHARD_AXIS), check_vma=False))


def _make_bool(op: str, width: int):
    """Jitted T-term AND/OR over sentinel-padded posting windows.

    One query, T terms (T static, padded to a power of two): decode all
    runs to (T, width), then intersect (membership probes via
    ``jnp.searchsorted`` on each other run) or union (flat sort +
    neighbor-compare dedup).  Returns the sorted result pushed to the
    front plus its count — the host slices."""

    def body(post_offsets, postings, idx, n):
        docs = _decode_window(post_offsets, postings, idx, n, width=width)
        return _bool_tail(op, docs, n, width)

    return jax.jit(body)


def _bool_tail(op: str, docs, n, width: int):
    """Shared AND/OR combine over a (T, width) sentinel-padded window."""
    T = docs.shape[0]
    if op == "and":
        vals = docs[0]
        alive = jnp.arange(width) < n[0]
        for t in range(1, T):
            j = jnp.searchsorted(docs[t], vals)
            alive = alive & (j < width) & (
                docs[t][jnp.minimum(j, width - 1)] == vals)
        out = jnp.sort(jnp.where(alive, vals, _SENTINEL))
        return out, alive.sum()
    flat = jnp.sort(docs.ravel())
    first = jnp.concatenate(
        [jnp.ones((1,), bool), flat[1:] != flat[:-1]])
    keep = first & (flat != _SENTINEL)
    out = jnp.sort(jnp.where(keep, flat, _SENTINEL))
    return out, keep.sum()


def _make_bool_v2(op: str, width: int, block_size: int):
    def body(term_block_off, blk_first, blk_width, blk_woff, post_words,
             idx, n):
        docs = _decode_window_v2(
            term_block_off, blk_first, blk_width, blk_woff, post_words,
            idx, n, width=width, block_size=block_size)
        return _bool_tail(op, docs, n, width)

    return jax.jit(body)


def _bm25_tail(docs, tfs, n, found, doc_lens, ndocs, avgdl, width: int,
               k: int):
    """Scatter-add BM25 contributions into a dense doc-score column and
    ``lax.top_k`` it: ties prefer the lower doc id (top_k is stable)."""
    lane_ok = (jnp.arange(width)[None, :] < n[:, None]) \
        & found[:, None] & (docs != _SENTINEL)
    dfv = jnp.where(found, n, 0).astype(jnp.float32)
    idf = jnp.log(1.0 + (ndocs - dfv + 0.5) / (dfv + 0.5))
    tff = tfs.astype(jnp.float32)
    dl = doc_lens[jnp.where(lane_ok, docs, 0)]
    denom = tff + BM25_K1 * (1.0 - BM25_B + BM25_B * dl / avgdl)
    contrib = jnp.where(
        lane_ok, idf[:, None] * tff * (BM25_K1 + 1.0) / denom, 0.0)
    scores = jnp.zeros(doc_lens.shape[0], jnp.float32).at[
        jnp.where(lane_ok, docs, 0).ravel()].add(contrib.ravel())
    vals, ids = jax.lax.top_k(scores, k)
    return ids, vals


def _make_bm25(width: int, k: int):
    def body(post_offsets, postings, idx, n, found, doc_lens, ndocs,
             avgdl):
        docs = _decode_window(post_offsets, postings, idx, n, width=width)
        tfs = jnp.ones(docs.shape, jnp.int32)  # v1: no tf column
        return _bm25_tail(docs, tfs, n, found, doc_lens, ndocs, avgdl,
                          width, k)

    return jax.jit(body)


def _make_bm25_v2(width: int, k: int, block_size: int):
    def body(term_block_off, blk_first, blk_width, blk_woff, post_words,
             blk_tf_width, blk_tf_woff, tf_words, idx, n, found,
             doc_lens, ndocs, avgdl):
        docs = _decode_window_v2(
            term_block_off, blk_first, blk_width, blk_woff, post_words,
            idx, n, width=width, block_size=block_size)
        tfs = _tf_window_v2(
            term_block_off, blk_tf_width, blk_tf_woff, tf_words,
            idx, n, width=width, block_size=block_size)
        return _bm25_tail(docs, tfs, n, found, doc_lens, ndocs, avgdl,
                          width, k)

    return jax.jit(body)


def _make_bm25_blocks(k: int, block_size: int):
    """Jitted BM25 scatter-add over an (S, block_size) SURVIVOR-BLOCK
    window instead of whole (T, width) term windows — the device form
    of Block-Max pruning.  The host picks the surviving global block
    ids (``bl``) from the v2.1 bound columns and pre-folds each block's
    ``weight * idf`` into ``widf``; the kernel decodes exactly those
    blocks (lane 0 reads the skip table's absolute ``blk_first``, other
    lanes bit-extract deltas, one per-row cumsum), scores them, and
    ``lax.top_k``s the dense column.  Padded rows carry ``cnt == 0``
    and contribute nothing."""

    def body(blk_first, blk_width, blk_woff, post_words,
             blk_tf_width, blk_tf_woff, tf_words,
             bl, cnt, widf, doc_lens, avgdl):
        lane = jnp.arange(block_size, dtype=jnp.int32)
        w = blk_width[bl][:, None]
        off = jnp.maximum(lane - 1, 0)[None, :] * w
        delta = _bit_window(post_words, blk_woff[bl][:, None]
                            + (off >> 5), off, w) + 1
        vals = jnp.where(lane[None, :] == 0,
                         blk_first[bl][:, None], delta)
        docs = jnp.cumsum(vals, axis=1, dtype=jnp.int32)
        tw = blk_tf_width[bl][:, None]
        toff = lane[None, :] * tw
        tf = _bit_window(tf_words, blk_tf_woff[bl][:, None]
                         + (toff >> 5), toff, tw) + 1
        lane_ok = lane[None, :] < cnt[:, None]
        tff = tf.astype(jnp.float32)
        dl = doc_lens[jnp.where(lane_ok, docs, 0)]
        denom = tff + BM25_K1 * (1.0 - BM25_B + BM25_B * dl / avgdl)
        contrib = jnp.where(
            lane_ok, widf[:, None] * tff * (BM25_K1 + 1.0) / denom, 0.0)
        scores = jnp.zeros(doc_lens.shape[0], jnp.float32).at[
            jnp.where(lane_ok, docs, 0).ravel()].add(contrib.ravel())
        svals, ids = jax.lax.top_k(scores, k)
        return ids, svals

    return jax.jit(body)


def _make_topk(k: int):
    def body(df_order, df, lo):
        pick = jax.lax.dynamic_slice(df_order, (lo,), (k,))
        return pick, df[pick]

    return jax.jit(body)


class DeviceEngine:
    """Batched query API over one artifact resident in device memory.

    Mirrors :class:`.engine.Engine`'s surface exactly (same inputs,
    same outputs, byte-identical answers); ``shards`` sizes the 1-D
    batch mesh (default: ``$MRI_SERVE_SHARDS`` or every local device).
    The host LRU posting cache does not apply here — decodes are
    vectorized device work, so the cache is present but idle (capacity
    kept for stats-surface parity).
    """

    engine_name = "device"

    def __init__(self, path, cache_terms: int = 4096,
                 shards: int | None = None,
                 decode_budget: int | None = None):
        if artifact_mod.is_segment_managed(path):
            raise artifact_mod.ArtifactError(
                f"{path} is segment-managed (segments.manifest.json "
                "present): the device engine serves single artifacts "
                "only — use create_engine with host/auto")
        self.artifact = artifact_mod.load_artifact(path)
        art = self.artifact
        if art.max_doc_id >= int(_SENTINEL):
            raise artifact_mod.ArtifactError(
                f"{art.path}: max_doc_id {art.max_doc_id} collides with "
                f"the device engine's padding sentinel")
        cols = artifact_mod.device_columns(art)
        self.vocab_size = cols["vocab"]
        self._width = cols["width"]
        self._sdtype = f"S{self._width}"
        self._group = cols["max_prefix_group"]
        self._h_df = cols["df"]
        self._h_letter_dir = cols["letter_dir"]

        if shards is None:
            shards = envknobs.get(SHARDS_ENV)
        self._mesh = make_mesh(shards)
        self._num_shards = self._mesh.devices.size
        self._decode_budget = int(
            decode_budget if decode_budget is not None
            else envknobs.get(DECODE_BUDGET_ENV))

        rep = NamedSharding(self._mesh, P())
        put = lambda a: jax.device_put(a, rep)  # noqa: E731
        self._d_key_hi = put(cols["key_hi"])
        self._d_key_lo = put(cols["key_lo"])
        self._d_rows = put(cols["rows"])
        self._d_df = put(cols["df"])
        self._d_df_order = put(cols["df_order"])
        # where the columns really live (stats: a run can show the
        # engine spread over its mesh)
        self._placed_on = sorted(str(d)
                                 for d in self._d_df.sharding.device_set)
        self._fmt = cols["format"]
        if self._fmt >= artifact_mod.VERSION_V2:
            self._block_size = cols["block_size"]
            self._d_term_block_off = put(cols["term_block_off"])
            self._d_blk_first = put(cols["blk_first"])
            self._d_blk_width = put(cols["blk_width"])
            self._d_blk_woff = put(cols["blk_woff"])
            self._d_post_words = put(cols["post_words"])
            self._d_blk_tf_width = put(cols["blk_tf_width"])
            self._d_blk_tf_woff = put(cols["blk_tf_woff"])
            self._d_tf_words = put(cols["tf_words"])
            self._decode_cols = (
                self._d_term_block_off, self._d_blk_first,
                self._d_blk_width, self._d_blk_woff, self._d_post_words)
            self._d_post_offsets = self._d_postings = None
        else:
            self._block_size = 0
            self._d_post_offsets = put(cols["post_offsets"])
            self._d_postings = put(cols["postings"])
            self._decode_cols = (self._d_post_offsets, self._d_postings)
        self._d_doc_lens = None  # lazy: uploaded at first top_k_scored
        self._bm25_scalars = None

        # posting tiers: powers of 4 from 8 up to the global max df, so
        # every batch decodes at the smallest static width covering it
        max_df = int(self._h_df.max()) if self.vocab_size else 1
        tiers, t = [], _MIN_LANES
        while True:
            tiers.append(t)
            if t >= max_df:
                break
            t *= 4
        self._tiers = tiers

        nsteps = max(self.vocab_size, 1).bit_length() + 1
        self._lookup_fn = _make_lookup(self._mesh, nsteps, self._group)
        self._decode_fns: dict[int, object] = {}
        self._bool_fns: dict[tuple, object] = {}
        self._topk_fns: dict[int, object] = {}
        self._bm25_fns: dict[tuple, object] = {}
        self._blocks_fns: dict[tuple, object] = {}

        # per-engine obs registry: describe() stays a view over it and
        # the daemon folds it into the Prometheus exposition
        self.metrics = obs_metrics.Registry()
        self.metrics.gauge("mri_engine_vocab_terms").set(self.vocab_size)
        self.metrics.gauge("mri_engine_artifact_bytes").set(art.nbytes)
        self._cache = LRUCache(cache_terms, registry=self.metrics,
                               prefix="mri_serve_cache")  # idle on the device path
        self._ops = OpTimer(registry=self.metrics)
        # decode-plane counters, host-engine names: the device decodes
        # inside jitted kernels, so the tallies are computed host-side
        # from the artifact's block/offset columns per resolved term
        self._c_blocks_decoded = \
            self.metrics.counter("mri_engine_blocks_decoded_total")
        self._c_blocks_skipped = \
            self.metrics.counter("mri_engine_blocks_skipped_total")
        self._c_bytes_decoded = \
            self.metrics.counter("mri_engine_bytes_decoded_total")
        self.planner = planner_mod.Planner(self.metrics)
        # host-side BM25 memos feeding the pruning plan: per-term f64
        # contributions (theta bootstrap) and per-block upper bounds
        self._bm25_host = None  # (doc_lens f64, ndocs, avgdl)
        self._score_memo: dict[int, np.ndarray] = {}
        self._bound_memo: dict[int, tuple] = {}
        self._memo_cap = max(int(cache_terms), 1)

    # -- shape bucketing ------------------------------------------------

    def _bucket(self, n: int) -> int:
        """Padded batch size: power-of-two lanes per shard, min 8."""
        D = self._num_shards
        return D * max(_MIN_LANES, _next_pow2(-(-n // D)))

    def _tier(self, max_len: int) -> int:
        for t in self._tiers:
            if t >= max_len:
                return t
        return self._tiers[-1]

    def _decode_fn(self, width: int):
        fn = self._decode_fns.get(width)
        if fn is None:
            if self._fmt >= artifact_mod.VERSION_V2:
                fn = _make_decode_v2(self._mesh, width, self._block_size)
            else:
                fn = _make_decode(self._mesh, width)
            self._decode_fns[width] = fn
        return fn

    # -- term resolution ------------------------------------------------

    def encode_batch(self, terms) -> np.ndarray:
        return encode_terms(terms, self._width)

    def _split_keys(self, q: np.ndarray):
        """S-dtype batch -> (rows u8, key_hi u32, key_lo u32), the
        device mirror of the artifact's key columns."""
        B, w = len(q), self._width
        rows = np.ascontiguousarray(q).view(np.uint8).reshape(B, w)
        k8 = rows if w >= 8 else np.pad(rows, ((0, 0), (0, 8 - w)))
        k8 = np.ascontiguousarray(k8[:, :8])
        q_hi = np.ascontiguousarray(k8[:, :4]).view(">u4").ravel()
        q_lo = np.ascontiguousarray(k8[:, 4:]).view(">u4").ravel()
        return rows, q_hi.astype(np.uint32), q_lo.astype(np.uint32)

    def _resolve(self, batch):
        """(idx i32, found bool, df i32) per query, host numpy."""
        q = np.asarray(batch, dtype=self._sdtype)
        B = len(q)
        if B == 0 or self.vocab_size == 0:
            return (np.zeros(B, dtype=np.int32),
                    np.zeros(B, dtype=bool),
                    np.zeros(B, dtype=np.int32))
        rows, q_hi, q_lo = self._split_keys(q)
        Bp = self._bucket(B)
        if Bp != B:
            rows = np.vstack(
                [rows, np.zeros((Bp - B, self._width), np.uint8)])
            q_hi = np.concatenate([q_hi, np.zeros(Bp - B, np.uint32)])
            q_lo = np.concatenate([q_lo, np.zeros(Bp - B, np.uint32)])
        idx, found, dfv = self._lookup_fn(
            self._d_key_hi, self._d_key_lo, self._d_rows, self._d_df,
            q_hi, q_lo, rows)
        idx = np.asarray(idx)[:B]
        found = np.asarray(found)[:B]
        dfv = np.asarray(dfv)[:B]
        coll = obs_attrib.active()
        if coll is not None:
            for t, i, ok, d in zip(q.tolist(), idx.tolist(),
                                   found.tolist(), dfv.tolist()):
                coll.term(t, int(i), bool(ok), int(d), "device")
        return idx, found, dfv

    def lookup(self, batch):
        """Host-API parity: (lex idx, found) per query."""
        # mrilint: allow(trace) resolution is attributed in _resolve
        idx, found, _ = self._resolve(batch)
        return idx.astype(np.int64), found

    # -- single-term answers --------------------------------------------

    def df(self, batch) -> np.ndarray:
        with self._ops.time("df"):
            _, _, dfv = self._resolve(batch)
            return dfv.astype(np.int64)

    def _note_decode(self, uidx) -> None:
        """Count one decode pass over terms ``uidx`` (host-side mirror
        of the kernels' work: block/byte spans from the artifact's
        offset columns) on the registry and the attribution collector.
        The feed sits beside the counter incs, so per-request reports
        can never drift from the registry (the parity gate)."""
        uidx = np.asarray(uidx, dtype=np.int64)
        if not len(uidx):
            return
        art = self.artifact
        if self._fmt >= artifact_mod.VERSION_V2:
            b0 = art.term_block_off[uidx]
            b1 = art.term_block_off[uidx + 1]
            blocks = int((b1 - b0).sum())
            nbytes = int((art.blk_woff[b1]
                          - art.blk_woff[b0]).sum()) * 4
        else:
            blocks = len(uidx)
            nbytes = int(self._h_df[uidx].sum()) * 4
        self._c_blocks_decoded.inc(blocks)
        self._c_bytes_decoded.inc(nbytes)
        coll = obs_attrib.active()
        if coll is not None:
            coll.decoded(blocks, nbytes)

    def _decode_batch(self, idx, n, width):
        """Chunked (len(idx), width) sentinel-padded decode, bucketed so
        B * width stays under the decode budget per device call."""
        B = len(idx)
        D = self._num_shards
        per = max(1, self._decode_budget // max(width, 1) // D)
        cap = D * max(_MIN_LANES, _pow2_floor(per))
        out = np.empty((B, width), dtype=np.int32)
        fn = self._decode_fn(width)
        step = min(self._bucket(B), cap)
        for at in range(0, B, step):
            part_idx = idx[at:at + step]
            part_n = n[at:at + step]
            L = len(part_idx)
            Bp = min(self._bucket(L), step)
            if Bp != L:
                part_idx = np.concatenate(
                    [part_idx, np.zeros(Bp - L, np.int32)])
                part_n = np.concatenate(
                    [part_n, np.zeros(Bp - L, np.int32)])
            win = fn(*self._decode_cols,
                     part_idx.astype(np.int32), part_n.astype(np.int32))
            out[at:at + L] = np.asarray(win)[:L]
        return out

    def postings(self, batch) -> list[np.ndarray | None]:
        with self._ops.time("postings"):
            idx, found, dfv = self._resolve(batch)
            B = len(found)
            if B == 0:
                return []
            if not found.any():
                return [None] * B
            self._note_decode(idx[found])
            width = self._tier(int(dfv.max()))
            win = self._decode_batch(idx, np.where(found, dfv, 0), width)
            return [win[i, :dfv[i]] if found[i] else None
                    for i in range(B)]

    # -- compound queries -----------------------------------------------

    def top_k(self, letter, k: int) -> list[tuple[bytes, int]]:
        letter = letter_index(letter)
        with self._ops.time("top_k"):
            lo = int(self._h_letter_dir[letter])
            hi = int(self._h_letter_dir[letter + 1])
            k_eff = min(max(k, 0), hi - lo)
            if k_eff == 0:
                return []
            fn = self._topk_fns.get(k_eff)
            if fn is None:
                fn = self._topk_fns[k_eff] = _make_topk(k_eff)
            pick, dfs = fn(self._d_df_order, self._d_df, np.int32(lo))
            art = self.artifact
            return [(art.term(int(i)), int(d))
                    for i, d in zip(np.asarray(pick), np.asarray(dfs))]

    def _bool_fn(self, op: str, T: int, width: int):
        fn = self._bool_fns.get((op, T, width))
        if fn is None:
            if self._fmt >= artifact_mod.VERSION_V2:
                fn = _make_bool_v2(op, width, self._block_size)
            else:
                fn = _make_bool(op, width)
            self._bool_fns[(op, T, width)] = fn
        return fn

    def _run_bool(self, op: str, uidx: np.ndarray) -> np.ndarray:
        """Shared AND/OR tail: pad the unique term set to a power of
        two (AND repeats the first run — intersection-neutral; OR pads
        empty runs — union-neutral), call the (op, T, W) kernel, slice
        the count."""
        self._note_decode(uidx)
        n = self._h_df[uidx].astype(np.int32)
        T = _next_pow2(len(uidx))
        if T != len(uidx):
            pad = T - len(uidx)
            if op == "and":
                uidx = np.concatenate([uidx, np.repeat(uidx[:1], pad)])
                n = np.concatenate([n, np.repeat(n[:1], pad)])
            else:
                uidx = np.concatenate([uidx, np.zeros(pad, np.int32)])
                n = np.concatenate([n, np.zeros(pad, np.int32)])
        width = self._tier(int(n.max()) if len(n) else 1)
        out, cnt = self._bool_fn(op, T, width)(
            *self._decode_cols, uidx.astype(np.int32), n)
        return np.asarray(out)[:int(cnt)].astype(np.int32)

    def query_and(self, batch) -> np.ndarray:
        with self._ops.time("and"):
            idx, found, _ = self._resolve(batch)
            if len(found) == 0 or not found.all():
                return np.zeros(0, dtype=np.int32)
            return self._run_bool("and", np.unique(idx))

    def query_or(self, batch) -> np.ndarray:
        with self._ops.time("or"):
            idx, found, _ = self._resolve(batch)
            uidx = np.unique(idx[found])
            if len(uidx) == 0:
                return np.zeros(0, dtype=np.int32)
            return self._run_bool("or", uidx)

    # -- ranked retrieval -----------------------------------------------

    def _bm25_device(self):
        """Upload the doc-length column + corpus scalars once."""
        if self._d_doc_lens is None:
            doc_lens, ndocs, avgdl = artifact_mod.bm25_corpus(
                self.artifact)
            rep = NamedSharding(self._mesh, P())
            self._d_doc_lens = jax.device_put(
                doc_lens.astype(np.float32), rep)
            self._bm25_scalars = (np.float32(ndocs), np.float32(avgdl))
        return self._d_doc_lens, self._bm25_scalars

    def _bm25_fn(self, T: int, width: int, k: int):
        fn = self._bm25_fns.get((T, width, k))
        if fn is None:
            if self._fmt >= artifact_mod.VERSION_V2:
                fn = _make_bm25_v2(width, k, self._block_size)
            else:
                fn = _make_bm25(width, k)
            self._bm25_fns[(T, width, k)] = fn
        return fn

    def _bm25_host_cols(self):
        """Float64 host mirror of the corpus stats (theta bootstrap)."""
        if self._bm25_host is None:
            self._bm25_host = artifact_mod.bm25_corpus(self.artifact)
        return self._bm25_host

    def _term_contribs(self, i: int) -> tuple:
        """``(docs, contrib, contrib_sorted_desc)`` for term ``i`` (f64,
        host) — the host engine's shared ``bm25_contrib``, so the exact
        rescoring below is bit-equal to ``Engine._term_scores``."""
        hit = self._score_memo.get(i)
        if hit is not None:
            return hit
        doc_lens, ndocs, avgdl = self._bm25_host_cols()
        art = self.artifact
        docs = art.decode_postings(i).astype(np.int64)
        tf = art.decode_tf(i).astype(np.float64)
        contrib = bm25_contrib(bm25_idf(len(docs), ndocs), tf,
                               doc_lens[docs], avgdl)
        if len(self._score_memo) >= self._memo_cap:
            self._score_memo.clear()
        self._score_memo[i] = (docs, contrib, np.sort(contrib)[::-1])
        return self._score_memo[i]

    def _exact_top_k(self, occ: list[int], k: int, D: int, run
                     ) -> list[tuple[int, float]]:
        """Exact BM25 top-k from the device's float32 candidates.

        ``run(kk)`` is the device's top-``kk`` ``(ids, vals)``.  The
        candidates are rescored in float64 on the host, summed in
        occurrence order like the host engine, so scores and ties are
        byte-equal to it.  The candidate set is complete once the exact
        k-th score clears the device's smallest returned score by more
        than float32 error (``_F32_REL``); until then ``kk`` grows."""
        kk = min(D, _next_pow2(k + 16))
        while True:
            ids, vals = (np.asarray(a) for a in run(kk))
            cand = ids[vals > 0.0].astype(np.int64)
            exact = np.zeros(len(cand), np.float64)
            for i in occ:
                docs, contrib, _ = self._term_contribs(i)
                pos = np.minimum(np.searchsorted(docs, cand),
                                 max(len(docs) - 1, 0))
                hit = docs[pos] == cand
                exact[hit] += contrib[pos[hit]]
            top = np.lexsort((cand, -exact))[:k]
            complete = (len(cand) < kk or kk >= D or (
                len(top) == k
                and exact[top[-1]] > float(vals[-1]) * (1.0 + _F32_REL)))
            if complete:
                return [(int(cand[j]), float(exact[j])) for j in top]
            kk = min(D, kk * 4)

    def _term_bounds(self, i: int) -> tuple:
        """(per-block f64 upper bounds, their max, idf) for term i."""
        hit = self._bound_memo.get(i)
        if hit is not None:
            return hit
        doc_lens, ndocs, avgdl = self._bm25_host_cols()
        idf = bm25_idf(int(self._h_df[i]), ndocs)
        ubs = planner_mod.block_upper_bounds(
            self.artifact, i, idf, avgdl, BM25_K1, BM25_B)
        if len(self._bound_memo) >= self._memo_cap:
            self._bound_memo.clear()
        self._bound_memo[i] = (
            ubs, float(ubs.max()) if len(ubs) else 0.0, idf)
        return self._bound_memo[i]

    def _top_k_scored_pruned(self, occ: list[int], k: int, mode: str
                             ) -> list[tuple[int, float]]:
        """Block-survivor form of pruned ranked retrieval: the host
        derives theta (the k-th best contribution of the strongest
        term) and keeps only blocks whose bound plus every other term's
        summed bounds clears it; the kernel decodes and scatter-adds
        exactly those blocks.  Every true top-k doc's blocks all
        survive (its total is a lower bound on every such test), so the
        returned doc set matches exhaustive scoring; partially-covered
        losers score strictly below the k-th best and cannot displace.
        ``maxscore`` masks whole terms, ``bmw`` masks per block."""
        art = self.artifact
        doc_lens_d, (_ndocs32, avgdl32) = self._bm25_device()
        D = int(doc_lens_d.shape[0])
        weight: dict[int, int] = {}
        for i in occ:
            weight[i] = weight.get(i, 0) + 1
        terms = [(i, w) + self._term_bounds(i)
                 for i, w in weight.items()]
        total = sum(w * umax for _i, w, _ubs, umax, _idf in terms)
        theta = 0.0
        for i, w, _ubs, _umax, _idf in terms:
            srt = self._term_contribs(i)[2]
            if len(srt) >= k:
                theta = max(theta, w * float(srt[k - 1]))
        coll = obs_attrib.active()
        if coll is not None:
            coll.theta(theta)
        margin = planner_mod.DEVICE_MARGIN
        bl_parts, widf_parts = [], []
        nb_total = 0
        for i, w, ubs, umax, idf in terms:
            b0 = int(art.term_block_off[i])
            nb = len(ubs)
            nb_total += nb * w
            rest = total - w * umax
            if mode == "maxscore":
                sel = np.arange(nb, dtype=np.int64) \
                    if w * umax + rest >= theta * margin \
                    else np.zeros(0, dtype=np.int64)
            else:
                sel = np.nonzero(w * ubs + rest >= theta * margin)[0]
            if not len(sel):
                continue
            # one survivor row per query occurrence: the scatter-add
            # then accumulates duplicates exactly like the exhaustive
            # kernel's duplicated term rows
            for _ in range(int(w)):
                bl_parts.append(sel + b0)
                widf_parts.append(
                    np.full(len(sel), np.float32(idf), np.float32))
        if not bl_parts:
            self._c_blocks_skipped.inc(nb_total)
            if coll is not None:
                coll.skipped(nb_total)
            self.planner.note_ranked(mode, 0, nb_total, 0)
            return []
        bl = np.concatenate(bl_parts).astype(np.int32)
        widf = np.concatenate(widf_parts)
        cnt = self.artifact.blk_cnt[bl].astype(np.int32)
        S = len(bl)
        nbytes = int((art.blk_woff[bl.astype(np.int64) + 1]
                      - art.blk_woff[bl]).sum()) * 4
        self._c_blocks_decoded.inc(S)
        self._c_blocks_skipped.inc(nb_total - S)
        self._c_bytes_decoded.inc(nbytes)
        if coll is not None:
            coll.decoded(S, nbytes)
            coll.skipped(nb_total - S)
        Sp = max(_MIN_LANES, _next_pow2(S))
        if Sp != S:
            bl = np.concatenate([bl, np.zeros(Sp - S, np.int32)])
            cnt = np.concatenate([cnt, np.zeros(Sp - S, np.int32)])
            widf = np.concatenate([widf, np.zeros(Sp - S, np.float32)])
        def run(kk: int):
            fn = self._blocks_fns.get((Sp, kk))
            if fn is None:
                fn = self._blocks_fns[(Sp, kk)] = _make_bm25_blocks(
                    kk, self._block_size)
            return fn(self._d_blk_first, self._d_blk_width,
                      self._d_blk_woff, self._d_post_words,
                      self._d_blk_tf_width, self._d_blk_tf_woff,
                      self._d_tf_words, bl, cnt, widf,
                      doc_lens_d, avgdl32)

        self.planner.note_ranked(mode, S, nb_total - S, 0)
        return self._exact_top_k(occ, k, D, run)

    def top_k_scored(self, batch, k: int) -> list[tuple[int, float]]:
        """BM25-ranked ``(doc_id, score)``, best first, ties by doc id —
        the device mirror of ``Engine.top_k_scored``: the device ranks
        in float32 and :meth:`_exact_top_k` rescores its candidates, so
        the answer is byte-equal to the host engine's.  On a
        v2.1 artifact the planner can swap the whole-term windows for a
        survivor-block window (:meth:`_top_k_scored_pruned`)."""
        with self._ops.time("top_k_scored"):
            idx, found, dfv = self._resolve(batch)
            doc_lens, (ndocs, avgdl) = self._bm25_device()
            D = int(doc_lens.shape[0])
            if k <= 0 or D == 0 or not found.any():
                if k > 0:
                    self.planner.note_ranked("exhaustive", 0, 0, 0)
                return []
            occ = [int(i) for i, ok in zip(idx, found) if ok]
            mode = self.planner.plan_ranked(
                self.artifact, [int(d) for d, ok in zip(dfv, found)
                                if ok], k)
            if mode != "exhaustive":
                return self._top_k_scored_pruned(occ, k, mode)
            self.planner.note_ranked("exhaustive", 0, 0, 0)
            self._note_decode(np.asarray(occ))
            # duplicates accumulate (host parity): keep the full batch,
            # padded to a power of two with never-found zero lanes
            T = _next_pow2(len(idx))
            if T != len(idx):
                pad = T - len(idx)
                idx = np.concatenate([idx, np.zeros(pad, np.int32)])
                found = np.concatenate([found, np.zeros(pad, bool)])
                dfv = np.concatenate([dfv, np.zeros(pad, np.int32)])
            n = np.where(found, dfv, 0).astype(np.int32)
            width = self._tier(int(n.max()) if len(n) else 1)
            if self._fmt >= artifact_mod.VERSION_V2:
                cols = self._decode_cols + (
                    self._d_blk_tf_width, self._d_blk_tf_woff,
                    self._d_tf_words)
            else:
                cols = self._decode_cols
            return self._exact_top_k(
                occ, k, D, lambda kk: self._bm25_fn(T, width, kk)(
                    *cols, idx.astype(np.int32), n, found, doc_lens,
                    ndocs, avgdl))

    # -- bookkeeping ----------------------------------------------------

    @property
    def cache(self) -> LRUCache:
        return self._cache

    def cache_stats(self) -> dict:
        return self._cache.stats()

    def op_stats(self) -> dict:
        return self._ops.stats()

    def compile_stats(self) -> dict:
        """Jit-cache census: the bench's zero-recompile assertion
        compares this before/after the steady-state run."""
        fns = ([self._lookup_fn] + list(self._decode_fns.values())
               + list(self._bool_fns.values())
               + list(self._topk_fns.values())
               + list(self._bm25_fns.values())
               + list(self._blocks_fns.values()))
        return {
            "jit_functions": len(fns),
            "jit_cache_entries": sum(f._cache_size() for f in fns),
        }

    def describe(self) -> dict:
        return {
            "engine": self.engine_name,
            "format": self._fmt,
            "vocab": self.vocab_size,
            "artifact_bytes": self.artifact.nbytes,
            "cache": self.cache_stats(),
            "ops": self.op_stats(),
            "planner": self.planner.describe(),
            "device": {
                **device.identity(),
                "shards": self._num_shards,
                "devices": self._placed_on,
                "tiers": self._tiers,
                "max_prefix_group": self._group,
                **self.compile_stats(),
            },
        }

    def close(self) -> None:
        self._cache.clear()
        self._d_key_hi = self._d_key_lo = self._d_rows = None
        self._d_df = self._d_post_offsets = self._d_postings = None
        self._d_df_order = self._d_doc_lens = None
        self._decode_cols = ()
        if self._fmt >= artifact_mod.VERSION_V2:
            self._d_term_block_off = self._d_blk_first = None
            self._d_blk_width = self._d_blk_woff = None
            self._d_post_words = self._d_blk_tf_width = None
            self._d_blk_tf_woff = self._d_tf_words = None
        self._decode_fns.clear()
        self._bool_fns.clear()
        self._topk_fns.clear()
        self._bm25_fns.clear()
        self._blocks_fns.clear()
        self._bm25_host = None
        self._score_memo.clear()
        self._bound_memo.clear()
        self.artifact.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

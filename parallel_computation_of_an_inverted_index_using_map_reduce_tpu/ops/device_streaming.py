"""Streaming all-device engine: raw byte windows in, bounded rows kept.

The one-shot all-device engine (ops/device_tokenizer.py) needs the
whole corpus byte tensor and its token-capacity arrays in HBM at once.
Here the corpus arrives in doc-aligned byte windows and the device
carries only the **unique (word, doc) rows seen so far**, each row the
``ceil(width/12)`` 30-bit (hi, lo) 5-bit-group code pairs that
``ops/device_tokenizer.tokenize_groups`` emits directly, plus the doc
id — bounded by the output's unique-pair count, not the stream length.
(``pack_groups`` survives only as the property-test reference for this
code layout; the hot path never materializes byte columns.)  The same
blockwise-accumulator discipline as the integer-pair streaming engine
(ops/streaming.py), lifted from packed ints to word rows, so the
"device scan" column of the engine matrix gets the same
larger-than-HBM story the host-scan engines have:

    per window:  rows  <- tokenize_groups ► sort ► dedup
                 acc   <- unique(merge_sort(acc, rows))

as fused XLA programs with static shapes and NO device->host sync in
the stream loop: the host bounds unique rows by the fed token count
(host_token_stats, already computed per window for tok_cap), growing
the accumulator by host-side doubling BEFORE a window that could
overflow it.  Group passes whose chars the stream has not seen yet are
skipped (the host's running max cleaned length is exact).

Exactness: rows are the actual cleaned bytes under an injective code
map — no hashing anywhere; a window whose max cleaned token exceeds
``width`` raises WidthOverflow BEFORE that window is fed and the model
restarts on the host path, so output stays byte-identical always
(main.c:105-111 / main.c:227-234 semantics, like every other engine).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import faults
from ..utils.rounding import round_up
from .device_tokenizer import (
    INT32_MAX,
    groups_sort_perm,
    live_groups_for,
    num_groups_for,
    tokenize_groups,
)
from .segment import first_occurrence_mask, set_bit_positions


def _row_first_mask(rows):
    """first-occurrence mask over sorted (group pairs…, doc) rows;
    rows[0] (group-0 hi) carries INT32_MAX on padding."""
    neq = first_occurrence_mask(rows[0])
    for r in rows[1:]:
        neq = neq | first_occurrence_mask(r)
    return neq & (rows[0] != INT32_MAX)


def _compact_rows(rows, mask, out_cap: int):
    """Set-bit-sort/gather compaction of row tuples (no scatters —
    ops/segment.py discipline); dropped slots become padding rows
    (INT32_MAX in every column, so later sorts still push them last)."""
    n = rows[0].shape[0]
    kept = set_bit_positions(mask, out_cap)
    live = kept != INT32_MAX
    pos = jnp.clip(kept, 0, n - 1)
    return tuple(jnp.where(live, r[pos], INT32_MAX) for r in rows)


@functools.partial(
    jax.jit,
    static_argnames=("width", "tok_cap", "num_docs", "sort_cols",
                     "num_groups", "out_cap"),
)
def window_rows(data, doc_ends, doc_id_values, *, width: int, tok_cap: int,
                num_docs: int, sort_cols: int, num_groups: int,
                out_cap: int):
    """One byte window -> its deduped (group rows…, doc) pairs.

    Returns ``(rows, counts)``: ``rows`` is ``2 * num_groups + 1``
    int32 arrays of length ``out_cap`` (compressed unique pairs first,
    INT32_MAX padding after), ``counts = [num_pairs, max_word_len,
    num_tokens]`` for the caller's divergence asserts (fetched lazily,
    never inside the stream loop).
    """
    groups, doc_col, max_word_len, num_tokens = tokenize_groups(
        data, doc_ends, doc_id_values, width=width, tok_cap=tok_cap,
        num_docs=num_docs, sort_cols=sort_cols)
    live = live_groups_for(sort_cols, width)
    perm = groups_sort_perm(groups[:live], doc_col, tok_cap)
    zero = jnp.zeros(tok_cap, jnp.int32)
    s_rows = tuple(
        g[perm] for pair in groups[:live] for g in pair
    ) + tuple([zero] * (2 * (num_groups - live))) + (doc_col[perm],)
    first = _row_first_mask(s_rows)
    rows = _compact_rows(s_rows, first, out_cap)
    counts = jnp.stack([first.sum(dtype=jnp.int32), max_word_len,
                        num_tokens])
    return rows, counts


@functools.partial(jax.jit, static_argnames=("cap", "live_groups"),
                   donate_argnums=(0,))
def _merge_unique_rows(acc, window, *, cap: int, live_groups: int):
    """Fold a window's row tuple into the sorted-unique accumulator;
    also returns the accumulator's true unique-row count (the host
    reads it two merges LATE, keeping two merges in flight).  "True"
    is exact, not an upper bound: _row_first_mask masks all-INT32_MAX
    padding rows, so no padding row counts as a first occurrence
    (pinned by tests/test_device_streaming.py::
    test_merge_count_is_exact_not_upper_bound).

    ``live_groups``: groups the stream has produced a nonzero char for
    so far (host-exact running max) — later groups are all zero in both
    operands except on padding rows, where every column is INT32_MAX,
    equal too; their sort passes are skipped, their dedup compares
    kept (cheap elementwise, robustness)."""
    cat = tuple(jnp.concatenate([a, w]) for a, w in zip(acc, window))
    doc = cat[-1]
    groups = [(cat[2 * g], cat[2 * g + 1]) for g in range(live_groups)]
    perm = groups_sort_perm(groups, doc, doc.shape[0])
    s_rows = tuple(r[perm] for r in cat)
    first = _row_first_mask(s_rows)
    return _compact_rows(s_rows, first, cap), first.sum(dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("cap",))
def _regrow_rows(acc, *, cap: int):
    """Copy row arrays into larger INT32_MAX-padded buffers."""
    def one(a):
        out = jnp.full((cap,), INT32_MAX, jnp.int32)
        return lax.dynamic_update_slice(out, a, (0,))
    return tuple(one(a) for a in acc)


@functools.partial(jax.jit, static_argnames=("pad",))
def _head_rows(acc, *, pad: int):
    """Static-size prefix of every accumulator column — the snapshot
    fetch moves only this instead of the full capacity (the cap can
    sit at ~2x the live count right after a doubling; at 1M-doc scale
    that slack is >100 MB of transfer).  ``pad`` is granule-
    rounded by the caller so the program count stays O(high-water /
    granule), not one per distinct live count."""
    return tuple(lax.slice(a, (0,), (pad,)) for a in acc)


def finalize_rows_body(acc, *, num_groups: int):
    """Traceable core of :func:`_finalize_rows` — also runs per shard
    inside the mesh streaming engine's ``shard_map`` finalize
    (parallel/dist_device_streaming.py), where each owner's
    accumulator is one independent row set.

    Every valid row is one unique (word, doc) pair and the rows are
    already in emit-ready lexicographic order, so: postings are the doc
    column's valid prefix verbatim; df falls out of the word-run edges;
    unique word rows return AS the 5-bit group pairs gathered at each
    run's first row — the host decodes them at vocab scale
    (ops/device_tokenizer.decode_word_groups), matching the one-shot
    engine's contract.
    """
    cap = acc[0].shape[0]
    doc = acc[-1]
    valid = acc[0] != INT32_MAX
    word_cols = acc[:-1]
    neq = first_occurrence_mask(word_cols[0])
    for r in word_cols[1:]:
        neq = neq | first_occurrence_mask(r)
    first_word = neq & valid
    num_words = first_word.sum(dtype=jnp.int32)
    num_pairs = valid.sum(dtype=jnp.int32)

    slots = jnp.arange(cap, dtype=jnp.int32)
    # word-start positions via the shared set-bit sort (segment.py);
    # W[cap] == cap keeps the df difference below always in range
    W = jnp.concatenate([
        jnp.minimum(set_bit_positions(first_word, cap), cap),
        jnp.full(1, cap, jnp.int32)])
    word_live = slots < num_words
    Wg = jnp.clip(W[:-1], 0, cap - 1).astype(jnp.int32)
    df = jnp.where(word_live, jnp.minimum(W[1:], num_pairs) - W[:-1], 0)
    postings = jnp.where(slots < num_pairs, doc, 0)

    groups = [(jnp.where(word_live, acc[2 * g][Wg], 0),
               jnp.where(word_live, acc[2 * g + 1][Wg], 0))
              for g in range(num_groups)]
    # >12-char word count so the sparse tail-group fetch can size its
    # transfer (device_tokenizer.fetch_pack contract)
    num_long = ((word_live & (groups[1][0] != 0)).sum(dtype=jnp.int32)
                if num_groups > 1 else jnp.int32(0))
    return {
        "counts": jnp.stack([num_words, num_pairs, num_long]),
        "df": df,
        "postings": postings,
        "unique_groups": tuple(groups),
    }


_finalize_rows = functools.partial(
    jax.jit, static_argnames=("num_groups",))(finalize_rows_body)


class DeviceStreamEngine:
    """Bounded-memory all-device reduction over a raw byte-window
    stream.  ``width`` fixes the row shape for the whole stream; the
    caller guards WidthOverflow per window BEFORE feeding (host-exact
    max cleaned length), so the accumulator never holds a truncated
    row.  ``window_pad`` rounds per-window token capacities so window
    programs reuse across similar windows.
    """

    def __init__(self, *, width: int, window_pad: int = 1 << 14,
                 initial_capacity: int = 1 << 16):
        self._width = width
        self._num_groups = num_groups_for(width)
        self._window_pad = window_pad
        self._cap = initial_capacity
        self._acc = None
        self._unique_bound = 0     # host bound on unique rows in acc
        # in-flight merges' (true-count handle, tokens folded) pairs,
        # oldest first; depth 2 keeps one merge always dispatchable
        # while the previous still runs (see feed)
        self._pending = []
        self._max_inflight = 2
        self._live_groups = 1      # running ceil(ceil(maxlen/4)/3)
        self.windows_fed = 0
        self.max_word_len = 0
        self._window_checks = []   # (counts_dev, tok_cap, host_max_len)
        # snapshot prefix-fetch rounding: bounds the number of distinct
        # _head_rows programs at high-water/granule while keeping the
        # over-fetch under one granule of rows per column
        self._snapshot_granule = 1 << 16
        # resolved unique-row counts in resolution order — the
        # accumulator GROWTH curve (trails windows_fed by the in-flight
        # merges; snapshot drains those, finalize leaves them): free
        # observability for scale artifacts, mirroring the host-stream
        # engines' vocab_curve
        self.rows_curve: list[int] = []

    @property
    def capacity(self) -> int:
        return self._cap

    @property
    def snapshot_nbytes(self) -> int:
        """Bytes a :meth:`snapshot` would fetch over the link right
        now: a granule-padded valid-prefix of every int32 column (the
        host bound on unique rows stands in for the drained count).
        Callers use this to project the snapshot tax before paying it
        — at 1M-doc scale an accumulator snapshot is hundreds of MB
        (VERDICT r4 weak #3)."""
        if self._acc is None:
            return 0
        # snapshot() drains the in-flight merges BEFORE fetching, so
        # project from the last resolved true count, not the pending-
        # inflated capacity bound: _unique_bound carries every pending
        # window's whole token count (worst case all-unique), which at
        # streaming scale overstates the fetch by windows' worth of
        # tokens and makes the budget loop skip affordable snapshots.
        drained_bound = self._unique_bound - sum(
            tc for _, tc in self._pending)
        pad = min(round_up(max(drained_bound, 1),
                           self._snapshot_granule), self._cap)
        return (2 * self._num_groups + 1) * pad * 4

    def _ensure_capacity(self, extra: int) -> None:
        self._unique_bound += extra
        while self._unique_bound > self._cap:
            self._cap *= 2
            if self._acc is not None:
                self._acc = _regrow_rows(self._acc, cap=self._cap)

    def feed(self, buf: np.ndarray, ends: np.ndarray, ids: np.ndarray,
             *, tok_count: int, max_len: int, stage_hook=None) -> None:
        """Tokenize one padded byte window on device and fold its
        unique rows into the accumulator.  ``tok_count`` / ``max_len``
        are the window's host-exact stats (host_token_stats) — the
        caller has already rejected ``max_len > width``.

        ``stage_hook(name, device_value)``, when given, is called after
        each stage (``upload``, ``window_rows``, ``merge``) with a
        device value the hook can fetch-barrier on — so stage
        attribution tooling (tools/profile_stream_stages.py) times the
        PRODUCTION path instead of a re-implementation that drifts
        (advisor r4).  A hooked feed also resolves every in-flight
        merge count at the end (serialized semantics: the 2-deep
        pipeline is exactly what the hook's barriers suppress), keeping
        the capacity-growth path identical to a resolved-count run.
        Production callers pass nothing and pay nothing."""
        if tok_count == 0:
            return
        self.max_word_len = max(self.max_word_len, max_len)
        sort_cols = -(-max(self.max_word_len, 1) // 4)
        self._live_groups = max(self._live_groups,
                                live_groups_for(sort_cols, self._width))
        tok_cap = round_up(tok_count + 1, self._window_pad)
        out_cap = round_up(min(tok_count, tok_cap), self._window_pad)
        d_buf = jax.device_put(buf)
        d_ends = jax.device_put(ends)
        d_ids = jax.device_put(ids)
        if stage_hook is not None:
            # all three uploads: barriering d_buf alone lets the ends /
            # ids transfers leak into the next stage's measured time
            stage_hook("upload", (d_buf, d_ends, d_ids))
        rows, counts = window_rows(
            d_buf, d_ends, d_ids,
            width=self._width, tok_cap=tok_cap, num_docs=ends.shape[0],
            sort_cols=sort_cols, num_groups=self._num_groups,
            out_cap=out_cap)
        counts.copy_to_host_async()
        self._window_checks.append((counts, tok_cap, max_len))
        if stage_hook is not None:
            stage_hook("window_rows", counts)
        # tighten the host bound against resolved merge counts, read
        # TWO merges late: resolving merge i-2 before dispatching
        # merge i keeps two merges in flight (the previous count sync
        # serialized the stream — each window paid a full link RTT
        # with the device idle during the host scan).  The bound stays
        # provably safe: true count of the last RESOLVED merge plus
        # every token folded by the still-unresolved ones — unique
        # rows + two windows' tokens, never the stream length (the
        # module's bounded-memory claim).
        while len(self._pending) >= self._max_inflight:
            handle, _ = self._pending.pop(0)
            resolved = int(np.asarray(handle))
            self.rows_curve.append(resolved)
            self._unique_bound = (resolved
                                  + sum(tc for _, tc in self._pending))
        self._ensure_capacity(tok_count)
        if self._acc is None:
            pad = np.full(self._cap, INT32_MAX, np.int32)
            self._acc = tuple(
                jax.device_put(pad) for _ in range(2 * self._num_groups + 1))
        self._acc, pending_count = _merge_unique_rows(
            self._acc, rows, cap=self._cap, live_groups=self._live_groups)
        pending_count.copy_to_host_async()
        self._pending.append((pending_count, tok_count))
        self.windows_fed += 1
        # fault hook (faults.py stream-crash:window=K): raise AFTER this
        # window's merge is dispatched but before any later checkpoint —
        # the worst-case crash position for the resume contract
        inj = faults.active()
        if inj is not None:
            inj.on_stream_window(self.windows_fed)
        if stage_hook is not None:
            stage_hook("merge", pending_count)
            while self._pending:
                handle, _ = self._pending.pop(0)
                self._unique_bound = int(np.asarray(handle))
                self.rows_curve.append(self._unique_bound)

    def _verify_window_checks(self) -> None:
        """Fetch + verify the accumulated per-window device stats
        against the host classifier (shared by finalize and snapshot —
        a snapshot must not persist an unverified prefix)."""
        for counts_dev, tok_cap, host_max_len in self._window_checks:
            _pairs, dev_max_len, dev_tokens = (
                int(v) for v in np.asarray(counts_dev))
            if dev_tokens + 1 > tok_cap:
                raise AssertionError(
                    f"device token count {dev_tokens} exceeded tok_cap "
                    f"{tok_cap}: host mask count diverged from the "
                    "device classifier (bug)")
            if dev_max_len != host_max_len:
                raise AssertionError(
                    f"device max word len {dev_max_len} != host "
                    f"{host_max_len}: classifier divergence (bug)")
        self._window_checks = []

    def snapshot(self) -> dict | None:
        """Verified host snapshot of the stream state — the durable
        form of the reference's spill files (main.c:332-341, which
        persist after the run and make the reduce phase re-runnable;
        SURVEY.md §5 checkpoint row).

        Drains the in-flight merges (paying the pipeline depth once),
        verifies every window fed so far, then fetches the accumulator
        and keeps only the valid row prefix.  Returns ``None`` when
        nothing has been fed.  The engine stays live — streaming
        continues after a snapshot.
        """
        if self._acc is None:
            return None
        while self._pending:
            handle, _ = self._pending.pop(0)
            self._unique_bound = int(np.asarray(handle))
            self.rows_curve.append(self._unique_bound)
        self._verify_window_checks()
        count = self._unique_bound
        # fetch only a granule-padded prefix: every valid row sits in
        # acc[:count] (merges compact valid rows first), and the cap
        # can be ~2x count right after a doubling — slack worth >100 MB
        # at 1M-doc scale
        pad = min(round_up(max(count, 1), self._snapshot_granule),
                  self._cap)
        heads = (_head_rows(self._acc, pad=pad) if pad < self._cap
                 else self._acc)
        cols = jax.device_get(heads)
        return {
            "width": self._width,
            # bytes this fetch actually moved — the budget loop
            # calibrates its link rate from this, NOT from the pre-
            # drain snapshot_nbytes projection (whose pending-inflated
            # bound can overstate the transfer and inflate the rate)
            "fetched_nbytes": (2 * self._num_groups + 1) * pad * 4,
            "count": count,
            "cap": self._cap,
            "live_groups": self._live_groups,
            "max_word_len": self.max_word_len,
            "windows_fed": self.windows_fed,
            "rows_curve": list(self.rows_curve),
            "columns": [np.asarray(c[:count]) for c in cols],
        }

    def restore(self, state: dict) -> None:
        """Rebuild the device accumulator from :meth:`snapshot` output.
        The engine must be freshly constructed with the same ``width``."""
        if self._acc is not None or self.windows_fed:
            raise ValueError("restore() requires a fresh engine")
        if state["width"] != self._width:
            raise ValueError(
                f"checkpoint width {state['width']} != engine width "
                f"{self._width}")
        ncols = 2 * self._num_groups + 1
        if len(state["columns"]) != ncols:
            raise ValueError(
                f"checkpoint has {len(state['columns'])} row columns, "
                f"engine width {self._width} needs {ncols}")
        count = int(state["count"])
        cap = int(state["cap"])
        if count > cap:
            raise ValueError(
                f"checkpoint count {count} exceeds its capacity {cap}: "
                "truncated or corrupt stream checkpoint")
        for i, c in enumerate(state["columns"]):
            if len(c) != count:
                raise ValueError(
                    f"checkpoint column {i} holds {len(c)} rows, header "
                    f"says {count}: truncated or corrupt stream checkpoint")
        self._cap = cap
        cols = []
        for c in state["columns"]:
            buf = np.full(self._cap, INT32_MAX, np.int32)
            buf[:count] = c
            cols.append(jax.device_put(buf))
        self._acc = tuple(cols)
        self._unique_bound = count
        self._live_groups = int(state["live_groups"])
        self.max_word_len = int(state["max_word_len"])
        self.windows_fed = int(state["windows_fed"])
        # pre-crash growth history, so a resumed run's reported curve
        # covers the WHOLE stream (absent in checkpoints written
        # before the key existed)
        self.rows_curve = [int(v) for v in state.get("rows_curve", [])]
        self._pending = []
        self._window_checks = []

    def finalize(self):
        """Device dict with the one-shot engine's output contract
        (counts / df / postings / unique_groups valid prefixes).

        Re-checks every window's device-computed stats against the
        host classifier here — ONE lazy fetch per window, all outside
        the stream loop — so host/device divergence fails as loudly as
        the one-shot engine's asserts instead of silently truncating.
        """
        if self._acc is None:
            raise ValueError("no windows fed")
        self._verify_window_checks()
        out = _finalize_rows(self._acc, num_groups=self._num_groups)
        self._acc = None
        self._pending = []
        return out

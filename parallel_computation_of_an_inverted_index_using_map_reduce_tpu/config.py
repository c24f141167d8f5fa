"""Framework configuration.

The reference exposes exactly three positional CLI args — num_mappers,
num_reducers, input list (main.c:248-255) — plus compile-time caps
(main.c:7-11).  Here those become an explicit, validated config object;
mapper/reducer counts map onto host shards and device hash buckets.
"""

from __future__ import annotations

import dataclasses


# Reference compile-time caps (main.c:7-11).  MAX_WORD bounds the *cleaned*
# token: the reference keeps at most MAX_WORD-1 = 299 letters per token
# (main.c:105 loop guard `j < MAX_WORD - 1`).
MAX_WORD_LETTERS = 299
ALPHABET_SIZE = 26


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """End-to-end pipeline configuration.

    ``num_mappers`` / ``num_reducers`` keep the reference CLI's meaning as
    *host shard count* and *reduce partition count*; on device, the work is
    balanced by sort/hash regardless (the reference's 1000x letter skew,
    SURVEY.md §2.3, does not survive the redesign).
    """

    # CLI-compat knobs.  The reference's output is invariant to its thread
    # counts (SURVEY.md §2.3 determinism), and so is ours: ``num_mappers``
    # sets the host map-phase thread count when ``host_threads`` is unset
    # (the reference's mapper threads, main.c:348-365, re-expressed —
    # byte-identical output at any count).  On ``backend="cpu"`` with
    # read-ahead on, that count is K scan workers, each with its own
    # arena ring + reader + incremental native handle, pulling byte
    # windows from a shared steal queue; ``num_reducers`` is then M
    # reducer threads owning contiguous letter ranges
    # (corpus/scheduler.plan_letter_ranges — the reference's reducer
    # ownership, main.c:129-130) over the merged vocabulary.  On device,
    # reduce is balanced by sort/hash regardless, so the reference's
    # 1000x letter skew (SURVEY.md §2.3) cannot recur.
    num_mappers: int = 1
    num_reducers: int = 1
    # "tpu"    — device engine (jit sort pipeline; pipelined/one-shot plans)
    # "cpu"    — whole pipeline in one native C++ call, no accelerator
    #            (the reference's all-on-host regime without its
    #            pathologies; falls back to "oracle" if g++ is absent)
    # "oracle" — pure-Python dict oracle, the conformance seam
    backend: str = "tpu"
    output_dir: str = "."         # where a.txt .. z.txt are written
    # Pad token-count up to a multiple of this so XLA re-uses compiled
    # programs across similarly-sized corpora instead of recompiling.
    pad_multiple: int = 1 << 16
    # Device shards for the multi-chip engine (parallel/dist_engine.py):
    # None = all visible devices; 1 = force the single-chip engine.
    device_shards: int | None = None
    profile_dir: str | None = None  # write a jax.profiler trace of the device phase
    # Host tokenizer: C++ (native/tokenizer.cc, built on first use) with
    # automatic fallback to the vectorized numpy path.
    use_native: bool = True
    # Durable map-phase artifact (the analogue of the reference's spill
    # files, which double as a checkpoint — SURVEY.md §5): save the
    # tokenized pair arrays here, and resume from them if present.
    checkpoint_path: str | None = None
    # Measure shuffle-partition skew on device (utils/stats.py): letter
    # partitioning vs hash buckets.  Off the hot path; adds a device
    # round-trip, so opt-in.
    collect_skew_stats: bool = False
    # Streaming mode (SURVEY.md §5 long-context): process the corpus in
    # windows of this many whole documents with a bounded device
    # accumulator (ops/streaming.py) instead of one-shot arrays.  None =
    # single-shot.  Output is byte-identical either way.
    stream_chunk_docs: int | None = None
    # Single-chip pipelined fast path (native tokenizer + provisional-key
    # device sort): documents per upload window.  None = auto (two windows:
    # window 1's upload overlaps window 2's tokenize); 0 disables the
    # pipelined path entirely (forces the one-shot engine).
    pipeline_chunk_docs: int | None = None
    # Windowed overlap plan (single-chip pipelined variant for
    # high-latency host<->device links): this fraction of corpus bytes —
    # the LAST contiguous doc range — is indexed on the host (numpy sort
    # of its packed keys) while the earlier windows' device sorts and
    # async fetches are still in flight, so the device round-trip
    # latency hides under host work instead of serializing after it.
    # Emit concatenates the per-window runs in doc order (no merge
    # pass).  None = disabled (plain pipelined plan); must be in (0, 1).
    overlap_tail_fraction: float | None = None
    # Device windows for the overlap plan: 2 issues the first fetch
    # earlier; 1 halves the dispatch RPCs (wins when per-call link
    # overhead dominates the hidden round trip).
    overlap_device_windows: int = 2
    # Byte split between the two device windows (first window's share
    # of the device fraction).  The fetch wait left after the scan is
    # proportional to the LAST window's bytes (its fetch is issued
    # latest), so a larger first window shrinks the residual — at the
    # cost of issuing that bigger upload later into the scan.  A grid
    # probe, like the tail fraction.
    overlap_window_split: float = 0.55
    # Device-side tokenizer (ops/device_tokenizer.py): raw corpus bytes
    # go up, the finished index comes down — the ENTIRE map phase (byte
    # classify, token segmentation, cleaning, dedup, df, postings) as
    # one XLA program; no host scan at all.  Exact (no hashing): words
    # live as fixed-width byte rows sorted lexicographically; a cleaned
    # token longer than ``device_tokenize_width`` aborts to the host
    # path (WidthOverflow), keeping output byte-identical always.
    # Single chip; wins where the host<->device link is cheap (local
    # PCIe) — on a high-RTT link the host-scan plans win end-to-end.
    device_tokenize: bool = False
    # Word-row width in bytes (multiple of 4; >= the longest cleaned
    # token or the run falls back).  48 covers real text with margin
    # (reference corpus max: 38 letters).
    device_tokenize_width: int = 48
    # Host map-phase threads: the native tokenizer's fork-join worker
    # count AND the pipelined cpu path's scan-worker count (one arena
    # ring + reader + native handle per worker, windows from a shared
    # steal queue; merged at vocab scale — output-identical at any
    # count).  None = ``num_mappers`` if > 1, else auto (min(cores, 8)).
    host_threads: int | None = None
    # Crash-resumable streaming for the single-chip all-device engine:
    # persist the bounded row accumulator's VERIFIED valid prefix plus
    # the stream position here every ``stream_checkpoint_every``
    # windows (utils/checkpoint.save_stream_state, atomic).  A rerun
    # with the same manifest + stream config resumes at the last
    # checkpointed window instead of restarting — the durable-spill
    # role of the reference's partial_<letter>.txt files
    # (main.c:332-341), which survive a crash and make the remaining
    # work re-runnable.  Motivated by a real failure: the round-3
    # 1M-doc on-chip run lost ~9 minutes of stream to a TPU worker
    # crash (round 3).
    stream_checkpoint: str | None = None
    stream_checkpoint_every: int = 2
    # Checkpoint-trust policy at resume time:
    #   "strict" — a corrupt checkpoint file is a hard error
    #              (utils/checkpoint.CheckpointCorrupt); mismatched
    #              version/fingerprint stays a hard error in both modes
    #   "auto"   — a corrupt file is quarantined to ``<path>.corrupt``
    #              and the run restarts fresh (crash-safe auto-resume:
    #              a SIGKILL mid-save must never wedge the rerun)
    resume: str = "strict"
    # Letter-file writer:
    #   "auto"   — native vectorized emit (tokenizer.cc EmitLettersRuns:
    #              pre-rendered id strings, single-allocation render,
    #              atomic tmp+rename per letter) when the library is
    #              loadable, else the pure-Python formatter
    #   "native" — require the native path (error if unavailable)
    #   "python" — force the pure-Python formatter (the byte-parity
    #              oracle; same atomic write contract)
    # Output is byte-identical across all three.  backend="cpu" fuses
    # scan and emit inside one native call, so this knob governs the
    # device engines' emit tail; an all-Python cpu run is use_native=False
    # (the oracle).
    emit_backend: str = "auto"
    # Read-ahead depth for the host pipeline (backend="cpu"): the reader
    # thread keeps up to this many ~2 MB window arenas filled while the
    # native scan (GIL released) chews the current one.  0 disables the
    # pipelined ingest path (one-shot load + native call).
    io_prefetch: int = 2
    # Integrity audit (audit.py): per-window feed ledger + merge
    # invariant checks before emit on the parallel host path, and an
    # ``index.manifest.json`` output manifest (per-letter-file md5)
    # written after every emit — ``--verify`` re-checks it later.
    # Recovery bugs surface as AuditError (exit 2), never as silently
    # wrong bytes.  Cheap (<5% of cpu_ms; bench tracks ``audit_ms``),
    # but off by default to keep the measured hot path exact.
    audit: bool = False
    # Emit-side ownership for the multi-chip pipelined path:
    #   "merged" — one host assembles and writes all 26 files (default)
    #   "letter" — pairs are exchanged by *letter owner*
    #              (corpus/scheduler.plan_letter_ranges — the reference's
    #              reducer ownership, main.c:129-150) and each owner
    #              emits only its own letter files; no global merge
    #              anywhere.  The multi-host emit strategy.
    emit_ownership: str = "merged"
    # Serving artifact (serve/artifact.py): pack a compact mmap-able
    # ``index.mri`` next to the letter files at emit time, so the query
    # engine (``mri-tpu query``, serve.Engine) never re-parses text.
    # Needs the merged postings on one host: incompatible with the
    # letter-ownership emit and the overlap plan's split emit.
    artifact: bool = False
    # Chrome trace_event export (obs.chrometrace): write the run's
    # per-stage timeline — reader windows, per-worker scans, reducer
    # emit ranges, merge, artifact pack — to this file after the build,
    # loadable in chrome://tracing / Perfetto.  Host pipeline only; the
    # oracle and tpu backends write a valid but sparse trace.
    trace_out: str | None = None

    def resolved_host_threads(self) -> int:
        """The map-phase thread count this run will actually use."""
        if self.host_threads is not None:
            return self.host_threads
        if self.num_mappers > 1:
            return self.num_mappers
        from .native import default_threads

        return default_threads()

    def __post_init__(self) -> None:
        if self.num_mappers < 1:
            raise ValueError(f"num_mappers must be >= 1, got {self.num_mappers}")
        if self.num_reducers < 1:
            raise ValueError(f"num_reducers must be >= 1, got {self.num_reducers}")
        if self.backend not in ("tpu", "cpu", "oracle"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.pad_multiple < 1:
            raise ValueError("pad_multiple must be >= 1")
        if self.device_shards is not None and self.device_shards < 1:
            raise ValueError(
                f"device_shards must be >= 1 or None (auto), got {self.device_shards}")
        if self.backend != "tpu":
            # device-era options the host backends do not implement: fail
            # loudly rather than silently ignore a flag the user passed
            for flag in ("stream_chunk_docs", "checkpoint_path", "profile_dir"):
                if getattr(self, flag) is not None:
                    raise ValueError(
                        f"{flag} requires backend='tpu', got backend={self.backend!r}")
            if self.collect_skew_stats:
                raise ValueError(
                    "collect_skew_stats requires backend='tpu', "
                    f"got backend={self.backend!r}")
        if self.pipeline_chunk_docs is not None and self.pipeline_chunk_docs < 0:
            raise ValueError(
                "pipeline_chunk_docs must be >= 1, 0 (disabled) or None (auto), "
                f"got {self.pipeline_chunk_docs}")
        if self.backend not in ("tpu",) and self.pipeline_chunk_docs is not None:
            raise ValueError(
                f"pipeline_chunk_docs requires backend='tpu', got backend={self.backend!r}")
        if self.overlap_tail_fraction is not None:
            if not 0.0 < self.overlap_tail_fraction < 1.0:
                raise ValueError(
                    "overlap_tail_fraction must be in (0, 1) or None, "
                    f"got {self.overlap_tail_fraction}")
            if self.backend != "tpu":
                raise ValueError(
                    "overlap_tail_fraction requires backend='tpu', "
                    f"got backend={self.backend!r}")
            if self.pipeline_chunk_docs == 0:
                raise ValueError(
                    "overlap_tail_fraction requires the pipelined path "
                    "(pipeline_chunk_docs=0 disables it)")
            if self.stream_chunk_docs is not None:
                raise ValueError(
                    "overlap_tail_fraction is incompatible with "
                    "stream_chunk_docs (the streaming engine has its own "
                    "window pipeline)")
            if self.emit_ownership == "letter":
                raise ValueError(
                    "overlap_tail_fraction is single-chip; "
                    "emit_ownership='letter' is the multi-chip emit path")
        if self.artifact:
            if self.emit_ownership == "letter":
                raise ValueError(
                    "artifact requires the merged emit (one host holds "
                    "the global postings); emit_ownership='letter' "
                    "splits them across owners")
            if self.overlap_tail_fraction is not None:
                raise ValueError(
                    "artifact is incompatible with overlap_tail_fraction "
                    "(the overlap plan emits from two disjoint partial "
                    "indexes, never materializing merged postings)")
        if self.overlap_device_windows not in (1, 2):
            raise ValueError(
                f"overlap_device_windows must be 1 or 2, "
                f"got {self.overlap_device_windows}")
        if not (0.0 < self.overlap_window_split < 1.0):
            raise ValueError(
                f"overlap_window_split must be in (0, 1), "
                f"got {self.overlap_window_split}")
        # upper bound 296 (< MAX_WORD_LETTERS): a width that could hold
        # a 299+-letter token would silently skip the reference's 299
        # cap (main.c:105) instead of falling back to the host path
        if not (4 <= self.device_tokenize_width <= 296
                and self.device_tokenize_width % 4 == 0):
            raise ValueError(
                "device_tokenize_width must be a multiple of 4 in [4, 296], "
                f"got {self.device_tokenize_width}")
        if self.device_tokenize:
            if self.backend != "tpu":
                raise ValueError(
                    "device_tokenize requires backend='tpu', "
                    f"got backend={self.backend!r}")
            for flag in ("checkpoint_path",
                         "pipeline_chunk_docs", "overlap_tail_fraction"):
                if getattr(self, flag) is not None:
                    raise ValueError(
                        f"device_tokenize is a complete engine; {flag} "
                        "belongs to the host-scan plans")
            if self.collect_skew_stats:
                raise ValueError(
                    "device_tokenize is incompatible with collect_skew_stats "
                    "(no host-side pair ids exist)")
            # letter + stream_chunk_docs is rejected by the general
            # emit_ownership='letter' block below
        if self.host_threads is not None and self.host_threads < 1:
            raise ValueError(
                f"host_threads must be >= 1 or None (auto), got {self.host_threads}")
        if self.emit_backend not in ("auto", "native", "python"):
            raise ValueError(
                f"emit_backend must be 'auto', 'native' or 'python', "
                f"got {self.emit_backend!r}")
        if self.io_prefetch < 0:
            raise ValueError(
                f"io_prefetch must be >= 0 (0 disables read-ahead), "
                f"got {self.io_prefetch}")
        if self.emit_ownership not in ("merged", "letter"):
            raise ValueError(
                f"emit_ownership must be 'merged' or 'letter', got {self.emit_ownership!r}")
        if self.emit_ownership == "letter":
            if self.backend != "tpu":
                raise ValueError(
                    f"emit_ownership='letter' requires backend='tpu', "
                    f"got backend={self.backend!r}")
            if self.stream_chunk_docs is not None:
                raise ValueError(
                    "emit_ownership='letter' requires the pipelined multi-chip "
                    "path (incompatible with stream_chunk_docs)")
            if self.pipeline_chunk_docs == 0:
                raise ValueError(
                    "emit_ownership='letter' requires the pipelined multi-chip "
                    "path (pipeline_chunk_docs=0 disables it)")
        if self.resume not in ("strict", "auto"):
            raise ValueError(
                f"resume must be 'strict' or 'auto', got {self.resume!r}")
        if self.stream_checkpoint_every < 1:
            raise ValueError(
                f"stream_checkpoint_every must be >= 1, "
                f"got {self.stream_checkpoint_every}")
        if self.stream_checkpoint is not None:
            if not (self.device_tokenize
                    and self.stream_chunk_docs is not None):
                raise ValueError(
                    "stream_checkpoint requires the streaming all-device "
                    "engine (device_tokenize=True with stream_chunk_docs)")
            if self.device_shards != 1:
                raise ValueError(
                    "stream_checkpoint is single-chip only: pass "
                    "device_shards=1 explicitly (None routes to the mesh "
                    "streaming engine when several devices are visible, "
                    f"which has no checkpoint); got {self.device_shards}")
        if self.stream_chunk_docs is not None:
            if self.stream_chunk_docs < 1:
                raise ValueError(
                    f"stream_chunk_docs must be >= 1 or None, got {self.stream_chunk_docs}")
            # options the windowed pipeline does not implement: fail
            # loudly rather than silently ignore a flag the user passed
            if self.checkpoint_path is not None:
                raise ValueError(
                    "stream_chunk_docs is incompatible with checkpoint_path "
                    "(the accumulator itself is the evolving map-phase state)")
            if self.collect_skew_stats:
                raise ValueError(
                    "stream_chunk_docs is incompatible with collect_skew_stats "
                    "(per-window pair ids are discarded after each merge)")
            # device_shards > 1 routes to the distributed streaming
            # accumulator (parallel/dist_streaming.py)

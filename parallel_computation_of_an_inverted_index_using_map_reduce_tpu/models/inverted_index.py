"""The flagship model: end-to-end inverted-index pipeline.

Orchestrates the full chain the reference runs as fork-join pthread
phases (main.c:246-390):

    manifest -> load docs -> tokenize (host) -> index (device) -> emit (host)

with backends:
    "tpu"    — sorted-vocab ids + packed-key device engine (ops/engine.py)
    "oracle" — pure-Python dict oracle (models/oracle.py)

Output is byte-identical across backends and to the pthread reference
(conformance tests in tests/).
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
import time

import numpy as np

import jax

from .. import faults
from ..config import IndexConfig
from ..parallel import dist_engine
from ..parallel.mesh import make_mesh, replicated_spec, shard_spec, sharding
from ..utils import checkpoint
from ..utils import device, envknobs
from ..corpus.manifest import Manifest, load_documents
from ..ops import engine
from ..ops import keys as K
from ..text import formatter
from ..text.tokenizer import tokenize
from ..utils.rounding import round_up as _round_up
from ..obs import chrometrace
from ..utils.timing import PhaseTimer
from .oracle import oracle_index

log = logging.getLogger("mri_tpu.model")


def _profile_ctx(profile_dir):
    """One shared jax.profiler gate for every runner (8 call sites)."""
    return (jax.profiler.trace(profile_dir) if profile_dir
            else contextlib.nullcontext())


def _pack_window(contents, ids, shard_len: int, docs_cap: int):
    """Pack one doc window into the device byte-feed layout:
    ``(buf[shard_len] space-padded, ends[docs_cap], ids[docs_cap])``.
    One join + one copy — no per-doc python loop (the loop was ~1 us a
    doc, real money at 1M-doc streaming scale).  Padded ``ends``
    entries stay at ``shard_len``: the pad region is all spaces, so
    those "docs" emit nothing.

    Always fresh arrays: an upload and the window program that reads it
    run asynchronously (on the CPU backend ``device_put`` may alias the
    host array), so refilling a buffer an earlier window handed to the
    device can corrupt that window's input."""
    joined = b"".join(contents)
    buf = np.full(shard_len, 0x20, np.uint8)
    ends = np.full(docs_cap, shard_len, np.int32)
    idv = np.full(docs_cap, 1, np.int32)
    buf[: len(joined)] = np.frombuffer(joined, np.uint8)
    if contents:
        lens = np.fromiter((len(c) for c in contents), np.int64,
                           len(contents))
        ends[: len(contents)] = np.cumsum(lens).astype(np.int32)
    idv[: len(ids)] = np.asarray(ids, np.int32)
    return buf, ends, idv


class InvertedIndexModel:
    """Reusable pipeline object (compiled engine state is cached by jit).

    ``run`` is re-entrant: each call gets a fresh timer; ``self.timer``
    holds the most recent run's.
    """

    def __init__(self, config: IndexConfig | None = None):
        self.config = config or IndexConfig()
        self.timer = PhaseTimer()

    def run(self, manifest: Manifest, output_dir: str | None = None) -> dict:
        # One degradation report per run: every read path below records
        # retries and skipped documents into it, and the summary rides
        # the stats dict into the CLI (exit faults.EXIT_DEGRADED when
        # documents were skipped) and the bench JSON.
        report = faults.begin_run()
        # Chrome trace_event export (--trace-out): one collector per
        # run; the host pipeline records per-stage spans into it and
        # the file is written once, after the run (non-cpu backends
        # produce a valid but sparse trace).
        trace = None
        if self.config.trace_out:
            trace = chrometrace.TraceEvents()
            trace.name_thread(chrometrace.MAIN, "main")
        self._trace = trace
        engine.dedup_kernel = None  # set again if this run traces it
        stats = self._run_dispatch(manifest, output_dir)
        if self.config.backend == "tpu":
            stats.update(device.identity())
            if engine.dedup_kernel is not None:
                stats["dedup_kernel"] = engine.dedup_kernel
        if trace is not None:
            trace.write(self.config.trace_out)
            stats["trace_out"] = self.config.trace_out
        if self.config.audit:
            # Output manifest AFTER emit (any backend): per-letter-file
            # digests so --verify can re-check the directory later.
            # Manifest time counts toward audit_ms — the audit layer's
            # whole cost must be measurable, not guessed.
            from .. import audit as audit_mod

            out_dir = (output_dir if output_dir is not None
                       else self.config.output_dir)
            t0 = time.perf_counter()
            audit_mod.write_output_manifest(out_dir)
            stats["audit_ms"] = round(
                stats.get("audit_ms", 0.0)
                + (time.perf_counter() - t0) * 1e3, 3)
        stats["degradation"] = report.summary()
        if report.degraded or report.worker_recoveries \
                or report.reducer_takeovers:
            report.log_summary()
        return stats

    def _artifact_path(self, out_dir) -> str | None:
        """Where ``--artifact`` packs the serving index (None when off)."""
        if not self.config.artifact:
            return None
        from ..serve import artifact as artifact_mod

        return str(artifact_mod.artifact_path(out_dir))

    def _run_dispatch(self, manifest: Manifest,
                      output_dir: str | None = None) -> dict:
        cfg = self.config
        self.timer = timer = PhaseTimer()
        # Reference-CLI knobs, recorded as config.py promises (the
        # reference logs its mapper ranges at main.c:327).
        timer.count("num_mappers", cfg.num_mappers)
        timer.count("num_reducers", cfg.num_reducers)
        out_dir = output_dir if output_dir is not None else cfg.output_dir
        if cfg.backend == "oracle":
            with timer.phase("oracle"):
                stats = oracle_index(
                    manifest, out_dir,
                    artifact_path=self._artifact_path(out_dir))
            return {**stats, **timer.report()}
        if cfg.backend == "cpu":
            return self._run_cpu(manifest, out_dir, timer)
        if cfg.stream_chunk_docs is not None and not cfg.device_tokenize:
            return self._run_tpu_streaming(manifest, out_dir, timer)
        return self._run_tpu(manifest, out_dir, timer)

    # -- CPU backend ---------------------------------------------------

    def _run_cpu(self, manifest: Manifest, out_dir: str, timer: PhaseTimer) -> dict:
        """All-on-host engine, pipelined read → tokenize → emit.

        The reference's regime — CPU only — re-architected: no spill
        files, no locks, no token-scale sorts.  Default path: a reader
        thread fills reusable window arenas (io.executor) while the
        GIL-releasing incremental scan (native.HostIndexStream) chews
        the previous window — zero join/marshal copies end to end.
        ``--io-prefetch 0`` or multi-threaded scans take the one-shot
        fork-join call instead (its byte-balanced worker split needs
        the whole corpus resident).  Falls back to the Python oracle
        when no C++ toolchain is available, keeping the backend usable
        everywhere.
        """
        from .. import native

        if not self.config.use_native or not native.available():
            with timer.phase("oracle"):
                stats = oracle_index(
                    manifest, out_dir,
                    artifact_path=self._artifact_path(out_dir))
            timer.count("cpu_fallback", "oracle")
            return {**stats, **timer.report()}
        threads = self.config.resolved_host_threads()
        timer.count("host_threads", threads)
        if envknobs.get("MRI_BUILD_SPILL_BYTES") is not None:
            # Out-of-core route: scan workers spill term-hash-sharded
            # postings runs at the MRI_BUILD_SPILL_BYTES budget and the
            # reduce becomes a per-shard streaming k-way merge over the
            # run files.  Takes the parallel topology even at K = M = 1
            # so every (K, M, shards, budget) point shares one path —
            # and stays byte-identical to the in-memory merge.
            return self._run_cpu_parallel(manifest, out_dir, timer, threads)
        if self.config.artifact:
            # The serving artifact packs straight off the merge state's
            # columnar export (no letter-file round-trip), so --artifact
            # routes through the parallel reduce even at K = M = 1 —
            # byte-identical letter files at every (K, M) regardless.
            return self._run_cpu_parallel(manifest, out_dir, timer, threads)
        if self.config.io_prefetch > 0:
            # resolved_host_threads drives the pipelined path too (it
            # used to fall off to the one-shot call for any K > 1,
            # reporting host_threads=1 work): K scan workers + M letter
            # reducers when either knob asks for parallelism.
            if threads > 1 or self.config.num_reducers > 1:
                return self._run_cpu_parallel(manifest, out_dir, timer,
                                              threads)
            return self._run_cpu_pipelined(manifest, out_dir, timer)
        with timer.phase("load"):
            contents, doc_ids = load_documents(manifest)
        with timer.phase("index_emit"):
            stats = native.host_index_native(
                contents, doc_ids, out_dir, num_threads=threads)
        for key, value in stats.items():
            timer.count(key, value)
        return timer.report()

    # ~2 MB windows: several windows even for small corpora (so the
    # read-ahead has something to hide behind) while staying resident in
    # L2/L3 for the scan that immediately follows the fill.
    _CPU_WINDOW_BYTES = 2 << 20

    # Spill-budget cost model (MRI_BUILD_SPILL_BYTES): estimated native
    # scan-state footprint is pairs * 16 + vocab * 56 — a (term, doc)
    # pair holds a packed 8-byte id plus tf and flatten scratch; a
    # local term holds its arena bytes, offset/length entries, combiner
    # row, and hash slot.  An estimate, not an accounting: the budget
    # bounds worker postings memory to within a small constant factor.
    _SPILL_PAIR_BYTES = 16
    _SPILL_TERM_BYTES = 56

    def _cpu_window_bytes(self) -> int:
        # MRI_CPU_WINDOW_BYTES forces tiny windows from a subprocess —
        # the SIGKILL-at-window-boundary e2e tests need a multi-window
        # plan on a corpus small enough to kill deterministically.
        override = envknobs.get("MRI_CPU_WINDOW_BYTES")
        return override if override is not None else self._CPU_WINDOW_BYTES

    def _run_cpu_pipelined(self, manifest: Manifest, out_dir: str,
                           timer: PhaseTimer) -> dict:
        """Arena-fed incremental host index (the io subsystem path).

        Stage attribution lands in the ``stage_*_ms`` counters: read is
        the reader thread's busy time, tokenize the native scan +
        postings finalize, emit the letter-file render + write — the
        split bench.py reports as ``host_stage_split``.
        """
        from .. import native
        from ..io.executor import PipelinedWindowReader
        from ..io.reader import plan_byte_windows

        window_bytes = self._cpu_window_bytes()
        windows = plan_byte_windows(manifest, window_bytes)
        max_docs = max((hi - lo for lo, hi in windows), default=1)
        # The arena ring is reused across run() calls (steady-state: no
        # page faults from fresh buffers); construct the reader FIRST —
        # its thread starts filling window 0 while HostIndexStream
        # allocates its vocab table below.
        arenas = getattr(self, "_cpu_arenas", None)
        if arenas is not None and len(arenas) != self.config.io_prefetch + 1:
            arenas = None
        trace = getattr(self, "_trace", None)
        if trace is not None:
            trace.name_thread(chrometrace.READER_BASE, "reader-0")
            trace.name_thread(chrometrace.SCAN_BASE, "scan-worker-0")
        reader = PipelinedWindowReader(
            manifest, windows, depth=self.config.io_prefetch,
            byte_capacity=window_bytes + (window_bytes >> 2),
            doc_capacity=max_docs, arenas=arenas, trace=trace)
        self._cpu_arenas = reader.arenas
        stream = native.HostIndexStream()
        try:
            with reader, timer.phase("ingest_scan"):
                for arena in reader:
                    buf, ends, ids = arena.feed_views()
                    t0 = time.perf_counter()
                    stream.feed_arrays(buf, ends, ids)
                    if trace is not None:
                        trace.span("scan", t0, time.perf_counter(),
                                   tid=chrometrace.SCAN_BASE,
                                   args={"window": arena.window_index})
                    reader.recycle(arena)
            with timer.phase("finalize_emit"):
                t0 = time.perf_counter()
                stats = stream.finalize_emit(out_dir)
                if trace is not None:
                    trace.span("finalize_emit", t0, time.perf_counter())
        finally:
            stream.close()
            reader.close()
        for key, value in stats.items():
            timer.count(key, value)
        timer.count("io_windows", len(windows))
        timer.count("io_prefetch", self.config.io_prefetch)
        timer.count("stage_read_ms", round(reader.read_busy_s * 1e3, 3))
        timer.count("stage_tokenize_ms",
                    round(stats["scan_ms"] + stats["finalize_ms"], 3))
        timer.count("stage_emit_ms", round(stats["emit_ms"], 3))
        timer.count("read_wait_ms", round(reader.read_wait_s * 1e3, 3))
        timer.count("consume_wait_ms", round(reader.consume_wait_s * 1e3, 3))
        return timer.report()

    def _run_cpu_parallel(self, manifest: Manifest, out_dir: str,
                          timer: PhaseTimer, num_workers: int) -> dict:
        """K-worker map + M-reducer reduce on the pipelined host path.

        The reference's fork-join topology (N mapper threads scanning
        file shards, M reducer threads owning letter ranges,
        main.c:85-242) rebuilt on the zero-copy pipeline: every scan
        worker owns its own arena ring + reader thread + incremental
        native handle and pulls byte windows from one shared
        :class:`StealQueue`, so a slow stripe never idles the rest.
        ctypes releases the GIL for the native scan, partial-flatten,
        and emit calls — the Python threads are genuinely concurrent in
        C++.  Reduce is letter-partitioned: ``plan_letter_ranges``
        (``num_reducers``) splits the merged emit order and each
        reducer renders its range through the shared vectorized emit.
        Output is byte-identical to the single-worker path at every
        (K, M) — scheduling can reorder work, never bytes.

        Fault tolerance (the MapReduce re-execution move): windows are
        LEASED, not given away — a worker death (escaping exception,
        :class:`~..io.executor.ReaderDied`, or the optional
        ``MRI_WINDOW_DEADLINE_S`` lease watchdog) requeues everything
        attributed to it, completed windows included (its native handle
        dies with it), and survivors rescan; when the queue is left
        non-empty after the join, up to ``MRI_WORKER_RESPAWNS``
        (default 1) replacement workers drain it.  Only with the budget
        exhausted do the remaining windows' documents become recorded
        skips (degraded exit 3).  A dead reducer's letter range is
        re-emitted off the read-only merge state by the main thread
        (emit is atomic tmp+rename per file, so re-emit is idempotent).
        Recovered runs stay byte-identical: merge order is restored
        from global plan indices, never arrival order.
        """
        import threading

        from .. import audit as audit_mod
        from .. import native
        from ..corpus.scheduler import StealQueue, plan_letter_ranges
        from ..io.executor import PipelinedWindowReader
        from ..io.reader import plan_byte_windows

        cfg = self.config
        spill_budget = envknobs.get("MRI_BUILD_SPILL_BYTES")
        spill_mode = spill_budget is not None
        num_shards = envknobs.get("MRI_BUILD_SHARDS")
        sdir = None
        if spill_mode:
            from ..build import spill as spill_mod
            from ..obs import metrics as obs_metrics

            # a SIGKILLed spill build leaves only a stale .spill-<pid>
            # dir behind; sweep those before arming our own
            spill_mod.clean_stale_dirs(out_dir)
            sdir = spill_mod.spill_dir(out_dir)
            os.makedirs(sdir, exist_ok=True)
            reg = obs_metrics.default_registry()
            ctr_spill_flushes = reg.counter(
                "mri_build_spill_flushes_total",
                help="Spill-run flushes across all scan workers")
            ctr_spill_bytes = reg.counter(
                "mri_build_spill_bytes_total",
                help="Bytes written to spill run files")
        elif cfg.num_reducers > 26:
            # letter-partitioned reduce: the reference's degenerate
            # R > 26 arithmetic leaves reducers beyond the alphabet
            # with empty ranges (documented conformance contract) —
            # say so instead of clamping silently
            log.warning(
                "num_reducers=%d exceeds the 26 letter partitions; "
                "reducers past the alphabet get empty ranges (set "
                "MRI_BUILD_SPILL_BYTES to partition by term-hash "
                "shard, where every reducer gets real work)",
                cfg.num_reducers)
        window_bytes = self._cpu_window_bytes()
        if spill_mode:
            # the budget check runs at window boundaries, so a window
            # must be a small fraction of the budget or one window's
            # intake overshoots it before the first check; floor at
            # 4 KiB so toy budgets don't degenerate to per-doc windows
            window_bytes = min(window_bytes,
                               max(spill_budget >> 4, 1 << 12))
        windows = plan_byte_windows(manifest, window_bytes)
        max_docs = max((hi - lo for lo, hi in windows), default=1)
        K = max(1, num_workers)
        # --artifact reaches here even with --io-prefetch 0 (the merge
        # state is the artifact's source); the reader needs depth >= 1
        depth = max(1, cfg.io_prefetch)
        queue = StealQueue(
            windows,
            shuffle_seed=envknobs.get("MRI_STEAL_SHUFFLE_SEED"))
        window_deadline_s = envknobs.get("MRI_WINDOW_DEADLINE_S")
        respawns_left = max(0, envknobs.get("MRI_WORKER_RESPAWNS"))

        # Per-worker arena rings, recycled across run() calls like the
        # single-worker path's ring (invalidated when K or depth moves,
        # or after any recovery — a failed run's arenas may still be
        # referenced by a leaked thread).
        rings = getattr(self, "_cpu_arena_rings", None)
        if rings is not None and (
                len(rings) != K
                or any(len(r) != depth + 1 for r in rings)):
            rings = None
        if rings is None:
            rings = [None] * K

        # Private DegradationReport per worker (reader threads record
        # without cross-worker lock contention), merged at the join so
        # a degraded run still reports every skipped doc id.
        run_report = faults.current_report()
        policy = faults.default_policy()
        ledger = audit_mod.WindowLedger() if cfg.audit else None
        audit_s = 0.0  # in-path invariant-check time (--audit)
        inj = faults.active()

        # Workers live in growable slots (respawns append), not fixed
        # arrays: each slot owns one reader + one native stream, and a
        # ``failed`` slot's stream is excluded from the merge.
        slots: list[dict] = []
        fail_lock = threading.Lock()
        trace = getattr(self, "_trace", None)

        def make_slot(w: int, arenas=None) -> dict:
            rep = faults.DegradationReport()
            slot = {
                "id": w, "report": rep, "partial": None,
                "fatal": None, "failed": False, "leaked": False,
                "thread": None,
                "stream": native.HostIndexStream(),
                # spill-mode state: completed run files, window ranges
                # fed since the last flush, and the footprint watermark
                "runs": [], "run_windows": [], "docs": 0,
                "scan_ms_acc": 0.0, "partial_ms_acc": 0.0, "peak_est": 0,
            }
            if trace is not None:
                trace.name_thread(chrometrace.READER_BASE + w,
                                  f"reader-{w}")
                trace.name_thread(chrometrace.SCAN_BASE + w,
                                  f"scan-worker-{w}")
            # reader last: its thread starts pulling windows immediately
            slot["reader"] = PipelinedWindowReader(
                manifest, queue, depth=depth,
                byte_capacity=window_bytes + (window_bytes >> 2),
                doc_capacity=max_docs, arenas=arenas,
                policy=policy, report=rep, worker=w, trace=trace)
            slots.append(slot)
            return slot

        def fail_slot(slot: dict, reason: str) -> None:
            """Idempotent worker-death transition: blacklist the worker,
            requeue every window attributed to it (its native handle —
            the only place those windows' postings live — is discarded
            with it), and count the recovery."""
            with fail_lock:
                if slot["failed"]:
                    return
                slot["failed"] = True
                requeued = queue.fail_worker(slot["id"])
                if ledger is not None:
                    ledger.discard_worker(slot["id"])
                run_report.record_worker_recovery(
                    windows_requeued=len(requeued))
                # spill mode: the dead worker's run files cover the
                # same windows fail_worker just requeued — delete them
                # so the rescan (by a survivor with its own runs) can't
                # double-merge those documents
                stale_runs = [run["path"] for run in slot["runs"]]
                slot["runs"] = []
                slot["run_windows"] = []
            for path in stale_runs:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            log.warning(
                "scan worker %d died (%s); requeued %d window(s) %s for "
                "rescan", slot["id"], reason, len(requeued), requeued)

        def flush_run(slot: dict, final: bool = False):
            """Spill the worker's scan state as one checksummed run
            file; unless ``final``, hand back a fresh native stream
            (the memory release that bounds the worker's footprint)."""
            stream, w = slot["stream"], slot["id"]
            t0 = time.perf_counter()
            p = stream.partial()
            slot["scan_ms_acc"] += p["scan_ms"]
            slot["partial_ms_acc"] += p["partial_ms"]
            pack = stream.runpack(num_shards)
            path, nbytes = spill_mod.write_run(
                sdir, w, len(slot["runs"]), pack, slot["run_windows"])
            t1 = time.perf_counter()
            slot["runs"].append({
                "path": path, "windows": list(slot["run_windows"]),
                "pairs": pack["pairs"], "vocab": pack["vocab"],
                "width": pack["width"], "docs": len(pack["doc_ids"]),
                "max_doc_id": pack["max_doc_id"],
                "raw_tokens": pack["raw_tokens"], "bytes": nbytes,
            })
            slot["run_windows"] = []
            ctr_spill_flushes.inc()
            ctr_spill_bytes.inc(nbytes)
            if trace is not None:
                trace.span("spill_flush", t0, t1,
                           tid=chrometrace.SCAN_BASE + w,
                           args={"run": len(slot["runs"]) - 1,
                                 "pairs": pack["pairs"],
                                 "bytes": int(nbytes)})
            if not final:
                stream.close()
                slot["stream"] = native.HostIndexStream()
            return slot["stream"]

        def scan_worker(slot: dict) -> None:
            w, reader, stream = slot["id"], slot["reader"], slot["stream"]
            try:
                for arena in reader:
                    wi = arena.window_index
                    dropped = False
                    if inj is not None:
                        inj.on_worker_window(w, wi)
                        dropped = inj.on_scan_window(wi)
                    if not dropped:
                        t0s = time.perf_counter()
                        buf, ends, ids = arena.feed_views()
                        stream.feed_arrays(buf, ends, ids)
                        if trace is not None:
                            trace.span("scan", t0s, time.perf_counter(),
                                       tid=chrometrace.SCAN_BASE + w,
                                       args={"window": wi})
                        if ledger is not None:
                            ledger.record(
                                wi, worker=w, docs=int(arena.num_docs),
                                nbytes=int(arena.used_bytes),
                                checksum=audit_mod.window_checksum(
                                    buf, ends, ids))
                        if spill_mode:
                            # wi is the 1-based global plan index
                            lo_d, hi_d = windows[wi - 1]
                            slot["run_windows"].append((wi, lo_d, hi_d))
                            slot["docs"] += int(arena.num_docs)
                    queue.ack(wi, worker=w)
                    reader.recycle(arena)
                    if spill_mode and slot["run_windows"]:
                        # documented cost model for the native scan
                        # state: ~16 B per deduped (term, doc) pair
                        # (packed id + tf) and ~56 B per local term
                        # (arena bytes + offset/len + combiner row +
                        # hash slot) — the budget trip point
                        info = stream.info()
                        est = (info["pairs"] * self._SPILL_PAIR_BYTES
                               + info["vocab"] * self._SPILL_TERM_BYTES)
                        if est > slot["peak_est"]:
                            slot["peak_est"] = est
                        # trip at half budget: the NEXT window's intake
                        # lands on top of the current estimate before
                        # the next boundary check, so the headroom is
                        # what keeps the true peak under the budget
                        if est >= spill_budget // 2:
                            stream = flush_run(slot)
                if spill_mode and slot["runs"]:
                    # this worker tripped the budget mid-scan, so its
                    # tail postings must spill too (the reduce k-way
                    # merges this worker entirely from disk)
                    if slot["run_windows"]:
                        flush_run(slot, final=True)
                    slot["partial"] = {
                        "scan_ms": slot["scan_ms_acc"],
                        "partial_ms": slot["partial_ms_acc"],
                    }
                else:
                    # flatten this worker's postings runs here, inside
                    # the map phase's parallelism, not at the serial
                    # join.  A spill-armed worker that never tripped
                    # the budget lands here too: its state stays in
                    # memory until the join decides whether ANY worker
                    # spilled (the zero-spill fast path).
                    slot["partial"] = stream.partial()
            except (KeyboardInterrupt, SystemExit) as e:
                # not a worker fault: requeue for bookkeeping but carry
                # the exception out of the scan phase
                slot["fatal"] = e
                fail_slot(slot, type(e).__name__)
            except BaseException as e:  # noqa: BLE001 — recovery path
                fail_slot(slot, f"{type(e).__name__}: {e}")
                reader.close()  # unstick + retire this slot's reader

        merge = None
        empty_stream = None
        try:
            with timer.phase("ingest_scan"):
                for w in range(K):
                    make_slot(w, arenas=rings[w])
                for slot in slots[1:]:
                    t = threading.Thread(
                        target=scan_worker, args=(slot,),
                        name=f"scan-worker-{slot['id']}", daemon=True)
                    slot["thread"] = t
                    t.start()
                scan_worker(slots[0])  # the caller's thread is worker 0
                # Join survivors; under MRI_WINDOW_DEADLINE_S a worker
                # holding any lease past the deadline is retired in
                # absentia (windows requeued) and its thread abandoned
                # — "leaked": its native stream is never closed, since
                # the wedged thread may still be inside a native call
                # (a leak beats a use-after-free).
                while True:
                    waiting = [s for s in slots[1:]
                               if s["thread"] is not None
                               and s["thread"].is_alive()
                               and not s["leaked"]]
                    if not waiting:
                        break
                    for s in waiting:
                        s["thread"].join(
                            0.2 if window_deadline_s is not None else 60.0)
                    if window_deadline_s is None:
                        continue
                    expired = queue.expired_workers(window_deadline_s)
                    for s in slots:
                        if s["id"] in expired and not s["failed"]:
                            s["leaked"] = True
                            fail_slot(s, "window lease deadline "
                                         f"({window_deadline_s}s) expired")
                # Requeued windows left after every worker exited (a
                # death can land after survivors already drained out):
                # respawn replacement workers, fresh ring + fresh native
                # handle, on this thread, until the queue is dry or the
                # budget is spent.  A replacement can die too — the loop
                # handles it like any other worker death.
                next_id = K
                while len(queue) > 0 and respawns_left > 0:
                    respawns_left -= 1
                    log.warning(
                        "respawning scan worker %d to rescan %d "
                        "requeued window(s)", next_id, len(queue))
                    scan_worker(make_slot(next_id))
                    next_id += 1
                lost_windows: list[int] = []
                if len(queue) > 0:
                    # Budget exhausted with windows unscanned: the
                    # honest degraded arm — record exactly which
                    # documents were lost, then finish with what we
                    # have (exit 3, never silence).
                    while True:
                        item = queue.pop_window()
                        if item is None:
                            break
                        wi, (lo, hi) = item
                        lost_windows.append(wi)
                        for i in range(lo, hi):
                            run_report.record_skip(
                                doc_id=manifest.doc_id(i),
                                path=manifest.paths[i],
                                reason=f"window {wi} lost to worker "
                                       "death (respawn budget "
                                       "exhausted)")
                    log.error(
                        "worker respawn budget exhausted; %d window(s) "
                        "%s lost", len(lost_windows), lost_windows)
            for slot in slots:
                run_report.merge(slot["report"])
            for slot in slots:
                if slot["fatal"] is not None:
                    raise slot["fatal"]
            if spill_mode:
                if not any(s["runs"] for s in slots if not s["failed"]):
                    # zero-spill fast path: no worker ever tripped the
                    # budget, so nothing left memory — reduce through
                    # the in-memory native merge exactly like the
                    # unset-knob build (within noise of its wall clock)
                    timer.count("spill", {
                        "budget_bytes": int(spill_budget),
                        "runs": 0, "runs_quarantined": 0, "flushes": 0,
                        "bytes_spilled": 0,
                        "peak_worker_est_bytes": max(
                            (s["peak_est"] for s in slots), default=0),
                    })
                    spill_mod.remove_dir(sdir)
                    spill_mode = False
                else:
                    # mixed case: flush the workers that never tripped
                    # the budget (their partial() already ran in the
                    # map phase, so runpack here is pure copy-out)
                    for slot in slots:
                        if not slot["failed"] and slot["run_windows"]:
                            flush_run(slot, final=True)
            live = []
            if not spill_mode:
                live = [s["stream"] for s in slots if not s["failed"]]
                if not live:
                    # every worker died: merge one empty stream so the
                    # reduce still writes the 26 (empty) letter files
                    # and the degraded report carries the whole story
                    empty_stream = native.HostIndexStream()
                    live = [empty_stream]
            if ledger is not None:
                t0 = time.perf_counter()
                ledger.check_complete(len(windows),
                                      missing_ok=lost_windows)
                audit_s += time.perf_counter() - t0
            if spill_mode:
                with timer.phase("finalize_emit"):
                    red = self._spill_reduce(
                        manifest, out_dir, timer, slots, run_report,
                        inj, trace, sdir, num_shards)
                mstats = red["mstats"]
                emit_ms = red["emit_ms"]
                emit_bytes = red["emit_bytes"]
                audit_s += red["audit_s"]
                timer.count("build_shards", red["build_shards"])
                timer.count("spill", {
                    "budget_bytes": int(spill_budget),
                    "runs": red["runs_merged"],
                    "runs_quarantined": red["runs_quarantined"],
                    "flushes": sum(len(s["runs"]) for s in slots),
                    "bytes_spilled": red["bytes_spilled"],
                    "peak_worker_est_bytes": max(
                        (s["peak_est"] for s in slots), default=0),
                })
            else:
                with timer.phase("finalize_emit"):
                    t0m = time.perf_counter()
                    merge = native.HostIndexMerge(live)
                    if trace is not None:
                        trace.span("merge", t0m, time.perf_counter())
                    if cfg.audit:
                        t0 = time.perf_counter()
                        audit_mod.check_merge(merge, live)
                        audit_s += time.perf_counter() - t0
                    ranges = plan_letter_ranges(cfg.num_reducers)
                    emit_ms = [0.0] * len(ranges)
                    emit_bytes = [0] * len(ranges)
                    emit_errors: list[BaseException | None] = [None] * len(ranges)

                    def reduce_worker(r: int, lo: int, hi: int) -> None:
                        t0 = time.perf_counter()
                        try:
                            if inj is not None:
                                inj.on_reducer(r)
                            emit_bytes[r] = merge.emit_range(lo, hi, out_dir)
                        except BaseException as e:  # noqa: BLE001
                            emit_errors[r] = e
                        t1 = time.perf_counter()
                        emit_ms[r] = (t1 - t0) * 1e3
                        if trace is not None:
                            trace.name_thread(chrometrace.REDUCE_BASE + r,
                                              f"reduce-worker-{r}")
                            trace.span("emit_range", t0, t1,
                                       tid=chrometrace.REDUCE_BASE + r,
                                       args={"letters": [lo, hi]})

                    reducers = [
                        threading.Thread(target=reduce_worker, args=(r, lo, hi),
                                         name=f"reduce-worker-{r}")
                        for r, (lo, hi) in list(enumerate(ranges))[1:]
                    ]
                    for t in reducers:
                        t.start()
                    reduce_worker(0, *ranges[0])
                    for t in reducers:
                        t.join()
                    # Reducer takeover: emit_range is read-only on the
                    # merge state and atomic per letter file, so a dead
                    # reducer's range is simply re-emitted here.  A second
                    # failure on the SAME range is a real I/O problem and
                    # raises (exit 2).
                    for r, err in enumerate(emit_errors):
                        if err is None:
                            continue
                        lo, hi = ranges[r]
                        log.warning(
                            "reduce worker %d died (%s: %s); re-emitting "
                            "letters [%d, %d) on the main thread",
                            r, type(err).__name__, err, lo, hi)
                        t0 = time.perf_counter()
                        emit_bytes[r] = merge.emit_range(lo, hi, out_dir)
                        emit_ms[r] += (time.perf_counter() - t0) * 1e3
                        run_report.record_reducer_takeover()
                        emit_errors[r] = None
                    mstats = merge.stats()
                    if cfg.artifact:
                        from ..serve import artifact as artifact_mod

                        t0 = time.perf_counter()
                        art_bytes = artifact_mod.build_from_merge(
                            artifact_mod.artifact_path(out_dir), merge)
                        t1 = time.perf_counter()
                        if trace is not None:
                            trace.span("artifact_pack", t0, t1)
                        timer.count("artifact_bytes", int(art_bytes))
                        timer.count(
                            "artifact_build_ms",
                            round((t1 - t0) * 1e3, 3))
        finally:
            recovered = any(s["failed"] for s in slots)
            for slot in slots:
                slot["reader"].close()
            if merge is not None:
                merge.close()
            for slot in slots:
                if not slot["leaked"]:
                    slot["stream"].close()
            if empty_stream is not None:
                empty_stream.close()
            # cache the rings only for a clean same-shape run
            if recovered or len(slots) != K:
                self._cpu_arena_rings = None
            else:
                self._cpu_arena_rings = [s["reader"].arenas for s in slots]

        for key, value in mstats.items():
            if key != "merge_ms":
                timer.count(key, value)
        timer.count("bytes_written", int(sum(emit_bytes)))
        timer.count("reduce_workers", len(emit_ms))
        timer.count("io_windows", len(windows))
        timer.count("io_prefetch", cfg.io_prefetch)
        if cfg.audit:
            timer.count("audit_ms", round(audit_s * 1e3, 3))
        read_ms = [round(s["reader"].read_busy_s * 1e3, 3) for s in slots]
        tok_ms = [round(s["partial"]["scan_ms"]
                        + s["partial"]["partial_ms"], 3)
                  for s in slots
                  if s["partial"] is not None and not s["failed"]]
        timer.count("stage_read_ms", round(sum(read_ms), 3))
        timer.count("stage_tokenize_ms",
                    round(sum(tok_ms) + mstats["merge_ms"], 3))
        timer.count("stage_emit_ms", round(sum(emit_ms), 3))
        timer.count("stage_read_ms_per_worker", read_ms)
        timer.count("stage_tokenize_ms_per_worker", tok_ms)
        timer.count("stage_emit_ms_per_reducer",
                    [round(ms, 3) for ms in emit_ms])
        timer.count("merge_ms", round(mstats["merge_ms"], 3))
        timer.count("read_wait_ms",
                    round(sum(s["reader"].read_wait_s
                              for s in slots) * 1e3, 3))
        timer.count("consume_wait_ms",
                    round(sum(s["reader"].consume_wait_s
                              for s in slots) * 1e3, 3))
        return timer.report()

    def _spill_reduce(self, manifest, out_dir, timer, slots, run_report,
                      inj, trace, sdir, num_shards) -> dict:
        """Disk-tier reduce for the out-of-core build.

        Input: the scan phase's checksummed run files (term-hash-sharded
        sorted postings runs, one or more per surviving worker).  Three
        phases, all bounded by O(corpus / shards) memory:

        1. **verify** — full checksum walk over every run; a torn or
           bit-flipped run is quarantined and its windows' documents
           become recorded skips (degraded exit 3 — same contract as a
           spent respawn budget, never silent corruption).
        2. **shard merge** — M reduce workers own shards round-robin
           (``shard % M``) and k-way-merge every run's slice of each
           shard into one lex-sorted shard file.  No 26-partition cap:
           every reducer gets real work at any M.
        3. **letter emit** — letters are likewise owned round-robin;
           each is assembled from the shard files' letter slices and
           rendered through the same native emit as the in-memory path,
           so the letter files are byte-identical at every (mappers,
           reducers, shards, budget) point.

        Worker deaths in 2 and 3 degrade to main-thread takeover
        (shard/letter writes are atomic and idempotent), mirroring the
        in-memory reducer-takeover contract.
        """
        import threading

        from .. import audit as audit_mod
        from .. import native
        from ..build import ooc
        from ..build import spill as spill_mod
        from ..corpus import scheduler
        from ..obs import metrics as obs_metrics

        cfg = self.config
        M = max(1, cfg.num_reducers)
        reg = obs_metrics.default_registry()
        ctr_quarantined = reg.counter(
            "mri_build_spill_runs_quarantined_total",
            help="Spill runs that failed their checksum walk")
        ctr_merge_shards = reg.counter(
            "mri_build_merge_shards_total",
            help="Term-hash shards merged from spill runs")
        ctr_merge_runs = reg.counter(
            "mri_build_merge_runs_total",
            help="Spill runs consumed by shard merges")
        ctr_merge_pairs = reg.counter(
            "mri_build_merge_pairs_total",
            help="(term, doc) pairs produced by shard merges")
        ctr_merge_takeovers = reg.counter(
            "mri_build_merge_takeovers_total",
            help="Reduce workers whose shards/letters were re-done on "
                 "the main thread")

        # -- 1. verify every run up front; quarantine + skip on damage
        all_runs = [run for slot in slots if not slot["failed"]
                    for run in slot["runs"]]
        good_runs = []
        quarantined = 0
        for run in all_runs:
            try:
                spill_mod.verify_file(run["path"])
            except (spill_mod.SpillError, OSError) as e:
                quarantined += 1
                ctr_quarantined.inc()
                try:
                    spill_mod.quarantine(run["path"])
                except OSError:
                    pass
                log.error("spill run %s failed verification (%s); its "
                          "windows' documents are skipped",
                          os.path.basename(str(run["path"])), e)
                for wi, lo, hi in run["windows"]:
                    for i in range(lo, hi):
                        run_report.record_skip(
                            doc_id=manifest.doc_id(i),
                            path=manifest.paths[i],
                            reason=f"window {wi} lost to corrupt spill "
                                   f"run {os.path.basename(str(run['path']))}"
                                   f" ({e})")
            else:
                good_runs.append(run)
        run_paths = [run["path"] for run in good_runs]
        width_g = max([run["width"] for run in good_runs] + [1])
        max_doc_id = max((run["max_doc_id"] for run in good_runs),
                         default=0)
        audit_s = 0.0

        # -- 2. per-shard k-way merge, shards owned round-robin by M
        # workers; each worker opens its own SpillFile handles (the
        # readers seek, so a shared handle would race)
        shard_pairs = [0] * num_shards
        shard_vocab = [0] * num_shards
        shard_done = [False] * num_shards
        merge_errors: list[BaseException | None] = [None] * M
        merge_ms = [0.0] * M

        def merge_worker(r: int) -> None:
            readers = []
            t_w0 = time.perf_counter()
            try:
                if trace is not None:
                    trace.name_thread(chrometrace.REDUCE_BASE + r,
                                      f"reduce-worker-{r}")
                readers = [spill_mod.SpillFile(p) for p in run_paths]
                for s in range(r, num_shards, M):
                    t0 = time.perf_counter()
                    if inj is not None:
                        inj.on_shard_merge(s)
                    merged = ooc.merge_shard(readers, s, width_g)
                    spill_mod.write_shard(sdir, s, merged)
                    shard_pairs[s] = int(merged["postings"].shape[0])
                    shard_vocab[s] = int(merged["df"].shape[0])
                    shard_done[s] = True
                    if trace is not None:
                        trace.span("shard_merge", t0, time.perf_counter(),
                                   tid=chrometrace.REDUCE_BASE + r,
                                   args={"shard": s,
                                         "postings": shard_pairs[s]})
            except BaseException as e:  # noqa: BLE001 — recovery path
                merge_errors[r] = e
            finally:
                for f in readers:
                    f.close()
            merge_ms[r] += (time.perf_counter() - t_w0) * 1e3

        t_merge0 = time.perf_counter()
        threads = [threading.Thread(target=merge_worker, args=(r,),
                                    name=f"reduce-worker-{r}")
                   for r in range(1, M)]
        for t in threads:
            t.start()
        merge_worker(0)
        for t in threads:
            t.join()
        # Takeover: shard files are atomic and the merge inputs are
        # read-only run files, so a dead worker's shards are simply
        # re-merged here (injection hooks deliberately not re-fired —
        # same rule as the in-memory reducer takeover).
        for r, err in enumerate(merge_errors):
            if err is None:
                continue
            todo = [s for s in range(r, num_shards, M)
                    if not shard_done[s]]
            log.warning(
                "shard-merge worker %d died (%s: %s); re-merging "
                "shard(s) %s on the main thread",
                r, type(err).__name__, err, todo)
            t0 = time.perf_counter()
            readers = [spill_mod.SpillFile(p) for p in run_paths]
            try:
                for s in todo:
                    merged = ooc.merge_shard(readers, s, width_g)
                    spill_mod.write_shard(sdir, s, merged)
                    shard_pairs[s] = int(merged["postings"].shape[0])
                    shard_vocab[s] = int(merged["df"].shape[0])
                    shard_done[s] = True
            finally:
                for f in readers:
                    f.close()
            merge_ms[r] += (time.perf_counter() - t0) * 1e3
            run_report.record_reducer_takeover()
            ctr_merge_takeovers.inc()
            merge_errors[r] = None
        merge_wall_ms = (time.perf_counter() - t_merge0) * 1e3
        ctr_merge_shards.inc(num_shards)
        ctr_merge_runs.inc(len(good_runs))
        ctr_merge_pairs.inc(sum(shard_pairs))

        if cfg.audit:
            t0 = time.perf_counter()
            audit_mod.check_spill(
                sum(run["pairs"] for run in good_runs),
                sum(shard_pairs),
                sum(run["vocab"] for run in good_runs),
                sum(shard_vocab))
            audit_s += time.perf_counter() - t0

        # -- 3. letter emit off the merged shard files, letters owned
        # round-robin; native emit keeps the bytes identical to the
        # in-memory path (empty letters still write their files)
        shard_paths = [spill_mod.shard_path(sdir, s)
                       for s in range(num_shards)]
        emit_ms = [0.0] * M
        emit_bytes = [0] * M
        emit_errors: list[BaseException | None] = [None] * M
        letter_done = [False] * ooc.ALPHABET_SIZE

        def emit_letter(files, letter: int) -> int:
            parts = [p for p in (ooc.letter_slice(f, letter, width_g)
                                 for f in files) if p is not None]
            if not parts:
                return native.emit_native(
                    out_dir, np.zeros(0, dtype="S1"),
                    np.zeros(0, dtype=np.int64),
                    np.zeros(0, dtype=np.int64),
                    np.zeros(1, dtype=np.int64),
                    np.zeros(0, dtype=np.int32),
                    letter_range=(letter, letter + 1), idx_bounds=(0, 0))
            cat = ooc.concat_letter(parts)
            order = ooc.emit_order(cat["df"])
            return native.emit_native(
                out_dir, cat["terms"], order, cat["df"], cat["offsets"],
                cat["postings"], letter_range=(letter, letter + 1),
                idx_bounds=(0, int(order.shape[0])))

        def emit_worker(r: int) -> None:
            files = []
            t_w0 = time.perf_counter()
            try:
                if inj is not None:
                    inj.on_reducer(r)
                files = [spill_mod.SpillFile(p) for p in shard_paths]
                for letter in range(r, ooc.ALPHABET_SIZE, M):
                    t0 = time.perf_counter()
                    emit_bytes[r] += emit_letter(files, letter)
                    letter_done[letter] = True
                    if trace is not None:
                        trace.span("emit_letter", t0, time.perf_counter(),
                                   tid=chrometrace.REDUCE_BASE + r,
                                   args={"letter": letter})
            except BaseException as e:  # noqa: BLE001 — recovery path
                emit_errors[r] = e
            finally:
                for f in files:
                    f.close()
            emit_ms[r] += (time.perf_counter() - t_w0) * 1e3

        threads = [threading.Thread(target=emit_worker, args=(r,),
                                    name=f"reduce-worker-{r}")
                   for r in range(1, M)]
        for t in threads:
            t.start()
        emit_worker(0)
        for t in threads:
            t.join()
        for r, err in enumerate(emit_errors):
            if err is None:
                continue
            todo = [letter for letter in range(r, ooc.ALPHABET_SIZE, M)
                    if not letter_done[letter]]
            log.warning(
                "letter-emit worker %d died (%s: %s); re-emitting "
                "letter(s) %s on the main thread",
                r, type(err).__name__, err, todo)
            t0 = time.perf_counter()
            files = [spill_mod.SpillFile(p) for p in shard_paths]
            try:
                for letter in todo:
                    emit_bytes[r] += emit_letter(files, letter)
                    letter_done[letter] = True
            finally:
                for f in files:
                    f.close()
            emit_ms[r] += (time.perf_counter() - t0) * 1e3
            run_report.record_reducer_takeover()
            ctr_merge_takeovers.inc()
            emit_errors[r] = None

        # -- artifact: whole-index lex assembly off the shard files
        if cfg.artifact:
            from ..serve import artifact as artifact_mod

            t0 = time.perf_counter()
            files = [spill_mod.SpillFile(p) for p in shard_paths]
            try:
                u8_all = np.concatenate(
                    [f.section("vocab").reshape(-1, width_g)
                     for f in files])
                word_lens_all = np.concatenate(
                    [f.section("word_lens") for f in files])
                df_all = np.concatenate([f.section("df") for f in files])
                post_all = np.concatenate(
                    [f.section("postings") for f in files])
                tf_all = np.concatenate([f.section("tf") for f in files])
            finally:
                for f in files:
                    f.close()
            src_off = np.zeros(df_all.shape[0] + 1, dtype=np.int64)
            np.cumsum(df_all, out=src_off[1:])
            lex = np.argsort(ooc.as_terms(u8_all, width_g), kind="stable")
            idx, post_off = ooc.gather_pairs(lex, src_off)
            rows_lex = u8_all[lex]
            lens_lex = word_lens_all[lex].astype(np.int64)
            term_blob = rows_lex[
                np.arange(width_g)[None, :] < lens_lex[:, None]]
            term_offsets = np.zeros(lens_lex.shape[0] + 1, dtype=np.int64)
            np.cumsum(lens_lex, out=term_offsets[1:])
            df_lex = df_all[lex]
            # df_order[emit position] = lex index; emit order is letter
            # asc, then df desc with ties word asc within the letter —
            # letter blocks are contiguous in both orders
            df_order = np.zeros(lens_lex.shape[0], dtype=np.int64)
            firsts = rows_lex[:, 0] if lens_lex.shape[0] else \
                np.zeros(0, dtype=np.uint8)
            for letter in range(ooc.ALPHABET_SIZE):
                b0 = int(np.searchsorted(firsts, ord("a") + letter))
                b1 = int(np.searchsorted(firsts, ord("a") + letter + 1))
                if b1 > b0:
                    df_order[b0:b1] = b0 + ooc.emit_order(df_lex[b0:b1])
            run_files = [spill_mod.SpillFile(p) for p in run_paths]
            try:
                doc_lens = ooc.doc_lengths(run_files, max_doc_id)
            finally:
                for f in run_files:
                    f.close()
            art_bytes = artifact_mod.pack(
                artifact_mod.artifact_path(out_dir),
                term_blob=term_blob, term_offsets=term_offsets,
                df=df_lex, post_offsets=post_off,
                postings=post_all[idx], df_order=df_order,
                max_doc_id=int(max_doc_id), width=width_g,
                tf=tf_all[idx], doc_lens=doc_lens)
            t1 = time.perf_counter()
            if trace is not None:
                trace.span("artifact_pack", t0, t1)
            timer.count("artifact_bytes", int(art_bytes))
            timer.count("artifact_build_ms", round((t1 - t0) * 1e3, 3))

        spill_mod.remove_dir(sdir)
        return {
            "mstats": {
                "documents": sum(run["docs"] for run in good_runs),
                "tokens": sum(run["raw_tokens"] for run in good_runs),
                "unique_terms": sum(shard_vocab),
                "unique_pairs": sum(shard_pairs),
                "lines_written": sum(shard_vocab),
                "merge_ms": merge_wall_ms,
            },
            "emit_ms": emit_ms,
            "emit_bytes": emit_bytes,
            "audit_s": audit_s,
            "build_shards": scheduler.term_shard_balance(shard_pairs),
            "runs_merged": len(good_runs),
            "runs_quarantined": quarantined,
            "bytes_spilled": sum(run["bytes"] for run in all_runs),
        }

    # -- TPU backend ---------------------------------------------------

    def _tokenize_or_resume(self, manifest: Manifest, timer: PhaseTimer):
        ckpt = self.config.checkpoint_path
        fp = checkpoint.manifest_fingerprint(manifest) if ckpt is not None else ""
        if ckpt is not None and os.path.exists(ckpt):
            try:
                with timer.phase("resume"):
                    corpus = checkpoint.load_pairs(ckpt, expect_fingerprint=fp)
                timer.count("resumed_from", ckpt)
                return corpus, 0
            except checkpoint.CheckpointCorrupt:
                # resume='auto': a torn/garbage checkpoint must not wedge
                # the rerun — quarantine it and tokenize fresh.  Version
                # and fingerprint mismatches stay hard ValueErrors in
                # both modes (a WRONG checkpoint is not a damaged one).
                if self.config.resume != "auto":
                    raise
                timer.count("quarantined_checkpoint",
                            checkpoint.quarantine(ckpt))
        threads = self.config.resolved_host_threads()
        timer.count("host_threads", threads)
        with timer.phase("load"):
            contents, doc_ids = load_documents(manifest)
        with timer.phase("tokenize"):
            corpus = tokenize(contents, doc_ids, use_native=self.config.use_native,
                              dedup_pairs=True, num_threads=threads)
        if ckpt is not None:
            with timer.phase("checkpoint"):
                checkpoint.save_pairs(ckpt, corpus, fingerprint=fp)
        return corpus, len(contents)

    def _run_tpu_streaming(self, manifest: Manifest, out_dir: str,
                           timer: PhaseTimer) -> dict:
        """Windowed pipeline for corpora larger than host/device memory.

        Host memory stays O(window + vocab); device memory O(window +
        unique pairs).  Byte-identical output to the one-shot path
        (tests/test_streaming.py).  ``checkpoint_path`` is ignored here
        — the accumulator itself is the evolving map-phase state.
        On a mesh (device_shards > 1) the accumulator is hash-sharded
        per owner (parallel/dist_streaming.py) — BASELINE config 5's
        streaming-on-a-mesh regime.
        """
        import types

        from ..corpus.manifest import iter_document_chunks
        from ..ops.streaming import StreamingIndexEngine
        from ..text.streaming import StreamingTokenizer

        cfg = self.config
        if self._num_shards() > 1:
            return self._run_tpu_streaming_dist(manifest, out_dir, timer)
        max_doc_id = len(manifest)
        threads = cfg.resolved_host_threads()
        timer.count("host_threads", threads)
        tok = StreamingTokenizer(use_native=cfg.use_native, num_threads=threads)
        eng = StreamingIndexEngine(
            max_doc_id=max_doc_id, window_pad=cfg.pad_multiple)
        docs_loaded = raw_tokens = pairs_fed = 0
        vocab_curve: list[int] = []   # unique terms after each window —
        # the growth curve the real-text regime exists to exercise
        # (corpus/realtext.py; VERDICT r4 #6)
        profile = _profile_ctx(cfg.profile_dir)
        with timer.phase("stream"), profile:
            for contents, ids in iter_document_chunks(manifest, cfg.stream_chunk_docs):
                chunk = tok.feed(contents, ids)
                docs_loaded += len(contents)
                raw_tokens += chunk.raw_tokens
                pairs_fed += int(chunk.prov_term_ids.shape[0])
                eng.feed(chunk.prov_term_ids, chunk.doc_ids, tok.vocab_size)
                vocab_curve.append(tok.vocab_size)
        vocab, remap, letters = tok.finalize()
        vocab_size = int(vocab.shape[0])
        timer.count("documents", docs_loaded)
        timer.count("tokens", raw_tokens)
        timer.count("unique_terms", vocab_size)
        timer.count("vocab_curve", vocab_curve)
        timer.count("stream_windows", eng.windows_fed)
        timer.count("accumulator_capacity", eng.capacity)
        timer.count("accumulator_mode", eng.mode)

        if pairs_fed == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()

        with timer.phase("device_index"):
            out = eng.finalize(remap, letters, vocab_size)
            for v in out.values():
                v.copy_to_host_async()
        with timer.phase("fetch"):
            host = {k: np.asarray(v) for k, v in out.items()}
            host["num_unique"] = int(host["num_unique"])
        corpus_view = types.SimpleNamespace(vocab=vocab, letter_of_term=letters)
        return self._emit_and_report(
            corpus_view, host, out_dir, timer, vocab_size, max_doc_id)

    def _run_tpu_streaming_dist(self, manifest: Manifest, out_dir: str,
                                timer: PhaseTimer) -> dict:
        """Streaming + mesh: per-window ICI shuffle into hash-sharded
        bounded accumulators (parallel/dist_streaming.py).  Per-chip
        memory is O(unique pairs / n); output byte-identical to every
        other path (tests/test_dist_streaming.py)."""
        import types

        from ..corpus.manifest import iter_document_chunks
        from ..parallel.dist_streaming import DistStreamingIndexEngine
        from ..text.streaming import StreamingTokenizer

        cfg = self.config
        num_shards = self._num_shards()
        mesh = make_mesh(num_shards)
        max_doc_id = len(manifest)
        stride = max_doc_id + 2
        threads = cfg.resolved_host_threads()
        timer.count("host_threads", threads)
        timer.count("device_shards", num_shards)
        tok = StreamingTokenizer(use_native=cfg.use_native, num_threads=threads)
        eng = DistStreamingIndexEngine(
            max_doc_id=max_doc_id, mesh=mesh, window_pad=cfg.pad_multiple)
        docs_loaded = raw_tokens = 0
        vocab_curve: list[int] = []
        profile = _profile_ctx(cfg.profile_dir)
        with timer.phase("stream"), profile:
            for contents, ids in iter_document_chunks(manifest, cfg.stream_chunk_docs):
                chunk = tok.feed(contents, ids)
                docs_loaded += len(contents)
                raw_tokens += chunk.raw_tokens
                eng.feed(chunk.prov_term_ids, chunk.doc_ids, tok.vocab_size)
                vocab_curve.append(tok.vocab_size)
        with timer.phase("finalize_vocab"):
            vocab, remap, letters = tok.finalize()
        vocab_size = int(vocab.shape[0])
        timer.count("documents", docs_loaded)
        timer.count("tokens", raw_tokens)
        timer.count("unique_terms", vocab_size)
        timer.count("vocab_curve", vocab_curve)
        timer.count("stream_windows", eng.windows_fed)
        timer.count("accumulator_capacity_per_owner", eng.capacity)
        timer.count("accumulator_mode", eng.mode)
        timer.count("merge_retries", eng.merge_retries)

        dist_stats: dict = {}
        with timer.phase("fetch"):
            mode, rows = eng.finalize(stats=dist_stats)
        for k, v in dist_stats.items():
            timer.count(k, v)
        sizes = [(r[0].size if mode == "pairs" else r.size)
                 for r in rows.values()]
        num_pairs = int(sum(sizes))
        if num_pairs == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()

        # vocab-scale host views in prov space, then the O(N) owner-run
        # merge (same math as the pipelined dist tail)
        if mode == "pairs":
            terms = np.concatenate(
                [r[0].astype(np.int64) for r in rows.values()])
        else:
            terms = np.concatenate([r // stride for r in rows.values()])
        df_prov = np.bincount(terms, minlength=vocab_size).astype(np.int64)
        offsets_prov = np.cumsum(df_prov) - df_prov
        if mode == "pairs":
            postings = dist_engine.merge_owner_pair_runs(
                rows.values(), offsets_prov, num_pairs)
        else:
            postings = dist_engine.merge_owner_runs(
                rows.values(), stride, offsets_prov, num_pairs)
        prov_of_rank = np.empty(vocab_size, dtype=np.int64)
        prov_of_rank[remap] = np.arange(vocab_size)
        df_rank = df_prov[prov_of_rank]
        order, _ = engine.host_order_offsets(letters, df_rank)
        host = {
            "df": df_rank, "order": order,
            "offsets": offsets_prov[prov_of_rank],
            "postings": postings, "num_unique": num_pairs,
        }
        corpus_view = types.SimpleNamespace(vocab=vocab, letter_of_term=letters)
        return self._emit_and_report(
            corpus_view, host, out_dir, timer, vocab_size, max_doc_id)

    def _num_shards(self) -> int:
        cfg = self.config
        return (
            cfg.device_shards if cfg.device_shards is not None
            else len(jax.devices())
        )

    def _pipelined_eligible(self, manifest: Manifest) -> bool:
        """Whether the provisional-key pipelined fast path applies.

        It needs the native incremental tokenizer and none of the
        features that require the token arrays on host (checkpointing,
        skew stats) or the bounded-memory streaming engine.  Single-chip
        additionally needs uint16 postings (doc ids < 0xFFFF); the
        multi-chip variant fetches int32 and has no doc cap."""
        from .. import native

        cfg = self.config
        return (
            cfg.pipeline_chunk_docs != 0
            and cfg.use_native
            and cfg.stream_chunk_docs is None
            and cfg.checkpoint_path is None
            and not cfg.collect_skew_stats
            and (self._num_shards() > 1 or len(manifest) <= 0xFFFE)
            and native.available()
        )

    def _run_tpu_pipelined(self, manifest: Manifest, out_dir: str,
                           timer: PhaseTimer) -> dict:
        """Pipelined fast path: uploads overlap tokenization.

        The reference pays its host<->"device" cost per token (stdio
        locks on shared spill files, main.c:116); the one-shot path
        below pays it once but serially *after* tokenizing.  Here the
        native tokenizer emits packed ``prov_id * stride + doc_id``
        keys per document window, and each window's keys start their
        async host->device DMA immediately — provisional ids are stable
        at first occurrence, so the device programs never wait for the
        final vocab.  After the last window, one dispatch + one
        device->host fetch is the entire critical path; emit order, df
        and offsets are resolved host-side in prov space (vocab-sized
        work) from the combiner's counts.

        Single chip, the finalize program is one sort
        (ops/engine.sort_prov_chunks); on a mesh, windows upload
        *sharded* and finalize is a hash-bucket ``all_to_all`` +
        owner-side sort (parallel/dist_engine.dist_sort_prov_windows).
        """
        from .. import native
        from ..corpus.manifest import prefetch_document_ranges
        from ..corpus.scheduler import plan_contiguous_windows

        cfg = self.config
        max_doc_id = len(manifest)
        stride = max_doc_id + 2
        num_shards = self._num_shards()
        mesh = make_mesh(num_shards) if num_shards > 1 else None
        # Auto = two windows, byte-balanced by the scheduler (the
        # reference's greedy size cut, main.c:307-323): window 1's upload
        # DMA flushes while window 2 tokenizes; on the round-3 chip link
        # this beat both one-shot (everything serialized after tokenize)
        # and many small windows (per-transfer overhead compounds).
        if cfg.pipeline_chunk_docs:
            n = len(manifest)
            windows = tuple(
                (s, min(s + cfg.pipeline_chunk_docs, n))
                for s in range(0, n, cfg.pipeline_chunk_docs))
        else:
            windows = plan_contiguous_windows(manifest, min(2, max(len(manifest), 1)))
        threads = cfg.resolved_host_threads()
        timer.count("host_threads", threads)
        # scheduling observability (the reference logs its mapper ranges,
        # main.c:327): per-window byte loads + imbalance ratio
        from ..corpus.scheduler import window_balance_stats

        wstats = window_balance_stats(manifest, windows)
        timer.count("window_plan_bytes", wstats["bytes_per_shard"])
        timer.count("window_imbalance", wstats["max_over_mean"])
        # Window padding granule; sharded windows must also split evenly
        # over the mesh (lcm, not product: a power-of-two granule on a
        # power-of-two mesh needs no extra padding).
        granule = math.lcm(
            min(1 << 14, self.config.pad_multiple), max(num_shards, 1))
        chunks_dev = []
        num_pairs = docs_loaded = keys_capacity = 0
        stream = native.NativeKeyStream(stride, num_threads=threads)
        try:
            with timer.phase("tokenize_feed"):
                for contents, ids in prefetch_document_ranges(manifest, windows):
                    docs_loaded += len(contents)
                    if mesh is None:
                        # the native scan assembles the half-bandwidth
                        # [terms | docs] uint16 upload buffer directly
                        # (int32 keys when prov ids outgrow uint16 —
                        # one gate, owned by mri_stream_feed_u16)
                        mode, buf, nvalid, _ = stream.feed_u16(
                            contents, ids, granule=granule)
                        if nvalid == 0:
                            continue
                        if mode == "u16":
                            padded = buf.shape[0] // 2
                        else:
                            padded = _round_up(nvalid, granule)
                            keys = buf
                            buf = np.full(padded, K.INT32_MAX, dtype=np.int32)
                            buf[:nvalid] = keys
                        chunks_dev.append(jax.device_put(buf))  # async DMA
                    else:
                        keys, _ = stream.feed(contents, ids)
                        nvalid = int(keys.size)
                        if nvalid == 0:
                            continue
                        padded = _round_up(nvalid, granule)
                        buf = np.full(padded, K.INT32_MAX, dtype=np.int32)
                        buf[:nvalid] = keys
                        chunks_dev.append(jax.device_put(
                            buf, sharding(mesh, shard_spec())))
                    keys_capacity += padded
                    num_pairs += nvalid
            with timer.phase("finalize_vocab"):
                (vocab, letters, remap, df_prov, raw_tokens, _,
                 emit_order) = stream.finalize()
        finally:
            stream.close()

        vocab_size = int(vocab.shape[0])
        timer.count("documents", docs_loaded)
        timer.count("tokens", raw_tokens)
        timer.count("unique_terms", vocab_size)
        timer.count("device_shards", max(num_shards, 1))
        timer.count("upload_windows", len(chunks_dev))
        if num_pairs == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()

        profile = _profile_ctx(self.config.profile_dir)
        # Emit order / offsets in *prov* space from the combiner's df
        # counts: postings are grouped by prov id, so per-rank views
        # just indirect through rank -> prov.
        def host_views():
            prov_of_rank = np.empty(vocab_size, dtype=np.int64)
            prov_of_rank[remap] = np.arange(vocab_size)
            df64 = df_prov.astype(np.int64)
            offsets_prov = np.cumsum(df64) - df64
            df_rank = df64[prov_of_rank]
            off_rank = offsets_prov[prov_of_rank]
            # emit order came from native finalize (C++ per-letter
            # stable sort) — no vocab-scale lexsort on this path
            return df_rank, off_rank, emit_order, offsets_prov, prov_of_rank

        if mesh is None:
            nfetch = min(keys_capacity, _round_up(num_pairs, 1 << 14))
            with timer.phase("device_index"), profile:
                post_dev = engine.sort_prov_chunks(
                    tuple(chunks_dev), stride=stride, out_size=nfetch)
                post_dev.copy_to_host_async()
                # overlapped with the in-flight sort + D2H
                df_rank, off_rank, order, _, _ = host_views()
                if self.config.profile_dir:
                    post_dev.block_until_ready()
            with timer.phase("fetch"):
                postings = np.asarray(post_dev)
        elif cfg.emit_ownership == "letter":
            df_rank, off_rank, order, offsets_prov, prov_of_rank = host_views()
            return self._emit_per_owner(
                chunks_dev, stride=stride, mesh=mesh, vocab=vocab,
                letters=letters, remap=remap, df_prov=df_prov, order=order,
                df_rank=df_rank, prov_of_rank=prov_of_rank, out_dir=out_dir,
                timer=timer, vocab_size=vocab_size, max_doc_id=max_doc_id,
                num_pairs=num_pairs, profile=profile)
        else:
            df_rank, off_rank, order, offsets_prov, _ = host_views()
            # dispatch + exchange + fetch + host merge in one blocking
            # call; keep it all inside the profiled device phase
            dist_stats: dict = {}
            with timer.phase("device_index"), profile:
                postings = dist_engine.dist_sort_prov_windows(
                    chunks_dev, stride=stride, mesh=mesh,
                    offsets_prov=offsets_prov, num_pairs=num_pairs,
                    stats=dist_stats)
            for k, v in dist_stats.items():
                timer.count(k, v)
        host = {
            "df": df_rank, "order": order, "offsets": off_rank,
            "postings": postings, "num_unique": num_pairs,
        }
        import types

        corpus_view = types.SimpleNamespace(vocab=vocab, letter_of_term=letters)
        return self._emit_and_report(
            corpus_view, host, out_dir, timer, vocab_size, max_doc_id)

    def _emit_per_owner(self, chunks_dev, *, stride, mesh, vocab, letters,
                        remap, df_prov, order, df_rank, prov_of_rank,
                        out_dir, timer, vocab_size, max_doc_id, num_pairs,
                        profile) -> dict:
        """Per-owner letter emission (the multi-host emit strategy).

        One ``all_to_all`` keyed by *letter owner* — the reference's
        reducer ownership (contiguous letter ranges incl. the R > 26
        degenerate collapse, main.c:129-150) via
        corpus/scheduler.plan_letter_ranges — then every owner emits
        only its own letter files from its own pairs.  No host ever
        holds or merges the global postings array.  On a real pod each
        host runs only its owner's iteration (``jax.process_index``);
        this single-controller loop simulates every host.
        """
        from ..corpus.scheduler import owner_of_letter_table

        n = mesh.devices.size
        ranges, owner_of_letter = owner_of_letter_table(n)
        letters = np.asarray(letters)
        letters_prov = letters[np.asarray(remap)]
        owner_of_prov = owner_of_letter[letters_prov]

        dist_stats: dict = {}
        with timer.phase("device_index"), profile:
            rows = dist_engine.dist_letter_windows(
                chunks_dev, owner_of_prov, stride=stride, mesh=mesh,
                stats=dist_stats)
        for k, v in dist_stats.items():
            timer.count(k, v)

        df64 = df_prov.astype(np.int64)
        lines = 0
        with timer.phase("emit"):
            for o, row in sorted(rows.items()):
                df_o = np.where(owner_of_prov == o, df64, 0)
                offsets_local = np.cumsum(df_o) - df_o
                postings_o = dist_engine.merge_owner_runs(
                    [row], stride, offsets_local, int(df_o.sum()))
                stats_o = formatter.emit_index(
                    out_dir, vocab=vocab, letter_of_term=letters,
                    order=order, df=df_rank,
                    offsets=offsets_local[prov_of_rank],
                    postings=postings_o, max_doc_id=max_doc_id,
                    letter_range=ranges[o], backend=self._emit_backend())
                lines += stats_o["lines_written"]
        timer.count("emit_ownership", "letter")
        timer.count("letter_owners", n)
        timer.count("unique_pairs", num_pairs)
        timer.count("lines_written", lines)
        return timer.report()

    def _run_tpu_overlap(self, manifest: Manifest, out_dir: str,
                         timer: PhaseTimer) -> dict:
        """Windowed overlap plan: device round trips hide under the scan.

        The pipelined plan still serializes its one device->host fetch
        *after* tokenization ends; on a high-RTT host<->device link
        that round trip dominates the run.  Here the corpus is cut into
        contiguous byte-weighted doc windows (corpus/scheduler.plan_fraction_windows):
        each *device* window's packed provisional keys are uploaded,
        sorted and fetched asynchronously the moment the window is
        scanned — those chains progress in the background while the host
        scans later windows — and the last ``overlap_tail_fraction`` of
        bytes never goes to the device at all: its keys are sorted with
        numpy while the fetches are still in flight.  Windows are
        contiguous ascending doc ranges and a window's sorted keys give
        docs ascending per term, so each term's global postings list is
        the concatenation of its per-window segments in window order —
        the native multi-run emit renders them with no merge pass
        (native/tokenizer.cc mri_emit_runs).

        The reference's strict map->reduce join barrier (main.c:367-369)
        forbids exactly this overlap; dissolving it — while keeping the
        output byte-identical — is the point of the redesign.
        """
        from .. import native
        from ..corpus.manifest import prefetch_document_ranges
        from ..corpus.scheduler import plan_fraction_windows, window_balance_stats

        cfg = self.config
        max_doc_id = len(manifest)
        stride = max_doc_id + 2
        tail_f = cfg.overlap_tail_fraction
        # Device windows when there is enough corpus to cut: with two,
        # the first window's fetch is issued as early as possible and
        # the second balances upload sizes; with one, half the dispatch
        # RPCs (wins when per-call link overhead dominates).
        dev_f = 1.0 - tail_f
        if len(manifest) >= 8 and cfg.overlap_device_windows == 2:
            split = cfg.overlap_window_split
            fractions = (split * dev_f, (1.0 - split) * dev_f, tail_f)
        else:
            fractions = (dev_f, tail_f)
        windows = plan_fraction_windows(manifest, fractions)
        threads = cfg.resolved_host_threads()
        timer.count("host_threads", threads)
        wstats = window_balance_stats(manifest, windows)
        timer.count("window_plan_bytes", wstats["bytes_per_shard"])
        granule = min(1 << 14, cfg.pad_multiple)

        dev_handles: list[tuple] = []  # (in-flight fetch, nvalid)
        dev_snaps: list[tuple] = []    # (df before, df after) per window
        prev_snap = np.zeros(0, np.int32)
        tail_keys = None
        num_pairs = docs_loaded = 0
        # the trace must span dispatch THROUGH fetch — the device sorts
        # and D2H transfers this plan overlaps complete long after the
        # feed loop ends (closed in the finally below)
        trace = contextlib.ExitStack()
        if cfg.profile_dir:
            trace.enter_context(jax.profiler.trace(cfg.profile_dir))
        stream = native.NativeKeyStream(stride, num_threads=threads)
        try:
            with timer.phase("tokenize_feed"):
                for wi, (contents, ids) in enumerate(
                        prefetch_document_ranges(manifest, windows)):
                    docs_loaded += len(contents)
                    if wi == len(windows) - 1:
                        keys, _ = stream.feed(contents, ids)
                        num_pairs += int(keys.size)
                        if keys.size:
                            tail_keys = keys
                        continue
                    # device window: the native scan assembles the
                    # [terms | docs] uint16 upload buffer directly
                    mode, buf, nvalid, _ = stream.feed_u16(
                        contents, ids, granule=granule)
                    num_pairs += nvalid
                    if nvalid == 0:
                        continue
                    if mode != "u16":  # prov ids outgrew uint16
                        keys = buf
                        padded = _round_up(nvalid, granule)
                        buf = np.full(padded, K.INT32_MAX, dtype=np.int32)
                        buf[:nvalid] = keys
                    post = engine.sort_prov_chunks(
                        (jax.device_put(buf),), stride=stride,
                        out_size=_round_up(nvalid, granule))
                    post.copy_to_host_async()
                    dev_handles.append((post, nvalid))
                    # per-window per-term pair counts come from combiner
                    # df snapshot diffs (vocab-scale) — not token-scale
                    # bincounts over the window's term ids
                    snap = stream.df_snapshot(
                        hint=max(1 << 16, prev_snap.shape[0] * 2))
                    dev_snaps.append((prev_snap, snap))
                    prev_snap = snap
            with timer.phase("finalize_vocab"):
                (vocab, letters, remap, df_prov, raw_tokens, _,
                 emit_order) = stream.finalize()
        except BaseException:
            trace.close()
            raise
        finally:
            stream.close()

        vocab_size = int(vocab.shape[0])
        timer.count("documents", docs_loaded)
        timer.count("tokens", raw_tokens)
        timer.count("unique_terms", vocab_size)
        timer.count("upload_windows", len(dev_handles))
        timer.count("overlap_tail_fraction", tail_f)
        dev_pairs = sum(n for _, n in dev_handles)
        timer.count("device_pairs", dev_pairs)
        timer.count("unique_pairs", num_pairs)
        timer.count("device_shards", 1)
        if num_pairs == 0:
            trace.close()
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()

        with timer.phase("host_tail"):
            if tail_keys is not None and tail_keys.size:
                tail_sorted = np.sort(tail_keys)
                tail_docs = (tail_sorted % stride).astype(np.uint16)
            else:
                tail_docs = np.empty(0, np.uint16)

        with timer.phase("host_views"):
            # All vocab-scale, all while the device fetches are in
            # flight: emit order, plus per-run rank-space segment
            # tables from combiner-snapshot diffs (nothing token-scale
            # survives on the host).
            prov_of_rank = np.empty(vocab_size, dtype=np.int64)
            prov_of_rank[remap] = np.arange(vocab_size)
            df_rank = df_prov.astype(np.int64)[prov_of_rank]
            # emit order came from native finalize (C++ per-letter
            # stable sort) — no vocab-scale lexsort on this path
            order = emit_order

            def run_meta(prev, cur):
                c = np.zeros(vocab_size, np.int64)
                c[: cur.shape[0]] = cur
                c[: prev.shape[0]] -= prev
                off = np.cumsum(c) - c
                return off[prov_of_rank], c[prov_of_rank]

            runs_meta = [run_meta(prev, cur) for prev, cur in dev_snaps]
            # the tail window's counts: final combiner df minus the
            # last device-window snapshot
            tail_meta = run_meta(prev_snap, df_prov.astype(np.int64))

        with timer.phase("fetch"):
            fetched = [np.asarray(post) for post, _ in dev_handles]
        trace.close()

        with timer.phase("emit"):
            runs = [
                (arr, off_rank, c_rank)
                for arr, (off_rank, c_rank) in zip(fetched, runs_meta)
            ]
            runs.append((tail_docs, *tail_meta))
            bytes_written = native.emit_native_runs(out_dir, vocab, order, runs)
        timer.count("lines_written", vocab_size)
        timer.count("bytes_written", bytes_written)
        return timer.report()

    def _run_tpu_device_tokenize(self, manifest: Manifest, out_dir: str,
                                 timer: PhaseTimer) -> dict:
        """All-device engine: raw bytes up, finished index down.

        The whole map phase — the reference's mapper tokenize/clean/emit
        (main.c:85-124) AND its reducer dedup/df/sort (main.c:126-242) —
        runs as one XLA program over the corpus byte tensor
        (ops/device_tokenizer.py).  The host only loads files, decodes
        the fetched unique word rows, and renders the letter files.
        Exact by construction (words are sorted byte rows, not hashes);
        a cleaned token longer than ``device_tokenize_width`` raises
        WidthOverflow and the caller restarts on the host-scan path.
        """
        from ..ops import device_tokenizer as DT

        cfg = self.config
        width = cfg.device_tokenize_width
        max_doc_id = len(manifest)
        with timer.phase("load"):
            contents, doc_ids = load_documents(manifest)
        num_docs = len(contents)
        total = sum(len(c) for c in contents)
        timer.count("documents", num_docs)
        timer.count("device_tokenize_width", width)
        if num_docs == 0 or total == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()

        profile = _profile_ctx(cfg.profile_dir)
        with profile:
            with timer.phase("feed"):
                padded = _round_up(total, cfg.pad_multiple)
                buf, ends, _ = _pack_window(
                    contents, doc_ids, padded, num_docs)
                # Exact token count (DT.count_token_starts mirrors the
                # device classifier): a snug tok_cap shrinks every
                # device array ~2.5x vs the worst-case bound; note
                # N//2+1 is NOT a valid bound (doc boundaries split
                # tokens, so up to one token per byte).
                # one host pass: exact token count (snug tok_cap) and
                # exact max cleaned length — abort a doomed launch
                # before paying for it, and skip radix passes over
                # provably all-zero word columns (sort_cols)
                tok_count, host_max_len = DT.host_token_stats(buf, ends)
                tok_cap = _round_up(tok_count + 1, 1 << 15)
                if host_max_len > width:
                    raise DT.WidthOverflow(
                        f"cleaned token of {host_max_len} letters "
                        f"exceeds device_tokenize_width={width}")
                sort_cols = -(-max(host_max_len, 1) // 4)  # ceil div
                timer.count("sort_cols", sort_cols)
                out = DT.index_bytes_device(
                    jax.device_put(buf), jax.device_put(ends),
                    jax.device_put(np.asarray(doc_ids, np.int32)),
                    width=width, tok_cap=tok_cap, num_docs=num_docs,
                    sort_cols=sort_cols)
            with timer.phase("device_index"):
                num_words, num_pairs, max_len, num_tokens, num_long = (
                    int(v) for v in np.asarray(out["counts"]))
                if num_tokens + 1 > tok_cap:
                    raise AssertionError(
                        f"device token count {num_tokens} exceeded "
                        f"tok_cap {tok_cap}: host mask count diverged "
                        "from the device classifier (bug)")
                if max_len != host_max_len:
                    raise AssertionError(
                        f"device max word len {max_len} != host "
                        f"{host_max_len}: classifier divergence (bug)")
                if max_len > width:
                    raise DT.WidthOverflow(
                        f"cleaned token of {max_len} letters exceeds "
                        f"device_tokenize_width={width}")
        timer.count("unique_terms", num_words)
        timer.count("unique_pairs", num_pairs)
        timer.count("device_shards", 1)
        # raw token count is not materialized on host in this engine;
        # record the deduped pair count the device measured instead
        timer.count("tokens", num_pairs)
        return self._fetch_decode_emit_device(
            out, cap=tok_cap, num_words=num_words, num_pairs=num_pairs,
            num_long=num_long, sort_cols=sort_cols, max_doc_id=max_doc_id,
            out_dir=out_dir, timer=timer)

    def _fetch_decode_emit_device(self, out, *, cap: int, num_words: int,
                                  num_pairs: int, num_long: int,
                                  sort_cols: int, max_doc_id: int,
                                  out_dir: str,
                                  timer: PhaseTimer) -> dict:
        """Shared tail of the single-chip device engines (one-shot and
        streaming): prefix-slice fetch with transfer trimming, word-row
        decode, and the letter-file emit.

        Transfer trimming (DT.fetch_pack, ONE jitted prep program so
        the link pays one dispatch): group pairs past the host-exact
        ``sort_cols`` bound are provably all zero; tail groups ride
        SPARSELY (indices + values for only the >12-char words, the
        dense arrays rebuilt by host scatter at vocab scale); postings
        pack 3 doc ids per int32 when ids fit 10 bits, else uint16
        when they fit 16.  Every transfer is dispatched before any is
        materialized — sequential fetches would each pay the link's
        fixed RTT.
        """
        from ..ops import device_tokenizer as DT

        cfg = self.config
        width = cfg.device_tokenize_width
        if num_pairs == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()
        with timer.phase("fetch"):
            nu = min(cap, _round_up(max(num_words, 1), 1 << 13))
            npairs = min(cap, _round_up(max(num_pairs, 1), 1 << 13))
            ngroups_fetch = DT.live_groups_for(sort_cols, width)
            narrow = max_doc_id < (1 << 16)
            k = DT.doc_pack_width(max_doc_id)
            nlong = (min(nu, _round_up(num_long, 1 << 10))
                     if ngroups_fetch > 1 and num_long else 0)
            packed = DT.fetch_pack(out, nu=nu, npairs=npairs,
                                   nlong=nlong, k=k, live=ngroups_fetch,
                                   narrow=narrow)
            leaves = jax.tree_util.tree_leaves(packed)
            for a in leaves:
                a.copy_to_host_async()
            df = np.asarray(packed["df"])[:num_words].astype(np.int32)
            postings = DT.unpack_postings(packed["post"], num_pairs, k)
            g0 = tuple(np.asarray(h)[:num_words] for h in packed["g0"])
            groups = [g0] + DT.rebuild_tail_groups(
                num_words, ngroups_fetch,
                idx=(np.asarray(packed["long_idx"])[:num_long]
                     if nlong else None),
                tails=packed.get("tail", ()),
                num_long=num_long if nlong else 0)
            timer.count("fetched_bytes", sum(a.nbytes for a in leaves))
        with timer.phase("host_views"):
            vocab = DT.decode_word_groups(groups, width)
            letters = vocab.view(np.uint8).reshape(num_words, width)[:, 0] - ord("a")
            df64 = df.astype(np.int64)
            order, offsets = engine.host_order_offsets(letters, df64)
        with timer.phase("emit"):
            emit_stats = formatter.emit_index(
                out_dir, vocab=vocab, letter_of_term=letters,
                order=order, df=df64, offsets=offsets,
                postings=postings, max_doc_id=max_doc_id,
                backend=self._emit_backend(),
                artifact_path=self._artifact_path(out_dir))
        timer.count("lines_written", emit_stats["lines_written"])
        self._count_artifact_stats(timer, emit_stats)
        return timer.report()

    def _run_tpu_device_tokenize_stream(self, manifest: Manifest,
                                        out_dir: str,
                                        timer: PhaseTimer) -> dict:
        """Streaming all-device engine: doc-aligned byte windows feed a
        bounded on-device row accumulator (ops/device_streaming.py) —
        the all-device engine's larger-than-HBM story, same exactness
        contract (WidthOverflow aborts to the host path BEFORE the
        offending window is fed)."""
        from ..corpus.manifest import iter_document_chunks
        from ..ops import device_streaming as DS
        from ..ops import device_tokenizer as DT

        cfg = self.config
        width = cfg.device_tokenize_width
        max_doc_id = len(manifest)
        timer.count("device_tokenize_width", width)
        timer.count("device_shards", 1)
        timer.count("documents", len(manifest))
        engine_s = DS.DeviceStreamEngine(width=width)
        fed_tokens = 0

        # Crash-resumable stream (config.stream_checkpoint): restore
        # the verified accumulator prefix and skip already-folded
        # windows.  iter_document_chunks is deterministic for a given
        # (manifest, chunk size), so window index identifies position.
        ckpt_path = cfg.stream_checkpoint
        resume_from = 0
        if ckpt_path:
            stream_fp = checkpoint.stream_fingerprint(
                manifest, width=width, chunk_docs=cfg.stream_chunk_docs,
                pad_multiple=cfg.pad_multiple)
            if os.path.exists(ckpt_path):
                try:
                    state = checkpoint.load_stream_state(ckpt_path,
                                                         stream_fp)
                except checkpoint.CheckpointCorrupt:
                    # resume='auto': a SIGKILL can land mid-save; the
                    # write is atomic (tmp + rename) so this normally
                    # never fires, but disk corruption or a foreign
                    # file at the path must not wedge the rerun
                    if cfg.resume != "auto":
                        raise
                    timer.count("quarantined_checkpoint",
                                checkpoint.quarantine(ckpt_path))
                else:
                    engine_s.restore(state)
                    fed_tokens = state["fed_tokens"]
                    # loop position, NOT engine windows_fed: the engine
                    # skips empty (tok_count == 0) windows, so its count
                    # can run behind the iteration index
                    resume_from = state["window_pos"]
                    timer.count("resumed_from_window", resume_from)
        # test hook: simulate the round-3 on-chip TPU worker crash
        # (round 3) at a deterministic stream position
        crash_after = envknobs.get("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS")
        total_windows = -(-len(manifest) // cfg.stream_chunk_docs)
        ckpt_seconds, ckpt_saves = 0.0, 0
        ckpt_ms_per_save: list[float] = []
        ckpt_skipped_projection_s: list[float] = []
        # Snapshot-tax budget (VERDICT r4 weak #3): each snapshot
        # drains the merge pipeline and fetches the full-capacity
        # accumulator over the link — hundreds of MB at 1M-doc scale,
        # plausibly minutes per save on a slow link.  Project the cost
        # from the accumulator size BEFORE paying it and STRETCH the
        # cadence when it would blow the budget: up to `stretch`
        # consecutive cadence points are
        # skipped, then one save is forced — so an early
        # fixed-cost-dominated save that mis-calibrates the rate can
        # delay later checkpoints but never lock them out (the forced
        # save re-measures the true rate), and a crash mid-stream
        # always has a checkpoint at most stretch+1 cadence intervals
        # old.  The rate re-calibrates from every save actually
        # measured (so a fast local link stops skipping).
        ckpt_budget_s = envknobs.get("MRI_TPU_CKPT_BUDGET_S")
        ckpt_rate_mbps = envknobs.get("MRI_TPU_CKPT_LINK_MBPS")
        ckpt_stretch = envknobs.get("MRI_TPU_CKPT_STRETCH")
        ckpt_consec_skips = 0

        profile = _profile_ctx(cfg.profile_dir)
        with profile, timer.phase("stream_feed"):
            for win_i, (contents, ids) in enumerate(
                    iter_document_chunks(manifest, cfg.stream_chunk_docs),
                    start=1):
                if win_i <= resume_from:
                    continue
                total = sum(len(c) for c in contents)
                padded = _round_up(max(total, 1), cfg.pad_multiple)
                buf, ends, _ = _pack_window(
                    contents, ids, padded, max(len(contents), 1))
                ends = ends[: len(contents)]
                cnt, ml = DT.host_token_stats(buf, ends)
                if ml > width:
                    raise DT.WidthOverflow(
                        f"cleaned token of {ml} letters exceeds "
                        f"device_tokenize_width={width}")
                engine_s.feed(buf, ends, np.asarray(ids, np.int32),
                              tok_count=cnt, max_len=ml)
                fed_tokens += cnt
                # skip the checkpoint that would land on the LAST
                # window: finalize deletes it moments later
                if (ckpt_path and win_i < total_windows
                        and (win_i - resume_from)
                        % cfg.stream_checkpoint_every == 0):
                    nbytes = engine_s.snapshot_nbytes
                    projected = nbytes / (ckpt_rate_mbps * 1e6)
                    if (projected > ckpt_budget_s
                            and ckpt_consec_skips < ckpt_stretch):
                        ckpt_consec_skips += 1
                        ckpt_skipped_projection_s.append(
                            round(projected, 2))
                    else:
                        ckpt_consec_skips = 0
                        t0 = time.perf_counter()
                        snap = engine_s.snapshot()
                        if snap is not None:
                            checkpoint.save_stream_state(
                                ckpt_path, snap, fed_tokens, win_i,
                                stream_fp)
                            dt = time.perf_counter() - t0
                            ckpt_seconds += dt
                            ckpt_saves += 1
                            ckpt_ms_per_save.append(round(dt * 1e3, 2))
                            moved = snap.get("fetched_nbytes", nbytes)
                            if dt > 1e-3 and moved:
                                # measured whole-save rate (drain +
                                # fetch + write) over the bytes the
                                # fetch ACTUALLY moved, floored so one
                                # outlier can't lock out every later
                                # save
                                ckpt_rate_mbps = max(
                                    moved / dt / 1e6, 0.5)
                if crash_after and win_i >= crash_after:
                    raise RuntimeError(
                        "injected stream crash after window "
                        f"{win_i} "
                        "(MRI_TPU_STREAM_CRASH_AFTER_WINDOWS)")
                # fault hook (faults.py sigkill:window=K): hard-kill
                # THIS process at the window boundary, after any
                # checkpoint save above — the crash-safety e2e proves
                # a rerun with resume='auto' is byte-identical
                inj = faults.active()
                if inj is not None:
                    inj.on_window_boundary(win_i)
        if ckpt_saves:
            # inside stream_feed's wall time — recorded separately so
            # checkpointed docs/s is comparable to uncheckpointed runs
            # (each snapshot drains the 2-deep merge pipeline and
            # fetches the accumulator over the link)
            timer.count("checkpoint_saves", ckpt_saves)
            timer.count("checkpoint_ms", round(ckpt_seconds * 1e3, 2))
            timer.count("checkpoint_ms_per_save", ckpt_ms_per_save)
        if ckpt_skipped_projection_s:
            timer.count("checkpoint_skips", len(ckpt_skipped_projection_s))
            timer.count("checkpoint_skipped_projection_s",
                        ckpt_skipped_projection_s)
            timer.count("checkpoint_budget_s", ckpt_budget_s)
        timer.count("stream_windows", engine_s.windows_fed)
        timer.count("accumulator_capacity", engine_s.capacity)
        if engine_s.rows_curve:
            # resolved unique-row counts per merge — the device-stream
            # analogue of the host engines' vocab_curve (trails the
            # window count by the still-in-flight merges)
            timer.count("unique_rows_curve", engine_s.rows_curve)
        if engine_s.windows_fed == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()
        host_max_len = engine_s.max_word_len
        sort_cols = -(-max(host_max_len, 1) // 4)  # ceil div
        timer.count("sort_cols", sort_cols)

        with timer.phase("device_index"):
            out = engine_s.finalize()
            num_words, num_pairs, num_long = (
                int(v) for v in np.asarray(out["counts"]))
        if ckpt_path and os.path.exists(ckpt_path):
            # the stream completed; a stale checkpoint would make the
            # next identical run skip every window and re-finalize
            os.remove(ckpt_path)
        timer.count("unique_terms", num_words)
        timer.count("unique_pairs", num_pairs)
        timer.count("tokens", fed_tokens)
        return self._fetch_decode_emit_device(
            out, cap=int(out["df"].shape[0]), num_words=num_words,
            num_pairs=num_pairs, num_long=num_long, sort_cols=sort_cols,
            max_doc_id=max_doc_id, out_dir=out_dir, timer=timer)

    def _run_tpu_device_tokenize_dist(self, manifest: Manifest, out_dir: str,
                                      timer: PhaseTimer) -> dict:
        """Mesh all-device engine: sharded raw bytes in, index out.

        Each chip tokenizes a contiguous doc range's bytes locally; one
        ``all_to_all`` exchanges whole word rows by content hash; owners
        dedup/count their terms (parallel/dist_device_tokenizer.py).
        The host decodes per-owner vocab blocks and merges at vocab
        scale — token-scale data never re-sorts on host.
        """
        from ..corpus.manifest import iter_document_ranges
        from ..corpus.scheduler import plan_contiguous_windows
        from ..ops import device_tokenizer as DT
        from ..parallel import dist_device_tokenizer as DDT

        cfg = self.config
        width = cfg.device_tokenize_width
        n = self._num_shards()
        mesh = make_mesh(n)
        max_doc_id = len(manifest)
        with timer.phase("load"):
            windows = plan_contiguous_windows(manifest, n)
            shards = list(iter_document_ranges(manifest, windows))
        num_docs = sum(len(c) for c, _ in shards)
        total = sum(len(b) for c, _ in shards for b in c)
        timer.count("documents", num_docs)
        timer.count("device_shards", n)
        timer.count("device_tokenize_width", width)
        if num_docs == 0 or total == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()

        with timer.phase("feed"):
            shard_len = _round_up(
                max(max(sum(len(b) for b in c) for c, _ in shards), 1),
                cfg.pad_multiple)
            docs_cap = max(max(len(c) for c, _ in shards), 1)
            bufs, ends_l, ids_l = [], [], []
            tok_count = host_max_len = 0
            for contents, ids in shards:
                # the padded tail of ends stays at shard_len: the pad
                # region is all spaces, so those "docs" emit nothing
                buf, ends, idv = _pack_window(
                    contents, ids, shard_len, docs_cap)
                cnt, ml = DT.host_token_stats(buf, ends)
                tok_count = max(tok_count, cnt)
                host_max_len = max(host_max_len, ml)
                bufs.append(buf)
                ends_l.append(ends)
                ids_l.append(idv)
            tok_cap = _round_up(tok_count + 1, 1 << 14)
            if host_max_len > width:
                raise DT.WidthOverflow(
                    f"cleaned token of {host_max_len} letters exceeds "
                    f"device_tokenize_width={width}")
            sort_cols = -(-max(host_max_len, 1) // 4)  # ceil div
            timer.count("sort_cols", sort_cols)

        letter_mode = cfg.emit_ownership == "letter"
        owner_of_letter = ranges = None
        if letter_mode:
            from ..corpus.scheduler import owner_of_letter_table

            ranges, owner_of_letter = owner_of_letter_table(n)
            timer.count("emit_ownership", "letter")

        dist_stats: dict = {}
        with timer.phase("device_index"):
            owners, (max_len, _) = DDT.index_bytes_dist(
                bufs, ends_l, ids_l, width=width, tok_cap=tok_cap,
                mesh=mesh, stats=dist_stats, sort_cols=sort_cols,
                max_doc_id=max_doc_id, owner_of_letter=owner_of_letter)
            if max_len != host_max_len:
                raise AssertionError(
                    f"device max word len {max_len} != host "
                    f"{host_max_len}: classifier divergence (bug)")
            if max_len > width:
                raise DT.WidthOverflow(
                    f"cleaned token of {max_len} letters exceeds "
                    f"device_tokenize_width={width}")
        for k, v in dist_stats.items():
            timer.count(k, v)

        if letter_mode:
            # per-owner letter emission: owner o holds EVERY word of
            # its letter range (the reference's reducer ownership,
            # main.c:129-150, at raw-text level), so each owner's
            # block emits its own letter files with no global merge —
            # on a multi-host pod every process writes exactly its
            # addressable owners' files (tests/test_distributed.py)
            lines = 0
            with timer.phase("host_views_emit"):
                for o, ow in sorted(owners.items()):
                    if ow["num_words"] == 0:
                        formatter.emit_index(
                            out_dir, vocab=np.empty(0, "S1"),
                            letter_of_term=np.empty(0, np.int64),
                            order=np.empty(0, np.int64),
                            df=np.empty(0, np.int64),
                            offsets=np.empty(0, np.int64),
                            postings=np.empty(0, np.int32),
                            max_doc_id=max_doc_id, letter_range=ranges[o])
                        continue
                    vocab_o = DT.decode_word_groups(
                        ow["unique_groups"], width)
                    df_o = ow["df"].astype(np.int64)
                    letters_o = vocab_o.view(np.uint8).reshape(
                        ow["num_words"], width)[:, 0] - ord("a")
                    order_o = np.lexsort((vocab_o, -df_o, letters_o))
                    stats_o = formatter.emit_index(
                        out_dir, vocab=vocab_o, letter_of_term=letters_o,
                        order=order_o, df=df_o,
                        offsets=np.cumsum(df_o) - df_o,
                        postings=ow["postings"].astype(np.int32),
                        max_doc_id=max_doc_id, letter_range=ranges[o],
                        backend=self._emit_backend())
                    lines += stats_o["lines_written"]
            timer.count("letter_owners", n)
            timer.count("unique_terms",
                        sum(ow["num_words"] for ow in owners.values()))
            timer.count("unique_pairs",
                        sum(ow["num_pairs"] for ow in owners.values()))
            timer.count("lines_written", lines)
            return timer.report()

        return self._merge_emit_owner_blocks(
            owners, max_doc_id=max_doc_id, out_dir=out_dir, timer=timer)

    def _merge_emit_owner_blocks(self, owners, *, max_doc_id: int,
                                 out_dir: str, timer: PhaseTimer) -> dict:
        """Shared merged-emit tail of the mesh device engines: decode
        per-owner vocab blocks and merge at vocab scale — token-scale
        data never re-sorts on host."""
        from ..ops import device_tokenizer as DT

        cfg = self.config
        width = cfg.device_tokenize_width
        with timer.phase("host_views"):
            vocab_parts, df_parts, off_parts, post_parts = [], [], [], []
            base = 0
            for o in sorted(owners):
                ow = owners[o]
                if ow["num_words"] == 0:
                    continue
                vocab_parts.append(
                    DT.decode_word_groups(ow["unique_groups"], width))
                df_o = ow["df"].astype(np.int64)
                off_parts.append(np.cumsum(df_o) - df_o + base)
                df_parts.append(df_o)
                post_parts.append(ow["postings"].astype(np.int32))
                base += ow["num_pairs"]
            num_words = sum(len(v) for v in vocab_parts)
            num_pairs = base
            timer.count("unique_terms", num_words)
            timer.count("unique_pairs", num_pairs)
            timer.count("tokens", num_pairs)
            if num_pairs == 0:
                with timer.phase("emit"):
                    formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
                return timer.report()
            vocab = np.concatenate(vocab_parts)
            df64 = np.concatenate(df_parts)
            offsets = np.concatenate(off_parts)
            postings = np.concatenate(post_parts)
            letters = vocab.view(np.uint8).reshape(num_words, width)[:, 0] - ord("a")
            # global emit order across the owner blocks: (letter asc,
            # df desc, word asc) — the word array itself is the tiebreak
            # (owner blocks are hash-ordered, not rank-ordered)
            order = np.lexsort((vocab, -df64, letters))

        with timer.phase("emit"):
            emit_stats = formatter.emit_index(
                out_dir, vocab=vocab, letter_of_term=letters,
                order=order, df=df64, offsets=offsets,
                postings=postings, max_doc_id=max_doc_id,
                backend=self._emit_backend(),
                artifact_path=self._artifact_path(out_dir))
        timer.count("lines_written", emit_stats["lines_written"])
        self._count_artifact_stats(timer, emit_stats)
        return timer.report()

    def _run_tpu_device_tokenize_stream_dist(self, manifest: Manifest,
                                             out_dir: str,
                                             timer: PhaseTimer) -> dict:
        """Mesh streaming all-device engine: each window's raw bytes
        are sharded over the mesh, tokenized per chip, exchanged by
        content hash, and folded into bounded per-owner row
        accumulators (parallel/dist_device_streaming.py)."""
        from ..ops import device_tokenizer as DT
        from ..corpus.manifest import iter_document_chunks
        from ..parallel import dist_device_streaming as DDS

        cfg = self.config
        width = cfg.device_tokenize_width
        n = self._num_shards()
        mesh = make_mesh(n)
        max_doc_id = len(manifest)
        timer.count("device_tokenize_width", width)
        timer.count("device_shards", n)
        timer.count("documents", len(manifest))
        engine_s = DDS.DistDeviceStreamEngine(width=width, mesh=mesh)
        profile = _profile_ctx(cfg.profile_dir)
        with profile, timer.phase("stream_feed"):
            from ..corpus.scheduler import plan_contiguous_ranges

            for win_i, (contents, ids) in enumerate(iter_document_chunks(
                    manifest, cfg.stream_chunk_docs)):
                # byte-balanced contiguous doc split of this chunk —
                # the scheduler's one greedy-cut policy
                ranges_c = plan_contiguous_ranges(
                    [len(c) for c in contents], n)
                parts = [(contents[lo:hi], ids[lo:hi])
                         for lo, hi in ranges_c]
                shard_len = max(
                    max((sum(len(c) for c in cs) for cs, _ in parts),
                        default=1), 1)
                shard_len = _round_up(shard_len, cfg.pad_multiple)
                docs_cap = max(max(len(c) for c, _ in parts), 1)
                bufs, ends_l, ids_l = [], [], []
                tok_count = max_len = 0
                for contents_s, ids_s in parts:
                    buf, ends, idv = _pack_window(
                        contents_s, ids_s, shard_len, docs_cap)
                    cnt, ml = DT.host_token_stats(buf, ends)
                    tok_count = max(tok_count, cnt)
                    max_len = max(max_len, ml)
                    bufs.append(buf)
                    ends_l.append(ends)
                    ids_l.append(idv)
                if max_len > width:
                    raise DT.WidthOverflow(
                        f"cleaned token of {max_len} letters exceeds "
                        f"device_tokenize_width={width}")
                engine_s.feed(bufs, ends_l, ids_l, tok_count=tok_count,
                              max_len=max_len)
        timer.count("stream_windows", engine_s.windows_fed)
        if engine_s.windows_fed == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()
        sort_cols = -(-max(engine_s.max_word_len, 1) // 4)  # ceil div
        timer.count("sort_cols", sort_cols)

        dist_stats: dict = {}
        with timer.phase("device_index"):
            owners = engine_s.finalize(
                sort_cols=sort_cols, max_doc_id=max_doc_id,
                stats=dist_stats)
        for k, v in dist_stats.items():
            timer.count(k, v)
        return self._merge_emit_owner_blocks(
            owners, max_doc_id=max_doc_id, out_dir=out_dir, timer=timer)

    def _run_tpu(self, manifest: Manifest, out_dir: str, timer: PhaseTimer) -> dict:
        if self.config.device_tokenize:
            from ..ops.device_tokenizer import WidthOverflow

            try:
                if self.config.stream_chunk_docs is not None:
                    if self._num_shards() > 1:
                        return self._run_tpu_device_tokenize_stream_dist(
                            manifest, out_dir, timer)
                    return self._run_tpu_device_tokenize_stream(
                        manifest, out_dir, timer)
                if self._num_shards() > 1:
                    return self._run_tpu_device_tokenize_dist(
                        manifest, out_dir, timer)
                if self.config.emit_ownership == "letter":
                    raise ValueError(
                        "emit_ownership='letter' requires a multi-chip "
                        "mesh (device_shards > 1)")
                return self._run_tpu_device_tokenize(manifest, out_dir, timer)
            except WidthOverflow as e:
                # exactness guard tripped: restart on the host-scan path
                if (self.config.stream_checkpoint
                        and os.path.exists(self.config.stream_checkpoint)):
                    # the stream is abandoned for good — a stale
                    # checkpoint would make every later identical run
                    # restore, re-stream, and re-trip the overflow
                    os.remove(self.config.stream_checkpoint)
                aborted_ms = timer.total_seconds * 1e3
                self.timer = timer = PhaseTimer()
                timer.count("num_mappers", self.config.num_mappers)
                timer.count("num_reducers", self.config.num_reducers)
                timer.count("device_tokenize_fallback", str(e))
                timer.phases["aborted_device_tokenize"] = aborted_ms / 1e3
                if self.config.stream_chunk_docs is not None:
                    # a streaming config falls back to the HOST streaming
                    # engine (same bounded-memory contract)
                    return self._run_tpu_streaming(manifest, out_dir, timer)
        if self.config.emit_ownership == "letter":
            if self._num_shards() < 2:
                raise ValueError(
                    "emit_ownership='letter' requires a multi-chip mesh "
                    "(device_shards > 1)")
            if not self._pipelined_eligible(manifest):
                raise ValueError(
                    "emit_ownership='letter' requires the pipelined path "
                    "(native tokenizer available, no checkpoint/skew flags)")
        if self.config.overlap_tail_fraction is not None:
            if self._num_shards() > 1:
                raise ValueError(
                    "overlap_tail_fraction is a single-chip plan "
                    "(device_shards > 1 selects the multi-chip engine)")
            if not self._pipelined_eligible(manifest):
                # fail loudly rather than silently run a different plan
                # than the one the config names (same policy as
                # emit_ownership='letter' above)
                raise ValueError(
                    "overlap_tail_fraction requires the pipelined path: "
                    "native tokenizer available, no checkpoint/skew flags, "
                    "no streaming, and <= 65534 documents")
        if self._pipelined_eligible(manifest):
            from ..native import KeyOverflow

            try:
                if self.config.overlap_tail_fraction is not None:
                    return self._run_tpu_overlap(manifest, out_dir, timer)
                return self._run_tpu_pipelined(manifest, out_dir, timer)
            except KeyOverflow:
                if self.config.emit_ownership == "letter":
                    raise ValueError(
                        "emit_ownership='letter' cannot fall back to the "
                        "one-shot engine after packed-key overflow") from None
                # vocab * stride outgrew int32 keys mid-stream: restart on
                # the one-shot path (whose general engine sorts two-key).
                aborted_ms = timer.total_seconds * 1e3
                self.timer = timer = PhaseTimer()
                timer.count("num_mappers", self.config.num_mappers)
                timer.count("num_reducers", self.config.num_reducers)
                timer.count("pipelined_fallback", "key_overflow")
                # keep total_ms honest: the aborted attempt's wall time
                # stays in the report as its own phase
                timer.phases["aborted_pipelined"] = aborted_ms / 1e3
        corpus, num_loaded = self._tokenize_or_resume(manifest, timer)

        max_doc_id = len(manifest)  # doc ids are 1..len(manifest)
        num_tokens, vocab_size = corpus.num_tokens, corpus.vocab_size
        timer.count("documents", num_loaded)
        timer.count("tokens", corpus.raw_tokens if corpus.raw_tokens is not None else num_tokens)
        timer.count("unique_terms", vocab_size)

        if self.config.collect_skew_stats and num_tokens:
            from ..utils.stats import partition_skew

            with timer.phase("skew_stats"):
                skew = partition_skew(
                    corpus.term_ids, corpus.letter_of_term,
                    num_buckets=max(len(jax.devices()), 2))
            timer.count("letter_imbalance", round(skew["letter_imbalance"], 3))
            timer.count("bucket_imbalance", round(skew["bucket_imbalance"], 3))

        if num_tokens == 0:
            with timer.phase("emit"):
                formatter.emit_grouped(
                    out_dir, {},
                    artifact_path=self._artifact_path(out_dir))
            return timer.report()

        num_shards = self._num_shards()
        use_dist = num_shards > 1 and K.can_pack(vocab_size, max_doc_id)
        # Half-bandwidth single-chip path: uint16 feed + fetch (the
        # device->host link dominates single-chip wall time; SURVEY.md §6).
        use_u16 = (
            not use_dist
            and vocab_size <= 0xFFFF
            and max_doc_id <= 0xFFFE
            and K.can_pack(vocab_size, max_doc_id)  # keys are packed in int32
        )
        padded = _round_up(num_tokens, self.config.pad_multiple)
        if use_dist:
            padded = _round_up(padded, num_shards)
        timer.count("device_shards", num_shards if use_dist else 1)
        mesh = make_mesh(num_shards) if use_dist else None
        with timer.phase("feed"):
            if use_u16:
                # one upload op: [terms | docs] as uint16 (fixed per-transfer
                # cost dominates the link; see ops/engine.index_u16)
                feed_dev = jax.device_put(
                    engine.pack_u16_feed(corpus.term_ids, corpus.doc_ids, padded))
            elif K.can_pack(vocab_size, max_doc_id):
                host_keys = np.full(padded, K.INT32_MAX, dtype=np.int32)
                stride = max_doc_id + 2
                np.multiply(corpus.term_ids, stride, out=host_keys[:num_tokens])
                host_keys[:num_tokens] += corpus.doc_ids
                if use_dist:
                    keys_dev = jax.device_put(host_keys, sharding(mesh, shard_spec()))
                    letters_dev = jax.device_put(
                        corpus.letter_of_term, sharding(mesh, replicated_spec()))
                else:
                    keys_dev = jax.device_put(host_keys)
                    letters_dev = jax.device_put(corpus.letter_of_term)
                packed = True
            else:
                term_dev = jax.device_put(
                    np.concatenate([corpus.term_ids,
                                    np.full(padded - num_tokens, K.INT32_MAX, np.int32)]))
                doc_dev = jax.device_put(
                    np.concatenate([corpus.doc_ids,
                                    np.full(padded - num_tokens, K.INT32_MAX, np.int32)]))
                letters_dev = jax.device_put(corpus.letter_of_term)
                packed = False

        profile = _profile_ctx(self.config.profile_dir)
        if use_u16 and corpus.pairs_deduped:
            # Latency-pipelined fast path.  The device->host link has a
            # large fixed (RTT-like) issue cost; issuing the fetch right
            # after dispatch hides it behind the in-flight upload +
            # sort, and the host derives df/order/offsets meanwhile.
            num_unique = num_tokens
            nfetch = min(padded, _round_up(num_unique, 1 << 14))
            with timer.phase("device_index"), profile:
                post_dev = engine.index_prededuped_u16(
                    feed_dev, max_doc_id=max_doc_id, out_size=nfetch)
                post_dev.copy_to_host_async()
                df = np.bincount(corpus.term_ids, minlength=vocab_size).astype(np.int64)
                # guard the combiner invariant this path relies on: term
                # ids within vocab, per-term counts within the doc count
                if len(df) != vocab_size or (vocab_size and int(df.max()) > max_doc_id):
                    raise ValueError(
                        "pairs_deduped feed violates its invariant "
                        f"(df len {len(df)} vs vocab {vocab_size}); "
                        "corrupt checkpoint or tokenizer bug")
                order, offsets = engine.host_order_offsets(corpus.letter_of_term, df)
                if self.config.profile_dir:
                    # keep the in-flight sort + D2H inside the trace window
                    post_dev.block_until_ready()
            with timer.phase("fetch"):
                postings = np.asarray(post_dev)
                host = {
                    "df": df, "order": order, "offsets": offsets,
                    "postings": postings, "num_unique": num_unique,
                }
            return self._emit_and_report(
                corpus, host, out_dir, timer, vocab_size, max_doc_id)

        with timer.phase("device_index"), profile:
            if use_u16:
                out = engine.index_u16(
                    feed_dev, vocab_size=vocab_size, max_doc_id=max_doc_id)
            elif use_dist:
                out = dist_engine.dist_index(
                    keys_dev, letters_dev, vocab_size=vocab_size, max_doc_id=max_doc_id,
                    mesh=mesh)
            elif packed:
                out = engine.index_packed(
                    keys_dev, letters_dev, vocab_size=vocab_size, max_doc_id=max_doc_id)
            else:
                out = engine.index_pairs(
                    term_dev, doc_dev, letters_dev,
                    vocab_size=vocab_size, max_doc_id=max_doc_id)
            # dist path returns host-assembled numpy postings; wait for
            # device arrays so fetch below times the transfer, not the
            # compute
            jax.block_until_ready(out)

        with timer.phase("fetch"):
            if use_u16:
                # two ops: df (num_unique derives from its sum), then the
                # valid postings prefix (rounded so slice shapes, and with
                # them compiled slice programs, reuse)
                df = jax.device_get(out["combined"][:vocab_size]).astype(np.int64)
                num_unique = int(df.sum())
                nfetch = min(padded, _round_up(max(num_unique, 1), 1 << 14))
                postings = jax.device_get(
                    out["combined"][vocab_size : vocab_size + nfetch])
                order, offsets = engine.host_order_offsets(corpus.letter_of_term, df)
                host = {
                    "df": df, "order": order, "offsets": offsets,
                    "postings": postings, "num_unique": num_unique,
                }
            else:
                host = jax.device_get(out)

        return self._emit_and_report(corpus, host, out_dir, timer, vocab_size, max_doc_id)

    def _emit_backend(self) -> str:
        """Resolve ``config.emit_backend`` for the formatter dispatch:
        ``auto`` respects ``use_native`` (the scan path's native kill
        switch) so one knob still forces an all-Python run."""
        if self.config.emit_backend == "auto" and not self.config.use_native:
            return "python"
        return self.config.emit_backend

    def _emit_and_report(self, corpus, host, out_dir, timer, vocab_size, max_doc_id) -> dict:
        with timer.phase("emit"):
            emit_stats = formatter.emit_index(
                out_dir,
                vocab=corpus.vocab,
                letter_of_term=corpus.letter_of_term,
                order=host["order"],
                df=host["df"],
                offsets=host["offsets"],
                postings=host["postings"],
                max_doc_id=max_doc_id,
                backend=self._emit_backend(),
                artifact_path=self._artifact_path(out_dir),
            )
        timer.count("unique_pairs", int(host["num_unique"]))
        timer.count("lines_written", emit_stats["lines_written"])
        self._count_artifact_stats(timer, emit_stats)
        return timer.report()

    @staticmethod
    def _count_artifact_stats(timer: PhaseTimer, emit_stats: dict) -> None:
        for key in ("artifact_bytes", "artifact_build_ms"):
            if key in emit_stats:
                timer.count(key, emit_stats[key])


def build_index(manifest: Manifest, config: IndexConfig | None = None,
                output_dir: str | None = None) -> dict:
    """One-shot convenience: index a manifest and write the letter files."""
    return InvertedIndexModel(config).run(manifest, output_dir)

"""Host-side phase profiler for the <=50 ms push (VERDICT r2 #3).

Times the overlap plan's host components in isolation on this machine —
native scan, u16 feed assembly, df snapshots, finalize, emit-order
lexsort, run-meta tables, native multi-run emit — so the optimization
targets are measured, not guessed.  Device RTT is excluded on purpose
(run on the cpu platform); on-chip e2e comes from ``python3 -m benchmark``.

    python tools/profile_host.py [--threads N] [--reps R]
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def best_of(fn, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3, out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--threads", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--corpus", default="/root/reference/test_in")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        manifest_from_dir, native,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
        iter_document_ranges,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.scheduler import (
        plan_fraction_windows,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
        engine,
    )

    manifest = manifest_from_dir(args.corpus)
    max_doc_id = len(manifest)
    stride = max_doc_id + 2
    out = {"corpus_bytes": int(manifest.total_bytes), "threads": args.threads}

    # --- file IO alone (page-cached read of every doc)
    def read_all():
        total = 0
        for contents, ids in iter_document_ranges(
                manifest, plan_fraction_windows(manifest, (1.0,))):
            total += sum(len(c) for c in contents)
        return total

    out["read_ms"], _ = best_of(read_all, args.reps)

    windows = plan_fraction_windows(manifest, (0.275, 0.225, 0.5))
    ranges = list(iter_document_ranges(manifest, windows))

    # --- native scan + combiner, feed() only (no u16 assembly)
    def scan_only():
        s = native.NativeKeyStream(stride, num_threads=args.threads)
        n = 0
        for contents, ids in ranges:
            k, _ = s.feed(contents, ids)
            n += k.size
        s.close()
        return n

    out["scan_feed_ms"], out["pairs"] = best_of(scan_only, args.reps)

    # --- the overlap plan's real feed loop: u16 windows + snapshots +
    # tail feed (everything tokenize_feed does except device_put)
    def scan_u16():
        s = native.NativeKeyStream(stride, num_threads=args.threads)
        prev = np.zeros(0, np.int32)
        snaps = []
        for wi, (contents, ids) in enumerate(ranges):
            if wi == len(ranges) - 1:
                s.feed(contents, ids)
                continue
            s.feed_u16(contents, ids, granule=1 << 14)
            snap = s.df_snapshot(hint=max(1 << 16, prev.shape[0] * 2))
            snaps.append((prev, snap))
            prev = snap
        fin = s.finalize()
        s.close()
        return fin, snaps, prev

    t_u16, (fin, snaps, prev) = best_of(scan_u16, args.reps)
    out["feed_u16_loop_ms"] = t_u16
    vocab, letters, remap, df_prov, raw_tokens, num_pairs, emit_order = fin
    vocab_size = int(vocab.shape[0])
    out["vocab_size"] = vocab_size
    out["raw_tokens"] = int(raw_tokens)

    # --- finalize alone (needs a fed stream each rep: time by diff)
    def scan_no_finalize():
        s = native.NativeKeyStream(stride, num_threads=args.threads)
        for contents, ids in ranges:
            s.feed(contents, ids)
        fin2 = s.finalize()
        s.close()
        return fin2

    t_with, _ = best_of(scan_no_finalize, args.reps)
    out["finalize_delta_ms"] = round(t_with - out["scan_feed_ms"], 2)

    # --- host_views pieces
    out["order_lexsort_ms"], _ = best_of(
        lambda: engine.host_order_offsets(
            letters, df_prov.astype(np.int64)[np.argsort(remap)]), args.reps)

    prov_of_rank = np.empty(vocab_size, dtype=np.int64)
    prov_of_rank[remap] = np.arange(vocab_size)

    def run_meta_all():
        def run_meta(prev_s, cur):
            c = np.zeros(vocab_size, np.int64)
            c[: cur.shape[0]] = cur
            c[: prev_s.shape[0]] -= prev_s
            off = np.cumsum(c) - c
            return off[prov_of_rank], c[prov_of_rank]

        metas = [run_meta(p, c) for p, c in snaps]
        metas.append(run_meta(prev, df_prov.astype(np.int64)))
        return metas

    out["run_meta_ms"], metas = best_of(run_meta_all, args.reps)

    # --- tail np.sort (the host_tail phase at tail fraction 0.5)
    s = native.NativeKeyStream(stride, num_threads=args.threads)
    tail_keys = None
    for wi, (contents, ids) in enumerate(ranges):
        if wi == len(ranges) - 1:
            tail_keys, _ = s.feed(contents, ids)
        else:
            s.feed(contents, ids)
    s.close()
    out["tail_pairs"] = int(tail_keys.size)
    out["tail_sort_ms"], _ = best_of(
        lambda: np.sort(tail_keys), args.reps)

    # --- native multi-run emit (fake runs: the tail alone as one run)
    df_rank = df_prov.astype(np.int64)[prov_of_rank]
    order, _ = engine.host_order_offsets(letters, df_rank)
    tail_sorted = np.sort(tail_keys)
    tail_docs = (tail_sorted % stride).astype(np.uint16)
    c = np.zeros(vocab_size, np.int64)
    np.add.at(c, remap[tail_sorted // stride], 1)  # rank-space counts
    off = np.cumsum(c) - c
    emit_dir = tempfile.mkdtemp(prefix="profile_emit_")
    out["emit_tail_only_ms"], _ = best_of(
        lambda: native.emit_native_runs(
            emit_dir, vocab, order, [(tail_docs, off, c)]), args.reps)

    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Stage attribution for the streaming all-device engine.

The one-shot program has truncated-cut attribution
(attribute_device_stages.py); the stream engine's unit of work is a
window, and at scale-bench window sizes (tens of MB, seconds per
stage) every stage sits far above the per-dispatch floor — so a
SERIALIZED run with a real fetch barrier after each stage gives
honest per-stage sums, and a second, normally-pipelined run gives the
true wall clock.  The gap between them is what the 2-deep merge
pipeline buys on this link.

    python tools/profile_stream_stages.py [--docs N] [--vocab V]
        [--chunk C]        # JAX_PLATFORMS=cpu: off-chip smoke

Prints one JSON line: per-stage totals (host window prep, upload,
window_rows, merge) from the serialized run, plus pipelined wall and
docs/s for both.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=120_000)
    ap.add_argument("--vocab", type=int, default=30_000)
    ap.add_argument("--chunk", type=int, default=20_000)
    args = ap.parse_args()

    import jax

    print(json.dumps({"devices": [str(d) for d in jax.devices()]}),
          flush=True)

    import numpy as np

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
        iter_document_chunks,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        synthetic_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.models.inverted_index import (
        _pack_window, _round_up,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
        device_streaming as DS,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
        device_tokenizer as DT,
    )

    manifest = synthetic_manifest(
        num_docs=args.docs, vocab_size=args.vocab, tokens_per_doc=40,
        seed=11)
    pad_multiple = 1 << 16
    width = 48

    def windows():
        for contents, ids in iter_document_chunks(manifest, args.chunk):
            total = sum(len(c) for c in contents)
            padded = _round_up(max(total, 1), pad_multiple)
            buf, ends, _ = _pack_window(
                contents, ids, padded, max(len(contents), 1))
            ends = ends[: len(contents)]
            cnt, ml = DT.host_token_stats(buf, ends)
            yield buf, ends, np.asarray(ids, np.int32), cnt, ml

    def fetch_barrier(x):
        """Real host fetch of a tiny slice: a barrier on the in-order
        device stream.
        Tuples barrier EVERY element: the upload hook hands all three
        window transfers (d_buf, d_ends, d_ids), and skipping two would
        credit their copy time to the next stage."""
        for a in (x if isinstance(x, tuple) else (x,)):
            np.asarray(a if getattr(a, "ndim", 0) == 0 else a[:1])

    # --- pass 1 (cold, pipelined): pays every XLA compile so the two
    # timed passes below compare warm programs; its wall is reported
    # separately (compile included)
    eng0 = DS.DeviceStreamEngine(width=width)
    t_all = time.perf_counter()
    for buf, ends, ids, cnt, ml in windows():
        if cnt:
            eng0.feed(buf, ends, ids, tok_count=cnt, max_len=ml)
    eng0.finalize()
    cold_wall = time.perf_counter() - t_all
    del eng0  # free its device accumulator before the timed passes
    print(json.dumps({"pipelined_cold_wall_s": round(cold_wall, 2),
                      "note": "includes XLA compile"}), flush=True)

    # --- pass 2 (warm, serialized): the PRODUCTION feed path with its
    # stage_hook barriering + timing each stage (ops/device_streaming
    # .feed — the hook also drains the merge pipeline per window, so
    # this pass is exactly "production minus pipelining"; advisor r4:
    # no stage-by-stage re-implementation to desynchronize)
    stage = {"host_prep_s": 0.0, "upload_s": 0.0, "window_rows_s": 0.0,
             "merge_s": 0.0}
    clock = [0.0]

    def stage_hook(name, val):
        fetch_barrier(val)
        now = time.perf_counter()
        stage[name + "_s"] += now - clock[0]
        clock[0] = now

    eng = DS.DeviceStreamEngine(width=width)
    t_all = time.perf_counter()
    t0 = time.perf_counter()
    for buf, ends, ids, cnt, ml in windows():
        stage["host_prep_s"] += time.perf_counter() - t0
        if cnt:
            clock[0] = time.perf_counter()
            eng.feed(buf, ends, ids, tok_count=cnt, max_len=ml,
                     stage_hook=stage_hook)
        t0 = time.perf_counter()
    serialized_wall = time.perf_counter() - t_all
    out = {
        "docs": args.docs, "vocab": args.vocab, "chunk": args.chunk,
        "windows": eng.windows_fed,
        "accumulator_capacity": eng.capacity,
        "serialized_wall_s": round(serialized_wall, 2),
        "serialized_docs_per_s": round(args.docs / serialized_wall, 1),
        **{k: round(v, 2) for k, v in stage.items()},
    }
    print(json.dumps(out), flush=True)

    del eng  # free the serialized pass's accumulator HBM
    # --- pass 3 (warm, pipelined): the production feed loop (2-deep
    # merges, no mid-stream syncs) on a FRESH engine.  The feed loop is
    # timed SEPARATELY from finalize so the pipeline comparison is
    # feed-vs-feed — the serialized wall has no finalize in it, and
    # folding finalize into one side would understate (even negate)
    # the pipeline's benefit.
    eng2 = DS.DeviceStreamEngine(width=width)
    t_all = time.perf_counter()
    for buf, ends, ids, cnt, ml in windows():
        if cnt == 0:
            continue
        eng2.feed(buf, ends, ids, tok_count=cnt, max_len=ml)
    pipelined_feed_wall = time.perf_counter() - t_all
    t_fin = time.perf_counter()
    final = eng2.finalize()
    counts = np.asarray(final["counts"])
    finalize_s = time.perf_counter() - t_fin
    out["pipelined_feed_wall_s"] = round(pipelined_feed_wall, 2)
    out["finalize_s"] = round(finalize_s, 2)
    # feed-only, like serialized_docs_per_s (neither wall includes
    # finalize — the only like-for-like comparison)
    out["pipelined_feed_docs_per_s"] = round(
        args.docs / pipelined_feed_wall, 1)
    out["pipelined_docs_per_s_incl_finalize"] = round(
        args.docs / (pipelined_feed_wall + finalize_s), 1)
    out["pipeline_gain_pct"] = round(
        100.0 * (serialized_wall - pipelined_feed_wall) / serialized_wall,
        1)
    out["unique_pairs"] = int(counts[1])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

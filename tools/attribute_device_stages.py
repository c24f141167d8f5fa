"""Attribute the all-device program's on-chip time by stage truncation.

Round-3 finding (tools/profile_device_stages.py): standalone micro-ops
cannot be timed below the per-dispatch floor, so stage costs are attributed by timing TRUNCATED variants of
the real program instead — each variant runs the pipeline up to a cut
point and reduces everything computed so far to one scalar (so XLA
cannot dead-code-eliminate the work, and the fetch is 4 bytes).
Successive differences are the stage costs; the dispatch floor and the
reduction epsilon cancel.

    python tools/attribute_device_stages.py [--corpus DIR]

Cuts (the production group pipeline, mirrored stage by stage):
  tokenize     tokenize_groups complete (5-bit group pairs + doc col
               forced; includes the windowed packing gathers)
  perm         + groups_sort_perm over the live pairs (LSD radix)
  gather       + s_groups/s_docs row gathers
  masks        + boundary masks, pair-rank cumsum, counts
  full         + W/P set-bit compactions, df, postings, unique_groups
               (the whole index_bytes_device, its real counts fetch)
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def timed(fn, *args, reps=5):
    import numpy as np

    out = fn(*args)
    np.asarray(out[:1] if getattr(out, "ndim", 0) else out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        np.asarray(out[:1] if getattr(out, "ndim", 0) else out)
        best = min(best, time.perf_counter() - t0)
    return round(best * 1e3, 2)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpus", default="/root/reference/test_in")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import jax

    print(json.dumps({"devices": [str(d) for d in jax.devices()]}),
          flush=True)

    import jax.numpy as jnp
    import numpy as np

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, manifest_from_dir,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
        load_documents,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.models.inverted_index import (
        _pack_window, _round_up,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops import (
        device_tokenizer as DT,
    )

    cfg = IndexConfig(output_dir="/tmp/ads_out", backend="tpu",
                      device_tokenize=True)
    manifest = manifest_from_dir(args.corpus)
    contents, doc_ids = load_documents(manifest)
    num_docs = len(contents)
    total = sum(len(c) for c in contents)
    padded = _round_up(total, cfg.pad_multiple)
    buf, ends, _ = _pack_window(contents, doc_ids, padded, num_docs)
    tok_count, host_max_len = DT.host_token_stats(buf, ends)
    tok_cap = _round_up(tok_count + 1, 1 << 15)
    width = cfg.device_tokenize_width
    sort_cols = -(-max(host_max_len, 1) // 4)
    print(json.dumps({"n_bytes": int(buf.shape[0]), "tok_cap": tok_cap,
                      "sort_cols": sort_cols}), flush=True)

    data = jax.device_put(buf)
    ends_d = jax.device_put(ends)
    ids_d = jax.device_put(np.asarray(doc_ids, np.int32))

    def upto(stage):
        @jax.jit
        def run(data, doc_ends, ids):
            # mirrors index_bytes_device's group pipeline stage by stage
            groups, doc_col, max_word_len, num_tokens = DT.tokenize_groups(
                data, doc_ends, ids, width=width, tok_cap=tok_cap,
                num_docs=num_docs, sort_cols=sort_cols)
            if stage == "tokenize":
                acc = sum(jnp.sum(h) + jnp.sum(l) for h, l in groups)
                return acc + jnp.sum(doc_col) + max_word_len + num_tokens
            live = DT.live_groups_for(sort_cols, width)
            live_pairs = list(groups[:max(1, live)])
            perm = DT.groups_sort_perm(live_pairs, doc_col, tok_cap)
            if stage == "perm":
                return jnp.sum(perm) + max_word_len
            s_groups = [(hi[perm], lo[perm]) for hi, lo in live_pairs]
            s_docs = doc_col[perm]
            if stage == "gather":
                return (sum(jnp.sum(h) + jnp.sum(l) for h, l in s_groups)
                        + jnp.sum(s_docs) + max_word_len)
            INT32_MAX = DT.INT32_MAX
            word_valid = s_groups[0][0] != INT32_MAX

            def neq_prev(a):
                return jnp.concatenate(
                    [jnp.ones(1, jnp.bool_), a[1:] != a[:-1]])

            first_word = word_valid & functools.reduce(
                jnp.logical_or,
                (neq_prev(h) for pair in s_groups for h in pair))
            first_pair = word_valid & (first_word | neq_prev(s_docs))
            pair_rank = jnp.cumsum(first_pair.astype(jnp.int32)) - 1
            if stage == "masks":
                return (jnp.sum(pair_rank[-1:])
                        + jnp.sum(first_word.astype(jnp.int32))
                        + max_word_len)
            raise AssertionError(stage)

        return run

    lines = {}
    for stage in ("tokenize", "perm", "gather", "masks"):
        lines[stage] = timed(upto(stage), data, ends_d, ids_d,
                             reps=args.reps)
        print(json.dumps({"cut": stage, "ms": lines[stage]}), flush=True)

    full_fn = jax.jit(functools.partial(
        DT.index_bytes_device, width=width, tok_cap=tok_cap,
        num_docs=num_docs, sort_cols=sort_cols))

    def full(data, doc_ends, ids):
        return full_fn(data, doc_ends, ids)["counts"]

    lines["full"] = timed(full, data, ends_d, ids_d, reps=args.reps)
    print(json.dumps({"cut": "full", "ms": lines["full"]}), flush=True)

    deltas = {}
    order = ["tokenize", "perm", "gather", "masks", "full"]
    prev = 0.0
    for k in order:
        deltas[k] = round(lines[k] - prev, 2)
        prev = lines[k]
    print(json.dumps({"cuts_ms": lines, "stage_deltas_ms": deltas}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

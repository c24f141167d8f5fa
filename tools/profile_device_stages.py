"""Stage-level on-chip timing of the all-device engine's XLA program.

Round-3 follow-up to the measured device_index regression (1156.6 ms
post-redesign vs 817.4 ms pre-redesign, round 3): splits
``index_bytes_device`` into its stages and times each as a standalone
jitted program; every loop closes with a real host fetch of a tiny
output, a barrier on the in-order device stream.

    python tools/profile_device_stages.py [--corpus DIR]

Stages (all on the real corpus's shapes):
  full             index_bytes_device end to end
  tokenize_groups  map phase only (byte scans, letter-compaction sort,
                   windowed 5-bit group packing gathers)
  sort_dedup       reduce phase only (sort_dedup_groups on
                   tokenize_groups' materialized output: LSD passes ->
                   boundary masks -> set-bit compactions)
  micro-ops        the individual primitives: the n-element letter-
                   compaction lax.sort, one 3-key and one 2-key stable
                   sort at tok_cap, the (cap+1)-point searchsorted, and
                   a cumsum over n — lets the stage costs be attributed
                   (CAVEAT: each stands alone in its own dispatch, so
                   anything under the per-dispatch floor is
                   unmeasurable here — trust the
                   truncated-cut deltas of attribute_device_stages.py
                   for intra-program attribution).

Caveat shared with measure_tpu.py: absolute numbers include one link
round-trip (~6.5 ms floor measured round 3); comparisons within one
run are the signal.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def timed(fn, *args, reps=5, **kw):
    """Best-of-reps wall time of fn(*args) closed by a real 1-elt fetch."""
    import numpy as np

    out = fn(*args, **kw)  # warmup/compile
    _force(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        _force(out)
        best = min(best, time.perf_counter() - t0)
    return round(best * 1e3, 2)


def _force(out):
    import jax
    import numpy as np

    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf[:1] if getattr(leaf, "ndim", 0) else leaf)


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
    from jax import lax

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

    cfg = IndexConfig(output_dir="/tmp/pds_out", backend="tpu",
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
    n = int(buf.shape[0])
    print(json.dumps({"n_bytes": n, "tok_cap": tok_cap,
                      "sort_cols": sort_cols, "width": width}), flush=True)

    data = jax.device_put(buf)
    ends_d = jax.device_put(ends)
    ids_d = jax.device_put(np.asarray(doc_ids, np.int32))

    lines = {}

    lines["full"] = timed(
        partial(DT.index_bytes_device, width=width, tok_cap=tok_cap,
                num_docs=num_docs, sort_cols=sort_cols),
        data, ends_d, ids_d, reps=args.reps)
    print(json.dumps({"stage": "full", "ms": lines["full"]}), flush=True)

    tok_jit = jax.jit(partial(DT.tokenize_groups, width=width,
                              tok_cap=tok_cap, num_docs=num_docs,
                              sort_cols=sort_cols))
    lines["tokenize_groups"] = timed(tok_jit, data, ends_d, ids_d,
                                     reps=args.reps)
    print(json.dumps({"stage": "tokenize_groups",
                      "ms": lines["tokenize_groups"]}), flush=True)

    groups, doc_col, _, _ = tok_jit(data, ends_d, ids_d)
    groups = tuple((jax.device_put(np.asarray(h)),
                    jax.device_put(np.asarray(l))) for h, l in groups)
    doc_col = jax.device_put(np.asarray(doc_col))

    sd_jit = jax.jit(partial(DT.sort_dedup_groups, cap=tok_cap,
                             live=DT.live_groups_for(sort_cols, width)))
    lines["sort_dedup"] = timed(sd_jit, groups, doc_col, reps=args.reps)
    print(json.dumps({"stage": "sort_dedup", "ms": lines["sort_dedup"]}),
          flush=True)

    # ---- micro-ops at the program's real shapes ----
    pos = np.arange(n, dtype=np.int32)
    flagged = jax.device_put(
        np.where(np.random.default_rng(0).random(n) < 0.8, pos,
                 pos + (1 << 24)).astype(np.int32))

    @jax.jit
    def letter_sort(key):
        return lax.sort(key) & ((1 << 24) - 1)

    lines["micro_letter_sort_n"] = timed(letter_sort, flagged,
                                         reps=args.reps)

    rng = np.random.default_rng(1)
    k1 = jax.device_put(rng.integers(0, 1 << 30, tok_cap, np.int32))
    k2 = jax.device_put(rng.integers(0, 1 << 30, tok_cap, np.int32))
    k3 = jax.device_put(rng.integers(0, 1 << 30, tok_cap, np.int32))
    perm0 = jax.device_put(np.arange(tok_cap, dtype=np.int32))

    @jax.jit
    def sort3(a, b, c, p):
        return lax.sort((a, b, c, p), num_keys=3, is_stable=True)[3]

    @jax.jit
    def sort2(a, b, p):
        return lax.sort((a, b, p), num_keys=2, is_stable=True)[2]

    lines["micro_sort3_cap"] = timed(sort3, k1, k2, k3, perm0,
                                     reps=args.reps)
    lines["micro_sort2_cap"] = timed(sort2, k1, k2, perm0, reps=args.reps)

    mono = jax.device_put(np.sort(rng.integers(0, n, tok_cap, np.int32)))
    targets = jax.device_put(np.arange(tok_cap + 1, dtype=np.int32))

    @jax.jit
    def ssorted(a, t):
        return jnp.searchsorted(a, t)

    lines["micro_searchsorted_cap"] = timed(ssorted, mono, targets,
                                            reps=args.reps)

    bytes_u8 = jax.device_put(buf)

    @jax.jit
    def cumsum_n(b):
        return jnp.cumsum((b > 0x60).astype(jnp.int32))

    lines["micro_cumsum_n"] = timed(cumsum_n, bytes_u8, reps=args.reps)

    print(json.dumps({"profile": lines}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

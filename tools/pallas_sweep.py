"""Pallas-vs-XLA sweep for the two kernels (VERDICT r2 #7).

Round 2's single datapoint (fused dedup, 2^20 keys, 64-row blocks) had
Pallas LOSING to XLA 18.4 vs 14.3 us.  This sweep tests the two
hypotheses before the claim is settled:

- grid overhead: 64-row blocks mean 128+ sequential block dispatches at
  2^20; larger blocks amortize.  Sweep block_rows in {64, 256, 512}.
- size: the fused pass saves one HBM round trip, which should matter
  more as n grows.  Sweep n in {2^20, 2^22, 2^24}.

Also measures bucket_histogram against BOTH honest XLA alternatives:
``jnp.bincount`` (natural formulation — lowers to TPU scatter-add, the
serial ~75 ns/update loop) and the unrolled compare+sum (what you would
hand-write in XLA).  Every timing loop closes with a real host fetch of
a tiny result, a barrier on the in-order device stream.

    python tools/pallas_sweep.py            # on the real chip
    JAX_PLATFORMS=cpu python tools/pallas_sweep.py --interpret  # smoke
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _time_batched(fn, arg, fetch, reps=20, chain=10):
    """Best per-dispatch seconds, amortized over ``chain`` dispatches
    closed by one tiny host fetch (a true barrier on the in-order
    device stream)."""
    res = fn(arg)
    fetch(res)  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = [fn(arg) for _ in range(chain)]
        fetch(out[-1])
        best = min(best, (time.perf_counter() - t0) / chain)
    return best


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="force interpreter mode (cpu smoke)")
    ap.add_argument("--sizes", default="20,22,24",
                    help="log2 key counts to sweep")
    ap.add_argument("--block-rows", default="64,256,512")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax


    import jax.numpy as jnp
    import numpy as np

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops.pallas import (
        kernels as pk,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops.segment import (
        first_occurrence_mask,
    )

    interpret = args.interpret or pk._should_interpret()
    sizes = [1 << int(s) for s in args.sizes.split(",")]
    block_rows = [int(b) for b in args.block_rows.split(",")]
    out = {"platform": jax.devices()[0].platform, "interpret": interpret,
           "lines": []}
    print(json.dumps({"devices": [str(d) for d in jax.devices()]}),
          flush=True)

    for n in sizes:
        rng = np.random.default_rng(3)
        keys = np.sort(rng.integers(0, 1 << 28, size=n, dtype=np.int32))
        limit = 1 << 28
        kd = jax.device_put(keys)
        k2d = jax.device_put(keys.reshape(n // pk._LANES, pk._LANES))
        lim = jnp.full((1, 1), limit, jnp.int32)

        @jax.jit
        def xla_dedup(k):
            m = first_occurrence_mask(k) & (k < limit)
            return m.astype(jnp.int32), m.astype(jnp.int32).sum()

        def fetch_dedup(res):
            np.asarray(res[1]).reshape(-1)[:1]

        line = {"kernel": "dedup", "n": n,
                "xla_us": round(_time_batched(
                    xla_dedup, kd, fetch_dedup, args.reps) * 1e6, 1)}
        for br in block_rows:
            if (n // pk._LANES) % br:
                continue
            fn = jax.jit(lambda k2, _br=br: pk._unique_call(
                k2, lim, interpret=interpret, block_rows=_br))
            line[f"pallas_br{br}_us"] = round(_time_batched(
                fn, k2d, fetch_dedup, args.reps) * 1e6, 1)
        out["lines"].append(line)
        print(json.dumps(line), flush=True)

        # --- histogram: 8 buckets (a mesh-sized skew count)
        nb = 8
        vals = rng.integers(0, nb, size=n, dtype=np.int32)
        vd = jax.device_put(vals)
        v2d = jax.device_put(vals.reshape(n // pk._LANES, pk._LANES))

        @jax.jit
        def xla_bincount(v):
            return jnp.bincount(v, length=nb)

        @jax.jit
        def xla_compare_sum(v):
            return jnp.stack(
                [jnp.sum((v == b).astype(jnp.int32)) for b in range(nb)])

        def fetch_hist(res):
            np.asarray(res).reshape(-1)[:1]

        line = {"kernel": "hist8", "n": n,
                "xla_bincount_us": round(_time_batched(
                    xla_bincount, vd, fetch_hist, args.reps) * 1e6, 1),
                "xla_compare_sum_us": round(_time_batched(
                    xla_compare_sum, vd, fetch_hist, args.reps) * 1e6, 1)}
        for br in block_rows:
            if (n // pk._LANES) % br:
                continue
            fn = jax.jit(lambda v2, _br=br: pk._hist_call(
                v2, num_buckets=nb, interpret=interpret, block_rows=_br))
            line[f"pallas_br{br}_us"] = round(_time_batched(
                fn, v2d, fetch_hist, args.reps) * 1e6, 1)
        out["lines"].append(line)
        print(json.dumps(line), flush=True)

    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

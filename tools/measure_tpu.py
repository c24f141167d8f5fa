"""On-chip measurement of the single-chip engines.

    python tools/measure_tpu.py            # host-scan + device-scan engines
    python tools/measure_tpu.py --quick    # skip the streaming engine

Prints a header naming the device (platform, kind, count), then one JSON
line per engine with end-to-end and phase timings.  Best-of-N across
reps after one warmup run, which pays compilation (the compile cache is
placed by ``utils/device.setup``).  ``JAX_PLATFORMS=cpu`` runs it off
the chip as a smoke test; its times then say nothing about the chip.

The interesting comparison for the scatter-free + compressed-radix
redesign: ``device_index`` here vs the 817 ms (and 990 ms e2e)
recorded pre-redesign in round 2 (not measured since).
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# full-reference-corpus fingerprint; for any other --corpus the
# engines are cross-checked against each other instead
EXPECT_MD5 = "92600581e0685e69c056b65082326fc3"


def measure(label, cfg_kwargs, manifest, reps=5, expect_md5=None):
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, InvertedIndexModel,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.text.formatter import (
        letters_md5,
    )

    out_dir = tempfile.mkdtemp(prefix=f"mtpu_{label}_")
    model = InvertedIndexModel(IndexConfig(output_dir=out_dir, **cfg_kwargs))
    model.run(manifest)  # compile + caches
    best, rep = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        r = model.run(manifest)
        dt = time.perf_counter() - t0
        if dt < best:
            best, rep = dt, r
    md5 = letters_md5(out_dir)
    line = {
        "engine": label,
        "e2e_ms": round(best * 1e3, 2),
        "phases_ms": {k: round(v, 2) for k, v in rep["phases_ms"].items()},
        "md5": md5,
    }
    if expect_md5 is not None:
        line["md5_ok"] = md5 == expect_md5
    for k in ("sort_cols", "fetched_bytes", "dist_fetched_bytes",
              "stream_windows", "accumulator_capacity",
              "accumulator_capacity_per_owner", "device_shards"):
        if k in rep:
            line[k] = rep[k]
    print(json.dumps(line), flush=True)
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="one-shot engines only")
    ap.add_argument("--corpus", default="/root/reference/test_in")
    args = ap.parse_args()

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.utils import (
        device,
    )

    device.setup()
    print(json.dumps({"device": device.identity()}), flush=True)
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        manifest_from_dir,
    )

    manifest = manifest_from_dir(args.corpus)
    # full reference corpus -> absolute fingerprint; any other corpus
    # -> the cpu backend's output is the cross-check baseline
    if args.corpus == "/root/reference/test_in":
        expect = EXPECT_MD5
        cpu = measure("cpu_native", dict(backend="cpu"), manifest,
                      expect_md5=expect)
    else:
        cpu = measure("cpu_native", dict(backend="cpu"), manifest)
        expect = cpu["md5"]
    # host-scan reference point, then the redesigned device engines
    measure("overlap_0.5", dict(backend="tpu", device_shards=1,
                                overlap_tail_fraction=0.5), manifest,
            expect_md5=expect)
    measure("overlap_0.5_1win", dict(backend="tpu", device_shards=1,
                                     overlap_tail_fraction=0.5,
                                     overlap_device_windows=1), manifest,
            expect_md5=expect)
    measure("device_tokenize_oneshot",
            dict(backend="tpu", device_tokenize=True, device_shards=1),
            manifest, expect_md5=expect)
    if not args.quick:
        measure("device_tokenize_stream",
                dict(backend="tpu", device_tokenize=True, device_shards=1,
                     stream_chunk_docs=60), manifest, reps=3,
                expect_md5=expect)
    return 0


if __name__ == "__main__":
    sys.exit(main())

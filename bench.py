"""Benchmark: end-to-end wall time indexing the full test_in corpus.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "ms", "vs_baseline": R, ...}

Baseline (BASELINE.md): the reference pthread program at -O2 indexes the
same corpus in 796 ms on this container's CPU (4 mappers / 26 reducers).
``vs_baseline`` is the speedup ratio (baseline_ms / our_ms; > 1 means
faster than the reference).

Several execution plans for the same device engine are measured —
pipelined (uploads overlap tokenize), one-shot (fewest transfers), and
the windowed overlap plan at two tail fractions — and the best plan's
best-of-N is reported, like the reference's best thread config
(BASELINE.md measures its 1/1..8/13 grid the same way).  The TPU line
also records device-side Pallas-vs-XLA timings for the fused dedup
kernel (``kernel_timings``) and the all-device engine's time.

The TPU measurement runs in one child process (this parent stays off
JAX) and names the device it ran on.  It fails — non-zero exit, no
result line — when JAX finds no TPU or when any phase fails; a CPU
number is never reported under the TPU metric.  The native cpu backend
is measured too (``cpu_ms``), in the parent, after the child exits.

Falls back to a deterministic Zipfian corpus of the same scale if
/root/reference/test_in is not mounted, scaling the baseline by corpus
bytes.

``--scale`` runs the large-corpus streaming benchmark instead
(BASELINE.json config 4 magnitude): Zipfian docs through the bounded
streaming engine, reporting docs/s and the accumulator high-water mark.

``--sweep`` runs only the host map-phase scaling curve (cpu e2e at
1/2/4 scan workers with the per-worker stage split); the same block is
embedded in the main line as ``host_threads_sweep``.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def _load_envknobs():
    # File-path load of the knob registry: the package __init__ pulls
    # in jax, which must stay out of this parent process until the TPU
    # child has exited.
    import importlib.util
    import sys
    if "mri_envknobs" in sys.modules:
        return sys.modules["mri_envknobs"]
    path = (Path(__file__).resolve().parent
            / "parallel_computation_of_an_inverted_index_using_map_reduce_tpu"
            / "utils" / "envknobs.py")
    spec = importlib.util.spec_from_file_location("mri_envknobs", path)
    mod = importlib.util.module_from_spec(spec)
    # dataclass processing introspects sys.modules[cls.__module__], so
    # the module must be registered before exec
    sys.modules["mri_envknobs"] = mod
    spec.loader.exec_module(mod)
    return mod


envknobs = _load_envknobs()

BASELINE_MS = 796.0
BASELINE_BYTES = 5_793_058
REFERENCE_CORPUS = Path("/root/reference/test_in")


def _scratch_mkdtemp(prefix: str) -> str:
    """Temp dir for bench scratch (corpus + per-round letter files),
    RAM-backed when the host offers it.

    The e2e rounds rewrite ~4 MB of letter files 15+ times per run; on
    this VM's network-backed /tmp that makes the emit stage hostage to
    the kernel's dirty-page writeback throttle, whose state drifts with
    hours of unrelated disk traffic (observed: the same binary's emit
    stage 1.8 ms vs 8.6 ms depending on when it ran).  /dev/shm takes
    the storage weather out of a metric that exists to track code, not
    the shared disk.  The `scratch` field in the JSON line records
    which backing a run got, so numbers are never compared across
    backings unknowingly."""
    root = "/dev/shm"
    if os.path.isdir(root) and os.access(root, os.W_OK):
        return tempfile.mkdtemp(prefix=prefix, dir=root)
    return tempfile.mkdtemp(prefix=prefix)


def _scratch_backing() -> str:
    root = "/dev/shm"
    if os.path.isdir(root) and os.access(root, os.W_OK):
        return "tmpfs"
    return "default-tmp"


@functools.lru_cache(maxsize=1)
def _manifest():
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
        manifest_from_dir, read_manifest, write_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        write_corpus, zipf_corpus,
    )

    override = envknobs.get("MRI_TPU_BENCH_CORPUS")
    if override:
        return manifest_from_dir(override), "custom_corpus_e2e_wall_ms"
    if REFERENCE_CORPUS.is_dir():
        return manifest_from_dir(REFERENCE_CORPUS), "test_in_e2e_wall_ms"
    tmp = Path(_scratch_mkdtemp("bench_corpus_"))
    docs = zipf_corpus(num_docs=355, vocab_size=33_000, tokens_per_doc=2900, seed=7)
    paths = write_corpus(tmp / "docs", docs)
    write_manifest(tmp / "list.txt", paths)
    return read_manifest(tmp / "list.txt"), "synthetic_zipf_e2e_wall_ms"


def _measure(backend: str, plans: list[dict], rounds: int = 5) -> dict:
    """Best wall time (ms) over ``rounds`` rounds of every plan, after
    warmup.

    Returns ``{"best_ms": .., "phases_ms": {..}}`` — phases from the
    best-timed run, so device vs host time is reported, not asserted.
    """
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, InvertedIndexModel,
    )

    manifest, _ = _manifest()
    models = []
    for plan in plans:
        out_dir = _scratch_mkdtemp("bench_out_")
        models.append(InvertedIndexModel(
            IndexConfig(backend=backend, output_dir=out_dir, **plan)))
        models[-1].run(manifest)  # warmup: XLA compile + numpy/jit caches
    best, best_report, best_plan = float("inf"), {}, {}
    for _ in range(rounds):
        for model, plan in zip(models, plans):
            t0 = time.perf_counter()
            report = model.run(manifest)
            dt = time.perf_counter() - t0
            if dt < best:
                best, best_report, best_plan = dt, report, plan
    return {
        "best_ms": best * 1e3,
        "best_plan": best_plan,
        "phases_ms": best_report.get("phases_ms", {}),
        "host_threads": best_report.get("host_threads"),
        "report": best_report,
    }


def _kernel_timings() -> dict:
    """Pallas vs XLA time for the fused dedup (VERDICT r1 #3: measured,
    not asserted), over chained dispatches so host sync is amortized."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops.pallas import (
        kernels as pk,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.ops.segment import (
        first_occurrence_mask,
    )

    n = 1 << 20
    keys = np.sort(np.random.default_rng(3).integers(
        0, 1 << 28, size=n, dtype=np.int32))
    limit = 1 << 28

    @jax.jit
    def xla_path(k):
        m = first_occurrence_mask(k) & (k < limit)
        return m.astype(jnp.int32), m.astype(jnp.int32).sum()

    lim = jnp.full((1, 1), limit, jnp.int32)

    def pallas_path(k2d):
        return pk._unique_call(k2d, lim, interpret=False)

    kd = jax.device_put(keys)
    k2d = jax.device_put(keys.reshape(n // 128, 128))
    out = {"dedup_keys": n,
           "note": "host clock over 10 chained dispatches closed by "
                   "block_until_ready; the pallas-vs-xla RATIO is the "
                   "signal"}
    for name, fn, arg in (("xla", xla_path, kd), ("pallas", pallas_path, k2d)):
        jax.block_until_ready(fn(arg))
        best = float("inf")
        for _ in range(30):
            t0 = time.perf_counter()
            jax.block_until_ready([fn(arg) for _ in range(10)])
            best = min(best, (time.perf_counter() - t0) / 10)
        out[f"{name}_dedup_us"] = round(best * 1e6, 1)
    return out


def _require_tpu() -> dict:
    """The device block of every line; exits 2 unless JAX found a TPU."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.utils import (
        device,
    )

    device.setup()
    ident = device.identity()
    if ident["platform"] != "tpu":
        print(f"bench: JAX found no TPU (platform {ident['platform']!r})",
              file=sys.stderr)
        sys.exit(2)
    return ident


def _tpu_child() -> int:
    ident = _require_tpu()
    # the plan grid (like the reference's thread-count grid,
    # BASELINE.md): pipelined, one-shot, and the windowed overlap plan
    result = _measure("tpu", [
        {},
        {"pipeline_chunk_docs": 0},
        {"overlap_tail_fraction": 0.4, "device_shards": 1},
        {"overlap_tail_fraction": 0.5, "device_shards": 1},
        {"overlap_tail_fraction": 0.5, "device_shards": 1,
         "overlap_device_windows": 1},
        # bigger first window -> smaller LAST window -> smaller
        # residual fetch wait after the scan (config knob docs)
        {"overlap_tail_fraction": 0.5, "device_shards": 1,
         "overlap_window_split": 0.75},
    ])
    result["kernel_timings"] = _kernel_timings()
    devtok = _measure("tpu", [{"device_tokenize": True}])
    result["device_tokenize_ms"] = round(devtok["best_ms"], 2)
    result["device_tokenize_phases_ms"] = {
        k: round(v, 2) for k, v in devtok.get("phases_ms", {}).items()}
    result["device"] = {k: ident[k] for k in
                        ("platform", "device_kind", "device_count")}
    print(json.dumps(result), flush=True)
    return 0


def _run_tpu_child() -> dict:
    """The TPU child's result line; exits with its code when it fails."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--tpu-child"],
        stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        print(f"bench: tpu child failed (exit {proc.returncode})",
              file=sys.stderr)
        sys.exit(proc.returncode)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_scale() -> int:
    """Large-corpus streaming benchmark (BASELINE.json config 4 scale)."""
    ident = _require_tpu()
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, InvertedIndexModel,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus import (
        synthetic,
    )

    num_docs = envknobs.get("MRI_TPU_SCALE_DOCS")
    vocab = envknobs.get("MRI_TPU_SCALE_VOCAB")
    shards = envknobs.get("MRI_TPU_SCALE_SHARDS")  # 0 = all devices
    # MRI_TPU_SCALE_DEVTOK=1: the streaming ALL-DEVICE engine
    # (ops/device_streaming.py, single chip) instead of the host-scan
    # streaming engine — raw byte windows up, bounded row accumulator
    devtok = bool(envknobs.get("MRI_TPU_SCALE_DEVTOK"))
    # MRI_TPU_SCALE_REALTEXT=1: BASELINE.json config 5's regime — the
    # reference books resharded at paragraph granularity and cycled to
    # magnitude (corpus/realtext.py) instead of Zipf synthesis: real
    # vocabulary growth, real letter skew, real cleaning work.
    realtext = bool(envknobs.get("MRI_TPU_SCALE_REALTEXT"))
    # Salted repeat cycles (default ON): vocabulary keeps growing with
    # real-text shape past one source pass instead of freezing at the
    # source's 33,262 terms (corpus/realtext.py salt_cycles; VERDICT r4
    # #6 — 8 cycles ≈ 266K real-shaped terms through the accumulator).
    salt = bool(envknobs.get("MRI_TPU_SCALE_SALT"))
    if realtext:
        from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.realtext import (
            ParagraphManifest,
        )

        manifest = ParagraphManifest(
            REFERENCE_CORPUS,
            num_docs=(num_docs if envknobs.is_set("MRI_TPU_SCALE_DOCS")
                      else None),
            repeats=envknobs.get("MRI_TPU_SCALE_REPEATS"),
            salt_cycles=salt)
        num_docs = len(manifest)
    else:
        manifest = synthetic.synthetic_manifest(
            num_docs=num_docs, vocab_size=vocab, tokens_per_doc=40, seed=11)
    out_dir = tempfile.mkdtemp(prefix="bench_scale_")
    # MRI_TPU_SCALE_CKPT=path: crash-resumable stream (single-chip
    # devtok only) — a rerun of the same command resumes at the last
    # checkpointed window, so a TPU worker crash (the round-3 1M-doc
    # failure) costs one checkpoint interval, not the
    # whole run.
    ckpt = envknobs.get("MRI_TPU_SCALE_CKPT") if devtok else None
    chunk = envknobs.get("MRI_TPU_SCALE_CHUNK")
    model = InvertedIndexModel(IndexConfig(
        backend="tpu", output_dir=out_dir,
        device_shards=shards if shards else (1 if devtok else None),
        device_tokenize=devtok,
        stream_checkpoint=ckpt,
        stream_checkpoint_every=envknobs.get("MRI_TPU_SCALE_CKPT_EVERY"),
        stream_chunk_docs=chunk))
    t0 = time.perf_counter()
    stats = model.run(manifest)
    wall = time.perf_counter() - t0
    # a RESUMED run only streamed the windows after the checkpoint:
    # docs/s over full num_docs would overstate throughput by the
    # skipped fraction
    docs_streamed = num_docs - stats.get("resumed_from_window", 0) * chunk
    line = {
        "metric": "scale_stream_docs_per_s",
        "value": round(docs_streamed / wall, 1),
        "unit": "docs/s",
        "vs_baseline": round((docs_streamed / wall) / 446.0, 3),  # ref: 446 docs/s
        "num_docs": num_docs,
        "configured_vocab": vocab,
        "unique_terms": stats.get("unique_terms"),
        "unique_pairs": stats.get("unique_pairs"),
        "wall_s": round(wall, 2),
        "accumulator_capacity": stats.get(
            "accumulator_capacity", stats.get("accumulator_capacity_per_owner")),
        "device_shards": stats.get("device_shards", 1),
        "stream_windows": stats.get("stream_windows"),
        "engine": "device-stream" if devtok else "host-stream",
        "corpus": ("realtext-paragraphs" if realtext else "zipf"),
        "device": {k: ident[k] for k in
                   ("platform", "device_kind", "device_count")},
    }
    if "vocab_curve" in stats:
        # per-window unique-term counts: the vocabulary GROWTH curve
        # (must keep climbing past one source cycle when salted)
        line["vocab_curve"] = stats["vocab_curve"]
    if "unique_rows_curve" in stats:
        line["unique_rows_curve"] = stats["unique_rows_curve"]
    if realtext:
        line["source_paragraphs"] = manifest.source_paragraphs
        line["corpus_bytes"] = manifest.total_bytes
        line["salt_cycles"] = salt
        # docs/s is not comparable across corpora (a paragraph is
        # ~430 B, a reference chapter ~16 KB): vs_baseline for the
        # real-text regime is BYTES throughput over the reference's
        # 7.28 MB/s (5,793,058 B / 0.796 s, BASELINE.md)
        bytes_streamed = manifest.total_bytes * docs_streamed / num_docs
        line["vs_baseline"] = round(
            (bytes_streamed / wall) / (BASELINE_BYTES / (BASELINE_MS / 1e3)),
            3)
        line["vs_baseline_basis"] = "bytes_throughput"
    if "resumed_from_window" in stats:
        line["resumed_from_window"] = stats["resumed_from_window"]
        line["docs_streamed"] = docs_streamed
        line["note"] = ("resumed run: value covers the "
                        f"{docs_streamed} docs streamed after the "
                        "window-"
                        f"{stats['resumed_from_window']} checkpoint")
    for k in ("checkpoint_saves", "checkpoint_ms", "checkpoint_ms_per_save",
              "checkpoint_skips", "checkpoint_budget_s",
              "checkpoint_skipped_projection_s"):
        if k in stats:
            line[k] = stats[k]
    # print the measurement now; the checks below re-print it enriched
    print(json.dumps(line), flush=True)
    if realtext and envknobs.get("MRI_TPU_SCALE_SKEW"):
        # hash-vs-letter partition skew on the real text: ONE source
        # cycle through the skew-collecting one-shot engine (cycling
        # multiplies every partition count by the same factor, so one
        # cycle IS the full corpus's distribution)
        one = ParagraphManifest(REFERENCE_CORPUS, repeats=1)
        skew_stats = InvertedIndexModel(IndexConfig(
            backend="tpu", output_dir=tempfile.mkdtemp(
                prefix="bench_scale_skew_"),
            device_shards=1, collect_skew_stats=True)).run(one)
        line["skew_one_cycle"] = {
            k: skew_stats[k]
            for k in ("letter_imbalance", "bucket_imbalance")
            if k in skew_stats}
        print(json.dumps(line), flush=True)
    if envknobs.get("MRI_TPU_SCALE_CROSSCHECK"):
        from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.text.formatter import (
            letters_md5,
        )

        cpu_dir = tempfile.mkdtemp(prefix="bench_scale_cpu_")
        InvertedIndexModel(IndexConfig(
            backend="cpu", output_dir=cpu_dir)).run(manifest)
        line["md5"] = letters_md5(out_dir)
        line["md5_matches_cpu_backend"] = (
            line["md5"] == letters_md5(cpu_dir))
        print(json.dumps(line), flush=True)
        if not line["md5_matches_cpu_backend"]:
            return 1
    return 0


def _host_stage_split(report: dict) -> dict:
    """read/tokenize/emit ms for the best cpu run.

    The pipelined host path surfaces native ns-level timers as
    ``stage_*_ms`` counters; the one-shot fallback only knows its two
    coarse phases (load ≈ read, index_emit ≈ tokenize+emit fused)."""
    if "stage_read_ms" in report:
        split = {k: round(float(report[f"stage_{k}_ms"]), 2)
                 for k in ("read", "tokenize", "emit")}
        # out-of-core runs carry the term-hash shard balance (postings
        # per shard + max/mean skew) so the split shows WHERE the
        # reduce-side work landed, not just how long it took
        if "build_shards" in report:
            split["build_shards"] = report["build_shards"]
        return split
    phases = report.get("phases_ms", {})
    split = {}
    if "load" in phases:
        split["read"] = round(float(phases["load"]), 2)
    if "index_emit" in phases:
        split["tokenize_emit_fused"] = round(float(phases["index_emit"]), 2)
    elif "oracle" in phases:
        split["oracle"] = round(float(phases["oracle"]), 2)
    return split


SWEEP_WORKERS = tuple(
    int(k) for k in envknobs.get("MRI_BENCH_SWEEP_WORKERS").split(","))


def _host_threads_sweep(rounds: int = 7) -> dict:
    """cpu e2e at 1/2/4 scan workers on the same corpus: the host
    map-phase scaling curve, tracked round over round.

    Each worker count is its own plan (its own model + warmup) so the
    steal-queue path and the single-worker pipelined path are measured
    as the dispatcher actually routes them.  ``host_cores`` is recorded
    because the curve is only meaningful relative to the physical
    parallelism on offer — on a 1-core container the 4-worker point
    measures coordination overhead, not speedup, and the number must
    say so rather than look like a regression."""
    sweep: dict = {"host_cores": os.cpu_count(), "rounds": rounds,
                   "points": {}}
    for k in SWEEP_WORKERS:
        res = _measure("cpu", [{"host_threads": k}], rounds=rounds)
        report = res.get("report", {})
        point = {
            "best_ms": round(res["best_ms"], 2),
            "host_threads": report.get("host_threads"),
            "stage_split_ms": _host_stage_split(report),
        }
        for key in ("stage_read_ms_per_worker",
                    "stage_tokenize_ms_per_worker",
                    "stage_emit_ms_per_reducer", "merge_ms",
                    "read_wait_ms", "consume_wait_ms", "reduce_workers"):
            if key in report:
                point[key] = ([round(float(v), 2) for v in report[key]]
                              if isinstance(report[key], list)
                              else round(float(report[key]), 2))
        sweep["points"][str(k)] = point
    pts = sweep["points"]
    if "1" in pts and "4" in pts:
        sweep["speedup_4v1"] = round(
            pts["1"]["best_ms"] / pts["4"]["best_ms"], 3)
    return sweep


def _bench_sweep() -> int:
    """Standalone sweep mode (make bench-sweep): one JSON line, no TPU."""
    _, metric = _manifest()
    sweep = _host_threads_sweep()
    print(json.dumps({
        "metric": "host_threads_sweep",
        "corpus_metric": metric,
        "unit": "ms",
        "scratch": _scratch_backing(),
        **sweep,
    }))
    return 0


def main(artifact: bool = False) -> int:
    _, metric = _manifest()
    tpu = _run_tpu_child()
    # best-of-15: the host path's run-to-run spread (±2-5 ms on a
    # shared VM) is the same order as the stage costs being tracked,
    # and cpu rounds are ~50 ms each — sample enough that the floor,
    # not the scheduler, is what gets reported
    cpu = _measure("cpu", [{}], rounds=15)
    # audited cpu run: what the --audit integrity layer costs on the
    # same corpus (the report carries audit_ms; the contract is < 5 %
    # of the unaudited cpu_ms)
    cpu_audited = _measure("cpu", [{"audit": True}], rounds=3)
    # --artifact: the same corpus built WITH the serving artifact, so
    # the pack overhead (contract: <= 10 % of the unaudited cpu e2e)
    # is measured next to the number it dilutes
    cpu_artifact = (_measure("cpu", [{"artifact": True}], rounds=3)
                    if artifact else None)

    baseline_ms = BASELINE_MS
    if metric != "test_in_e2e_wall_ms":
        # synthetic or override corpus: scale the reference baseline by
        # corpus bytes so vs_baseline stays meaningful
        manifest, _ = _manifest()
        baseline_ms = BASELINE_MS * manifest.total_bytes / BASELINE_BYTES
    line = {
        "metric": metric,
        "value": round(tpu["best_ms"], 2),
        "unit": "ms",
        "vs_baseline": round(baseline_ms / tpu["best_ms"], 3),
        "device": tpu["device"],
        "tpu_plan": tpu.get("best_plan", {}),
        "tpu_phases_ms": {
            k: round(v, 2) for k, v in tpu.get("phases_ms", {}).items()},
        "tpu_host_threads": tpu.get("host_threads"),
        "kernel_timings": tpu["kernel_timings"],
        "device_tokenize_ms": tpu["device_tokenize_ms"],
        "device_tokenize_phases_ms": tpu["device_tokenize_phases_ms"],
        "cpu_ms": round(cpu["best_ms"], 2),
        "cpu_host_threads": cpu.get("host_threads"),
        "host_stage_split": _host_stage_split(cpu.get("report", {})),
        "scratch": _scratch_backing(),
        # failure-handling outcome of the best cpu run (faults.py):
        # non-empty skipped_docs means the measurement itself is suspect
        "degradation": cpu.get("report", {}).get(
            "degradation", {"read_retries": 0, "skipped_docs": []}),
        # integrity-audit overhead (--audit): ledger + merge invariants
        # + output manifest, measured on a separate audited run
        "audit_ms": round(
            cpu_audited.get("report", {}).get("audit_ms", 0.0), 3),
        "audited_cpu_ms": round(cpu_audited["best_ms"], 2),
        # host map-phase scaling curve (1/2/4 scan workers, same
        # corpus) with the per-worker stage split — tracked round over
        # round; host_cores qualifies what the curve can even show
        "host_threads_sweep": _host_threads_sweep(),
    }
    if cpu_artifact is not None:
        rep = cpu_artifact.get("report", {})
        line["artifact_cpu_ms"] = round(cpu_artifact["best_ms"], 2)
        line["artifact_build_ms"] = round(
            float(rep.get("artifact_build_ms", 0.0)), 3)
        line["artifact_bytes"] = int(rep.get("artifact_bytes", 0))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    if "--tpu-child" in sys.argv:
        sys.exit(_tpu_child())
    if "--scale" in sys.argv:
        sys.exit(_bench_scale())
    if "--sweep" in sys.argv:
        sys.exit(_bench_sweep())
    sys.exit(main(artifact="--artifact" in sys.argv))

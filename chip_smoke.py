#!/usr/bin/env python3
"""Chip smoke: the build, serve and stream paths once on a TPU, at real size.

    python chip_smoke.py              # one chip: build, serve, stream
    python chip_smoke.py --chips 4    # four chips: mesh builds, sharded serve

The parent only orchestrates.  It never initializes a JAX backend: each
phase that touches the device runs as a child process (the real CLI, or
this script's ``--child stream`` for the library entry point) and exits
before the next one starts, so one process holds the chip at a time.
Every corpus is generated from a seed.

- ``build``: the test_in-shaped Zipf corpus (355 docs, 33K vocabulary,
  2,900 tokens per doc, seed 7) through ``4 26 list.txt --backend=tpu
  --stats`` under each plan; the letter files' md5 must equal the
  oracle backend's, no report may name a fallback, and one plan must
  have run the Pallas dedup kernel compiled for the chip.
- ``serve``: ``serve OUT --engine device`` on the default plan's
  artifact; 20 wire requests (df, postings, and, or, BM25 top_k) must
  be byte-equal to the numpy host engine's answers, computed here in
  the parent.
- ``stream``: BASELINE.json config 4 (100K vocabulary, 40 tokens per
  doc, seed 11; its 1M docs cut to 200K by default, ``--stream-docs``)
  through the host-scan and the device-stream engines; md5 must equal
  the cpu backend's.

Each phase prints one JSON line (wall seconds, compile seconds, md5).
Every child reports the device as JAX sees it; they must agree and the
platform must be ``tpu``.  The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``;
any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
PKG = "parallel_computation_of_an_inverted_index_using_map_reduce_tpu"

#: a test_in-shaped corpus (about 5.8 MB)
BUILD_CORPUS = dict(num_docs=355, vocab_size=33_000, tokens_per_doc=2900,
                    seed=7)
#: BASELINE.json config 4
STREAM_CORPUS = dict(num_docs=1_000_000, vocab_size=100_000,
                     tokens_per_doc=40, seed=11, chunk_docs=100_000)
#: the stream phase's default cut: at 1M docs a cold run took 1,086 s,
#: 677 s of it compiling the two stream engines (PR 21), too close to
#: the 1,200 s limit; 200K docs (two windows) keeps it near 10 minutes
STREAM_DOCS_CUT = 200_000

#: one-chip build plans: name -> extra CLI flags.  ``stream`` is the
#: plan whose device program runs the dedup kernel (ops/engine.py).
BUILD_PLANS = {
    "pipelined": ["--artifact"],
    "overlap": ["--overlap-tail-fraction", "0.5"],
    "device_tokenize": ["--device-tokenize", "--artifact"],
    "stream": ["--stream-chunk-docs", "128", "--artifact"],
}
MESH_PLANS = {
    "mesh_pipelined": ["--device-shards", "4", "--artifact"],
    "mesh_device_tokenize": ["--device-tokenize", "--device-shards", "4"],
}
FALLBACK_KEYS = ("cpu_fallback", "pipelined_fallback",
                 "device_tokenize_fallback")

#: whole-script budget: the driver allows 1200 s, compilation included
DEADLINE_S = 1140.0


class PhaseError(RuntimeError):
    """A phase failed: wrong answer, fallback, crash or timeout."""


class _Clock:
    def __init__(self, budget_s: float):
        self.end = time.monotonic() + budget_s

    def left(self) -> float:
        left = self.end - time.monotonic()
        if left <= 5:
            raise PhaseError("out of time before the next child")
        return left


def _child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH")) if p)
    env.update(extra or {})
    return env


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait()


def _run(cmd: list[str], *, clock: _Clock, env: dict | None = None,
         what: str) -> str:
    """Run a child to its end in its own process group; its stdout."""
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            env=_child_env(env), cwd=REPO,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=clock.left())
    except subprocess.TimeoutExpired:
        raise PhaseError(f"{what}: timed out") from None
    finally:
        if proc.poll() is None:
            _kill_group(proc)
    if proc.returncode != 0:
        raise PhaseError(f"{what}: exit {proc.returncode}: "
                         f"{err.strip()[-2000:]}")
    return out


def _cli(args: list[str], **kw) -> str:
    return _run([sys.executable, "-m", PKG, *args], **kw)


def _last_json(out: str, what: str) -> dict:
    for line in reversed(out.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise PhaseError(f"{what}: no JSON line in its output")


def _device_of(report: dict) -> dict:
    return {"platform": report.get("platform"),
            "kind": report.get("device_kind"),
            "count": report.get("device_count")}


def _emit(record: dict) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


# -- corpus ------------------------------------------------------------


def write_build_corpus(workdir: Path, *, num_docs: int, vocab_size: int,
                       tokens_per_doc: int, seed: int) -> Path:
    """The build corpus as files plus its manifest; the manifest path."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.manifest import (
        write_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        write_corpus, zipf_corpus,
    )

    docs = zipf_corpus(num_docs=num_docs, vocab_size=vocab_size,
                       tokens_per_doc=tokens_per_doc, seed=seed)
    paths = write_corpus(workdir / "docs", docs)
    write_manifest(workdir / "list.txt", paths)
    return workdir / "list.txt"


def _md5(out_dir: Path) -> str:
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.text.formatter import (
        letters_md5,
    )

    return letters_md5(out_dir)


# -- build -------------------------------------------------------------


def phase_build(workdir: Path, list_path: Path, plans: dict, *,
                clock: _Clock, check=None) -> list[dict]:
    """Oracle once, then each plan; every plan's md5 must equal the
    oracle's and no report may name a fallback.  One record per plan;
    ``check(record)`` (the device check) runs as each one lands."""
    oracle_dir = workdir / "out_oracle"
    _cli(["4", "26", str(list_path), "--backend=oracle",
          f"--output-dir={oracle_dir}"], clock=clock, what="build oracle")
    want = _md5(oracle_dir)
    records = []
    for name, flags in plans.items():
        out_dir = workdir / f"out_{name}"
        t0 = time.perf_counter()
        out = _cli(["4", "26", str(list_path), "--backend=tpu", "--stats",
                    f"--output-dir={out_dir}", *flags],
                   clock=clock, what=f"build {name}")
        wall = time.perf_counter() - t0
        report = _last_json(out, f"build {name}")
        record = {"phase": "build", "plan": name, "wall_s": round(wall, 3),
                  "compile_s": report.get("compile_s"),
                  "md5": _md5(out_dir), "md5_oracle": want,
                  "total_ms": report.get("total_ms"),
                  "dedup_kernel": report.get("dedup_kernel"),
                  "device_shards": report.get("device_shards", 1),
                  "device_spread": report.get("device_spread"),
                  "device": _device_of(report), "out_dir": str(out_dir)}
        _emit(record)
        if check is not None:
            check(record)
        fell_back = [k for k in FALLBACK_KEYS if k in report]
        if fell_back:
            raise PhaseError(f"build {name}: report names {fell_back}")
        if record["md5"] != want:
            raise PhaseError(f"build {name}: md5 {record['md5']} != "
                             f"oracle {want}")
        records.append(record)
    return records


# -- serve -------------------------------------------------------------


def _pick_terms(host) -> dict:
    """Terms that occur in the corpus, by df: frequent, middling, rare."""
    terms = []
    for letter in "abcdefghijklmnopqrstuvwxyz":
        terms += host.top_k(letter, host.vocab_size)
    terms.sort(key=lambda td: (-td[1], td[0]))
    words = [t.decode() for t, _ in terms]
    n = len(words)
    if n < 64:
        raise PhaseError(f"serve: only {n} terms in the corpus")
    return {"hot": words[:8], "mid": words[n // 4:n // 4 + 8],
            "rare": words[-8:]}


def _host_answer(host, req: dict) -> dict:
    """The numpy host engine's payload for one wire request (the
    daemon's _exec_one, without the daemon)."""
    batch = host.encode_batch(req["terms"])
    op = req["op"]
    if op == "df":
        return {"ok": True, "df": host.df(batch).tolist()}
    if op == "postings":
        return {"ok": True, "postings": [
            r.tolist() if r is not None else None
            for r in host.postings(batch)]}
    if op == "and":
        return {"ok": True, "docs": host.query_and(batch).tolist()}
    if op == "or":
        return {"ok": True, "docs": host.query_or(batch).tolist()}
    top = host.top_k_scored(batch, req["k"])
    return {"ok": True, "docs": [[d, s] for d, s in top]}


def serve_requests(terms: dict) -> list[dict]:
    hot, mid, rare = terms["hot"], terms["mid"], terms["rare"]
    reqs = []
    for i in range(4):
        reqs.append({"op": "df", "terms": [hot[i], mid[i], rare[i]]})
        reqs.append({"op": "postings", "terms": [mid[i + 4], rare[i + 4]]})
        reqs.append({"op": "and", "terms": [hot[i], hot[i + 4], mid[i]]})
        reqs.append({"op": "or", "terms": [mid[i], rare[i], rare[i + 4]]})
        reqs.append({"op": "top_k", "score": "bm25", "k": 10,
                     "terms": [hot[i], mid[i + 4], rare[i]]})
    return reqs


def _wire(sock_file, req: dict) -> dict:
    sock_file.write((json.dumps(req) + "\n").encode())
    sock_file.flush()
    line = sock_file.readline()
    if not line:
        raise PhaseError("serve: daemon closed the connection")
    return json.loads(line)


def phase_serve(artifact_dir: Path, *, clock: _Clock,
                env: dict | None = None, label: str = "serve",
                check=None) -> dict:
    """Device-engine daemon answers vs the host engine, byte for byte."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.serve.engine import (
        NATIVE_ENV, Engine,
    )

    saved = os.environ.get(NATIVE_ENV)
    os.environ[NATIVE_ENV] = "0"  # the numpy reference engine
    try:
        host = Engine(artifact_dir)
    finally:
        if saved is None:
            os.environ.pop(NATIVE_ENV)
        else:
            os.environ[NATIVE_ENV] = saved
    reqs = serve_requests(_pick_terms(host))
    want = [_host_answer(host, r) for r in reqs]
    host.close()

    cmd = [sys.executable, "-m", PKG, "serve", str(artifact_dir),
           "--engine", "device", "--listen", "127.0.0.1:0"]
    # stderr to a file: a pipe nobody drains could stall the daemon
    err_path = artifact_dir.parent / f"{label}.stderr"
    with open(err_path, "wb") as err:
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=err,
                                env=_child_env(env), cwd=REPO,
                                start_new_session=True)
    t0 = time.perf_counter()
    try:
        ready = _await_ready(proc, clock, err_path)
        startup = time.perf_counter() - t0
        with socket.create_connection(
                (ready["host"], ready["port"]), timeout=clock.left()) as s:
            f = s.makefile("rwb")
            t1 = time.perf_counter()
            got = []
            for i, req in enumerate(reqs):
                resp = _wire(f, {"id": i, **req})
                resp.pop("id", None)
                resp.pop("trace_id", None)
                got.append(resp)
            answer_s = time.perf_counter() - t1
            stats = _wire(f, {"id": len(reqs), "op": "stats"})["stats"]
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=min(60.0, clock.left()))
        if proc.returncode != 0:
            raise PhaseError(f"{label}: daemon exit {proc.returncode}: "
                             f"{err_path.read_text()[-2000:]}")
    finally:
        if proc.poll() is None:
            _kill_group(proc)
    bad = [i for i, (g, w) in enumerate(zip(got, want))
           if json.dumps(g, sort_keys=True) != json.dumps(w, sort_keys=True)]
    engine = stats.get("engine", {})
    dev = engine.get("device", {})
    record = {"phase": label, "requests": len(reqs), "mismatches": len(bad),
              "startup_s": round(startup, 3), "answer_s": round(answer_s, 3),
              "compile_s": dev.get("compile_s"),
              "engine": engine.get("engine"), "shards": dev.get("shards"),
              "shard_devices": len(set(dev.get("devices", []))),
              "device": {"platform": dev.get("platform"),
                         "kind": dev.get("device_kind"),
                         "count": dev.get("device_count")}}
    _emit(record)
    if check is not None:
        check(record)
    if engine.get("engine") != "device":
        raise PhaseError(f"{label}: daemon served from "
                         f"{engine.get('engine')!r}, not the device")
    if bad:
        i = bad[0]
        raise PhaseError(f"{label}: {len(bad)} answers differ from the host "
                         f"engine; first {reqs[i]}: {got[i]} != {want[i]}")
    return record


def _await_ready(proc: subprocess.Popen, clock: _Clock,
                 err_path: Path) -> dict:
    """The daemon's ``listening`` line, its first line on stdout."""
    import selectors

    sel = selectors.DefaultSelector()
    sel.register(proc.stdout, selectors.EVENT_READ)
    while not sel.select(timeout=min(5.0, clock.left())):
        if proc.poll() is not None:
            break
    line = proc.stdout.readline()
    if line.startswith(b"{"):
        return json.loads(line)
    raise PhaseError(f"serve: no listening line (exit {proc.poll()}): "
                     f"{err_path.read_text()[-2000:]}")


# -- stream ------------------------------------------------------------


def stream_child(workdir: Path, *, num_docs: int, vocab_size: int,
                 tokens_per_doc: int, seed: int, chunk_docs: int) -> int:
    """Child: the library entry point on the virtual config-4 manifest —
    cpu reference, host-scan stream, device stream.  One JSON line per
    engine, printed as each finishes."""
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        IndexConfig, InvertedIndexModel,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.corpus.synthetic import (
        synthetic_manifest,
    )
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu.utils import (
        device,
    )

    device.setup()
    import jax

    manifest = synthetic_manifest(num_docs=num_docs, vocab_size=vocab_size,
                                  tokens_per_doc=tokens_per_doc, seed=seed)
    engines = {
        "cpu": IndexConfig(backend="cpu"),
        "host_stream": IndexConfig(backend="tpu", device_shards=1,
                                   stream_chunk_docs=chunk_docs),
        "device_stream": IndexConfig(backend="tpu", device_shards=1,
                                     device_tokenize=True,
                                     stream_chunk_docs=chunk_docs),
    }
    for name, cfg in engines.items():
        out_dir = workdir / f"stream_{name}"
        c0 = device.compile_seconds()
        t0 = time.perf_counter()
        report = InvertedIndexModel(cfg).run(manifest, str(out_dir))
        wall = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        line = {**device.identity(), "engine": name,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "wall_s": round(wall, 3),
                "compile_s": round(device.compile_seconds() - c0, 3),
                "md5": _md5(out_dir), "docs": num_docs,
                "unique_pairs": report.get("unique_pairs"),
                "stream_windows": report.get("stream_windows"),
                "fallbacks": [k for k in FALLBACK_KEYS if k in report]}
        print(json.dumps(line, sort_keys=True), flush=True)
        shutil.rmtree(out_dir, ignore_errors=True)
    return 0


def phase_stream(workdir: Path, *, clock: _Clock, num_docs: int,
                 vocab_size: int, tokens_per_doc: int, seed: int,
                 chunk_docs: int, check=None) -> list[dict]:
    out = _run([sys.executable, str(Path(__file__).resolve()), "--child",
                "stream", "--workdir", str(workdir),
                "--docs", str(num_docs), "--vocab", str(vocab_size),
                "--tokens", str(tokens_per_doc), "--seed", str(seed),
                "--chunk", str(chunk_docs)], clock=clock, what="stream")
    lines = {}
    for line in out.strip().splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            lines[rec["engine"]] = rec
    want = lines["cpu"]["md5"]
    records = []
    for name in ("host_stream", "device_stream"):
        rec = lines[name]
        record = {"phase": "stream", "engine": name, "docs": rec["docs"],
                  "wall_s": rec["wall_s"], "compile_s": rec["compile_s"],
                  "md5": rec["md5"], "md5_cpu": want,
                  "cpu_wall_s": lines["cpu"]["wall_s"],
                  "unique_pairs": rec["unique_pairs"],
                  "stream_windows": rec["stream_windows"],
                  "peak_bytes_in_use": rec["peak_bytes_in_use"],
                  "device": _device_of(rec)}
        _emit(record)
        if check is not None:
            check(record)
        if rec["fallbacks"]:
            raise PhaseError(f"stream {name}: report names "
                             f"{rec['fallbacks']}")
        if rec["md5"] != want:
            raise PhaseError(f"stream {name}: md5 {rec['md5']} != cpu "
                             f"{want}")
        records.append(record)
    return records


# -- checks ------------------------------------------------------------


def check_device(records: list[dict], count: int) -> dict:
    """Every child saw the same device, a TPU, ``count`` of them."""
    seen = {json.dumps(r["device"], sort_keys=True) for r in records}
    if len(seen) != 1:
        raise PhaseError(f"children disagree on the device: {sorted(seen)}")
    dev = records[0]["device"]
    if dev["platform"] != "tpu":
        raise PhaseError(f"platform is {dev['platform']!r}, not 'tpu': "
                         "JAX found no TPU")
    if dev["count"] != count:
        raise PhaseError(f"{dev['count']} devices, expected {count}")
    return dev


def check_spread(records: list[dict], count: int) -> None:
    """Mesh builds and the sharded engine placed their arrays on
    ``count`` distinct devices (an output's or a column's sharding, not
    the count requested), not the first one ``count`` times."""
    for r in records:
        used = (r.get("device_spread") if r["phase"] == "build"
                else r.get("shard_devices"))
        if used != count:
            raise PhaseError(f"{r['phase']} {r.get('plan', '')}: spread over "
                             f"{used} devices, expected {count}")


def check_dedup_compiled(records: list[dict]) -> None:
    """Some build plan ran the Pallas dedup kernel compiled, not
    interpreted and not the XLA path."""
    kernels = {r["plan"]: r.get("dedup_kernel") for r in records}
    if "pallas" not in kernels.values():
        raise PhaseError(f"no build plan ran the compiled Pallas dedup "
                         f"kernel: {kernels}")


def _native_ready() -> None:
    from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import (
        native,
    )

    if not native.available():
        raise PhaseError(f"native library did not load: "
                         f"{native.load_error()}")


def _assert_no_backend() -> None:
    """The parent must never hold the chip."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and getattr(xb, "_backends", None):
        raise PhaseError("the parent process initialized a JAX backend")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--stream-docs", type=int, default=STREAM_DOCS_CUT)
    p.add_argument("--child", choices=("stream",), help=argparse.SUPPRESS)
    p.add_argument("--workdir", help=argparse.SUPPRESS)
    p.add_argument("--docs", type=int, help=argparse.SUPPRESS)
    p.add_argument("--vocab", type=int, help=argparse.SUPPRESS)
    p.add_argument("--tokens", type=int, help=argparse.SUPPRESS)
    p.add_argument("--seed", type=int, help=argparse.SUPPRESS)
    p.add_argument("--chunk", type=int, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.child == "stream":
        return stream_child(Path(args.workdir), num_docs=args.docs,
                            vocab_size=args.vocab, tokens_per_doc=args.tokens,
                            seed=args.seed, chunk_docs=args.chunk)

    one_chip = args.chips == 1
    if one_chip and args.stream_docs != STREAM_CORPUS["num_docs"]:
        print(f"chip_smoke: stream corpus cut to {args.stream_docs} of "
              f"{STREAM_CORPUS['num_docs']} docs", flush=True)
    clock = _Clock(DEADLINE_S)
    workdir = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    records: list[dict] = []

    def check(record: dict) -> None:
        # fail on the first child that saw the wrong device
        records.append(record)
        check_device(records, args.chips)

    try:
        _native_ready()
        plans = BUILD_PLANS if one_chip else MESH_PLANS
        list_path = write_build_corpus(workdir, **BUILD_CORPUS)
        _assert_no_backend()
        build = phase_build(workdir, list_path, plans, clock=clock,
                            check=check)
        if one_chip:
            check_dedup_compiled(build)
        art = Path(next(r["out_dir"] for r in build
                        if "--artifact" in plans[r["plan"]]))
        env = None if one_chip else {"MRI_SERVE_SHARDS": "4"}
        _assert_no_backend()
        phase_serve(art, clock=clock, env=env, check=check)
        if one_chip:
            _assert_no_backend()
            phase_stream(workdir, clock=clock, check=check,
                         **{**STREAM_CORPUS, "num_docs": args.stream_docs})
        else:
            check_spread(records, 4)
        dev = check_device(records, args.chips)
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

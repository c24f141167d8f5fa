"""Command-line driver, compatible with the reference's invocation.

Reference: ``./tema1 <num_mappers> <num_reducers> <input_file>``
(main.c:248-255, README.md).  Here the same three positionals work —
outputs a.txt..z.txt land in the CWD by default, exactly like the
reference — plus flags for the TPU-era knobs:

    python -m parallel_computation_of_an_inverted_index_using_map_reduce_tpu \
        4 26 test_small.txt --backend=tpu --output-dir=out --stats
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from . import faults
from .audit import AuditError, verify_output_dir
from .config import IndexConfig
from .corpus.manifest import read_manifest
from .models.inverted_index import build_index
from .utils import device, envknobs
from .utils.checkpoint import CheckpointCorrupt

_EPILOG = """\
exit codes:
  0  clean run (output complete and, under --audit, integrity-checked);
     for 'serve': graceful drain completed
  1  serve daemon forced exit (second SIGTERM/SIGINT during drain)
  2  error (bad arguments, I/O failure, integrity/audit failure)
  3  degraded (completed, but skipped unreadable documents or lost
     windows after exhausting retry/respawn budgets; see the
     'degradation' block of --stats)

fault-spec grammar (test/bench only; clauses joined by ';'):
  read-error:doc=2:times=2       transient OSError, first 2 attempts
  read-error:all:times=-1        permanent OSError on every doc
  slow-read:doc=1:ms=50          sleep before the read
  truncate:doc=4:bytes=10        document bytes cut short
  reader-death:window=1          silent reader-thread death
  sigkill:window=2               SIGKILL at stream window boundary
  worker-death:worker=1:window=2 scan worker dies at a window (the
                                 lease/requeue recovery rescans it)
  worker-death:window=2          ... whichever worker scans window 2
  reducer-death:reducer=0        reduce worker dies pre-emit (a
                                 survivor re-emits its letter range)
  scan-error:window=3            native scan failure on window 3
  scan-error:window=3:silent=1   window silently dropped (--audit
                                 catches the corruption)
  handler-crash:req=3            serve daemon: request 3's handler dies
                                 (answered with a counted 'internal')
  client-disconnect:req=2        serve daemon: peer vanishes as
                                 response 2 is written
  slow-client:req=1:ms=200       serve daemon: response write stalls
  reload-corrupt                 serve daemon: next hot reload fails
                                 verification (old artifact keeps
                                 serving, 'reload_rejected' counted)
  dispatcher-hang:ms=500         serve daemon: the dispatch loop wedges
                                 for ms on its next batch (proves the
                                 watchdog: stall event + flight dump +
                                 healthz readiness flip)
  append-torn-manifest           segments: the staged manifest is torn
                                 mid-publish — the append aborts and
                                 the old generation keeps serving
  compact-crash                  segments: crash after the merged
                                 segment is built, before the swap
  tombstone-corrupt              segments: staged tombstone bitmap
                                 corrupted; the write is rejected
  wal-torn-record                segments: the WAL append tears before
                                 its fsync — the mutation is rejected
                                 un-acked; recover quarantines the
                                 torn tail bytes
  fetch-partial                  replication: one shipped segment file
                                 is truncated in flight — the replica's
                                 adler32 check rejects it and refetches
  lease-steal                    replication: a foreign owner grabs the
                                 mutation lease — the next mutation is
                                 rejected 'lease_lost' until the TTL
                                 expires
  shard-dead:shard=0             router: the next send to shard 0
                                 hits a reset connection (failover
                                 retries another replica)
  shard-slow:shard=1:ms=50       router: sends to shard 1 stall 50 ms
                                 (the hedge path's test hook)
  router-conn-reset:req=3        router: client connection 3 is reset
                                 mid-stream (exactly-once: admitted
                                 requests still answer or count)
  shard-blackout:shard=0         router: EVERY send to shard 0 dies,
                                 all replicas, permanently — drives
                                 the partial-result/breaker paths
  overload-storm:req=8:times=16  daemon: requests 8..23 shed with a
                                 typed 'overloaded' answer (synthetic
                                 sustained overload for admission-
                                 control soaks)
  chaos:seed=5:n=3               sample 3 faults deterministically
                                 (bounds: windows= workers= reducers=
                                 docs= reqs= kinds=a,b,c)

verify mode:
  mri-tpu --verify DIR           re-check DIR's letter files (and
                                 index.mri, when present) against its
                                 index.manifest.json (written by
                                 --audit runs); a segment-managed DIR
                                 additionally re-hashes every live
                                 segment + tombstone file against
                                 segments.manifest.json; exit 0 ok,
                                 2 mismatch

incremental indexing (live index; see README "Incremental indexing"):
  mri-tpu append DIR --add F...  index new files as one immutable
                                 segment and publish the next manifest
                                 generation (first append seeds the
                                 manifest from DIR's index.mri)
  mri-tpu delete DIR --docs N... tombstone global doc ids (query-
                                 invisible at once; space reclaimed at
                                 compaction)
  mri-tpu compact DIR            k-way merge the cheapest adjacent
                                 segment run into one replacement
                                 segment, dropping its tombstones
  mri-tpu compact DIR --prune    also delete retired segment dirs no
                                 longer referenced by the manifest
                                 (only safe with no live readers on
                                 older generations)

durability & replication (see README "Durability & replication"):
  mri-tpu recover DIR            replay the mutation WAL after a crash:
                                 acknowledged-but-unpublished records
                                 are applied, torn tail records land in
                                 segments.wal.corrupt, mutation scratch
                                 is swept; idempotent (a primary daemon
                                 runs this on every start)
  mri-tpu replicate DIR --from HOST:PORT
                                 one catch-up round against a primary
                                 daemon: snapshot diff, adler32-verified
                                 segment fetches, WAL tail adoption —
                                 never re-indexes
  mri-tpu serve DIR --replica-of HOST:PORT
                                 run a read-only replica: catches up
                                 every MRI_REPLICA_POLL_MS ms, rejects
                                 mutations, healthz says
                                 'replica_lagging' until the first
                                 round lands; promote by stopping it
                                 and running 'mri-tpu recover DIR'

query mode (the serving read path; needs an --artifact build):
  mri-tpu query DIR word...          df + postings per word (JSON lines)
  mri-tpu query DIR --batch-file F   one query word per line (an empty
                                 file is an empty batch: exit 0, no
                                 output)
  mri-tpu query DIR --op and w1 w2   docs containing every word
  mri-tpu query DIR --op or  w1 w2   docs containing any word
  mri-tpu query DIR --top-k 5 --letter t   the letter's 5 highest-df
                                 terms (== head -5 DIR/t.txt)
  mri-tpu query DIR --score bm25 --top-k 5 w1 w2   the 5 best-scoring
                                 docs for the words (BM25: tf + df +
                                 doc-length norm; format-v2 artifacts
                                 carry real tf, v1 scores with tf=1)
  mri-tpu query DIR --engine device  answer from the device-resident
                                 jit/shard_map engine (--engine auto,
                                 the default, picks it on accelerator
                                 backends); byte-identical to host
  a missing/torn index.mri exits 2 with one line on stderr, never
  garbage answers

serve mode (resident daemon; loads the artifact ONCE):
  mri-tpu serve DIR --listen 127.0.0.1:7070
                                 JSON-lines protocol over TCP — one
                                 request object per line, one response
                                 line back; ops df/postings/and/or/
                                 top_k plus stats/healthz/reload;
                                 pending requests coalesce into micro-
                                 batches for the vectorized batch path
                                 (MRI_SERVE_COALESCE_US window); the
                                 pending queue is bounded (MRI_SERVE_
                                 QUEUE_DEPTH) with counted 'overloaded'
                                 shedding, requests may carry
                                 deadline_ms ('deadline_expired' when
                                 missed before dispatch); SIGTERM/
                                 SIGINT = graceful drain then exit 0
                                 (second signal forces exit 1); SIGHUP
                                 = crash-safe hot reload of index.mri
                                 (a failed verification keeps the old
                                 artifact and counts reload_rejected)

cluster mode (doc-sharded scale-out; see README "Cluster serving"):
  mri-tpu shard LIST --shards 4 --out DIR [--mode size-balanced]
                                 partition the corpus into 4 doc-
                                 shards under DIR/shard-N, build each
                                 with the unchanged --artifact path,
                                 and stamp global BM25 stats into
                                 per-shard sidecars; --verify byte-
                                 checks every per-shard manifest
  mri-tpu serve DIR/shard-N --listen ...   a shard daemon is a plain
                                 serve daemon over the shard dir (the
                                 sidecar makes it answer global ids)
  mri-tpu router --shards h:1|h:2,h:3 --listen HOST:PORT
                                 scatter-gather front-end: same JSON-
                                 lines protocol, data ops fan out and
                                 gather (D-way ranked merge); '|'
                                 joins replicas of one shard — hedged
                                 requests (MRI_CLUSTER_HEDGE_MS) and
                                 failover ride per-replica health
                                 probes (MRI_CLUSTER_HEALTH_MS);
                                 answers are byte-identical to one
                                 monolithic daemon over the same
                                 corpus, BM25 floats included
  mri-tpu top ROUTER:PORT        fleet view: the router's stats carry
                                 per-shard replica health rows with
                                 circuit-breaker state and per-shard
                                 partial-coverage readiness
  degraded serving: requests may carry partial_policy 'fail' (default:
                                 a dead shard is a typed
                                 shard_unavailable error) or
                                 'allow:min_coverage=F' (answer from
                                 the live shards, flagged partial:true
                                 with coverage metadata);
                                 MRI_CLUSTER_PARTIAL sets the router
                                 default, MRI_CLUSTER_RETRY_BUDGET
                                 bounds retry/hedge amplification,
                                 MRI_SERVE_CODEL_TARGET_MS arms CoDel
                                 admission control in shard daemons

metrics mode (Prometheus text exposition; obs/ registry):
  mri-tpu metrics DIR            open DIR's artifact, print the engine
                                 registry in Prometheus text format
  mri-tpu metrics HOST:PORT      ask a running serve daemon (the
                                 'metrics' admin op) and print its text
  mri-tpu serve DIR --listen-metrics PORT
                                 daemon also serves the same text over
                                 plain HTTP on 127.0.0.1:PORT (a scrape
                                 endpoint; 0 = ephemeral)

top mode (operational health; see README "Operational health"):
  mri-tpu top HOST:PORT          live dashboard over a running daemon's
                                 stats/slo/healthz admin ops: rolling
                                 qps + latency quantiles (10s/1m/5m),
                                 SLO ratios and burn rates, readiness
                                 with reasons; redraws every --interval
                                 seconds, Ctrl-C exits 0
  mri-tpu top HOST:PORT --once --json   one machine-readable sample
                                 (scripting and parity checks)
  mri-tpu top DIR                one static engine metrics snapshot of
                                 a built artifact (nothing rolls
                                 without a daemon)
"""


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mri-tpu",
        description="TPU-native inverted-index MapReduce",
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("num_mappers", type=int,
                   help="host shard count (reference mapper threads; "
                        "backend=cpu scan workers; output-invariant)")
    p.add_argument("num_reducers", type=int,
                   help="reduce partition count (reference reducer threads; "
                        "backend=cpu letter-range reduce workers; "
                        "output-invariant)")
    p.add_argument("file_list", help="manifest: count header then one path per line")
    p.add_argument("--backend", choices=("tpu", "cpu", "oracle"), default="tpu",
                   help="tpu: device engine; cpu: one native host call; "
                        "oracle: pure-Python conformance backend")
    p.add_argument("--output-dir", default=".", help="where a.txt..z.txt are written (default: CWD)")
    p.add_argument("--pad-multiple", type=int, default=1 << 16)
    p.add_argument("--checkpoint", default=None,
                   help="save/resume the tokenized map-phase pairs at this path")
    p.add_argument("--profile-dir", default=None, help="write a jax.profiler trace here")
    p.add_argument("--stats", action="store_true", help="print a JSON stats line to stdout")
    p.add_argument("--skew", action="store_true",
                   help="also measure letter vs hash-bucket partition skew on device")
    p.add_argument("--stream-chunk-docs", type=int, default=None,
                   help="streaming mode: window size in whole documents "
                        "(bounded host/device memory; default: one-shot)")
    p.add_argument("--pipeline-chunk-docs", type=int, default=None,
                   help="pipelined fast path: documents per upload window "
                        "(default: auto, two windows; 0 = one-shot engine)")
    p.add_argument("--device-tokenize", action="store_true",
                   help="all-device engine: raw corpus bytes up, finished "
                        "index down (the whole map phase as one XLA program; "
                        "single chip; exact, with host fallback for tokens "
                        "longer than --device-tokenize-width)")
    p.add_argument("--device-tokenize-width", type=int, default=48,
                   help="device word-row bytes (multiple of 4)")
    p.add_argument("--device-shards", type=int, default=None,
                   help="mesh size: shard the device engine over this many "
                        "chips (default: all visible devices; 1 = single "
                        "chip)")
    p.add_argument("--overlap-tail-fraction", type=float, default=None,
                   help="windowed overlap plan: this fraction of corpus "
                        "bytes (the last doc range) is indexed on host "
                        "while earlier windows' device sorts + fetches fly "
                        "in the background (single chip; hides link RTT)")
    p.add_argument("--overlap-device-windows", type=int, default=2,
                   choices=(1, 2),
                   help="overlap plan device windows: 2 = earliest first "
                        "fetch, 1 = half the dispatch RPCs")
    p.add_argument("--overlap-window-split", type=float, default=0.55,
                   help="first device window's share of the overlap "
                        "plan's device bytes; larger shrinks the LAST "
                        "window and with it the residual fetch wait")
    p.add_argument("--stream-checkpoint", default=None,
                   help="crash-resumable streaming (single-chip "
                        "--device-tokenize --stream-chunk-docs "
                        "--device-shards 1): persist the verified "
                        "accumulator here; a rerun of the same command "
                        "resumes at the last checkpointed window")
    p.add_argument("--stream-checkpoint-every", type=int, default=2,
                   help="windows between stream checkpoints")
    p.add_argument("--host-threads", type=int, default=None,
                   help="host map-phase threads — backend=cpu scan workers "
                        "pulling windows from a shared steal queue "
                        "(default: num_mappers if > 1, else min(cores, 8)); "
                        "output-invariant")
    p.add_argument("--emit-ownership", choices=("merged", "letter"),
                   default="merged",
                   help="merged: one host writes all 26 files; letter: "
                        "multi-chip owners emit their own letter ranges "
                        "(the reference's reducer ownership, multi-host mode)")
    p.add_argument("--emit-backend", choices=("auto", "native", "python"),
                   default="auto",
                   help="letter-file writer: auto = native vectorized emit "
                        "when available, python = the pure-Python parity "
                        "oracle; byte-identical either way")
    p.add_argument("--io-prefetch", type=int, default=2,
                   help="backend=cpu read-ahead depth: window arenas the "
                        "reader thread keeps filled while the native scan "
                        "runs (0 = one-shot load, no pipeline)")
    p.add_argument("--resume", choices=("strict", "auto"), default="strict",
                   help="checkpoint-trust policy: strict = a corrupt "
                        "checkpoint is a hard error; auto = quarantine it "
                        "to <path>.corrupt and restart fresh (crash-safe "
                        "rerun after SIGKILL mid-save)")
    p.add_argument("--fault-spec", default=None,
                   help="arm the deterministic fault injector (faults.py "
                        "grammar, e.g. 'read-error:doc=2:times=2' or "
                        "'worker-death:window=2;chaos:seed=5:n=3'; also "
                        f"readable from ${faults.ENV_VAR}) — test/bench "
                        "only, never needed for production runs")
    p.add_argument("--artifact", action="store_true",
                   help="also pack the compact mmap serving artifact "
                        "(index.mri) next to the letter files at emit "
                        "time — the read path 'mri-tpu query' and "
                        "serve.Engine load (serve/artifact.py format)")
    p.add_argument("--audit", action="store_true",
                   help="integrity audit: per-window feed ledger + merge "
                        "invariant checks before emit, and an "
                        "index.manifest.json output manifest (per-file "
                        "adler32) after it; audit failures exit 2, never "
                        "silently wrong bytes")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a Chrome trace_event JSON timeline of the "
                        "build here (reader/scan/reduce/merge spans; load "
                        "in chrome://tracing or ui.perfetto.dev)")
    return p


def _query_main(argv: list[str]) -> int:
    """``mri-tpu query DIR ...`` — serve from an --artifact build."""
    p = argparse.ArgumentParser(
        prog="mri-tpu query",
        description="batched lookups against a built index.mri artifact")
    p.add_argument("index_dir", help="output dir of an --artifact run "
                                     "(or the index.mri file itself)")
    p.add_argument("terms", nargs="*", help="query words")
    p.add_argument("--batch-file", default=None,
                   help="read query words from this file, one per line")
    p.add_argument("--op", choices=("and", "or"), default=None,
                   help="combine ALL query words into one multi-term "
                        "query instead of answering each separately")
    p.add_argument("--top-k", type=int, default=None, metavar="K",
                   help="df mode: the K highest-df terms of --letter's "
                        "range; bm25 mode (--score bm25): the K best-"
                        "scoring documents for the query words")
    p.add_argument("--letter", default=None,
                   help="letter for --top-k (a..z)")
    p.add_argument("--score", choices=("df", "bm25"), default=None,
                   help="--top-k scoring mode: df = per-letter highest-"
                        "df terms (today's behavior), bm25 = ranked "
                        "document retrieval over the query words (tf + "
                        "df + doc-length norm; needs a v2 artifact for "
                        "real tf, v1 scores with tf=1). Default: "
                        "MRI_SERVE_SCORE env, else df")
    p.add_argument("--engine", choices=("host", "device", "auto"),
                   default=None,
                   help="query backend: host = numpy over mmap views; "
                        "device = jit/shard_map over device-resident "
                        "columns (batched lookups sharded across "
                        "chips); auto = device when jax's default "
                        "backend is an accelerator, else host "
                        "(default: MRI_SERVE_ENGINE env, else auto). "
                        "Answers are byte-identical either way")
    p.add_argument("--stats", action="store_true",
                   help="print an engine stats JSON line last (engine/"
                        "shard info, cache hit/miss/eviction counters, "
                        "per-op timing)")
    p.add_argument("--explain", action="store_true",
                   help="print a per-request cost report JSON line "
                        "after the answers: per-term df and resolution "
                        "path, planner decision with its theta "
                        "progression, blocks scored/skipped, bytes "
                        "decoded, cache hits/misses (per segment on a "
                        "segment-managed dir)")
    # intermixed: ``query DIR --op and the dog`` must not feed "the dog"
    # back into --op's greedy positional scan.
    args = p.parse_intermixed_args(argv)

    from .serve import ArtifactError, create_engine
    from .serve.engine import resolve_score

    try:
        score = resolve_score(args.score)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    terms = list(args.terms)
    if args.batch_file is not None:
        try:
            # mrilint: allow(fault-boundary) operator-supplied batch file, not corpus I/O; OSError maps to exit 2 below
            with open(args.batch_file, "r", encoding="utf-8") as f:
                terms.extend(line.strip() for line in f if line.strip())
        except OSError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    if args.top_k is None and not terms:
        # an empty --batch-file is a valid (empty) batch: answer it
        # with no output, exit 0 — only a missing query is an error
        if args.batch_file is not None:
            return 0
        print("error: no query terms (positional words, --batch-file, "
              "or --top-k with --letter)", file=sys.stderr)
        return 2
    ranked = args.top_k is not None and score == "bm25"
    if args.top_k is not None and not ranked and args.letter is None:
        print("error: --top-k needs --letter (or --score bm25 with "
              "query terms)", file=sys.stderr)
        return 2
    if ranked and not terms:
        print("error: --score bm25 --top-k needs query terms",
              file=sys.stderr)
        return 2
    try:
        engine = create_engine(args.index_dir, args.engine)
    except (ArtifactError, ValueError) as e:
        # ValueError covers construction-time knob reads (KnobError,
        # e.g. a bad $MRI_SERVE_NATIVE) — same one-line exit-2
        # contract the lazily-read knobs get from the query guard
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.explain:
        from .obs import attribution as obs_attrib
        if ranked:
            explain_op = "top_k_scored"
        elif args.top_k is not None:
            explain_op = "top_k"
        elif args.op is not None:
            explain_op = f"query_{args.op}"
        else:
            explain_op = "df+postings"
        explain_cm = obs_attrib.collect(explain_op)
    else:
        explain_cm = None
    try:
        coll = explain_cm.__enter__() if explain_cm is not None else None
        if ranked:
            top = engine.top_k_scored(engine.encode_batch(terms),
                                      args.top_k)
            print(json.dumps({
                "score": "bm25", "k": args.top_k, "terms": terms,
                "docs": [{"doc": d, "score": round(s, 6)}
                         for d, s in top]}))
        elif args.top_k is not None:
            top = engine.top_k(args.letter, args.top_k)
            print(json.dumps({
                "letter": args.letter,
                "top": [{"term": t.decode("ascii"), "df": d}
                        for t, d in top]}))
        if terms and not ranked and args.op is not None:
            batch = engine.encode_batch(terms)
            docs = (engine.query_and(batch) if args.op == "and"
                    else engine.query_or(batch))
            print(json.dumps({"op": args.op, "terms": terms,
                              "docs": docs.tolist()}))
        elif terms and not ranked:
            batch = engine.encode_batch(terms)
            dfs = engine.df(batch)
            posts = engine.postings(batch)
            for term, d, ids in zip(terms, dfs.tolist(), posts):
                print(json.dumps({
                    "term": term, "found": ids is not None, "df": d,
                    "postings": ids.tolist() if ids is not None else []}))
        if coll is not None:
            explain_cm.__exit__(None, None, None)
            explain_cm = None
            print(json.dumps({"explain": coll.report()}))
        if args.stats:
            print(json.dumps(engine.describe()))
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    finally:
        if explain_cm is not None:
            explain_cm.__exit__(None, None, None)
        engine.close()
    return 0


def _serve_main(argv: list[str]) -> int:
    """``mri-tpu serve DIR --listen HOST:PORT`` — the resident daemon
    (serve/daemon.py).  Blocks until drained by SIGTERM/SIGINT."""
    import signal
    import threading

    p = argparse.ArgumentParser(
        prog="mri-tpu serve",
        description="resident JSON-lines query daemon over a built "
                    "index.mri artifact (see the main --help epilog "
                    "for the protocol and signal semantics)")
    p.add_argument("index_dir", help="output dir of an --artifact run "
                                     "(or the index.mri file itself)")
    p.add_argument("--listen", default="127.0.0.1:0", metavar="HOST:PORT",
                   help="bind address (port 0 = ephemeral; the chosen "
                        "port is printed in the 'listening' JSON line)")
    p.add_argument("--engine", choices=("host", "device", "auto"),
                   default=None,
                   help="query backend (same choices as 'query')")
    p.add_argument("--cache-terms", type=int, default=4096,
                   help="hot-term LRU capacity (host engine)")
    p.add_argument("--shards", type=int, default=None,
                   help="device engine mesh size")
    p.add_argument("--fault-spec", default=None,
                   help="arm the deterministic fault injector "
                        "(serve kinds: handler-crash/client-disconnect/"
                        "slow-client/reload-corrupt/dispatcher-hang) "
                        "— test/bench only")
    p.add_argument("--listen-metrics", type=int, default=None,
                   metavar="PORT",
                   help="also serve Prometheus text metrics over plain "
                        "HTTP on 127.0.0.1:PORT (0 = ephemeral; the "
                        "chosen port is printed in the 'listening' line)")
    p.add_argument("--replica-of", default=None, metavar="HOST:PORT",
                   help="run as a read-only replica of the primary "
                        "daemon at HOST:PORT: catch up by segment "
                        "shipping every MRI_REPLICA_POLL_MS, reject "
                        "mutations, report replica_lagging in healthz "
                        "until the first round succeeds")
    args = p.parse_args(argv)

    # the daemon is the one long-lived process: route every mri_tpu.*
    # logger through the structured obs funnel (MRI_OBS_LOG_FORMAT).
    # NOT done for in-process embedding (ServeDaemon.start()) — a host
    # application owns its own logging tree.
    from .obs import logging as obs_logging
    obs_logging.configure()

    if args.fault_spec is not None:
        try:
            faults.install(args.fault_spec)
        except faults.FaultSpecError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    host, _, port_s = args.listen.rpartition(":")
    try:
        port = int(port_s)
        if not host or not (0 <= port <= 65535):
            raise ValueError
    except ValueError:
        print(f"error: --listen must be HOST:PORT, got {args.listen!r}",
              file=sys.stderr)
        return 2

    from .serve import ArtifactError
    from .serve.daemon import ServeDaemon

    if args.listen_metrics is not None and not (
            0 <= args.listen_metrics <= 65535):
        print(f"error: --listen-metrics must be 0..65535, got "
              f"{args.listen_metrics}", file=sys.stderr)
        return 2

    from . import segments
    if args.replica_of is not None:
        try:
            segments.replica.parse_addr(args.replica_of)
        except segments.SegmentError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    try:
        # resolved before the daemon exists so a bad value is the
        # one-line exit-2 knob contract, not a traceback mid-serve
        gc_freeze = envknobs.get("MRI_SERVE_GC_FREEZE")
        # construction runs startup WAL recovery (primaries) before the
        # first engine open — a torn directory rejects here, exit 2
        daemon = ServeDaemon(args.index_dir, host, port,
                             engine=args.engine,
                             cache_terms=args.cache_terms,
                             shards=args.shards,
                             metrics_port=args.listen_metrics,
                             replica_of=args.replica_of)
    except (ArtifactError, segments.SegmentError, ValueError,
            OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        daemon.start()
    except OSError as e:
        print(f"error: cannot listen on {args.listen}: {e}",
              file=sys.stderr)
        return 2

    if gc_freeze:
        # The startup heap (interpreter, imports, engine) is permanent;
        # without this, request churn — an admission-shed storm runs
        # tens of thousands of allocations a second — schedules full
        # cyclic-GC passes whose stop-the-world scan of that heap
        # lands as multi-ms spikes in OTHER tenants' tail latency.
        # Freeze it so every future pass scans only the churn.  CLI
        # path only: an embedding application owns its own collector.
        import gc
        gc.collect()
        gc.freeze()

    stop = threading.Event()

    def _on_stop_signal(signum, frame):
        if stop.is_set():
            # second signal: the drain is not fast enough for the
            # operator — documented forced exit, code 1
            # mrilint: allow(exit-code) the one sanctioned exit-1 path
            os._exit(1)
        stop.set()

    def _on_hup(signum, frame):
        # reload off the signal frame AND off the dispatcher: open +
        # verify happen on this throwaway thread, only the engine swap
        # touches the dispatch lock
        threading.Thread(target=daemon.reload, name="mri-serve-reload",
                         daemon=True).start()

    def _on_quit(signum, frame):
        # SIGQUIT = dump the flight recorder and keep serving: the
        # file write runs on a throwaway thread, off the signal frame
        threading.Thread(target=daemon.dump_flight, args=("sigquit",),
                         name="mri-serve-flight", daemon=True).start()

    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGTERM, _on_stop_signal)
        signal.signal(signal.SIGINT, _on_stop_signal)
        signal.signal(signal.SIGHUP, _on_hup)
        signal.signal(signal.SIGQUIT, _on_quit)

    bound_host, bound_port = daemon.address
    listening = {"event": "listening", "host": bound_host,
                 "port": bound_port, "pid": os.getpid(),
                 "engine": daemon._engine.engine_name}
    if daemon.metrics_address is not None:
        listening["metrics_port"] = daemon.metrics_address[1]
    print(json.dumps(listening), flush=True)
    try:
        while not stop.is_set():
            stop.wait(0.2)
        rc = daemon.drain()
    except Exception:
        # unexpected serve crash: preserve the black box before the
        # traceback takes the process down
        daemon.dump_flight("crash")
        raise
    print(json.dumps({"event": "drained",
                      "counters": daemon.final_stats["counters"]},
                     sort_keys=True), flush=True)
    return rc


def _shard_main(argv: list[str]) -> int:
    """``mri-tpu shard SRC --shards D --out DIR`` — partition a corpus
    into D buildable doc-shards with global-BM25 sidecars
    (cluster/partition.py)."""
    p = argparse.ArgumentParser(
        prog="mri-tpu shard",
        description="partition a corpus manifest into D doc-shards, "
                    "build each with the unchanged --artifact path, "
                    "and stamp global BM25 stats into per-shard "
                    "sidecars so a router over the shards answers "
                    "byte-identically to a monolithic build")
    p.add_argument("file_list", help="source corpus manifest (count "
                                     "header then one path per line)")
    p.add_argument("--shards", type=int, required=True, metavar="D",
                   help="number of doc-shards (1 <= D <= corpus size)")
    p.add_argument("--out", required=True, metavar="DIR",
                   help="cluster directory; shard s builds into "
                        "DIR/shard-s")
    p.add_argument("--mode", choices=("round-robin", "size-balanced"),
                   default="round-robin",
                   help="doc assignment: round-robin by manifest "
                        "position (default) or greedy size-balanced "
                        "over file bytes")
    p.add_argument("--mappers", type=int, default=1,
                   help="per-shard build mapper count")
    p.add_argument("--reducers", type=int, default=2,
                   help="per-shard build reducer count")
    p.add_argument("--verify", action="store_true",
                   help="after building (or against an existing DIR), "
                        "byte-verify every per-shard manifest and gid "
                        "map against the recomputed assignment")
    p.add_argument("--verify-only", action="store_true",
                   help="skip the build; just verify DIR")
    args = p.parse_args(argv)

    from .cluster import partition as part_mod
    try:
        if not args.verify_only:
            cluster = part_mod.partition(
                args.file_list, args.shards, args.out,
                mode=args.mode, mappers=args.mappers,
                reducers=args.reducers,
                progress=lambda msg: print(
                    json.dumps({"event": "progress", "detail": msg}),
                    flush=True))
            print(json.dumps({"event": "partitioned", **cluster},
                             sort_keys=True), flush=True)
        if args.verify or args.verify_only:
            summary = part_mod.verify(args.file_list, args.out)
            print(json.dumps({"event": "verified", **summary},
                             sort_keys=True), flush=True)
    except part_mod.PartitionError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


def _router_main(argv: list[str]) -> int:
    """``mri-tpu router --shards SPEC --listen HOST:PORT`` — the
    scatter-gather front-end over shard daemons (cluster/router.py).
    Blocks until drained by SIGTERM/SIGINT, mirroring 'serve'."""
    import signal
    import threading

    p = argparse.ArgumentParser(
        prog="mri-tpu router",
        description="scatter-gather router over doc-shard serve "
                    "daemons: same JSON-lines protocol as 'serve', "
                    "data ops fan out to every shard and gather "
                    "through a D-way merge; hedged requests and "
                    "replica failover ride shard health")
    p.add_argument("--shards", required=True, metavar="SPEC",
                   help="shard endpoints: shards joined by ',', "
                        "replicas of one shard joined by '|' — "
                        "'h:1|h:2,h:3' is two shards, the first with "
                        "two replicas")
    p.add_argument("--listen", default="127.0.0.1:0", metavar="HOST:PORT",
                   help="bind address (port 0 = ephemeral; the chosen "
                        "port is printed in the 'listening' JSON line)")
    p.add_argument("--hedge-ms", type=float, default=None,
                   help="hedge delay: -1 adaptive shard p95 (default, "
                        "from MRI_CLUSTER_HEDGE_MS), 0 off, >0 fixed ms")
    p.add_argument("--fault-spec", default=None,
                   help="arm the deterministic fault injector "
                        "(cluster kinds: shard-dead/shard-slow/"
                        "router-conn-reset/shard-blackout/"
                        "overload-storm) — test/bench only")
    args = p.parse_args(argv)

    from .obs import logging as obs_logging
    obs_logging.configure()

    if args.fault_spec is not None:
        try:
            faults.install(args.fault_spec)
        except faults.FaultSpecError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    from .cluster import router as router_mod
    try:
        shard_addrs = router_mod.parse_shard_arg(args.shards)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    host, _, port_s = args.listen.rpartition(":")
    try:
        port = int(port_s)
        if not host or not (0 <= port <= 65535):
            raise ValueError
    except ValueError:
        print(f"error: --listen must be HOST:PORT, got {args.listen!r}",
              file=sys.stderr)
        return 2

    try:
        router = router_mod.RouterDaemon(shard_addrs, host, port,
                                         hedge_ms=args.hedge_ms)
    except ValueError as e:
        # covers construction-time knob reads (KnobError, e.g. a bad
        # $MRI_CLUSTER_HEDGE_MS) — the one-line exit-2 contract
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        router.start()
    except OSError as e:
        print(f"error: cannot listen on {args.listen}: {e}",
              file=sys.stderr)
        return 2

    stop = threading.Event()

    def _on_stop_signal(signum, frame):
        if stop.is_set():
            # mrilint: allow(exit-code) the one sanctioned exit-1 path
            os._exit(1)
        stop.set()

    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGTERM, _on_stop_signal)
        signal.signal(signal.SIGINT, _on_stop_signal)

    bound_host, bound_port = router.address
    print(json.dumps({"event": "listening", "host": bound_host,
                      "port": bound_port, "pid": os.getpid(),
                      "shards": len(shard_addrs),
                      "replicas": [len(r) for r in shard_addrs]}),
          flush=True)
    while not stop.is_set():
        stop.wait(0.2)
    rc = router.drain()
    print(json.dumps({"event": "drained",
                      "counters": router.final_stats["counters"]},
                     sort_keys=True), flush=True)
    return rc


def _metrics_main(argv: list[str]) -> int:
    """``mri-tpu metrics TARGET`` — Prometheus text exposition.

    TARGET is either a running daemon's HOST:PORT (asks it via the
    'metrics' admin op) or an --artifact output dir / index.mri path
    (opens a throwaway engine and prints its registry)."""
    import socket

    p = argparse.ArgumentParser(
        prog="mri-tpu metrics",
        description="print Prometheus text-format metrics from a "
                    "running serve daemon (HOST:PORT) or a built "
                    "artifact (DIR)")
    p.add_argument("target", help="serve daemon HOST:PORT, or the "
                                  "output dir of an --artifact run")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="daemon connect/read timeout in seconds")
    args = p.parse_args(argv)

    host, _, port_s = args.target.rpartition(":")
    is_addr = bool(host) and port_s.isdigit() and int(port_s) <= 65535
    if is_addr and not os.path.exists(args.target):
        try:
            # mrilint: allow(fault-boundary) operator scrape RPC, not corpus I/O; OSError maps to exit 2 below
            with socket.create_connection((host, int(port_s)),
                                          timeout=args.timeout) as sock:
                sock.sendall(b'{"op": "metrics", "id": 1}\n')
                buf = b""
                while not buf.endswith(b"\n"):
                    chunk = sock.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
        except OSError as e:
            print(f"error: cannot reach daemon at {args.target}: {e}",
                  file=sys.stderr)
            return 2
        try:
            resp = json.loads(buf)
        except ValueError:
            print(f"error: bad response from {args.target}",
                  file=sys.stderr)
            return 2
        if not resp.get("ok"):
            print(f"error: daemon refused metrics: "
                  f"{resp.get('error', 'unknown')}", file=sys.stderr)
            return 2
        sys.stdout.write(resp.get("text", ""))
        return 0

    from .serve import ArtifactError, create_engine
    try:
        engine = create_engine(args.target, None)
    except ArtifactError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        sys.stdout.write(engine.metrics.render_text())
    finally:
        engine.close()
    return 0


def _flightdump_main(argv: list[str]) -> int:
    """``mri-tpu flightdump HOST:PORT`` — pull a running daemon's
    flight recorder (last N completed request cost-reports + slow
    offenders) as one JSON document, without waiting for a crash."""
    import socket

    p = argparse.ArgumentParser(
        prog="mri-tpu flightdump",
        description="dump a running serve daemon's flight recorder "
                    "(bounded ring of recent request cost-reports, "
                    "MRI_OBS_FLIGHT_RING) as one JSON document")
    p.add_argument("target", metavar="HOST:PORT",
                   help="a running serve daemon's protocol address")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="also write the dump to this file (stdout "
                        "always gets the JSON)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="daemon connect/read timeout in seconds")
    args = p.parse_args(argv)

    host, _, port_s = args.target.rpartition(":")
    if not (host and port_s.isdigit() and int(port_s) <= 65535):
        print(f"error: target must be HOST:PORT, got {args.target!r}",
              file=sys.stderr)
        return 2
    try:
        # mrilint: allow(fault-boundary) operator RPC, not corpus I/O; OSError maps to exit 2 below
        with socket.create_connection((host, int(port_s)),
                                      timeout=args.timeout) as sock:
            sock.sendall(b'{"op": "flightdump", "id": 1}\n')
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = sock.recv(65536)
                if not chunk:
                    break
                buf += chunk
    except OSError as e:
        print(f"error: cannot reach daemon at {args.target}: {e}",
              file=sys.stderr)
        return 2
    try:
        resp = json.loads(buf)
    except ValueError:
        print(f"error: bad response from {args.target}", file=sys.stderr)
        return 2
    if not resp.get("ok"):
        print(f"error: daemon refused flightdump: "
              f"{resp.get('error', 'unknown')}", file=sys.stderr)
        return 2
    text = json.dumps(resp.get("flight", {}), sort_keys=True)
    print(text)
    if args.out is not None:
        try:
            # mrilint: allow(fault-boundary) operator-chosen output file; OSError maps to exit 2 below
            with open(args.out, "w", encoding="utf-8") as f:
                f.write(text + "\n")
        except OSError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    return 0


def _top_sample(addr: tuple, timeout: float) -> dict:
    """One dashboard poll: ``healthz`` + ``stats`` + ``slo`` pipelined
    over a single daemon connection, matched back up by request id."""
    import socket

    reqs = (b'{"op":"healthz","id":1}\n'
            b'{"op":"stats","id":2}\n'
            b'{"op":"slo","id":3}\n')
    by_id: dict = {}
    # mrilint: allow(fault-boundary) operator dashboard RPC, not corpus I/O; callers map OSError to exit 2
    with socket.create_connection(addr, timeout=timeout) as sock:
        sock.sendall(reqs)
        # mrilint: allow(fault-boundary) response framing on the same operator RPC
        f = sock.makefile("rb")
        try:
            for _ in range(3):
                line = f.readline()
                if not line:
                    break
                resp = json.loads(line)
                by_id[resp.get("id")] = resp
        finally:
            f.close()
    health = dict(by_id.get(1, {}))
    health.pop("id", None)
    return {
        "healthz": health,
        "stats": by_id.get(2, {}).get("stats", {}),
        "slo": by_id.get(3, {}).get("slo", {}),
    }


def _top_num(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.3f}"
    return str(v)


def _top_render(target: str, sample: dict) -> str:
    """One plain-text dashboard frame over a poll's sample."""
    h = sample.get("healthz") or {}
    st = sample.get("stats") or {}
    slo = sample.get("slo") or {}
    ready = "ready" if h.get("ready") else "NOT READY"
    reasons = ",".join(h.get("reasons") or []) or "-"
    counters = st.get("counters") or {}
    lines = [
        f"mri top — {target} — {ready} ({h.get('status', '?')})",
        f"queue_depth={st.get('queue_depth', h.get('queue_depth', 0))}"
        f"  inflight={st.get('inflight', 0)}"
        f"  connections={st.get('connections', 0)}"
        f"  reasons={reasons}",
        "",
        f"{'window':<8}{'qps':>12}{'shed/s':>10}{'err/s':>10}"
        f"{'p50 ms':>10}{'p99 ms':>10}",
    ]
    rolling = st.get("rolling") or {}
    for label in ("10s", "1m", "5m"):
        w = rolling.get(label) or {}
        lines.append(f"{label:<8}{_top_num(w.get('qps')):>12}"
                     f"{_top_num(w.get('shed_per_s')):>10}"
                     f"{_top_num(w.get('error_per_s')):>10}"
                     f"{_top_num(w.get('p50_ms')):>10}"
                     f"{_top_num(w.get('p99_ms')):>10}")
    tenants = st.get("tenants") or {}
    if tenants:
        # per-tenant QoS slice, all from the same single stats poll:
        # admission vs shed, cache absorption, live lane depth, 1m
        # tail latency and the worst 1m SLO burn
        lines.append("")
        lines.append(f"{'tenant':<12}{'wt':>4}{'rate':>8}"
                     f"{'admitted':>10}{'shed':>8}{'hits':>8}"
                     f"{'depth':>7}{'p95 ms':>10}{'burn 1m':>9}")
        for name in sorted(tenants):
            t = tenants[name] or {}
            admitted = (t.get("requests", 0) or 0) \
                - (t.get("shed", 0) or 0)
            burns = [b for b in (t.get("burn_1m") or {}).values()
                     if isinstance(b, (int, float))]
            rate = t.get("rate_rps")
            lines.append(
                f"{name:<12}{_top_num(t.get('weight')):>4}"
                f"{('-' if rate is None else f'{rate:g}'):>8}"
                f"{admitted:>10}{_top_num(t.get('shed')):>8}"
                f"{_top_num(t.get('cache_hits')):>8}"
                f"{_top_num(t.get('queue_depth')):>7}"
                f"{_top_num(t.get('p95_ms')):>10}"
                f"{_top_num(max(burns) if burns else None):>9}")
    for name in sorted(slo):
        entry = slo[name] or {}
        head = f"slo {name} (target {entry.get('target')}"
        if entry.get("threshold_ms") is not None:
            head += f", <= {entry['threshold_ms']} ms"
        lines.append("")
        lines.append(head + ")")
        lines.append(f"  {'window':<8}{'ratio':>12}{'burn':>10}"
                     f"{'events':>10}")
        for label in ("10s", "1m", "5m"):
            pt = (entry.get("windows") or {}).get(label) or {}
            lines.append(f"  {label:<8}"
                         f"{_top_num(pt.get('ratio')):>12}"
                         f"{_top_num(pt.get('burn')):>10}"
                         f"{_top_num(pt.get('total')):>10}")
    cluster = st.get("cluster") or {}
    if cluster.get("shards"):
        # router target: one fleet row per replica, all from the same
        # single pipelined stats poll — no extra connections
        lines.append("")
        lines.append(f"{'shard':<8}{'replica':<22}{'state':<10}"
                     f"{'breaker':<11}{'p95 ms':>10}  reasons")
        answerable = 0
        for sh in cluster["shards"]:
            p95 = sh.get("p95_ms")
            reps = sh.get("replicas") or []
            if any(r.get("ready")
                   and r.get("breaker", "closed") != "open"
                   for r in reps):
                answerable += 1
            for rep in reps:
                state = "ready" if rep.get("ready") else "DOWN"
                if rep.get("primary"):
                    state += "*"
                why = ",".join(rep.get("reasons") or []) or "-"
                lines.append(
                    f"{sh.get('shard', '?'):<8}"
                    f"{rep.get('addr', '?'):<22}{state:<10}"
                    f"{rep.get('breaker', 'closed'):<11}"
                    f"{_top_num(p95):>10}  {why}")
        # degraded-serving readiness: a shard can answer (and so count
        # toward partial coverage) while any replica is ready with a
        # breaker still admitting traffic
        nshards = len(cluster["shards"])
        cov_line = (f"coverage: {answerable}/{nshards} shards "
                    f"answerable")
        if answerable < nshards:
            cov_line += "  [DEGRADED]"
        cov_line += (f"  partial_default="
                     f"{cluster.get('partial_default') or 'fail'}"
                     f"  breakers_open="
                     f"{cluster.get('breakers_open', 0)}")
        lines.append(cov_line)
    lines.append("")
    nonzero = "  ".join(f"{k}={v}" for k, v in counters.items() if v)
    lines.append("counters: " + (nonzero or "-"))
    return "\n".join(lines) + "\n"


def _top_static(args) -> int:
    """``mri-tpu top DIR`` — one static engine metrics snapshot of a
    built artifact.  Nothing rolls without a daemon, so there is no
    live refresh in this mode."""
    from .serve import ArtifactError, create_engine
    try:
        engine = create_engine(args.target, None)
    except ArtifactError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        desc = engine.describe()
        text = engine.metrics.render_text()
    finally:
        engine.close()
    if args.as_json:
        print(json.dumps({"engine": desc, "metrics_text": text},
                         sort_keys=True))
    else:
        print(f"mri top — {args.target} (static artifact snapshot)")
        print(json.dumps(desc, sort_keys=True))
        sys.stdout.write(text)
    return 0


def _top_main(argv: list[str]) -> int:
    """``mri-tpu top TARGET`` — the live operational-health dashboard.

    HOST:PORT polls a running daemon's ``stats``/``slo``/``healthz``
    admin ops and redraws every ``--interval`` seconds (Ctrl-C exits
    0); ``--once --json`` prints one machine-readable sample — the
    mode scripts and the parity test consume.  DIR prints one static
    engine snapshot."""
    import time as time_mod

    p = argparse.ArgumentParser(
        prog="mri-tpu top",
        description="live operational-health dashboard for a running "
                    "serve daemon (HOST:PORT — rolling rates, latency "
                    "quantiles, SLO burn, readiness) or one static "
                    "metrics snapshot of a built artifact (DIR)")
    p.add_argument("target", help="serve daemon HOST:PORT, or the "
                                  "output dir of an --artifact run")
    p.add_argument("--interval", type=float, default=1.0,
                   help="refresh period in seconds (live mode)")
    p.add_argument("--once", action="store_true",
                   help="print one frame and exit (no screen clear)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output (implies --once)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="daemon connect/read timeout in seconds")
    args = p.parse_args(argv)
    once = args.once or args.as_json

    host, _, port_s = args.target.rpartition(":")
    is_addr = bool(host) and port_s.isdigit() and int(port_s) <= 65535
    if not is_addr or os.path.exists(args.target):
        return _top_static(args)

    addr = (host, int(port_s))
    try:
        while True:
            try:
                sample = _top_sample(addr, args.timeout)
            except (OSError, ValueError) as e:
                print(f"error: cannot poll daemon at {args.target}: "
                      f"{e}", file=sys.stderr)
                return 2
            if args.as_json:
                print(json.dumps(sample, sort_keys=True))
            else:
                if not once:
                    sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
                sys.stdout.write(_top_render(args.target, sample))
                sys.stdout.flush()
            if once:
                return 0
            time_mod.sleep(max(0.05, args.interval))
    except KeyboardInterrupt:
        return 0


def _segments_main(cmd: str, argv: list[str]) -> int:
    """``mri-tpu append|delete|compact DIR ...`` — incremental indexing.

    Mutations are serialized under the segments lock and published by
    atomic manifest swap: readers on the old generation are never
    disturbed, and a failed mutation leaves the old manifest live."""
    p = argparse.ArgumentParser(
        prog=f"mri-tpu {cmd}",
        description={
            "append": "index new files as one immutable segment and "
                      "publish the next manifest generation",
            "delete": "tombstone global doc ids (query-invisible "
                      "immediately; space reclaimed at compaction)",
            "compact": "merge the cheapest adjacent segment run into "
                       "one replacement segment, dropping tombstones",
        }[cmd])
    p.add_argument("index_dir", help="an --artifact output dir (first "
                                     "append seeds segments/ from its "
                                     "index.mri)")
    if cmd == "append":
        p.add_argument("--add", nargs="+", required=True, metavar="FILE",
                       help="text files to index as the new segment")
    elif cmd == "delete":
        p.add_argument("--docs", nargs="+", required=True, type=int,
                       metavar="ID", help="global doc ids to tombstone")
    else:
        p.add_argument("--force", action="store_true",
                       help="compact even below the "
                            "MRI_SEGMENT_COMPACT_TRIGGER segment count")
        p.add_argument("--prune", action="store_true",
                       help="after compacting, delete retired segment "
                            "dirs no longer referenced by the manifest "
                            "(unsafe while readers hold old generations)")
    p.add_argument("--fault-spec", default=None,
                   help="inject faults (see mri-tpu --help for grammar)")
    args = p.parse_args(argv)

    if args.fault_spec is not None:
        try:
            faults.install(args.fault_spec)
        except faults.FaultSpecError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    from . import segments
    try:
        if cmd == "append":
            missing = [f for f in args.add if not os.path.exists(f)]
            if missing:
                print(f"error: input files do not exist: {missing}",
                      file=sys.stderr)
                return 2
            res = segments.append_files(args.index_dir, args.add)
        elif cmd == "delete":
            res = segments.delete_docs(args.index_dir, args.docs)
        else:
            res = segments.compact(args.index_dir, force=args.force)
        print(json.dumps(res, sort_keys=True))
        if cmd == "compact" and args.prune:
            pruned = segments.prune_retired(args.index_dir)
            print(json.dumps({"pruned": pruned}, sort_keys=True))
    except segments.SegmentError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except faults.InjectedCompactCrash as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


def _recover_main(argv: list[str]) -> int:
    """``mri-tpu recover DIR`` — roll a live index directory forward to
    the last acknowledged mutation (segments/wal.py): replay WAL
    records above the manifest's generation, quarantine torn tail
    records, sweep mutation scratch.  Idempotent; also runs implicitly
    when a primary daemon starts."""
    p = argparse.ArgumentParser(
        prog="mri-tpu recover",
        description="replay the mutation WAL after a crash: apply "
                    "acknowledged-but-unpublished records, quarantine "
                    "torn tail records, remove mutation scratch")
    p.add_argument("index_dir", help="a live (segment-managed) index "
                                     "directory")
    p.add_argument("--fault-spec", default=None,
                   help="inject faults (see mri-tpu --help for grammar)")
    args = p.parse_args(argv)
    if args.fault_spec is not None:
        try:
            faults.install(args.fault_spec)
        except faults.FaultSpecError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    from . import segments
    try:
        report = segments.recover(args.index_dir)
    except segments.SegmentError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(report, sort_keys=True))
    return 0


def _replicate_main(argv: list[str]) -> int:
    """``mri-tpu replicate DIR --from HOST:PORT`` — one catch-up round
    against a primary daemon (segments/replica.py): fetch the segment
    files this directory is missing (adler32-verified, staged, then
    atomically adopted) plus the primary's WAL tail.  Never re-indexes.
    Run it in a loop — or use ``mri-tpu serve --replica-of`` — for a
    live replica."""
    p = argparse.ArgumentParser(
        prog="mri-tpu replicate",
        description="catch a local index directory up to a primary "
                    "daemon by segment shipping (snapshot diff + "
                    "verified fetch + WAL tail adoption)")
    p.add_argument("index_dir", help="the replica's index directory "
                                     "(created if empty)")
    p.add_argument("--from", dest="source", required=True,
                   metavar="HOST:PORT",
                   help="the primary daemon's --listen address")
    p.add_argument("--timeout", type=float, default=30.0,
                   help="per-RPC socket timeout in seconds")
    p.add_argument("--fault-spec", default=None,
                   help="inject faults (see mri-tpu --help for grammar)")
    args = p.parse_args(argv)
    if args.fault_spec is not None:
        try:
            faults.install(args.fault_spec)
        except faults.FaultSpecError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    from . import segments
    try:
        addr = segments.replica.parse_addr(args.source)
        res = segments.replicate(args.index_dir, addr,
                                 timeout=args.timeout)
    except (segments.SegmentError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(res, sort_keys=True))
    return 0


def main(argv: list[str] | None = None) -> int:
    # --verify DIR / query DIR / serve DIR / metrics TARGET are
    # standalone modes (no reference positionals): pre-parse them so
    # 'mri-tpu --verify out/' and 'mri-tpu query out/ word' work
    # without dummy mapper counts.
    argv = list(sys.argv[1:] if argv is None else argv)
    device.setup()
    if argv and argv[0] == "query":
        return _query_main(argv[1:])
    if argv and argv[0] == "serve":
        return _serve_main(argv[1:])
    if argv and argv[0] == "shard":
        return _shard_main(argv[1:])
    if argv and argv[0] == "router":
        return _router_main(argv[1:])
    if argv and argv[0] == "metrics":
        return _metrics_main(argv[1:])
    if argv and argv[0] == "flightdump":
        return _flightdump_main(argv[1:])
    if argv and argv[0] == "top":
        return _top_main(argv[1:])
    if argv and argv[0] in ("append", "delete", "compact"):
        return _segments_main(argv[0], argv[1:])
    if argv and argv[0] == "recover":
        return _recover_main(argv[1:])
    if argv and argv[0] == "replicate":
        return _replicate_main(argv[1:])
    if "--verify" in argv:
        i = argv.index("--verify")
        if i + 1 >= len(argv):
            print("error: --verify needs an output directory",
                  file=sys.stderr)
            return 2
        ok, problems = verify_output_dir(argv[i + 1])
        for line in problems:
            print(f"verify: {line}", file=sys.stderr)
        if ok:
            print(f"verify: {argv[i + 1]} matches its index manifest")
        return 0 if ok else 2
    args = make_parser().parse_args(argv)
    # Satellite: validate the reference positionals up front with ONE
    # clear line on stderr — not an IndexConfig traceback, not a
    # confusing manifest parse error three layers down.
    if args.num_mappers < 1:
        print(f"error: num_mappers must be >= 1, got {args.num_mappers}",
              file=sys.stderr)
        return 2
    if args.num_reducers < 1:
        print(f"error: num_reducers must be >= 1, got {args.num_reducers}",
              file=sys.stderr)
        return 2
    if not os.path.exists(args.file_list):
        print(f"error: input list {args.file_list!r} does not exist",
              file=sys.stderr)
        return 2
    if args.fault_spec is not None:
        try:
            faults.install(args.fault_spec)
        except faults.FaultSpecError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    try:
        manifest = read_manifest(args.file_list)
        config = IndexConfig(
            num_mappers=args.num_mappers,
            num_reducers=args.num_reducers,
            backend=args.backend,
            output_dir=args.output_dir,
            pad_multiple=args.pad_multiple,
            checkpoint_path=args.checkpoint,
            profile_dir=args.profile_dir,
            collect_skew_stats=args.skew,
            stream_chunk_docs=args.stream_chunk_docs,
            pipeline_chunk_docs=args.pipeline_chunk_docs,
            overlap_tail_fraction=args.overlap_tail_fraction,
            overlap_device_windows=args.overlap_device_windows,
            overlap_window_split=args.overlap_window_split,
            device_tokenize=args.device_tokenize,
            device_tokenize_width=args.device_tokenize_width,
            device_shards=args.device_shards,
            stream_checkpoint=args.stream_checkpoint,
            stream_checkpoint_every=args.stream_checkpoint_every,
            host_threads=args.host_threads,
            emit_ownership=args.emit_ownership,
            emit_backend=args.emit_backend,
            io_prefetch=args.io_prefetch,
            resume=args.resume,
            audit=args.audit,
            artifact=args.artifact,
            trace_out=args.trace_out,
        )
        stats = build_index(manifest, config)
    except AuditError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except (OSError, ValueError, CheckpointCorrupt) as e:
        # Covers RetryPolicy.from_env too: a bad MRI_READ_* value is a
        # one-line configuration error, not a worker-thread traceback.
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.stats:
        print(json.dumps(stats, sort_keys=True))
    degradation = stats.get("degradation") or {}
    skipped = degradation.get("skipped_docs") or []
    if skipped:
        print(f"warning: completed DEGRADED — skipped {len(skipped)} "
              f"unreadable document(s) (doc ids {sorted(skipped)}); "
              f"exit {faults.EXIT_DEGRADED}", file=sys.stderr)
        return faults.EXIT_DEGRADED
    return 0


if __name__ == "__main__":
    sys.exit(main())

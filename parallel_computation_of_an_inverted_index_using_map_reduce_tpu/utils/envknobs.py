"""Single declaration point for every ``MRI_*`` environment knob.

Every knob the package and its tools read from the environment is
declared here once — name, type, default, bounds, and a help line —
and read through :func:`get`.  Invalid values raise a one-line
:class:`KnobError` (a ``ValueError``) naming the variable, so every
CLI surface maps it to exit 2 instead of surfacing a bare ``int()``
traceback three layers down a worker thread.

The ``mrilint`` env-knobs checker rejects raw ``os.environ["MRI_*"]``
reads anywhere else, and the readme-knobs checker keeps the README
table in sync with :func:`markdown_table`.  This module is
deliberately stdlib-only and free of package-relative imports so the
linter can load it standalone (no jax import) via its file path.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Iterator


class KnobError(ValueError):
    """One-line validation error naming the knob (CLI exit 2)."""


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    cast: Callable[[str], Any]
    default: Any
    help: str
    scope: str = "build"
    minimum: Any = None
    exclusive: bool = False
    choices: tuple | None = None

    def parse(self, raw: str) -> Any:
        """Cast + validate ``raw``; one-line :class:`KnobError` on bad."""
        try:
            val = self.cast(raw)
        except ValueError:
            raise KnobError(
                f"{self.name}={raw!r} is not a valid "
                f"{self.cast.__name__}") from None
        if self.choices is not None and val not in self.choices:
            raise KnobError(
                f"{self.name}={raw!r} not in {self.choices}")
        if self.minimum is not None and (
                val < self.minimum
                or (self.exclusive and val == self.minimum)):
            bound = (f"> {self.minimum}" if self.exclusive
                     else f">= {self.minimum}")
            raise KnobError(f"{self.name} must be {bound}, got {raw!r}")
        return val


_REGISTRY: dict[str, Knob] = {}


def declare(name: str, cast: Callable[[str], Any], default: Any,
            help: str, *, scope: str = "build", minimum: Any = None,
            exclusive: bool = False, choices: tuple | None = None) -> Knob:
    if name in _REGISTRY:
        raise ValueError(f"knob {name} declared twice")
    knob = Knob(name=name, cast=cast, default=default, help=help,
                scope=scope, minimum=minimum, exclusive=exclusive,
                choices=choices)
    _REGISTRY[name] = knob
    return knob


def get(name: str) -> Any:
    """The knob's parsed value from the environment, or its default.

    ``KeyError`` on an undeclared name is a programming error, caught
    by the env-knobs lint rule before it ships.
    """
    knob = _REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    return knob.parse(raw)


def is_set(name: str) -> bool:
    """Whether the (declared) knob is present in the environment."""
    _ = _REGISTRY[name]
    return name in os.environ


def knobs() -> Iterator[Knob]:
    """All declared knobs, sorted by (scope, name)."""
    return iter(sorted(_REGISTRY.values(),
                       key=lambda k: (k.scope, k.name)))


_SCOPE_TITLES = {
    "build": "Build / index pipeline",
    "faults": "Fault injection & retries",
    "serve": "Query serving",
    "obs": "Observability",
    "test": "Test hooks",
}


def markdown_table() -> str:
    """The README env-knob table (kept in sync by the lint rule)."""
    out: list[str] = []
    scope = None
    for k in knobs():
        if k.scope != scope:
            scope = k.scope
            if out:
                out.append("")
            out.append(f"**{_SCOPE_TITLES.get(scope, scope)}**")
            out.append("")
            out.append("| Knob | Type | Default | Meaning |")
            out.append("|---|---|---|---|")
        if k.default is None:
            default = "unset"
        elif k.default == "":
            default = "`\"\"`"
        else:
            default = f"`{k.default}`"
        constraint = ""
        if k.choices is not None:
            constraint = " one of " + "/".join(
                f"`{c}`" if c != "" else "`\"\"`" for c in k.choices)
        elif k.minimum is not None:
            op = ">" if k.exclusive else ">="
            constraint = f" ({op} {k.minimum})"
        out.append(f"| `{k.name}` | {k.cast.__name__}{constraint} "
                   f"| {default} | {k.help} |")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------
# Declarations.  Scopes: build (index pipeline), faults, serve, obs,
# test (crash-injection hooks the e2e suite arms in subprocesses).
# ---------------------------------------------------------------------

# -- build / index pipeline -------------------------------------------
declare("MRI_CPU_WINDOW_BYTES", int, None,
        "Bytes per host scan window (default: the backend's ~2 MB); "
        "tests force tiny values for deterministic multi-window plans.")
declare("MRI_STEAL_SHUFFLE_SEED", int, None,
        "Seeded shuffle of the steal queue's window order (unset: "
        "manifest order).")
declare("MRI_WINDOW_DEADLINE_S", float, None,
        "Per-window watchdog deadline; a wedged worker past it is "
        "abandoned and its window requeued (unset: no watchdog).")
declare("MRI_WORKER_RESPAWNS", int, 1,
        "Scan-worker respawn budget after crashes (clamped to >= 0).")
declare("MRI_TPU_PALLAS", str, "auto",
        "Fused Pallas dedup kernel: auto (TPU only), force "
        "(interpret mode off-TPU), off (XLA everywhere).")
declare("MRI_TPU_CKPT_BUDGET_S", float, 120.0,
        "Snapshot-tax budget: a projected checkpoint save slower than "
        "this is skipped (recorded, not paid).")
declare("MRI_TPU_CKPT_LINK_MBPS", float, 8.0,
        "Assumed device->host link rate seeding the checkpoint cost "
        "projection (re-calibrated from measured saves).")
declare("MRI_TPU_CKPT_STRETCH", int, 4,
        "Max consecutive over-budget checkpoint skips before one save "
        "is forced.")
declare("MRI_BUILD_SHARDS", int, 8,
        "Term-hash shard count for the out-of-core build (spill runs "
        "and the streaming merge partition by term hash, not letter).",
        minimum=1)
declare("MRI_BUILD_SPILL_BYTES", int, None,
        "Per-worker postings memory budget; when set, scan workers "
        "spill term-hash-sharded sorted runs to disk at this estimated "
        "footprint and reducers k-way-merge the runs (unset: the "
        "all-in-memory merge).",
        minimum=1)
declare("MRI_NATIVE_SANITIZE", str, "",
        "Native tokenizer build variant: \"\" (production), asan, or "
        "ubsan — sanitized builds get suffix-tagged .so names.",
        choices=("", "asan", "ubsan"))

# -- fault injection & retries ----------------------------------------
declare("MRI_FAULTS", str, None,
        "Fault-injection spec armed at first faults.active() call "
        "(subprocess arming); same grammar as --fault-spec.",
        scope="faults")
declare("MRI_READ_RETRIES", int, 3,
        "Read attempts per document (counts the first try).",
        scope="faults", minimum=1)
declare("MRI_READ_BACKOFF_MS", float, 5.0,
        "Initial retry backoff in milliseconds (doubles per retry).",
        scope="faults", minimum=0)
declare("MRI_READ_DEADLINE_S", float, 1.0,
        "Total per-document retry deadline in seconds.",
        scope="faults", minimum=0, exclusive=True)

# -- query serving ----------------------------------------------------
declare("MRI_SERVE_ENGINE", str, None,
        "Engine when 'mri query' gets no --engine flag: host, device, "
        "or auto (validated by serve.engine.resolve_engine).",
        scope="serve")
declare("MRI_SERVE_SHARDS", int, None,
        "Device-engine shard count (unset: all visible devices).",
        scope="serve")
declare("MRI_SERVE_DEVICE_DECODE_BUDGET", int, 1 << 24,
        "Max postings rows the device engine decodes per batch tier.",
        scope="serve")
declare("MRI_SERVE_COALESCE_US", int, 200,
        "Daemon micro-batch coalescing window in microseconds "
        "(0: dispatch immediately).",
        scope="serve", minimum=0)
declare("MRI_SERVE_QUEUE_DEPTH", int, 1024,
        "Daemon admission queue depth; requests past it are shed as "
        "'overloaded'.",
        scope="serve", minimum=1)
declare("MRI_SERVE_MAX_BATCH", int, 1024,
        "Max coalesced requests dispatched as one engine batch.",
        scope="serve", minimum=1)
declare("MRI_SERVE_DRAIN_S", float, 5.0,
        "Graceful-drain deadline after SIGTERM/SIGINT before inflight "
        "requests are abandoned.",
        scope="serve", minimum=0, exclusive=True)
declare("MRI_SERVE_FORMAT", int, 3,
        "Artifact format packed when no explicit version is requested: "
        "1 (plain delta postings), 2 (block-bitpacked + skip table) or "
        "3 (v2.1: adds the per-block max-score columns).",
        scope="serve", choices=(1, 2, 3))
declare("MRI_SERVE_BLOCK_SIZE", int, 128,
        "Format-v2 postings block size in doc ids (power of two).",
        scope="serve", minimum=2)
declare("MRI_SERVE_SCORE_BITS", int, 8,
        "v2.1 max-score column width in bits: 8 (saturating u8 max-tf "
        "/ min-doclen) or 16.",
        scope="serve", choices=(8, 16))
declare("MRI_SERVE_SCORE", str, "df",
        "Default top_k scoring mode when no --score flag is given: "
        "df (document frequency) or bm25 (ranked retrieval).",
        scope="serve", choices=("df", "bm25"))
declare("MRI_SERVE_PLANNER", str, "auto",
        "Ranked-query planner: auto (df/k heuristic), exhaustive "
        "(score every posting), bmw (Block-Max WAND) or maxscore.",
        scope="serve", choices=("auto", "exhaustive", "bmw", "maxscore"))
declare("MRI_SERVE_NATIVE", str, "auto",
        "Native (C++) serve kernels for v2 decode/AND/BM25: auto "
        "(on when the compiled library loads), 1 (require native — "
        "engine creation fails loudly if the .so is unavailable) or "
        "0 (numpy only).  Answers are byte-identical either way.",
        scope="serve", choices=("auto", "0", "1"))
declare("MRI_SERVE_CROSSOVER", int, None,
        "--engine auto host->device batch-size crossover: unset probes "
        "it by measurement, 0 pins host, N>0 routes batches >= N to "
        "the device engine.",
        scope="serve")
declare("MRI_SEGMENT_COMPACT_TRIGGER", int, 4,
        "Segment count at which compaction kicks in; also the width "
        "of the adjacent merge window each round folds.",
        scope="serve", minimum=2)
declare("MRI_SEGMENT_MAX_SEGMENTS", int, 16,
        "Hard segment-count backstop: the daemon auto-compacts after "
        "an append while the live set exceeds it.",
        scope="serve", minimum=1)
declare("MRI_SEGMENT_TOMBSTONE_FLUSH", int, 1,
        "Daemon delete batching: buffer delete ops and publish ONE "
        "tombstone generation every N ops (N=1 publishes immediately; "
        "a compact or drain flushes the remainder; CLI deletes always "
        "publish).",
        scope="serve", minimum=1)
declare("MRI_SEGMENT_WAL", int, 1,
        "Mutation write-ahead log: 1 fsyncs a checksummed WAL record "
        "before every segment mutation publish (crash replay via 'mri "
        "recover' / daemon start), 0 disables logging (replay of an "
        "existing log still runs).",
        scope="serve", choices=(0, 1))
declare("MRI_SEGMENT_LEASE_TTL_S", float, 0.0,
        "Primary-election lease TTL in seconds: mutations renew a "
        "TTL'd lease inside segments.lock and are rejected with "
        "'lease_lost' once another holder owns it; 0 disables "
        "leasing (single-writer deployments).",
        scope="serve", minimum=0)
declare("MRI_REPLICA_POLL_MS", int, 500,
        "Replica catch-up poll period in ms for 'mri serve "
        "--replica-of' (each poll ships missing segments + WAL tail "
        "from the primary).",
        scope="serve", minimum=1)
declare("MRI_CLUSTER_HEDGE_MS", float, -1.0,
        "Router hedging delay in ms: a shard RPC unanswered this long "
        "is re-sent to another replica of the same shard. -1 adapts "
        "per shard (rolling p95 of recent RPC latency, 1 ms floor), "
        "0 disables hedging, positive values are a fixed delay.",
        scope="serve", minimum=-1.0)
declare("MRI_CLUSTER_HEALTH_MS", int, 500,
        "Router health-probe period in ms: each replica's `healthz` "
        "is polled on its pipelined connection and the readiness "
        "reasons (draining/stalled/overloaded/replica_lagging) steer "
        "replica selection away before requests fail.",
        scope="serve", minimum=1)
declare("MRI_CLUSTER_INFLIGHT", int, 1024,
        "Router admission cap: client requests in flight (scattered "
        "but not yet gathered) beyond this are shed with "
        "`overloaded`, mirroring the daemon's bounded queue.",
        scope="serve", minimum=1)
declare("MRI_CLUSTER_RPC_TIMEOUT_MS", float, 30000.0,
        "Router-side ceiling in ms on one shard RPC (including "
        "failover retries) when the client request carries no "
        "deadline_ms of its own.",
        scope="serve", minimum=1.0)
declare("MRI_CLUSTER_PARTIAL", str, "fail",
        "Router default partial-result policy for requests carrying "
        "no partial_policy field: 'fail' (any unanswerable shard "
        "fails the whole request — byte-compat default) or "
        "'allow[:min_coverage=F]' (answer from the shards that did "
        "answer, flagged with partial+coverage metadata, provided at "
        "least fraction F of the corpus answered; F defaults to 0).",
        scope="serve")
declare("MRI_CLUSTER_RETRY_BUDGET", float, 0.1,
        "Router retry/hedge token budget per shard, as a ratio of "
        "live (first-attempt) traffic: each original shard leg "
        "deposits this many tokens and every retry or hedge spends "
        "one, so brownout amplification is capped near (1 + ratio)x "
        "instead of compounding; 0 disables retries and hedges "
        "(first attempt only).",
        scope="serve", minimum=0.0)
declare("MRI_SERVE_CODEL_TARGET_MS", float, 0.0,
        "CoDel-style adaptive admission target in ms: once the "
        "dispatcher's observed queue delay stays above this for a "
        "full MRI_SERVE_CODEL_INTERVAL_MS, the daemon sheds "
        "('overloaded') early at admission and late at dequeue until "
        "delay drops back under target, keeping executed requests' "
        "queueing near the target under sustained overload; 0 "
        "disables adaptive admission (fixed queue-depth shedding "
        "only).",
        scope="serve", minimum=0.0)
declare("MRI_SERVE_CODEL_INTERVAL_MS", float, 100.0,
        "CoDel sliding interval in ms: queue delay must exceed the "
        "target this long before shedding starts, and it is the base "
        "period of the control law that paces admission sheds while "
        "the daemon stays over target.",
        scope="serve", minimum=1.0)
declare("MRI_SERVE_RESULT_CACHE", int, 1,
        "Generation-keyed query-result cache: 1 answers repeat "
        "queries from the reader thread (daemon) / above the "
        "scatter-gather (router) without touching the engine, keyed "
        "on (op, normalized terms, k, score, manifest generation) so "
        "a mutation's generation bump invalidates exactly; 0 "
        "disables the cache (every request reaches the engine).",
        scope="serve", choices=(0, 1))
declare("MRI_SERVE_RESULT_CACHE_ENTRIES", int, 4096,
        "Entry-count bound on the result cache (LRU beyond it).",
        scope="serve", minimum=1)
declare("MRI_SERVE_RESULT_CACHE_BYTES", int, 8 << 20,
        "Byte bound on the result cache: cached payloads are sized "
        "by their JSON encoding and evicted LRU-first once the sum "
        "exceeds this; 0 removes the byte bound (entry count only).",
        scope="serve", minimum=0)
declare("MRI_SERVE_TENANT_WEIGHTS", str, "",
        "Weighted-fair dequeue shares per tenant as "
        "'name=w,name=w,*=w' (integer weights; '*' sets the default "
        "for unlisted tenants, 1 if absent). Empty string gives every "
        "tenant weight 1 (pure round-robin between active tenants).",
        scope="serve")
declare("MRI_SERVE_TENANT_RATE", str, "",
        "Per-tenant token-bucket admission as "
        "'name=rps[:burst],*=rps[:burst]' (floats; burst defaults to "
        "one second of rps). Requests over a tenant's bucket are shed "
        "with `overloaded` before queueing; empty string disables "
        "rate limiting (weighted-fair dequeue still applies).",
        scope="serve")
declare("MRI_SERVE_TENANT_MAX", int, 32,
        "Cap on distinct tracked tenants: past it, new tenant names "
        "fold into the shared 'other' lane (bounds per-tenant metric "
        "and queue memory against tenant-id cardinality attacks).",
        scope="serve", minimum=1)
declare("MRI_SERVE_GC_FREEZE", int, 1,
        "Daemon-process GC taming (the `mri serve` CLI only, never "
        "in-process embedding): after the engine is loaded, collect "
        "once and gc.freeze() the warm startup heap so cyclic-GC "
        "passes scan only request churn — an admission-shed storm "
        "allocates fast enough to schedule full collections, and a "
        "full pass over the interpreter+engine heap is a multi-ms "
        "stop-the-world spike in someone else's tail latency. 0 "
        "leaves the collector untouched.",
        scope="serve", choices=(0, 1))
declare("MRI_SERVE_TENANT_QUEUE_DEPTH", int, 0,
        "Per-tenant dispatch-queue depth; a tenant whose lane is full "
        "sheds with `overloaded` without displacing other tenants. 0 "
        "inherits MRI_SERVE_QUEUE_DEPTH.",
        scope="serve", minimum=0)

# -- observability ----------------------------------------------------
declare("MRI_OBS_ENABLE", int, 1,
        "Per-request tracing on the daemon: 1 auto-generates trace ids "
        "and records spans into the trace ring, 0 disables recording "
        "(client-provided trace ids are still echoed).",
        scope="obs", choices=(0, 1))
declare("MRI_OBS_TRACE_RING", int, 256,
        "Capacity of the daemon's ring of recent request traces "
        "(served by the `trace` admin op).",
        scope="obs", minimum=1)
declare("MRI_OBS_SLOW_MS", float, 0.0,
        "Slow-query threshold in ms: requests at least this slow emit "
        "one structured JSON line on the mri_tpu.obs logger; 0 "
        "disables the slow log.",
        scope="obs", minimum=0)
declare("MRI_OBS_FLIGHT_RING", int, 64,
        "Capacity of the daemon's flight recorder (last N completed "
        "request cost-reports + slow offenders, dumped as one JSON "
        "file on SIGQUIT, crash, abnormal drain, or the `flightdump` "
        "admin op); 0 disables the recorder.",
        scope="obs", minimum=0)
declare("MRI_OBS_EXEMPLARS", int, 1,
        "OpenMetrics exemplars on the daemon's latency histograms: 1 "
        "attaches the trace_id of a recent bucket-representative "
        "request to each bucket line in the scrape text, 0 omits them.",
        scope="obs", choices=(0, 1))
declare("MRI_OBS_SAMPLE_MS", int, 1000,
        "Rolling-window sampler period in ms: how often the daemon "
        "snapshot-diffs the cumulative registry into per-period "
        "buckets (the 10s/1m/5m SLI windows are built from them).",
        scope="obs", minimum=10)
declare("MRI_OBS_SLO_LATENCY_MS", float, 50.0,
        "Latency SLO threshold in ms: the latency SLI is the fraction "
        "of data requests answered at least this fast.",
        scope="obs", minimum=0.001)
declare("MRI_OBS_SLO_TARGET", float, 0.999,
        "SLO objective (good-event fraction) shared by the "
        "availability and latency SLOs; burn rate over a window is "
        "error-rate / (1 - target).",
        scope="obs", minimum=0.0)
declare("MRI_OBS_STALL_MS", float, 5000.0,
        "Watchdog stall threshold in ms: a monitored daemon thread "
        "(dispatcher, accept) whose heartbeat ages past this is "
        "declared stalled — counted, logged, flight-dumped, and "
        "surfaced as `healthz` readiness `stalled`; 0 disables the "
        "watchdog.",
        scope="obs", minimum=0)
declare("MRI_OBS_OVERLOAD_SHED_RATE", float, 0.5,
        "healthz readiness threshold: the daemon reports `overloaded` "
        "while the shed fraction (sheds / admission attempts) over "
        "the rolling 10s window exceeds this.",
        scope="obs", minimum=0.0)
declare("MRI_OBS_LOG_FORMAT", str, "text",
        "Runtime log rendering for mri_tpu.* loggers once "
        "obs.logging.configure() has run (the serve daemon does): "
        "text keeps classic `LEVEL logger: message` lines, json emits "
        "one structured JSON object per line.",
        scope="obs", choices=("text", "json"))
declare("MRI_OBS_LOG_RATE_LIMIT", int, 200,
        "Per-(logger, event) structured-log rate limit in records/s; "
        "excess records are dropped and counted in "
        "mri_obs_log_dropped_total. 0 disables the limiter.",
        scope="obs", minimum=0)

# -- test hooks -------------------------------------------------------
declare("MRI_EMIT_KILL_AFTER_LETTERS", int, None,
        "Crash hook: SIGKILL the process after N complete letter "
        "files (kill-mid-emit durability test).", scope="test")
declare("MRI_SPILL_KILL_AFTER", int, None,
        "Crash hook: SIGKILL the process after N complete spill run "
        "files (kill-at-spill-boundary resume test).", scope="test")
declare("MRI_TPU_STREAM_CRASH_AFTER_WINDOWS", int, 0,
        "Crash hook: die at a deterministic device-stream position "
        "(0: disabled).", scope="test")

"""Op/phase timers over the obs histogram, with spans on the profiler's clock.

``OpTimer`` (per-op call count + total seconds: the serve engines' ops,
the device engine's steps, the daemon's dispatcher spans) and
``PhaseTimer`` (the build pipeline's phases) record through
:class:`~.metrics.Histogram`, so every timed op/phase gets a latency
distribution (exact quantiles under the sample cap), while the
``stats()`` / ``report()`` dict shapes stay as they were.

Every span also opens a ``jax.profiler.TraceAnnotation`` under a stable
dotted name, so a JAX profiler trace shows the program's own work on the
same clock as the device's.  The annotation opens only where JAX is
already imported: this module never imports JAX, so a process that never
uses it (the router, the host engine, a load generator) stays free of
it.  With no profiler session an annotation costs well under a
microsecond.  The names:

- ``build.<phase>``: a build phase (``PhaseTimer.phase``), e.g.
  ``build.emit``; ``build.pack`` is the ``index.mri`` pack inside it;
- ``serve.op.<op>``: one engine op (``df``, ``postings``, ``and``,
  ``or``, ``top_k``, ``top_k_scored``);
- ``serve.step.device``: one jitted call of the device engine, from
  dispatch through the host fetch of its result; ``serve.step.rescore``:
  the engine's BM25 host work (block bounds, theta, float64 rescoring);
- ``serve.batch``: one batch the daemon's dispatcher executes, engine
  lock wait included; ``serve.reply``: one engine-answered reply, from
  the result in hand to the line queued for the writer.
"""

from __future__ import annotations

import json
import sys
import time
from contextlib import contextmanager, nullcontext

from . import metrics


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` where JAX is
    already imported, else a context that does nothing."""
    ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    return nullcontext() if ann is None else ann(name)


class OpTimer:
    """Per-op latency accounting: the serve engines' ops, the device
    engine's steps and the daemon's dispatcher spans.

    ``stats()`` keeps the historical shape (``calls`` / ``total_ms`` /
    ``avg_us`` per op, sorted by op name); when constructed with a
    :class:`~.metrics.Registry`, each op's histogram is registered as
    ``<prefix>_<op>_seconds`` and shows up in the Prometheus text.
    ``time(op)`` is also the profiler span ``<span>.<op>``.
    """

    def __init__(self, registry: metrics.Registry | None = None,
                 prefix: str = "mri_engine_op", span: str = "serve.op"):
        self._registry = registry if registry is not None \
            else metrics.Registry()
        self._prefix = prefix
        self._span = span
        self._hists: dict[str, metrics.Histogram] = {}

    def _hist(self, op: str) -> metrics.Histogram:
        h = self._hists.get(op)
        if h is None:
            h = self._registry.histogram(f"{self._prefix}_{op}_seconds")
            self._hists[op] = h
        return h

    @contextmanager
    def time(self, op: str):
        with _annotation(f"{self._span}.{op}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self._hist(op).observe(time.perf_counter() - t0)

    def histogram(self, op: str) -> metrics.Histogram:
        """The op's latency histogram, for callers that inline their
        timing — a hot path observes directly instead of paying the
        context-manager machinery per call."""
        return self._hist(op)

    def stats(self) -> dict:
        out = {}
        for op in sorted(self._hists):
            h = self._hists[op]
            calls, secs = h.count, h.sum
            if not calls:
                continue
            out[op] = {
                "calls": calls,
                "total_ms": round(secs * 1e3, 3),
                "avg_us": round(secs / calls * 1e6, 2),
            }
        return out

    def quantile_ms(self, op: str, p: float) -> float:
        """p-th percentile of one op's latency in ms (nan if unseen)."""
        h = self._hists.get(op)
        return h.quantile(p) * 1e3 if h is not None else float("nan")

    def reset(self) -> None:
        for h in self._hists.values():
            h.reset()
        self._hists.clear()


class PhaseTimer:
    """Wall-clock phase accounting for one build run.

    ``self.phases`` stays a plain mutable dict (callers assign into it
    for abort bookkeeping); each ``phase()`` observation additionally
    lands in a histogram so repeated phases expose a distribution, and
    is the profiler span ``build.<name>``.
    """

    def __init__(self):
        self.phases: dict[str, float] = {}
        self.counters: dict = {}
        self._hists: dict[str, metrics.Histogram] = {}

    @contextmanager
    def phase(self, name: str):
        with _annotation(f"build.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self.phases[name] = self.phases.get(name, 0.0) + dt
                h = self._hists.get(name)
                if h is None:
                    h = metrics.Histogram(f"mri_build_phase_{name}_seconds")
                    self._hists[name] = h
                h.observe(dt)

    def count(self, name: str, value) -> None:
        """Record a scalar alongside the timings (sets, not adds)."""
        self.counters[name] = value

    def histogram(self, name: str) -> metrics.Histogram | None:
        return self._hists.get(name)

    @property
    def total_seconds(self) -> float:
        return sum(self.phases.values())

    def report(self) -> dict:
        out = {
            "phases_ms": {k: round(v * 1e3, 3)
                          for k, v in self.phases.items()},
            "total_ms": round(self.total_seconds * 1e3, 3),
        }
        out.update(self.counters)
        return out

    def dumps(self) -> str:
        return json.dumps(self.report(), sort_keys=True)

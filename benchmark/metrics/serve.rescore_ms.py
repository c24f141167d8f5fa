"""Mean BM25 host rescoring per ranked engine call over the window: the
change in the device engine's ``serve.step.rescore`` span total (block
bounds and theta of the pruned plan, float64 rescoring of the device's
candidates, memo misses included) over the change in
``stats.engine.ops.top_k_scored.calls``."""

from benchmark.snapshot import delta


def read(run):
    ms = delta(run, "engine", "steps", "rescore", "total_ms")
    calls = delta(run, "engine", "ops", "top_k_scored", "calls")
    return ms / calls if ms is not None and calls else None

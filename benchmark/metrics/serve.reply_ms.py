"""Mean reply encoding per engine-answered request over the window: the
daemon's ``serve.reply`` span (``stats.steps.reply``: on the dispatcher,
from the engine's result in hand to the line queued for the writer),
change in total over change in count."""

from benchmark.snapshot import delta


def read(run):
    ms = delta(run, "steps", "reply", "total_ms")
    calls = delta(run, "steps", "reply", "calls")
    return ms / calls if ms is not None and calls else None

"""Share of the window the daemon's single dispatcher spent executing
batches: the change in its ``serve.batch`` span total (``stats.steps``,
engine-lock wait included) over the change in ``stats.uptime_s``."""

from benchmark.snapshot import delta


def read(run):
    ms = delta(run, "steps", "batch", "total_ms")
    up = delta(run, "uptime_s")
    return ms / (up * 1e3) * 100.0 if ms is not None and up else None

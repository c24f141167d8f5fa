"""Mean device wait per engine call over the window: the change in the
device engine's ``serve.step.device`` span total (``stats.engine.steps``:
every jitted call from dispatch through the host fetch of its result)
over the change in engine calls, summed over ``stats.engine.ops`` as
``serve.engine_ms`` sums them."""

from benchmark.snapshot import delta, stat


def read(run):
    ms = delta(run, "engine", "steps", "device", "total_ms")
    ops = stat(run.data.get("after"), "engine", "ops") or {}
    calls = sum(delta(run, "engine", "ops", op, "calls") or 0
                for op in ops)
    return ms / calls if ms is not None and calls else None

"""Window changes in the serve daemon's ``stats()``: what per-layer
readers take from a serve run's ``before`` and ``after`` snapshots
(``drivers/serve.py``).  A number the program does not report reads as
None, so a reader of a span or counter that a program lacks returns
None instead of raising."""


def stat(snap: dict | None, *path: str):
    """The value at ``path`` in a snapshot's ``stats``, or None."""
    v = snap.get("stats") if snap else None
    for key in path:
        if not isinstance(v, dict) or key not in v:
            return None
        v = v[key]
    return v


def delta(run, *path: str):
    """``after`` minus ``before`` of the number at ``path``; None when
    ``after`` lacks it (a span or counter first seen inside the window
    counts from 0)."""
    after = stat(run.data.get("after"), *path)
    if after is None or not run.data.get("before"):
        return None
    return after - (stat(run.data["before"], *path) or 0)

"""The ``index.mri`` pack per build, inside the ``emit`` phase: the
report's ``artifact_build_ms``, timed by the ``build.pack`` span."""


def read(run):
    packs = [r["artifact_build_ms"] for r in run.data.get("reports") or ()
             if "artifact_build_ms" in r]
    return sum(packs) / len(packs) if packs else None

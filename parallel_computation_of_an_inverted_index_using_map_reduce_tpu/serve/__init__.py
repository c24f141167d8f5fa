"""Read side of the index: the ``index.mri`` serving artifact.

The build engines (models/, native/) end at 26 letter files — a
write-only artifact.  This package is the query path: a compact,
memory-mappable columnar artifact packed at emit time
(:mod:`~.artifact`), two byte-identical vectorized query engines over
it — host numpy over mmap views (:mod:`~.engine`) and device-resident
jit/shard_map (:mod:`~.device_engine`, selected via
:func:`create_engine`) — and the LRU hot-term cache the host engine
decodes postings through (:mod:`~.cache`).  ``mri-tpu query`` (cli.py)
sits on top, and :mod:`~.daemon` keeps one
engine resident behind a JSON-lines protocol (``mri-tpu serve``) with
micro-batch coalescing, admission control, deadlines, graceful drain,
and crash-safe hot reload.
"""

from .artifact import ARTIFACT_NAME, ArtifactError, load_artifact
from .daemon import ServeDaemon
from .engine import (ENGINE_CHOICES, AutoEngine, Engine,
                     create_engine, resolve_engine)

__all__ = ["ARTIFACT_NAME", "ArtifactError", "ENGINE_CHOICES",
           "AutoEngine", "Engine",
           "ServeDaemon", "create_engine", "load_artifact",
           "resolve_engine"]

"""Process-level JAX setup shared by every entry point that runs on a device.

:func:`setup` places JAX's persistent compilation cache and starts
counting backend compile seconds; ``cli.main``, ``bench.py``'s child and
``tools/measure_tpu.py`` call it before their first compile.  The cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says when that is set (JAX reads
the variable itself, and nothing else is set here); otherwise it goes to
``<checkout>/.jax_cache``, a fixed path, because the cache key includes
the directory.

:func:`identity` is the device block every build report and benchmark
line carries: the platform, kind and count as JAX reports them, so a
number is never read as a chip number when it came from the CPU.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = 0.0
_configured = False


def cache_dir() -> str:
    """Where this process's compile cache lives."""
    return os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    global _compile_s
    if event == _COMPILE_EVENT:
        _compile_s += duration_secs


def setup() -> None:
    """Place the compile cache and count compile time (idempotent)."""
    global _configured
    if _configured:
        return
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _configured = True


def compile_seconds() -> float:
    """Backend compile seconds in this process since :func:`setup`."""
    return _compile_s


def identity() -> dict:
    """``platform``, ``device_kind`` and ``device_count`` as JAX reports
    them, plus ``compile_s`` so far."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "compile_s": round(_compile_s, 3),
    }

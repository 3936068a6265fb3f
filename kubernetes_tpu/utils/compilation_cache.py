"""Persistent JAX compilation cache: one directory, placed from outside.

Every process bring-up pays an XLA compile storm (wave kernel variants,
scatter/gather programs, the serial batch kernel); the persistent cache
amortizes it across processes. It was once deliberately OFF: a donating
scatter deserialized from the cache was observed corrupting rows it was
never asked to touch when its donation aliased buffers a concurrent
reader observed (the PR-4 `_scatter_rows_safe` incident). The
generational snapshot removed that aliasing structurally — donation only
ever consumes lease-private, unpinned buffers — so every entry that
compiles (cmd/scheduler.py, bench.py) enables the cache through the one
function below.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` if the environment
sets it — JAX reads the variable itself, so nothing is set in code —
and otherwise ``<checkout>/.jax_cache``, resolved from this package's own
location. The path is part of what makes a cache findable by the next
process, so it is never derived from a temporary name, a pid or the time.

Each executable JAX asks the backend for is counted in this process's
metrics registry as ``jax_backend_compiles_total{program,
persistent_cache=hit|miss}``, so a warm start can be told from a cold one
(and a mid-run recompile from steady state) by reading /metrics.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

from .metrics import metrics

logger = logging.getLogger("kubernetes_tpu.utils.compilation_cache")

DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
COUNTER_BACKEND_COMPILES = "jax_backend_compiles_total"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_tls = threading.local()
_counting = False


def cache_dir() -> str:
    """The directory the persistent cache uses in this process."""
    return os.environ.get(DIR_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def _on_event(event: str, **_kw) -> None:
    if event == _HIT_EVENT:
        _tls.hit = True


def _on_duration(event: str, _secs: float, **kw) -> None:
    # JAX wraps compile_or_get_cached (hit or real compile) in this one
    # duration event and fires the hit event inside it on the same
    # thread, so the flag attributes the hit to the program by name
    if event != _COMPILE_EVENT:
        return
    hit = getattr(_tls, "hit", False)
    _tls.hit = False
    metrics.inc(
        COUNTER_BACKEND_COMPILES,
        {
            "program": str(kw.get("fun_name", "")),
            "persistent_cache": "hit" if hit else "miss",
        },
    )


def enable_persistent_compilation_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory. Call before the first jit dispatch; idempotent."""
    global _counting
    import jax

    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache even quick compiles: the wave path's scatter/gather programs
    # are individually fast to compile but numerous, and a cold start
    # pays all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _counting:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _counting = True
    logger.info("persistent JAX compilation cache: %s", path)
    return path

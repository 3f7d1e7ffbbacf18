"""JAX's persistent compilation cache for the repository's entry points.

``enable()`` is called by ``chip_smoke.py``, ``launch/serve.py`` and
``benchmarks/serve_bench.py`` at start-up, never at import time:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
  other directory is set;
* otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path
  (git ignores it), so a second run in the same checkout finds what the
  first one compiled.

``stats()`` counts persistent-cache hits and misses and the seconds spent
in backend compilation (cache retrievals included) since ``enable()``.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE = "/jax/core/compile/backend_compile_duration"

_stats = {"hits": 0, "misses": 0, "compile_s": 0.0}
_listening = False


def _on_event(event: str, **_):
    if event == _HIT:
        _stats["hits"] += 1
    elif event == _MISS:
        _stats["misses"] += 1


def _on_duration(event: str, secs: float, **_):
    if event == _COMPILE:
        _stats["compile_s"] += secs


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    global _listening
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # every executable counts: the serving segments are many and short
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return path


def stats() -> dict:
    """Cache hits, misses and backend compile seconds so far."""
    return dict(_stats)

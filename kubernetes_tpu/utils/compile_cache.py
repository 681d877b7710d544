"""Placement of JAX's persistent compilation cache.

Warm-up compiles dozens of solver signatures (cold / refresh / steady
layouts x pad rungs x family combos), and a fresh process pays them all
again. Every entry point calls ``configure_compile_cache()`` first:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  the cache lives there; no directory is set in code.
- unset: the cache lives at ``<checkout>/.jax_cache`` (git-ignored). The
  path is FIXED -- never a tempdir, pid or timestamp -- because the
  directory is part of the cache key: a cache that moves never hits.

The one-second minimum compile time JAX applies before persisting an
entry is lowered to zero: most solver signatures compile in well under a
second each and there are many of them.
"""

from __future__ import annotations

import os
import threading

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _CacheCounter:
    """Process-wide persistent-cache request/hit counts, fed by
    jax.monitoring (JAX offers no un-register, so the listener installs
    once per process)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._installed = False
        self.requests = 0
        self.hits = 0

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == _REQUEST_EVENT:
            with self._lock:
                self.requests += 1
        elif event == _HIT_EVENT:
            with self._lock:
                self.hits += 1


_counter = _CacheCounter()


def configure_compile_cache() -> str:
    """Point the persistent compile cache at its directory (see module
    docstring) and start counting cache requests/hits. Call before the
    first compile; idempotent. Returns the directory in use."""
    import jax

    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _counter.install()
    return cache_dir


def compile_cache_stats() -> dict:
    """``{"requests", "hits", "misses"}`` of the persistent cache since
    ``configure_compile_cache()``: a compile that found its executable
    on disk is a hit."""
    requests, hits = _counter.requests, _counter.hits
    return {"requests": requests, "hits": hits, "misses": requests - hits}

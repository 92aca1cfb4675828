"""The one place that turns on JAX's persistent compilation cache.

A cold process on the chip compiles every program it runs (the dense16
device loop alone takes ~10 s), so every entry point that compiles for
the device — ``chip_smoke.py``, ``bench.py``, the learner CLIs, launched
workers — calls :func:`enable` before its first compile.  The rule
(doc/parameters.md "Compilation cache"):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  sets no other directory.
* unset: one fixed, git-ignored directory at the root of the checkout.
  The path is part of the cache key, so it is never derived from a
  temporary name, a pid or a clock — a directory that moves never hits.

All processes of a run share the directory (JAX writes entries
atomically).
"""
from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileClock:
    """Seconds this process spent getting executables, split by phase.

    ``take()`` returns what accumulated since the previous call:
    ``seconds`` is the backend's compile time when the cache missed
    (cold) and the cache read time when it hit (warm); ``misses`` /
    ``hits`` count the programs of each kind.  Fed by ``jax.monitoring``
    — the numbers are JAX's own, not a wall-clock difference that would
    fold execution in."""

    def __init__(self) -> None:
        import jax.monitoring

        self._seconds = 0.0
        self._requests = 0
        self._hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, seconds: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self._seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == _REQUEST_EVENT:
            self._requests += 1
        elif event == _HIT_EVENT:
            self._hits += 1

    def take(self) -> dict:
        out = {"seconds": round(self._seconds, 3),
               "misses": self._requests - self._hits, "hits": self._hits}
        self._seconds, self._requests, self._hits = 0.0, 0, 0
        return out

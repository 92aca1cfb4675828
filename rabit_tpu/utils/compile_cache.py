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
import sys

from rabit_tpu.obs import program

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


class _CompileClock:
    """Seconds this process spent getting executables, split by phase.

    Feeds the program counters ``compile.seconds`` / ``compile.misses``
    / ``compile.hits`` (:mod:`rabit_tpu.obs.program`; a process's
    ``Engine.path_stats`` carries them): ``seconds`` is the backend's
    compile time when the cache missed (cold) and the cache read time
    when it hit (warm); ``misses`` / ``hits`` count the programs of
    each kind.  From ``jax.monitoring`` — the numbers are JAX's own,
    not a wall-clock difference that would fold execution in.

    ``take()`` returns what those counters gained since the previous
    call.  One clock a process: get it from :func:`count_compiles`."""

    _NAMES = ("seconds", "misses", "hits")

    def __init__(self) -> None:
        import jax.monitoring

        self._taken = self._read()
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    @staticmethod
    def _on_secs(event: str, seconds: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            program.count("compile.seconds", seconds)

    @staticmethod
    def _on_event(event: str, **_kw) -> None:
        # a request is a miss until its hit is reported
        if event == _REQUEST_EVENT:
            program.count("compile.misses", 1)
        elif event == _HIT_EVENT:
            program.count("compile.misses", -1)
            program.count("compile.hits", 1)

    def _read(self) -> list:
        stats = program.stats()
        return [stats.get("compile." + name, 0) for name in self._NAMES]

    def take(self) -> dict:
        now = self._read()
        seconds, misses, hits = (b - a for a, b in zip(self._taken, now))
        self._taken = now
        return {"seconds": round(seconds, 3), "misses": misses,
                "hits": hits}


_CLOCK: _CompileClock | None = None


def count_compiles() -> _CompileClock | None:
    """This process's one compile clock, registered on the first call
    made once JAX is imported (``rabit_tpu.init`` calls; JAX is not
    imported for the clock's sake: a process without it compiles
    nothing).  None until then."""
    global _CLOCK
    if _CLOCK is None and "jax" in sys.modules:
        _CLOCK = _CompileClock()
    return _CLOCK

"""Program spans and counters: the device-plane path timed where the
work happens.

``with program.span("commit.barrier"):`` around a layer boundary does
three things (doc/observability.md "Program spans"):

1. **accumulates** ``n`` / ``total_s`` / ``max_s`` under the span's name
   in one process-wide table, counters (:func:`count`) beside them —
   always on; :func:`stats` flattens the table into what an engine
   reports as ``Engine.path_stats``;
2. **opens a** ``jax.profiler.TraceAnnotation("rabit:" + name)`` while a
   profiler session is recording, so the span sits in the
   ``.xplane.pb`` on the device trace's clock with nothing to switch
   on.  JAX is never imported for the span's sake: a process that has
   not imported it has no profiler session either;
3. **when telemetry is on** (``rabit_obs``; :func:`attach`) also emits
   one ``span`` event into the engine's :class:`~rabit_tpu.obs.EventTrace`
   (``kind`` = the span's name, ``parent``, ``version``, the caller's
   fields) and observes ``span.<name>.seconds`` in its
   :class:`~rabit_tpu.obs.Metrics`.

The version number is the identifier the spans of one unit of work
share: the caller that has it at hand passes ``version=`` and the spans
nested inside inherit it.  Nesting is per thread.
"""
from __future__ import annotations

import sys
import threading
import time

PREFIX = "rabit:"

_perf = time.perf_counter
_spans: dict[str, list] = {}         # name -> [n, total_s, max_s]
_counters: dict[str, float] = {}
_annotation = None                   # jax.profiler.TraceAnnotation, once seen
_sink = None                         # (Metrics, EventTrace, engine) or None
_open = threading.local()            # .stack: [(name, version)] of the
                                     # spans entered while _sink was set


def _find_annotation():
    """The profiler's annotation type, once this process has JAX."""
    global _annotation
    jax = sys.modules.get("jax")
    _annotation = getattr(getattr(jax, "profiler", None),
                          "TraceAnnotation", None)
    return _annotation


class span:
    """One timed section.  ``seconds`` holds its duration once closed;
    an exception inside it still closes it.

    The table is plain ints and floats, updated without a lock (a span
    must cost a microsecond, and the spans of the program run on the
    thread that calls into it): two threads closing spans of one name
    at the same instant can lose one of the two updates, nothing more.
    """

    __slots__ = ("name", "fields", "seconds", "_t0", "_ann", "_pushed")

    def __init__(self, name: str, **fields) -> None:
        self.name = name
        self.fields = fields

    def __enter__(self) -> "span":
        ann = _annotation or _find_annotation()
        if ann is not None and ann.is_enabled():
            ann = self._ann = ann(PREFIX + self.name, **self.fields)
            ann.__enter__()
        else:
            self._ann = None
        self._pushed = _sink is not None
        if self._pushed:
            self._push()
        self._t0 = _perf()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = self.seconds = _perf() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        entry = _spans.get(self.name)
        if entry is None:
            _spans[self.name] = [1, dt, dt]
        else:
            entry[0] += 1
            entry[1] += dt
            if dt > entry[2]:
                entry[2] = dt
        if self._pushed:
            self._export(dt)
        return False

    # ---- the telemetry sink (rabit_obs on) ---------------------------
    def _push(self) -> None:
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        version = self.fields.get("version")
        if version is None and stack:
            version = stack[-1][1]
        stack.append((self.name, version))

    def _export(self, dt: float) -> None:
        stack = _open.stack
        version = stack.pop()[1]     # what this span pushed
        sink = _sink
        if sink is None:             # detached while the span was open
            return
        metrics, trace, engine = sink
        metrics.histogram(f"span.{self.name}.seconds").observe(dt)
        fields = {k: v for k, v in self.fields.items() if k != "version"}
        trace.emit("span", dur=dt, kind=self.name,
                   parent=stack[-1][0] if stack else None,
                   version=version, rank=engine.rank, **fields)


def count(name: str, k: float = 1) -> None:
    """Add ``k`` to the counter ``name`` (unlocked, like the spans)."""
    _counters[name] = _counters.get(name, 0) + k


def stats() -> dict:
    """The table, flat and JSON-serialisable: ``<span>.n``,
    ``<span>.total_s``, ``<span>.max_s`` and every counter under its own
    name."""
    out = dict(_counters)
    for name, (n, total, longest) in list(_spans.items()):
        out[name + ".n"] = n
        out[name + ".total_s"] = total
        out[name + ".max_s"] = longest
    return out


def reset() -> None:
    """Empty the table (tests; a process's table otherwise lives as long
    as the process)."""
    _spans.clear()
    _counters.clear()


def attach(engine) -> None:
    """Send spans to ``engine``'s telemetry too, if it is on
    (``Engine.metrics()`` gives a registry).  ``engine.init`` attaches
    the process's outermost engine once it is up, so the spans of
    set-up that close before then (``init.group``) are in the table
    and the profiler's trace only."""
    global _sink
    metrics, trace = engine.metrics(), engine.event_trace()
    _sink = (metrics, trace, engine) if metrics is not None \
        and trace is not None else None


def detach() -> None:
    global _sink
    _sink = None

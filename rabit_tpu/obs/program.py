"""Program spans and counters: the device-plane path timed where the
work happens.

``with program.span("commit.barrier"):`` around a layer boundary does
three things (doc/observability.md "Program spans"):

1. **accumulates** ``n`` / ``total_s`` / ``max_s`` / ``self_s`` /
   ``exposed_s`` under the span's name in one process-wide table,
   counters (:func:`count`) beside them — always on; :func:`stats`
   flattens the table into what an engine reports as
   ``Engine.path_stats``;
2. **opens a** ``jax.profiler.TraceAnnotation("rabit:" + name)`` while a
   profiler session is recording, so the span sits in the
   ``.xplane.pb`` on the device trace's clock with nothing to switch
   on.  JAX is never imported for the span's sake: a process that has
   not imported it has no profiler session either;
3. **when telemetry is on** (``rabit_obs``; :func:`attach`) also emits
   one ``span`` event into the engine's :class:`~rabit_tpu.obs.EventTrace`
   (``kind`` = the span's name, ``parent``, ``version``, ``self``,
   ``exposed``, the caller's fields) and observes
   ``span.<name>.seconds`` in its :class:`~rabit_tpu.obs.Metrics`.

The version number is the identifier the spans of one unit of work
share: the caller that has it at hand passes ``version=`` and the spans
nested inside inherit it.  Nesting is per thread.

**The boundary rule.**  Every enter and every exit of a span is a
boundary of its thread.  The interval since the thread's previous
boundary is added to ``self_s`` of the innermost span open on the thread
(so a span's ``self_s`` is its duration less what its children cover),
and, if the device had nothing running or queued when the interval
began, to ``exposed_s`` of *every* span open on the thread (inclusive,
as ``total_s`` is).  Whether the device has work is observed, not
reckoned: a loop names the newest result the device owes with
:func:`enqueued` where it hands a program over, and at a boundary the
device is idle when there is none or that result ``is_ready()``.  So
``exposed_s`` is a lower bound of the device's idle time seen from the
host: an interval that began with a kernel running counts nothing, even
if the kernel ended inside it.
"""
from __future__ import annotations

import sys
import threading
import time
import weakref

PREFIX = "rabit:"
STEP = "learn.step"                  # a thread that has opened one counts
NO_SPAN = "(no span)"                # its time between spans under this name

_perf = time.perf_counter
_spans: dict[str, list] = {}         # name -> [n, total_s, max_s, self_s,
                                     #          exposed_s]
_counters: dict[str, float] = {}
_no_span = [0.0, 0.0]                # self_s, exposed_s
_annotation = None                   # jax.profiler.TraceAnnotation, once seen
_sink = None                         # (Metrics, EventTrace, engine) or None
_owed = None                         # weak reference to the newest result
                                     # the device owes, or None


class _State:
    """One thread's innermost open span (each knows its parent); its
    previous boundary (when it was, and whether the device was idle
    then); and the seconds of its intervals that began idle, added up
    since the thread's first span: what a span was exposed for is what
    this gained while it was open."""

    __slots__ = ("top", "last", "idle", "exposed", "stepped")

    def __init__(self) -> None:
        self.top = None
        self.last = _perf()
        self.idle = True
        self.exposed = 0.0
        self.stepped = False


class _Thread(threading.local):
    def __init__(self) -> None:
        self.state = _State()


_thread = _Thread()


def _find_annotation():
    """The profiler's annotation type, once this process has JAX."""
    global _annotation
    jax = sys.modules.get("jax")
    _annotation = getattr(getattr(jax, "profiler", None),
                          "TraceAnnotation", None)
    return _annotation


def enqueued(result) -> None:
    """Name the newest result the device owes: one array (any object
    with ``is_ready()``) that the program just handed over will write.
    A device runs its programs in order, so the newest is enough.  Held
    weakly: the table keeps no buffer alive.  One name a process: the
    thread that hands programs over is the one whose boundaries ask
    (the learner's loop); a second such thread would read the first's
    device as its own."""
    global _owed
    try:
        _owed = weakref.ref(result) if hasattr(result, "is_ready") else None
    except TypeError:                # cannot be referenced weakly
        _owed = None


def _device_idle() -> bool:
    """Has the newest result landed?  One that was collected, or deleted
    (donated to a later program), counts as landed."""
    global _owed
    ref = _owed
    if ref is None:
        return True
    result = ref()
    if result is not None:
        # a donated array raises from `is_ready`; one deleted by hand
        # can crash jaxlib there, so it is asked first where it can say
        deleted = getattr(result, "is_deleted", None)
        try:
            if (deleted is None or not deleted()) and not result.is_ready():
                return False
        except Exception:            # noqa: BLE001 — "Array has been deleted"
            pass
    if _owed is ref:                 # not one another thread named since
        _owed = None
    return True


class span:
    """One timed section.  ``seconds`` holds its duration once closed;
    an exception inside it still closes it.

    The table is plain ints and floats, updated without a lock (a span
    must cost a microsecond, and the spans of the program run on the
    thread that calls into it): two threads closing spans of one name
    at the same instant can lose one of the two updates, nothing more.
    """

    __slots__ = ("name", "fields", "seconds", "_t0", "_ann", "_pushed",
                 "_state", "_parent", "_exposed", "_children")

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
        self._pushed = _sink is not None     # telemetry sees it open
        state = self._state = _thread.state
        now = self._t0 = _perf()
        # the boundary.  The interval since the last one is exposed if
        # it began idle; it is the parent's own, which `self_s` has as
        # the parent's duration less its children's
        if state.idle:
            state.exposed += now - state.last
        parent = self._parent = state.top
        if parent is None:
            _outermost(state, now, self.name)
        state.last = now
        state.idle = _owed is None or _device_idle()
        self._exposed = state.exposed
        self._children = 0.0
        state.top = self
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        now = _perf()
        dt = self.seconds = now - self._t0
        state = self._state
        if state.idle:
            state.exposed += now - state.last
        state.last = now
        state.idle = _owed is None or _device_idle()
        parent = state.top = self._parent
        if parent is not None:
            parent._children += dt
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        own = dt - self._children
        exposed = state.exposed - self._exposed
        entry = _spans.get(self.name)
        if entry is None:
            _spans[self.name] = [1, dt, dt, own, exposed]
        else:
            entry[0] += 1
            entry[1] += dt
            if dt > entry[2]:
                entry[2] = dt
            entry[3] += own
            entry[4] += exposed
        if self._pushed:
            self._export(dt, own, exposed)
        return False

    # ---- the telemetry sink (rabit_obs on) ---------------------------
    def _export(self, dt: float, own: float, exposed: float) -> None:
        sink = _sink
        if sink is None:             # detached while the span was open
            return
        # the nearest span still open that telemetry saw open is the
        # parent; the version is the nearest one's that has one
        parent, version = None, self.fields.get("version")
        above = self._parent
        while above is not None:
            if above._pushed:
                if parent is None:
                    parent = above.name
                if version is None:
                    version = above.fields.get("version")
            above = above._parent
        metrics, trace, engine = sink
        metrics.histogram(f"span.{self.name}.seconds").observe(dt)
        fields = {k: v for k, v in self.fields.items() if k != "version"}
        fields["self"], fields["exposed"] = own, exposed
        trace.emit("span", dur=dt, kind=self.name, parent=parent,
                   version=version, rank=engine.rank, **fields)


def _outermost(state: _State, now: float, name: str) -> None:
    """A span opens with none open on its thread: once the thread has
    opened a ``learn.step``, the time since the last one closed is
    time under no span."""
    if state.stepped:
        _no_span[0] += now - state.last
        if state.idle:
            _no_span[1] += now - state.last
    elif name == STEP:
        state.stepped = True


def count(name: str, k: float = 1) -> None:
    """Add ``k`` to the counter ``name`` (unlocked, like the spans)."""
    _counters[name] = _counters.get(name, 0) + k


def put(name: str, value: float) -> None:
    """Set the counter ``name``: a size of the job (its number of
    classes), which a second job of the process states again and does
    not add to."""
    _counters[name] = value


def stats() -> dict:
    """The table, flat and JSON-serialisable: ``<span>.n``,
    ``<span>.total_s``, ``<span>.max_s``, ``<span>.self_s``,
    ``<span>.exposed_s`` and every counter under its own name; and,
    once a thread has opened a ``learn.step``, the time it spent between
    spans since as ``(no span).self_s`` / ``(no span).exposed_s``."""
    out = dict(_counters)
    for name, (n, total, longest, own, exposed) in list(_spans.items()):
        out[name + ".n"] = n
        out[name + ".total_s"] = total
        out[name + ".max_s"] = longest
        out[name + ".self_s"] = own
        out[name + ".exposed_s"] = exposed
    if _no_span[0]:
        out[NO_SPAN + ".self_s"], out[NO_SPAN + ".exposed_s"] = _no_span
    return out


def reset() -> None:
    """Empty the table (tests; a process's table otherwise lives as long
    as the process).  Of the per-thread state only the calling thread's
    is touched: another thread that has opened a ``learn.step`` goes on
    counting its time between spans."""
    _spans.clear()
    _counters.clear()
    _no_span[:] = 0.0, 0.0
    _thread.state.stepped = False


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

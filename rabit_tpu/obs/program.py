"""Program spans and counters: the device-plane path timed where the
work happens.

``with program.span("commit.barrier"):`` around a layer boundary does
three things (doc/observability.md "Program spans"):

1. **accumulates** ``n`` / ``total_s`` / ``max_s`` / ``self_s`` /
   ``exposed_s`` / ``unsure_s`` under the span's name in one
   process-wide table, counters (:func:`count`) beside them — always
   on; :func:`stats` flattens the table into what an engine reports as
   ``Engine.path_stats``;
2. **opens a** ``jax.profiler.TraceAnnotation("rabit:" + name)`` while a
   profiler session is recording, so the span sits in the
   ``.xplane.pb`` on the device trace's clock with nothing to switch
   on, and, if the session still records when the span closes, adds the
   span a second time under ``traced/<name>``: the table of the
   recorded session, whole spans inside the trace's window and nothing
   of the profiler's own start and stop.  JAX is never imported for the
   span's sake: a process that has not imported it has no profiler
   session either;
3. **when telemetry is on** (``rabit_obs``; :func:`attach`) also emits
   one ``span`` event into the engine's :class:`~rabit_tpu.obs.EventTrace`
   (``kind`` = the span's name, ``parent``, ``version``, ``self``,
   ``exposed``, ``unsure``, the caller's fields) and observes
   ``span.<name>.seconds`` in its :class:`~rabit_tpu.obs.Metrics`.

The version number is the identifier the spans of one unit of work
share: the caller that has it at hand passes ``version=`` and the spans
nested inside inherit it.  Nesting is per thread.

**The boundary rule.**  Every enter and every exit of a span, every
hand-over of a program (:func:`enqueued`) and every return from a
blocking wait for the device (:func:`waited`) is a boundary of its
thread.  The interval since the thread's previous boundary is added to
``self_s`` of the innermost span open on the thread (so a span's
``self_s`` is its duration less what its children cover), and then,
inclusive as ``total_s`` is, to one column at most of *every* span open
on the thread:

* ``exposed_s`` if the device had nothing running or queued when the
  interval **began**: nothing was handed over inside it (a hand-over
  would have ended it), so the device was idle all through;
* ``unsure_s`` if it began with work on the device and **ended** with
  none: the device went idle somewhere inside it;
* nothing if it ended as it began, busy.

Whether the device has work is observed, not reckoned: a loop names the
newest result the device owes with :func:`enqueued` where it hands a
program over, and at a boundary the device is idle when there is none
or that result ``is_ready()``.  One sample a boundary ends one interval
and begins the next.  A blocking wait for the device says where it ends
(:func:`waited`): the device ran until then, so a wait is never unsure.
So over any stretch of time, for every span,

    exposed_s  <=  device idle under the span
               <=  exposed_s + unsure_s + handovers_idle * launch lag
                                        + waits_idle * notice lag

``exposed_s`` is the lower bound of the device's idle time seen from the
host, ``unsure_s`` the band above it, and the last two terms what no
column can see: a program handed to an idle device starts a launch lag
later (half a millisecond from the host's call on a v5e), in an interval
that began busy by the table's reckoning, and a wait comes back a notice
after the device went idle (half a millisecond too).  ``learn.handovers_idle`` and
``learn.waits_idle`` count the two.
"""
from __future__ import annotations

import sys
import threading
import time
import weakref

PREFIX = "rabit:"
STEP = "learn.step"                  # a thread that has opened one counts
NO_SPAN = "(no span)"                # its time between spans under this name
TRACED = "traced/"                   # the table of a recorded session
HANDOVERS = "learn.handovers"        # `enqueued` under an open step, and
WAITS = "learn.waits"                # `waited`; `<name>_idle`: those of
IDLE = "_idle"                       # them that found the device idle
MARK = "enqueued"                    # a hand-over in a recorded session
COLUMNS = ("n", "total_s", "max_s", "self_s", "exposed_s", "unsure_s")

_perf = time.perf_counter
_spans: dict[str, list] = {}         # name -> a value a column
_traced: dict[str, list] = {}        # the same, of the spans that began
                                     # and ended inside a profiler session
_counters: dict[str, float] = {}
_no_span = [0.0, 0.0, 0.0]           # self_s, exposed_s, unsure_s
_annotation = None                   # jax.profiler.TraceAnnotation, once seen
_sink = None                         # (Metrics, EventTrace, engine) or None
_owed = None                         # weak reference to the newest result
                                     # the device owes, or None


class _State:
    """One thread's innermost open span (each knows its parent); its
    previous boundary (when it was, and whether the device was idle
    then); and the seconds of its intervals that began idle, and of
    those that began busy and ended idle, added up since the thread's
    first span: what a span was exposed or unsure for is what these
    gained while it was open."""

    __slots__ = ("top", "last", "idle", "exposed", "unsure", "stepped")

    def __init__(self) -> None:
        self.top = None
        self.last = _perf()
        self.idle = True
        self.exposed = 0.0
        self.unsure = 0.0
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
    device as its own.

    A hand-over is a boundary of its thread: the interval that ends
    here is counted as an enter or an exit counts it, and the next
    begins busy.  Under an open ``learn.step`` it is also counted, and
    counted again if it found the device idle; while a profiler session
    records it is an annotation ``rabit:enqueued`` of no length."""
    global _owed
    state = _thread.state
    now = _perf()
    idle = _owed is None or _device_idle()
    top = state.top
    _boundary(state, now, idle, top is None and state.stepped)
    try:
        _owed = weakref.ref(result) if hasattr(result, "is_ready") else None
    except TypeError:                # cannot be referenced weakly
        _owed = None
    state.idle = _owed is None
    if not state.idle:
        _tally(top, HANDOVERS, idle)
        if top is not None and top._ann is not None:
            # a session records: the hand-over as the host saw it, an
            # instant on the trace's clock (the runtime's own
            # `DoEnqueueProgram` follows on a thread of its own)
            with _annotation(PREFIX + MARK):
                pass


def waited() -> None:
    """The calling thread has just come back from a blocking wait for a
    device result (``block_until_ready``).  A boundary that knows more
    than a sample does: whatever the device ran when the wait began, it
    ran until the runtime told the host.  So the interval that ends
    here ended as it began: exposed if it began idle (the result was
    there already), nothing if it began busy, and never unsure.  The
    next begins as a sample finds the device.  What the device idled at
    the wait's tail is the runtime's notice, which no column can see:
    under an open ``learn.step`` the wait is counted, and counted again
    if it came back to an idle device."""
    state = _thread.state
    top = state.top
    _boundary(state, _perf(), state.idle, top is None and state.stepped)
    state.idle = _owed is None or _device_idle()
    _tally(top, WAITS, state.idle)


def _tally(top, name: str, idle: bool) -> None:
    """Count a hand-over or a wait made with a ``learn.step`` open on
    the thread (``top``: its innermost open span), again under
    ``name + "_idle"`` if it found the device idle, and both again for
    the recorded session if the innermost span holds an annotation."""
    above = top
    while above is not None and above.name != STEP:
        above = above._parent
    if above is not None:
        for prefix in ("", TRACED) if top._ann is not None else ("",):
            count(prefix + name)
            if idle:
                count(prefix + name + IDLE)


def _boundary(state: _State, now: float, idle: bool, between: bool) -> None:
    """The thread's running interval ends at ``now`` with the device
    ``idle`` or not: into the column its two ends say, and into the
    time ``between`` spans if none is open and the thread has opened a
    ``learn.step`` before.  The next interval begins as this one ended,
    unless the caller knows better."""
    dt = now - state.last
    column = 1 if state.idle else 2 if idle else 0
    if column == 1:
        state.exposed += dt
    elif column:
        state.unsure += dt
    if between:
        _no_span[0] += dt
        if column:
            _no_span[column] += dt
    state.last = now
    state.idle = idle


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
                 "_state", "_parent", "_exposed", "_unsure", "_children")

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
        # the boundary (`_boundary`, inline: a span must cost a
        # microsecond).  The interval since the last one is the
        # parent's own, which `self_s` has as the parent's duration
        # less its children's
        idle = _owed is None or _device_idle()
        parent = self._parent = state.top
        if parent is None:
            _boundary(state, now, idle, state.stepped)
            if self.name == STEP:    # time under no span counts from here
                state.stepped = True
        else:
            if state.idle:
                state.exposed += now - state.last
            elif idle:
                state.unsure += now - state.last
            state.last = now
            state.idle = idle
        self._exposed = state.exposed
        self._unsure = state.unsure
        self._children = 0.0
        state.top = self
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        now = _perf()
        dt = self.seconds = now - self._t0
        state = self._state
        idle = _owed is None or _device_idle()
        if state.idle:
            state.exposed += now - state.last
        elif idle:
            state.unsure += now - state.last
        state.last = now
        state.idle = idle
        parent = state.top = self._parent
        if parent is not None:
            parent._children += dt
        own = dt - self._children
        exposed = state.exposed - self._exposed
        unsure = state.unsure - self._unsure
        entry = _spans.get(self.name)
        if entry is None:
            _spans[self.name] = [1, dt, dt, own, exposed, unsure]
        else:
            entry[0] += 1
            entry[1] += dt
            if dt > entry[2]:
                entry[2] = dt
            entry[3] += own
            entry[4] += exposed
            entry[5] += unsure
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            if _annotation.is_enabled():     # the session still records
                entry = _traced.setdefault(self.name, [0, 0.0, 0.0, 0.0, 0.0,
                                                       0.0])
                entry[0] += 1
                entry[1] += dt
                entry[2] = max(entry[2], dt)
                entry[3] += own
                entry[4] += exposed
                entry[5] += unsure
        if self._pushed:
            self._export(dt, own, exposed, unsure)
        return False

    # ---- the telemetry sink (rabit_obs on) ---------------------------
    def _export(self, dt: float, own: float, exposed: float,
                unsure: float) -> None:
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
        fields["self"], fields["exposed"], fields["unsure"] = (
            own, exposed, unsure)
        trace.emit("span", dur=dt, kind=self.name, parent=parent,
                   version=version, rank=engine.rank, **fields)


def count(name: str, k: float = 1) -> None:
    """Add ``k`` to the counter ``name`` (unlocked, like the spans)."""
    _counters[name] = _counters.get(name, 0) + k


def put(name: str, value: float) -> None:
    """Set the counter ``name``: a size of the job (its number of
    classes), which a second job of the process states again and does
    not add to."""
    _counters[name] = value


def stats() -> dict:
    """The table, flat and JSON-serialisable: ``<span>.<column>`` for
    every column of :data:`COLUMNS` and every counter under its own
    name; the same under ``traced/<span>`` for the spans that began and
    ended inside a profiler session, if there was one; and, once a
    thread has opened a ``learn.step``, the time it spent between spans
    since as ``(no span).self_s`` / ``.exposed_s`` / ``.unsure_s``."""
    out = dict(_counters)
    for prefix, table in (("", _spans), (TRACED, _traced)):
        for name, row in list(table.items()):
            for column, value in zip(COLUMNS, row):
                out[f"{prefix}{name}.{column}"] = value
    if _no_span[0]:
        for column, value in zip(COLUMNS[3:], _no_span):
            out[f"{NO_SPAN}.{column}"] = value
    return out


def reset() -> None:
    """Empty the table (tests; a process's table otherwise lives as long
    as the process).  Of the per-thread state only the calling thread's
    is touched: another thread that has opened a ``learn.step`` goes on
    counting its time between spans."""
    _spans.clear()
    _traced.clear()
    _counters.clear()
    _no_span[:] = 0.0, 0.0, 0.0
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

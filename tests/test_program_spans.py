"""Program spans and counters (rabit_tpu/obs/program.py): the table's
arithmetic, ``Engine.path_stats``, the spans a k-means run and a robust
commit leave, and the three sinks — the table (always), the profiler's
trace (while a session records), the engine's telemetry (``rabit_obs``).
All on the CPU; a time read here is never a device metric."""
import json
import os
import statistics
import sys
import time

import numpy as np
import pytest

import rabit_tpu
from rabit_tpu import engine as engine_mod
from rabit_tpu import obs
from rabit_tpu.obs import program

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STAGE = {"stage.to_ell", "stage.clamp", "stage.put"}
LOOP = {"learn.step", "learn.dispatch", "learn.fetch", "learn.fetch.wait",
        "learn.fetch.copy", "learn.update"}
COLUMNS = ("n", "total_s", "max_s", "self_s", "exposed_s", "unsure_s")
# what kmeans.run leaves on engine `empty`: its two calls before the
# loop, staging, the loop, the commit as far as `empty` has layers
KMEANS_SPANS = (STAGE | LOOP
                | {"load_checkpoint", "allreduce", "commit",
                   "commit.serialize"})


@pytest.fixture
def table():
    program.reset()
    yield program
    program.reset()


def open_span() -> str:
    """The innermost span open on this thread."""
    return program._thread.state.top.name


def span_names(stats: dict) -> set:
    return {k[:-2] for k in stats if k.endswith(".n")}


# ------------------------------------------------------------ the table
def test_span_accumulates_n_total_and_max(table):
    for seconds in (0.002, 0.02, 0.002):
        with program.span("a"):
            time.sleep(seconds)
    s = program.stats()
    assert s["a.n"] == 3
    assert 0.024 <= s["a.total_s"] < 0.2
    assert 0.02 <= s["a.max_s"] < s["a.total_s"]


def test_nested_spans_are_both_counted_and_the_child_fits(table):
    with program.span("outer") as outer:
        with program.span("outer.inner") as inner:
            time.sleep(0.005)
    s = program.stats()
    assert s["outer.n"] == s["outer.inner.n"] == 1
    assert 0.005 <= s["outer.inner.total_s"] <= s["outer.total_s"]
    assert inner.seconds == s["outer.inner.total_s"]
    assert outer.seconds == s["outer.total_s"]


def test_an_exception_closes_the_span_and_passes_through(table):
    with pytest.raises(KeyError):
        with program.span("boom"):
            with program.span("boom.child"):
                raise KeyError("x")
    s = program.stats()
    assert s["boom.n"] == 1 and s["boom.child.n"] == 1
    with program.span("boom"):          # and the table still works
        pass
    assert program.stats()["boom.n"] == 2


def test_counters_share_the_table_and_keep_ints(table):
    program.count("c.bytes", 100)
    program.count("c.bytes", 28)
    program.count("c.calls")
    program.count("c.seconds", 0.25)
    s = program.stats()
    assert s["c.bytes"] == 128 and isinstance(s["c.bytes"], int)
    assert s["c.calls"] == 1 and s["c.seconds"] == 0.25


def test_stats_is_flat_json_and_reset_empties_it(table):
    with program.span("x", version=3, anything="goes"):
        program.count("x.k", 2)
    s = program.stats()
    assert set(s) == {"x." + column for column in COLUMNS} | {"x.k"}
    assert all(isinstance(v, (int, float)) for v in s.values())
    assert json.loads(json.dumps(s)) == s
    program.reset()
    assert program.stats() == {}


def test_span_off_cost_is_microseconds(table):
    """No profiler session, telemetry off: a span is a few dictionary
    operations.  A loose ceiling (the median of 10,000, under 10 us), so
    that a loaded box does not fail it; the x4 loop opens 12 a version
    of 22 ms."""
    samples = []
    for _ in range(10000):
        t0 = time.perf_counter()
        with program.span("cost"):
            pass
        samples.append(time.perf_counter() - t0)
    assert statistics.median(samples) < 10e-6
    assert program.stats()["cost.n"] == 10000


# ------------------------------------------- self and exposed seconds
class Clock:
    """The table's clock, moved by hand."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.fixture
def clock(table, monkeypatch):
    """A clock the test moves and a thread state that starts on it."""
    clock = Clock()
    monkeypatch.setattr(program, "_perf", clock)
    monkeypatch.setattr(program._thread, "state", program._State())
    yield clock
    program.enqueued(None)


class Owed:
    """A result the device owes until the test says it has landed."""

    def __init__(self):
        self.landed = False

    def is_ready(self):
        return self.landed


class Deleted:
    def is_ready(self):
        raise RuntimeError("Array has been deleted.")


class DeletedByHand:
    """``Array.delete()``: jaxlib can crash in ``is_ready`` of one."""

    def is_deleted(self):
        return True

    def is_ready(self):
        raise AssertionError("asked a deleted array whether it is ready")


def test_self_seconds_of_a_parent_and_its_children_add_up_to_its_total(clock):
    with program.span("outer"):                     # 100 .. 110
        clock.now = 102.0
        with program.span("outer.a"):               # 102 .. 105
            clock.now = 105.0
        clock.now = 106.0
        with program.span("outer.b"):               # 106 .. 109
            clock.now = 107.0
            with program.span("outer.b.deep"):      # 107 .. 108
                clock.now = 108.0
            clock.now = 109.0
        clock.now = 110.0
    s = program.stats()
    assert s["outer.total_s"] == 10.0 and s["outer.self_s"] == 4.0
    assert s["outer.a.self_s"] == s["outer.a.total_s"] == 3.0
    assert s["outer.b.total_s"] == 3.0 and s["outer.b.self_s"] == 2.0
    assert s["outer.b.deep.self_s"] == 1.0
    assert sum(s[name + ".self_s"] for name in span_names(s)) == 10.0


def test_an_interval_is_exposed_if_it_began_with_the_device_idle(clock):
    """A step that hands a program over, waits for it and copies the
    result back: the dispatch up to its hand-over and the copy began
    with nothing in flight and count under themselves and the step; the
    launch after the dispatch and the wait began with the program owed
    and are not exposed, though it landed inside the wait: the wait is
    what the table is unsure of."""
    owed = Owed()
    with program.span("learn.step"):                # 100 .. 109
        clock.now = 101.0
        with program.span("learn.dispatch"):        # 101 .. 102
            clock.now = 102.0
            program.enqueued(owed)
        clock.now = 103.0                           # the launch
        with program.span("learn.fetch.wait"):      # 103 .. 105
            owed.landed = True
            clock.now = 105.0
        clock.now = 106.0
        with program.span("learn.fetch.copy"):      # 106 .. 108
            clock.now = 108.0
        clock.now = 109.0
    s = program.stats()
    assert s["learn.dispatch.exposed_s"] == 1.0
    assert s["learn.fetch.wait.exposed_s"] == 0.0
    assert s["learn.fetch.copy.exposed_s"] == 2.0
    # 100-101 and the dispatch, then everything after the wait
    assert s["learn.step.exposed_s"] == 6.0
    assert s["learn.step.self_s"] == 4.0
    assert s["learn.fetch.wait.unsure_s"] == s["learn.step.unsure_s"] == 2.0
    assert s["learn.dispatch.unsure_s"] == s["learn.fetch.copy.unsure_s"] == 0
    for name in span_names(s):
        assert s[name + ".exposed_s"] + s[name + ".unsure_s"] \
            <= s[name + ".total_s"]


def test_a_result_still_owed_exposes_nothing_however_many_spans_pass(clock):
    owed = Owed()
    program.enqueued(owed)
    with program.span("busy"):
        for _ in range(3):
            clock.now += 1.0
            with program.span("busy.inner"):
                clock.now += 1.0
    s = program.stats()
    assert s["busy.total_s"] == 6.0
    assert s["busy.exposed_s"] == s["busy.inner.exposed_s"] == 0.0
    owed.landed = True          # seen at the next boundary, not before
    clock.now += 1.0
    with program.span("after"):
        clock.now += 2.0
    assert program.stats()["after.exposed_s"] == 2.0


@pytest.mark.parametrize("result", ["deleted", "deleted_by_hand", "collected",
                                    "numpy", "none"])
def test_a_result_that_is_gone_counts_as_landed(clock, result):
    """A donated array raises from ``is_ready``, one deleted by hand is
    not asked, a freed one is not there to ask (the table holds it
    weakly), a host array was never owed."""
    import gc

    made = {"deleted": Deleted, "deleted_by_hand": DeletedByHand,
            "collected": Owed,
            "numpy": lambda: np.ones(3), "none": lambda: None}[result]()
    program.enqueued(made)
    if result == "collected":
        with program.span("held"):
            clock.now += 1.0
        assert program.stats()["held.exposed_s"] == 0.0
        del made
        gc.collect()
        clock.now += 1.0
    with program.span("gone"):
        clock.now += 2.0
    assert program.stats()["gone.exposed_s"] == 2.0
    assert program._owed is None


def test_a_result_named_while_another_lands_is_not_forgotten(clock):
    """The name of the newest result is the process's: a result that
    another thread names while this thread finds the one before it
    landed stays owed."""
    newer = Owed()

    class LandsAsAnotherIsNamed(Owed):
        def is_ready(self):
            program.enqueued(newer)     # as a second thread would, now
            return True

    first = LandsAsAnotherIsNamed()
    program.enqueued(first)
    with program.span("first.landed"):
        clock.now += 1.0
    assert program._owed() is newer
    with program.span("newer.owed"):
        clock.now += 1.0
    assert program.stats()["newer.owed.exposed_s"] == 0.0


def test_two_threads_keep_two_stacks_of_open_spans(table):
    import threading

    def helper():
        with program.span("helper.work"):
            with program.span("helper.work.inner"):
                time.sleep(0.01)

    with program.span("main.work"):
        t = threading.Thread(target=helper)
        t.start()
        t.join(10)
        assert not t.is_alive()
    s = program.stats()
    # the helper's spans are no children of what the main thread had open
    assert s["main.work.self_s"] == s["main.work.total_s"] >= 0.01
    assert s["helper.work.inner.self_s"] == s["helper.work.inner.total_s"]
    assert s["helper.work.self_s"] == pytest.approx(
        s["helper.work.total_s"] - s["helper.work.inner.total_s"], abs=1e-12)
    assert program._thread.state.top is None


def test_time_between_spans_after_the_first_step_is_under_no_span(clock):
    with program.span("stage.put"):
        clock.now += 1.0
    clock.now += 5.0                    # before any step: nobody's
    with program.span("learn.step"):
        clock.now += 1.0
    assert not any(k.startswith(program.NO_SPAN) for k in program.stats())
    clock.now += 2.0
    owed = Owed()
    program.enqueued(owed)
    with program.span("learn.step"):    # the 2 s began idle
        clock.now += 1.0
    clock.now += 3.0                    # these 3 s began with one owed
    with program.span("learn.step"):
        clock.now += 1.0
    s = program.stats()
    assert s[program.NO_SPAN + ".self_s"] == 5.0
    assert s[program.NO_SPAN + ".exposed_s"] == 2.0
    assert s[program.NO_SPAN + ".unsure_s"] == 0.0
    assert set(s) == {name + "." + column for column in COLUMNS
                      for name in ("stage.put", "learn.step")} | {
        program.NO_SPAN + "." + column for column in COLUMNS[3:]}
    program.reset()
    assert program.stats() == {}
    clock.now += 4.0
    with program.span("learn.step"):
        clock.now += 1.0
    # a table that was emptied starts again at nothing (and the result
    # is owed still)
    assert program.stats() == {
        "learn.step.n": 1, "learn.step.total_s": 1.0, "learn.step.max_s": 1.0,
        "learn.step.self_s": 1.0, "learn.step.exposed_s": 0.0,
        "learn.step.unsure_s": 0.0}


# ------------------ a hand-over is a boundary; the band; a session's table
def test_a_hand_over_inside_an_interval_that_began_idle_cuts_it(clock):
    """Only the part of the step's own time before the hand-over is
    exposed; what follows it began busy.  Before this rule the seven
    seconds to the wait's enter counted whole."""
    owed, second = Owed(), Owed()
    with program.span("learn.step"):                # 100 .. 110
        clock.now = 103.0
        program.enqueued(owed)                      # found the device idle
        clock.now = 105.0
        program.enqueued(second)                    # found it busy
        clock.now = 107.0
        with program.span("learn.fetch.wait"):      # 107 .. 109
            clock.now = 109.0
            owed.landed = second.landed = True
        clock.now = 110.0
    s = program.stats()
    assert s["learn.step.exposed_s"] == 3.0 + 1.0
    assert s["learn.step.unsure_s"] == s["learn.fetch.wait.unsure_s"] == 2.0
    assert s["learn.step.self_s"] == 8.0            # a hand-over is no span
    assert s["learn.fetch.wait.exposed_s"] == 0.0
    assert (s["learn.handovers"], s["learn.handovers_idle"]) == (2, 1)


def test_an_interval_that_began_busy_and_ended_idle_is_unsure_alone(clock):
    """A commit under a kernel that it outlasts: the second before the
    kernel's end is nobody's, the serialisation inside which it ended is
    the band's, what follows is exposed; no second is in two columns."""
    owed = Owed()
    program.enqueued(owed)
    with program.span("commit"):                    # 100 .. 108
        clock.now = 101.0
        with program.span("commit.serialize"):      # 101 .. 106
            clock.now = 106.0
            owed.landed = True
        clock.now = 108.0
    s = program.stats()
    assert (s["commit.serialize.exposed_s"],
            s["commit.serialize.unsure_s"]) == (0.0, 5.0)
    assert (s["commit.exposed_s"], s["commit.unsure_s"]) == (2.0, 5.0)
    assert s["commit.self_s"] == 3.0 and s["commit.total_s"] == 8.0
    assert "learn.handovers" not in s               # no step was open


def test_a_wait_is_never_unsure_and_counts_what_it_came_back_to(clock):
    """``block_until_ready`` says where the device's work ended: at the
    wait's end, a notice ago.  The wait's seconds are nobody's; the
    wait that comes back to an idle device is counted for the notice."""
    first, second = Owed(), Owed()
    with program.span("learn.step"):                # 100 .. 110
        program.enqueued(first)
        program.enqueued(second)
        with program.span("learn.fetch.wait"):      # 100 .. 104
            clock.now = 104.0
            first.landed = True                     # the newer one runs on
            program.waited()
        with program.span("learn.fetch.wait"):      # 104 .. 107
            clock.now = 107.0
            second.landed = True
            program.waited()
        with program.span("learn.fetch.wait"):      # 107 .. 108: no wait,
            clock.now = 108.0                       # the result was there
            program.waited()
        clock.now = 110.0
    s = program.stats()
    assert s["learn.fetch.wait.unsure_s"] == s["learn.step.unsure_s"] == 0.0
    assert s["learn.fetch.wait.exposed_s"] == 1.0
    assert s["learn.step.exposed_s"] == 3.0
    assert (s["learn.waits"], s["learn.waits_idle"]) == (3, 2)
    assert (s["learn.handovers"], s["learn.handovers_idle"]) == (2, 1)
    before = program.stats()
    program.waited()                                # no span open
    assert program.stats() == before


def test_a_hand_over_with_no_span_open_changes_no_spans_columns(clock):
    with program.span("stage.put"):
        clock.now += 1.0
    before = program.stats()
    clock.now += 1.0
    program.enqueued(Owed())
    clock.now += 1.0
    program.enqueued(None)
    assert program.stats() == before


def test_a_process_without_jax_pays_no_import_for_a_span():
    """Spans, hand-overs and waits in a process that never imported JAX:
    none imports it, and there is no session's table."""
    import subprocess

    code = (
        "import sys\n"
        "from rabit_tpu.obs import program\n"
        "class Owed:\n"
        "    def is_ready(self): return True\n"
        "owed = Owed()\n"
        "with program.span('learn.step'):\n"
        "    with program.span('learn.dispatch'):\n"
        "        program.enqueued(owed)\n"
        "    program.waited()\n"
        "s = program.stats()\n"
        "assert 'jax' not in sys.modules, 'imported'\n"
        "assert s['learn.handovers'] == s['learn.waits'] == 1, s\n"
        "assert not any(k.startswith(program.TRACED) for k in s), s\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   timeout=120)


class Session:
    """What the table asks of ``jax.profiler.TraceAnnotation``, with a
    session the test starts and stops."""
    recording = False

    def __init__(self, name, **fields):
        pass

    @classmethod
    def is_enabled(cls):
        return cls.recording

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_the_table_of_a_session_holds_the_spans_with_both_ends_in_it(
        clock, monkeypatch):
    """Four steps of 10 s: before the session, across its start (as the
    benchmark starts it, after the step's commit), inside it, and cut
    by its stop.  ``traced/`` holds the third and the children of the
    others that lie wholly inside."""
    monkeypatch.setattr(program, "_annotation", Session)
    monkeypatch.setattr(Session, "recording", False)
    assert not any(k.startswith(program.TRACED) for k in program.stats())

    def step(after_commit=None, landed_after=2.0):
        with program.span("learn.step"):
            owed = Owed()
            with program.span("learn.dispatch"):
                clock.now += 1.0
                program.enqueued(owed)
            clock.now += landed_after
            owed.landed = True
            clock.now += 5.0 - landed_after
            with program.span("commit"):
                clock.now += 3.0
            if after_commit is not None:
                monkeypatch.setattr(Session, "recording", after_commit)
            with program.span("learn.update"):
                clock.now += 1.0

    step()
    step(after_commit=True)
    step()
    step(after_commit=False)
    s = program.stats()
    assert s["learn.step.n"] == s["commit.n"] == 4
    assert s["learn.handovers"] == s["learn.handovers_idle"] == 4
    traced = {k[len(program.TRACED):]: v for k, v in s.items()
              if k.startswith(program.TRACED)}
    # one whole step; the update of the step the start cut, the dispatch
    # and the commit of the step the stop cut
    assert {k: v for k, v in traced.items() if k.endswith(".n")} == {
        "learn.step.n": 1, "learn.dispatch.n": 2, "commit.n": 2,
        "learn.update.n": 2}
    assert traced["learn.step.total_s"] == traced["learn.step.max_s"] == 10.0
    assert traced["learn.step.exposed_s"] == 1.0 + 3.0 + 1.0
    assert traced["learn.step.unsure_s"] == 5.0     # dispatch to the commit
    assert traced["learn.step.self_s"] == 5.0
    assert traced["commit.exposed_s"] == 6.0 and traced["commit.unsure_s"] == 0
    assert (traced["learn.handovers"], traced["learn.handovers_idle"]) == (2, 2)
    for name in span_names(traced):
        assert traced[name + ".total_s"] <= s[name + ".total_s"]
    program.reset()
    assert program.stats() == {}


# ----------------------------------------------------------- path_stats
def test_path_stats_of_engine_empty(table, empty_engine):
    rabit_tpu.allreduce(np.ones(4, np.float32))
    rabit_tpu.checkpoint({"a": 1})
    s = engine_mod.get_engine().path_stats
    assert json.loads(json.dumps(s)) == s
    assert all(isinstance(v, (int, float)) for v in s.values())
    assert "host_ops" not in s and "device_ops" not in s
    assert s["allreduce.n"] == 1
    assert s["commit.n"] == s["commit.serialize.n"] == 1


def test_path_stats_of_engine_xla_keeps_its_own_counters(table):
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="xla")
    try:
        rabit_tpu.checkpoint({"a": 1})
        s = engine_mod.get_engine().path_stats
        assert s["device_ops"] == 0 and s["host_ops"] == 0
        assert s["init.n"] == 1 and s["commit.n"] == 1
        assert json.loads(json.dumps(s)) == s
        assert all(isinstance(v, (int, float)) for v in s.values())
        # telemetry is off: nothing of the spans is exported
        eng = engine_mod.get_engine()
        assert eng.stats() == {} and eng.events() == []
    finally:
        rabit_tpu.finalize()


# ------------------------------------------------------ the learner loop
def blobs(n=2048, nnz=8, d=64, k=4, seed=0):
    """Rows in k well-separated clusters, every row ``nnz`` entries."""
    from rabit_tpu.learn.data import SparseMat

    rng = np.random.default_rng(seed)
    band = d // k
    cluster = np.arange(n) % k
    findex = (cluster[:, None] * band
              + rng.integers(0, band, (n, nnz))).astype(np.int32)
    fvalue = (1.0 + rng.random((n, nnz))).astype(np.float32)
    return SparseMat(indptr=np.arange(0, n * nnz + 1, nnz),
                     findex=findex.reshape(-1), fvalue=fvalue.reshape(-1),
                     labels=np.zeros(n, np.float32), feat_dim=d)


@pytest.mark.parametrize("chain,per_version,children", [
    (4, 4, ("learn.dispatch", "learn.fetch", "learn.update", "commit")),
    # on a host engine the stats are computed lazily inside the
    # allreduce, which then holds the dispatch and the fetch
    (0, 1, ("allreduce", "learn.update", "commit")),
])
def test_kmeans_run_leaves_the_spans_of_its_layers(
        table, empty_engine, chain, per_version, children):
    from rabit_tpu.learn import kmeans

    program.reset()                     # the fixture's `init` span
    kmeans.run(blobs(), 4, 8, device_chain=chain)
    s = engine_mod.get_engine().path_stats
    # the chained loop hands its first chain over before its first step
    assert span_names(s) == KMEANS_SPANS | (
        {"stage.compile"} if chain else set())
    versions = rabit_tpu.version_number()
    assert s["learn.versions"] == s["learn.step.n"] == versions
    assert s["learn.iterations"] == per_version * versions == 8
    # a resident shard: every row of every iteration, none over the link
    assert s["learn.rows"] % s["learn.iterations"] == 0 < s["learn.rows"]
    assert not {k for k in s if k.startswith(("stream.", "learn.stream"))}
    assert s["commit.n"] == versions
    for name in STAGE:
        assert s[name + ".n"] == 1
    # every counter of the table has a reader (PERF.md section 3)
    assert {k for k in s if "." in k and k.split(".")[-1] not in
            COLUMNS} <= {
        "learn.iterations", "learn.versions", "learn.rows", "learn.ahead",
        "learn.ahead_discarded", "learn.device_updates",
        "learn.handovers", "learn.handovers_idle",
        "learn.waits", "learn.waits_idle",
        "allreduce.programs_built",
        "compile.seconds", "compile.misses", "compile.hits",
        "stage.clamped"}
    assert s["stage.clamped"] == 0
    # the loop's 1 + the feature-width agreement before it
    assert s["allreduce.n"] == (1 if chain else 1 + versions)
    covered = sum(s[c + ".total_s"] for c in children)
    assert covered >= 0.9 * s["learn.step.total_s"]
    assert covered <= s["learn.step.total_s"]


@pytest.mark.parametrize("max_iter,stop_at,order", [
    # 4 + 4 + 2 iterations: a chain is enqueued before the one ahead of
    # it is fetched and committed, and nothing after the last
    (10, None, ["enqueue 4", "enqueue 4", "commit 1", "enqueue 2",
                "commit 2", "commit 3"]),
    # a caller that leaves from inside a commit (the benchmark's window
    # does) finds whole versions counted, the queued chain not among them
    (100, 2, ["enqueue 4", "enqueue 4", "commit 1", "enqueue 4",
              "commit 2"]),
])
def test_chained_loop_enqueues_a_chain_ahead_of_its_fetch(
        table, empty_engine, monkeypatch, max_iter, stop_at, order):
    from rabit_tpu.learn import kmeans

    seen, under = [], []
    iterate, commit = kmeans.device_iterations, rabit_tpu.checkpoint

    def device_iterations(cent, x, valid, iters, **kw):
        seen.append(f"enqueue {iters}")
        under.append(open_span())
        return iterate(cent, x, valid, iters, **kw)

    def checkpoint(model):
        commit(model)
        seen.append(f"commit {rabit_tpu.version_number()}")
        if rabit_tpu.version_number() == stop_at:
            raise KeyboardInterrupt

    monkeypatch.setattr(kmeans, "device_iterations", device_iterations)
    monkeypatch.setattr(rabit_tpu, "checkpoint", checkpoint)
    data = blobs()
    if stop_at:
        with pytest.raises(KeyboardInterrupt):
            kmeans.run(data, 4, max_iter, device_chain=4)
    else:
        chained = kmeans.run(data, 4, max_iter, device_chain=4)
    assert seen == order
    s = program.stats()
    versions = rabit_tpu.version_number()
    assert s["learn.versions"] == s["learn.fetch.n"] == versions
    done = [int(e.split()[1]) for e in order if e.startswith("enqueue")]
    assert s["learn.iterations"] == sum(done[:versions])
    # the job's first hand-over, which compiles, is set-up's: no step
    # holds it
    assert under == ["stage.compile"] + ["learn.dispatch"] * (len(done) - 1)
    assert s["stage.compile.n"] == 1
    assert s["learn.dispatch.n"] == len(done) - 1
    if not stop_at:
        # the same centroids as the loop that goes through the host
        # after every iteration
        rabit_tpu.finalize()
        rabit_tpu.init(rabit_engine="empty")
        plain = kmeans.run(data, 4, max_iter)
        np.testing.assert_allclose(chained.centroids, plain.centroids,
                                   rtol=1e-4, atol=1e-5)


# ---------------------- every learner's fetch split, exposed <= total
def run_kmeans(monkeypatch, chain):
    from rabit_tpu.learn import kmeans

    kmeans.run(blobs(), 4, 8, device_chain=chain)
    return "learn.fetch"


def run_boosting(monkeypatch):
    from rabit_tpu.learn import boosting

    # shapes no other test's job has: the programs of a shape are kept
    # for the process, and tests/perfbench times their compile
    rng = np.random.default_rng(35)
    X = rng.standard_normal((1300, 5)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.standard_normal(1300) > 0).astype(np.float32)
    monkeypatch.setattr(boosting, "on_tpu", lambda: True)   # _DeviceShard
    boosting.train(X, y, num_round=3, max_depth=3, nbin=16,
                   min_child_weight=40.0, use_pallas=False)
    assert program.stats()["gbdt.levels_device_scan"] > 0
    return "gbdt.level.fetch"


def run_lbfgs(monkeypatch):
    from rabit_tpu.learn import LinearObjFunction

    rng = np.random.default_rng(5)
    idx = rng.integers(0, 300, (600, 6)).astype(np.int32)
    val = (rng.integers(1, 9, (600, 6)) / 8.0).astype(np.float32)
    obj = LinearObjFunction()
    obj.load_arrays(idx, val, (rng.random(600) < 0.4).astype(np.float32), 300)
    for name, value in (("reg_L1", 0.5), ("silent", 1),
                        ("max_lbfgs_iter", 4)):
        obj.set_param(name, str(value))
    obj.lbfgs.run()
    return "learn.fetch"


@pytest.mark.parametrize("learner,runs_ahead", [
    (lambda m: run_kmeans(m, 4), True),
    (lambda m: run_kmeans(m, 0), False),
    (run_boosting, False),
    (run_lbfgs, False),
], ids=["kmeans-chained", "kmeans-periter", "boosting-device", "lbfgs"])
def test_every_fetch_is_a_wait_and_a_copy_and_exposed_fits_in_total(
        table, empty_engine, monkeypatch, learner, runs_ahead):
    program.reset()
    outer = learner(monkeypatch)
    s = program.stats()
    fetches = s[outer + ".n"]
    assert fetches >= s["learn.versions"] >= 2
    assert s["learn.fetch.wait.n"] == s["learn.fetch.copy.n"] == fetches
    inside = s["learn.fetch.wait.total_s"] + s["learn.fetch.copy.total_s"]
    assert 0 < inside <= s[outer + ".total_s"]
    for name in span_names(s):
        assert 0 <= s[name + ".exposed_s"] <= s[name + ".total_s"] + 1e-9, name
        assert 0 <= s[name + ".self_s"] <= s[name + ".total_s"] + 1e-9, name
        assert 0 <= s[name + ".unsure_s"] <= s[name + ".total_s"] \
            - s[name + ".exposed_s"] + 1e-9, name
    # every program the loop handed over inside a step was counted, and
    # no session recorded
    assert 0 <= s.get("learn.handovers_idle", 0) <= s["learn.handovers"]
    assert s["learn.handovers"] >= s["learn.versions"] - 1
    assert not any(k.startswith(program.TRACED) for k in s)
    # the host's copy and arithmetic between two programs began with
    # nothing in flight wherever the loop runs nothing ahead
    if not runs_ahead:
        assert s["learn.step.exposed_s"] > 0
        assert s["learn.fetch.copy.exposed_s"] > 0
    assert s["learn.fetch.wait.exposed_s"] <= s["learn.fetch.wait.total_s"]


# ------------------------------- the distributed loop, a commit ahead
class DevicePlane:
    """The per-iteration loop's device-plane arm switched on in one
    process on engine `empty`: ``is_device_plane`` says yes, and the
    three calls the order is made of — ``shard_stats_device``,
    ``rabit_tpu.allreduce`` of its result, ``rabit_tpu.checkpoint`` —
    and ``prepare_shard`` write what they did into ``seen`` (the shards
    staged also into ``staged``).  A stats
    result travels in a ``Queued`` that only the allreduce opens, so a
    result that was dropped unread shows as a dispatch no allreduce
    follows.  ``in_commit[v]`` runs inside commit v, after the real one.
    ``given[v]`` is the centroid array version v's stats program was
    handed (a copy), ``committed[v]`` the centroids commit v wrote;
    ``spans`` holds every span's opening and closing, in order.
    """

    class Queued:
        def __init__(self, version, array):
            self.version, self.array = version, array

    def __init__(self, monkeypatch, base=0, in_commit=None, budget=None):
        from rabit_tpu.learn import kmeans

        self.seen, self.staged, self.epoch, self.under = [], [], 0, []
        self.given, self.committed, self.spans = {}, {}, []
        in_commit = in_commit or {}
        stats, reduce, commit, stage = (
            kmeans.shard_stats_device, rabit_tpu.allreduce,
            rabit_tpu.checkpoint, kmeans.prepare_shard)
        enter, leave_ = program.span.__enter__, program.span.__exit__
        spans = self.spans

        def span_enter(span):
            spans.append(("open", span.name))
            return enter(span)

        def span_exit(span, *exc):
            spans.append(("close", span.name))
            return leave_(span, *exc)

        def shard_stats_device(centroids, shard):
            # the version whose stats these are: one past the versions
            # this run() has updated the centroids of
            version = base + program.stats().get(
                "learn.device_updates", 0) + 1
            self.seen.append(f"dispatch {version}")
            self.under.append(open_span())
            self.given[version] = np.array(centroids)
            return self.Queued(version, stats(centroids, shard))

        def allreduce(data, *a, **kw):
            if not isinstance(data, self.Queued):
                return reduce(data, *a, **kw)   # the feature-width one
            with program.span("allreduce"):
                self.seen.append(f"allreduce {data.version}")
                return data.array               # world 1: the sum is it

        def checkpoint(model):
            commit(model)
            version = rabit_tpu.version_number()
            self.seen.append(f"commit {version}")
            self.committed[version] = np.array(model.centroids)
            in_commit.get(version, lambda: None)()

        def prepare_shard(*a, **kw):
            self.seen.append("stage")
            if budget is not None:
                kw["budget"] = budget
            self.staged.append(stage(*a, **kw))
            return self.staged[-1]

        monkeypatch.setattr(engine_mod, "is_device_plane", lambda: True)
        monkeypatch.setattr(kmeans, "shard_stats_device", shard_stats_device)
        monkeypatch.setattr(kmeans, "prepare_shard", prepare_shard)
        monkeypatch.setattr(rabit_tpu, "allreduce", allreduce)
        monkeypatch.setattr(rabit_tpu, "checkpoint", checkpoint)
        monkeypatch.setattr(rabit_tpu, "device_epoch", lambda: self.epoch)
        monkeypatch.setattr(program.span, "__enter__", span_enter)
        monkeypatch.setattr(program.span, "__exit__", span_exit)

    def move_epoch(self):
        self.epoch += 1

    def steps(self):
        """The spans' openings and closings, a list a ``learn.step``."""
        steps = []
        for event in self.spans:
            if event == ("open", "learn.step"):
                steps.append([])
            elif steps:
                steps[-1].append(event)
        return steps


def leave():
    raise KeyboardInterrupt


def version_order(first, last, final):
    """Versions ``first``..``last`` with the stats program of each
    enqueued before the commit of the one before; ``final``: the job
    ends at ``last`` (nothing is enqueued for a version after it)."""
    order = ["stage", f"dispatch {first}"]
    for v in range(first, last + 1):
        order.append(f"allreduce {v}")
        if v < last or not final:
            order.append(f"dispatch {v + 1}")
        order.append(f"commit {v}")
    return order


@pytest.mark.parametrize("case", ["full", "resume", "leave", "epoch"])
def test_distributed_loop_enqueues_the_stats_a_commit_ahead(
        table, empty_engine, monkeypatch, case):
    from rabit_tpu.learn import kmeans

    data = blobs()
    if case == "resume":
        kmeans.run(data, 4, 2)          # versions 1 and 2, the host arm
        program.reset()
    plane = DevicePlane(
        monkeypatch, base=2 if case == "resume" else 0,
        in_commit={"leave": {2: leave},
                   "epoch": {2: lambda: plane.move_epoch()}}.get(case))
    if case == "leave":
        # a caller that leaves from inside a commit (the benchmark's
        # window does): whole versions counted, the queued program
        # abandoned
        with pytest.raises(KeyboardInterrupt):
            kmeans.run(data, 4, 100)
        want, versions, ahead = version_order(1, 2, final=False), 2, 1
    elif case == "epoch":
        # the device plane re-formed inside commit 2: the result queued
        # for version 3 died with the old epoch's arrays and is dropped
        # unread, the shard staged anew, version 3 dispatched in place
        kmeans.run(data, 4, 5)
        want = version_order(1, 2, final=False) + version_order(3, 5, True)
        versions, ahead = 5, 3          # versions 2, 4 and 5
    else:
        # the first version after load_checkpoint is dispatched before
        # the loop, a fresh start or a resume; nothing after the last
        first = 3 if case == "resume" else 1
        kmeans.run(data, 4, 5)
        want = version_order(first, 5, final=True)
        versions, ahead = 5 - first + 1, 5 - first
    assert plane.seen == want
    # the next version's collective never before this version's commit
    reduced = [int(e.split()[1]) for e in plane.seen
               if e.startswith("allreduce")]
    for v in reduced[1:]:
        assert (plane.seen.index(f"commit {v - 1}")
                < plane.seen.index(f"allreduce {v}"))
    s = program.stats()
    assert s["learn.versions"] == s["learn.iterations"] == versions
    assert s["learn.step.n"] == s["learn.fetch.n"] == versions
    assert s["learn.device_updates"] == s["learn.update.n"] == versions
    assert s.get("learn.ahead", 0) == ahead
    assert s.get("learn.ahead_discarded", 0) == (1 if case == "epoch" else 0)
    dispatched = sum(e.startswith("dispatch") for e in plane.seen)
    # the job's first hand-over, which compiles, is set-up's, and is
    # ahead of no commit
    assert plane.under == ["stage.compile"] + ["learn.dispatch"] * (
        dispatched - 1)
    assert s["stage.compile.n"] == 1
    assert s["learn.dispatch.n"] == dispatched - 1
    assert dispatched == sum(
        e.startswith("allreduce") for e in plane.seen) + (
        case in ("leave", "epoch"))
    assert rabit_tpu.version_number() == (2 if case == "leave" else 5)


TIERS = [
    ("dense", "float32", None),         # densified float32 blocks
    ("dense16", "bfloat16", 0),         # half-width rows
    ("ell", "float32", 0),              # blocked ELL, the scan path
]


def run_device_arm(monkeypatch, tier, dtype, budget, iters=6, reform_in=None):
    """``kmeans.run`` through the device-plane arm on ``blobs()``, the
    shard in ``tier``, the epoch moved inside commit ``reform_in``;
    returns the fixture and the model."""
    from rabit_tpu.learn import kmeans

    with monkeypatch.context() as patched:
        plane = DevicePlane(patched, budget=budget, in_commit={
            reform_in: lambda: plane.move_epoch()})
        model = kmeans.run(blobs(), 4, iters, compute_dtype=dtype)
    assert {s[0] for s in plane.staged} == {tier}
    return plane, model


def fresh_job():
    rabit_tpu.finalize()
    program.reset()
    rabit_tpu.init(rabit_engine="empty")


@pytest.mark.parametrize("tier,dtype,budget", TIERS)
@pytest.mark.parametrize("epoch_moves", [False, True], ids=["", "reform"])
def test_distributed_loop_a_commit_ahead_gives_the_host_arms_centroids(
        table, empty_engine, monkeypatch, tier, dtype, budget, epoch_moves):
    """The same iterations whichever arm runs them and whether or not a
    queued result is dropped on the way.  The two arms are two float32
    implementations of a division and a norm (numpy's, XLA's), so they
    agree to a rounding; a run of the device arm whose epoch moved agrees
    with its own undisturbed run exactly."""
    from rabit_tpu.learn import kmeans

    plane, ahead = run_device_arm(
        monkeypatch, tier, dtype, budget, reform_in=3 if epoch_moves else None)
    assert len(plane.staged) == (2 if epoch_moves else 1)
    assert plane.epoch == int(epoch_moves)
    assert program.stats()["learn.ahead"] == (4 if epoch_moves else 5)
    if epoch_moves:
        fresh_job()
        _, undisturbed = run_device_arm(monkeypatch, tier, dtype, budget)
        np.testing.assert_array_equal(ahead.centroids, undisturbed.centroids)
    fresh_job()
    stage = kmeans.prepare_shard
    if budget is not None:
        monkeypatch.setattr(kmeans, "prepare_shard", lambda *a, **kw: stage(
            *a, **{**kw, "budget": budget}))
    plain = kmeans.run(blobs(), 4, 6, compute_dtype=dtype)
    np.testing.assert_allclose(ahead.centroids, plain.centroids, rtol=1e-6)


@pytest.mark.parametrize("tier,dtype,budget", TIERS)
def test_device_arm_hands_three_programs_over_before_it_waits(
        table, empty_engine, monkeypatch, tier, dtype, budget):
    """In a step of the device arm the allreduce, the update program
    and the next version's stats program are all handed over before the
    host waits for anything, and every version's centroids are computed
    on the device."""
    plane, _ = run_device_arm(monkeypatch, tier, dtype, budget, iters=5)
    steps = plane.steps()
    assert len(steps) == 5
    for v, step in enumerate(steps, 1):
        wait = step.index(("open", "learn.fetch.wait"))
        before = step[:wait]
        assert ("close", "allreduce") in before
        assert ("close", "learn.update") in before
        # the last version enqueues nothing after itself
        assert (("close", "learn.dispatch") in before) == (v < 5), v
        assert (before.index(("close", "allreduce"))
                < before.index(("open", "learn.update")))
        if v < 5:
            assert (before.index(("close", "learn.update"))
                    < before.index(("open", "learn.dispatch")))
        # what is left of the step after the hand-overs: the fetch,
        # then the commit under the kernel queued ahead
        after = [name for kind, name in step[wait:] if kind == "open"]
        assert after[:2] == ["learn.fetch.wait", "learn.fetch.copy"]
        assert "commit" in after and "learn.dispatch" not in after
    s = program.stats()
    assert s["learn.device_updates"] == s["learn.versions"] == 5
    assert s["learn.fetch.wait.n"] == 5


@pytest.mark.parametrize("tier,dtype,budget", TIERS)
def test_device_arm_commits_what_the_next_stats_program_was_given(
        table, empty_engine, monkeypatch, tier, dtype, budget):
    """The committed centroids of every version are, bit for bit, the
    array the next version's stats program was handed: the host binds
    what it fetched and recomputes nothing, so a resumed job continues
    from the state the undisturbed one had."""
    plane, model = run_device_arm(monkeypatch, tier, dtype, budget)
    assert sorted(plane.committed) == [1, 2, 3, 4, 5, 6]
    assert sorted(plane.given) == [1, 2, 3, 4, 5, 6]
    for v in range(1, 6):
        assert plane.committed[v].dtype == np.float32
        np.testing.assert_array_equal(plane.committed[v], plane.given[v + 1])
    np.testing.assert_array_equal(plane.committed[6], model.centroids)
    # unit rows, by the model's own rule
    np.testing.assert_allclose(
        np.linalg.norm(model.centroids, axis=1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("tier,dtype,budget", TIERS)
def test_device_arm_with_the_epoch_moved_in_a_commit_ends_where_it_would(
        table, empty_engine, monkeypatch, tier, dtype, budget):
    """The device plane re-formed inside commit 3: the result queued for
    version 4 and the device centroids it was computed on are dropped,
    the loop restarts from the host's copy of the committed version 3,
    which is bit for bit what the device held, and every later version
    is the undisturbed run's exactly."""
    _, undisturbed = run_device_arm(monkeypatch, tier, dtype, budget)
    fresh_job()
    plane, moved = run_device_arm(monkeypatch, tier, dtype, budget,
                                  reform_in=3)
    s = program.stats()
    assert s["learn.ahead_discarded"] == 1
    assert s["learn.device_updates"] == s["learn.versions"] == 6
    # version 4 was dispatched twice, the second time from the host's
    # copy of commit 3 on the shard staged anew
    assert plane.seen.count("dispatch 4") == 2
    assert plane.seen.count("stage") == 2
    np.testing.assert_array_equal(plane.given[4], plane.committed[3])
    np.testing.assert_array_equal(moved.centroids, undisturbed.centroids)


@pytest.mark.parametrize("tier,dtype,budget", TIERS)
def test_device_arm_compiles_its_update_program_in_set_up(
        table, empty_engine, monkeypatch, tier, dtype, budget):
    """The update program's first hand-over is set-up's, beside the
    job's first stats program: no step of the loop holds a compile, of
    either program (an operand committed to the device where the first
    one was not would compile the stats program a second time)."""
    from rabit_tpu.learn import kmeans
    from rabit_tpu.utils import compile_cache

    assert compile_cache.count_compiles() is not None
    kmeans._STEP_CACHE.clear()          # programs of an earlier test
    under, count = [], program.count

    def seen_count(name, k=1):
        if name == "compile.misses" and k > 0:   # a request to compile
            span = program._thread.state.top
            while span._parent is not None:
                span = span._parent
            under.append(span.name)
        count(name, k)

    monkeypatch.setattr(program, "count", seen_count)
    with monkeypatch.context() as patched:
        DevicePlane(patched, budget=budget)
        kmeans.run(blobs(n=2048 + 512, seed=37), 4, 4, compute_dtype=dtype)
    # the update program and the stats program; staging's own besides,
    # and no step's
    assert under.count("stage.compile") >= 2
    assert set(under) <= {"stage.compile", "stage.put"}
    s = program.stats()
    assert s["stage.compile.n"] == 1 and s["learn.step.n"] == 4


# -------------------------------------------------- the robust commit
def test_world2_pyrobust_commit_has_its_rounds_once_a_commit(tmp_path):
    """Two processes under the tracker, telemetry on: every commit is
    one barrier round and one acknowledgement, in the table
    and, with parent and version, in ``Engine.events()``."""
    from rabit_tpu.tracker.launch_local import launch

    commits = 3
    code = launch(2, [sys.executable, "tests/workers/span_worker.py",
                      str(commits)],
                  extra_env={"RABIT_ENGINE": "pyrobust", "RABIT_OBS": "1",
                             "SPAN_OUT": str(tmp_path)})
    assert code == 0
    for rank in range(2):
        out = json.loads((tmp_path / f"rank{rank}.json").read_text())
        s = out["path_stats"]
        for name in ("commit", "commit.serialize", "commit.barrier",
                     "commit.ack"):
            assert s[name + ".n"] == commits, name
        inside = sum(s[f"commit.{c}.total_s"] for c in
                     ("serialize", "barrier", "ack"))
        assert 0.5 * s["commit.total_s"] <= inside <= s["commit.total_s"]
        rounds = [e for e in out["spans"]
                  if e["kind"] in ("commit.barrier", "commit.ack")]
        assert len(rounds) == 2 * commits
        assert all(e["parent"] == "commit" and e["rank"] == rank
                   for e in rounds)
        assert sorted(e["version"] for e in rounds) == sorted(
            2 * list(range(1, commits + 1)))


# ----------------------------------------------- sink 3: rabit_obs on
def test_with_rabit_obs_the_spans_are_events_and_histograms(table):
    from rabit_tpu.learn import kmeans

    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="xla", rabit_obs="1")
    try:
        eng = engine_mod.get_engine()
        kmeans.run(blobs(), 4, 8, device_chain=4)
        events = [e for e in eng.events() if e["name"] == "span"]
        table_now = eng.path_stats
        by_kind = {}
        for e in events:
            by_kind.setdefault(e["kind"], []).append(e)
        # `init` closed around the engine's own init, before the
        # engine had telemetry to attach: the table alone has it
        assert set(by_kind) == KMEANS_SPANS | {"stage.compile"}
        assert table_now["init.n"] == 1
        for kind, evs in by_kind.items():
            assert len(evs) == table_now[kind + ".n"], kind
            assert all(e["dur"] >= 0 and e["rank"] == 0 for e in evs)
        steps = by_kind["learn.step"]
        assert [e["version"] for e in steps] == [1, 2]
        assert all("parent" not in e for e in steps)
        for kind in ("learn.fetch", "learn.update", "commit"):
            assert [e["parent"] for e in by_kind[kind]] == ["learn.step"] * 2
        # two chains: the first handed over before the loop, as set-up
        assert [e["parent"] for e in by_kind["learn.dispatch"]] == [
            "learn.step"]
        assert ["parent" in e for e in by_kind["stage.compile"]] == [False]
        # a child inherits the version of the unit of work it is in
        assert [e["version"] for e in by_kind["learn.fetch"]] == [1, 2]
        assert [(e["parent"], e["version"])
                for e in by_kind["commit.serialize"]] == [("commit", 1),
                                                          ("commit", 2)]
        hist = eng.stats()["histograms"]
        for kind in by_kind:
            assert hist[f"span.{kind}.seconds"]["count"] == len(by_kind[kind])
        # the merged timeline stays renderable
        assert len(obs.chrome_trace(events)) == len(events)
    finally:
        rabit_tpu.finalize()
    # the next engine of the process has telemetry off: nothing follows
    rabit_tpu.init(rabit_engine="xla")
    try:
        rabit_tpu.checkpoint({"a": 1})
        assert engine_mod.get_engine().events() == []
    finally:
        rabit_tpu.finalize()


class StubEngine:
    """What ``program.attach`` needs of an engine with telemetry on."""
    rank = 0

    def __init__(self):
        self._m, self._t = obs.Metrics(), obs.EventTrace(64)

    def metrics(self):
        return self._m

    def event_trace(self):
        return self._t


def test_with_rabit_obs_the_span_event_carries_self_and_exposed(clock):
    stub = StubEngine()
    program.attach(stub)
    try:
        with program.span("learn.step", version=4):
            clock.now += 1.0
            with program.span("learn.dispatch"):
                clock.now += 2.0
                program.enqueued(Owed())    # collected at once: landed
            owed = Owed()
            program.enqueued(owed)
            with program.span("learn.fetch.wait"):
                clock.now += 4.0
                owed.landed = True
    finally:
        program.detach()
    events = {e["kind"]: e for e in stub.event_trace().events()}
    step = events["learn.step"]
    assert (step["dur"], step["self"], step["exposed"]) == (7.0, 1.0, 3.0)
    wait = events["learn.fetch.wait"]
    assert (wait["dur"], wait["self"], wait["exposed"]) == (4.0, 4.0, 0.0)
    assert wait["parent"] == "learn.step" and wait["version"] == 4
    assert events["learn.dispatch"]["exposed"] == 2.0
    # the wait began with a result owed and ended with it landed
    assert (wait["unsure"], step["unsure"]) == (4.0, 4.0)
    assert events["learn.dispatch"]["unsure"] == 0.0


def test_a_span_open_across_detach_leaves_no_stale_nesting(table):
    """``finalize`` inside a span, then the next engine of the process:
    the span that was open is exported nowhere and is nobody's parent."""
    first, second = StubEngine(), StubEngine()
    program.attach(first)
    with program.span("old.outer", version=1):
        program.detach()
    program.attach(second)
    try:
        with program.span("new.work"):
            pass
    finally:
        program.detach()
    assert program.stats()["old.outer.n"] == 1
    assert first.event_trace().events() == []
    (event,) = second.event_trace().events()
    assert event["kind"] == "new.work"
    assert "parent" not in event and "version" not in event


def test_a_span_entered_before_attach_is_in_the_table_alone(table):
    sink = StubEngine()
    with program.span("early"):
        program.attach(sink)
        try:
            with program.span("late"):
                pass
        finally:
            program.detach()
    assert {e["kind"] for e in sink.event_trace().events()} == {"late"}
    assert program.stats()["early.n"] == program.stats()["late.n"] == 1


def test_a_span_on_another_thread_has_its_own_nesting(table):
    """Nesting is per thread: a span opened on a helper thread is not
    the child of what the main thread has open."""
    import threading

    stub = StubEngine()
    program.attach(stub)
    try:
        with program.span("main.work", version=9):
            t = threading.Thread(target=lambda: program.span(
                "helper.work").__enter__().__exit__(None, None, None))
            t.start()
            t.join(10)
            assert not t.is_alive()
            with program.span("main.child"):
                pass
    finally:
        program.detach()
    events = {e["kind"]: e for e in stub.event_trace().events()}
    assert "parent" not in events["helper.work"]
    assert "version" not in events["helper.work"]
    assert events["main.child"]["parent"] == "main.work"
    assert events["main.child"]["version"] == 9


# -------------------------------------- sink 2: the profiler's own trace
def test_under_a_profiler_session_the_spans_are_in_the_xplane(
        table, empty_engine, tmp_path):
    import jax

    sys.path.insert(0, ROOT)
    from perfbench import trace_reduce

    assert not jax.profiler.TraceAnnotation.is_enabled()
    with jax.profiler.trace(str(tmp_path)):
        with program.span("learn.step", version=5):
            rabit_tpu.checkpoint({"a": 1})
    profile = jax.profiler.ProfileData.from_file(
        trace_reduce.find_xplane(str(tmp_path)))
    spans = trace_reduce.host_spans(profile, program.PREFIX)
    assert {"learn.step", "commit", "commit.serialize"} <= set(spans)
    (a, b), = spans["learn.step"]
    (ca, cb), = spans["commit"]
    assert a <= ca and cb <= b          # on one clock, nested
    versions = [dict(e.stats).get("version")
                for plane in profile.planes for line in plane.lines
                for e in line.events
                if e.name == program.PREFIX + "learn.step"]
    assert versions == [5]


# ------------------------------------------------------- compile counters
def test_one_compile_clock_a_process_feeds_the_counters(table):
    import jax
    import jax.numpy as jnp

    from rabit_tpu.utils import compile_cache

    clock = compile_cache.count_compiles()
    assert clock is not None and compile_cache.count_compiles() is clock
    clock.take()
    jax.jit(lambda x: x * 3.25 + 1.5)(jnp.arange(7.0)).block_until_ready()
    s = program.stats()
    assert s["compile.seconds"] > 0
    taken = clock.take()
    assert taken["seconds"] == pytest.approx(s["compile.seconds"], abs=2e-3)
    assert taken["misses"] == s.get("compile.misses", 0)
    assert taken["hits"] == s.get("compile.hits", 0)
    assert clock.take() == {"seconds": 0.0, "misses": 0, "hits": 0}


# ------------------------------------------------------ tools/span_trace
def test_span_trace_gives_idle_time_to_the_innermost_span():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    sys.path.insert(0, ROOT)
    import span_trace
    from perfbench.trace_reduce import _overlap

    thread = [("learn.step", 0, 100), ("learn.dispatch", 5, 20),
              ("commit", 60, 95), ("commit.barrier", 62, 80),
              ("commit.ack", 80, 94),
              # the step the end of the trace cut off: no parent event
              ("learn.dispatch", 105, 120)]
    own = span_trace.self_intervals([thread, [("other", 0, 10)]])
    assert own["learn.step"] == [[0, 5], [20, 60], [95, 100]]
    assert own["commit"] == [[60, 62], [94, 95]]
    assert own["learn.dispatch"] == [[5, 20], [105, 120]]
    assert own["commit.barrier"] == [[62, 80]]
    idle = [[10, 30], [70, 110]]
    got = {name: _overlap(idle, cover) for name, cover in own.items()}
    assert got == {"learn.step": 15, "learn.dispatch": 15, "commit": 1,
                   "commit.barrier": 10, "commit.ack": 14, "other": 0}
    # every idle instant under a span of this thread is counted once
    assert sum(got.values()) == 55


def test_span_trace_puts_the_programs_exposed_seconds_beside_the_idle():
    """What the table gained over the window, inclusive as it is, and
    less its children's where the trace shows whose children they are."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    sys.path.insert(0, ROOT)
    import span_trace

    thread = [("learn.step", 0, 100), ("learn.dispatch", 5, 20),
              ("learn.fetch", 20, 60), ("learn.fetch.wait", 21, 50),
              ("learn.fetch.copy", 50, 59), ("commit", 60, 95),
              ("lbfgs.eval", 100, 140), ("learn.dispatch", 101, 110),
              # the step the end of the trace cut off: no parent event
              ("learn.fetch", 150, 160), ("learn.fetch.copy", 151, 159)]
    parents = span_trace.parents_of([thread])
    assert parents["learn.step"] == {None}
    assert parents["learn.fetch"] == {"learn.step", None}
    assert parents["learn.fetch.copy"] == {"learn.fetch"}
    assert parents["learn.dispatch"] == {"learn.step", "lbfgs.eval"}

    def table(scale):
        return {f"{name}.{col}": scale * value for name, row in {
            "learn.step": (10, 50.0, 4.0, 9.0, 20.0),
            "learn.fetch": (10, 40.0, 2.0, 6.0, 20.0),
            "learn.fetch.wait": (10, 29.0, 29.0, 0.0, 20.0),
            "learn.fetch.copy": (10, 9.0, 9.0, 5.0, 0.0),
            "learn.dispatch": (20, 15.0, 15.0, 2.0, 0.0),
            "commit": (10, 3.5, 3.5, 0.5, 0.0)}.items()
            for col, value in zip(("n", "total_s", "self_s", "exposed_s",
                                   "unsure_s"), row)}

    gained = span_trace.window_table(table(1), table(3), parents)
    assert gained["learn.fetch"] == {
        "n": 20, "total_s": 80.0, "self_s": 4.0, "exposed_s": 12.0,
        "unsure_s": 40.0,
        "exposed_own_s": 2.0}                   # 12 - wait's 0 - copy's 10
    assert gained["learn.fetch.copy"]["exposed_own_s"] == 10.0
    # a child seen under two parents: whose seconds they were is unknown
    assert "exposed_own_s" not in gained["learn.step"]
    # the table of a recorded session, from its own keys alone
    session = {program.TRACED + k: v for k, v in table(2).items()}
    assert span_trace.window_table({}, {**table(5), **session}, parents,
                                   program.TRACED) == \
        span_trace.window_table(table(1), table(3), parents)


def test_span_trace_aligns_the_clocks_and_finds_the_launch_lag():
    """Times in ms; the device's clock is 1.0 early and the fastest
    launch takes 0.1.  A: handed over at 10 to an idle device, starts at
    10.2; B: handed over at 11, queued behind A; C: a program with no
    hand-over of its own in the trace; D: handed over at 30, idle."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    sys.path.insert(0, ROOT)
    import span_trace

    ms = 1e6
    enqueues = [(10 * ms, 7), (11 * ms, 8), (30 * ms, 10)]
    ran = [(9.2 * ms, 14 * ms, 7), (14 * ms, 19 * ms, 8),
           (19 * ms, 19.5 * ms, 9), (29.1 * ms, 34 * ms, 10)]
    # each program against the hand-over of its own run id: C's lies
    # before the trace, and a hand-over whose program has not started
    # when the trace stops pairs with nothing
    shift, pairs = span_trace.clock_shift(enqueues + [(40 * ms, 11)], ran)
    assert (round(shift / ms, 6), pairs) == (0.9, 3)
    # no ids: in order, if hand-overs and programs are as many
    bare = [(h, None) for h, _run in enqueues]
    shift, pairs = span_trace.clock_shift(
        bare, [(a, b, None) for a, b, _run in ran[:2] + ran[3:]])
    assert (round(shift / ms, 6), pairs) == (0.9, 3)
    assert span_trace.clock_shift(bare, ran) == (0.0, 0)
    assert span_trace.clock_shift([], ran) == (0.0, 0)
    ran = [(a, b) for a, b, _run in ran]
    moved = [(a + shift, b + shift) for a, b in ran]
    busy = [[moved[0][0], moved[2][1]], list(moved[3])]
    idle = [[busy[0][1], busy[1][0]]]
    # the first hand-over inside an idle interval ends it; the one that
    # follows queues behind it, one made with the device busy is no
    # launch, and nothing precedes the window's first program
    assert [(h / ms, round(x / ms, 6)) for h, x in span_trace.launch_lags(
        [10 * ms, 11 * ms, 29.5 * ms, 29.8 * ms], idle)] == [(29.5, 0.5)]
    assert span_trace.launch_lags([11 * ms], idle) == []
    # a wait the device's last operation ended in, one that ended with
    # the device busy, one that began with it idle
    waits = [[19 * ms, 21 * ms], [31 * ms, 33 * ms], [25 * ms, 26 * ms]]
    assert [(b / ms, round(x / ms, 6)) for b, x in span_trace.notice_lags(
        waits, busy)] == [(20.0, 0.6)]


def test_span_trace_says_how_far_outside_its_bounds_a_row_lies():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import span_trace

    row = {"n": 10, "total_s": 9.0, "self_s": 1.0}
    got = span_trace.verdicts({
        "lag": {"notice_us_p95": 1000.0},
        "program_window": {
            "learn.step": {**row, "exposed_s": 1.0, "unsure_s": 2.0},
            "commit": {**row, "exposed_s": 0.5, "unsure_s": 0.0},
            "gbdt.split": {**row, "exposed_s": 0.25, "unsure_s": 0.0},
            "learn.fetch.wait": {**row, "exposed_s": 0.0, "unsure_s": 0.0}},
        "idle_by_enclosing_span": {"learn.step": 3.009, "commit": 0.25,
                                   "gbdt.split": 0.75,
                                   "learn.fetch.wait": 0.004},
        # the split's annotations are a quarter of a second wider than
        # the table's intervals
        "seconds_by_span": {"learn.step": 8.9, "gbdt.split": 9.25},
        "handovers_idle_by_enclosing_span": {"learn.step": 10},
        "launch_s_by_enclosing_span": {"learn.step": 0.005},
        "waits_idle_by_enclosing_span": {"learn.step": 5,
                                         "learn.fetch.wait": 5}})
    assert got["learn.step"]["upper_s"] == pytest.approx(3.010)
    assert got["learn.step"]["outside_s"] == 0.0
    assert got["commit"]["outside_s"] == -0.25      # under the lower bound
    assert got["gbdt.split"]["outside_s"] == 0.25   # over the upper one
    assert got["learn.fetch.wait"] == {
        "idle_s": 0.004, "lower_s": 0.0, "upper_s": 0.005,
        "handovers_idle": 0, "waits_idle": 5, "outside_s": 0.0}


def test_span_cost_compares_segments_with_their_neighbours():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    sys.path.insert(0, ROOT)
    import span_trace

    # versions of 1 s that drift by 1 ms each; 0.25 s more with the
    # spans on; the version after a switch 5 s longer
    every, first, t, stamps = 4, 2, 0.0, [0.0]
    for i in range(first + 1, 12 * every):
        t += (1.0 + 1e-3 * i + (0.25 if (i // every) % 2 == 0 else 0.0)
              + (5.0 if i % every == 0 else 0.0))
        stamps.append(t)
    cost = span_trace.span_cost(stamps, first, every)
    assert cost["segments_compared"] == 9        # 11 whole, 9 inside
    assert cost["on_minus_off_s"] == pytest.approx(0.25, abs=1e-9)
    assert cost["on_minus_off_q1_s"] == pytest.approx(0.25, abs=1e-9)
    assert cost["version_s_on"] > cost["version_s_off"]


def test_alternating_switches_the_spans_off_and_on(table):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import span_trace

    class Clock:
        stamps = []

        def __call__(self):
            self.stamps.append(0.0)

    on = (program.span, program.count, program.enqueued, program.waited)
    commit = span_trace.alternating(Clock(), 2, program, on)
    try:
        seen = []
        for _ in range(8):
            with program.span("v"):
                program.count("v.k")
                program.waited()
            seen.append(program.stats().get("v.n", 0))
            commit()
    finally:
        (program.span, program.count, program.enqueued,
         program.waited) = on
    # commits 0-1 on, 2-3 off, 4-5 on, 6-7 off
    assert seen == [1, 2, 2, 2, 3, 4, 4, 4]
    assert program.stats()["v.k"] == 4


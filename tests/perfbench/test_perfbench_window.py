"""The measured window: whole versions only, the job left by the commit
wrapper at the deadline, every rank of several leaving at one version."""
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402
from perfbench.window import StopWord, VersionClock, WindowClosed  # noqa: E402


class Store:
    """A stand-in for the engine's checkpoint store."""

    def __init__(self, lose_every=0):
        self.version, self.calls, self.lose_every = 0, 0, lose_every

    def commit(self, model=None):
        self.calls += 1
        if not (self.lose_every and self.calls % self.lose_every == 0):
            self.version += 1


def drive(clock, step_s=0.002, limit=100000):
    with pytest.raises(WindowClosed):
        for _ in range(limit):
            time.sleep(step_s)
            clock("model")


def test_counts_whole_versions_between_opening_and_deadline():
    store = Store()
    clock = VersionClock(store.commit, lambda: store.version, 0.1, 3,
                         True, None)
    drive(clock)
    n = clock.versions()
    assert store.version == 3 + n + 1         # warm-up, counted, the straddler
    assert len(clock.stamps) == store.version
    assert clock.stamps[2 + n] <= clock.deadline < clock.stamps[3 + n]
    assert clock.span_s() == pytest.approx(
        clock.stamps[2 + n] - clock.stamps[2])
    assert 0 < clock.span_s() <= 0.1
    assert len(clock.version_gaps()) == n == len(clock.commit_seconds())
    assert sum(clock.version_gaps()) == pytest.approx(clock.span_s())
    assert clock.failed == 0
    assert clock.opened_wall is not None and clock.first_wall is not None


def test_a_lost_commit_is_a_failure():
    store = Store(lose_every=5)
    clock = VersionClock(store.commit, lambda: store.version, 0.05, 1,
                         True, None)
    with pytest.raises(WindowClosed):
        for _ in range(100000):
            time.sleep(0.001)
            clock("model")
            if store.calls > 2000:
                break
    assert clock.failed >= 1


def test_open_and_close_hooks_run_once_at_the_window_edges():
    store, seen = Store(), []
    clock = VersionClock(store.commit, lambda: store.version, 0.03, 2, True,
                         None, on_open=lambda: seen.append(("open",
                                                            store.version)),
                         on_close=lambda: seen.append(("close",
                                                       store.version)))
    drive(clock)
    assert seen == [("open", 2), ("close", store.version)]


def test_every_rank_leaves_at_the_version_rank_0_decides(tmp_path):
    """Four 'ranks' in lock step (a barrier stands for the collective
    of each iteration), clocks skewed: all stop at one version."""
    world = 4
    barrier = threading.Barrier(world)
    stores = [Store() for _ in range(world)]
    left, errors = {}, []

    def rank_main(rank):
        try:
            word = StopWord(str(tmp_path / "stop"))
            clock = VersionClock(stores[rank].commit,
                                 lambda: stores[rank].version, 0.05, 2,
                                 rank == 0, word)
            try:
                for _ in range(100000):
                    barrier.wait(timeout=10)
                    time.sleep(0.0005 * (rank + 1))     # skew
                    clock("model")
            except WindowClosed:
                left[rank] = (stores[rank].version, clock.stop_at)
        except Exception as e:                           # noqa: BLE001
            errors.append(e)
            barrier.abort()

    threads = [threading.Thread(target=rank_main, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads) and not errors
    assert len(left) == world
    assert len(set(left.values())) == 1, left
    version, stop_at = left[0]
    assert version == stop_at


def test_stop_word_is_shared_through_the_file(tmp_path):
    a, b = StopWord(str(tmp_path / "w")), StopWord(str(tmp_path / "w"))
    assert b.get() == 0
    a.set(1234567890123)
    assert b.get() == 1234567890123


@pytest.mark.parametrize("values,q,want", [
    (list(range(1, 101)), 95, 95), (list(range(1, 101)), 50, 50),
    ([5.0], 95, 5.0), ([1, 2, 3, 4], 95, 4), ([3, 1, 2], 50, 2),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert harness.percentile(values, q) == want


def test_spans_keep_durations_by_name():
    spans = harness.Spans(annotate=False)
    fn = spans.wrap(lambda x: x + 1, "call")
    assert fn(1) == 2 and fn(2) == 3
    token = spans.begin("stage")
    time.sleep(0.01)
    assert spans.end("stage", token) >= 0.01
    assert len(spans.seconds["call"]) == 2 and len(spans.seconds["stage"]) == 1

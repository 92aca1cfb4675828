"""The metrics that read the span table of a recorded session (PR 51):
``exposed_s`` and ``unsure_s`` of the steps that lie wholly inside the
profiler's trace, over those steps' ``total_s``, and the hand-overs that
found the device idle.  Each is data (``layers/<name>.json``, kind
``counter_share``) and is read here off a hand-made ``path_stats``, off
a table without a session (nothing to read: the parent of PR 51, and
any untraced run) and off a small job on the CPU under ``jax.profiler``.
A time read here is never a device metric."""
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

sys.path.insert(0, rehearsal.ROOT)

import rabit_tpu  # noqa: E402
from perfbench import harness, readers  # noqa: E402
from rabit_tpu import engine as engine_mod  # noqa: E402
from rabit_tpu.obs import program  # noqa: E402

MANIFEST = json.load(open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")))
ENTRY = {m["name"]: m for m in MANIFEST["per_layer"]}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LOOP, HOST = "learner loop", "host control plane"
T = program.TRACED


def table(scale: float = 1.0) -> dict:
    """One rank's table after a traced run: 130 steps of 0.1 s from the
    start of the process, of which 100 lie inside the session.  The
    process's own columns hold the profiler's start and stop (3 s of a
    step, exposed) and must not be read."""
    return {
        "learn.step.n": 130, "learn.step.total_s": 16.0,
        "learn.step.exposed_s": 3.6, "learn.step.unsure_s": 0.9,
        "commit.n": 130, "commit.total_s": 1.3, "commit.exposed_s": 0.01,
        "commit.unsure_s": 0.4,
        "learn.handovers": 390, "learn.handovers_idle": 140,
        T + "learn.step.n": 100, T + "learn.step.total_s": 10.0,
        T + "learn.step.exposed_s": 0.4 * scale,
        T + "learn.step.unsure_s": 0.7 * scale,
        T + "commit.n": 101, T + "commit.total_s": 1.0,
        T + "commit.exposed_s": 0.0, T + "commit.unsure_s": 0.3 * scale,
        T + "learn.handovers": 300,
        T + "learn.handovers_idle": 60 * scale}


# name -> (the mean of a rank and one that read twice as much, layer,
#          source)
WANT = {
    "loop_idle_lower_pct": (6.0, LOOP, "program_span"),
    "loop_idle_band_pct": (10.5, LOOP, "program_span"),
    "commit_idle_band_pct": (4.5, HOST, "program_span"),
    "loop_handovers_idle_pct": (30.0, LOOP, "program_counter"),
}


def read(name: str, *tables):
    spec = harness.read_json(os.path.join(
        rehearsal.ROOT, "perfbench", "layers", name + ".json"))
    assert spec["kind"] == "counter_share" and spec["over"] == "mean"
    assert spec["part"].startswith(T)
    assert all(key.startswith(T) for key in spec["whole"])
    return readers.counter_share(SimpleNamespace(
        ranks=[{"path_stats": t} for t in tables]), spec)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_table(name):
    assert read(name, table(), table(2.0)) == pytest.approx(
        WANT[name][0], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_table_without_a_session_has_nothing_to_read(name):
    """The parent of PR 51 and every untraced run: no ``traced/`` key."""
    untraced = {k: v for k, v in table().items() if not k.startswith(T)}
    assert read(name, untraced, untraced) is None
    assert read(name, {"device_ops": 10, "host_ops": 0}, {}) is None


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", sorted(WANT))
def test_the_manifest_lists_the_metric_in_the_cell(name, cell):
    _value, layer, source = WANT[name]
    entry = ENTRY[name]
    assert cell in entry["workloads"] and len(entry["workloads"]) == len(CELLS)
    assert (entry["layer"], entry["source"]) == (layer, source)
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        "%", "lower", "rows_per_s")
    assert name in {m["name"] for m in harness.metrics_of(
        harness.load_cell(cell), "per_layer")}
    assert not os.path.exists(os.path.join(
        rehearsal.ROOT, "perfbench", "layers", name + ".py"))


def test_the_new_entries_are_the_manifests_last():
    assert [m["name"] for m in MANIFEST["per_layer"][-4:]] == [
        "loop_idle_lower_pct", "loop_idle_band_pct", "commit_idle_band_pct",
        "loop_handovers_idle_pct"]


class Leave(Exception):
    pass


def test_a_job_under_the_profiler_keeps_the_steps_inside_the_session(
        empty_engine, monkeypatch, tmp_path):
    """A k-means job of whole steps, the session started after the
    second commit and stopped after the fifth, from the wrapper of
    ``rabit_tpu.checkpoint`` as the benchmark does it: steps three and
    four lie inside, the second and the fifth are cut."""
    import jax

    from rabit_tpu.learn import kmeans
    from rabit_tpu.learn.data import SparseMat

    rng = np.random.default_rng(51)
    n, nnz, d = 1024, 4, 32
    rows = SparseMat(
        indptr=np.arange(0, n * nnz + 1, nnz),
        findex=rng.integers(0, d, n * nnz).astype(np.int32),
        fvalue=(1.0 + rng.random(n * nnz)).astype(np.float32),
        labels=np.zeros(n, np.float32), feat_dim=d)
    commit, commits = rabit_tpu.checkpoint, []

    def wrapped(*args, **kwargs):
        commit(*args, **kwargs)
        commits.append(program.stats().get("learn.step.n", 0))
        if len(commits) == 2:
            jax.profiler.start_trace(str(tmp_path))
        if len(commits) == 5:
            jax.profiler.stop_trace()
            raise Leave

    monkeypatch.setattr(rabit_tpu, "checkpoint", wrapped)
    program.reset()
    try:
        with pytest.raises(Leave):
            kmeans.run(rows, 4, 8, device_chain=0)
    finally:
        if jax.profiler.TraceAnnotation.is_enabled():
            jax.profiler.stop_trace()
    s = engine_mod.get_engine().path_stats
    assert commits == [0, 1, 2, 3, 4]       # each inside its open step
    assert s["learn.step.n"] == s["commit.n"] == 5
    assert s[T + "learn.step.n"] == 2 and s[T + "commit.n"] == 3
    assert 0 < s[T + "learn.step.total_s"] < s["learn.step.total_s"]
    for column in ("exposed_s", "unsure_s"):
        assert 0 <= s[T + "learn.step." + column] \
            <= s["learn.step." + column]
    assert s[T + "learn.step.exposed_s"] + s[T + "learn.step.unsure_s"] \
        <= s[T + "learn.step.total_s"] + 1e-9
    assert 0 <= s.get(T + "learn.handovers_idle", 0) \
        <= s[T + "learn.handovers"] <= s["learn.handovers"]
    # every hand-over of the session is an instant on the trace's clock
    from perfbench import trace_reduce

    profile = jax.profiler.ProfileData.from_file(
        trace_reduce.find_xplane(str(tmp_path)))
    marks = [e for plane in profile.planes for line in plane.lines
             for e in line.events
             if e.name == program.PREFIX + program.MARK]
    assert len(marks) == s[T + "learn.handovers"] > 0
    observed = SimpleNamespace(ranks=[{"path_stats": s}])
    for name in WANT:
        spec = harness.read_json(os.path.join(
            rehearsal.ROOT, "perfbench", "layers", name + ".json"))
        assert 0 <= readers.counter_share(observed, spec) <= 100
    program.reset()

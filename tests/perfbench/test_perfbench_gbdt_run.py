"""The boosting cell rehearsed on the CPU end to end through
``harness.main`` under a steering file of its own
(``as_if_on_chip_gbdt.py``): the contract's last line, `correct` true
for the stated precision and false for the control and for a timed path
broken underneath, and the program's spans and counters in
``path_stats``."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

CELL = "gbdt-hist-round-x1"
STEERED = os.path.join("tests", "perfbench", "as_if_on_chip_gbdt.py")
EXACT = ("unsplit_above_limit", "cuts_gap", "bin_gap", "recompiles_in_window",
         "version_gap", "rank_disagreement", "host_ops", "tier_mismatch",
         "kernel_missing")
NEW = {"gbdt_fetch_share_pct", "gbdt_split_share_pct",
       "gbdt_dispatch_share_pct", "gbdt_partition_share_pct",
       "gbdt_live_channel_pct", "gbdt_stage_bin_s"}


def run(trace, env=None, **kw):
    return rehearsal.run(
        rehearsal.cell_args(CELL, trace, rows=8192, seconds=1.5, **kw),
        entry=STEERED, env=env)


def test_untraced_run_prints_both_end_to_end_metrics_and_is_correct():
    proc, line = run(0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    rows = rehearsal.compared(proc)
    assert all(rows[name]["value"] == 0 for name in EXACT), rows
    for name in ("leaf_sum_rel_err", "leaf_sum_rounded_rel_err"):
        assert 0 < rows[name]["value"] < rows[name]["limit"]


def test_traced_run_prints_the_new_metrics():
    proc, line = run(1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = line["metrics"]
    assert NEW | {"stage_s", "resume_s", "commit_stall_s",
                  "device_idle_pct"} <= set(got)
    assert got["gbdt_live_channel_pct"]["value"] <= 100.0
    shares = sum(got[n]["value"] for n in NEW if n.endswith("share_pct"))
    assert 0 < shares <= 100.0
    assert got["gbdt_stage_bin_s"]["value"] <= got["stage_s"]["value"]
    assert any(name.startswith("gbdt_level/")
               for name, _s in line["breakdown"]["device_ops"])
    assert line["correct"] is True


@pytest.mark.parametrize("seed", [2 ** 31 + 301, 2 ** 31 + 302])
def test_control_is_not_correct_by_the_rounded_leaf_sums(seed):
    proc, line = rehearsal.run(
        rehearsal.cell_args(CELL, 0, rows=8192, seconds=1.5, seed=seed)
        + ["--grid", "float8_e4m3fn"], entry=STEERED)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert rows["leaf_sum_rounded_rel_err"]["ok"] is False
    assert all(rows[name]["value"] == 0 for name in EXACT), rows


@pytest.mark.parametrize("broken,by", [
    ("leaf_ignores_rounding", "leaf_sum_rounded_rel_err"),
    ("kernel_interpreted", "kernel_missing"),
    ("host_arm", "tier_mismatch"),
])
def test_a_timed_path_broken_underneath_is_not_correct(broken, by):
    proc, line = run(0, env={"PERFBENCH_TEST_BREAK": broken})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert rehearsal.compared(proc)[by]["ok"] is False


def test_spans_and_counters_are_in_path_stats(monkeypatch):
    import numpy as np

    import rabit_tpu
    from rabit_tpu import engine
    from rabit_tpu.learn import boosting
    from rabit_tpu.obs import program

    rng = np.random.default_rng(31)
    X = rng.standard_normal((1500, 4)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.standard_normal(1500) > 0).astype(np.float32)
    monkeypatch.setattr(boosting, "on_tpu", lambda: True)
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    program.reset()
    rabit_tpu.init(rabit_engine="empty")
    try:
        boosting.train(X, y, num_round=3, max_depth=4, nbin=16,
                       min_child_weight=40.0, use_pallas=False)
        stats = engine.get_engine().path_stats
    finally:
        rabit_tpu.finalize()
    for name in ("learn.step", "gbdt.grad", "gbdt.level", "learn.dispatch",
                 "gbdt.level.fetch", "allreduce", "gbdt.split",
                 "gbdt.partition", "gbdt.leaf", "commit", "stage.cuts",
                 "stage.put", "stage.bin", "stage.compile"):
        assert stats[name + ".n"] >= 1 and stats[name + ".total_s"] > 0, name
    assert stats["learn.versions"] == stats["learn.iterations"] == 3
    assert stats["learn.step.n"] == 3
    # a tree all of whose nodes stopped runs no further level
    assert 6 <= stats["gbdt.levels"] <= 12
    assert stats["gbdt.level.n"] == stats["gbdt.levels"]
    # one allreduce a level, and round 0's vote on missing values
    assert stats["allreduce.n"] == stats["gbdt.levels"] + 1
    # at most 2 + 4 + 8 + 16 channel slots a round, and trees stopped early
    assert stats["gbdt.channels"] <= 3 * 30
    assert 0 < stats["gbdt.channels_live"] < stats["gbdt.channels"]
    assert stats["gbdt.nodes_split"] * 2 <= stats["gbdt.channels_live"]
    # the compiles sit outside the round
    assert stats["stage.compile.total_s"] > stats["learn.step.total_s"] / 50

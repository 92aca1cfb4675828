"""The multi-class boosting cell rehearsed on the CPU end to end through
``harness.main``: the contract's last line, `correct` true for the
stated precision and the stated objective, false for both controls each
by its own number, and false for a timed path broken underneath; the
new counters in the result line.

`correct` is judged on a fixed number of rounds: with a window of 10 ms
the job commits its two warm-up rounds and the one that closes the
window, three rounds of seven trees on any machine, and the replayed
rounds are the first and the third.  What a longer window holds depends
on the machine's speed: the run that prints ``rows_per_s`` asks only
what does not."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

CELL = "gbdt-covtype-round-x1"
STEERED = os.path.join("tests", "perfbench",
                       "as_if_on_chip_gbdt_softprob.py")
EXACT = ("unsplit_above_limit", "trees_per_round_gap", "cuts_gap", "bin_gap",
         "recompiles_in_window", "version_gap", "rank_disagreement",
         "host_ops", "tier_mismatch", "kernel_missing")
NEW = {"gbdt_mc_trees_per_version", "gbdt_mc_waits_per_tree_pct",
       "gbdt_mc_channels_per_call", "gbdt_mc_live_channel_pct",
       "gbdt_mc_split_exposed_share_pct",
       "gbdt_mc_partition_exposed_share_pct",
       "gbdt_mc_fetch_copy_exposed_share_pct"}
THREE_ROUNDS = 0.01


def run(trace, env=None, entry=STEERED, extra=(), seconds=THREE_ROUNDS, **kw):
    return rehearsal.run(
        rehearsal.cell_args(CELL, trace, rows=4096, seconds=seconds, **kw)
        + list(extra), entry=entry, env=env)


def saw(proc) -> dict:
    import json

    (text,) = [ln for ln in proc.stderr.splitlines()
               if ln.startswith("perfbench gbdt_softprob saw ")]
    return json.loads(text[len("perfbench gbdt_softprob saw "):])


@pytest.mark.parametrize("entry", [STEERED, rehearsal.STEERED],
                         ids=["own-steering", "kmeans-steering"])
def test_three_rounds_of_seven_trees_are_correct(entry):
    proc, line = run(0, entry=entry)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "a rehearsal off the chip" in proc.stderr
    assert line["correct"] is True and line["failed"] == 0
    seen = saw(proc)
    assert (seen["rounds"], seen["trees"]) == (3, 21)
    assert seen["grad_calls"] == 4            # the fourth round was open
    assert seen["counters"]["gbdt.classes"] == 7
    assert seen["counters"]["gbdt.trees"] == 21
    assert seen["counters"]["gbdt.levels"] == 9     # three a round, not 21
    assert len(seen["by_class"]) == 7
    rows = rehearsal.compared(proc)
    assert all(rows[name]["value"] == 0 for name in EXACT), rows
    assert all(row["ok"] for row in rows.values()), rows
    for name in ("leaf_sum_rel_err", "leaf_sum_rounded_rel_err",
                 "softmax_grad_err"):
        assert 0 < rows[name]["value"] < rows[name]["limit"]
    assert rows["softmax_grad_err"]["value"] < 1e-4


def test_a_window_that_holds_rounds_prints_both_end_to_end_metrics():
    proc, line = run(0, seconds=1.0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    rows = rehearsal.compared(proc)
    assert all(rows[name]["value"] == 0 for name in EXACT), rows
    assert rows["softmax_grad_err"]["ok"] is True
    assert rows["leaf_sum_rounded_rel_err"]["ok"] is True


def test_traced_run_prints_the_new_metrics():
    proc, line = run(1, seconds=1.0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = line["metrics"]
    assert NEW | {"stage_s", "resume_s", "commit_stall_s",
                  "device_idle_pct", "gbdt_device_scan_pct",
                  "loop_wait_share_pct", "loop_exposed_share_pct",
                  "commit_exposed_share_pct"} <= set(got)
    assert got["gbdt_mc_trees_per_version"]["value"] == 700.0
    # three levels a round of seven trees here (depth 3): 3 / 7; six
    # levels on the chip read 85.7, and tree by tree it were 300 here
    assert got["gbdt_mc_waits_per_tree_pct"]["value"] == pytest.approx(
        300.0 / 7)
    assert got["gbdt_device_scan_pct"]["value"] == 100.0
    assert 0 < got["gbdt_mc_live_channel_pct"]["value"] <= 100.0
    assert got["gbdt_mc_channels_per_call"]["value"] >= 200.0
    shares = sum(got[n]["value"] for n in NEW if n.endswith("share_pct"))
    assert 0 <= shares <= 100.0
    names = {name.split("/")[0] for name, _s in
             line["breakdown"]["device_ops"]}
    assert "gbdt_level" in names
    assert "restart iter" in proc.stdout          # the resume, 7 margins
    rows = rehearsal.compared(proc)
    assert all(rows[name]["value"] == 0 for name in EXACT), rows
    assert rows["softmax_grad_err"]["ok"] is True


@pytest.mark.parametrize("seed", [2 ** 31 + 421, 2 ** 31 + 422])
def test_float8_control_is_not_correct_by_the_rounded_leaf_sums(seed):
    proc, line = run(0, seed=seed, extra=["--grid", "float8_e4m3fn"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert rows["leaf_sum_rounded_rel_err"]["ok"] is False
    assert rows["softmax_grad_err"]["ok"] is True   # the gradient is sound
    assert all(rows[name]["value"] == 0 for name in EXACT), rows


@pytest.mark.parametrize("seed", [2 ** 31 + 421, 2 ** 31 + 422])
def test_one_vs_rest_control_is_not_correct_by_the_gradient(seed):
    """A sigmoid a class where the job states a softmax: the gradients
    read back are not the reference's, and neither are the leaves grown
    on them."""
    proc, line = run(0, seed=seed, extra=["--grid", "one_vs_rest"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert rows["softmax_grad_err"]["ok"] is False
    assert rows["softmax_grad_err"]["value"] > 100 * rows[
        "softmax_grad_err"]["limit"]
    assert rows["leaf_sum_rel_err"]["ok"] is False
    # (trees grown on other gradients stop where the reference would not)
    assert all(rows[name]["value"] == 0 for name in EXACT[1:]), rows


@pytest.mark.parametrize("broken,by", [
    ("hessian_not_doubled", "softmax_grad_err"),
    ("classes_from_stale_margins", "softmax_grad_err"),
    ("forest_states_six_classes", "trees_per_round_gap"),
    ("leaf_ignores_rounding", "leaf_sum_rounded_rel_err"),
    ("kernel_interpreted", "kernel_missing"),
    ("host_arm", "tier_mismatch"),
])
def test_a_timed_path_broken_underneath_is_not_correct(broken, by):
    proc, line = run(0, env={"PERFBENCH_TEST_BREAK": broken})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert rehearsal.compared(proc)[by]["ok"] is False

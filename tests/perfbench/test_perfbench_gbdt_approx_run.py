"""The ``approx`` boosting cell rehearsed on the CPU end to end through
``harness.main``: the contract's last line, `correct` true for the
stated precision and the stated sketch, false for both controls each by
its own number, and false for a timed path broken underneath (cuts kept
from the first round, a summary too coarse, rows binned once, split
values off their cuts); the new spans and counters in the result line.
The window is short, so that a rehearsal holds a handful of rounds on
any machine."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

CELL = "gbdt-approx-round-x1"
STEERED = os.path.join("tests", "perfbench", "as_if_on_chip_gbdt_approx.py")
EXACT = ("unsplit_above_limit", "split_value_gap", "bin_gap",
         "recompiles_in_window", "version_gap", "rank_disagreement",
         "host_ops", "tier_mismatch", "kernel_missing")
NEW = {"gbdt_approx_sketch_exposed_share_pct", "gbdt_approx_merge_share_pct",
       "gbdt_approx_rebin_exposed_share_pct", "gbdt_approx_resketch_pct"}


def run(trace, env=None, entry=STEERED, extra=(), **kw):
    return rehearsal.run(
        rehearsal.cell_args(CELL, trace, rows=8192, seconds=0.5, **kw)
        + list(extra), entry=entry, env=env)


@pytest.mark.parametrize("entry", [STEERED, rehearsal.STEERED],
                         ids=["own-steering", "kmeans-steering"])
def test_untraced_run_prints_both_end_to_end_metrics_and_is_correct(entry):
    proc, line = run(0, entry=entry)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "a rehearsal off the chip" in proc.stderr
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    rows = rehearsal.compared(proc)
    assert all(rows[name]["value"] == 0 for name in EXACT), rows
    for name in ("leaf_sum_rel_err", "leaf_sum_rounded_rel_err"):
        assert 0 < rows[name]["value"] < rows[name]["limit"]
    # one rank: exact weighted quantiles, to the float32 sums
    assert rows["cut_rank_err"]["value"] < 1e-5 < rows["cut_rank_err"][
        "limit"]


def test_traced_run_prints_the_new_metrics():
    proc, line = run(1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = line["metrics"]
    assert NEW | {"stage_s", "resume_s", "commit_stall_s",
                  "device_idle_pct", "gbdt_device_scan_pct",
                  "loop_wait_share_pct"} <= set(got)
    assert got["gbdt_approx_resketch_pct"]["value"] == 100.0
    assert got["gbdt_device_scan_pct"]["value"] == 100.0
    shares = sum(got[n]["value"] for n in NEW if n.endswith("share_pct"))
    assert 0 <= shares <= 100.0
    names = {name.split("/")[0] for name, _s in
             line["breakdown"]["device_ops"]}
    assert {"gbdt_sketch", "gbdt_level"} <= names
    assert line["correct"] is True
    assert "restart iter" in proc.stdout          # the resume, on its cuts


@pytest.mark.parametrize("seed", [2 ** 31 + 403, 2 ** 31 + 404])
def test_float8_control_is_not_correct_by_the_rounded_leaf_sums(seed):
    proc, line = run(0, seed=seed, extra=["--grid", "float8_e4m3fn"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert rows["leaf_sum_rounded_rel_err"]["ok"] is False
    assert rows["cut_rank_err"]["ok"] is True     # the sketch is sound
    assert all(rows[name]["value"] == 0 for name in EXACT), rows


@pytest.mark.parametrize("seed", [2 ** 31 + 403, 2 ** 31 + 404])
def test_unweighted_control_is_not_correct_by_the_cuts_rank(seed):
    """The sketch handed ones for hessians: the first tree's cuts are
    right (at margin 0 every hessian is 0.25), the last tree's are the
    unweighted quantiles where the reference asks for the weighted."""
    proc, line = run(0, seed=seed, extra=["--grid", "unweighted_sketch"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert rows["cut_rank_err"]["ok"] is False
    assert rows["cut_rank_err"]["value"] > 4 * rows["cut_rank_err"]["limit"]
    assert rows["leaf_sum_rounded_rel_err"]["ok"] is True
    assert all(rows[name]["value"] == 0 for name in EXACT), rows


@pytest.mark.parametrize("broken,by", [
    ("cuts_kept_from_the_first_round", "cut_rank_err"),
    ("summary_of_64_entries", "cut_rank_err"),
    ("rows_binned_once", "bin_gap"),
    ("split_values_off_their_cuts", "split_value_gap"),
    ("leaf_ignores_rounding", "leaf_sum_rounded_rel_err"),
    ("kernel_interpreted", "kernel_missing"),
    ("host_arm", "tier_mismatch"),
])
def test_a_timed_path_broken_underneath_is_not_correct(broken, by):
    proc, line = run(0, env={"PERFBENCH_TEST_BREAK": broken})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert rehearsal.compared(proc)[by]["ok"] is False

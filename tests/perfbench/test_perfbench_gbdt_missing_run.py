"""The wide boosting cell rehearsed on the CPU end to end through
``harness.main``: the contract's last line, `correct` true for the
stated precision and false for the control and for a timed path broken
underneath (absent rows sent one fixed way, a station dropped from the
histograms), and the new spans and counters in the result line.  The
window is short, so that a rehearsal holds a handful of rounds on any
machine (late trees on so few rows choose among near-zero gains:
PERF.md section 7)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

CELL = "gbdt-bosch-round-x1"
STEERED = os.path.join("tests", "perfbench", "as_if_on_chip_gbdt_missing.py")
EXACT = ("unsplit_above_limit", "cuts_gap", "bin_gap", "recompiles_in_window",
         "version_gap", "rank_disagreement", "host_ops", "tier_mismatch",
         "kernel_missing")
NEW = {"gbdt_wide_fetch_share_pct", "gbdt_wide_split_share_pct",
       "gbdt_wide_partition_share_pct", "gbdt_wide_calls_per_level",
       "gbdt_wide_default_left_pct", "gbdt_wide_missing_entry_pct",
       "gbdt_wide_stage_cuts_s"}


def run(trace, env=None, entry=STEERED, extra=(), **kw):
    return rehearsal.run(
        rehearsal.cell_args(CELL, trace, rows=8192, seconds=0.5, **kw)
        + list(extra), entry=entry, env=env)


@pytest.mark.parametrize("entry", [STEERED, rehearsal.STEERED],
                         ids=["own-steering", "kmeans-steering"])
def test_untraced_run_prints_both_end_to_end_metrics_and_is_correct(entry):
    proc, line = run(0, entry=entry)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REHEARSAL_WIDTHS" in proc.stderr
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    rows = rehearsal.compared(proc)
    assert all(rows[name]["value"] == 0 for name in EXACT), rows
    for name in ("leaf_sum_rel_err", "leaf_sum_rounded_rel_err"):
        assert 0 < rows[name]["value"] < rows[name]["limit"]
    assert rows["split_regret"]["value"] < rows["split_regret"]["limit"]


def test_traced_run_prints_the_new_metrics():
    proc, line = run(1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = line["metrics"]
    assert NEW | {"stage_s", "resume_s", "commit_stall_s",
                  "device_idle_pct"} <= set(got)
    shares = sum(got[n]["value"] for n in NEW if n.endswith("share_pct"))
    assert 0 < shares <= 100.0
    # the mechanism is live: the direction goes both ways
    assert 0 < got["gbdt_wide_default_left_pct"]["value"] < 100
    # the rehearsal's rows are 55% absent
    assert 45 < got["gbdt_wide_missing_entry_pct"]["value"] < 65
    # 24 features in one kernel call a level
    assert got["gbdt_wide_calls_per_level"]["value"] == 100.0
    assert got["gbdt_wide_stage_cuts_s"]["value"] <= got["stage_s"]["value"]
    assert any(name.startswith("gbdt_level/")
               for name, _s in line["breakdown"]["device_ops"])
    assert line["correct"] is True


@pytest.mark.parametrize("seed", [2 ** 31 + 403, 2 ** 31 + 404])
def test_control_is_not_correct_by_the_rounded_leaf_sums(seed):
    """At the rehearsal's 8,192 rows the float8 control reads 2e-4 to
    2e-3 on the tree-wide number against sound runs' 1e-8 (a rounding
    that is random averages out over a tree) and is over the limit on
    four seeds of five, these two among them; on the chip it fails by
    every limit, by orders of magnitude (PERF.md section 2)."""
    proc, line = run(0, seed=seed, extra=["--grid", "float8_e4m3fn"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert rows["leaf_sum_rounded_rel_err"]["ok"] is False
    assert all(rows[name]["value"] == 0 for name in EXACT), rows


@pytest.mark.parametrize("broken,by", [
    ("absent_rows_one_fixed_way", "split_regret"),
    ("histogram_drops_a_station", "split_regret"),
    ("leaf_ignores_rounding", "leaf_sum_rounded_rel_err"),
    ("kernel_interpreted", "kernel_missing"),
    ("host_arm", "tier_mismatch"),
])
def test_a_timed_path_broken_underneath_is_not_correct(broken, by):
    proc, line = run(0, env={"PERFBENCH_TEST_BREAK": broken})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert rehearsal.compared(proc)[by]["ok"] is False

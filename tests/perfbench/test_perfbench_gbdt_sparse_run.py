"""The sparse boosting cell rehearsed on the CPU end to end through
``harness.main``: the contract's last line, `correct` true for the
stated precision and false for the control and for a timed path broken
underneath (absent rows scored one fixed way, the numeric columns
dropped from the flat histograms, absent rows moved one fixed way), and
the new spans
and counters in the result line.  The window is short, so that a
rehearsal holds a handful of rounds on any machine."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

CELL = "gbdt-allstate-round-x1"
STEERED = os.path.join("tests", "perfbench", "as_if_on_chip_gbdt_sparse.py")
EXACT = ("unsplit_above_limit", "cuts_gap", "bin_gap", "recompiles_in_window",
         "version_gap", "rank_disagreement", "host_ops", "tier_mismatch",
         "kernel_missing")
NEW = {"gbdt_sparse_padding_pct", "gbdt_sparse_bins_pct",
       "gbdt_sparse_stage_bucket_s", "gbdt_sparse_stage_bin_s",
       "gbdt_live_channel_pct", "gbdt_sparse_split_exposed_share_pct",
       "gbdt_sparse_partition_exposed_share_pct",
       "gbdt_sparse_allreduce_exposed_share_pct"}


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
    assert NEW | {"stage_s", "resume_s", "commit_stall_s", "device_idle_pct",
                  "gbdt_device_scan_pct", "loop_exposed_share_pct",
                  "loop_wait_share_pct", "commit_exposed_share_pct"} \
        <= set(got)
    # every level's histograms stayed on the device and were ranked there
    assert got["gbdt_device_scan_pct"]["value"] == 100.0
    # 8,192 rows of 12 slots: two tiles of 4,096 rows, their buckets
    # padded to whole sub-chunks
    assert 100 < got["gbdt_sparse_padding_pct"]["value"] < 400
    # 82 columns: 6 of up to 256 bins, 76 of two
    assert 0 < got["gbdt_sparse_bins_pct"]["value"] < 10
    assert got["gbdt_sparse_stage_bucket_s"]["value"] \
        <= got["stage_s"]["value"]
    assert 0 < got["gbdt_sparse_stage_bin_s"]["value"] \
        <= got["stage_s"]["value"]
    assert 0 < got["gbdt_live_channel_pct"]["value"] <= 100.0
    assert any(name.startswith("gbdt_level/")
               for name, _s in line["breakdown"]["device_ops"])
    assert line["correct"] is True
    assert "restart iter" in proc.stdout


def test_the_programs_table_holds_the_sparse_spans_and_counters():
    proc, _line = run(0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    saw = [ln for ln in proc.stderr.splitlines()
           if ln.startswith("perfbench gbdt_sparse saw ")]
    import json

    saw = json.loads(saw[-1][len("perfbench gbdt_sparse saw "):])
    assert saw["mosaic_kernels"] and set(saw["mosaic_kernels"]) == {
        "hist_sparse"}
    assert saw["staged"] == ["int32"]
    for span in ("stage.sparse_cuts", "stage.sparse_bin",
                 "stage.sparse_bucket", "gbdt.level", "gbdt.split",
                 "gbdt.partition"):
        assert span in saw["totals"], span
    counters = saw["counters"]
    assert counters["gbdt.sparse.entries"] == saw["present_entries"]
    assert counters["gbdt.entries"] == saw["entries"]
    assert counters["gbdt.entries_missing"] \
        == saw["entries"] - saw["present_entries"]
    assert counters["gbdt.sparse.bins"] == saw["flat_bins"]
    assert counters["gbdt.sparse.bins_rect"] == 82 * 256
    assert counters["gbdt.sparse.slots"] >= saw["present_entries"]
    assert counters["gbdt.sparse.payload_bytes"] > 0
    assert counters["gbdt.levels_device_scan"] == counters["gbdt.levels"]


@pytest.mark.parametrize("seed", [2 ** 31 + 403, 2 ** 31 + 404])
def test_control_is_not_correct_by_the_rounded_leaf_sums(seed):
    proc, line = run(0, seed=seed, extra=["--grid", "float8_e4m3fn"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert rows["leaf_sum_rounded_rel_err"]["ok"] is False
    assert all(rows[name]["value"] == 0 for name in EXACT), rows


@pytest.mark.parametrize("broken,by", [
    ("absent_rows_scored_right", "split_regret"),
    ("histogram_drops_the_numeric_columns", "split_regret"),
    ("absent_moves_right", "leaf_sum_rounded_rel_err"),
    ("leaf_ignores_rounding", "leaf_sum_rounded_rel_err"),
    ("kernel_interpreted", "kernel_missing"),
    ("host_arm", "kernel_missing"),
])
def test_a_timed_path_broken_underneath_is_not_correct(broken, by):
    proc, line = run(0, env={"PERFBENCH_TEST_BREAK": broken})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert rehearsal.compared(proc)[by]["ok"] is False

"""A learner that is not k-means, added as files and entries only: its
adapter, a configuration that names it, a traffic mix and a cell, in a
copy of the benchmark in a temporary directory.  The harness finds the
adapter by the configuration's ``learner`` and decides ``correct`` from
the numbers the adapter compares; nothing that was there is edited."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    root = rehearsal.copy_benchmark(tmp_path_factory.mktemp("bench"))
    before = rehearsal.digest(root)
    return root, rehearsal.add_second_learner(root), before


def args(cell, trace=0, **kw):
    return rehearsal.cell_args(cell, trace, rows=4096, **kw)


def test_a_learner_is_added_without_editing_a_file_that_is_there(added):
    root, _cell, before = added
    after = rehearsal.digest(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "perfbench/configs/colsum-d64.json", "perfbench/learners/colsum.py",
        "perfbench/traffic/step-x1.json"]


def test_second_learner_untraced_run(added):
    root, cell, _ = added
    proc, line = rehearsal.run(args(cell), root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(line) >= rehearsal.RESULT_KEYS
    assert line["correct"] is True and line["attempted"] >= 1
    # the rate is the adapter's work per version over the window
    assert line["metrics"]["rows_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    rows = rehearsal.compared(proc)
    assert set(rows) == {"acc_gap", "version_gap", "rank_disagreement",
                         "host_ops"}


def test_second_learner_traced_run_reports_what_it_has(added):
    root, cell, _ = added
    proc, line = rehearsal.run(args(cell, 1), root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True
    got = set(line["metrics"])
    # what every job has: a commit, a resume, a device that is busy or not
    assert {"commit_stall_s", "resume_s", "device_idle_pct"} <= got
    # and nothing of k-means: its configuration names no step kernel
    assert not {"kernel_per_step_s", "host_gap_per_step_s", "stage_s",
                "kmeans_stats_fused_roofline"} & got
    assert line["breakdown"]["device_ops"]


def test_second_learner_control_fails_by_the_adapters_own_number(added):
    root, cell, _ = added
    proc, line = rehearsal.run(args(cell) + ["--grid", "float8_e4m3fn"],
                               root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = rehearsal.compared(proc)
    assert line["correct"] is False and not rows["acc_gap"]["ok"]
    assert all(r["ok"] for n, r in rows.items() if n != "acc_gap")


def test_a_configuration_that_names_no_adapter_prints_no_result(added):
    root, cell, _ = added
    path = os.path.join(root, "perfbench", "configs", "colsum-d64.json")
    cfg = json.load(open(path))
    try:
        json.dump({**cfg, "learner": "nobody"}, open(path, "w"))
        proc, line = rehearsal.run(args(cell), root=root)
    finally:
        json.dump(cfg, open(path, "w"))
    assert proc.returncode != 0 and line is None
    assert "no adapter" in proc.stderr

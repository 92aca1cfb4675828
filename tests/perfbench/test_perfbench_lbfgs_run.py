"""The L-BFGS cell rehearsed on the CPU end to end through
``harness.main`` (under the one-chip cells' steering file; what this
learner needs beyond it is in its adapter, ``learners/lbfgs.py
on_chip``): the contract's last line,
`correct` true for the stated precision and false for the control, the
new per-layer metrics, and a program that lacks the staging entry point
(the parent of the PR that added the cell) refused at once."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

CELL = "lbfgs-logreg-iter-x1"
EXACT = ("step_not_first", "objective_rises", "stopped_by_convergence",
         "recompiles_in_window", "version_gap", "rank_disagreement",
         "host_ops", "tier_mismatch", "kernel_missing")
NEW = {"lbfgs_grad_share_pct", "lbfgs_eval_share_pct",
       "lbfgs_direction_share_pct", "lbfgs_commit_share_pct",
       "lbfgs_evals_per_version_pct", "lbfgs_margin_reuse_pct",
       "lbfgs_padding_pct", "lbfgs_stage_bucket_s"}


def run(trace, env=None, entry=rehearsal.STEERED, **kw):
    return rehearsal.run(rehearsal.cell_args(CELL, trace, **kw), env=env,
                         entry=entry)


def test_untraced_run_is_correct_and_every_number_is_beside_its_limit():
    proc, line = run(0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    rows = rehearsal.compared(proc)
    assert all(rows[name]["value"] == 0 for name in EXACT), rows
    for name in ("objval_rel_err", "grad_rel_err", "step_cosine_gap",
                 "armijo_gap"):
        assert 0 <= rows[name]["value"] < rows[name]["limit"], name
    assert rows["grad_rel_err"]["value"] > 0


def test_traced_run_prints_the_new_metrics():
    proc, line = run(1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = line["metrics"]
    assert NEW | {"stage_s", "resume_s", "commit_stall_s",
                  "device_idle_pct"} <= set(got)
    shares = sum(got[n]["value"] for n in NEW if n.endswith("share_pct"))
    assert 0 < shares <= 100.0
    assert got["lbfgs_evals_per_version_pct"]["value"] >= 100.0
    assert got["lbfgs_margin_reuse_pct"]["value"] == 100.0
    assert 100.0 <= got["lbfgs_padding_pct"]["value"] < 200.0
    assert got["lbfgs_stage_bucket_s"]["value"] <= got["stage_s"]["value"]
    assert any(name.startswith(("lbfgs_margin/", "lbfgs_grad/"))
               for name, _s in line["breakdown"]["device_ops"])
    assert line["correct"] is True


@pytest.mark.parametrize("seed", [2 ** 31 + 401, 2 ** 31 + 402])
def test_control_is_not_correct_by_the_gradient(seed):
    proc, _line = run(0, seed=seed)
    sound = rehearsal.compared(proc)
    proc, line = rehearsal.run(
        rehearsal.cell_args(CELL, 0, seed=seed) + ["--grid", "bfloat16"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    rows = rehearsal.compared(proc)
    assert not rows["grad_rel_err"]["ok"]
    assert rows["grad_rel_err"]["value"] > 20 * sound["grad_rel_err"]["value"]
    # by one of the cell's limits, not by each
    assert all(rows[name]["ok"] for name in EXACT)


PARENT = '''import os, sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import as_if_on_chip
as_if_on_chip.steer()
from rabit_tpu.learn import linear
del linear.stage_rows
from perfbench import harness
sys.exit(harness.main(entry=os.path.abspath(__file__)))
'''


def test_a_program_without_the_staging_entry_point_is_refused_at_once(
        tmp_path):
    """What the parent of the PR that added the cell does with it: exit
    3 before any data is made, and no result line."""
    entry = os.path.join(rehearsal.ROOT, "tests", "perfbench",
                         "_lbfgs_parent_entry.py")
    with open(entry, "w") as f:
        f.write(PARENT)
    try:
        proc, line = run(0, entry=os.path.relpath(entry, rehearsal.ROOT))
    finally:
        os.remove(entry)
    assert proc.returncode == 3 and line is None
    assert "has no stage_rows" in proc.stderr

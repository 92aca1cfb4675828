"""`correct`: true for the stated precision, false for a lower one and
for a timed path broken underneath; and the runs that must print no
result.  The cell is one a test adds from a temporary directory as
files and entries only, which is also the proof that a configuration, a
traffic mix and a per-layer metric can each be added that way."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    root = rehearsal.copy_benchmark(tmp_path_factory.mktemp("bench"))
    return root, rehearsal.add_tiny_cell(root)


def args(cell, trace=0, **kw):
    return rehearsal.cell_args(cell, trace, **kw)


def test_added_cell_runs_and_is_correct(added):
    root, cell = added
    proc, line = rehearsal.run(args(cell, 1), root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True
    # the metrics the test added, each read by its own new file
    assert line["metrics"]["generate_s"]["value"] > 0
    assert line["metrics"]["versions_per_s"]["unit"] == "1/s"
    # two iterations a version: the traffic file's, not chain8's
    kernel = [s for n, s in line["breakdown"]["device_ops"]]
    assert kernel and line["attempted"] >= 1


def test_a_lower_precision_fails_correct(added):
    """The control: the same job on rows rounded to the 8-bit float grid
    (what storing them one precision lower would keep)."""
    root, cell = added
    proc, line = rehearsal.run(
        args(cell) + ["--grid", "float8_e4m3fn"], root=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = rehearsal.compared(proc)
    assert line["correct"] is False
    assert not rows["centroid_err_x_sqrt_rows"]["ok"]
    assert rows["centroid_err_x_sqrt_rows"]["value"] > rows["centroid_err_x_sqrt_rows"]["limit"]
    # and only that number: the job itself ran as stated
    assert all(r["ok"] for n, r in rows.items() if n != "centroid_err_x_sqrt_rows")


def test_a_step_that_returns_its_state_unchanged_fails_correct(added):
    """Drives the whole run with the timed path broken underneath:
    the chained program's centroid update hands back the centroids it
    was given, so every commit stores the initial ones."""
    root, cell = added
    proc, line = rehearsal.run(
        args(cell), root=root, env={"PERFBENCH_TEST_BREAK": "step_keeps_state"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert not rehearsal.compared(proc)["centroid_err_x_sqrt_rows"]["ok"]


@pytest.mark.parametrize("broken, number", [
    ("other_tier", "tier_mismatch"), ("kernel_interpreted", "kernel_missing")])
def test_another_tier_or_no_kernel_fails_correct(added, broken, number):
    """The tier is read off the arrays ``prepare_shard`` returned and
    the kernel off what was handed to ``pallas_call``, no name of the
    program between: rows staged in float32 where the configuration
    says bfloat16, and a kernel that is interpreted, each fail its own
    number and no other."""
    root, cell = added
    proc, line = rehearsal.run(
        args(cell), root=root, env={"PERFBENCH_TEST_BREAK": broken})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = rehearsal.compared(proc)
    assert line["correct"] is False
    assert [n for n, r in rows.items() if not r["ok"]] == [number]


def test_no_result_off_the_chip():
    """The real entry, unsteered, where JAX finds no accelerator."""
    proc, line = rehearsal.run(
        rehearsal.cell_args("kmeans-dense-chain8-x1", 0),
        entry=os.path.join("perfbench", "run.py"))
    assert proc.returncode != 0
    assert line is None and "correct" not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_no_result_for_an_unknown_cell():
    proc, line = rehearsal.run(rehearsal.cell_args("no-such-cell", 0))
    assert proc.returncode != 0 and line is None


def test_no_result_in_a_directory_that_holds_only_the_benchmark(tmp_path):
    root = rehearsal.copy_benchmark(tmp_path)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py")]
        + [str(a) for a in rehearsal.cell_args("kmeans-dense-chain8-x1", 0)],
        cwd=root, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_seed_changes_data_and_centroids_not_shapes(added):
    root, cell = added
    lines = [rehearsal.run(args(cell, seed=s), root=root)
             for s in (11, 2 ** 31 + 5)]
    for proc, line in lines:
        assert proc.returncode == 0 and line["correct"] is True
    errs = [rehearsal.compared(p)["centroid_err_x_sqrt_rows"]["value"]
            for p, _ in lines]
    assert errs[0] != errs[1]

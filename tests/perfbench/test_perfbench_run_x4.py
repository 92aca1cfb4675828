"""The four-rank cell rehearsed: four CPU processes under the tracker,
XLA engine, the parent off JAX; the contract's last line."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

MANIFEST = json.load(open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")))
CELL = "kmeans-dense-periter-x4"


@pytest.mark.parametrize("trace", [0, 1])
def test_four_ranks_print_one_line(trace):
    proc, line = rehearsal.run(rehearsal.cell_args(CELL, trace))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert rehearsal.RESULT_KEYS <= set(line)
    assert line["device"]["count"] == 4
    assert line["attempted"] >= 1 and line["failed"] == 0
    rows = rehearsal.compared(proc)
    for name in ("version_gap", "rank_disagreement", "host_ops"):
        assert rows[name]["value"] == 0 and rows[name]["ok"], name
    assert rows["reference_count_gap"]["ok"]
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in MANIFEST[group]
            if "workloads" not in m or CELL in m["workloads"]}
    got = set(line["metrics"])
    if trace:
        # the step kernel is interpreted here and not in a CPU trace:
        # the readers keyed on it find nothing (see test_perfbench_run_x1)
        assert want - got <= {
            "peak_hbm_GB", "host_gap_per_step_s", "kernel_per_step_s",
            "collective_exposed_per_step_s", "kmeans_stats_fused_roofline"}
        assert {"stage_s", "resume_s", "commit_stall_s", "commit_stall_p95_s",
                "device_idle_pct"} <= got
        assert line["metrics"]["host_op_share_pct"]["value"] == 0
        assert line["metrics"]["allreduce_call_s"]["value"] > 0
        assert "breakdown" in line
        assert any("all-reduce" in n or "psum" in n
                   for n, _ in line["breakdown"]["device_ops"])
    else:
        # a tail wants hundreds of versions: 1.5 s here holds tens, so
        # the reader finds nothing to read and the metric is left out
        assert want - got <= {"version_p95_s"}
        assert {"rows_per_s", "setup_s"} <= got
    assert "perfbench phases" in proc.stderr

"""The benchmark's command rehearsed on the CPU for the one-chip cells
(tiny rows, Pallas interpreted, steering in the test's own entry file):
the contract's last line, traced and untraced."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearsal  # noqa: E402

MANIFEST = json.load(open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")))
ONE_CHIP = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1]


def kernel_keyed():
    """Per-layer metrics whose reader looks for the step kernel's device
    operation (a Mosaic custom call): interpreted here, it is not in a
    CPU trace, the reader finds nothing and the metric is left out.
    Their arithmetic is checked on the recorded chip trace
    (test_perfbench_trace.py)."""
    out = set()
    for m in MANIFEST["per_layer"]:
        path = os.path.join(rehearsal.ROOT, "perfbench", "layers",
                            m["name"] + ".json")
        if os.path.exists(path) and "pattern" in open(path).read():
            out.add(m["name"])
    return out


def reported(cell, group):
    return {m["name"] for m in MANIFEST[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_untraced_run_prints_the_end_to_end_metrics(cell):
    proc, line = rehearsal.run(rehearsal.cell_args(cell, 0))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rehearsal.RESULT_KEYS <= set(line) and "breakdown" not in line
    assert set(line["metrics"]) == reported(cell, "end_to_end")
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and m["unit"], name
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == 1
    assert "busy_s" not in line["device"]
    rows = rehearsal.compared(proc)
    assert rows and all({"value", "limit", "ok"} <= set(r)
                        for r in rows.values())
    assert rows["version_gap"]["value"] == 0
    assert rows["tier_mismatch"]["value"] == 0
    assert rows["kernel_missing"]["value"] == 0
    # an untraced run performs no resume
    assert "restart iter" not in proc.stdout


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_traced_run_prints_the_per_layer_metrics_and_a_breakdown(cell):
    proc, line = rehearsal.run(rehearsal.cell_args(cell, 1))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert rehearsal.RESULT_KEYS <= set(line)
    want = reported(cell, "per_layer")
    got = set(line["metrics"])
    assert got <= want
    # the CPU's memory is not the chip's: no reading, so no metric
    assert want - got <= {"peak_hbm_GB"} | kernel_keyed()
    assert {"stage_s", "resume_s", "commit_stall_s",
            "device_idle_pct"} <= got
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    breakdown = line["breakdown"]
    assert 1 <= len(breakdown["device_ops"]) <= 10
    assert len(breakdown["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0
               for n, s in breakdown["device_ops"] + breakdown["idle_gaps"])
    assert {"learner_host_code", "commit"} & {n for n, _ in
                                              breakdown["idle_gaps"]}
    assert line["metrics"]["resume_s"]["value"] > 0
    assert "restart iter" in proc.stdout          # the traced run's resume

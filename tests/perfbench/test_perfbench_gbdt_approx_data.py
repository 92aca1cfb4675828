"""The ``approx`` boosting cell's data and files: the HIGGS cell's rows
from the same seed, the controls kept apart, a parent refused cleanly,
the configuration's arithmetic, the manifest's entries and the two new
kernels' costs against a hand count."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, readers  # noqa: E402

ADAPTER = harness.load_module(os.path.join(
    ROOT, "perfbench", "learners", "gbdt_approx.py"))
HIGGS = harness.load_module(os.path.join(
    ROOT, "perfbench", "learners", "gbdt.py"))
CFG = harness.read_json(os.path.join(
    ROOT, "perfbench", "configs", "gbdt-approx-f28-b256-d6.json"))
HIGGS_CFG = harness.read_json(os.path.join(
    ROOT, "perfbench", "configs", "gbdt-hist-f28-b256-d6.json"))
MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "gbdt-approx-round-x1"
N = 5000


@pytest.mark.parametrize("grid", [None, "float8_e4m3fn", "unweighted_sketch"])
def test_rows_are_the_higgs_cells_and_the_controls_are_kept_apart(grid):
    data = ADAPTER.make_data(CFG, 2 ** 31 + 9, 0, 1, 2, N, grid)
    values, labels = HIGGS.make_rows(2 ** 31 + 9, 0, N, 28, 1)
    np.testing.assert_array_equal(data.values, values)
    np.testing.assert_array_equal(data.labels, labels)
    assert data.unweighted == (grid == "unweighted_sketch")
    # the kernel's wrapper reads grid as a float type
    assert data.grid == (None if data.unweighted else grid)
    about = ADAPTER.describe(CFG, {}, data)
    assert about["work_per_version"] == N
    assert about["kernel_shape"]["summary_entries"] == 8192
    assert about["kernel_shape"]["rows"] == N


def test_a_program_without_the_sketch_is_refused_before_any_row_is_made(
        monkeypatch):
    from rabit_tpu.learn import histogram

    monkeypatch.delattr(histogram, "sketch_summary")
    with pytest.raises(harness.Refused) as refused:
        ADAPTER.make_data(CFG, 1, 0, 1, 1, N)
    assert refused.value.code == 3


def test_the_file_states_the_published_widths_and_its_own_arithmetic():
    from rabit_tpu.learn import histogram

    same = ("features", "value_dtype", "missing_values", "max_bin",
            "max_depth", "grow_policy", "reg_lambda", "min_child_weight",
            "learning_rate", "subsample", "loss", "base_margin",
            "checkpoint_every_rounds", "rows_per_chip", "compute_dtype",
            "kernel", "step_op")
    assert {k: CFG[k] for k in same} == {k: HIGGS_CFG[k] for k in same}
    assert CFG["tree_method"] == "approx" and CFG["reduced"] == []
    assert CFG["architecture"] is None and CFG["learner"] == "gbdt_approx"
    assert CFG["sketch_eps"] == histogram.sketch_eps(256) == 1 / 2048
    assert CFG["summary_entries"] == histogram.summary_entries(256) == 8192
    # values as staged, bins as staged, five 4-byte quantities a row
    n = 32 << 20
    assert CFG["resident_bytes_per_chip"] == n * (28 * 4 + 32 * 4 + 20)
    assert CFG["resident_bytes_per_chip"] > 8.7e9 > 0.5 * 16e9
    assert sorted(CFG["staged_dtypes"]) == ["float32", "int32"]
    assert CFG["guarantees"][:5] == HIGGS_CFG["guarantees"]
    assert len(CFG["guarantees"]) == 8
    assert {"source", "sketch_eps", "summary_entries", "accumulation",
            "rows_per_chip", "data"} <= set(CFG["assumed"])
    limits = CFG["correct"]["limits"]
    assert {"cut_rank_err", "split_value_gap", "bin_gap"} <= set(limits)
    assert "cuts_gap" not in limits           # no cut is made up front
    assert CFG["sketch_eps"] < limits["cut_rank_err"]["limit"] \
        < 1.1 * CFG["sketch_eps"]
    for name in ("leaf_sum_rounded_rel_err", "split_regret"):
        assert limits[name]["limit"] == HIGGS_CFG["correct"]["limits"][
            name]["limit"]
    # set from this cell's own readings: other leaves under moving cuts
    assert limits["leaf_sum_rel_err"]["limit"] == 0.003
    assert all(len(v["why"]) > 20 for v in limits.values())


def test_the_manifest_gains_one_configuration_and_one_cell_on_one_chip():
    config = MANIFEST["configs"][-1]
    cell = MANIFEST["workloads"][-1]
    assert config["name"] == "gbdt-approx-f28-b256-d6" == cell["config"]
    assert config["source"] == CFG["source"] and len(config["source"]) <= 200
    assert config["reduced"] == [] and os.path.exists(
        os.path.join(ROOT, config["file"]))
    assert cell == {"name": CELL, "config": config["name"],
                    "traffic": "round-x1", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert len(MANIFEST["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    mine = [m for m in MANIFEST["per_layer"]
            if m["name"].startswith("gbdt_approx_")]
    assert len(mine) == 12 and MANIFEST["per_layer"][-12:] == mine
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
        spec = harness.read_json(os.path.join(
            ROOT, "perfbench", "layers", m["name"] + ".json"))
        assert spec["kind"] in readers.KINDS and spec["layer"] == m["layer"]
        assert m["name"].endswith("_roofline") == (spec["kind"] == "roofline")
    # the generic metrics that carry a list gained the cell at its end
    for name in ("loop_exposed_share_pct", "loop_wait_share_pct",
                 "loop_fetch_copy_exposed_share_pct",
                 "loop_dispatch_exposed_share_pct",
                 "commit_exposed_share_pct", "gbdt_device_scan_pct"):
        listed = next(m for m in MANIFEST["per_layer"]
                      if m["name"] == name)["workloads"]
        assert listed[-1] == CELL and listed.count(CELL) == 1
    # three whose cells test_perfbench_exposed.py lists exactly are read
    # in this cell under names of its own, by the same spans
    for name in ("allreduce_exposed_share_pct", "gbdt_split_exposed_share_pct",
                 "gbdt_partition_exposed_share_pct"):
        theirs = harness.read_json(os.path.join(
            ROOT, "perfbench", "layers", name + ".json"))
        own = harness.read_json(os.path.join(
            ROOT, "perfbench", "layers", "gbdt_approx_" + name.replace(
                "gbdt_", "") + ".json"))
        assert {k: own[k] for k in theirs} == theirs
        assert CELL not in next(m for m in MANIFEST["per_layer"]
                                if m["name"] == name)["workloads"]


SHAPE = {"rows": 1000, "features": 28, "nbin": 256, "max_depth": 6,
         "ops_dtype": "bfloat16", "summary_entries": 8192}


def _cost(kernel):
    return harness.load_module(os.path.join(
        ROOT, "perfbench", "kernels", kernel + ".py")).cost


def test_sketch_cost_against_a_hand_count():
    got = _cost("gbdt_sketch")(SHAPE)
    # a feature: its values once, a 28th of the weights, one add a value,
    # 8192 (value, rmin, rmax) entries written
    assert got["ops"] == 1000
    assert got["bytes"] == pytest.approx(1000 * 4 + 1000 * 4 / 28
                                         + 8192 * 12)
    # 28 of them are the issue's n (4 f + 4) and the summaries
    assert 28 * got["bytes"] == pytest.approx(1000 * (4 * 28 + 4)
                                              + 28 * 8192 * 12)
    peaks = harness.read_json(os.path.join(
        ROOT, "perfbench", "peaks.json"))["TPU v5 lite"]
    full = _cost("gbdt_sketch")({**SHAPE, "rows": CFG["rows_per_chip"]})
    floors = readers.floors(full, peaks)
    assert max(floors, key=floors.get) == "hbm"
    assert floors["hbm"] == pytest.approx(1.698e-4, rel=0.01)


def test_rebin_cost_against_a_hand_count():
    got = _cost("gbdt_rebin")(SHAPE)
    assert got["bytes"] == 8 * 1000 and got["ops"] == 1000 * 8
    peaks = harness.read_json(os.path.join(
        ROOT, "perfbench", "peaks.json"))["TPU v5 lite"]
    full = _cost("gbdt_rebin")({**SHAPE, "rows": CFG["rows_per_chip"]})
    floors = readers.floors(full, peaks)
    assert max(floors, key=floors.get) == "hbm"
    # 28 features: 8 n f bytes a round, 9.2 ms
    assert 28 * floors["hbm"] == pytest.approx(
        8 * CFG["rows_per_chip"] * 28 / 819e9)


def test_a_parent_without_the_spans_gives_the_new_readers_nothing():
    parent = {"path_stats": {"learn.step.total_s": 10.0,
                             "learn.versions": 5, "device_ops": 3}}
    loaded = {"bench_dir": os.path.join(ROOT, "perfbench"), "cfg": CFG}
    obs = readers.Observed(loaded, [{**parent, "device": {
        "kind": "TPU v5 lite"}, "kernel_shape": SHAPE, "trace": {
        "ops": {"gbdt_level/_hist_multi:custom-call": [1.0, 5]}}}])
    got = {m["name"]: obs.read(m, "per_layer") for m in MANIFEST["per_layer"]
           if m["name"].startswith("gbdt_approx_")}
    assert got.pop("gbdt_approx_hist_roofline") > 0
    # shares of a span that is not there read 0 and the counter's too:
    # the parent and a hist job re-sketch nothing
    assert got.pop("gbdt_approx_resketch_pct") == 0
    shares = {k: got.pop(k) for k in list(got) if k.endswith("share_pct")}
    assert set(shares.values()) == {0.0} and len(shares) == 6
    assert set(got.values()) == {None} and len(got) == 4


def test_committed_holds_every_trees_cuts_and_every_nodes_split():
    from rabit_tpu.learn import boosting

    tree = [boosting.TreeNode(feature=1, bin_threshold=2, left=1, right=2,
                              split=0.5),
            boosting.TreeNode(value=-1.0), boosting.TreeNode(value=1.0)]
    model = boosting.BoostedModel(
        cuts=np.zeros((3, 7), np.float32), trees=[tree, tree[1:2]],
        tree_method="approx",
        tree_cuts=[np.full((3, 7), 0.5, np.float32)] * 2)
    got = ADAPTER.committed(model)
    assert got["tree_cuts"].shape == (2, 3, 7)
    assert got["forest_split"].shape == got["forest_val"].shape == (2, 3)
    assert got["forest_split"][0, 0] == 0.5 and got["forest_int"][1, 1, 0] == -2
    assert json.dumps(sorted(got)) == json.dumps(
        ["cuts", "forest_int", "forest_split", "forest_val", "has_missing",
         "tree_cuts"])

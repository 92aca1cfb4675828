"""The sparse boosting cell's rows, configuration and manifest entries:
the generator holds its stated shares (entries a row, group widths, the
absent share, the positives, one layout for every seed), the
configuration keeps the source's shapes, and what the manifest gained is
well formed.  (The manifest's total counts are the benchmark's own to
assert.)"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

CELL = "gbdt-allstate-round-x1"
CONFIG = "gbdt-hist-allstate-f4227-sparse-d6"
MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
CFG = harness.read_json(os.path.join(ROOT, "perfbench", "configs",
                                     CONFIG + ".json"))
adapter = harness.load_module(os.path.join(ROOT, "perfbench", "learners",
                                           "gbdt_sparse.py"))
N = 1 << 16


@pytest.fixture(scope="module")
def made():
    schema = adapter.Schema(CFG["numeric_columns"], CFG["onehot_groups"])
    rows, labels, present = adapter.make_rows(
        2 ** 31 + 5, 0, N, schema, CFG["positive_share"], 4)
    return schema, rows, labels, present


def test_schema_is_the_sources_shape_and_the_same_for_every_seed(made):
    schema = made[0]
    assert schema.f == CFG["features"] == 4227
    assert schema.numeric == 15 and len(schema.widths) == 16
    assert schema.widths.sum() == 4212
    assert sorted(schema.widths)[-3:] == [75, 1300, 2738]
    assert schema.slots == 31 and schema.width() == CFG["ell_width"] == 32
    again = adapter.Schema(CFG["numeric_columns"], CFG["onehot_groups"])
    for name in ("first", "numeric_present", "group_present", "levels",
                 "label_groups"):
        np.testing.assert_array_equal(getattr(schema, name),
                                      getattr(again, name))


def test_rows_hold_their_stated_shares(made):
    schema, (idx, val, counts), labels, present = made
    assert idx.shape == val.shape == (N, 32) and idx.dtype == np.int32
    assert present == counts.sum()
    assert 24 <= counts.min() and counts.max() <= 31
    assert 29.5 < counts.mean() < 30.7
    slot = np.arange(32)[None, :]
    held = slot < counts[:, None]
    # padding: the index one past the last column, value 0
    assert (idx[~held] == schema.f).all() and (val[~held] == 0).all()
    assert (idx[held] < schema.f).all() and (idx[held] >= 0).all()
    # a row's entries in column order, a column at most once
    step = np.diff(np.where(held, idx, schema.f + slot), axis=1)
    assert (step > 0).all()
    # at most one indicator of a group, and an indicator reads 1
    group_of = np.searchsorted(schema.first, idx, side="right") - 1
    indicator = held & (idx >= schema.numeric)
    assert (val[indicator] == 1.0).all()
    for g in range(len(schema.widths)):
        mine = (indicator & (group_of == g)).sum(axis=1)
        assert mine.max() == 1
        # the field unknown in a few per cent of the rows
        assert abs(mine.mean() - schema.group_present[g]) < 0.01
        assert 0.93 <= schema.group_present[g] <= 0.99
    for j in range(schema.numeric):
        share = (held & (idx == j)).any(axis=1).mean()
        assert abs(share - schema.numeric_present[j]) < 0.01
    # a power law within a group: from a third of the rows to a handful
    freq = np.bincount(idx[indicator], minlength=schema.f)[schema.numeric:] \
        / N
    assert freq.max() > 1 / 3 and 0 < freq[freq > 0].min() < 1e-4
    widest = schema.first[np.argmax(schema.widths)]
    assert 0.05 < freq[widest - schema.numeric] < 0.1
    # claims: 0.7% of the rows
    assert set(np.unique(labels)) == {0.0, 1.0}
    assert 0.005 < labels.mean() < 0.009


def test_rows_are_a_function_of_seed_and_shard_alone():
    schema = adapter.Schema(*adapter.REHEARSAL_WIDTHS)
    n = (1 << 16) + 1000
    a = adapter.make_rows(7, 1, n, schema, 0.2, 1)
    b = adapter.make_rows(7, 1, n, schema, 0.2, 5)
    for x, y in zip(a[0] + (a[1],), b[0] + (b[1],)):
        np.testing.assert_array_equal(x, y)
    c = adapter.make_rows(8, 1, n, schema, 0.2, 5)
    d = adapter.make_rows(7, 2, n, schema, 0.2, 5)
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][0], d[0][0])
    assert a[0][0].shape[1] == schema.width() == 16


def test_the_label_reads_presence_with_both_signs():
    schema = adapter.Schema(*adapter.REHEARSAL_WIDTHS)
    z = np.zeros((4, 6), np.float32)
    cat = np.zeros((4, 4), np.int64) + 5
    known = np.ones((4, 4), bool)
    known[1, 3] = False                 # lowers
    known[2, 0] = False                 # raises
    cat[3, 0] = 0                       # a common category raises
    base, absent3, absent0, common = adapter.logit_of(z, cat, known, schema)
    assert absent3 < base < absent0 and common > base


def test_manifest_entries_of_this_cell_are_well_formed():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    cell, config = cells[CELL], configs[CONFIG]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "round-x1",
                    "chips": 1, "why": cell["why"]}
    assert MANIFEST["workloads"][-1] is cell
    assert MANIFEST["configs"][-1] is config
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    assert len(CELL) <= 64 and len(CONFIG) <= 64
    assert config["source"] == CFG["source"]
    assert config["reduced"] == CFG["reduced"]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    mine = [m for m in MANIFEST["per_layer"]
            if m["name"].startswith("gbdt_sparse_")]
    assert len(mine) == 9
    assert MANIFEST["per_layer"][-9:] == mine
    for m in mine:
        assert m["workloads"] == [CELL] and len(m["name"]) <= 64
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "layers", m["name"] + ".json"))
        assert m["moves"] == ("setup_s" if m["name"].endswith(
            ("stage_bucket_s", "stage_bin_s")) else "rows_per_s")
    generic = [m["name"] for m in MANIFEST["per_layer"]
               if CELL in m.get("workloads", []) and m not in mine]
    assert sorted(generic) == sorted([
        "loop_exposed_share_pct", "loop_wait_share_pct",
        "loop_fetch_copy_exposed_share_pct",
        "loop_dispatch_exposed_share_pct", "commit_exposed_share_pct",
        "gbdt_device_scan_pct", "gbdt_live_channel_pct"])
    for m in MANIFEST["per_layer"]:
        if m["name"] in generic:
            assert m["workloads"][-1] == CELL


def test_configuration_keeps_the_sources_shapes():
    assert CFG["learner"] == "gbdt_sparse" and CFG["architecture"] is None
    assert (CFG["features"], CFG["max_bin"], CFG["max_depth"]) == (4227, 256,
                                                                   6)
    assert CFG["numeric_columns"] + sum(CFG["onehot_groups"]) == 4227
    assert (CFG["reg_lambda"], CFG["min_child_weight"], CFG["learning_rate"],
            CFG["subsample"], CFG["loss"]) == (1.0, 1.0, 0.3, 1.0,
                                               "logistic")
    assert CFG["missing_values"] is True
    assert CFG["compute_dtype"] == "bfloat16"
    assert CFG["checkpoint_every_rounds"] == 1
    assert set(CFG["reduced"]) <= {"rows_per_chip"}
    assert CFG["rows_per_chip"] in (1 << 24, 1 << 25)
    assert (CFG["rows_per_chip"] == 1 << 25) == (CFG["reduced"] == [])
    for key in CFG["reduced"]:
        assert key in CFG["assumed"]
    bosch = harness.read_json(os.path.join(
        ROOT, "perfbench", "configs", "gbdt-hist-bosch-f968-b256-d6.json"))
    assert CFG["guarantees"][:8] == bosch["guarantees"]
    assert CFG["guarantees"][8:] == [
        "a sparse shard grows the forest its dense form with NaN would"]
    assert list(CFG["correct"]["limits"]) == list(bosch["correct"]["limits"])
    assert CFG["correct"]["control_grid"] == "float8_e4m3fn"
    for name, spec in CFG["correct"]["limits"].items():
        assert spec["why"] and spec["limit"] >= 0, name
    # the bytes as staged: two copies of the entries and five words a row
    n, width = CFG["rows_per_chip"], CFG["ell_width"]
    assert 0.25 * 16e9 < CFG["resident_bytes_per_chip"] < 0.75 * 16e9
    assert CFG["resident_bytes_per_chip"] >= n * width * 4 * 2 + n * 20


def test_kernel_cost_counts_entries_and_not_products():
    cost = harness.load_module(os.path.join(
        ROOT, "perfbench", "kernels", "hist_sparse.py")).cost
    shape = {"rows": 1000, "features": 4227, "ell_width": 32,
             "present_entries": 30000, "flat_bins": 12264, "max_depth": 6,
             "ops_dtype": "bfloat16"}
    got = cost(shape)
    assert got["ops"] == 2 * 30000
    assert got["bytes"] == 1000 * (4 * 32 + 12) + 63 / 6 * 12264 * 8
    wider = cost(dict(shape, features=8000))
    assert wider == got                 # columns cost nothing: entries do


def test_the_parent_of_the_cells_pr_is_refused(monkeypatch):
    from rabit_tpu.learn import histogram

    monkeypatch.delattr(histogram, "stage_entries")
    with pytest.raises(SystemExit):
        adapter.make_data(CFG, 1, 0, 1, 1, rows=64)

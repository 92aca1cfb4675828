"""The wide boosting cell's data: a function of ``(seed, shard)`` alone,
entries absent station by station at the stated shares, labels that
follow the stated function, the configuration's widths, and the
kernel's cost against a hand count."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

ADAPTER = harness.load_module(os.path.join(
    ROOT, "perfbench", "learners", "gbdt_missing.py"))
CFG = harness.read_json(os.path.join(
    ROOT, "perfbench", "configs", "gbdt-hist-bosch-f968-b256-d6.json"))
HIGGS = harness.read_json(os.path.join(
    ROOT, "perfbench", "configs", "gbdt-hist-f28-b256-d6.json"))
N = (1 << 15) + 700                    # two generator blocks
SHAPE = (968, 52, 0.19, 0.0058)
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def rows():
    return ADAPTER.make_rows(SEED, 0, N, *SHAPE, 1)


@pytest.fixture(scope="module")
def line():
    return ADAPTER.Line(*SHAPE[:3])


@pytest.mark.parametrize("threads", [2, 5])
def test_rows_do_not_depend_on_the_thread_count(rows, threads):
    values, labels, present = ADAPTER.make_rows(SEED, 0, N, *SHAPE, threads)
    np.testing.assert_array_equal(values, rows[0])
    np.testing.assert_array_equal(labels, rows[1])
    assert present == rows[2] == np.count_nonzero(~np.isnan(values))


@pytest.mark.parametrize("seed,shard", [(SEED + 1, 0), (SEED, 1)],
                         ids=["another-seed", "another-shard"])
def test_another_seed_or_shard_gives_other_rows(rows, seed, shard):
    values, labels, _ = ADAPTER.make_rows(seed, shard, N, *SHAPE, 2)
    assert not np.array_equal(np.isnan(values[:1000]),
                              np.isnan(rows[0][:1000]))
    assert not np.array_equal(labels, rows[1])


def test_the_layout_is_52_uneven_stations_and_the_same_for_every_seed(line):
    assert line.width.sum() == 968 and len(line.width) == 52
    assert line.width.min() >= 2 and line.width.max() >= 40
    assert len(np.unique(line.width)) > 10
    assert set(line.line_of) == {0, 1, 2, 3}
    assert line.visit.min() == pytest.approx(0.004)
    assert line.visit.max() == pytest.approx(0.995)
    assert float(line.visit @ line.width) / 968 == pytest.approx(0.19)
    again = ADAPTER.Line(*SHAPE[:3])
    np.testing.assert_array_equal(again.width, line.width)
    np.testing.assert_array_equal(again.label_cols, line.label_cols)


def test_entries_are_absent_station_by_station(rows, line):
    values = rows[0]
    here = ~np.isnan(values)
    first = np.concatenate([[0], np.cumsum(line.width)])
    for s in range(52):
        block = here[:, first[s]:first[s + 1]]
        assert (block == block[:, :1]).all(), s       # whole or not at all
    share = here.mean(axis=0)
    assert share.min() < 0.01 and share.max() > 0.98
    assert here.mean() == pytest.approx(0.19, abs=0.01)
    # stations of a line go together more than independent draws would
    went = here[:, first[:-1]]
    common = np.argsort(-line.visit)[:12]
    same = [(a, b) for a in common for b in common
            if a < b and line.line_of[a] == line.line_of[b]]
    assert same and np.mean([np.corrcoef(went[:, a], went[:, b])[0, 1]
                             for a, b in same]) > 0.1


def test_values_lie_in_the_unit_box_with_few_levels_in_many_columns(rows,
                                                                    line):
    values = rows[0]
    assert values.dtype == np.float32
    assert np.nanmin(values) >= -1 and np.nanmax(values) <= 1
    levels = np.array([len(np.unique(col[~np.isnan(col)]))
                       for col in values.T])
    assert line.few.mean() == 0.5
    assert levels[line.few].max() <= 129
    assert {3, 5, 9, 17, 33} <= set(levels[line.few])
    # a column that is visited often and is not held to levels has
    # about as many values as parts
    common = (~np.isnan(values)).mean(axis=0) > 0.5
    assert levels[common & ~line.few].min() > 10000


def test_labels_follow_the_stated_function(rows, line):
    """A part's label is a Bernoulli draw of the logistic of
    ``logit_of`` of its measurements (0 where absent) and its visits,
    plus the bias that gives 0.58%: the measurements and the visits
    that the function reads carry signal, others none."""
    values, labels, _ = rows
    assert set(np.unique(labels)) <= {0.0, 1.0}
    assert labels.mean() == pytest.approx(0.0058, abs=0.002)
    first = np.concatenate([[0], np.cumsum(line.width)])
    went = ~np.isnan(values[:, first[:-1]])
    z = np.nan_to_num(values[:, line.label_cols])
    logit = ADAPTER.logit_of(z, went[:, line.label_stations].astype(
        np.float32))
    assert np.corrcoef(logit, labels)[0, 1] > 0.05
    assert logit[labels > 0].mean() > logit[labels == 0].mean() + 1.0
    # the visits carry signal of both signs
    rate = [labels[went[:, s]].mean() / labels[~went[:, s]].mean()
            for s in line.label_stations[:2]]
    assert rate[0] > 1.5 and rate[1] < 0.75


def test_a_rehearsal_off_the_chip_takes_its_own_widths(capsys):
    data = ADAPTER.make_data(CFG, SEED, 0, 1, 2, rows=2048)
    assert data.f == ADAPTER.REHEARSAL_WIDTHS[0] == 24
    assert "REHEARSAL_WIDTHS" in capsys.readouterr().err
    assert data.values.shape == (2048, 24)
    assert np.isnan(data.values).mean() == pytest.approx(0.55, abs=0.1)
    assert data.present == np.count_nonzero(~np.isnan(data.values))
    assert ADAPTER.describe(CFG, {}, data)["kernel_shape"] == {
        "rows": 2048, "features": 24, "present_entries": data.present,
        "nbin": 256, "max_depth": 3, "ops_dtype": "bfloat16"}


def test_kernel_cost_against_a_hand_count():
    cost = harness.load_module(os.path.join(
        ROOT, "perfbench", "kernels", "hist_fused_missing.py")).cost
    got = cost({"rows": 1000, "features": 968, "present_entries": 183920,
                "nbin": 256, "max_depth": 6, "ops_dtype": "bfloat16"})
    # grad and hess of every PRESENT entry into one bin
    assert got["ops"] == 2 * 183920
    # the staged bins whole (the absent entries' codes are read too),
    # node + grad + hess a row, a sixth of a round's 63 histograms
    assert got["bytes"] == 1000 * (968 * 4 + 12) + 63 / 6 * 968 * 256 * 8
    peaks = harness.read_json(os.path.join(
        ROOT, "perfbench", "peaks.json"))["TPU v5 lite"]
    from perfbench import readers

    n = CFG["rows_per_chip"]
    full = cost({"rows": n, "features": 968, "nbin": 256, "max_depth": 6,
                 "present_entries": round(0.19 * n * 968),
                 "ops_dtype": "bfloat16"})
    floors = readers.floors(full, peaks)
    assert max(floors, key=floors.get) == "hbm"
    assert floors["hbm"] == pytest.approx(5.64e-3, rel=0.01)


def test_the_file_states_the_sources_shapes_and_cuts_nothing():
    assert (CFG["features"], CFG["max_bin"], CFG["max_depth"],
            CFG["reg_lambda"], CFG["min_child_weight"], CFG["learning_rate"],
            CFG["subsample"], CFG["loss"], CFG["grow_policy"]) == (
                968, 256, 6, 1.0, 1.0, 0.3, 1.0, "logistic", "depthwise")
    assert CFG["rows_per_chip"] == 1183747 and CFG["reduced"] == []
    assert CFG["missing_values"] is True and CFG["missing_share"] == 0.81
    assert CFG["architecture"] is None and CFG["value_dtype"] == "float32"
    assert {"source_figures", "rows_per_chip", "cut_sample_rows", "data",
            "same_work_every_round"} <= set(CFG["assumed"])
    from rabit_tpu.learn import boosting, histogram

    assert CFG["cut_sample_rows"] == boosting.CUT_SAMPLE_ROWS
    # bins as staged (121 groups of 8, no padding feature) plus five
    # 4-byte quantities a row: over a quarter of the chip's 16 GB
    assert histogram.staged_features(968, 256) == 968
    assert CFG["resident_bytes_per_chip"] == 1183747 * (968 * 4 + 20)
    assert CFG["resident_bytes_per_chip"] > 0.25 * 16e9
    assert CFG["staged_dtypes"] == HIGGS["staged_dtypes"] == ["int32"]


def test_guarantees_and_limits_are_the_higgs_files_and_more():
    assert CFG["guarantees"][:len(HIGGS["guarantees"])] == HIGGS["guarantees"]
    added = " ".join(CFG["guarantees"][len(HIGGS["guarantees"]):])
    for word in ("no bin", "never imputed", "default direction", "predict"):
        assert word in added
    assert set(CFG["correct"]["limits"]) == set(HIGGS["correct"]["limits"])
    assert CFG["correct"]["control_grid"] == "float8_e4m3fn"
    for name, spec in HIGGS["correct"]["limits"].items():
        if spec["limit"] == 0:
            assert CFG["correct"]["limits"][name]["limit"] == 0, name

"""The boosting cell's data: a function of ``(seed, shard)`` alone,
labels that follow the stated function, and the kernel's cost against a
hand count."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

ADAPTER = harness.load_module(os.path.join(
    ROOT, "perfbench", "learners", "gbdt.py"))
CFG = harness.read_json(os.path.join(
    ROOT, "perfbench", "configs", "gbdt-hist-f28-b256-d6.json"))
N = (1 << 18) + 1000                   # two generator blocks


@pytest.fixture(scope="module")
def rows():
    return ADAPTER.make_rows(2 ** 31 + 5, 0, N, 28, 1)


@pytest.mark.parametrize("threads", [2, 5])
def test_rows_do_not_depend_on_the_thread_count(rows, threads):
    values, labels = ADAPTER.make_rows(2 ** 31 + 5, 0, N, 28, threads)
    np.testing.assert_array_equal(values, rows[0])
    np.testing.assert_array_equal(labels, rows[1])


@pytest.mark.parametrize("seed,shard", [(2 ** 31 + 6, 0), (2 ** 31 + 5, 1)],
                         ids=["another-seed", "another-shard"])
def test_another_seed_or_shard_gives_other_rows(rows, seed, shard):
    values, labels = ADAPTER.make_rows(seed, shard, N, 28, 2)
    assert not np.array_equal(values[:1000], rows[0][:1000])
    assert not np.array_equal(labels[:1000], rows[1][:1000])


def test_features_have_distinct_scales_and_no_missing_value(rows):
    values, labels = rows
    assert values.dtype == np.float32 and np.isfinite(values).all()
    spread = np.quantile(np.abs(values), 0.5, axis=0)
    assert spread.max() / spread.min() > 1e5
    assert set(np.unique(labels)) == {0.0, 1.0}
    assert 0.3 < labels.mean() < 0.7
    assert CFG["missing_values"] is False and CFG["features"] == 28


def test_labels_follow_the_stated_function(rows):
    """A row's label is a Bernoulli draw of the logistic of ``logit_of``
    of its normal scores: rebuilt from the generator's own stream."""
    rng = np.random.default_rng([2 ** 31 + 5, 0, N, 28, 0])
    z = rng.standard_normal((ADAPTER.GEN_BLOCK, 28), dtype=np.float32)
    p = 1.0 / (1.0 + np.exp(-ADAPTER.logit_of(z)))
    draw = rng.random(ADAPTER.GEN_BLOCK, dtype=np.float32) < p
    np.testing.assert_array_equal(draw, rows[1][:ADAPTER.GEN_BLOCK] > 0)
    # twelve features carry it, sixteen are noise
    assert abs(np.corrcoef(z[:, 5], draw)[0, 1]) > 0.05
    assert abs(np.corrcoef(z[:, 20], draw)[0, 1]) < 0.01


def test_kernel_cost_against_a_hand_count():
    cost = harness.load_module(os.path.join(
        ROOT, "perfbench", "kernels", "hist_fused_multi.py")).cost
    got = cost({"rows": 1000, "features": 28, "nbin": 256, "max_depth": 6,
                "ops_dtype": "bfloat16"})
    # grad and hess of every row into one bin of each feature
    assert got["ops"] == 1000 * 28 * 2
    # bins int32, node + grad + hess a row, and a sixth of the 63 node
    # histograms a round writes (28 x 256 x 2 float32 each)
    assert got["bytes"] == 1000 * (28 * 4 + 12) + 63 / 6 * 28 * 256 * 8
    assert got["ops_dtype"] == "bfloat16"
    # at the cell's size the HBM bound holds it: 5.08 ms a call
    peaks = harness.read_json(os.path.join(
        ROOT, "perfbench", "peaks.json"))["TPU v5 lite"]
    from perfbench import readers

    full = cost({"rows": CFG["rows_per_chip"], "features": 28, "nbin": 256,
                 "max_depth": 6, "ops_dtype": "bfloat16"})
    floors = readers.floors(full, peaks)
    assert max(floors, key=floors.get) == "hbm"
    assert floors["hbm"] == pytest.approx(5.08e-3, rel=0.01)


def test_the_file_states_the_published_widths():
    assert (CFG["max_bin"], CFG["max_depth"], CFG["reg_lambda"],
            CFG["min_child_weight"], CFG["learning_rate"], CFG["subsample"],
            CFG["loss"]) == (256, 6, 1.0, 1.0, 0.3, 1.0, "logistic")
    assert CFG["rows_per_chip"] == 32 << 20 and CFG["reduced"] == []
    assert {"rows_per_chip", "cut_sample_rows", "data"} <= set(CFG["assumed"])
    from rabit_tpu.learn import boosting

    assert CFG["cut_sample_rows"] == boosting.CUT_SAMPLE_ROWS
    # bins as staged plus five 4-byte quantities a row
    assert CFG["resident_bytes_per_chip"] == (32 << 20) * (32 * 4 + 20)
    assert json.dumps(CFG["correct"]["control_grid"]) == '"float8_e4m3fn"'

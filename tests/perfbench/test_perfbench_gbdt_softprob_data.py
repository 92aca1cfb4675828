"""The multi-class boosting cell's data: rows on Covertype's schema as a
function of ``(seed, shard)`` alone, one wilderness and one soil column
set a row, the classes' shares, labels that follow the one stated
function whatever the seed, what the one-hot columns do to the cuts,
and the softmax gradient's cost against a hand count."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

ADAPTER = harness.load_module(os.path.join(
    ROOT, "perfbench", "learners", "gbdt_softprob.py"))
CFG = harness.read_json(os.path.join(
    ROOT, "perfbench", "configs", "gbdt-softprob-covtype-f54-k7-d6.json"))
N = (1 << 18) + 1000                   # two generator blocks
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def rows():
    return ADAPTER.make_rows(SEED, 0, N, 54, 1)


@pytest.mark.parametrize("threads", [2, 5])
def test_rows_do_not_depend_on_the_thread_count(rows, threads):
    values, labels = ADAPTER.make_rows(SEED, 0, N, 54, threads)
    np.testing.assert_array_equal(values, rows[0])
    np.testing.assert_array_equal(labels, rows[1])


@pytest.mark.parametrize("seed,shard", [(SEED + 1, 0), (SEED, 1)],
                         ids=["another-seed", "another-shard"])
def test_another_seed_or_shard_gives_other_rows(rows, seed, shard):
    values, labels = ADAPTER.make_rows(seed, shard, N, 54, 2)
    assert not np.array_equal(values[:1000], rows[0][:1000])
    assert not np.array_equal(labels[:1000], rows[1][:1000])


def test_another_width_than_the_schemas_is_refused():
    with pytest.raises(ValueError, match="54 columns"):
        ADAPTER.make_rows(SEED, 0, 100, 28, 1)


def test_columns_are_covertypes(rows):
    """Ten quantitative columns on integer grids, then one of four
    wilderness columns and one of forty soil columns set a row."""
    values, _labels = rows
    assert values.shape == (N, 54) and values.dtype == np.float32
    assert np.isfinite(values).all() and CFG["missing_values"] is False
    quant = values[:, :10]
    np.testing.assert_array_equal(quant, np.rint(quant))
    for j, levels in enumerate(ADAPTER.LEVELS):
        assert 200 <= levels <= 4000 or levels == 255
        assert quant[:, j].min() >= 0 and quant[:, j].max() <= levels - 1
        assert len(np.unique(quant[:, j])) > min(120, levels // 3)
    wild, soil = values[:, 10:14], values[:, 14:]
    assert set(np.unique(values[:, 10:])) == {0.0, 1.0}
    np.testing.assert_array_equal(wild.sum(axis=1), 1.0)
    np.testing.assert_array_equal(soil.sum(axis=1), 1.0)
    np.testing.assert_allclose(wild.mean(axis=0), ADAPTER.WILD_SHARE,
                               atol=3e-3)
    # a power law: the first five soil types hold three quarters of the
    # rows, a dozen columns are nearly empty
    share = soil.mean(axis=0)
    assert share[:5].sum() > 0.7
    assert (share < 3e-4).sum() >= 12 and (share[-12:] > 0).all()
    np.testing.assert_allclose(share, ADAPTER.soil_share(), atol=2e-3)


def test_class_shares_are_covertypes(rows):
    _values, labels = rows
    assert set(np.unique(labels)) == set(map(float, range(7)))
    share = np.bincount(labels.astype(int), minlength=7) / N
    np.testing.assert_allclose(share, ADAPTER.TARGET_SHARE, atol=4e-3)
    assert share[:2].sum() > 0.84 and share[3] < 0.007   # 85%, and 0.5%
    assert CFG["num_class"] == 7 and CFG["features"] == 54


@pytest.mark.parametrize("seed", [SEED, 77])
def test_labels_follow_the_one_stated_function_whatever_the_seed(seed):
    """A row's label is a draw from the softmax of ``class_logits`` of
    its normal scores, its wilderness area and its soil type: rebuilt
    from the generator's own stream, for two seeds, by the same
    function."""
    n = 5000
    values, labels = ADAPTER.make_rows(seed, 0, n, 54, 1)
    rng = np.random.default_rng([seed, 0, n, 54, 0])
    z = rng.standard_normal((n, 10), dtype=np.float32)
    u = rng.random((n, 3), dtype=np.float32)
    wild, soil = values[:, 10:14].argmax(axis=1), values[:, 14:].argmax(axis=1)
    logits = ADAPTER.class_logits(z, wild, soil)
    assert logits.shape == (n, 7)
    p = np.exp(logits - logits.max(axis=1, keepdims=True)).astype(np.float64)
    edges = np.cumsum(p, axis=1) / p.sum(axis=1, keepdims=True)
    want = np.minimum((edges < u[:, 2:3]).sum(axis=1), 6)
    assert (want == labels).mean() > 0.999    # float32 ties at an edge
    # elevation (column 0) carries most: high rows are spruce or
    # krummholz, low rows ponderosa, cottonwood or douglas-fir
    high, low = z[:, 0] > 1.0, z[:, 0] < -1.0
    assert np.isin(labels[high], (0, 6)).mean() > 0.8
    assert np.isin(labels[low], (2, 3, 5)).mean() > 0.35
    # a column the function does not read says nothing
    assert abs(np.corrcoef(z[:, 8], labels == 1)[0, 1]) < 0.05


def test_what_one_hot_columns_do_to_the_cuts(rows):
    """The quantiles of an indicator column are zeros and then ones: a
    column set in under 1/256 of the rows has one distinct cut and one
    bin, the others two bins and 254 empty ones; a gridded column of
    fewer levels than bins has duplicate cuts."""
    from rabit_tpu.learn import histogram

    values, _labels = rows
    cuts = histogram.quantile_cuts(values, 256)
    bins = histogram.apply_cuts(values, cuts)
    share = values[:, 14:].mean(axis=0)
    for j in range(40):
        used = len(np.unique(bins[:, 14 + j]))
        distinct = len(np.unique(cuts[14 + j]))
        if share[j] < 1 / 256 - 1e-3:
            assert (used, distinct) == (1, 1), (j, share[j])
        elif share[j] > 1 / 256 + 1e-3:
            assert (used, distinct) == (2, 2), (j, share[j])
    assert sum(len(np.unique(bins[:, 14 + j])) == 1 for j in range(40)) >= 12
    assert len(np.unique(cuts[2])) < 255      # slope: 200 levels
    assert len(np.unique(cuts[0])) == 255     # elevation: 2,000
    assert (cuts[5] == 0).sum() > 8           # a distance piled up at 0


def test_softmax_gradient_cost_against_a_hand_count():
    kernel = harness.load_module(os.path.join(
        ROOT, "perfbench", "kernels", "gbdt_softmax_grad.py"))
    got = kernel.round_cost({"rows": 1000, "num_class": 7,
                             "ops_dtype": "bfloat16"})
    # 7 margins and a label read, 7 (grad, hess) pairs written: 88 B
    assert got["bytes"] == 1000 * 88 and got["ops"] == 1000 * 56
    # the reader counts an operation a call: a share an operation
    share = kernel.cost({"rows": 1000, "num_class": 7,
                         "ops_dtype": "bfloat16"})
    assert share["bytes"] * kernel.PASSES == got["bytes"]
    cost = kernel.round_cost
    from perfbench import readers

    peaks = harness.read_json(os.path.join(
        ROOT, "perfbench", "peaks.json"))["TPU v5 lite"]
    full = cost({"rows": CFG["rows_per_chip"], "num_class": 7,
                 "ops_dtype": "bfloat16"})
    floors = readers.floors(full, peaks)
    assert max(floors, key=floors.get) == "hbm"
    assert floors["hbm"] == pytest.approx(0.90e-3, rel=0.01)


def test_the_file_states_the_published_settings():
    assert (CFG["max_bin"], CFG["max_depth"], CFG["reg_lambda"],
            CFG["min_child_weight"], CFG["learning_rate"], CFG["subsample"],
            CFG["loss"], CFG["num_class"], CFG["tree_method"],
            CFG["base_score"], CFG["grow_policy"]) == (
                256, 6, 1.0, 1.0, 0.3, 1.0, "softprob", 7, "hist", 0.5,
                "depthwise")
    assert CFG["rows_per_chip"] == 8 << 20 and CFG["reduced"] == []
    assert CFG["architecture"] is None
    assert {"source", "rows_per_chip", "cut_sample_rows", "data"} <= set(
        CFG["assumed"])
    from rabit_tpu.learn import boosting

    assert CFG["cut_sample_rows"] == boosting.CUT_SAMPLE_ROWS
    # bins as staged (54 columns padded to 56), 7 margins, 7 (grad,
    # hess) pairs, 7 node ids and a label a row
    assert CFG["resident_bytes_per_chip"] == (8 << 20) * (
        56 * 4 + 7 * 4 + 14 * 4 + 7 * 4 + 4)
    assert CFG["correct"]["control_grid"] == "float8_e4m3fn"
    assert CFG["correct"]["control_objective"] == ADAPTER.ONE_VS_REST
    assert len(CFG["guarantees"]) == 6
    assert "one tree a class" in CFG["guarantees"][-1]

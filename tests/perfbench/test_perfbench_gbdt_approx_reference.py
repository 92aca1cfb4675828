"""The plain reference of the ``approx`` cell against brute force in
float64 (the weighted rank of given cuts, margins by split value), and
against the program on seeded rows where the two must agree: the
program's committed cuts read a rank error of rounding size, a sketch
that is not the stated one reads its own error."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402
from perfbench.reference import gbdt_approx as refa  # noqa: E402

N, F, NBIN = 3000, 6, 16


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(71)
    values = rng.standard_normal((N, F)).astype(np.float32)
    values[:, 1] = np.round(values[:, 1] * 2) / 2          # ties
    values[rng.random(N) < 0.3, 2] = np.nan
    values[:, 5] = np.nan                                  # nobody has it
    logit = 2.0 * values[:, 0] * values[:, 3] + values[:, 1]
    labels = (rng.random(N) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return values, labels


def test_reference_imports_nothing_of_the_program():
    for name in ("gbdt_approx.py", "gbdt.py"):
        text = open(os.path.join(ROOT, "perfbench", "reference", name)).read()
        assert "import rabit_tpu" not in text and "from rabit_tpu" not in text
        assert "pallas" not in text


def _brute(values, w, cuts):
    """Per feature and cut, float64: the weight of the present rows
    below the cut, at or below it, and all of it."""
    w = w.astype(np.float64)
    out = np.zeros(cuts.shape + (3,))
    for j in range(values.shape[1]):
        v = values[:, j].astype(np.float64)
        present = ~np.isnan(v)
        for i, cut in enumerate(cuts[j].astype(np.float64)):
            out[j, i] = (w[present & (v < cut)].sum(),
                         w[present & (v <= cut)].sum(), w[present].sum())
    return out


@pytest.mark.parametrize("weights", ["uniform", "heavy"])
def test_weighted_ranks_of_given_cuts_against_a_float64_loop(rows, weights):
    values, labels = rows
    rng = np.random.default_rng(72)
    w = (np.full(N, 0.25) if weights == "uniform"
         else rng.pareto(1.2, N)).astype(np.float32)
    # cuts on values of the data (ties!), on values between, repeated
    cuts = np.sort(np.concatenate([
        np.nan_to_num(values[rng.integers(0, N, (8,)), :4].T),
        rng.standard_normal((4, 7)).astype(np.float32)], axis=1), axis=1)
    cuts = np.concatenate([cuts, np.zeros((2, 15), np.float32)])
    cuts[1, 3] = cuts[1, 4]
    shard = refa.Rows(values, labels)
    under = shard.weight_under([np.asarray(w)], cuts)
    want = _brute(values, w, cuts)
    below = np.cumsum(under[1], axis=1)[:, :15]
    upto = np.cumsum(under[0], axis=1)[:, :15]
    np.testing.assert_allclose(below, want[:, :, 0], rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(upto, want[:, :, 1], rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(under[1].sum(axis=1), want[:, 0, 2],
                               rtol=1e-5)
    assert not under[:, 5].any()                  # no row has it
    # the number itself, from the loop's sums
    target = np.arange(1, 16) / 16.0
    live = want[:, 0, 2] > 0
    gap = np.maximum(np.maximum(
        want[live, :, 0] / want[live, :1, 2] - target,
        target - want[live, :, 1] / want[live, :1, 2]), 0).max()
    assert refa.cut_rank_err(under) == pytest.approx(gap, abs=2e-6)


def test_rank_error_is_zero_inside_a_tie_and_counts_what_lies_between():
    """Four values of weight 1, 1, 6, 2 (ten in all) under nine cuts:
    a cut on the heavy value holds every target its tie spans."""
    values = np.array([[0.0], [1.0], [2.0], [3.0]], np.float32)
    w = np.array([1, 1, 6, 2], np.float32)
    shard = refa.Rows(values, np.zeros(4, np.float32))
    right = np.array([[0, 1, 2, 2, 2, 2, 2, 2, 3]], np.float32)
    assert refa.cut_rank_err(shard.weight_under([w], right)) == 0.0
    early = np.sort(np.where(np.arange(9) == 7, 1.0, right))
    # sorted, the third cut sits on 1.0: wants 0.3, spans [0.1, 0.2]
    assert refa.cut_rank_err(shard.weight_under([w], early)) \
        == pytest.approx(0.1)
    # every cut on the lightest value: the last wants 0.9, spans [0, 0.1]
    assert refa.cut_rank_err(shard.weight_under(
        [w], np.zeros((1, 9), np.float32))) == pytest.approx(0.8)


def _train(rows, **kw):
    import rabit_tpu
    from rabit_tpu.learn import boosting

    values, labels = rows
    rabit_tpu.init(rabit_engine="empty")
    try:
        return boosting.train(values, labels, num_round=4, max_depth=3,
                              nbin=NBIN, use_pallas=False,
                              tree_method="approx", **kw)
    finally:
        rabit_tpu.finalize()


def _committed(model):
    adapter = harness.load_module(os.path.join(
        ROOT, "perfbench", "learners", "gbdt_approx.py"))
    return adapter.committed(model)


def test_margins_by_split_value_equal_the_programs_predict(rows):
    values, labels = rows
    model = _train(rows)
    got = _committed(model)
    assert got["tree_cuts"].shape == (4, F, NBIN - 1)
    assert got["forest_split"].shape == got["forest_val"].shape
    shard = refa.Rows(values, labels)
    margins = np.concatenate([np.asarray(m) for m in shard.margins(
        got["forest_int"], got["forest_val"], got["forest_split"],
        model.learning_rate, 3)])
    np.testing.assert_allclose(1 / (1 + np.exp(-margins)),
                               model.predict(values), atol=2e-6)
    # absent values went the way the committed default says
    assert model.has_missing and np.isnan(values[:, 2]).any()


def test_replay_of_the_programs_forest_reads_rounding_and_a_foreign_sketch_its_error(
        rows):
    values, labels = rows
    model = _train(rows)
    got = _committed(model)
    args = (got["forest_int"], got["forest_val"], got["forest_split"],
            [0, 3], NBIN, 3, model.learning_rate, 1.0, 1e-3, "float32")
    sound = refa.replay(values, labels, got["tree_cuts"], *args)
    assert sound["cut_rank_err"] < 1e-5
    assert set(sound["cut_rank_err_by_tree"]) == {0, 3}
    assert sound["leaf_sum_rel_err"] < 1e-4 and sound["split_regret"] < 1e-6
    assert sound["unsplit_above_limit"] == 0
    # the last tree on the first tree's cuts: right at round 0 (every
    # hessian 0.25), off by the drift of the weights at round 3
    stale = np.repeat(got["tree_cuts"][:1], 4, axis=0)
    off = refa.replay(values, labels, stale, *args)
    assert off["cut_rank_err_by_tree"][0] < 1e-5
    assert off["cut_rank_err_by_tree"][3] > 5 * sound["cut_rank_err_by_tree"][3]
    assert off["cut_rank_err"] == off["cut_rank_err_by_tree"][3] > 1e-3


def test_two_ranks_ranks_add_up_through_combine(rows):
    """``combine`` adds the tallies of the ranks: two half shards read
    what the whole shard reads."""
    values, labels = rows
    rng = np.random.default_rng(73)
    w = rng.random(N).astype(np.float32)
    cuts = np.sort(rng.standard_normal((F, 15)).astype(np.float32), axis=1)
    whole = refa.Rows(values, labels).weight_under([w], cuts)
    halves = [refa.Rows(values[a:b], labels[a:b]).weight_under([w[a:b]], cuts)
              for a, b in ((0, 1400), (1400, N))]
    np.testing.assert_allclose(halves[0] + halves[1], whole, rtol=1e-6)

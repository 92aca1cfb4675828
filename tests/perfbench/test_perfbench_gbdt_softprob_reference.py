"""The plain multi-class boosting reference against float64 numpy
oracles, and its replay of a round against a forest built here: sound
where the forest is the program's, not where a tree was grown on
another objective's gradients or saw another tree's update."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.reference import gbdt as ref  # noqa: E402
from perfbench.reference import gbdt_softprob as refs  # noqa: E402

N, F, NBIN, K, DEPTH, RATE, BASE = 3000, 5, 16, 4, 3, 0.3, 0.5


def test_reference_imports_nothing_of_the_program():
    text = open(os.path.join(ROOT, "perfbench", "reference",
                             "gbdt_softprob.py")).read()
    assert "rabit_tpu" not in text.replace("``rabit_tpu``", "")
    assert "import" in text and "perfbench.reference import gbdt" in text


def _softmax64(margins, labels):
    m = np.asarray(margins, np.float64)
    e = np.exp(m - m.max(axis=0))
    p = e / e.sum(axis=0)
    hit = np.asarray(labels)[None, :] == np.arange(len(m))[:, None]
    return p - hit, np.maximum(2.0 * p * (1.0 - p), 1e-16)


@pytest.mark.parametrize("k", [2, 7])
def test_softmax_gradient_against_float64(k):
    rng = np.random.default_rng(k)
    margins = (4.0 * rng.standard_normal((k, 2048))).astype(np.float32)
    margins[0, :4] = 80.0                      # all but certain
    labels = rng.integers(0, k, 2048).astype(np.float32)
    got = np.asarray(refs.softmax_grad_hess(margins, labels))
    assert got.shape == (k, 2048, 2) and got.dtype == np.float32
    g, h = _softmax64(margins, labels)
    np.testing.assert_allclose(got[:, :, 0], g, atol=2e-6)
    np.testing.assert_allclose(got[:, :, 1], h, atol=2e-6)
    assert got[:, :, 1].min() >= np.float32(refs.MIN_HESS) > 0
    # a row's gradients over the classes add up to nothing
    np.testing.assert_allclose(got[:, :, 0].sum(axis=0), 0.0, atol=1e-5)


@pytest.fixture(scope="module")
def job():
    """A forest of K classes x 3 rounds grown here by the program's host
    arm, as arrays."""
    import rabit_tpu
    from rabit_tpu.learn import boosting

    rng = np.random.default_rng(31)
    values = rng.standard_normal((N, F)).astype(np.float32)
    score = np.stack([values[:, c] * (1 + c % 2) + np.sin(values[:, -1] + c)
                      for c in range(K)])
    labels = np.argmax(score + 0.4 * rng.standard_normal(score.shape),
                       axis=0).astype(np.float32)
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    try:
        model = boosting.train(values, labels, num_round=3, max_depth=DEPTH,
                               nbin=NBIN, loss="softprob", num_class=K,
                               use_pallas=False)
    finally:
        rabit_tpu.finalize()
    from perfbench import harness

    got = harness.load_module(os.path.join(
        ROOT, "perfbench", "learners", "gbdt.py")).committed(model)
    forest_int, forest_val = got["forest_int"], got["forest_val"]
    return values, labels, model, forest_int, forest_val


def replay(job, forest_int=None, forest_val=None, which=(0, 2)):
    values, labels, model, f_int, f_val = job
    return refs.replay(
        values, labels, model.cuts,
        f_int if forest_int is None else forest_int,
        f_val if forest_val is None else forest_val, list(which), K, NBIN,
        DEPTH, RATE, BASE, 1.0, 1e-3, "float32")


def test_class_margins_are_the_models_and_skip_the_later_rounds(job):
    from rabit_tpu.learn import boosting

    values, labels, model, forest_int, forest_val = job
    shard = ref.Shard(values, labels, model.cuts, NBIN)
    bins = boosting.apply_cuts(values, model.cuts)
    for rounds in (0, 1, 3):
        (got,) = refs.class_margins(shard, forest_int, forest_val, rounds, K,
                                    RATE, BASE, DEPTH)
        part = boosting.BoostedModel(
            cuts=model.cuts, trees=model.trees[:rounds * K], base_score=BASE,
            learning_rate=RATE, num_class=K)
        np.testing.assert_allclose(np.asarray(got), part.margin(bins),
                                   rtol=1e-6, atol=1e-6)
    assert np.all(np.asarray(refs.class_margins(
        shard, forest_int, forest_val, 0, K, RATE, BASE, DEPTH)[0]) == BASE)


def test_head_gradients_are_those_of_the_round_after_the_forest(job):
    from rabit_tpu.learn import boosting

    values, labels, model, forest_int, forest_val = job
    got = refs.head_grad_hess(values[:500], labels[:500], model.cuts,
                              forest_int, forest_val, K, NBIN, DEPTH, RATE,
                              BASE)
    assert got.shape == (K, 2, 500)
    margin = model.margin(boosting.apply_cuts(values[:500], model.cuts))
    g, h = _softmax64(margin, labels[:500])
    np.testing.assert_allclose(got[:, 0], g, atol=5e-6)
    np.testing.assert_allclose(got[:, 1], h, atol=5e-6)


def test_replay_of_the_programs_forest_is_sound(job):
    got = replay(job, which=(0, 1, 2))
    assert got["unsplit_above_limit"] == 0
    assert got["split_regret"] < 1e-5 and got["leaf_sum_rel_err"] < 1e-5
    assert got["splits"] + got["leaves"] == sum(
        len(t) for t in job[2].trees)
    assert len(got["by_class"]) == K
    assert sum(c["splits"] for c in got["by_class"]) == got["splits"]
    assert max(c["leaf_sum_rel_err"] for c in got["by_class"]) \
        == got["leaf_sum_rel_err"]


def test_replay_tells_one_vs_rest_leaves_from_softmax_leaves(job):
    """Leaf weights recomputed from ``sigmoid(m_k) - [y = k]`` and
    ``p (1 - p)`` on the same trees: every round's leaves are off."""
    from rabit_tpu.learn import boosting

    values, labels, model, forest_int, forest_val = job
    bins = boosting.apply_cuts(values, model.cuts)
    off = forest_val.copy()
    for k in range(K):                           # the first round's trees
        p = 1.0 / (1.0 + np.exp(-BASE))
        g = p - (labels == k)
        h = np.full(N, p * (1 - p))
        tree = model.trees[k]
        leaf_of = np.zeros(N, int)
        for i in range(N):
            nid = 0
            while tree[nid].feature >= 0:
                n_ = tree[nid]
                nid = n_.left if bins[i, n_.feature] <= n_.bin_threshold \
                    else n_.right
            leaf_of[i] = nid
        for nid in np.unique(leaf_of):
            at = leaf_of == nid
            off[k, nid] = -g[at].sum() / (h[at].sum() + 1.0)
    got = replay(job, forest_val=off, which=(0,))
    assert got["leaf_sum_rel_err"] > 0.05
    assert replay(job, which=(0,))["leaf_sum_rel_err"] < 1e-5


def test_replay_tells_a_round_whose_trees_saw_one_anothers_updates(job):
    """The second round's trees replayed as if class 0's tree of that
    round had already been added when the others were grown: the
    reference, which takes every class from the margins the round
    before left, finds the others' leaves off."""
    values, labels, model, forest_int, forest_val = job
    # move round 1's class-0 tree into round 0's place of class 0 twice
    # over: the reference's margins then differ from the program's
    swapped_int, swapped_val = forest_int.copy(), forest_val.copy()
    swapped_int[0], swapped_val[0] = forest_int[K], forest_val[K]
    sound = replay(job, which=(1,))
    got = replay(job, swapped_int, swapped_val, which=(1,))
    assert sound["leaf_sum_rel_err"] < 1e-5
    assert got["leaf_sum_rel_err"] > 100 * sound["leaf_sum_rel_err"] + 1e-4

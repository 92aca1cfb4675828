"""The plain boosting reference against float64 numpy oracles, and
against the program where the two must agree exactly (cuts, bins)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.reference import gbdt as ref  # noqa: E402

N, F, NBIN = 3000, 4, 16


@pytest.fixture(scope="module")
def shard():
    rng = np.random.default_rng(21)
    values = rng.standard_normal((N, F)).astype(np.float32)
    labels = (values[:, 0] * values[:, 1] > 0).astype(np.float32)
    cuts = ref.quantile_cuts(values, NBIN)
    return values, labels, cuts, ref.Shard(values, labels, cuts, NBIN)


def test_reference_imports_nothing_of_the_program():
    text = open(os.path.join(ROOT, "perfbench", "reference",
                             "gbdt.py")).read()
    assert "rabit_tpu" not in text.replace("``rabit_tpu``", "")


def test_cuts_and_bins_equal_the_programs(shard):
    from rabit_tpu.learn import histogram

    values, _labels, cuts, sh = shard
    np.testing.assert_array_equal(cuts, histogram.quantile_cuts(values, NBIN))
    want = histogram.apply_cuts(values, cuts)
    np.testing.assert_array_equal(ref.bin_rows(values, cuts), want.T)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(b) for b in sh.bins]), want)


def test_cut_sample_is_the_programs():
    from rabit_tpu.learn import boosting

    values = np.arange(5000, dtype=np.float32).reshape(2500, 2)
    for size in (100, 2500, 1 << 20):
        saved, boosting.CUT_SAMPLE_ROWS = boosting.CUT_SAMPLE_ROWS, size
        try:
            np.testing.assert_array_equal(ref.cut_sample(values, size),
                                          boosting.cut_sample(values))
        finally:
            boosting.CUT_SAMPLE_ROWS = saved


@pytest.mark.parametrize("nslots", [1, 4])
def test_level_histograms_against_a_float64_bincount(shard, nslots):
    import jax.numpy as jnp

    values, labels, cuts, sh = shard
    rng = np.random.default_rng(22)
    node = rng.integers(-1, nslots, N).astype(np.int32)
    gh = rng.standard_normal((N, 2)).astype(np.float32)
    got = sh.level_hist([jnp.asarray(gh)], [jnp.asarray(node)], nslots)
    bins = ref.bin_rows(values, cuts).T
    want = np.zeros((nslots, F, NBIN, 2))
    live = node >= 0
    for j in range(F):
        cell = node[live] * NBIN + bins[live, j]
        for c in range(2):
            want[:, j, :, c] = np.bincount(
                cell, gh[live, c].astype(np.float64),
                nslots * NBIN).reshape(nslots, NBIN)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    sums = sh.abs_sums([jnp.asarray(gh)], [jnp.asarray(node)], nslots)
    assert sums[:, 0].sum() == live.sum()
    np.testing.assert_allclose(sums[:, 1].sum(), np.abs(gh[live, 0]).sum(),
                               rtol=1e-6)


def test_split_gain_against_brute_force():
    rng = np.random.default_rng(23)
    hist = rng.random((3, 8, 2))
    hist[:, :, 0] -= 0.5
    gain = ref.split_gain(hist, 1.0)

    def score(g, h):
        return g * g / (h + 1.0)

    for j in range(3):
        for t in range(7):
            left, right = hist[j, :t + 1].sum(0), hist[j, t + 1:].sum(0)
            want = (score(*left) + score(*right) - score(*(left + right)))
            assert gain[j, t] == pytest.approx(want, abs=1e-12)
    assert ref.would_split(hist, 1.0, 0.1) == (gain.max() > 1e-12)
    assert not ref.would_split(hist, 1.0, 1e9)


@pytest.mark.parametrize("bend", [None, "value", "split"])
def test_replay_of_a_tree_the_reference_would_grow(shard, bend):
    """A tree grown by the program in float32 on the same rows replays
    with no regret and no leaf gap; a bent leaf weight or a worse split
    shows in the number that is its own."""
    import rabit_tpu
    from rabit_tpu.learn import boosting

    values, labels, cuts, _sh = shard
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    try:
        model = boosting.train(values, labels, num_round=2, max_depth=3,
                               nbin=NBIN, min_child_weight=1.0,
                               use_pallas=False)
    finally:
        rabit_tpu.finalize()
    np.testing.assert_array_equal(model.cuts, cuts)
    from perfbench import harness

    adapter = harness.load_module(os.path.join(
        ROOT, "perfbench", "learners", "gbdt.py"))
    forest = adapter.committed(model)
    f_int, f_val = forest["forest_int"].copy(), forest["forest_val"].copy()
    if bend == "value":
        leaf = int(np.flatnonzero(f_int[1, :, 0] == -1)[0])
        f_val[1, leaf] *= 1.02
    if bend == "split":
        f_int[1, 0, 1] = (f_int[1, 0, 1] + NBIN // 2) % (NBIN - 1)
    got = ref.replay(values, labels, cuts, f_int, f_val, [0, 1], NBIN, 3,
                     0.3, 1.0, 1.0, "float32")
    assert got["leaves"] == got["splits"] + 2 and got["splits"] >= 6
    assert (got["split_regret"] < 1e-6) == (bend != "split")
    if bend != "split":            # a bent split moves the rows below it
        assert got["unsplit_above_limit"] == 0
        assert (got["leaf_sum_rel_err"] < 1e-5) == (bend is None)
        assert got["leaf_sum_rounded_rel_err"] == got["leaf_sum_rel_err"]

"""The plain reference for boosting with missing values against brute
force in float64, and against the program where the two must agree
exactly (cuts, bins, the missing code)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.reference import gbdt_missing as refm  # noqa: E402

N, F, NBIN = 3000, 6, 16


@pytest.fixture(scope="module")
def shard():
    rng = np.random.default_rng(61)
    values = rng.uniform(-1, 1, (N, F)).astype(np.float32)
    values[:, 1] = np.round(values[:, 1] * 2) / 2          # five levels
    went = rng.random((N, 3)) < np.array([0.9, 0.5, 0.1])
    values[~went[:, np.repeat(np.arange(3), 2)]] = np.nan
    values[:, 5] = np.nan                                  # nobody has it
    z = np.nan_to_num(values)
    logit = 3.0 * z[:, 0] * z[:, 2] + 2.0 * went[:, 1] - 1.0
    labels = (rng.random(N) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    cuts = refm.quantile_cuts(values, NBIN)
    return values, labels, cuts, refm.Shard(values, labels, cuts, NBIN)


def test_reference_imports_nothing_of_the_program():
    text = open(os.path.join(ROOT, "perfbench", "reference",
                             "gbdt_missing.py")).read()
    assert "import rabit_tpu" not in text and "from rabit_tpu" not in text
    assert "jax" not in text and "pallas" not in text.replace(
        "no kernel", "")


def test_cuts_bins_and_the_missing_code_equal_the_programs(shard):
    from rabit_tpu.learn import histogram

    values, _labels, cuts, sh = shard
    np.testing.assert_array_equal(cuts, histogram.quantile_cuts(values, NBIN))
    assert not cuts[5].any()                  # the all-absent column
    want = histogram.apply_cuts(values, cuts)
    np.testing.assert_array_equal(sh.bins.T, want)
    assert (sh.bins[5] == NBIN).all() and (want == NBIN).mean() > 0.4
    for j in range(F):
        np.testing.assert_array_equal(
            sh.rows_of[j], np.flatnonzero(~np.isnan(values[:, j])))


@pytest.mark.parametrize("nslots", [1, 4])
def test_level_histograms_and_missing_mass_against_a_tally(shard, nslots):
    """Per (slot, feature, bin) a float64 loop over the rows; and the
    identity the reference rests on: a node's total less a feature's
    present entries is the tally of the rows absent from it."""
    values, _labels, cuts, sh = shard
    rng = np.random.default_rng(62)
    node = rng.integers(-1, nslots, N)
    gh = rng.standard_normal((N, 2))
    got = sh.level_hist(gh, node, nslots)
    want = np.zeros((nslots, F, NBIN, 2))
    absent = np.zeros((nslots, F, 2))
    for r in range(N):
        if node[r] < 0:
            continue
        for j in range(F):
            if sh.bins[j, r] == NBIN:
                absent[node[r], j] += gh[r]
            else:
                want[node[r], j, sh.bins[j, r]] += gh[r]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-11)
    total = refm.slot_sums(gh, node, nslots)
    np.testing.assert_allclose(total[:, None, :] - got.sum(axis=2), absent,
                               rtol=0, atol=1e-10)
    assert not got[:, 5].any()


def test_split_gains_against_brute_force_both_directions():
    rng = np.random.default_rng(63)
    hist = rng.random((3, 8, 2))
    hist[:, :, 0] -= 0.5
    missing = rng.random((3, 2))
    missing[2] = 0.0                               # nobody misses it
    total = hist[0].sum(0) + missing[0]
    for j in (1, 2):                               # one total a node
        hist[j] *= (total[1] - missing[j, 1]) / hist[j, :, 1].sum()
        hist[j, 0, 0] += total[0] - missing[j, 0] - hist[j, :, 0].sum()
    left, right = refm.split_gains(hist, total, 1.0, 0.0)

    def score(g, h):
        return g * g / (h + 1.0)

    for j in range(3):
        for t in range(7):
            lo, hi = hist[j, :t + 1].sum(0), hist[j, t + 1:].sum(0)
            for gain, (a, b) in ((left, (lo + missing[j], hi)),
                                 (right, (lo, hi + missing[j]))):
                want = score(*a) + score(*b) - score(*total)
                assert gain[j, t] == pytest.approx(want, abs=1e-12)
    np.testing.assert_allclose(left[2], right[2], atol=1e-12)
    # eligibility: a child lighter than the least weight scores -inf
    barred = refm.split_gains(hist, total, 1.0, 1e9)
    assert np.isneginf(barred[0]).all() and np.isneginf(barred[1]).all()


@pytest.mark.parametrize("bend", [None, "value", "cut", "direction",
                                  "imputed", "stopped"])
def test_replay_of_a_forest_the_reference_would_grow(shard, bend):
    """A forest grown by the program in float32 on the same rows replays
    with no regret and no leaf gap; a bent leaf weight, a worse cut, a
    flipped default direction, or rows imputed instead of routed, shows
    in the number that is its own."""
    import rabit_tpu
    from perfbench import harness
    from rabit_tpu.learn import boosting

    values, labels, cuts, _sh = shard
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    rows = values
    if bend == "imputed":      # absent entries filled in: another job
        rows = np.where(np.isnan(values), np.float32(0.0), values)
        rows[:, 5] = np.nan
    try:
        model = boosting.train(rows, labels, num_round=2, max_depth=3,
                               nbin=NBIN, min_child_weight=1.0,
                               use_pallas=False)
    finally:
        rabit_tpu.finalize()
    adapter = harness.load_module(os.path.join(
        ROOT, "perfbench", "learners", "gbdt_missing.py"))
    forest = adapter.committed(model)
    f_int, f_val = forest["forest_int"].copy(), forest["forest_val"].copy()
    if bend == "value":
        leaf = int(np.flatnonzero(f_int[1, :, 0] == -1)[0])
        f_val[1, leaf] *= 1.02
    if bend == "cut":
        f_int[1, 0, 1] = (f_int[1, 0, 1] + NBIN // 2) % (NBIN - 1)
    if bend == "direction":
        # the first split of the second tree on a feature half the rows
        # lack: where they go is most of the gain
        node = next(i for i in range(f_int.shape[1])
                    if f_int[1, i, 0] in (2, 3))
        f_int[1, node, 2] ^= 1
    if bend == "stopped":
        # the second tree's root left a leaf: its rows' gain is beyond
        # any boundary band
        f_int[1, 0, 0] = -1
    got = refm.replay(values, labels, model.cuts if bend == "imputed"
                      else cuts, f_int, f_val, [0, 1], NBIN, 3, 0.3, 1.0,
                      1.0, "float32")
    if bend == "stopped":
        assert got["unsplit_above_limit"] == 1 and got["split_regret"] < 1e-6
        return
    assert got["leaves"] == got["splits"] + 2 and got["splits"] >= 6
    assert got["worst_split"] is None or got["worst_split"]["band"] > 0
    sound = bend in (None, "value")
    assert (got["split_regret"] < 1e-6) == sound, got
    if sound:                  # a bent split moves the rows below it
        assert got["unsplit_above_limit"] == 0
        assert (got["leaf_sum_rel_err"] < 1e-5) == (bend is None)
        # the operand's grid is float32 here, so the two sums differ
        # by that alone; the rounded number is a share of the PARENT's
        # sum of |g|, which is no smaller than the leaf's
        assert got["leaf_sum_rounded_rel_err"] <= got[
            "leaf_sum_rel_err"] * (1 + 1e-3) + 1e-7
        assert (got["leaf_sum_rounded_rel_err"] < 1e-5) == (bend is None)
        assert 0 < got["default_left"] < got["splits"]

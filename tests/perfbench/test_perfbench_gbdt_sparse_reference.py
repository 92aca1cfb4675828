"""The plain reference for boosting on sparse rows, by itself: its cuts,
bins and gains against brute force on rows a test can read, the missing
mass as an identity, and a hand-made tree replayed."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.reference import gbdt_sparse as ref  # noqa: E402

NBIN = 8


def _rows(n=400, f=9, width=4, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.stack([np.sort(rng.choice(f - 1, width, replace=False))
                    for _ in range(n)]).astype(np.int32)
    val = rng.standard_normal((n, width)).astype(np.float32)
    val[idx == 2] = 1.0                       # an indicator column
    counts = rng.integers(0, width + 1, n)
    idx[np.arange(width)[None, :] >= counts[:, None]] = f   # padding
    return idx, val, counts, f


def _dense(idx, val, counts, f):
    out = np.full((len(idx), f), np.nan)
    for i in range(len(idx)):
        out[i, idx[i, :counts[i]]] = val[i, :counts[i]]
    return out


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "perfbench", "reference",
                            "gbdt_sparse.py")).read()
    assert "rabit_tpu" not in src.split('"""', 2)[2]


def test_cuts_are_the_distinct_quantiles_of_the_present_entries():
    idx, val, counts, f = _rows()
    cut_ptr, cuts = ref.quantile_cuts(idx, val, counts, f, NBIN)
    dense = _dense(idx, val, counts, f)
    qs = np.linspace(0, 1, NBIN + 1)[1:-1]
    for j in range(f):
        have = dense[:, j][~np.isnan(dense[:, j])].astype(np.float32)
        want = np.unique(np.quantile(have, qs).astype(np.float32)) \
            if have.size else np.zeros(1, np.float32)
        np.testing.assert_array_equal(cuts[cut_ptr[j]:cut_ptr[j + 1]], want)
    assert cut_ptr[3] - cut_ptr[2] == 1 and cuts[cut_ptr[2]] == 1.0
    assert cut_ptr[f] - cut_ptr[f - 1] == 1 and cuts[-1] == 0.0   # no row


def test_cells_are_a_columns_start_and_the_cuts_at_or_below():
    idx, val, counts, f = _rows()
    cut_ptr, cuts = ref.quantile_cuts(idx, val, counts, f, NBIN)
    cells = ref.bin_rows(idx, val, counts, f, cut_ptr, cuts)
    ptr = cut_ptr + np.arange(f + 1)
    for i in range(len(idx)):
        for j in range(idx.shape[1]):
            if j >= counts[i]:
                assert cells[i, j] == -1
                continue
            c = idx[i, j]
            mine = cuts[cut_ptr[c]:cut_ptr[c + 1]]
            assert cells[i, j] == ptr[c] + (mine <= val[i, j]).sum()
            assert ptr[c] <= cells[i, j] < ptr[c + 1]


def test_gains_against_brute_force_over_both_directions():
    rng = np.random.default_rng(1)
    widths = np.array([3, 2, 5, 2])
    ptr = np.concatenate([[0], np.cumsum(widths)])
    hist = np.stack([rng.standard_normal(ptr[-1]),
                     rng.random(ptr[-1]) + 0.2], axis=1)
    total = hist[:3].sum(axis=0) + [0.7, 1.5]
    left, right = ref.split_gains(hist, ptr, total, 1.0, 0.5)

    def score(gl, hl):
        gr, hr = total[0] - gl, total[1] - hl
        if hl < 0.5 or hr < 0.5:
            return -np.inf
        return gl * gl / (hl + 1) + gr * gr / (hr + 1) \
            - total[0] ** 2 / (total[1] + 1)

    for j, w in enumerate(widths):
        col = hist[ptr[j]:ptr[j + 1]]
        gm, hm = total - col.sum(axis=0)
        for t in range(w):
            gl, hl = col[:t + 1].sum(axis=0)
            at = ptr[j] + t
            if t == w - 1:                   # a column's last cell: no cut
                assert left[at] == right[at] == -np.inf
                continue
            assert left[at] == pytest.approx(score(gl + gm, hl + hm))
            assert right[at] == pytest.approx(score(gl, hl))


def test_a_hand_made_tree_replayed():
    """Rows whose label is whether they hold the indicator column: the
    split that sends absent rows left of its one cut has no regret, one
    on a numeric column has, and a leaf's weight is held to the sums."""
    idx, val, counts, f = _rows(n=600, seed=4)
    has = (idx == 2).any(axis=1)
    labels = has.astype(np.float64)
    cut_ptr, cuts = ref.quantile_cuts(idx, val, counts, f, NBIN)
    shard = ref.Shard(idx, val, counts, f, labels, cut_ptr, cuts)
    gh = shard.grad_hess(np.zeros(len(idx)))
    g_has, g_not = gh[has].sum(axis=0), gh[~has].sum(axis=0)
    good = np.array([[2, 0, 1, 1, 2], [-1, 0, 1, -1, -1], [-1, 0, 1, -1, -1]])
    vals = np.array([0.0, -g_not[0] / (g_not[1] + 1),
                     -g_has[0] / (g_has[1] + 1)])
    got = ref.replay_tree(shard, gh, good, vals, 1, 1.0, 1.0, "float32")
    assert got["split_regret"] == 0 and got["default_left"] == 1
    assert got["leaf_sum_rounded_rel_err"] < 1e-12
    bad = good.copy()
    bad[0, :3] = (0, 1, 0)
    got = ref.replay_tree(shard, gh, bad, vals, 1, 1.0, 1.0, "float32")
    assert got["split_regret"] > 0.5
    # the same walk gives the margins: absent rows went left
    margin = shard.margins(good[None], vals[None], 1.0, 1)
    np.testing.assert_allclose(margin, np.where(has, vals[2], vals[1]))

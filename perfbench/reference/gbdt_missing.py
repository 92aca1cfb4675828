"""The plain reference for histogram boosting on rows with missing
values: level-wise trees on quantile bins, logistic loss, XGBoost's
structure score with **both default directions scored for every
candidate**, leaf weight ``-G / (H + lambda)``.  Float64 numpy: a
present entry is added into its bin, one ``np.bincount`` a feature
(per-row adds: no kernel, no one-hot product), an absent entry is added
into none; every node's histogram is built from its own rows (no
parent-minus-sibling subtraction); absent rows go the way the committed
split says.  Imports nothing of ``rabit_tpu``; ``reference/gbdt.py``
gives the pieces that know nothing of missing values (the sample, the
tree's levels).

Like ``reference/gbdt.py`` it trains no forest of its own: it replays
the program's committed forest one step, and says how good each split
the program chose (feature, cut and default direction) is by the
reference's own gains over both directions, and how far each committed
leaf weight is from the reference's sums.

The mass of a node's rows absent from a feature is the node's total
less the feature's present entries, an identity in float64 (checked in
the tests against a tally of the absent rows themselves).

Departures from XGBoost, shared with the program: the cuts are exact
quantiles of the present entries of a stated sample of rank 0's rows,
where XGBoost merges weighted quantile sketches over all rows; growth
is level-wise and synchronous (``grow_policy=depthwise``), every node of
a level split on one reduced histogram, where XGBoost's ``hist`` updater
may also grow loss-guided.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.reference.gbdt import levels_of

BATCH_COLS = 16
BLOCK_ROWS = 4096
THREADS = max(1, min(8, os.cpu_count() or 1))


# ----------------------------------------------------------------------
# cuts and bins
# ----------------------------------------------------------------------
def columns(values: np.ndarray, j0: int, j1: int) -> np.ndarray:
    """Columns ``[j0, j1)`` of a row-major matrix as contiguous rows."""
    out = np.empty((j1 - j0, values.shape[0]), values.dtype)
    for r in range(0, values.shape[0], BLOCK_ROWS):
        out[:, r:r + BLOCK_ROWS] = values[r:r + BLOCK_ROWS, j0:j1].T
    return out


def over_columns(f: int, fn) -> None:
    """``fn(j0, j1)`` for every batch of columns, on a few threads."""
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(lambda j0: fn(j0, min(f, j0 + BATCH_COLS)),
                      range(0, f, BATCH_COLS)))


def quantile_cuts(sample: np.ndarray, nbin: int) -> np.ndarray:
    """``nbin - 1`` interior quantiles of the present entries of each
    column, (f, nbin - 1) float32; zeros for a column with none."""
    qs = np.linspace(0, 1, nbin + 1)[1:-1]
    f = sample.shape[1]
    cuts = np.zeros((f, nbin - 1), np.float32)

    def batch(j0: int, j1: int) -> None:
        for j, col in enumerate(columns(sample, j0, j1), j0):
            have = col[~np.isnan(col)]
            if have.size:
                cuts[j] = np.quantile(have, qs)

    over_columns(f, batch)
    return cuts


def bin_rows(values: np.ndarray, cuts: np.ndarray, nbin: int) -> np.ndarray:
    """(f, rows) int16 bins: the number of cuts at or below each value,
    and the code ``nbin`` for an absent one."""
    f = values.shape[1]
    bins = np.empty((f, values.shape[0]), np.int16)

    def batch(j0: int, j1: int) -> None:
        for j, col in enumerate(columns(values, j0, j1), j0):
            bins[j] = np.searchsorted(cuts[j], col, side="right")
            bins[j][np.isnan(col)] = nbin

    over_columns(f, batch)
    return bins


# ----------------------------------------------------------------------
# the rows
# ----------------------------------------------------------------------
class Shard:
    """One rank's rows as bins, feature-major, and for every feature
    the rows that have it and their bins (what a histogram adds up)."""

    def __init__(self, values: np.ndarray, labels: np.ndarray,
                 cuts: np.ndarray, nbin: int):
        self.n, self.f = values.shape
        self.nbin = nbin
        self.bins = bin_rows(values, cuts, nbin)
        self.labels = np.asarray(labels, np.float64)
        self.rows_of = [None] * self.f
        self.bins_of = [None] * self.f

        def batch(j0: int, j1: int) -> None:
            for j in range(j0, j1):
                rows = np.flatnonzero(self.bins[j] != nbin)
                self.rows_of[j] = rows.astype(np.int32)
                self.bins_of[j] = self.bins[j][rows].astype(np.int64)

        over_columns(self.f, batch)

    # ---- margins of a forest -----------------------------------------
    def margins(self, forest_int, forest_val, rate: float, max_depth: int):
        """The margin of every row under the given trees (base 0): a
        row absent at its node's feature goes the committed way."""
        margin = np.zeros(self.n, np.float64)
        at = np.arange(self.n)
        for tree_int, tree_val in zip(forest_int, forest_val):
            node = np.zeros(self.n, np.int64)
            for _ in range(max_depth):
                feat, thr, dleft, left, right = tree_int[node].T
                b = self.bins[np.maximum(feat, 0), at]
                go_left = np.where(b == self.nbin, dleft != 0, b <= thr)
                node = np.where(feat >= 0, np.where(go_left, left, right),
                                node)
            margin += rate * np.asarray(tree_val, np.float64)[node]
        return margin

    def grad_hess(self, margin):
        p = 1.0 / (1.0 + np.exp(-margin))
        return np.stack([p - self.labels, p * (1.0 - p)], axis=1)

    # ---- one level ---------------------------------------------------
    def level_hist(self, gh, node, nslots: int) -> np.ndarray:
        """(nslots, f, nbin, 2) float64: per slot, feature and bin the
        sums of (grad, hess) over the rows at that slot that have the
        feature.  A row at no slot (node < 0) is in none."""
        out = np.zeros((nslots, self.f, self.nbin, 2), np.float64)
        size = nslots * self.nbin
        # a row at no slot adds nothing, into slot 0
        weights = [np.where(node >= 0, gh[:, c], 0.0) for c in range(2)]
        base = np.maximum(node, 0) * self.nbin

        def batch(j0: int, j1: int) -> None:
            for j in range(j0, j1):
                rows = self.rows_of[j]
                cell = base[rows] + self.bins_of[j]
                for c in range(2):
                    out[:, j, :, c] = np.bincount(
                        cell, weights[c][rows], size).reshape(
                            nslots, self.nbin)

        over_columns(self.f, batch)
        return out

    def partition(self, node, tab: np.ndarray):
        """Every row to its child's slot; a row of a node that is a leaf
        leaves the walk (-1).  ``tab[s]`` = (feature, threshold, default
        left, split)."""
        feat, thr, dleft, split = tab[np.maximum(node, 0)].T
        b = self.bins[feat, np.arange(self.n)]
        go_left = np.where(b == self.nbin, dleft != 0, b <= thr)
        return np.where((node >= 0) & (split > 0), 2 * node + 1 - go_left,
                        -1)


def slot_sums(values, node, nslots: int) -> np.ndarray:
    """(nslots, k) float64: the sums of the (n, k) ``values`` over the
    rows at each slot."""
    live = node >= 0
    return np.stack([np.bincount(node[live], values[live, c], nslots)
                     for c in range(values.shape[1])], axis=1)


# ----------------------------------------------------------------------
# the structure score, both default directions
# ----------------------------------------------------------------------
def split_gains(hist: np.ndarray, total: np.ndarray, reg_lambda: float,
                min_child_weight: float):
    """``(gain_left, gain_right)`` of one node: for every (feature,
    cut) of its (f, nbin, 2) histogram of present entries, left = bins
    0..cut, the gain with the rows absent from the feature sent left
    and sent right.  ``total`` is the node's (grad, hess) over all its
    rows.  A candidate one of whose children would weigh less than
    ``min_child_weight`` is not eligible and reads -inf (XGBoost scores
    no other)."""
    g, h = hist[:, :, 0], hist[:, :, 1]
    gl, hl = np.cumsum(g, axis=1)[:, :-1], np.cumsum(h, axis=1)[:, :-1]
    gm = total[0] - g.sum(axis=1, keepdims=True)
    hm = total[1] - h.sum(axis=1, keepdims=True)
    parent = total[0] * total[0] / (total[1] + reg_lambda)

    def score(gl_, hl_):
        gr_, hr_ = total[0] - gl_, total[1] - hl_
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (gl_ * gl_ / (hl_ + reg_lambda)
                    + gr_ * gr_ / (hr_ + reg_lambda) - parent)
        return np.where((hl_ >= min_child_weight)
                        & (hr_ >= min_child_weight), gain, -np.inf)

    return score(gl + gm, hl + hm), score(gl, hl)


# A decision is on the boundary where the reference's best gain is
# within this share of the node's scale, (sum |g|)^2 / (H + lambda), of
# zero: program and reference add up the same rounded numbers and
# differ by float32 accumulation, 1e-6 of a node's sum of |g|
# (``leaf_sum_rounded_rel_err``), which moves a gain by 1e-5 of that
# scale.  With 0.58% positives a tree is not full, so that every tree
# has nodes near the boundary; which side of it a node falls on is then
# the accumulation's to say, and is held against neither.  Likewise a
# child's weight against ``min_child_weight``.
BOUNDARY = 1e-4


def rounded(gh: np.ndarray, dtype: str) -> np.ndarray:
    """``gh`` on the grid of ``dtype`` (the kernel's operand type)."""
    import ml_dtypes

    grid = getattr(ml_dtypes, dtype, None) or np.dtype(dtype)
    return gh.astype(np.float32).astype(grid).astype(np.float64)


def replay_tree(shard: Shard, gh, tree_int: np.ndarray, tree_val: np.ndarray,
                max_depth: int, reg_lambda: float, min_child_weight: float,
                operand_dtype: str, combine=lambda tag, a: a) -> dict:
    """Walk one of the program's trees on the reference's gradients;
    the numbers are those of ``reference/gbdt.py replay_tree``.  The
    gains are taken on the gradients rounded to ``operand_dtype``, the
    numbers the program adds up, so that the two differ by accumulation
    alone.  The regret of a split is taken on its (feature, cut,
    default direction) against the best eligible candidate over both
    directions, as a share of that best gain or, near the boundary, of
    the band ``BOUNDARY`` of the node's scale; a leaf above the depth
    limit counts as unsplit where the best gain lies beyond that
    band.  ``leaf_sum_rounded_rel_err`` is taken over the tree: the sum
    of |G_program - G| over its leaves as a share of the sum of |g|
    over its rows.  The accumulation is not a leaf's own: the child
    that takes a split's absent rows holds their mass as the node's
    total less a feature's bins, and a node had as parent minus built
    carries its ancestors' float32 sums, so a light leaf reads
    thousands of times the accumulation as a share of its own sum (or
    of its parent's: a node heavy in hessian can be light in |g|)
    while its error stays 1e-6 of the tree's.  ``leaf_sum_rel_err``
    stays the worst leaf's share of its own."""
    levels = levels_of(tree_int, max_depth)
    node = np.zeros(shard.n, np.int64)
    gh_op = rounded(gh, operand_dtype)
    regret, unsplit, leaf_err, gap_op, abs_all = 0.0, 0, 0.0, 0.0, 0.0
    splits = leaves = default_left = 0
    worst = worst_leaf = None
    parent_of = {int(c): i for i in range(len(tree_int))
                 if tree_int[i, 0] >= 0 for c in tree_int[i, 3:5]}

    def leaf_gap(nid: int, tot: np.ndarray) -> float:
        """|G_program - G|, the first recovered with the reference's H."""
        return abs(-float(tree_val[nid]) * (tot[1] + reg_lambda) - tot[0])

    for depth in range(max_depth + 1):
        slots = levels[depth]
        if all(nid < 0 for nid in slots):
            break
        n = len(slots)
        abs_g = combine(f"abs{depth}", slot_sums(np.abs(gh[:, :1]), node, n))
        tot = combine(f"tot{depth}", slot_sums(gh, node, n))
        tot_op = combine(f"top{depth}", slot_sums(gh_op, node, n))
        hist = None if depth == max_depth else combine(
            f"hist{depth}", shard.level_hist(gh_op, node, n))
        tab = np.zeros((n, 4), np.int64)
        for s, nid in enumerate(slots):
            if nid < 0:
                continue
            feat, thr, dleft = (int(v) for v in tree_int[nid, :3])
            band = BOUNDARY * abs_g[s, 0] ** 2 / (tot_op[s, 1] + reg_lambda)
            if hist is not None:
                left, right = split_gains(hist[s], tot_op[s], reg_lambda,
                                          min_child_weight)
                best = float(max(left.max(), right.max()))
            if feat < 0:
                leaves += 1
                if abs_g[s, 0] > 0:
                    leaf_err = max(leaf_err,
                                   leaf_gap(nid, tot[s]) / abs_g[s, 0])
                gap = leaf_gap(nid, tot_op[s])
                gap_op += gap
                abs_all += abs_g[s, 0]
                if worst_leaf is None or gap > worst_leaf["gap"]:
                    worst_leaf = {
                        "gap": gap, "depth": depth, "slot": s, "node": nid,
                        "sum_abs_g": float(abs_g[s, 0]),
                        "G": float(tot_op[s, 0]), "H": float(tot_op[s, 1]),
                        "weight": float(tree_val[nid]),
                        "parent": [int(v) for v in tree_int[
                            parent_of[nid]]] if nid in parent_of else None}
                # the depth limit stops a node; above it, only the rule
                unsplit += hist is not None and best > band
                continue
            splits += 1
            default_left += dleft != 0
            # the committed candidate, eligible to within the band
            left, right = split_gains(
                hist[s], tot_op[s], reg_lambda,
                min_child_weight * (1.0 - BOUNDARY))
            took = float((left if dleft else right)[feat, thr])
            here = (best - took) / max(best, band) if band > 0 \
                else float("inf")
            if here > regret:
                regret, worst = here, {
                    "depth": depth, "slot": s, "best": best, "took": took,
                    "band": band, "rows_hess": float(tot_op[s, 1])}
            tab[s] = (feat, thr, dleft, 1)
        if depth < max_depth:
            node = shard.partition(node, tab)
    return {"split_regret": regret, "unsplit_above_limit": float(unsplit),
            "leaf_sum_rel_err": leaf_err,
            "leaf_sum_rounded_rel_err": gap_op / abs_all if abs_all else 0.0,
            "splits": splits,
            "leaves": leaves, "default_left": int(default_left),
            "worst_split": worst, "worst_leaf": worst_leaf}


def replay(values: np.ndarray, labels: np.ndarray, cuts: np.ndarray,
           forest_int: np.ndarray, forest_val: np.ndarray, which: list[int],
           nbin: int, max_depth: int, rate: float, reg_lambda: float,
           min_child_weight: float, operand_dtype: str,
           combine=lambda tag, a: a) -> dict:
    """The worst of each number over the trees ``which`` of the forest,
    each replayed on the margins of the trees before it."""
    shard = Shard(values, labels, cuts, nbin)
    counts = ("splits", "leaves", "default_left")
    out: dict = {"worst_split": None, "worst_leaf": None}
    for k in sorted(set(which)):
        gh = shard.grad_hess(shard.margins(
            forest_int[:k], forest_val[:k], rate, max_depth))
        got = replay_tree(
            shard, gh, forest_int[k], forest_val[k], max_depth,
            reg_lambda, min_child_weight, operand_dtype,
            lambda tag, a, k=k: combine(f"t{k}-{tag}", a))
        if got["split_regret"] >= out.get("split_regret", 0):
            out["worst_split"] = got["worst_split"]
        if got["leaf_sum_rounded_rel_err"] >= out.get(
                "leaf_sum_rounded_rel_err", 0):
            out["worst_leaf"] = dict(got["worst_leaf"] or {}, tree=k)
        for name, v in got.items():
            if not name.startswith("worst_"):
                out[name] = out.get(name, 0) + v if name in counts \
                    else max(out.get(name, 0), v)
    return out

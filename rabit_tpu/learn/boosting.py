"""Distributed gradient-boosted trees over the histogram allreduce.

The reference library's historical role is the collective inside
XGBoost: workers hold row shards, build per-node gradient histograms,
and Allreduce<Sum> them so every worker picks the same split
(rabit-learn ships the collective; the booster lived in XGBoost).  This
module closes that loop with a compact binned GBDT so the histogram
path is exercised end-to-end as a real app: logistic or squared loss,
level-wise trees, split gain from second-order statistics.

TPU-native notes: features are quantile-binned once (int32 on device);
per-node histograms come from the MXU one-hot contraction in
:mod:`rabit_tpu.learn.histogram` with node membership folded into the
grad/hess operand (static shapes — no gather/partition per node).  The
only cross-rank traffic per level is one histogram allreduce per node,
the XGBoost wire pattern.  Fault tolerance: one checkpoint per boosting
round, the reference's per-iteration commit structure.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import rabit_tpu
from rabit_tpu.learn import histogram
from rabit_tpu.ops import MAX, SUM, on_tpu
from rabit_tpu.utils.checks import check


@dataclass
class TreeNode:
    feature: int = -1          # -1 = leaf
    bin_threshold: int = 0     # go left if bin <= threshold
    value: float = 0.0         # leaf weight
    left: int = -1
    right: int = -1
    # learned default direction for missing values (XGBoost's
    # sparsity-aware split; rows whose bin is the missing bin go this way)
    default_left: bool = True


@dataclass
class BoostedModel:
    """A forest of binned trees + the quantile cuts that define bins."""

    cuts: np.ndarray = field(
        default_factory=lambda: np.zeros((0, 0), np.float32))
    trees: list[list[TreeNode]] = field(default_factory=list)
    base_score: float = 0.0
    learning_rate: float = 0.3
    loss: str = "logistic"
    # does ANY rank's shard carry NaN features?  Decided once at round 0
    # (a collective) and carried in the model: a resumed rank must NOT
    # re-issue that collective — an op the survivors don't issue in the
    # same span would break the robust engine's replay alignment.
    has_missing: bool = False

    def _tree_margin(self, tree: list[TreeNode], bins: np.ndarray
                     ) -> np.ndarray:
        missing_bin = self.cuts.shape[1] + 1
        node = np.zeros(bins.shape[0], np.int32)
        out = np.zeros(bins.shape[0], np.float32)
        live = np.ones(bins.shape[0], bool)
        # level-wise walk: every row sits at some node; descend until leaf
        for _ in range(64):  # depth bound
            if not live.any():
                break
            for nid in np.unique(node[live]):
                n = tree[nid]
                rows = live & (node == nid)
                if n.feature < 0:
                    out[rows] = n.value
                    live[rows] = False
                else:
                    b = bins[rows, n.feature]
                    go_left = np.where(b == missing_bin,
                                       getattr(n, "default_left", True),
                                       b <= n.bin_threshold)
                    idx = np.flatnonzero(rows)
                    node[idx[go_left]] = n.left
                    node[idx[~go_left]] = n.right
        return out

    def margin(self, bins: np.ndarray) -> np.ndarray:
        out = np.full(bins.shape[0], self.base_score, np.float32)
        for tree in self.trees:
            out += self.learning_rate * self._tree_margin(tree, bins)
        return out

    def predict(self, values: np.ndarray) -> np.ndarray:
        bins = apply_cuts(values, self.cuts)
        m = self.margin(bins)
        if self.loss == "logistic":
            return 1.0 / (1.0 + np.exp(-m))
        return m


# re-exported for callers binning prediction-time data
apply_cuts = histogram.apply_cuts


def _grad_hess(margin: np.ndarray, labels: np.ndarray, loss: str):
    if loss == "logistic":
        p = 1.0 / (1.0 + np.exp(-margin))
        return (p - labels).astype(np.float32), (p * (1 - p)).astype(
            np.float32)
    return (margin - labels).astype(np.float32), np.ones_like(margin)


def train(values: np.ndarray, labels: np.ndarray, num_round: int = 10,
          max_depth: int = 3, nbin: int = 32, learning_rate: float = 0.3,
          reg_lambda: float = 1.0, loss: str = "logistic",
          min_child_weight: float = 1e-3,
          subsample: float = 1.0, seed: int = 0,
          use_pallas: bool | None = None,
          compute_dtype: str | None = None) -> BoostedModel:
    """Train a distributed booster on this rank's row shard.

    Deterministic across ranks: cuts come from rank 0, every split
    decision is taken on the allreduced histogram.  Resumes from the
    last committed round after a failure (checkpoint per round).

    ``subsample < 1`` draws a fresh per-round row sample (stochastic
    gradient boosting): sampled-out rows contribute no gradient mass to
    any histogram or leaf this round.  The draw is seeded by
    ``(seed, round, rank)``, so a resumed run replays the exact sample
    of the round it died in — replay stays bit-aligned with survivors.

    NaN feature values are missing: they bin into a dedicated slot,
    every split learns a default direction from the missing rows'
    gradient mass (``histogram.split_gain_missing``), and prediction
    routes NaN the same way — XGBoost's sparsity-aware splits.

    ``use_pallas``/``compute_dtype`` pin the histogram path: on TPU the
    default is the fused Pallas kernel with bf16-rounded weights
    (fastest); reproducibility-sensitive callers can force the exact
    float32 XLA path with ``use_pallas=False`` (bit-identical to CPU)
    or keep the kernel but widen it with ``compute_dtype="float32"``.
    """
    check(0.0 < subsample <= 1.0, "subsample must be in (0, 1], got %s",
          subsample)
    n, f = values.shape
    version, restored = rabit_tpu.load_checkpoint()
    nan_handle = None
    if version == 0:
        # rank 0's shard defines the cuts; other ranks just receive them
        cuts = rabit_tpu.broadcast(
            histogram.quantile_cuts(values, nbin)
            if rabit_tpu.get_rank() == 0 else None, 0)
        # missing handling is GLOBAL: any rank with NaNs means every
        # rank must carry the extra histogram slot and the missing-aware
        # gain.  Decided HERE (round 0) and checkpointed in the model —
        # a resume must not repeat the collective (replay alignment).
        # Issued async with fuse=False (a lone op waiting in a bucket
        # would not start until wait()): the MAX vote rides the wire
        # while this rank runs the big apply_cuts binning pass below.
        nan_handle = rabit_tpu.allreduce_async(
            np.array([np.isnan(values).any()], np.int32), MAX, fuse=False)
        base = 0.0
        model = BoostedModel(cuts=cuts, base_score=base,
                             learning_rate=learning_rate, loss=loss,
                             has_missing=False)
    else:
        model = restored
    bins = apply_cuts(values, model.cuts)
    if nan_handle is not None:
        model.has_missing = bool(nan_handle.wait()[0])
    has_missing = getattr(model, "has_missing", False)
    missing_bin = model.cuts.shape[1] + 1
    margin = model.margin(bins)  # recomputed once on (re)start
    # resident transposed bins: the fused level-histogram kernel streams
    # the (f, n) layout; transpose once, reuse every node/level/round
    import jax
    bins_t = (jax.numpy.asarray(bins).T
              if on_tpu() else None)

    epoch = rabit_tpu.device_epoch()
    for round_idx in range(version, num_round):
        if bins_t is not None and rabit_tpu.device_epoch() != epoch:
            # device plane re-formed after a failure: old-epoch arrays
            # died with the backends — re-upload the resident bins
            epoch = rabit_tpu.device_epoch()
            bins_t = jax.numpy.asarray(bins).T
        grad, hess = _grad_hess(margin, labels, model.loss)
        if subsample < 1.0:
            # zeroed grad/hess = row contributes nothing anywhere this
            # round (histograms, depth-limit leaves) while every shape
            # stays static for the fused kernels
            rng = np.random.default_rng(
                (seed, round_idx, rabit_tpu.get_rank()))
            keep = rng.random(n) < subsample
            grad = np.where(keep, grad, 0.0).astype(np.float32)
            hess = np.where(keep, hess, 0.0).astype(np.float32)

        tree: list[TreeNode] = [TreeNode()]
        node_of_row = np.zeros(n, np.int32)
        frontier = [0]
        for depth in range(max_depth):
            next_frontier: list[int] = []
            # every live node's histogram in one fused bins pass and
            # ONE allreduce for the level (the per-node XGBoost wire
            # pattern, batched)
            hists = histogram.build_level_allreduce(
                bins, grad, hess, node_of_row, frontier,
                missing_bin + 1 if has_missing else missing_bin,
                bins_t=bins_t,
                use_pallas=use_pallas, compute_dtype=compute_dtype)
            for pos, nid in enumerate(frontier):
                hist = hists[pos]
                g_tot = hist[:, :, 0].sum(axis=1)[0]
                h_tot = hist[:, :, 1].sum(axis=1)[0]
                leaf_value = -g_tot / (h_tot + reg_lambda)
                if has_missing:
                    gain, default_left = histogram.split_gain_missing(
                        hist, reg_lambda)
                else:
                    gain = histogram.split_gain(hist, reg_lambda)
                    default_left = None
                j, t = np.unravel_index(int(gain.argmax()), gain.shape)
                dl = bool(default_left[j, t]) if has_missing else True
                hl = hist[j, :t + 1, 1].sum()
                if has_missing and dl:
                    hl += hist[j, -1, 1]
                hr = h_tot - hl
                if (gain[j, t] <= 1e-12 or hl < min_child_weight
                        or hr < min_child_weight):
                    tree[nid].value = float(leaf_value)
                    continue
                node = tree[nid]
                node.feature = int(j)
                node.bin_threshold = int(t)
                node.default_left = dl
                node.left = len(tree)
                tree.append(TreeNode())
                node.right = len(tree)
                tree.append(TreeNode())
                rows = node_of_row == nid
                b = bins[:, j]
                go_left = np.where(b == missing_bin, dl, b <= t)
                node_of_row[rows & go_left] = node.left
                node_of_row[rows & ~go_left] = node.right
                next_frontier += [node.left, node.right]
            frontier = next_frontier
            if not frontier:
                break
        # frontier nodes at max depth become leaves: one batched
        # allreduce of all their (g, h) sums (not one per leaf)
        if frontier:
            gh = np.empty((len(frontier), 2), np.float64)
            for i, nid in enumerate(frontier):
                mask = node_of_row == nid
                gh[i] = (grad[mask].sum(), hess[mask].sum())
            gh = rabit_tpu.allreduce(gh.reshape(-1), SUM).reshape(-1, 2)
            for i, nid in enumerate(frontier):
                tree[nid].value = float(-gh[i, 0] / (gh[i, 1] + reg_lambda))
        model.trees.append(tree)
        margin += model.learning_rate * model._tree_margin(tree, bins)
        rabit_tpu.checkpoint(model)
    return model

"""The plain reference for boosting under XGBoost's ``approx`` tree
method: level-wise trees, logistic loss, the structure-score gain, leaf
weight ``-G / (H + lambda)``, **and cuts that are sketched anew before
every tree from every row, with that round's hessians as weights**.
Float32 ``jax.numpy`` under matmul precision ``highest`` for the sums a
chunk, float64 numpy across chunks; imports nothing of ``rabit_tpu``.
``reference/gbdt.py`` gives the pieces that know nothing of the sketch
(a tree's levels, the rows binned by given cuts, the walk of one tree).

Like ``reference/gbdt.py`` it trains no forest of its own: it replays
the program's committed forest one step.  For tree ``k`` it computes
margins from the trees before it, **each routed by its nodes' float
split values** (every tree has cuts of its own, so no one binning of the
rows serves them all), then gradients and hessians, and holds tree
``k`` to them twice:

* its committed cuts against the semantics of the sketch.  With ``W``
  the summed hessian of the rows present at a feature, cut ``i`` has to
  lie where the weighted rank reaches ``(i + 1) / nbin`` of ``W``; a
  cut's rank is the interval from the weight of the rows below it to
  that of the rows at or below it (ties span an interval), and
  ``cut_rank_err`` is the largest distance from a target to its cut's
  interval, as a share of ``W``, over features and cuts;
* the tree itself on the rows binned by **its own** cuts, as
  ``reference/gbdt.py replay_tree`` walks it.

The weights at or below each cut are sums of float32 hessians added up
in float32 a block of 4,096 rows, in float32 over a chunk's 64 blocks
and in float64 across chunks: nothing of a sort, no summary, no merge.
"""
from __future__ import annotations

import numpy as np

from perfbench.reference import gbdt as base

CHUNK_ROWS = base.CHUNK_ROWS
BLOCK_ROWS = base.BLOCK_ROWS


# ----------------------------------------------------------------------
# margins by value
# ----------------------------------------------------------------------
def value_of(values, feat):
    """``values[r, feat[r]]`` without a gather (NaN where it is)."""
    import jax.numpy as jnp

    return jnp.sum(jnp.where(feat[:, None] == jnp.arange(values.shape[1]),
                             values, 0.0), axis=1)


class Rows:
    """The float rows and their labels on the device in chunks."""

    def __init__(self, values: np.ndarray, labels: np.ndarray):
        import jax.numpy as jnp

        self.n, self.f = values.shape
        chunk = min(self.n, CHUNK_ROWS)
        self.values = [jnp.asarray(values[lo:lo + chunk], jnp.float32)
                       for lo in range(0, self.n, chunk)]
        self.labels = [jnp.asarray(labels[lo:lo + chunk], jnp.float32)
                       for lo in range(0, self.n, chunk)]

    def free(self) -> None:
        self.values = self.labels = None

    def margins(self, forest_int, forest_val, forest_split, rate: float,
                max_depth: int):
        """The margin of every row under the given trees (base 0), a
        row going left where its value is under the node's split and,
        where it has none, the way the node's default says."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def add_tree(margin, values, tree_int, tree_val, tree_split):
            node = jnp.zeros(values.shape[0], jnp.int32)
            for _ in range(max_depth):
                feat, _thr, dleft, left, right = (
                    base.rows_of(tree_int, node).astype(jnp.int32).T)
                split = base.rows_of(tree_split[:, None], node)[:, 0]
                v = value_of(values, feat)
                goes_left = jnp.where(jnp.isnan(v), dleft != 0, v < split)
                node = jnp.where(feat >= 0,
                                 jnp.where(goes_left, left, right), node)
            return margin + jnp.float32(rate) * base.rows_of(
                tree_val[:, None], node)[:, 0]

        out = [jnp.zeros(v.shape[0], jnp.float32) for v in self.values]
        for t_int, t_val, t_split in zip(forest_int, forest_val,
                                         forest_split):
            t_int, t_val, t_split = (jnp.asarray(t_int), jnp.asarray(t_val),
                                     jnp.asarray(t_split))
            out = [add_tree(m, v, t_int, t_val, t_split)
                   for m, v in zip(out, self.values)]
        return out

    def grad_hess(self, margins):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def gh(margin, labels):
            p = 1.0 / (1.0 + jnp.exp(-margin))
            return jnp.stack([p - labels, p * (1.0 - p)], axis=1)

        return [gh(m, y) for m, y in zip(margins, self.labels)]

    # ---- the weighted rank of given cuts -----------------------------
    def weight_under(self, weights, cuts: np.ndarray) -> np.ndarray:
        """``(2, f, ncut + 1)`` float64: for every feature the summed
        weight of the present rows by how many of the feature's cuts lie
        below the row's value (``[0]``: a row tied with a cut counts as
        above it) and by how many lie at or below it (``[1]``)."""
        import jax
        import jax.numpy as jnp

        nbin = cuts.shape[1] + 1

        @jax.jit
        def tally(values, w, cuts):
            rows = values.shape[0]
            block = min(rows, BLOCK_ROWS)
            pad = -rows % block
            values = jnp.pad(values, ((0, pad), (0, 0)),
                             constant_values=jnp.nan)
            w = jnp.pad(w, (0, pad))
            nb = (rows + pad) // block

            def body(acc, xs):
                v, wb = xs
                present = ~jnp.isnan(v)
                below = jnp.sum(cuts[None] < v[:, :, None], axis=2)
                upto = jnp.sum(cuts[None] <= v[:, :, None], axis=2)
                oh = jnp.stack([below, upto])[..., None] == jnp.arange(nbin)
                oh = (oh & present[None, :, :, None]).astype(jnp.float32)
                return acc + jnp.einsum("srfk,r->sfk", oh, wb), None

            with jax.default_matmul_precision("highest"):
                acc, _ = jax.lax.scan(
                    body, jnp.zeros((2, values.shape[1], nbin), jnp.float32),
                    (values.reshape(nb, block, -1), w.reshape(nb, block)))
            return acc

        cuts = jnp.asarray(cuts, jnp.float32)
        total = np.zeros((2, self.f, nbin), np.float64)
        for part in [tally(v, w, cuts) for v, w in zip(self.values, weights)]:
            total += np.asarray(part, np.float64)
        return total


def cut_rank_err(under: np.ndarray) -> float:
    """From :meth:`Rows.weight_under` of a tree's cuts (summed over the
    ranks): the largest distance from ``(i + 1) / nbin`` to cut ``i``'s
    weighted rank interval, as a share of the feature's weight, over
    features and cuts.  A row's value is under cut ``i`` where at most
    ``i`` cuts lie at or below it, and at or under it where at most
    ``i`` cuts lie below it.  A feature without weight has no rank."""
    ncut = under.shape[2] - 1
    total = under[1].sum(axis=1)
    below = np.cumsum(under[1], axis=1)[:, :ncut]     # value < cut i
    upto = np.cumsum(under[0], axis=1)[:, :ncut]      # value <= cut i
    want = np.arange(1, ncut + 1) / (ncut + 1.0)
    live = total > 0
    if not live.any():
        return 0.0
    w = total[live, None]
    gap = np.maximum(np.maximum(below[live] / w - want,
                                want - upto[live] / w), 0.0)
    return float(gap.max())


# ----------------------------------------------------------------------
# the replay
# ----------------------------------------------------------------------
def replay(values: np.ndarray, labels: np.ndarray, tree_cuts: np.ndarray,
           forest_int: np.ndarray, forest_val: np.ndarray,
           forest_split: np.ndarray, which: list[int], nbin: int,
           max_depth: int, rate: float, reg_lambda: float,
           min_child_weight: float, operand_dtype: str,
           combine=lambda tag, a: a) -> dict:
    """The worst of each number over the trees ``which`` of the forest,
    each replayed on the margins of the trees before it and on its own
    cuts ``tree_cuts[k]``; ``cut_rank_err`` by tree as well
    (``cut_rank_err_by_tree``).  ``combine(tag, array)`` adds an array
    up over the ranks."""
    rows = Rows(values, labels)
    out: dict = {"cut_rank_err_by_tree": {}}
    try:
        for k in sorted(set(which)):
            gh = rows.grad_hess(rows.margins(
                forest_int[:k], forest_val[:k], forest_split[:k], rate,
                max_depth))
            under = combine(f"t{k}-rank", rows.weight_under(
                [w[:, 1] for w in gh], tree_cuts[k]))
            err = cut_rank_err(under)
            out["cut_rank_err_by_tree"][k] = err
            out["cut_rank_err"] = max(out.get("cut_rank_err", 0.0), err)
            shard = base.Shard(values, labels, tree_cuts[k], nbin)
            try:
                got = base.replay_tree(
                    shard, gh, forest_int[k], forest_val[k], max_depth,
                    reg_lambda, min_child_weight, operand_dtype,
                    lambda tag, a, k=k: combine(f"t{k}-{tag}", a))
            finally:
                shard.free()
            for name, v in got.items():
                out[name] = max(out.get(name, 0), v) \
                    if name not in ("splits", "leaves") \
                    else out.get(name, 0) + v
    finally:
        rows.free()
    return out

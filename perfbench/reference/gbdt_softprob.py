"""The plain reference for multi-class histogram boosting (XGBoost's
``objective=multi:softprob``, one tree a class a round): the softmax
gradient of every class from one set of margins, and per class the walk
of a committed tree that ``reference/gbdt.py replay_tree`` makes.  Float32
``jax.numpy`` under matmul precision ``highest``, no kernel; imports
nothing of ``rabit_tpu``.  The bins, the tree walk, the histograms and
the structure score are ``reference/gbdt.py``'s own, as they are.

Like that file it trains no forest: it replays the program's committed
forest one round.  The committed trees are numbered round-major: tree
``t * K + k`` is round t's for class k.  From the forest without round
``t`` it computes the ``(K, n)`` margins (``base_score`` a class and the
learning rate times every earlier tree of that class), from them
``p = softmax(m)`` a row (the largest margin subtracted first),
``g[k] = p[k] - [y = k]`` and ``h[k] = max(2 p[k] (1 - p[k]), 1e-16)``
for every class at once, and then walks the program's tree of each
class with its own histograms of ``(g[k], h[k])``, as
``reference/gbdt.py replay_tree`` walks a binary job's tree.  None of the K trees
of a round sees another's update: that is what a round is.

One thing of the walk is this file's own (``replay_tree``).  XGBoost's
``EnumerateSplit`` scores only the candidates whose children both weigh
``min_child_weight`` or more; ``reference/gbdt.py`` scores every
candidate and asks afterwards whether the best one's children do, which
is the same wherever no node is light (the HIGGS cell's half a million
rows a leaf) and is not at a rare class's nodes, where the unconstrained
best may cut off a few rows the rule does not allow.  Here a split is
held against the best candidate the rule allows, and a leaf above the
depth limit against whether the rule allows any candidate with a gain.
The program's hessian sums are those of bfloat16 operands and the
reference's of float32, so a child within ``WEIGHT_BAND`` of the least
weight may be allowed by one and not by the other: the reference's best
is taken over the candidates clearly allowed, and the program's split
is held to be allowed unless it is clearly not.  And the histograms the
splits are judged on are those of the gradients rounded to
``operand_dtype``, the precision the configuration states for the
kernel's operand (summed in float32 a chunk and float64 across, as the
rounded leaf sums are): the gain of a rare class's split is a small
difference of large terms, a few parts in a thousand of them, and by
the histograms of the unrounded float32 gradients the split a bfloat16
operand picks reads a regret of 1% to over 100% in sound runs (PERF.md
section 6, PR 42), which says what the stated precision costs a rare
class and nothing about whether the program computed what it states.

Departures from XGBoost, beyond ``reference/gbdt.py``'s: the cuts are
the program's committed ones (quantiles of a sample, where XGBoost
sketches), so a one-hot column with fewer ones than a bin holds has one
bin and cannot be split on; the default direction of missing values is
not modelled (the configuration has none); ``base_score`` is added to
every class as a margin, not transformed.
"""
from __future__ import annotations

import numpy as np

from perfbench.reference import gbdt as ref

MIN_HESS = 1e-16            # XGBoost's kRtEps under a softmax hessian
WEIGHT_BAND = 2.0 ** -7     # of min_child_weight: twice a bfloat16 rounding


def softmax_grad_hess(margins, labels):
    """``(K, rows, 2)`` float32 (grad, hess) of every class from the
    ``(K, rows)`` margins and the ``(rows,)`` class ids, traceable."""
    import jax.numpy as jnp

    margins = jnp.asarray(margins, jnp.float32)
    e = jnp.exp(margins - jnp.max(margins, axis=0, keepdims=True))
    p = e / jnp.sum(e, axis=0, keepdims=True)
    hit = (jnp.asarray(labels)[None, :]
           == jnp.arange(margins.shape[0])[:, None]).astype(jnp.float32)
    return jnp.stack([p - hit, jnp.maximum(2.0 * p * (1.0 - p), MIN_HESS)],
                     axis=2)


def class_margins(shard: ref.Shard, forest_int, forest_val, rounds: int,
                  num_class: int, rate: float, base_score: float,
                  max_depth: int):
    """The margins of every row under the first ``rounds`` rounds of
    the forest: a chunk of rows an entry, each ``(K, rows)`` float32."""
    import jax.numpy as jnp

    upto = rounds * num_class
    per_class = [shard.margins(forest_int[k:upto:num_class],
                               forest_val[k:upto:num_class], rate, max_depth)
                 for k in range(num_class)]
    return [jnp.stack(chunk) + jnp.float32(base_score)
            for chunk in zip(*per_class)]


def grad_hess(shard: ref.Shard, margins):
    """The round's gradients from its margins: for class k a list of
    ``(rows, 2)`` arrays, a chunk an entry, as ``replay_tree`` takes
    them."""
    import jax

    fn = jax.jit(softmax_grad_hess)
    chunks = [fn(m, y) for m, y in zip(margins, shard.labels)]
    return [[c[k] for c in chunks] for k in range(chunks[0].shape[0])]


def head_grad_hess(values: np.ndarray, labels: np.ndarray, cuts: np.ndarray,
                   forest_int, forest_val, num_class: int, nbin: int,
                   max_depth: int, rate: float, base_score: float
                   ) -> np.ndarray:
    """``(K, 2, rows)`` float32: the gradients of the round after the
    whole committed forest, of the rows given (a head of the shard)."""
    shard = ref.Shard(values, labels, cuts, nbin)
    try:
        rounds = len(forest_int) // num_class
        (margins,) = class_margins(shard, forest_int, forest_val, rounds,
                                   num_class, rate, base_score, max_depth)
        return np.asarray(softmax_grad_hess(margins, shard.labels[0])
                          ).transpose(0, 2, 1)
    finally:
        shard.free()


def allowed_gain(hist: np.ndarray, reg_lambda: float, least: float):
    """(f, nbin - 1) gains of one node's histogram, -inf where a child
    weighs under ``least``."""
    gain = ref.split_gain(hist, reg_lambda)
    hl = np.cumsum(hist[:, :, 1], axis=1)[:, :-1]
    hr = hist[:, :, 1].sum(axis=1, keepdims=True) - hl
    return np.where((hl >= least) & (hr >= least), gain, -np.inf)


def replay_tree(shard: ref.Shard, gh, tree_int: np.ndarray,
                tree_val: np.ndarray, max_depth: int, reg_lambda: float,
                min_child_weight: float, operand_dtype: str,
                combine=lambda tag, a: a) -> dict:
    """``reference/gbdt.py replay_tree`` (the same walk, the same leaf
    sums, the same names) with the stopping rule and the regret taken
    over the candidates ``min_child_weight`` allows (the module's
    docstring)."""
    import jax.numpy as jnp

    levels = ref.levels_of(tree_int, max_depth)
    node = [jnp.zeros(b.shape[0], jnp.int32) for b in shard.bins]
    gh_op = [w.astype(operand_dtype).astype(jnp.float32) for w in gh]
    regret, unsplit, leaf_err, leaf_err_op = 0.0, 0, 0.0, 0.0
    splits = leaves = 0
    clearly = min_child_weight * (1.0 + WEIGHT_BAND)
    hardly = min_child_weight * (1.0 - WEIGHT_BAND)

    def leaf_gap(nid: int, tot: np.ndarray, abs_g: float) -> float:
        g_prog = -float(tree_val[nid]) * (tot[1] + reg_lambda)
        return abs(g_prog - tot[0]) / abs_g if abs_g > 0 else 0.0

    for depth in range(max_depth + 1):
        slots = levels[depth]
        if all(nid < 0 for nid in slots):
            break
        n = len(slots)
        sums = combine(f"abs{depth}", shard.abs_sums(gh, node, n))
        tot = combine(f"tot{depth}", ref.leaf_sums(shard, gh, node, n))
        tot_op = combine(f"top{depth}", ref.leaf_sums(shard, gh_op, node, n))
        hist = None if depth == max_depth else combine(
            f"hist{depth}", shard.level_hist(gh_op, node, n))
        tab = np.zeros((n, 3), np.int32)
        for s, nid in enumerate(slots):
            if nid < 0:
                continue
            feat, thr = int(tree_int[nid, 0]), int(tree_int[nid, 1])
            if feat < 0:
                leaves += 1
                leaf_err = max(leaf_err, leaf_gap(nid, tot[s], sums[s, 1]))
                leaf_err_op = max(leaf_err_op,
                                  leaf_gap(nid, tot_op[s], sums[s, 1]))
                # the depth limit stops a node; above it, only the rule
                if hist is not None and allowed_gain(
                        hist[s], reg_lambda, clearly).max() > ref.MIN_GAIN:
                    unsplit += 1
                continue
            splits += 1
            best = float(allowed_gain(hist[s], reg_lambda, clearly).max())
            took = float(allowed_gain(hist[s], reg_lambda, hardly)[feat, thr])
            if best > 0:
                regret = max(regret, (best - took) / best)
            elif not took > 0:            # nothing to gain, and split
                regret = float("inf")
            tab[s] = (feat, thr, 1)
        if depth < max_depth:
            node = shard.partition(node, tab)
    return {"split_regret": regret, "unsplit_above_limit": float(unsplit),
            "leaf_sum_rel_err": leaf_err,
            "leaf_sum_rounded_rel_err": leaf_err_op, "splits": splits,
            "leaves": leaves}


def replay(values: np.ndarray, labels: np.ndarray, cuts: np.ndarray,
           forest_int: np.ndarray, forest_val: np.ndarray, which: list[int],
           num_class: int, nbin: int, max_depth: int, rate: float,
           base_score: float, reg_lambda: float, min_child_weight: float,
           operand_dtype: str, combine=lambda tag, a: a) -> dict:
    """The worst of each of ``replay_tree``'s numbers over the K trees
    of each round of ``which``, every tree of a round walked on the
    gradients of the forest without that round (``splits`` and
    ``leaves`` are added up); ``by_class`` holds each number's worst a
    class, for the run's record."""
    shard = ref.Shard(values, labels, cuts, nbin)
    out: dict = {}
    by_class = [dict() for _ in range(num_class)]
    try:
        for t in sorted(set(which)):
            gh = grad_hess(shard, class_margins(
                shard, forest_int, forest_val, t, num_class, rate,
                base_score, max_depth))
            for k in range(num_class):
                at = t * num_class + k
                got = replay_tree(
                    shard, gh[k], forest_int[at], forest_val[at], max_depth,
                    reg_lambda, min_child_weight, operand_dtype,
                    lambda tag, a, at=at: combine(f"t{at}-{tag}", a))
                for name, v in got.items():
                    counted = name in ("splits", "leaves")
                    for into in (out, by_class[k]):
                        into[name] = into.get(name, 0) + v if counted \
                            else max(into.get(name, 0), v)
    finally:
        shard.free()
    out["by_class"] = by_class
    return out

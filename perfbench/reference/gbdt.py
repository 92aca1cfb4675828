"""The plain reference for histogram boosting: level-wise trees on
quantile bins, logistic loss, the structure-score gain, leaf weight
``-G / (H + lambda)``.  Float32 ``jax.numpy`` under matmul precision
``highest``, no kernel; imports nothing of ``rabit_tpu``.

It does not train a forest of its own: it replays the program's
committed forest one step.  From the forest without tree ``k`` it
computes margins, then gradients, then walks *the program's* tree ``k``
level by level with its own histograms, and says how good each split
the program chose is by the reference's own gains and how far each
committed leaf weight is from the reference's sums.

The rows are held on the device in chunks and a level's histograms are
added up on the host in float64, one float32 partial a chunk, so the
sums the program's are held against carry no long float32 chain.
"""
from __future__ import annotations

import numpy as np

CHUNK_ROWS = 1 << 18
BLOCK_ROWS = 4096
MIN_GAIN = 1e-12


# ----------------------------------------------------------------------
# cuts and bins
# ----------------------------------------------------------------------
def cut_sample(values: np.ndarray, sample_rows: int) -> np.ndarray:
    """The stated sample: every ``n // sample_rows``-th row, at most
    ``sample_rows`` of them (all rows of a shard that small)."""
    return values[::max(1, values.shape[0] // sample_rows)][:sample_rows]


def quantile_cuts(sample: np.ndarray, nbin: int) -> np.ndarray:
    """``nbin - 1`` interior quantiles a column, (f, nbin - 1) float32."""
    qs = np.linspace(0, 1, nbin + 1)[1:-1]
    return np.quantile(sample.astype(np.float32), qs, axis=0).T.astype(
        np.float32)


def bin_rows(values: np.ndarray, cuts: np.ndarray) -> np.ndarray:
    """(f, rows) bins: the number of cuts at or below each value."""
    return np.stack([np.searchsorted(cuts[j], values[:, j], side="right")
                     for j in range(values.shape[1])]).astype(np.int32)


# ----------------------------------------------------------------------
# trees as the adapter hands them over
# ----------------------------------------------------------------------
# forest_int[t, i] = (feature, bin_threshold, default_left, left, right),
# feature -1 for a leaf and -2 for a row of padding; forest_val[t, i] is
# the leaf weight
def levels_of(tree_int: np.ndarray, max_depth: int) -> list[list[int]]:
    """Node ids by level slot: ``levels[d][s]``, -1 where there is
    none; the children of slot ``s`` are slots ``2s`` and ``2s + 1``."""
    levels = [[0]]
    for _ in range(max_depth):
        nxt = []
        for nid in levels[-1]:
            split = nid >= 0 and tree_int[nid, 0] >= 0
            nxt += [int(tree_int[nid, 3]), int(tree_int[nid, 4])] \
                if split else [-1, -1]
        levels.append(nxt)
    return levels


# ----------------------------------------------------------------------
# the rows, on the device in chunks
# ----------------------------------------------------------------------
def rows_of(table, idx):
    """``table[idx]`` for a table of a few hundred rows, as a one-hot
    product: exact in float32 under ``highest`` (one term a row), and
    no gather, which the chip does slowly.  Returns float32."""
    import jax
    import jax.numpy as jnp

    oh = (idx[:, None] == jnp.arange(table.shape[0])).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return oh @ table.astype(jnp.float32)


def bin_of(bins, feat):
    """``bins[r, feat[r]]`` without a gather."""
    import jax.numpy as jnp

    return jnp.sum(jnp.where(feat[:, None] == jnp.arange(bins.shape[1]),
                             bins, 0), axis=1)


class Shard:
    def __init__(self, values: np.ndarray, labels: np.ndarray,
                 cuts: np.ndarray, nbin: int):
        import jax
        import jax.numpy as jnp

        self.n, self.f = values.shape
        self.nbin = nbin
        self.chunk = min(self.n, CHUNK_ROWS)
        self.cuts = jnp.asarray(cuts)

        @jax.jit
        def bin_chunk(vals, cuts):
            return jax.vmap(
                lambda c, v: jnp.searchsorted(c, v, side="right",
                                              method="compare_all"),
                in_axes=(0, 1), out_axes=1)(cuts, vals).astype(jnp.int32)

        self.bins, self.labels = [], []
        for lo in range(0, self.n, self.chunk):
            self.bins.append(bin_chunk(
                jnp.asarray(values[lo:lo + self.chunk]), self.cuts))
            self.labels.append(jnp.asarray(
                labels[lo:lo + self.chunk], jnp.float32))
        self._fns: dict = {}

    def free(self) -> None:
        self.bins = self.labels = self._fns = None

    # ---- margins of a forest -----------------------------------------
    def margins(self, forest_int, forest_val, rate: float, max_depth: int):
        """The margin of every row under the given trees (base 0)."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def add_tree(margin, bins, tree_int, tree_val):
            node = jnp.zeros(bins.shape[0], jnp.int32)
            for _ in range(max_depth):
                feat, thr, _dl, left, right = (
                    rows_of(tree_int, node).astype(jnp.int32).T)
                child = jnp.where(bin_of(bins, feat) <= thr, left, right)
                node = jnp.where(feat >= 0, child, node)
            return margin + jnp.float32(rate) * rows_of(
                tree_val[:, None], node)[:, 0]

        out = [jnp.zeros(b.shape[0], jnp.float32) for b in self.bins]
        for t_int, t_val in zip(forest_int, forest_val):
            t_int, t_val = jnp.asarray(t_int), jnp.asarray(t_val)
            out = [add_tree(m, b, t_int, t_val)
                   for m, b in zip(out, self.bins)]
        return out

    def grad_hess(self, margins):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def gh(margin, labels):
            p = 1.0 / (1.0 + jnp.exp(-margin))
            return jnp.stack([p - labels, p * (1.0 - p)], axis=1)

        return [gh(m, y) for m, y in zip(margins, self.labels)]

    # ---- one level ---------------------------------------------------
    def level_hist(self, gh, node, nslots: int) -> np.ndarray:
        """(nslots, f, nbin, 2) float64: per slot, feature and bin the
        sums of (grad, hess) over the rows at that slot."""
        import jax
        import jax.numpy as jnp

        key = ("hist", nslots)
        if key not in self._fns:
            nbin, f = self.nbin, self.f

            @jax.jit
            def hist(bins, gh, node):
                rows = bins.shape[0]
                block = min(rows, BLOCK_ROWS)
                pad = -rows % block
                bins = jnp.pad(bins, ((0, pad), (0, 0)))
                gh = jnp.pad(gh, ((0, pad), (0, 0)))
                node = jnp.pad(node, (0, pad), constant_values=-1)
                nb = (rows + pad) // block

                def body(acc, xs):
                    b, w, nd = xs
                    oh = (b[:, :, None] == jnp.arange(nbin)).astype(
                        jnp.float32)
                    ws = (nd[:, None] == jnp.arange(nslots)).astype(
                        jnp.float32)[:, :, None] * w[:, None, :]
                    return acc + jnp.einsum("rfk,rsc->sfkc", oh, ws), None

                with jax.default_matmul_precision("highest"):
                    acc, _ = jax.lax.scan(
                        body, jnp.zeros((nslots, f, nbin, 2), jnp.float32),
                        (bins.reshape(nb, block, f),
                         gh.reshape(nb, block, 2), node.reshape(nb, block)))
                return acc

            self._fns[key] = hist
        total = np.zeros((nslots, self.f, self.nbin, 2), np.float64)
        for part in [self._fns[key](b, w, nd)
                     for b, w, nd in zip(self.bins, gh, node)]:
            total += np.asarray(part, np.float64)
        return total

    def partition(self, node, tab: np.ndarray):
        """Every row to its child's slot; a row of a node that is a leaf
        leaves the walk (-1).  ``tab[s]`` = (feature, threshold, split)."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def move(bins, node, tab):
            feat, thr, split = rows_of(tab, node).astype(jnp.int32).T
            child = 2 * node + (bin_of(bins, feat) > thr).astype(jnp.int32)
            return jnp.where((node >= 0) & (split > 0), child, -1)

        tab = jnp.asarray(tab)
        return [move(b, nd, tab) for b, nd in zip(self.bins, node)]

    def abs_sums(self, gh, node, nslots: int) -> np.ndarray:
        """(nslots, 3) float64: rows, sum |grad|, sum |hess| a slot."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def sums(gh, node):
            oh = (node[:, None] == jnp.arange(nslots)).astype(jnp.float32)
            w = jnp.concatenate([jnp.ones_like(gh[:, :1]), jnp.abs(gh)],
                                axis=1)
            with jax.default_matmul_precision("highest"):
                return oh.T @ w

        total = np.zeros((nslots, 3), np.float64)
        for w, nd in zip(gh, node):
            total += np.asarray(sums(w, nd), np.float64)
        return total


# ----------------------------------------------------------------------
# the structure score
# ----------------------------------------------------------------------
def split_gain(hist: np.ndarray, reg_lambda: float) -> np.ndarray:
    """(f, nbin - 1) gains of one node's (f, nbin, 2) histogram: left is
    bins 0..t."""
    g, h = hist[:, :, 0], hist[:, :, 1]
    gl, hl = np.cumsum(g, axis=1)[:, :-1], np.cumsum(h, axis=1)[:, :-1]
    gt, ht = g.sum(axis=1, keepdims=True), h.sum(axis=1, keepdims=True)
    gr, hr = gt - gl, ht - hl
    return (gl * gl / (hl + reg_lambda) + gr * gr / (hr + reg_lambda)
            - gt * gt / (ht + reg_lambda))


def would_split(hist: np.ndarray, reg_lambda: float,
                min_child_weight: float) -> bool:
    """The stopping rule: the best split by gain is taken unless its
    gain is not positive or a child is lighter than the least weight."""
    gain = split_gain(hist, reg_lambda)
    j, t = np.unravel_index(int(gain.argmax()), gain.shape)
    hl = hist[j, :t + 1, 1].sum()
    hr = hist[j, :, 1].sum() - hl
    return bool(gain[j, t] > MIN_GAIN and hl >= min_child_weight
                and hr >= min_child_weight)


def replay_tree(shard: Shard, gh, tree_int: np.ndarray, tree_val: np.ndarray,
                max_depth: int, reg_lambda: float, min_child_weight: float,
                operand_dtype: str, combine=lambda tag, a: a) -> dict:
    """Walk one of the program's trees on the reference's gradients.
    ``combine(tag, array)`` adds an array up over the ranks.

    A committed leaf weight is ``-G / (H + lambda)``.  It is held
    against the reference's sums twice: against those of the float32
    gradients (``leaf_sum_rel_err``: what the stated operand precision
    costs, rounding bias included) and against those of the gradients
    rounded to ``operand_dtype``, the precision the configuration states
    for the kernel's operand, still summed in float32 and float64
    (``leaf_sum_rounded_rel_err``: what is left is accumulation, so a
    coarser operand, a dropped row or a narrower accumulator shows at
    once).  Each as |G_program - G| over the leaf's sum of |g|, with
    G_program recovered from the weight with the reference's H, so that
    an error of H shows too."""
    import jax.numpy as jnp

    levels = levels_of(tree_int, max_depth)
    node = [jnp.zeros(b.shape[0], jnp.int32) for b in shard.bins]
    gh_op = [w.astype(operand_dtype).astype(jnp.float32) for w in gh]
    regret, unsplit, leaf_err, leaf_err_op = 0.0, 0, 0.0, 0.0
    splits = leaves = 0

    def leaf_gap(nid: int, tot: np.ndarray, abs_g: float) -> float:
        g_prog = -float(tree_val[nid]) * (tot[1] + reg_lambda)
        return abs(g_prog - tot[0]) / abs_g if abs_g > 0 else 0.0

    for depth in range(max_depth + 1):
        slots = levels[depth]
        if all(nid < 0 for nid in slots):
            break
        n = len(slots)
        sums = combine(f"abs{depth}", shard.abs_sums(gh, node, n))
        tot = combine(f"tot{depth}", leaf_sums(shard, gh, node, n))
        tot_op = combine(f"top{depth}", leaf_sums(shard, gh_op, node, n))
        hist = None if depth == max_depth else combine(
            f"hist{depth}", shard.level_hist(gh, node, n))
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
                if hist is not None and would_split(
                        hist[s], reg_lambda, min_child_weight):
                    unsplit += 1
                continue
            splits += 1
            gain = split_gain(hist[s], reg_lambda)
            best = float(gain.max())
            regret = max(regret, (best - float(gain[feat, thr])) / best
                         if best > 0 else float("inf"))
            tab[s] = (feat, thr, 1)
        if depth < max_depth:
            node = shard.partition(node, tab)
    return {"split_regret": regret, "unsplit_above_limit": float(unsplit),
            "leaf_sum_rel_err": leaf_err,
            "leaf_sum_rounded_rel_err": leaf_err_op, "splits": splits,
            "leaves": leaves}


def leaf_sums(shard: Shard, gh, node, nslots: int) -> np.ndarray:
    """(nslots, 2) float64: sum of grad and of hess a slot."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(gh, node):
        oh = (node[:, None] == jnp.arange(nslots)).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            return oh.T @ gh

    total = np.zeros((nslots, 2), np.float64)
    for w, nd in zip(gh, node):
        total += np.asarray(sums(w, nd), np.float64)
    return total


def replay(values: np.ndarray, labels: np.ndarray, cuts: np.ndarray,
           forest_int: np.ndarray, forest_val: np.ndarray, which: list[int],
           nbin: int, max_depth: int, rate: float, reg_lambda: float,
           min_child_weight: float, operand_dtype: str,
           combine=lambda tag, a: a) -> dict:
    """The worst of each number over the trees ``which`` of the forest,
    each replayed on the margins of the trees before it."""
    shard = Shard(values, labels, cuts, nbin)
    out: dict = {}
    try:
        for k in sorted(set(which)):
            gh = shard.grad_hess(shard.margins(
                forest_int[:k], forest_val[:k], rate, max_depth))
            got = replay_tree(
                shard, gh, forest_int[k], forest_val[k], max_depth,
                reg_lambda, min_child_weight, operand_dtype,
                lambda tag, a, k=k: combine(f"t{k}-{tag}", a))
            for name, v in got.items():
                out[name] = max(out.get(name, 0), v) \
                    if name not in ("splits", "leaves") \
                    else out.get(name, 0) + v
    finally:
        shard.free()
    return out

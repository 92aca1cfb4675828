"""Distributed gradient-boosted trees over the histogram allreduce.

The reference library's historical role is the collective inside
XGBoost: workers hold row shards, build per-node gradient histograms,
and Allreduce<Sum> them so every worker picks the same split
(rabit-learn ships the collective; the booster lived in XGBoost).  This
module closes that loop with a compact binned GBDT so the histogram
path is exercised end-to-end as a real app: logistic or squared loss,
level-wise trees, split gain from second-order statistics.

TPU-native notes: features are quantile-binned on the device (once
under ``tree_method="hist"``; before every tree under ``"approx"``, on
cuts sketched from that round's hessians, the float values resident
beside the bins) and stay there with every other per-row quantity;
per-node histograms
come from the MXU one-hot contraction in :mod:`rabit_tpu.learn.histogram`
with node membership folded into the grad/hess operand inside the
kernel.  Below the root a level builds one child of every node split
the level above (static shapes: 2^(depth-1) build slots) and has the
sibling as parent minus built, on the reduced histograms: XGBoost's
histogram subtraction.  The only cross-rank traffic per level is one
allreduce of the built histograms, the XGBoost wire pattern; where the
engine reduces device arrays they stay on the device afterwards, which
ranks each node's features and hands the host a shortlist to decide
on in float64.  Fault
tolerance: one checkpoint per boosting round, the reference's
per-iteration commit structure.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

import rabit_tpu
from rabit_tpu import engine as _engine_mod
from rabit_tpu.learn import histogram
from rabit_tpu.learn.data import EllRows, SparseMat, ell_rows, fetch
from rabit_tpu.obs import program
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
    # sparsity-aware split; rows absent at the feature go this way)
    default_left: bool = True
    # the split as a float, as XGBoost's trees hold it: the cut at
    # bin_threshold of the cuts the tree was grown on, left if value <
    # split (bin <= threshold says the same of a value binned by those
    # cuts).  A tree committed before the field existed reads 0.0
    split: float = 0.0


@dataclass
class BoostedModel:
    """A forest of binned trees and the quantile cuts their bins are
    defined by.  Under ``tree_method="hist"`` one ``cuts`` for the
    forest, the quantiles of rank 0's :func:`cut_sample`, and a row is
    routed by its bin.  Under ``"approx"`` every tree was grown on cuts
    of its own (``tree_cuts[k]``, the sketch of every rank's rows under
    round k's hessians), ``cuts`` holds their shape and no cut, and a
    row is routed by its value against the node's float ``split``."""

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
    tree_method: str = "hist"
    # (f, nbin - 1) float32 a tree under "approx" (28.6 KB at 28 x 255);
    # a model committed before the field existed has none
    tree_cuts: list = field(default_factory=list)
    # output groups (XGBoost's ``num_class``): a round appends this many
    # trees, tree ``t * num_class + k`` is round t's for class k.  A
    # model committed before the field existed reads 1
    num_class: int = 1
    # a forest grown on sparse rows: its bins are a flat space, column
    # ``j``'s cuts ``cuts[cut_ptr[j]:cut_ptr[j + 1]]`` of a 1-D ``cuts``
    # (``histogram.FlatBins``), and a row is routed by its value against
    # the node's float ``split``, an absent entry the default way
    cut_ptr: np.ndarray | None = None

    def _tree_margin(self, tree: list[TreeNode], bins,
                     by_value: bool = False) -> np.ndarray:
        """One tree's leaf weight a row, routed by ``bins`` or, with
        ``by_value``, by the float values in their place (NaN: absent),
        or by the entries of sparse rows (``data.EllRows``)."""
        sparse = isinstance(bins, EllRows)
        held = bins.present() if sparse else None
        missing_code = None if by_value or sparse else self.cuts.shape[1] + 1
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
                    if sparse:
                        # the row's entry of the split's column, if any
                        hit = held[rows] & (bins.indices[rows] == n.feature)
                        b = bins.values[rows][np.arange(len(hit)),
                                              hit.argmax(axis=1)]
                        absent, below = ~hit.any(axis=1), b < n.split
                    else:
                        b = bins[rows, n.feature]
                        absent, below = (np.isnan(b), b < n.split) \
                            if by_value else (b == missing_code,
                                              b <= n.bin_threshold)
                    go_left = np.where(
                        absent, getattr(n, "default_left", True), below)
                    idx = np.flatnonzero(rows)
                    node[idx[go_left]] = n.left
                    node[idx[~go_left]] = n.right
        return out

    def margin(self, bins, by_value: bool = False) -> np.ndarray:
        """``(n,)`` margins, or ``(num_class, n)`` of a model of several
        output groups."""
        groups = self.num_class
        out = np.full((groups, bins.shape[0]), self.base_score, np.float32)
        for t, tree in enumerate(self.trees):
            out[t % groups] += self.learning_rate * self._tree_margin(
                tree, bins, by_value)
        return out if groups > 1 else out[0]

    def predict(self, values) -> np.ndarray:
        if self.cut_ptr is not None:
            # sparse rows by their entries; the same rows dense, NaN
            # where absent, by value: the same walk
            m = self.margin(np.asarray(values, np.float32), by_value=True) \
                if isinstance(values, np.ndarray) \
                else self.margin(ell_rows(values))
        elif self.tree_method == "approx":
            m = self.margin(np.asarray(values, np.float32), by_value=True)
        else:
            m = self.margin(apply_cuts(values, self.cuts))
        if self.loss == "logistic":
            return 1.0 / (1.0 + np.exp(-m))
        if self.loss == "softprob":
            e = np.exp(m - m.max(axis=0))
            return (e / e.sum(axis=0)).T                # (n, num_class)
        return m


# re-exported for callers binning prediction-time data
apply_cuts = histogram.apply_cuts


def _grad_hess(margin: np.ndarray, labels: np.ndarray, loss: str):
    if loss == "softprob":
        # XGBoost's SoftmaxMultiClassObj: every class from the same
        # margins, the hessian doubled and floored
        e = np.exp(margin - margin.max(axis=0))
        p = e / e.sum(axis=0)
        hit = labels[None, :] == np.arange(len(margin), dtype=np.float32)[
            :, None]
        return (p - hit).astype(np.float32), np.maximum(
            2.0 * p * (1.0 - p), SOFTPROB_MIN_HESS).astype(np.float32)
    if loss == "logistic":
        p = 1.0 / (1.0 + np.exp(-margin))
        return (p - labels).astype(np.float32), (p * (1 - p)).astype(
            np.float32)
    return (margin - labels).astype(np.float32), np.ones_like(margin)


TREE_METHODS = ("hist", "approx")
# XGBoost's floor under a softmax hessian (kRtEps)
SOFTPROB_MIN_HESS = 1e-16

# rows the quantile cuts of tree_method="hist" are taken from (XGBoost's
# sketch is approximate too): a shard up to this size gives every row
CUT_SAMPLE_ROWS = 1 << 20


def cut_sample(values: np.ndarray) -> np.ndarray:
    """The strided sample of a shard that defines its cuts under
    ``tree_method="hist"``: every ``n // CUT_SAMPLE_ROWS``-th row, at
    most ``CUT_SAMPLE_ROWS`` of them.  (``"approx"`` samples nothing:
    its sketch covers every row.)  Of sparse rows (``data.EllRows``)
    the same rows."""
    stride = slice(None, None, max(1, values.shape[0] // CUT_SAMPLE_ROWS))
    if isinstance(values, EllRows):
        return values.rows(stride).rows(slice(CUT_SAMPLE_ROWS))
    return values[stride][:CUT_SAMPLE_ROWS]


def _keep_rows(seed: int, round_idx: int, n: int, subsample: float):
    """This round's row sample, seeded by ``(seed, round, rank)``."""
    rng = np.random.default_rng((seed, round_idx, rabit_tpu.get_rank()))
    return rng.random(n) < subsample


def _route(tree: list[TreeNode], slots: list[int], leaves: list[int]):
    """How the rows of one level move on.  ``slots[s]`` is the tree node
    in slot ``s`` of the level (-1: none).  Returns the table whose row
    ``s`` is ``(feature, bin_threshold, default_left, leaf)`` and the
    next level's slots: the rows of a split node go to slot ``2s`` or
    ``2s + 1``; those of a node that stays a leaf take its code
    ``leaf`` < 0, which is ``-1 - (its place in leaves)``, for good."""
    tab = np.zeros((len(slots), 4), np.int32)
    nxt = [-1] * (2 * len(slots))
    for s, nid in enumerate(slots):
        if nid < 0:
            continue
        node = tree[nid]
        if node.feature < 0:
            leaves.append(nid)
            tab[s, 3] = -len(leaves)
        else:
            tab[s] = (node.feature, node.bin_threshold,
                      getattr(node, "default_left", True), 0)
            nxt[2 * s], nxt[2 * s + 1] = node.left, node.right
    return tab, nxt


def _leaf_values(tree, slots, leaves, max_depth: int) -> np.ndarray:
    """Leaf weight by row code once every level is routed: a row still
    in slot ``s`` reads entry ``s``, a row with leaf code ``c`` entry
    ``2^max_depth - c - 1``."""
    width = 1 << max_depth
    vals = np.zeros(2 * width, np.float32)
    for s, nid in enumerate(slots):
        if nid >= 0:
            check(tree[nid].feature < 0,
                  "boosting: a tree is deeper than max_depth=%d", max_depth)
            vals[s] = tree[nid].value
    for k, nid in enumerate(leaves):
        vals[width + k] = tree[nid].value
    return vals


def _route_round(trees, slots: list[int], leaves: list[list[int]]):
    """:func:`_route` of every tree of a round.  ``slots`` holds the
    trees' level slots one tree after another (tree-major: slot ``s`` of
    tree ``k`` at a level ``w`` wide is entry ``k * w + s``, so that the
    children of entry ``e`` are entries ``2e`` and ``2e + 1`` as within
    one tree) and ``leaves[k]`` tree k's leaves.  Returns the tables
    ``(trees, w, 4)`` and the next level's slots."""
    width = len(slots) // len(trees)
    routed = [_route(tree, slots[k * width:(k + 1) * width], leaves[k])
              for k, tree in enumerate(trees)]
    return (np.stack([tab for tab, _nxt in routed]),
            [nid for _tab, nxt in routed for nid in nxt])


def _round_leaf_values(trees, slots, leaves, max_depth: int) -> np.ndarray:
    """:func:`_leaf_values` of every tree of a round, ``(trees, 2 *
    2^max_depth)``; ``slots`` and ``leaves`` as :func:`_route_round`
    left them."""
    width = len(slots) // len(trees)
    return np.stack([
        _leaf_values(tree, slots[k * width:(k + 1) * width], leaves[k],
                     max_depth) for k, tree in enumerate(trees)])


def _fill_splits(tree: list[TreeNode], cuts) -> None:
    """Every split's float value from the cuts the tree was grown on
    (``(f, nbin - 1)``, or a flat bin space's ``histogram.FlatBins``)."""
    for node in tree:
        if node.feature >= 0:
            node.split = float(cuts[node.feature, node.bin_threshold])


def _replay(shard, model, max_depth: int) -> None:
    """Add committed trees to the shard's margins (a resume), a round's
    trees together as they were grown.  A tree grown under ``"approx"``
    routes the rows binned by its own cuts again, which is the walk by
    ``value < split``: a bin is at or under the threshold exactly where
    the value is under that cut."""
    groups = model.num_class
    for t in range(0, len(model.trees), groups):
        trees = model.trees[t:t + groups]
        if model.tree_method == "approx":
            shard.rebin(model.tree_cuts[t])
        slots, leaves = [0] * groups, [[] for _ in trees]
        for depth in range(max_depth):
            if all(nid < 0 for nid in slots):
                break
            tabs, slots = _route_round(trees, slots, leaves)
            shard.partition(tabs, depth)
        shard.leaf(_round_leaf_values(trees, slots, leaves, max_depth))


class _HostShard:
    """One rank's rows as numpy arrays: the arm of the host engines and
    of a process without an accelerator."""

    def __init__(self, values, labels, model, max_depth, nbin, subsample,
                 seed, use_pallas, compute_dtype):
        self.n = values.shape[0]
        self.labels, self.model = labels, model
        self.max_depth, self.subsample, self.seed = max_depth, subsample, seed
        self.kw = {"use_pallas": use_pallas, "compute_dtype": compute_dtype}
        self.nbin = nbin
        self.approx = model.tree_method == "approx"
        if self.approx:
            # binned anew every round: the values stay
            self.values = np.asarray(values, np.float32)
            self.bins = np.zeros(values.shape, np.int32)
            absent = int(np.count_nonzero(np.isnan(self.values)))
            self.max_bin = nbin * (absent > 0)              # the code
        else:
            with program.span("stage.bin"):
                self.bins = apply_cuts(values, model.cuts)
            absent = int(np.count_nonzero(self.bins == nbin))   # the code
            self.max_bin = int(self.bins.max(initial=0))
        program.count("gbdt.entries", self.bins.size)
        program.count("gbdt.entries_missing", absent)
        self.any_nan = absent > 0

    def start(self, has_missing: bool) -> None:
        self.has_missing = has_missing
        margin = self.model.margin(self.values, by_value=True) \
            if self.approx else self.model.margin(self.bins)
        # a row a tree of the round: margins, gradients and node ids
        self.trees = self.model.num_class
        self.margin = margin.reshape(self.trees, self.n)
        self.node = np.zeros((self.trees, self.n), np.int32)

    def sketch(self):
        """This rank's slot of the merge's payload
        (``histogram.sketch_program``) under the round's hessians."""
        fn = histogram.sketch_program(
            self.n, self.values.shape[1], self.nbin,
            rabit_tpu.get_world_size(), rabit_tpu.get_rank())
        return fn(np.ascontiguousarray(self.values.T),
                  np.stack([self.grad[0], self.hess[0]]))

    def cuts_of(self, merged):
        """The cuts of the merged summaries, as every rank computes
        them."""
        return histogram.cuts_program(
            merged.shape[0], merged.shape[1], self.nbin)(merged)

    def rebin(self, cuts) -> None:
        self.bins = apply_cuts(self.values, np.asarray(cuts))

    def grad_hess(self, round_idx: int) -> None:
        self.grad, self.hess = _grad_hess(self.margin, self.labels,
                                          self.model.loss)
        if self.subsample < 1.0:
            # zeroed grad/hess = row contributes nothing anywhere this
            # round while every shape stays static for the fused kernels
            keep = _keep_rows(self.seed, round_idx, self.n, self.subsample)
            self.grad = np.where(keep, self.grad, 0.0).astype(np.float32)
            self.hess = np.where(keep, self.hess, 0.0).astype(np.float32)

    def level(self, build, depth: int):
        """The histograms of the level slots ``build`` names (-1: none;
        tree-major, the level ``2^depth`` slots a tree), which, and the
        kernel calls that took (``histogram.level_calls``' pair, and the
        features that shared a packed product: none, this arm has no
        plan): a tree's slots by its own gradients and node ids, one
        builder call a tree that builds any."""
        width = 1 << depth
        order = [s for s in build if s >= 0]
        built, calls = [], (0, 0)
        for k in range(self.trees):
            mine = [s - k * width for s in order if s // width == k]
            if not mine:
                continue
            made, lane = histogram.level_calls(
                len(mine), self.bins.shape[1], self.nbin,
                self.kw["use_pallas"])
            calls = calls[0] + made, calls[1] + lane
            built.append(histogram.build_level_local(
                self.bins, self.grad[k], self.hess[k], self.node[k], mine,
                self.nbin, totals=self.has_missing, **self.kw))
        if len(built) > 1:
            import jax.numpy as jnp

            built = [jnp.concatenate(built)]
        return built[0], order, calls + (0,)

    def partition(self, tabs: np.ndarray, depth: int) -> None:
        for k, tab in enumerate(tabs):
            node = self.node[k]
            live = node >= 0
            feat, thr, dleft, leaf = tab[np.where(live, node, 0)].T
            b = self.bins[np.arange(self.n), feat]
            left = np.where(b == self.model.cuts.shape[1] + 1, dleft != 0,
                            b <= thr)       # absent: the default direction
            self.node[k] = np.where(live, np.where(leaf < 0, leaf,
                                                   2 * node + 1 - left),
                                    node)

    def partition_rows(self, depth: int) -> tuple[int, int]:
        """A bin a row and tree, in feature rows, and the trees' bins
        whole."""
        return self.trees, self.trees * self.bins.shape[1]

    def leaf(self, vals: np.ndarray) -> None:
        width = 1 << self.max_depth
        for k, node in enumerate(self.node):
            self.margin[k] += self.model.learning_rate * vals[k][
                np.where(node >= 0, node, width - node - 1)]
        self.node = np.zeros_like(self.node)


_PROGRAMS: dict = {}


def _lookup(table, idx, width: int):
    """``table[idx]`` for a 1-D table of a few dozen entries, as a chain
    of selects (one fused pass; a gather of n scalars is the slow way
    on the chip)."""
    import jax.numpy as jnp

    if width > 256:
        return jnp.take(table, idx, axis=0)
    out = jnp.zeros(idx.shape, table.dtype)
    for k in range(width):
        out = jnp.where(idx == k, table[k], out)
    return out


# feature rows a one-row slice of the staged bins costs: the array is
# laid out in tiles of 8 rows by 128 columns and a row comes with its
# tile.  Timed on a v5e (tools/partition_check.py; the table is in
# PERF.md section 5): a slice takes what 8.4 to 9.1 rows' bytes take at
# the HBM's rate, at all three boosting shapes; at 8 * K * W = fpad a
# round of one tree is 12% faster sliced and one of seven 11% faster
# whole (it joins the seven trees' ids), a millisecond of a round of a
# second either way
_SLICE_ROWS = 8
# level nodes a chain of the sliced move takes: the compiler fuses a
# chain of 16 nodes' selects with their slices into one pass, and cuts a
# chain of 32 in two with the nodes' scalars written out as arrays of n
_MOVE_CHAIN_NODES = 16


def _move_slices(trees: int, width: int, fpad: int) -> bool:
    """Whether the row move of a level ``width`` nodes wide of ``trees``
    trees reads the feature rows its splits name (:func:`_move_sliced`)
    or passes over the staged ``(fpad, n)`` bins whole
    (:func:`_move_whole`): from the shapes alone, whatever the learner.
    It slices where its ``trees * width`` slices read less than the
    array."""
    return _SLICE_ROWS * trees * width < fpad


def _node_numbers(tab):
    """A level's tables ``([K,] W, 4)`` (:func:`_route`'s rows) as what
    a row of node ``j`` is compared with and moved to, each
    ``([K,] W)``: the split's feature row, its threshold, whether an
    absent bin goes left, and where a row goes that goes left and that
    does not: children ``2j`` and ``2j + 1``, or of a node that stays a
    leaf its code both ways."""
    import jax.numpy as jnp

    feat, thr, dleft, leaf = (tab[..., c] for c in range(4))
    first = 2 * jnp.arange(tab.shape[-2], dtype=jnp.int32)
    return (feat, thr, dleft != 0, jnp.where(leaf < 0, leaf, first),
            jnp.where(leaf < 0, leaf, first + 1))


def _moved(b, node, j: int, thr, dleft, lo, hi, missing_code: int, out):
    """``out`` with the rows of level node ``j`` moved on by their bin
    ``b`` of its split feature (:func:`_node_numbers`, of that node)."""
    import jax.numpy as jnp

    left = jnp.where(b == missing_code, dleft, b <= thr)
    return jnp.where(node == j, jnp.where(left, lo, hi), out)


def _move_sliced(bins_t, node, tab, missing_code: int):
    """A level's row move that reads the feature rows its splits name:
    ``(K, n)`` node ids under ``(K, W, 4)`` tables (a round of one tree:
    ``(n,)`` under ``(W, 4)``), of tree ``k`` and level node ``j`` the
    one row of ``bins_t`` its split names, a dynamic slice of n bins,
    compared under the one mask ``node[k] == j`` with the node's own
    threshold, default direction and leaf code.  A table entry of no
    node names row 0 and matches no row; a dead row (``node < 0``)
    keeps its code, as would an id the level has no slot for."""
    from jax import lax
    import jax.numpy as jnp

    if node.ndim == 1:
        return _move_sliced(bins_t, node[None], tab[None],
                            missing_code)[0]
    feat, *numbers = _node_numbers(tab)
    width, outs = tab.shape[1], []
    for k in range(node.shape[0]):
        # a tree's ids and a feature's bins as arrays of one row: the
        # compiler takes a chain over those in one pass
        ids, out = node[k:k + 1], None
        for first in range(0, width, _MOVE_CHAIN_NODES):
            # a chain moves its own nodes' rows and keeps the others'
            part = ids
            for j in range(first, min(first + _MOVE_CHAIN_NODES, width)):
                row = lax.dynamic_slice_in_dim(bins_t, feat[k, j], 1)
                part = _moved(row, ids, j, *(v[k, j] for v in numbers),
                              missing_code, part)
            out = part if out is None else jnp.where(ids < first, out, part)
        outs.append(out)
    return jnp.concatenate(outs)


def _move_whole(bins_t, node, tab, missing_code: int):
    """The same move (:func:`_move_sliced`) by one pass over the whole
    staged array for all K trees: each row's split feature from a chain
    of W selects, its bin of that feature picked out of the ``fpad``
    rows as they stream, then the W nodes' numbers as above, every step
    on the trees' ids at once."""
    import jax.numpy as jnp

    feat_of, *numbers = _node_numbers(tab)
    if node.ndim > 1:
        # node j's numbers as a column, an entry a tree
        feat_of, *numbers = (v.T[:, :, None] for v in (feat_of, *numbers))
    width = tab.shape[-2]
    feat = jnp.zeros_like(node)
    for j in range(width):
        feat = jnp.where(node == j, feat_of[j], feat)
    rows_of = jnp.arange(bins_t.shape[0], dtype=jnp.int32)[:, None]
    b = jnp.sum(jnp.where(feat[..., None, :] == rows_of, bins_t, 0), axis=-2)
    out = node
    for j in range(width):
        out = _moved(b, node, j, *(v[j] for v in numbers), missing_code, out)
    return out


def _move(bins_t, node, tab, missing_code: int):
    """A level's row move in the form its shapes give it
    (:func:`_move_slices`): the node ids of the next level."""
    trees = node.shape[0] if node.ndim > 1 else 1
    slices = _move_slices(trees, tab.shape[-2], bins_t.shape[0])
    return (_move_sliced if slices else _move_whole)(bins_t, node, tab,
                                                     missing_code)


def partition_program(n: int, fpad: int, trees: int, width: int,
                      missing_code: int):
    """Compiled ``gbdt_partition`` of one depth: from the staged
    ``(fpad, n)`` bins, the round's node ids (``(trees, n)`` int32,
    donated; ``(n,)`` of a round of one tree) and the level's tables
    (``(trees, width, 4)``, a tree's ``(width, 4)``: :func:`_route`'s, as
    wide as the level and no wider) to the next level's ids, every tree
    of the round in the one program, in the form :func:`_move_slices`
    gives these shapes."""
    import jax
    import jax.numpy as jnp

    key = ("partition", n, fpad, trees, width, missing_code,
           jax.default_backend())
    fn = _PROGRAMS.get(key)
    if fn is None:
        def gbdt_partition(bins_t, node, tab):
            with jax.named_scope("gbdt/partition"):
                return _move(bins_t, node, tab, missing_code)

        sds = jax.ShapeDtypeStruct
        lead = (trees,) if trees > 1 else ()
        fn = _PROGRAMS[key] = jax.jit(
            gbdt_partition, donate_argnums=(1,)).lower(
            sds((fpad, n), jnp.int32), sds(lead + (n,), jnp.int32),
            sds(lead + (width, 4), jnp.int32)).compile()
    return fn


def softprob_grad_program(n: int, num_class: int, sampled: bool = False):
    """Compiled ``gbdt_grad_softmax``: from the ``(K, n)`` margins the
    round before left and the ``(n,)`` float32 class ids (and, with
    ``sampled``, the round's ``(n,)`` bool row sample) to the round's
    ``(K, 2, n)`` float32 (grad, hess), every class at once: XGBoost's
    ``SoftmaxMultiClassObj``.  What it needs is (3K + 1) * 4 bytes a
    row."""
    import jax
    import jax.numpy as jnp

    key = ("grad_softmax", n, num_class, sampled, jax.default_backend())
    fn = _PROGRAMS.get(key)
    if fn is None:
        def gbdt_grad_softmax(margin, labels, *keep):
            with jax.named_scope("gbdt/grad"):
                e = jnp.exp(margin - jnp.max(margin, axis=0))
                p = e / jnp.sum(e, axis=0)
                hit = labels[None, :] == jnp.arange(
                    num_class, dtype=jnp.float32)[:, None]
                gh = jnp.stack([p - hit, jnp.maximum(
                    2.0 * p * (1.0 - p), SOFTPROB_MIN_HESS)], axis=1)
                return jnp.where(keep[0], gh, 0.0) if keep else gh

        sds = jax.ShapeDtypeStruct
        keep = (sds((n,), jnp.bool_),) if sampled else ()
        fn = _PROGRAMS[key] = jax.jit(gbdt_grad_softmax).lower(
            sds((num_class, n), jnp.float32), sds((n,), jnp.float32),
            *keep).compile()
    return fn


def _slots_of(node, takes, nslots: int):
    """The build slot of every row of a tree's level, traceable: build
    slot p takes the rows of level slot ``takes[p]``, a child of the
    node in slot p of the level above (the root: slot 0 of both); a row
    of the sibling, of an unsplit node or of a leaf is in no slot."""
    import jax.numpy as jnp

    above = node >> 1
    return jnp.where((node >= 0) & (node == _lookup(takes, above, nslots)),
                     above, -1)


def _level_slots(node, takes, nslots: int):
    """:func:`_slots_of` of a round's trees: ``(n,)`` node ids under
    ``(nslots,)`` takes, or ``(K, n)`` under ``(K, nslots)``."""
    import jax.numpy as jnp

    if node.ndim == 1:
        return _slots_of(node, takes, nslots)
    return jnp.stack([_slots_of(node[k], takes[k], nslots)
                      for k in range(node.shape[0])])


def _move_entries(cells_t, node, tab):
    """A level's row move of sparse rows: the split's column looked for
    among the row's entries.  ``cells_t`` is the ``(width, n)`` int32
    cells of the rows' entries (-1: none), ``node`` the ``(n,)`` node
    ids (``(K, n)`` of a round of K trees) and ``tab`` the level's
    ``([K,] W, 5)`` table, of node ``j`` the cells of its split's column
    ``[lo, hi)``, the last cell that goes left, whether a row without an
    entry there goes left, and the node's leaf code (< 0) where it
    stays a leaf.  A row has at most one entry a column: it goes left if
    that one lies at or under the cut, the default way if it has none.
    Dead rows and ids the level has no slot for keep their code, as in
    :func:`_move_sliced`."""
    import jax.numpy as jnp

    if node.ndim > 1:
        return jnp.stack([_move_entries(cells_t, node[k], tab[k])
                          for k in range(node.shape[0])])
    width = tab.shape[0]
    lo, cut, hi, dleft, leaf = (_lookup(tab[:, c], node, width)
                                for c in range(5))
    here = (cells_t >= lo) & (cells_t < hi)
    left = jnp.where(jnp.any(here, axis=0),
                     jnp.any(here & (cells_t <= cut), axis=0), dleft != 0)
    return jnp.where((node >= 0) & (node < width),
                     jnp.where(leaf < 0, leaf, 2 * node + 1 - left), node)


def _forest(fn, trees: int):
    """The program of a round's ``trees`` trees from ``fn``, the program
    of one, traceable: the leaf update (a level's histograms are
    ``histogram.level_hist``'s and the row move
    :func:`partition_program`'s, which both take the trees together).
    Every argument has a leading tree axis, of which call ``k`` of
    ``fn`` takes entry ``k``.  The calls' results are stacked (a leading
    tree axis again) and all of them are one program: one hand-over and
    one wait, whatever ``trees``.  A round of one tree is ``fn`` itself,
    its arrays without the axis."""
    if trees == 1:
        return fn
    import jax
    import jax.numpy as jnp

    def forest(*args):
        outs = [fn(*(a[k] for a in args)) for k in range(trees)]
        return jax.tree.map(lambda *parts: jnp.stack(parts), *outs)

    forest.__name__ = fn.__name__
    return forest


def _build(fn, *shapes, donate=()):
    """``fn`` compiled for ``shapes``."""
    import jax

    return jax.jit(fn, donate_argnums=donate).lower(*shapes).compile()


class _DeviceShard:
    """One rank's rows on the device for the whole job: staged bins,
    labels, margin, (grad, hess) and the node of every row (of a model
    of several output groups each with a leading axis of that length:
    ``(K, n)`` margins and node ids, ``(K, 2, n)`` gradients).  A round is
    a fixed set of programs, compiled before the first; the host sends
    back tables of a level's width.  With ``scan`` None it sees
    ``level``'s histograms whole.  With ``scan`` the job's
    ``(reg_lambda, min_child_weight)``, where the engine hands a
    reduced device array back (``train``), they stay here: ``scan()``
    assembles the level from the reduced built slots and the level
    above, ranks every slot's features in float32 and hands over
    ``histogram.SHORTLIST`` histogram rows a slot."""

    def __init__(self, values, labels, model, max_depth, nbin, subsample,
                 seed, use_pallas, compute_dtype, scan=None):
        self.scan_by, self.above = scan, None
        self.model, self.max_depth, self.nbin = model, max_depth, nbin
        self.trees = model.num_class            # trees a round
        # the leading axis of what is kept a tree of the round
        self.lead = (self.trees,) if self.trees > 1 else ()
        self.subsample, self.seed = subsample, seed
        self.use_pallas, self.compute_dtype = use_pallas, compute_dtype
        self.approx = model.tree_method == "approx"
        # the features of a few codes that share a product of the
        # lane-wide body: from the job's cuts, so none under "approx",
        # whose cuts are every round's own, and none for a job none of
        # whose levels takes the plan
        self.pack = None
        self._stage(values, labels)

    def _stage(self, values, labels) -> None:
        """The shard on the device, in this class's layout."""
        import jax

        self.n, self.f = values.shape
        if self.approx:
            self.values_t, self.bins_t, seen = histogram.stage_values(
                values, self.nbin)
        else:
            self.bins_t, seen = histogram.stage_bins(values, self.model.cuts,
                                                     self.nbin)
            from rabit_tpu.ops import histogram_kernel as hk

            pack = hk.pack_plan(self.model.cuts)
            if pack and any(map(self._level_packs, self._level_widths())):
                self.pack = pack
        with program.span("stage.put"):
            self.labels = jax.device_put(np.asarray(labels, np.float32))
        self.any_nan, self.max_bin = (int(v) for v in np.asarray(seen))

    def start(self, has_missing: bool) -> None:
        import jax.numpy as jnp

        self.has_missing = has_missing
        with program.span("stage.compile"):
            self.prog = self._programs()
            if self.approx:
                world = rabit_tpu.get_world_size()
                self.prog = dict(
                    self.prog,
                    sketch=histogram.sketch_program(
                        self.n, self.f, self.nbin, world,
                        rabit_tpu.get_rank()),
                    cuts=histogram.cuts_program(world, self.f, self.nbin),
                    rebin=histogram.rebin_program(
                        self.n, self.f, self.bins_t.shape[0],
                        self.model.cuts.shape[1]))
        self.codes = (jnp.asarray(self.pack[1]),) if self.pack else ()
        self.margin = jnp.full(self.lead + (self.n,), self.model.base_score,
                               jnp.float32)
        self.node = jnp.zeros(self.lead + (self.n,), jnp.int32)
        _replay(self, self.model, self.max_depth)

    def _level_widths(self) -> list[int]:
        """Build slots a tree of the job's level programs: the root's
        and one a node of the level above."""
        return [1] + [1 << d for d in range(1, self.max_depth - 1)]

    def _level_packs(self, per_tree: int) -> int:
        return histogram.level_packs(per_tree, self.f, self.nbin,
                                     self.use_pallas, self.trees)

    @property
    def sampled(self) -> bool:
        """Whether the gradient program takes a mask of the rows kept."""
        return self.subsample < 1.0

    def _layout_key(self) -> tuple:
        """What of the rows' layout the job's programs are compiled
        for."""
        from rabit_tpu.ops import histogram_kernel as hk

        return (self.f, self.bins_t.shape[0], self.model.cuts.shape[1] + 1,
                hk.hist_fused_multi, self.pack and self.pack[0])

    def _programs(self) -> dict:
        """The job's programs, compiled for its shapes: ``grad``,
        ``level`` (by its number of build slots: 1 at the root, then one
        a node of the level above; an empty slot holds no row, so a tree
        that stops early runs the same programs; its kernel calls are
        those ``histogram.level_calls`` counts), ``partition`` (by
        depth: :func:`partition_program`, its tables the level's own
        ``2^depth`` entries) and ``leaf``; and, where the level's
        histograms stay on the device, ``scan`` by the level's number of
        slots.  Of a round of several
        trees each is the round's: a level's program builds every
        tree's slots of that depth (``histogram.level_hist``: in one
        kernel call where the level is wide enough for the lane-wide
        body, a call a tree and more where it is not) and hands them
        back tree-major, which is the numbering ``scan`` keeps (a slot's
        children are slots ``2s`` and ``2s + 1`` across trees as within
        one; with the job's ``pack`` plan, whose codes are the program's
        last operand, the features of a few codes in one product of each
        lane-wide call), so that it, ``histogram.assemble_level`` and
        ``level_shortlist`` take a forest's level as a tree's of that
        many slots; a depth's row move takes the trees' ids and tables
        together and reads of the staged bins what that depth needs,
        once for all of them (:func:`_move_slices`); the leaf update is
        a tree's program lifted over the trees (:func:`_forest`).  What
        depends on the rows' layout is the shard class's own:
        ``_level_program``, ``_partition_program``, ``_scan_programs``."""
        import jax
        import jax.numpy as jnp

        n, depth = self.n, self.max_depth
        loss, rate = self.model.loss, self.model.learning_rate
        trees, lead, sampled = self.trees, self.lead, self.sampled
        key = (n, self.nbin, self.has_missing, depth, loss, rate, sampled,
               self.use_pallas, self.compute_dtype, jax.default_backend(),
               self.scan_by, trees) + self._layout_key()
        if key in _PROGRAMS:
            return _PROGRAMS[key]
        width = 1 << depth

        def gbdt_grad(margin, labels, *keep):
            with jax.named_scope("gbdt/grad"):
                if loss == "logistic":
                    p = 1.0 / (1.0 + jnp.exp(-margin))
                    g, h = p - labels, p * (1 - p)
                else:
                    g, h = margin - labels, jnp.ones_like(margin)
                gh = jnp.stack([g, h])
                return jnp.where(keep[0], gh, 0.0) if keep else gh

        def gbdt_leaf(margin, node, vals):
            with jax.named_scope("gbdt/leaf"):
                code = jnp.where(node >= 0, node, width - node - 1)
                return (margin + rate * _lookup(vals, code, 2 * width),
                        jnp.zeros_like(node))

        sds = jax.ShapeDtypeStruct
        rows_f = sds(lead + (n,), jnp.float32)
        rows_i = sds(lead + (n,), jnp.int32)
        keep = (sds((n,), jnp.bool_),) if sampled else ()
        prog = {
            "grad": softprob_grad_program(n, trees, sampled)
            if loss == "softprob" else _build(
                gbdt_grad, rows_f, sds((n,), jnp.float32), *keep),
            "level": {p: self._level_program(p)
                      for p in self._level_widths()},
            "partition": {d: self._partition_program(d)
                          for d in range(depth)},
            "leaf": _build(_forest(gbdt_leaf, trees), rows_f, rows_i,
                           sds(lead + (2 * width,), jnp.float32),
                           donate=(0, 1)),
        }
        if self.scan_by is not None:
            prog["scan"] = self._scan_programs()
        _PROGRAMS[key] = prog
        return prog

    def _level_program(self, nslots: int):
        """The level program of ``nslots`` build slots a tree."""
        import jax
        import jax.numpy as jnp

        f, nbin = self.f, self.nbin
        use_pallas, cdt, totals = (self.use_pallas, self.compute_dtype,
                                   self.has_missing)
        # the plan's indices and width are shapes; its codes an operand
        plan = self.pack and self.pack[0]

        def gbdt_level(bins_t, gh, node, takes, *codes):
            # a round's trees at once: their kernel calls are
            # level_hist's to share out
            with jax.named_scope("gbdt/level"):
                return histogram.level_hist(
                    bins_t, gh, _level_slots(node, takes, nslots), nslots,
                    f, nbin,
                    use_pallas=use_pallas, compute_dtype=cdt,
                    totals=totals,
                    pack=(plan, codes[0]) if codes else None)

        sds = jax.ShapeDtypeStruct
        codes = (sds(self.pack[1].shape, jnp.int32),) if plan else ()
        return _build(gbdt_level, sds(self.bins_t.shape, jnp.int32),
                      sds(self.lead + (2, self.n), jnp.float32),
                      sds(self.lead + (self.n,), jnp.int32),
                      sds(self.lead + (nslots,), jnp.int32), *codes)

    def _partition_program(self, depth: int):
        return partition_program(self.n, self.bins_t.shape[0], self.trees,
                                 1 << depth, self.model.cuts.shape[1] + 1)

    def _scan_spec(self):
        """``gbdt_scan`` of this layout: how a level is ranked, and the
        shapes a built slot and a slot of the level above have."""
        f, totals, scan_by = self.f, self.has_missing, self.scan_by
        rows = (f + totals, self.nbin)
        return (lambda level: histogram.level_shortlist(
            level, f, *scan_by, totals)), rows + (2,), rows

    def _scan_programs(self) -> dict:
        """``gbdt_scan`` by the level's slots a tree: the root's level
        is what was built; below, a level of 2p slots (a tree) comes
        from its p built slots and the p slots above."""
        import jax
        import jax.numpy as jnp

        rank, slot, slot_above = self._scan_spec()
        # a flat slot is assembled as a rectangle of one row
        flat = len(slot) == len(slot_above)

        def gbdt_scan(built, above=None, build=None):
            with jax.named_scope("gbdt/scan"):
                level = histogram.assemble_level(
                    built[:, None] if flat else built, above, build)
                return (level,) + rank(level)

        def hists(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32)

        trees = self.trees
        scan = {1: _build(gbdt_scan, hists(trees, *slot))}
        for p in (trees << d for d in range(self.max_depth - 1)):
            scan[2 * p // trees] = _build(
                gbdt_scan, hists(p, *slot), hists(2, p, *slot_above),
                jax.ShapeDtypeStruct((p,), jnp.int32))
        return scan

    def _keep(self, round_idx: int) -> np.ndarray:
        return _keep_rows(self.seed, round_idx, self.n, self.subsample)

    def grad_hess(self, round_idx: int) -> None:
        keep = ()
        if self.sampled:
            # the one host array of length n a round: the sample is
            # numpy's, so that both arms draw the same rows
            import jax

            keep = (jax.device_put(self._keep(round_idx)),)
        self.gh = self.prog["grad"](self.margin, self.labels, *keep)
        program.enqueued(self.gh)

    def sketch(self):
        payload = self.prog["sketch"](self.values_t, self.gh)
        program.enqueued(payload)
        return payload

    def cuts_of(self, merged):
        import jax.numpy as jnp

        if not isinstance(merged, np.ndarray) \
                and not merged.is_fully_addressable:
            # the device plane's result, replicated over the processes
            merged = merged.addressable_shards[0].data
        cuts = self.prog["cuts"](jnp.asarray(merged))
        program.enqueued(cuts)
        return cuts

    def rebin(self, cuts) -> None:
        import jax.numpy as jnp

        self.bins_t = self.prog["rebin"](self.bins_t, self.values_t,
                                         jnp.asarray(cuts, jnp.float32))
        program.enqueued(self.bins_t)

    def level(self, build, depth: int):
        """One program over the slots ``build`` names (tree-major, -1:
        none; as many a tree, built or not): the level's histograms on
        the device, ``build``, and the kernel calls the program holds
        (``histogram.level_calls``' pair, and the features that shared a
        packed product in them: the plan's a call that takes it)."""
        per_tree = len(build) // self.trees
        calls = histogram.level_calls(per_tree, self.f, self.nbin,
                                      self.use_pallas, self.trees)
        if self.pack:
            calls += (len(self.pack[0].narrow) * self._level_packs(per_tree),)
        else:
            calls += (0,)
        local = self.prog["level"][per_tree](
            self.bins_t, self.gh, self.node, self._takes(build, depth),
            *self.codes)
        program.enqueued(local)
        return local, build, calls

    def _takes(self, build, depth: int) -> np.ndarray:
        """``build`` as the level programs take it: each tree's kernel
        calls match its rows' node ids, which are slots of its own level
        of ``2^depth``."""
        takes = np.asarray(build, np.int32)
        return np.where(takes >= 0, takes & ((1 << depth) - 1), -1).astype(
            np.int32).reshape(self.lead + (len(build) // self.trees,))

    def scan(self, reduced, build, depth: int):
        """The shortlist of every slot of the level at ``depth`` as
        device arrays ``(features, rows)`` (``histogram.level_shortlist``),
        from ``level(build)``'s histograms once reduced.  The assembled
        level stays here, the parent of the next."""
        if not reduced.is_fully_addressable:
            # the device plane's result, replicated over the processes
            reduced = reduced.addressable_shards[0].data
        below = (self.above, np.asarray(build, np.int32)) if depth else ()
        self.above, feats, rows = self.prog["scan"][1 << depth](
            reduced, *below)
        program.enqueued(rows)
        return feats, rows

    def partition(self, tabs: np.ndarray, depth: int) -> None:
        self.node = self.prog["partition"][depth](
            self.bins_t, self.node, tabs.reshape(self.lead + tabs.shape[1:]))
        program.enqueued(self.node)

    def partition_rows(self, depth: int) -> tuple[int, int]:
        """Feature rows of the staged bins the move at ``depth`` reads,
        and what a pass over them whole a tree would."""
        fpad = self.bins_t.shape[0]
        sliced = _move_slices(self.trees, 1 << depth, fpad)
        return (self.trees << depth if sliced else fpad), self.trees * fpad

    def leaf(self, vals: np.ndarray) -> None:
        self.margin, self.node = self.prog["leaf"](
            self.margin, self.node, vals.reshape(self.lead + vals.shape[1:]))
        program.enqueued(self.node)


class _SparseShard(_DeviceShard):
    """One rank's sparse rows (``data.EllRows``) as **entries**, a
    present value its row and its cell of the job's flat bin space
    (``histogram.FlatBins``), and never an array of rows by columns: the
    ``(width, n)`` cells the row move searches for a split's column (an
    absent entry goes the split's default way) and, on the kernel's
    road, the same entries bucketed for ``ops.sparse_hist_kernel``
    (``histogram.stage_entries``).  A level's histograms are ``(slots,
    flat.size, 2)``, sized by the cuts the columns have; every other
    per-row array, program and the loop's protocol are
    :class:`_DeviceShard`'s, the rows padded to whole tiles with rows
    that hold no entry and whose gradients are zeroed.  Off the chip the
    same class runs the same programs with the level as XLA's
    ``segment_sum`` over the entries: the host arm of a sparse job."""

    def _stage(self, rows, labels) -> None:
        import jax

        self.rows, self.f = rows.shape
        if self.use_pallas is None:
            self.use_pallas = on_tpu()
        self.flat = histogram.FlatBins(self.model.cut_ptr, self.model.cuts,
                                       self.nbin)
        self.entries = histogram.stage_entries(rows, self.flat,
                                               self.use_pallas)
        self.width, self.n = self.entries[0].shape
        with program.span("stage.put"):
            self.labels = jax.device_put(np.pad(
                np.asarray(labels, np.float32), (0, self.n - self.rows)))
        # an absent entry is the rule: the levels always carry totals
        self.any_nan, self.max_bin = True, 0

    @property
    def sampled(self) -> bool:
        return self.subsample < 1.0 or self.n != self.rows

    def _keep(self, round_idx: int) -> np.ndarray:
        keep = np.zeros(self.n, bool)           # a row of padding: never
        keep[:self.rows] = True if self.subsample >= 1.0 else _keep_rows(
            self.seed, round_idx, self.rows, self.subsample)
        return keep

    def _layout_key(self) -> tuple:
        from rabit_tpu.ops import sparse_hist_kernel as sk

        return ("sparse", self.width, self.flat.cut_ptr.tobytes(),
                sk.hist_sparse)

    def _entry_shapes(self) -> tuple:
        import jax

        return tuple(None if a is None else jax.ShapeDtypeStruct(
            a.shape, a.dtype) for a in self.entries)

    def _level_program(self, nslots: int):
        import jax
        import jax.numpy as jnp

        flat, use_pallas, cdt = self.flat, self.use_pallas, self.compute_dtype

        def gbdt_level(cells_t, packed, fb, gh, node, takes):
            with jax.named_scope("gbdt/level"):
                return histogram.level_hist_flat(
                    (cells_t, packed, fb), gh,
                    _level_slots(node, takes, nslots), nslots, flat,
                    use_pallas=use_pallas, compute_dtype=cdt)

        sds = jax.ShapeDtypeStruct
        return _build(gbdt_level, *self._entry_shapes(),
                      sds(self.lead + (2, self.n), jnp.float32),
                      sds(self.lead + (self.n,), jnp.int32),
                      sds(self.lead + (nslots,), jnp.int32))

    def _partition_program(self, depth: int):
        import jax
        import jax.numpy as jnp

        def gbdt_partition(cells_t, node, tab):
            with jax.named_scope("gbdt/partition"):
                return _move_entries(cells_t, node, tab)

        sds = jax.ShapeDtypeStruct
        return _build(gbdt_partition, self._entry_shapes()[0],
                      sds(self.lead + (self.n,), jnp.int32),
                      sds(self.lead + (1 << depth, 5), jnp.int32),
                      donate=(1,))

    def _scan_spec(self):
        """``gbdt_scan`` on the flat bin space: the level assembled as
        the rectangle's is, of one row a slot; every column ranked and
        the shortlist fetched as windows
        (``histogram.level_shortlist_flat``)."""
        flat, scan_by = self.flat, self.scan_by
        return (lambda level: histogram.level_shortlist_flat(
            level, flat, *scan_by)), (flat.size, 2), (1, flat.size)

    def level(self, build, depth: int):
        """As :meth:`_DeviceShard.level`; the kernel takes a call a tree
        and 16 slots, none of them lane-wide or packed."""
        from rabit_tpu.ops.sparse_hist_kernel import CALL_SLOTS

        per_tree = len(build) // self.trees
        local = self.prog["level"][per_tree](
            *self.entries, self.gh, self.node, self._takes(build, depth))
        program.enqueued(local)
        calls = self.trees * -(-per_tree // CALL_SLOTS) \
            if self.entries[1] is not None else 0
        return local, build, (calls, 0, 0)

    def partition(self, tabs: np.ndarray, depth: int) -> None:
        # a split's (feature, threshold) as cells of the flat space
        ptr = self.flat.ptr
        feat, thr, dleft, leaf = np.moveaxis(tabs, -1, 0)
        tab = np.stack([ptr[feat], ptr[feat] + thr, ptr[feat + 1], dleft,
                        leaf], axis=-1).astype(np.int32)
        self.node = self.prog["partition"][depth](
            self.entries[0], self.node,
            tab.reshape(self.lead + tab.shape[1:]))
        program.enqueued(self.node)

    def partition_rows(self, depth: int) -> tuple[int, int]:
        """The move reads every entry row, once for all trees."""
        return self.width, self.width


def _reduce_level(local) -> np.ndarray:
    """One ``rabit_tpu.allreduce`` of the level's histograms through a
    host engine, which takes the fault-tolerant numpy path: their copy
    to the host, where such an engine needs them anyway and the splits
    are then chosen on them whole."""
    shape = local.shape
    program.count("gbdt.hist_bytes_fetched", local.nbytes)
    with program.span("gbdt.level.fetch"):
        local = fetch(local, histogram._writable)
    return rabit_tpu.allreduce(local.reshape(-1), SUM).reshape(shape)


def _merge_summaries(payload, on_device: bool):
    """One ``rabit_tpu.allreduce`` a round of the sketch's payload (this
    rank's summary in its own slot, zeros in the others:
    ``histogram.sketch_program``), issued at world 1 too: the sum is
    every rank's summary side by side, the same bytes on every rank.
    A sum of slots and not ``allreduce_custom`` or ``allgather``: it is
    the one collective every engine carries on the device plane and
    replays after a failure, adding zeros is exact and commutative where
    a merge that prunes is neither, and where the engine hands a device
    array back (``on_device``) the summary never crosses the host.
    Through a host engine it is fetched first, as a level is."""
    program.count("gbdt.summary_bytes_merged", payload.nbytes)
    if on_device:
        return rabit_tpu.allreduce(payload, SUM)
    shape = payload.shape
    host = fetch(payload, histogram._writable)
    return rabit_tpu.allreduce(host.reshape(-1), SUM).reshape(shape)


def _fetch_shortlist(feats, rows):
    """A level's shortlist (``_DeviceShard.scan``) on the host: the
    features ``(slots, k)`` and, a slot, the histogram rows ``(k [+ 1],
    nbin, 2)`` of those features alone (and the totals row), as
    :func:`decide_level` takes a level's: the float32 rows as they
    came, grad and hess apart, seen channel-last."""
    def convert(host):
        feats, rows = host
        program.count("gbdt.hist_bytes_fetched", feats.nbytes + rows.nbytes)
        return feats, np.moveaxis(rows, 0, -1)

    return fetch((feats, rows), convert)


def _assemble(level_of: dict, depth: int, built: np.ndarray, order,
              nslots: int) -> np.ndarray:
    """The level at ``depth`` in its static shape on the host, a slot an
    entry, in float64, from the reduced ``built`` slots (``order``: the
    level slot of each, -1 none) and the level above: one array a depth
    for the whole job, kept in ``level_of`` (127 MB of fresh pages a
    level were a tenth of a round at 968 features, and its noise), so a
    slot that holds no node holds what an earlier round left there."""
    hists = level_of.get(depth)
    if hists is None:
        hists = level_of[depth] = np.zeros((nslots,) + built.shape[1:])
    for pos, s in enumerate(order):
        if s < 0:
            continue
        hists[s] = built[pos]
        if depth:
            # the sum over ranks is linear: this IS the sibling's
            # reduced histogram
            np.subtract(level_of[depth - 1][s >> 1], hists[s],
                        out=hists[s ^ 1])
    return hists


# a level is decided in chunks of slots of about this many bytes of
# its histograms (numpy's temporaries, a dozen float64 grids a chunk,
# then stay small: at Covertype's 224 slots of 8 rows one pass over the
# level whole took half as long again as four over 64 slots each), and
# the chunks of a level of _SCAN_PARALLEL_BYTES and more go to a few
# threads (numpy's loops release the interpreter): at 968 features a
# slot is 4 MB and 7 ms of float64 arithmetic with nothing in flight on
# the device; the chunks of a shortlist's level are a fraction of a
# millisecond each, and threads only made those slower
_SCAN_THREADS = 4
_SCAN_CHUNK_BYTES = 1 << 20
_SCAN_PARALLEL_BYTES = 1 << 24
_scan_pool = None


class LevelSplits(NamedTuple):
    """What :func:`decide_level` decided, an entry a level slot."""

    gain: np.ndarray            # float64; <= 1e-12: the slot stays a leaf
    feature: np.ndarray
    cut: np.ndarray             # the split's bin_threshold
    default_left: np.ndarray    # bool: where the absent rows go
    side: np.ndarray            # the child the next level builds (1: right)
    value: np.ndarray           # float64 weights: the slot's as a leaf,
    left: np.ndarray            # and its children's as a split
    right: np.ndarray


def decide_level(hists, reg_lambda: float, min_child_weight: float,
                 has_missing: bool, features=None,
                 widths=None) -> LevelSplits:
    """Every slot of a level decided in one float64 pass, node or not:
    ``hists`` are the level's ``(slots, rows, nbin, 2)`` histograms as
    the loop holds them (the fetched shortlist's float32 rows seen
    channel-last, or a host engine's float64 level whole); with
    ``has_missing`` a slot's last row is not a feature but holds its
    (grad, hess) totals in bin 0 (``histogram.with_totals``);
    ``features`` ``(slots, rows)`` names each row's feature where the
    rows are a shortlist and not every feature in order, and ``widths``
    are the rows' as ``histogram.best_split`` has them.

    The best candidate of each slot is ``histogram.best_splits``', and
    :func:`_split` takes both sides' sums from the chosen feature's own
    bins.  A split gives both children the weight their side's sums
    give; a child that is split in turn gets its own.  The child whose
    histogram the next level builds is the one with the smaller hessian
    sum (ties: left), so that the other, derived as parent minus built,
    is the larger and inherits the parent's accumulation error at most
    doubled in relative size."""
    global _scan_pool

    nslots = len(hists)
    step = max(1, _SCAN_CHUNK_BYTES // max(1, hists[0].nbytes))
    parts = [slice(at, at + step) for at in range(0, nslots, step)]

    def scan(part):
        return histogram.best_splits(
            hists[part], reg_lambda, min_child_weight, has_missing,
            None if widths is None else widths[part])

    if hists.nbytes < _SCAN_PARALLEL_BYTES or len(parts) < 2:
        found = [scan(part) for part in parts]
    else:
        if _scan_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            _scan_pool = ThreadPoolExecutor(_SCAN_THREADS,
                                            thread_name_prefix="gbdt-scan")
        found = list(_scan_pool.map(scan, parts))
    gain, row, cut, default_left = (np.concatenate(x) for x in zip(*found))
    slot = np.arange(nslots)
    g_tot, h_tot, gl, hl = _split(
        cut, default_left, np.asarray(hists[slot, row], np.float64),
        np.asarray(hists[:, -1, 0], np.float64) if has_missing else None)
    gr, hr = g_tot - gl, h_tot - hl
    with np.errstate(divide="ignore", invalid="ignore"):
        # (a slot that holds no node may read 0 / 0: nobody reads it)
        return LevelSplits(
            gain, row if features is None else features[slot, row], cut,
            default_left, (hr < hl).astype(np.int64),
            -g_tot / (h_tot + reg_lambda), -gl / (hl + reg_lambda),
            -gr / (hr + reg_lambda))


def _split(cut, default_left, hist: np.ndarray, total=None):
    """``(g_tot, h_tot, gl, hl)``, an entry a slot: the (grad, hess)
    sums of each slot's node and of the left side of its split, from
    the chosen feature's own bins ``hist`` ``(slots, nbin, 2)`` in
    float64, added up in bin order as ``hist[s].sum(axis=0)`` adds
    them.  With ``total`` ``(slots, 2)``, the nodes' totals, the rows
    absent from the chosen feature, the totals less its bins
    (``histogram.missing_mass``), are the node's too and go where
    ``default_left`` says.  (The third argument is what the benchmark's
    rehearsals get at to weigh a forest wrongly:
    ``tests/perfbench/as_if_on_chip_gbdt.py``.)"""
    run = np.cumsum(hist, axis=1, dtype=np.float64)
    (g_tot, h_tot), (gl, hl) = run[:, -1].T, run[np.arange(len(run)), cut].T
    if total is not None:
        gm, hm = histogram.missing_mass(hist, total).T
        g_tot, h_tot = g_tot + gm, h_tot + hm
        gl, hl = (np.where(default_left, gl + gm, gl),
                  np.where(default_left, hl + hm, hl))
    return g_tot, h_tot, gl, hl


def _level_tables(found: LevelSplits, split: np.ndarray, live: np.ndarray,
                  leaves: list[list[int]]):
    """How the rows of a level move on, from its decisions
    (:func:`_route_round`'s tables, before the trees are written):
    ``split`` marks the slots whose node is split and ``live`` those
    that hold a node at all, tree-major, and ``leaves[k]`` are tree
    k's leaves so far.  Returns the tables ``(trees, w, 4)`` and the
    level slot built for each slot of this one (-1: none)."""
    trees = len(leaves)
    tab = np.zeros((len(live), 4), np.int32)
    tab[:, 0] = np.where(split, found.feature, 0)
    tab[:, 1] = np.where(split, found.cut, 0)
    tab[:, 2] = split & found.default_left
    # a node that stays a leaf takes the next code of its tree
    leaf = (live & ~split).reshape(trees, -1)
    code = np.cumsum(leaf, axis=1) + [[len(mine)] for mine in leaves]
    tab[:, 3] = np.where(leaf, -code, 0).reshape(-1)
    build = np.where(split, 2 * np.arange(len(live)) + found.side, -1)
    return tab.reshape(trees, -1, 4), build.tolist()


def _grow(trees, slots: list[int], leaves: list[list[int]],
          found: LevelSplits, split: np.ndarray) -> list[int]:
    """Write a level's decisions into the round's trees: the node in
    slot ``s`` (``slots[s]``, tree-major, -1: none) becomes a leaf of
    its tree, appended to ``leaves[k]``, or, where ``split[s]``, a split
    with two new nodes.  Returns the next level's slots."""
    width = len(slots) // len(trees)
    nxt = [-1] * (2 * len(slots))
    cols = [x.tolist() for x in (split, found.feature, found.cut,
                                 found.default_left, found.value,
                                 found.left, found.right)]
    for s, (nid, is_split, feature, cut, default_left, value, left,
            right) in enumerate(zip(slots, *cols)):
        if nid < 0:
            continue
        k = s // width
        tree, node = trees[k], trees[k][nid]
        if not is_split:
            node.value = value
            leaves[k].append(nid)
            continue
        node.feature, node.bin_threshold = feature, cut
        node.default_left, node.value = default_left, 0.0
        node.left, node.right = len(tree), len(tree) + 1
        nxt[2 * s], nxt[2 * s + 1] = node.left, node.right
        tree.append(TreeNode(value=left))
        tree.append(TreeNode(value=right))
    return nxt


def train(values, labels: np.ndarray, num_round: int = 10,
          max_depth: int = 3, nbin: int = 32, learning_rate: float = 0.3,
          reg_lambda: float = 1.0, loss: str = "logistic",
          min_child_weight: float = 1e-3,
          subsample: float = 1.0, seed: int = 0,
          use_pallas: bool | None = None,
          compute_dtype: str | None = None,
          tree_method: str = "hist", num_class: int = 1) -> BoostedModel:
    """Train a distributed booster on this rank's row shard.

    Deterministic across ranks: every rank holds the same cuts and
    every split decision is taken on the allreduced histogram.  Resumes
    from the last committed round after a failure (checkpoint per
    round).

    ``tree_method`` is XGBoost's parameter.  Under ``"hist"`` the cuts
    are made once: the unweighted quantiles of :func:`cut_sample` of
    rank 0's shard, by numpy on the host, broadcast; the rows are
    binned once.  Under ``"approx"`` (the approximate greedy algorithm
    of the XGBoost paper, section 3.3) they are made before every tree
    from every row of every rank, with the round's hessians as weights:
    each rank sorts every feature's values with their weights
    (``gbdt_sketch``) into a summary of ``histogram.summary_entries``
    exact weighted quantiles a feature, the summaries cross ranks by
    one ``rabit_tpu.allreduce`` a round (issued at world 1 too:
    :func:`_merge_summaries`), every rank computes the same cuts from
    the same bytes (``gbdt_cuts``; weighted rank error at most
    ``histogram.sketch_eps``) and bins its rows by them again, over the
    old bins (``gbdt_rebin``).  The tree is then grown as under
    ``"hist"``, by the same programs.  The committed model holds each
    tree's cuts and every split's float value, and routes by value.
    Round k + 1's gradients and its sort of the rows are enqueued
    before round k is committed; the collective, the cuts and the
    binning follow the commit, so that nothing of round k + 1 is agreed
    on before round k is safe.  A resumed rank replays the committed
    trees by their own cuts, computes the gradients and the summary of
    the round it died in again and joins that round's collective: the
    cuts are a function of the committed forest and the rows.

    On an accelerator, and under the XLA engine's device plane, the
    rows live on the device for the whole job (``_DeviceShard``): they
    are binned there, and a level is one fused histogram program over
    one child of every node split the level above (the root at depth
    0), one ``rabit_tpu.allreduce`` of those histograms as the device
    array they are (also at world 1), one small program (``gbdt_scan``)
    that assembles the level there (built slots as they are, siblings
    as parent minus built, a float32 difference) and ranks every slot's
    features by their best gain in float32, the fetch of each slot's
    shortlist (``histogram.SHORTLIST`` features, their histogram rows
    and the totals row: kilobytes where the level is megabytes), one
    float64 pass over those rows that decides every slot
    (:func:`decide_level`) and one program that moves every row to its
    child; the next level's programs and its allreduce are handed over
    next, and the level's ``TreeNode`` s are written from the pass's
    arrays while that kernel runs (:func:`_grow`).  Cut, default
    direction, gain, the stopping rule,
    child weights and the side to build next are therefore float64 sums
    of fetched bins; float32 only chooses which rows the host looks at.
    The assembled level stays on the device as the next level's parent.
    Depth-limit leaf weights come from the last level's rows and the
    margin update is one lookup by row.  The host touches no array of
    length n inside the loop.  Both the device's ranking and the host's
    decision cover every slot of the level, node or not: they have the
    level's static shape, so a round costs the same whatever the tree
    (where few labels are positive a tree is not full, and which nodes
    stop is the seed's to say).
    That holds wherever the engine hands a reduced device array back
    (``engine.keeps_device_payloads``, read off the engine: no option).
    Under a distributed host engine the built histograms cross the
    host for the allreduce anyway: they are fetched whole, the siblings
    come by a float64 subtraction and numpy scans every slot whole.
    Elsewhere the same loop runs on numpy arrays (``_HostShard``) and
    builds the same trees.

    ``loss="softprob"`` with ``num_class=K >= 2`` is XGBoost's
    ``objective=multi:softprob`` under its default
    ``multi_strategy=one_output_per_tree``: labels are class ids in
    ``[0, K)``, the margins are ``(K, n)`` (``base_score`` 0.5 a class:
    it cancels in the softmax and is kept because committed margins are
    compared), and a round commits K trees, ``model.trees[t * K + k]``
    round t's for class k.  Once a round, from the margins the round
    before left, ``p = softmax(m)`` a row (the largest margin
    subtracted first), ``g[k] = p[k] - [y = k]`` and ``h[k] = max(2 p[k]
    (1 - p[k]), 1e-16)`` (XGBoost's ``SoftmaxMultiClassObj``; on the
    device one program, ``gbdt_grad_softmax``); tree k is then fit to
    ``(g[k], h[k])`` as the binary job's one tree is to its ``(g, h)``.
    None of the K trees sees another's update, so they are grown
    together, level by level: **a level is a forest's**.  Per depth one
    program holds every tree's kernel calls (tree k's take ``gh[k]`` and
    ``node[k]``), one ``rabit_tpu.allreduce`` carries K times the built
    slots, one ``gbdt_scan`` ranks K times the level's slots, one fetch
    brings their shortlists, one pass decides them all in float64 and
    one program moves the rows of all K trees; one more a round adds the K
    leaf weights a row.  That is six waits and six collectives a round
    whatever K, where tree by tree it were 6K.  Slots are numbered
    tree-major (:func:`_route_round`), so everything that takes a
    level's slots takes a forest's unchanged, and the other objectives
    are K = 1 of the same loop.  ``predict`` gives ``(n, K)``
    probabilities.  The row sample of ``subsample`` is the round's,
    shared by its K trees, as XGBoost's is.  ``tree_method="approx"``
    with K > 1 is refused: each class would sketch under its own
    hessians.

    ``subsample < 1`` draws a fresh per-round row sample (stochastic
    gradient boosting): sampled-out rows contribute no gradient mass to
    any histogram or leaf this round.  The draw is seeded by
    ``(seed, round, rank)``, so a resumed run replays the exact sample
    of the round it died in — replay stays bit-aligned with survivors.

    NaN feature values are missing: they are added to no bin (a
    histogram has ``nbin`` slots with and without them), every split
    learns a default direction from the absent rows' gradient mass, the
    node's totals less the feature's bins
    (``histogram.split_candidates``), and prediction routes NaN the
    same way — XGBoost's sparsity-aware splits.

    **Sparse rows.**  ``values`` may be sparse: a ``data.SparseMat``
    (libsvm's CSR) or ``data.EllRows(indices, values, counts,
    feat_dim)``, rows of ``(index, value)`` pairs up to an ELL width.  Semantics are XGBoost's for libsvm input: an absent
    entry is missing, not zero, in no bin and never imputed, and the
    job is the job its rows would be as a dense array with NaN for the
    absent entries, the same forest.  What differs is the layout
    (``_SparseShard``): the shard is held as its entries, a present
    value its row and its cell of a flat bin space in which a column
    has the bins its cuts define (``histogram.sparse_cuts``: the
    distinct quantiles of its present values in :func:`cut_sample`, so
    an indicator column has one cut and two cells where the rectangle
    gives it ``nbin``); a level's histograms, the allreduce's payload
    and the level kept for the subtraction are ``(slots, cells, 2)``;
    ``gbdt_scan`` ranks the columns on that axis (a column's prefix sums
    begin anew at its first cell) and hands the host the shortlist as
    windows of ``nbin`` cells, on which the float64 decision is the one
    above; the row move looks for the split's column among the row's
    entries.  On the chip the level is ``ops.sparse_hist_kernel``; off
    it, and under a host engine, the same class adds the same entries up
    by XLA's ``segment_sum`` and the host ranks the reduced level in
    float64 (``histogram.flat_shortlist``).  ``tree_method="approx"``
    on sparse rows is refused (it would sketch present values only).

    ``use_pallas``/``compute_dtype`` pin the histogram path: on TPU the
    default is the fused Pallas kernel with bf16-rounded weights
    (fastest); reproducibility-sensitive callers can force the exact
    float32 XLA path with ``use_pallas=False`` (bit-identical to CPU)
    or keep the kernel but widen it with ``compute_dtype="float32"``.
    """
    check(0.0 < subsample <= 1.0, "subsample must be in (0, 1], got %s",
          subsample)
    check(tree_method in TREE_METHODS, "tree_method must be one of %s, "
          "got %r", TREE_METHODS, tree_method)
    approx = tree_method == "approx"
    check(num_class >= 1 and (num_class > 1) == (loss == "softprob"),
          "boosting: loss=%r with num_class=%d; \"softprob\" takes "
          "num_class >= 2 (XGBoost's multi:softprob), every other loss "
          "one margin a row", loss, num_class)
    check(not approx or num_class == 1,
          "boosting: tree_method=\"approx\" with num_class=%d: each class "
          "would sketch its cuts under its own hessians, K sketches and K "
          "binnings a round, which this loop does not do; use "
          "tree_method=\"hist\"", num_class)
    sparse = isinstance(values, (EllRows, SparseMat))
    if sparse:
        values = ell_rows(values)
        check(not approx, "boosting: tree_method=\"approx\" on sparse "
              "rows, whose sketch would cover present values only, is not "
              "built; use tree_method=\"hist\"")
    if num_class > 1:
        check(labels.min() >= 0 and labels.max() < num_class
              and not np.any(labels % 1),
              "boosting: softprob labels must be class ids in [0, %d)",
              num_class)
    version, restored = rabit_tpu.load_checkpoint()
    if version == 0:
        cut_ptr = None
        if approx:
            # every tree brings its own cuts: none to agree on yet
            cuts = np.zeros((values.shape[1], nbin - 1), np.float32)
        elif sparse:
            with program.span("stage.sparse_cuts"):
                cut_ptr, cuts = rabit_tpu.broadcast(
                    histogram.sparse_cuts(cut_sample(values), nbin)
                    if rabit_tpu.get_rank() == 0 else None, 0)
        else:
            # rank 0's shard defines the cuts; other ranks just receive
            # them
            with program.span("stage.cuts"):
                cuts = rabit_tpu.broadcast(
                    histogram.quantile_cuts(cut_sample(values), nbin)
                    if rabit_tpu.get_rank() == 0 else None, 0)
        model = BoostedModel(cuts=cuts, cut_ptr=cut_ptr,
                             base_score=0.5 if num_class > 1 else 0.0,
                             learning_rate=learning_rate, loss=loss,
                             has_missing=False, tree_method=tree_method,
                             num_class=num_class)
    else:
        model = restored
        # (a forest committed before the field existed reads "hist")
        check(model.tree_method == tree_method,
              "boosting: the committed forest was grown with tree_method="
              "%r, this job asks for %r", model.tree_method, tree_method)
        check(model.num_class == num_class,
              "boosting: the committed forest has num_class=%d, this job "
              "asks for %d", model.num_class, num_class)
        check((getattr(model, "cut_ptr", None) is not None) == sparse,
              "boosting: the committed forest was grown on %s rows, this "
              "job's are %s", "dense" if sparse else "sparse",
              "sparse" if sparse else "dense")
        rabit_tpu.tracker_print(
            "[%d] restart iter=%d" % (rabit_tpu.get_rank(), version))
    program.put("gbdt.classes", num_class)
    device_arm = on_tpu() or _engine_mod.is_device_plane()
    # where the engine hands a reduced device array back, a level's
    # histograms never cross to the host
    device_scan = device_arm and _engine_mod.keeps_device_payloads()

    def stage():
        args = (values, labels, model, max_depth, nbin, subsample, seed,
                use_pallas, compute_dtype)
        if sparse or device_arm:
            shard = (_SparseShard if sparse else _DeviceShard)(*args, scan=(
                reg_lambda, min_child_weight) if device_scan else None)
        else:
            shard = _HostShard(*args)
        if version == 0 and not model.trees:
            # missing handling is GLOBAL: any rank with NaNs means every
            # rank must carry the node totals with its histograms and
            # the missing-aware gain.  Decided HERE (round 0) and
            # checkpointed in the model — a resume must not repeat the
            # collective (replay alignment).
            model.has_missing = bool(rabit_tpu.allreduce(
                np.array([shard.any_nan], np.int32), MAX)[0])
        has_missing = getattr(model, "has_missing", False)
        check(shard.max_bin < nbin + has_missing,
              "boosting: a feature value is NaN (code %d) but the model's "
              "has_missing is False, so its levels carry no node totals "
              "to tell the absent rows' mass by: has_missing is decided "
              "once, at round 0, over every rank's shard, and this shard "
              "does not match it", shard.max_bin)
        shard.start(has_missing)
        return shard

    shard = stage()
    has_missing = getattr(model, "has_missing", False)
    level_of: dict = {}     # depth -> the level's histograms on the host
    epoch = rabit_tpu.device_epoch()
    ready = -1                      # the round whose (grad, hess) is made
    payload = None                  # and, under approx, whose rows are sorted

    def open_round(idx: int):
        """The round's first programs: its gradients and, under
        ``"approx"``, the sort of every feature under its hessians."""
        with program.span("gbdt.grad"):
            shard.grad_hess(idx)
        if not approx:
            return None
        with program.span("gbdt.sketch"), program.span("learn.dispatch"):
            return shard.sketch()

    def open_level(build, depth: int):
        """A level's programs handed over: the histograms of the slots
        ``build`` names, of every tree in one program (a fused bins pass
        a tree), and ONE allreduce for the level (the per-node XGBoost
        wire pattern, batched; every rank holds the same reduced
        histograms, so builds the same).  Where the engine reduces them
        where they are, they are ranked there too and the host will
        decide on the few rows a slot it fetches: nothing here waits.
        Returns the local histograms, the slots built, the kernel calls
        (``shard.level``) and the level reduced: its shortlist on the
        device, or the built slots on the host."""
        with program.span("learn.dispatch"):
            local, order, calls = shard.level(build, depth)
        if not device_scan:
            return local, order, calls, _reduce_level(local)
        reduced = rabit_tpu.allreduce(local, SUM)
        with program.span("learn.dispatch"):
            return local, order, calls, shard.scan(reduced, order, depth)

    for round_idx in range(version, num_round):
        with program.span("learn.step", version=round_idx + 1):
            if device_arm and rabit_tpu.device_epoch() != epoch:
                # device plane re-formed after a failure: old-epoch
                # arrays died with the backends — stage the shard again
                epoch = rabit_tpu.device_epoch()
                shard, ready = stage(), -1
            if ready != round_idx:
                payload = open_round(round_idx)
            if approx:
                # the summaries cross ranks, every rank derives the
                # same cuts and bins its rows by them, over the old bins
                with program.span("gbdt.sketch"):
                    with program.span("gbdt.sketch.merge"):
                        merged = _merge_summaries(payload, device_scan)
                    with program.span("gbdt.sketch.cuts"):
                        cuts = shard.cuts_of(merged)
                with program.span("gbdt.rebin"):
                    shard.rebin(cuts)
                program.count("gbdt.sketches")
                program.count("gbdt.summary_entries",
                              payload.shape[1] * payload.shape[2])
                program.count("gbdt.rows_rebinned", shard.n)
            # the round's trees, one a class, grown together: their
            # level slots tree-major (_route_round)
            trees: list[list[TreeNode]] = [[TreeNode()]
                                           for _ in range(num_class)]
            slots, leaves = [0] * num_class, [[] for _ in trees]
            # the level slot built for each slot of the level above (a
            # root for itself; -1: none)
            opened = open_level(list(range(num_class)), 0)
            for depth in range(max_depth):
                if opened is None:
                    break
                with program.span("gbdt.level", depth=depth):
                    local, order, calls, level = opened
                    if device_scan:
                        with program.span("gbdt.level.fetch"):
                            feats, hists = _fetch_shortlist(*level)
                    with program.span("gbdt.split"):
                        if not device_scan:
                            feats, hists = None, _assemble(
                                level_of, depth, level, order, len(slots))
                            if sparse:
                                # the shortlist the device would hand
                                # over, ranked here
                                feats, hists = histogram.flat_shortlist(
                                    hists, shard.flat, reg_lambda,
                                    min_child_weight)
                        # every slot is decided, node or not: a round's
                        # host work is then a full forest's whatever the
                        # trees, as the device's is (static shapes), and
                        # a job's rounds take the same time
                        found = decide_level(
                            hists, reg_lambda, min_child_weight, has_missing,
                            feats,
                            shard.flat.widths[feats] if sparse else None)
                        live = np.asarray(slots) >= 0
                        split = live & (found.gain > 1e-12)
                        tabs, build = _level_tables(found, split, live,
                                                    leaves)
                    with program.span("gbdt.partition"):
                        shard.partition(tabs, depth)
                    # the next level's programs before this one's trees
                    # are written: the host writes them under the kernel
                    opened = open_level(build, depth + 1) \
                        if depth + 1 < max_depth and split.any() else None
                    with program.span("gbdt.trees"):
                        slots = _grow(trees, slots, leaves, found, split)
                built = sum(s >= 0 for s in order)
                program.count("gbdt.levels")
                program.count("gbdt.split_passes")
                program.count("gbdt.split_slots", len(live))
                read, whole = shard.partition_rows(depth)
                program.count("gbdt.partition_rows_read", read)
                program.count("gbdt.partition_rows_whole", whole)
                program.count("gbdt.levels_device_scan", int(device_scan))
                program.count("gbdt.levels_chunked",
                              int(calls[0] > num_class))
                program.count("gbdt.kernel_calls", calls[0])
                program.count("gbdt.kernel_calls_lane", calls[1])
                # a feature's histograms a call, by the body that built
                # them, and those that shared a packed product
                program.count("gbdt.features_lane",
                              calls[1] * values.shape[1])
                program.count("gbdt.features_two_level",
                              (calls[0] - calls[1]) * values.shape[1])
                if sparse:
                    program.count("gbdt.sparse.payload_bytes", local.nbytes)
                program.count("gbdt.features_packed", calls[2])
                program.count("gbdt.channels", 2 * len(order))
                program.count("gbdt.channels_live", 2 * built)
                program.count("gbdt.hists_derived", built if depth else 0)
                program.count("gbdt.nodes_split", int(split.sum()))
                program.count("gbdt.splits_default_left",
                              int(found.default_left[split].sum()))
            # the nodes at the depth limit are leaves, with the weights
            # their parents' histograms gave them
            with program.span("gbdt.leaf"):
                shard.leaf(_round_leaf_values(trees, slots, leaves,
                                              max_depth))
            if approx:
                # long made by now: 28.6 KB at 28 x 255
                with program.span("gbdt.sketch"), \
                        program.span("gbdt.sketch.cuts"):
                    cuts = fetch(cuts, np.array)
                model.tree_cuts.append(cuts)
            for tree in trees:
                _fill_splits(tree, cuts if approx else
                             shard.flat if sparse else model.cuts)
            model.trees.extend(trees)
            program.count("gbdt.trees", num_class)
            if round_idx + 1 < num_round:
                # the next round's first programs are enqueued before
                # the commit, whose host rounds then run beside them
                payload = open_round(round_idx + 1)
                ready = round_idx + 1
            program.count("learn.iterations")
            program.count("learn.versions")
            rabit_tpu.checkpoint(model)
    return model

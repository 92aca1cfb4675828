"""The plain reference for histogram boosting on sparse rows: rows of
``(index, value)`` pairs with a count, an absent entry missing (not
zero), level-wise trees on each column's own quantile bins, logistic
loss, XGBoost's structure score with **both default directions scored
for every candidate**, leaf weight ``-G / (H + lambda)``.

Straightforward: a present entry is binned against its column's cuts
(numpy ``searchsorted``, a column at a time); a level's histograms are
``jax.ops.segment_sum`` over the entries of a block of rows, in float32
(no kernel, no bucketing, no one-hot product of entries), the blocks'
partial sums added up on the host in float64, so that it fits at 2^25
rows and carries no long float32 chain.  Those sums run on the **host's**
XLA backend, a block a thread: the chip's scatter takes 8 ns an update
one after another (PERF.md section 5), four minutes of a run at 2^24
rows, where the host's cores take the blocks side by side.  A row is
routed by looking for the split's column among its entries (absent: the
committed default direction), on the default device, under matmul
precision ``highest`` for the one-hot lookup of a node's numbers; node
sums, gains
and leaf weights are float64 numpy, as in ``reference/gbdt_missing.py``,
whose boundary band and rounding it shares.  Imports nothing of
``rabit_tpu``.

Like the other boosting references it trains no forest of its own: it
replays the program's committed forest one step, and says how good each
split the program chose (column, cut and default direction) is by the
reference's own gains over both directions, and how far each committed
leaf weight is from the reference's sums.

The bins are a flat space: column ``j`` has ``cut_ptr[j + 1] -
cut_ptr[j]`` cuts and one more bin, its cells ``[ptr[j], ptr[j + 1])``
with ``ptr[j] = cut_ptr[j] + j`` (XGBoost's ``HistogramCuts``).

Departures from XGBoost's ``hist``, shared with the program: the cuts
are the exact ``max_bin - 1`` quantiles of the present entries of a
strided sample of rank 0's rows, each distinct value once, where
XGBoost merges weighted quantile sketches over all rows (so a column
of one distinct value gets one cut at that value, and one without an
entry one cut at 0); growth is level-wise and synchronous
(``grow_policy=depthwise``).
"""
from __future__ import annotations

import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.reference.gbdt import levels_of, rows_of
from perfbench.reference.gbdt_missing import BOUNDARY, rounded

BLOCK_ROWS = 1 << 18
# rows binned at a time inside a block: a thread's temporaries are 1 KB a
# row, and the rows themselves stand beside them until all are binned
BIN_ROWS = 1 << 16
THREADS = max(1, min(12, os.cpu_count() or 1))
# seconds the last replay spent, by part (the adapter prints them)
TIMES: dict = {}


def timed(name: str):
    """Adds a call's seconds to ``TIMES[name]``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                TIMES[name] = TIMES.get(name, 0.0) + time.perf_counter() - t0
        return inner
    return wrap


# ----------------------------------------------------------------------
# entries, cuts and bins
# ----------------------------------------------------------------------
def present(idx: np.ndarray, val: np.ndarray, counts, f: int) -> np.ndarray:
    """``(n, width)`` bool: the slots of the ELL rows that hold an
    entry (within the row's count, a column of the matrix, not NaN)."""
    held = (idx >= 0) & (idx < f) & ~np.isnan(val)
    if counts is not None:
        held &= np.arange(idx.shape[1]) < np.asarray(counts)[:, None]
    return held


def cut_sample(arrays, sample_rows: int):
    """The stated sample of each of ``arrays``: every ``n //
    sample_rows``-th row, at most ``sample_rows`` of them."""
    n = arrays[0].shape[0]
    return [None if a is None else a[::max(1, n // sample_rows)][
        :sample_rows] for a in arrays]


@timed("cuts")
def quantile_cuts(idx, val, counts, f: int, nbin: int):
    """``(cut_ptr, cut_vals)``: of each column the distinct values among
    the ``nbin - 1`` interior quantiles of its present entries (float32,
    ascending); one cut at 0 for a column with none."""
    qs = np.linspace(0, 1, nbin + 1)[1:-1]
    held = present(idx, val, counts, f)
    cols, vals = idx[held], val[held].astype(np.float32)
    order = np.argsort(cols, kind="stable")
    cols, vals = cols[order], vals[order]
    ends = np.searchsorted(cols, np.arange(f + 1))
    out = []
    for j in range(f):
        have = vals[ends[j]:ends[j + 1]]
        out.append(np.unique(np.quantile(have, qs).astype(np.float32))
                   if have.size else np.zeros(1, np.float32))
    cut_ptr = np.concatenate([[0], np.cumsum([len(c) for c in out])])
    return cut_ptr.astype(np.int64), np.concatenate(out)


def bin_rows(idx, val, counts, f: int, cut_ptr, cut_vals) -> np.ndarray:
    """``(n, width)`` int32 cells of the rows' entries, -1 where a slot
    holds none: ``ptr[column] +`` the number of the column's cuts at or
    below the value, a column at a time."""
    held = present(idx, val, counts, f)
    ptr = cut_ptr + np.arange(f + 1)
    col = np.where(held, idx, 0)
    # a column of one cut, in one pass; the others each by its own
    first = cut_vals[cut_ptr[:-1]]
    bins = (val >= first[col]).astype(np.int64)
    wide = np.flatnonzero(np.diff(cut_ptr) > 1)
    if wide.size:
        some = held & np.isin(col, wide)
        sub_col, sub_val = col[some], val[some]
        sub = np.zeros(sub_col.shape, np.int64)
        for j in wide:
            mine = sub_col == j
            sub[mine] = np.searchsorted(
                cut_vals[cut_ptr[j]:cut_ptr[j + 1]], sub_val[mine],
                side="right")
        bins[some] = sub
    return np.where(held, ptr[col] + bins, -1).astype(np.int32)


# ----------------------------------------------------------------------
# the rows, on the device in blocks
# ----------------------------------------------------------------------
def _goes_left(cells, lo, cut, hi, dleft):
    """Which rows go left at a split whose column's cells are ``[lo,
    hi)``, the last of them that goes left ``cut`` (each a row's own):
    by the row's entry of that column, the default way without one."""
    import jax.numpy as jnp

    here = (cells >= lo[:, None]) & (cells < hi[:, None])
    under = here & (cells <= cut[:, None])
    return jnp.where(jnp.any(here, axis=1), jnp.any(under, axis=1),
                     dleft != 0)


def _walk(cells, table, depth: int):
    """The node each row of a block ends at after ``depth`` steps down
    one tree; ``table[i]`` = (lo, cut, hi, default left, left, right) of
    node ``i``, a leaf its own child both ways."""
    import jax.numpy as jnp

    node = jnp.zeros(cells.shape[0], jnp.int32)
    for _ in range(depth):
        lo, cut, hi, dleft, left, right = (
            c.astype(jnp.int32) for c in rows_of(table, node).T)
        node = jnp.where(_goes_left(cells, lo, cut, hi, dleft), left, right)
    return node


def _move(cells, node, tab):
    """Every row of a block to its child's slot; a row of a node that
    is a leaf leaves the walk (-1).  ``tab[s]`` = (lo, cut, hi, default
    left, split)."""
    import jax.numpy as jnp

    lo, cut, hi, dleft, split = (c.astype(jnp.int32) for c in rows_of(
        tab, jnp.maximum(node, 0)).T)
    left = _goes_left(cells, lo, cut, hi, dleft)
    return jnp.where((node >= 0) & (split > 0), 2 * node + 1 - left, -1)


def _hist(cells, gh, node, nbins: int, nslots: int):
    """``(nslots * nbins, 2)`` float32: the (grad, hess) of a block's
    rows added into cell ``node * nbins + cell`` of each of their
    entries; a row at no slot and a slot without an entry add nothing
    (into a segment past the last, dropped)."""
    import jax
    import jax.numpy as jnp

    size = nslots * nbins
    at = jnp.where((cells >= 0) & (node >= 0)[:, None],
                   node[:, None] * nbins + cells, size).reshape(-1)
    return jax.ops.segment_sum(
        jnp.broadcast_to(gh[:, None, :], cells.shape + (2,)).reshape(-1, 2),
        at, num_segments=size + 1)[:size]


class Shard:
    """One rank's rows as the cells of their entries, in blocks on the
    device.  Once it is made the rows it was made of are no longer
    read: a caller short of host memory drops them."""

    def __init__(self, idx, val, counts, f: int, labels, cut_ptr, cut_vals):
        import jax

        self.n, self.f = idx.shape[0], f
        self.cut_ptr = np.asarray(cut_ptr, np.int64)
        self.ptr = self.cut_ptr + np.arange(f + 1)
        self.nbins = int(self.ptr[-1])
        self.labels = np.asarray(labels, np.float64)
        self.starts = list(range(0, self.n, BLOCK_ROWS))

        # for the walks, on the default device; for the sums, the host's
        self.host = jax.devices("cpu")[0]

        def block(lo: int):
            hi = min(self.n, lo + BLOCK_ROWS)
            cells = np.empty((hi - lo, idx.shape[1]), np.int32)
            for b in range(lo, hi, BIN_ROWS):
                at = slice(b, min(hi, b + BIN_ROWS))
                cells[b - lo:at.stop - lo] = bin_rows(
                    idx[at], val[at], None if counts is None else counts[at],
                    f, self.cut_ptr, cut_vals)
            return jax.device_put(cells), jax.device_put(cells, self.host)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(THREADS) as pool:
            self.cells, self.cells_host = zip(*pool.map(block, self.starts))
        TIMES["bins"] = TIMES.get("bins", 0.0) + time.perf_counter() - t0
        self._jit = {}

    def _fn(self, name, fn, **static):
        import jax

        key = (name,) + tuple(sorted(static.items()))
        if key not in self._jit:
            self._jit[key] = jax.jit(functools.partial(fn, **static))
        return self._jit[key]

    def _split_table(self, feat, thr):
        """(lo, cut, hi) cells of splits at bin ``thr`` of column
        ``feat`` (a leaf: column 0, harmless)."""
        feat = np.maximum(feat, 0)
        return self.ptr[feat], self.ptr[feat] + thr, self.ptr[feat + 1]

    # ---- margins of a forest -----------------------------------------
    @timed("margins")
    def margins(self, forest_int, forest_val, rate: float, max_depth: int):
        """The margin of every row under the given trees (base 0)."""
        import jax

        margin = np.zeros(self.n, np.float64)
        walk = self._fn("walk", _walk, depth=max_depth)
        for tree_int, tree_val in zip(forest_int, forest_val):
            feat, thr, dleft, left, right = tree_int.T
            me = np.arange(len(tree_int))
            lo, cut, hi = self._split_table(feat, thr)
            table = np.stack([lo, cut, hi, dleft,
                              np.where(feat >= 0, left, me),
                              np.where(feat >= 0, right, me)],
                             axis=1).astype(np.float32)
            with jax.default_matmul_precision("highest"):
                ends = [walk(c, table) for c in self.cells]
            margin += rate * np.asarray(tree_val, np.float64)[
                np.concatenate([np.asarray(e) for e in ends])]
        return margin

    def grad_hess(self, margin):
        p = 1.0 / (1.0 + np.exp(-margin))
        return np.stack([p - self.labels, p * (1.0 - p)], axis=1)

    # ---- one level ---------------------------------------------------
    @timed("hist")
    def level_hist(self, gh, node, nslots: int) -> np.ndarray:
        """(nslots, nbins, 2) float64: per slot and cell the sums of
        (grad, hess) over the entries in that cell of the rows at that
        slot.  A row at no slot (node < 0) is in none."""
        import jax

        fn = self._fn("hist", _hist, nbins=self.nbins, nslots=nslots)
        gh, node = gh.astype(np.float32), node.astype(np.int32)

        def block(args):
            lo, cells = args
            at = slice(lo, lo + BLOCK_ROWS)
            return np.asarray(fn(cells, jax.device_put(gh[at], self.host),
                                 jax.device_put(node[at], self.host)),
                              np.float64)

        out = np.zeros((nslots * self.nbins, 2), np.float64)
        with ThreadPoolExecutor(THREADS) as pool:
            for part in pool.map(block, zip(self.starts, self.cells_host)):
                out += part
        return out.reshape(nslots, self.nbins, 2)

    @timed("partition")
    def partition(self, node, tab: np.ndarray):
        """Every row to its child's slot (:func:`_move`).  ``tab[s]`` =
        (feature, threshold, default left, split)."""
        import jax
        import jax.numpy as jnp

        lo, cut, hi = self._split_table(tab[:, 0], tab[:, 1])
        table = np.stack([lo, cut, hi, tab[:, 2], tab[:, 3]],
                         axis=1).astype(np.float32)
        move = self._fn("move", _move)
        with jax.default_matmul_precision("highest"):
            out = [np.asarray(move(
                cells, jnp.asarray(node[lo_:lo_ + BLOCK_ROWS], jnp.int32),
                table)) for lo_, cells in zip(self.starts, self.cells)]
        return np.concatenate(out).astype(np.int64)


def slot_sums(values, node, nslots: int) -> np.ndarray:
    """(nslots, k) float64: the sums of the (n, k) ``values`` over the
    rows at each slot (``reference/gbdt_missing.py slot_sums``, a block
    of rows a thread and no copy of the rows at a slot: 33.5M rows, five
    columns and fourteen levels a replay)."""
    def block(lo: int):
        at = slice(lo, lo + (1 << 22))
        slot = np.where(node[at] >= 0, node[at], nslots)
        return np.stack([np.bincount(slot, values[at, c], nslots + 1)[
            :nslots] for c in range(values.shape[1])], axis=1)

    with ThreadPoolExecutor(THREADS) as pool:
        return sum(pool.map(block, range(0, len(node), 1 << 22)))


# ----------------------------------------------------------------------
# the structure score on the flat bin space, both default directions
# ----------------------------------------------------------------------
def split_gains(hist: np.ndarray, ptr: np.ndarray, total: np.ndarray,
                reg_lambda: float, min_child_weight: float):
    """``(gain_left, gain_right)`` of one node, a value a cell of its
    ``(nbins, 2)`` histogram of present entries: for the candidate
    (column, cut) of a cell, left = the column's cells up to it, the
    gain with the rows absent from the column sent left and sent right.
    ``total`` is the node's (grad, hess) over all its rows.  A column's
    last cell is no cut, and a candidate one of whose children would
    weigh less than ``min_child_weight`` is not eligible: both read
    -inf."""
    widths = np.diff(ptr)
    run = np.cumsum(hist, axis=0)
    before = run[ptr[:-1]] - hist[ptr[:-1]]
    left = run - np.repeat(before, widths, axis=0)      # a column's own
    gl, hl = left[:, 0], left[:, 1]
    sums = np.repeat(left[ptr[1:] - 1], widths, axis=0)
    gm, hm = total[0] - sums[:, 0], total[1] - sums[:, 1]
    parent = total[0] * total[0] / (total[1] + reg_lambda)

    def score(gl_, hl_):
        gr_, hr_ = total[0] - gl_, total[1] - hl_
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (gl_ * gl_ / (hl_ + reg_lambda)
                    + gr_ * gr_ / (hr_ + reg_lambda) - parent)
        gain = np.where((hl_ >= min_child_weight)
                        & (hr_ >= min_child_weight), gain, -np.inf)
        gain[ptr[1:] - 1] = -np.inf
        return gain

    return score(gl + gm, hl + hm), score(gl, hl)


def replay_tree(shard: Shard, gh, tree_int: np.ndarray, tree_val: np.ndarray,
                max_depth: int, reg_lambda: float, min_child_weight: float,
                operand_dtype: str, combine=lambda tag, a: a) -> dict:
    """Walk one of the program's trees on the reference's gradients;
    the numbers are those of ``reference/gbdt_missing.py replay_tree``,
    taken the same way, on the flat bin space."""
    levels = levels_of(tree_int, max_depth)
    node = np.zeros(shard.n, np.int64)
    gh_op = rounded(gh, operand_dtype)
    regret, unsplit, leaf_err, gap_op, abs_all = 0.0, 0, 0.0, 0.0, 0.0
    splits = leaves = default_left = 0
    worst = worst_leaf = None

    def leaf_gap(nid: int, tot: np.ndarray) -> float:
        """|G_program - G|, the first recovered with the reference's H."""
        return abs(-float(tree_val[nid]) * (tot[1] + reg_lambda) - tot[0])

    for depth in range(max_depth + 1):
        slots = levels[depth]
        if all(nid < 0 for nid in slots):
            break
        n = len(slots)
        t0 = time.perf_counter()
        abs_g = combine(f"abs{depth}", slot_sums(np.abs(gh[:, :1]), node, n))
        tot = combine(f"tot{depth}", slot_sums(gh, node, n))
        tot_op = combine(f"top{depth}", slot_sums(gh_op, node, n))
        TIMES["sums"] = TIMES.get("sums", 0.0) + time.perf_counter() - t0
        hist = None if depth == max_depth else combine(
            f"hist{depth}", shard.level_hist(gh_op, node, n))
        tab = np.zeros((n, 4), np.int64)
        for s, nid in enumerate(slots):
            if nid < 0:
                continue
            feat, thr, dleft = (int(v) for v in tree_int[nid, :3])
            band = BOUNDARY * abs_g[s, 0] ** 2 / (tot_op[s, 1] + reg_lambda)
            if hist is not None:
                left, right = split_gains(hist[s], shard.ptr, tot_op[s],
                                          reg_lambda, min_child_weight)
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
                        "weight": float(tree_val[nid])}
                # the depth limit stops a node; above it, only the rule
                unsplit += hist is not None and best > band
                continue
            splits += 1
            default_left += dleft != 0
            # the committed candidate, eligible to within the band
            left, right = split_gains(
                hist[s], shard.ptr, tot_op[s], reg_lambda,
                min_child_weight * (1.0 - BOUNDARY))
            inside = thr < shard.ptr[feat + 1] - shard.ptr[feat] - 1
            took = float((left if dleft else right)[
                shard.ptr[feat] + thr]) if inside else -np.inf
            here = (best - took) / max(best, band) if band > 0 \
                else float("inf")
            if here > regret:
                regret, worst = here, {
                    "depth": depth, "slot": s, "best": best, "took": took,
                    "band": band, "rows_hess": float(tot_op[s, 1]),
                    "feature": feat, "threshold": thr}
            tab[s] = (feat, thr, dleft, 1)
        if depth < max_depth:
            node = shard.partition(node, tab)
    return {"split_regret": regret, "unsplit_above_limit": float(unsplit),
            "leaf_sum_rel_err": leaf_err,
            "leaf_sum_rounded_rel_err": gap_op / abs_all if abs_all else 0.0,
            "splits": splits,
            "leaves": leaves, "default_left": int(default_left),
            "worst_split": worst, "worst_leaf": worst_leaf}


def replay(idx, val, counts, f: int, labels, cut_ptr, cut_vals,
           forest_int: np.ndarray, forest_val: np.ndarray, which: list[int],
           max_depth: int, rate: float, reg_lambda: float,
           min_child_weight: float, operand_dtype: str,
           combine=lambda tag, a: a) -> dict:
    """:func:`replay_shard` of the rows' :class:`Shard`."""
    return replay_shard(
        Shard(idx, val, counts, f, labels, cut_ptr, cut_vals), forest_int,
        forest_val, which, max_depth, rate, reg_lambda, min_child_weight,
        operand_dtype, combine)


def replay_shard(shard: Shard, forest_int: np.ndarray, forest_val: np.ndarray,
                 which: list[int], max_depth: int, rate: float,
                 reg_lambda: float, min_child_weight: float,
                 operand_dtype: str, combine=lambda tag, a: a) -> dict:
    """The worst of each number over the trees ``which`` of the forest,
    each replayed on the margins of the trees before it."""
    counts_of = ("splits", "leaves", "default_left")
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
                out[name] = out.get(name, 0) + v if name in counts_of \
                    else max(out.get(name, 0), v)
    return out

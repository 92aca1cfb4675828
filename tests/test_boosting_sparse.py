"""Histogram boosting on sparse rows: the shard held as its entries, a
flat bin space of each column's own cuts, against the plain reference
(``perfbench/reference/gbdt_sparse.py``), against the dense learner on
the same rows with NaN for the absent entries (the tie: the layout is
an implementation, not another learner), and the kernel interpreted
against ``segment_sum``."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.reference import gbdt_sparse as ref  # noqa: E402
from rabit_tpu.learn import boosting, histogram  # noqa: E402
from rabit_tpu.learn.data import EllRows, SparseMat, ell_rows  # noqa: E402

NBIN = 16


def _rows(n=3000, width=6, f=40, seed=3, indicators=10):
    """Seeded rows without field structure: random columns, counts from
    0 to the width, an empty row, a column no row has (``f - 1``), a
    column every row with an entry has (0, an indicator), indicator
    columns (one distinct value) and numeric ones."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice(np.arange(1, f - 1), width, replace=False)
                    for _ in range(n)]).astype(np.int32)
    idx[:, 0] = 0
    val = rng.standard_normal((n, width)).astype(np.float32)
    val[:, 1] = np.round(val[:, 1] * 2) / 2           # few levels
    val = np.where(idx < indicators, 1.0, val).astype(np.float32)
    cnt = rng.integers(0, width + 1, n)
    cnt[7] = 0                                        # an empty row
    rows = EllRows(idx, val, cnt, f)
    z = np.nan_to_num(rows.to_dense())
    logit = (2 * z[:, 3] - 1.5 * z[:, 5] + z[:, 20] * z[:, 21]
             + np.sin(3 * z[:, 30]) + 0.8 * z[:, 0] - 0.4)
    labels = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return rows, labels


def _structure(model):
    return [[(n.feature, n.split, n.default_left, n.left, n.right)
             for n in tree] for tree in model.trees]


def _weights(model):
    return np.array([n.value for tree in model.trees for n in tree])


@pytest.fixture
def arm(monkeypatch):
    """``arm("device")`` makes ``train`` take the arm it takes on an
    accelerator (``on_tpu`` as the boosting module sees it: the kernel
    is then interpreted, ``histogram``'s own ``on_tpu`` says so)."""
    import rabit_tpu

    def switch(which: str) -> None:
        monkeypatch.setattr(boosting, "on_tpu", lambda: which == "device")
        if rabit_tpu.initialized():
            rabit_tpu.finalize()
        rabit_tpu.init(rabit_engine="empty")

    yield switch
    if rabit_tpu.initialized():
        rabit_tpu.finalize()


KW = dict(max_depth=4, nbin=NBIN, min_child_weight=1.0)


def _forest_arrays(model):
    """The forest as the benchmark's adapter hands it to the reference."""
    from perfbench import harness

    gbdt = harness.load_module(os.path.join(ROOT, "perfbench", "learners",
                                            "gbdt.py"))
    got = gbdt.committed(model)
    return got["forest_int"], got["forest_val"]


# ---- (i) against the plain reference ---------------------------------
@pytest.mark.parametrize("which,use_pallas", [
    ("host", False), ("device", False), ("device", True)],
    ids=["host", "device-xla", "device-kernel"])
def test_sparse_forest_against_the_plain_reference(arm, which, use_pallas):
    rows, labels = _rows()
    arm(which)
    model = boosting.train(rows, labels, num_round=3,
                           use_pallas=use_pallas, **KW)
    cut_ptr, cut_vals = ref.quantile_cuts(*rows[:3], rows.feat_dim, NBIN)
    np.testing.assert_array_equal(model.cut_ptr, cut_ptr)
    np.testing.assert_array_equal(model.cuts, cut_vals)
    widths = np.diff(cut_ptr)
    assert widths[0] == 1 and widths[-1] == 1     # every row's; no row's
    assert model.cuts[cut_ptr[-2]] == 0.0 and widths.max() == NBIN - 1
    forest_int, forest_val = _forest_arrays(model)
    got = ref.replay(*rows[:3], rows.feat_dim, labels, cut_ptr, cut_vals,
                     forest_int, forest_val, [0, 2], KW["max_depth"], 0.3,
                     1.0, KW["min_child_weight"],
                     "bfloat16" if use_pallas else "float32")
    # the same splits and default directions: no regret by the
    # reference's own gains over both directions; the same leaf weights
    assert got["split_regret"] <= 1e-6, got["worst_split"]
    assert got["unsplit_above_limit"] == 0
    assert got["leaf_sum_rounded_rel_err"] <= 2e-6, got["worst_leaf"]
    assert got["leaf_sum_rel_err"] <= (2e-2 if use_pallas else 1e-5)
    assert got["splits"] >= 10 and 0 < got["default_left"] < got["splits"]


@pytest.mark.parametrize("which", ["host", "device"])
def test_a_flat_level_decided_in_one_pass_commits_the_slot_by_slot_forest(
        arm, monkeypatch, which):
    """Windows of unequal widths and a totals row, every slot in one
    pass: the forest of the loop that decided a slot at a time, node
    for node and bit for bit, its hand-overs in the order they had."""
    import boosting_oracle as oracle

    rows, labels = _rows()
    model = oracle.held_to_the_loop_of_then(
        arm, which, monkeypatch, rows, labels, num_round=3,
        use_pallas=False, **KW)
    assert model.has_missing and model.cut_ptr is not None


def test_reference_bins_are_the_programs(arm):
    rows, _labels = _rows(n=500)
    cut_ptr, cut_vals = histogram.sparse_cuts(rows, NBIN)
    flat = histogram.FlatBins(cut_ptr, cut_vals, NBIN)
    np.testing.assert_array_equal(
        histogram.bin_entries(rows, flat),
        ref.bin_rows(*rows[:3], rows.feat_dim, flat.cut_ptr, cut_vals))


# ---- (ii) the tie to the dense learner --------------------------------
@pytest.mark.parametrize("which", ["host", "device"])
@pytest.mark.parametrize("kw", [
    {}, {"subsample": 0.5, "seed": 4},
    {"loss": "softprob", "num_class": 3},
    {"loss": "softprob", "num_class": 3, "subsample": 0.5, "seed": 4}],
    ids=["k1", "k1-subsample", "k3", "k3-subsample"])
def test_sparse_and_dense_with_nan_grow_the_same_forest(arm, which, kw):
    rows, labels = _rows()
    if kw.get("num_class"):
        z = np.nan_to_num(rows.to_dense())
        labels = (labels + (z[:, 20] > 0.3)).astype(np.float32)
    models = []
    for values in (rows, rows.to_dense()):
        arm(which)
        models.append(boosting.train(values, labels, num_round=3,
                                     use_pallas=False, **KW, **kw))
    sparse, dense = models
    assert dense.has_missing and dense.cut_ptr is None
    # features, thresholds by value, default directions, the tree's shape
    assert _structure(sparse) == _structure(dense)
    assert sum(len(t) for t in sparse.trees) > 30 * (kw.get("num_class", 1))
    # leaf weights to float32 rounding of their sums (a weight is
    # -G / (H + lambda), and G a sum of both signs)
    np.testing.assert_allclose(_weights(sparse), _weights(dense),
                               rtol=1e-5, atol=2e-4)


# ---- (iii) the flat bin space -----------------------------------------
def test_a_column_of_one_value_has_one_cut_and_splits_present_from_absent(
        arm):
    rng = np.random.default_rng(5)
    n, f = 2000, 12
    idx = np.tile(np.array([[3, 7, 9]], np.int32), (n, 1))
    val = rng.standard_normal((n, 3)).astype(np.float32)
    val[:, 0] = 1.0
    has = rng.random(n) < 0.4
    idx[~has, 0] = f                    # the padding index: no entry
    labels = (has ^ (rng.random(n) < 0.05)).astype(np.float32)
    rows = EllRows(idx, val, None, f)
    for which in ("host", "device"):
        arm(which)
        model = boosting.train(rows, labels, num_round=1, max_depth=2,
                               nbin=NBIN, use_pallas=False)
        assert np.diff(model.cut_ptr)[3] == 1 and \
            model.cuts[model.cut_ptr[3]] == 1.0
        root = model.trees[0][0]
        # absent rows left of the one cut, present rows right
        assert (root.feature, root.bin_threshold, root.split,
                root.default_left) == (3, 0, 1.0, True)
        p = model.predict(rows)
        assert ((p > 0.5) == has).mean() > 0.99


def _level(flat, slots, seed=0):
    rng = np.random.default_rng(seed)
    level = np.zeros((slots, flat.size, 2))
    level[:, :flat.nbins, 0] = rng.standard_normal((slots, flat.nbins))
    level[:, :flat.nbins, 1] = rng.random((slots, flat.nbins)) + 0.5
    # a node's totals: a column's sums and some absent mass
    level[:, flat.nbins] = level[:, flat.ptr[0]:flat.ptr[1]].sum(axis=1) \
        + [0.3, 2.0]
    return level


def test_shortlist_window_of_a_column_near_the_end_of_the_flat_axis():
    """The last column's window of ``nbin`` cells runs past the flat
    axis: it reads the column's own bins and zeros, on the device and on
    the host, and the device ranks as the host does."""
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    cuts = np.array([NBIN - 1] * 31 + [NBIN - 2])
    cut_ptr = np.concatenate([[0], np.cumsum(cuts)])
    flat = histogram.FlatBins(cut_ptr, rng.random(cut_ptr[-1]), NBIN)
    # the last column's 15 cells end at the totals' cell, the axis' last
    assert flat.nbins == flat.size - 1 and flat.size - flat.ptr[-2] == NBIN
    level = _level(flat, 4)
    want_feats, want_rows = histogram.flat_shortlist(level, flat, 1.0, 1.0)
    built = jnp.asarray(level, jnp.float32)
    feats, rows = histogram.level_shortlist_flat(
        histogram.assemble_level(built[:, None], None, None), flat, 1.0, 1.0)
    np.testing.assert_array_equal(np.asarray(feats), want_feats)
    rows = np.moveaxis(np.asarray(rows), 0, -1)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-6)
    for s in range(4):
        for r, j in enumerate(want_feats[s]):
            w = flat.widths[j]
            np.testing.assert_array_equal(
                want_rows[s, r, :w], level[s, flat.ptr[j]:flat.ptr[j + 1]])
            assert not want_rows[s, r, w:].any()
        np.testing.assert_array_equal(want_rows[s, -1, 0],
                                      level[s, flat.nbins])
    # the last column's window, asked for by name
    _f, rows = histogram.flat_shortlist(level, flat, 1.0, 1.0, k=flat.f)
    np.testing.assert_array_equal(
        rows[0, flat.f - 1, :flat.widths[-1]], level[0, flat.ptr[-2]:
                                                     flat.nbins])


def test_a_cut_from_a_windows_last_bin_on_is_none():
    hist = np.zeros((2, NBIN, 2))
    hist[0, :3] = [[1.0, 2.0], [-3.0, 2.0], [0.5, 2.0]]
    hist[1, :2] = [[-2.0, 3.0], [1.0, 3.0]]
    total = hist[0].sum(axis=0) + [4.0, 5.0]
    free = histogram.best_split(hist, 1.0, 1.0, total)
    held = histogram.best_split(hist, 1.0, 1.0, total, widths=[3, 2])
    assert held[2] < [3, 2][held[1]] - 1
    gain, _left = histogram.split_candidates(hist, 1.0, 1.0, total)
    assert free[0] == gain.max() and held[0] <= free[0]
    assert held[0] == max(gain[0, :2].max(), gain[1, :1].max())


# ---- (iv) kill and resume ---------------------------------------------
@pytest.mark.parametrize("which", ["host", "device"])
def test_resumed_sparse_job_replays_the_forest_to_the_same_margins(
        arm, which, monkeypatch):
    rows, labels = _rows(n=1500)
    kw = dict(use_pallas=False, **KW)
    arm(which)
    straight = boosting.train(rows, labels, num_round=5, **kw)
    arm(which)
    boosting.train(rows, labels, num_round=2, **kw)
    seen = {}
    start = boosting._SparseShard.start

    def started(self, has_missing):
        start(self, has_missing)
        seen["margin"] = np.asarray(self.margin)[:self.rows]
        seen["trees"] = len(self.model.trees)

    monkeypatch.setattr(boosting._SparseShard, "start", started)
    # the same process keeps the committed forest (world 1, empty engine)
    resumed = boosting.train(rows, labels, num_round=5, **kw)
    assert seen["trees"] == 2
    committed = boosting.BoostedModel(
        cuts=resumed.cuts, cut_ptr=resumed.cut_ptr, trees=resumed.trees[:2])
    np.testing.assert_allclose(seen["margin"], committed.margin(rows),
                               rtol=1e-6, atol=1e-6)
    assert _structure(resumed) == _structure(straight)
    np.testing.assert_allclose(_weights(resumed), _weights(straight),
                               rtol=1e-5, atol=1e-6)


def test_a_forest_is_resumed_on_rows_of_its_own_layout(empty_engine):
    rows, labels = _rows(n=400)
    boosting.train(rows, labels, num_round=1, **KW)
    with pytest.raises(Exception, match="grown on"):
        boosting.train(rows.to_dense(), labels, num_round=2, **KW)


def test_approx_on_sparse_rows_is_refused(empty_engine):
    rows, labels = _rows(n=400)
    with pytest.raises(Exception, match="approx"):
        boosting.train(rows, labels, num_round=1, tree_method="approx", **KW)


# ---- (v) predict -------------------------------------------------------
@pytest.mark.parametrize("kw", [{}, {"loss": "softprob", "num_class": 3}],
                         ids=["k1", "k3"])
def test_predict_on_sparse_rows_equals_predict_on_their_dense_form(
        empty_engine, kw):
    rows, labels = _rows(n=1200)
    if kw:
        labels = (labels + (rows.values[:, 0] > 0)).astype(np.float32)
    model = boosting.train(rows, labels, num_round=3, **KW, **kw)
    other, _ = _rows(n=700, seed=9)             # rows it has not seen
    for r in (rows, other):
        np.testing.assert_array_equal(model.predict(r),
                                      model.predict(r.to_dense()))
    assert model.predict(rows).shape == ((1200, 3) if kw else (1200,))


def test_a_sparsemat_trains_as_its_ell_rows(empty_engine):
    import rabit_tpu

    rows, labels = _rows(n=600)
    held = rows.present()
    mat = SparseMat(
        indptr=np.concatenate([[0], np.cumsum(held.sum(axis=1))]),
        findex=rows.indices[held], fvalue=rows.values[held], labels=labels,
        feat_dim=rows.feat_dim)
    again = ell_rows(mat)
    np.testing.assert_array_equal(np.nan_to_num(again.to_dense(), nan=-9),
                                  np.nan_to_num(rows.to_dense(), nan=-9))
    a = boosting.train(mat, labels, num_round=2, **KW)
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    b = boosting.train(rows, labels, num_round=2, **KW)
    assert _structure(a) == _structure(b)


# ---- (vi) the kernel, interpreted, against segment_sum -----------------
def _entries(n_tiles=2, width=5, cells=1300, seed=0):
    import jax.numpy as jnp

    from rabit_tpu.ops import sparse_hist_kernel as sk

    rng = np.random.default_rng(seed)
    n = n_tiles * sk.ROW_TILE
    c = rng.integers(0, cells, (width, n)).astype(np.int32)
    c[rng.random((width, n)) < 0.35] = -1             # ragged rows
    c[:, 11] = -1                                     # an empty row
    packed, fb, real = sk.bucket_group(jnp.asarray(c), cells=cells)
    assert int(real) == np.count_nonzero(c >= 0)
    return c, packed, fb, rng


def test_bucketing_keeps_every_entry_once_in_its_own_block():
    from rabit_tpu.ops import sparse_hist_kernel as sk

    c, packed, fb, _rng = _entries()
    row, cell, real = (np.asarray(a) for a in sk.coordinates(packed, fb, 2))
    got = np.stack([row[real], cell[real]])
    want = np.stack(np.nonzero(c.T >= 0)[:1] + (c.T[c.T >= 0],))
    np.testing.assert_array_equal(got[:, np.lexsort(got)],
                                  want[:, np.lexsort(want)])
    # a sub-chunk's entries lie in the one block it names
    np.testing.assert_array_equal(
        np.where(real, cell // sk.CELL_BLOCK, fb.reshape(-1, 1)),
        np.broadcast_to(np.asarray(fb).reshape(-1, 1), cell.shape))
    assert packed.shape[0] * sk.SUB == 2 * sk.capacity(5, 1300)


@pytest.mark.parametrize("nslots", [1, 2, 4, 8, 16, 32])
def test_interpreted_kernel_against_segment_sum_at_every_level_width(nslots):
    import jax.numpy as jnp

    from rabit_tpu.ops import sparse_hist_kernel as sk

    cells = 1300
    c, packed, fb, rng = _entries(cells=cells)
    n = c.shape[1]
    gh = rng.standard_normal((2, n)).astype(np.float32)
    slot = rng.integers(-1, nslots, n).astype(np.int32)  # rows at node -1
    rounded = np.asarray(jnp.asarray(gh).astype(jnp.bfloat16).astype(
        jnp.float32))
    widths = np.array([cells - 1 - (NBIN - 1)] + [1] * (NBIN - 1))
    flat = histogram.FlatBins(np.concatenate([[0], np.cumsum(widths - 1)]),
                              np.zeros(int((widths - 1).sum())), cells)
    assert flat.cells == cells
    got = np.asarray(histogram.level_hist_flat(
        (jnp.asarray(c), packed, fb), jnp.asarray(gh), jnp.asarray(slot),
        nslots, flat, use_pallas=True))
    want = np.zeros((nslots, flat.size, 2))
    for j in range(c.shape[0]):
        ok = (c[j] >= 0) & (slot >= 0)
        np.add.at(want, (slot[ok], c[j][ok]), rounded.T[ok].astype(
            np.float64))
    for s in range(nslots):
        want[s, flat.nbins] = rounded[:, slot == s].sum(axis=1,
                                                        dtype=np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-4)
    # and the XLA road, which adds the weights as they are
    xla = np.asarray(sk.hist_sparse_xla(
        jnp.asarray(c), jnp.asarray(rounded), jnp.asarray(slot), nslots,
        cells))
    np.testing.assert_allclose(got[:, :flat.nbins], xla[:, :flat.nbins],
                               rtol=1e-5, atol=2e-4)


# ---- (vi b) the dead tail of a tile: marked, and not worked -------------
TAIL_WIDTH, TAIL_CELLS = 5, 1300                      # 3 blocks, 6 steps
# entries a block of a tile and the steps they leave live (48
# sub-chunks a tile: 40 of ELL slots, 8 spare)
TILE_KINDS = {
    "empty": ((0, 0, 0), 0),            # every step dead: the join still runs
    "full": ((511, 513, 19456), 6),     # 1 + 2 + 38 sub-chunks: no step dead
    "edge": ((2048, 1024, 1024), 1),    # 8 sub-chunks: one step, to its edge
    "part": ((100, 3000, 700), 2),      # 1 + 6 + 2: the second step part dead
    "ragged": (None, None),             # drawn
}


def _tile_cells(kind, rng):
    """``(TAIL_WIDTH, ROW_TILE)`` cells of one tile with the kind's
    entries a block, at slots drawn over the whole tile."""
    from rabit_tpu.ops import sparse_hist_kernel as sk

    slots, counts = TAIL_WIDTH * sk.ROW_TILE, TILE_KINDS[kind][0]
    if counts is None:
        c = rng.integers(0, TAIL_CELLS, slots)
        c[rng.random(slots) < 0.35] = -1
        return c.reshape(TAIL_WIDTH, sk.ROW_TILE).astype(np.int32)
    c = np.full(slots, -1, np.int32)
    at = rng.permutation(slots)[:sum(counts)]
    c[at] = np.concatenate([
        rng.integers(b * sk.CELL_BLOCK,
                     min((b + 1) * sk.CELL_BLOCK, TAIL_CELLS), count)
        for b, count in enumerate(counts)])
    return c.reshape(TAIL_WIDTH, sk.ROW_TILE)


def _uneven_group(kinds, seed=0):
    import jax.numpy as jnp

    from rabit_tpu.ops import sparse_hist_kernel as sk

    rng = np.random.default_rng(seed)
    c = np.concatenate([_tile_cells(k, rng) for k in kinds], axis=1)
    packed, fb, real = sk.bucket_group(jnp.asarray(c), cells=TAIL_CELLS)
    assert int(real) == np.count_nonzero(c >= 0)
    return c, packed, fb, rng


@pytest.mark.parametrize("kinds,nslots", [
    (("ragged", "empty", "full", "edge", "part"), 1),
    (("ragged", "empty", "full", "edge", "part"), 16),
    (("full", "part", "empty"), 16),        # the call ends on a dead tile
    (("empty", "edge", "ragged"), 1),       # and starts on one
    (("empty", "empty"), 16),               # no step of the call is live
    (("part",), 1), (("edge",), 16), (("full",), 4)],
    ids=lambda v: "-".join(v) if isinstance(v, tuple) else str(v))
def test_kernel_skips_a_tiles_dead_steps_and_sums_as_before(kinds, nslots):
    """The kernel on the marked ``fb`` against itself on ``fb`` as the
    parent staged it (block 0 where no entry is: every step then runs,
    and the clamp never engages), bit for bit, and against
    ``segment_sum``."""
    import jax.numpy as jnp

    from rabit_tpu.ops import sparse_hist_kernel as sk

    c, packed, fb, rng = _uneven_group(kinds)
    n, nblk = c.shape[1], sk.num_blocks(TAIL_CELLS)
    live = np.asarray(fb)[:, 0].reshape(len(kinds), -1) < nblk
    for kind, steps in zip(kinds, live.sum(axis=1)):
        assert TILE_KINDS[kind][1] in (None, steps), kind
    assert int(sk.steps_worked(fb, cells=TAIL_CELLS)) == live.sum()
    gh = rng.standard_normal((2, n)).astype(np.float32)
    slot = rng.integers(-1, nslots, n).astype(np.int32)
    kw = dict(tiles=len(kinds), nslots=nslots, cells=TAIL_CELLS,
              interpret=True)
    got = np.asarray(sk.hist_sparse(packed, fb, jnp.asarray(gh),
                                    jnp.asarray(slot), **kw))
    unmarked = jnp.where(fb >= nblk, 0, fb)
    assert bool((unmarked != fb).any())     # no tile fills its capacity
    np.testing.assert_array_equal(got, np.asarray(sk.hist_sparse(
        packed, unmarked, jnp.asarray(gh), jnp.asarray(slot), **kw)))
    rounded = jnp.asarray(gh).astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose(got, np.asarray(sk.hist_sparse_xla(
        jnp.asarray(c), rounded, jnp.asarray(slot), nslots, TAIL_CELLS)),
        rtol=1e-5, atol=2e-4)
    if "empty" in kinds and len(set(kinds)) == 1:
        assert not got.any()


def test_the_marks_are_a_tiles_tail_and_the_counter_is_their_steps():
    """No live sub-chunk behind a dead one; a dead one holds padding
    alone; and ``stage_entries`` counts the slots of the steps that
    start live."""
    from rabit_tpu.obs import program
    from rabit_tpu.ops import sparse_hist_kernel as sk

    kinds = ("part", "empty", "full", "edge", "ragged")
    _c, packed, fb, _rng = _uneven_group(kinds)
    nblk = sk.num_blocks(TAIL_CELLS)
    dead = np.asarray(fb).reshape(len(kinds), -1) >= nblk
    assert (dead[:, 1:] >= dead[:, :-1]).all()        # a tail
    assert (np.asarray(fb)[dead.reshape(fb.shape)] == nblk).all()
    padding = (np.asarray(packed) >= sk.PAD).all(axis=1).reshape(dead.shape)
    assert (padding | ~dead).all() and dead[1].all() and not dead[0, :9].any()
    assert dead[0, 9:].all() and dead[3, 8:].all() and not dead[2, :41].any()

    rows, _labels = _rows(n=2 * sk.ROW_TILE + 100)
    flat = histogram.FlatBins(*histogram.sparse_cuts(rows, NBIN), NBIN)
    program.reset()
    _cells_t, packed, fb = histogram.stage_entries(rows, flat, True)
    stats = program.stats()
    live = np.asarray(fb)[:, 0] < sk.num_blocks(flat.cells)
    assert 0 < live.sum() < live.size                 # it engages here
    assert stats["gbdt.sparse.slots_worked"] == live.sum() * sk.STEP
    assert stats["gbdt.sparse.entries"] <= stats["gbdt.sparse.slots_worked"] \
        < stats["gbdt.sparse.slots"] == packed.size
    program.reset()
    histogram.stage_entries(rows, flat, False)        # XLA's road: every slot
    stats = program.stats()
    assert stats["gbdt.sparse.slots_worked"] == stats["gbdt.sparse.slots"]


@pytest.mark.parametrize("trees,depth", [(1, 0), (1, 3), (3, 2)])
def test_row_move_by_entries_against_the_formula(trees, depth):
    import jax.numpy as jnp

    rng = np.random.default_rng((trees, depth))
    n, width, w = 700, 5, 1 << depth
    widths = rng.integers(1, NBIN, 9)
    flat = histogram.FlatBins(np.concatenate([[0], np.cumsum(widths - 1)]),
                              np.zeros(int((widths - 1).sum())), NBIN)
    cols = np.stack([rng.choice(9, width, replace=False) for _ in range(n)])
    cells = flat.ptr[cols] + rng.integers(0, 99, (n, width)) % widths[cols]
    cells[rng.random((n, width)) < 0.4] = -1
    node = rng.integers(-3, w + 1, (trees, n)).astype(np.int32)
    tab = np.zeros((trees, w, 5), np.int32)
    for k in range(trees):
        for s in range(w):
            j = rng.integers(9)
            tab[k, s] = (flat.ptr[j], flat.ptr[j] + rng.integers(widths[j]),
                         flat.ptr[j + 1], rng.integers(2),
                         -5 - s if rng.random() < 0.3 else 0)
    want = node.copy()
    for k in range(trees):
        for i in range(n):
            s = node[k, i]
            if not 0 <= s < w:
                continue
            lo, cut, hi, dleft, leaf = tab[k, s]
            mine = [c for c in cells[i] if lo <= c < hi]
            left = (mine[0] <= cut) if mine else bool(dleft)
            want[k, i] = leaf if leaf < 0 else 2 * s + 1 - left
    lead = (slice(None),) if trees > 1 else (0,)
    got = boosting._move_entries(jnp.asarray(cells.T.astype(np.int32)),
                                 jnp.asarray(node[lead]),
                                 jnp.asarray(tab[lead]))
    np.testing.assert_array_equal(np.asarray(got), want[lead])


def test_sparse_job_counts_its_entries_and_its_payload(arm):
    from rabit_tpu import engine
    from rabit_tpu.obs import program

    rows, labels = _rows(n=1000)
    arm("device")
    program.reset()
    model = boosting.train(rows, labels, num_round=2, use_pallas=False, **KW)
    stats = engine.get_engine().path_stats
    flat = histogram.FlatBins(model.cut_ptr, model.cuts, NBIN)
    present = int(rows.present().sum())
    assert stats["gbdt.sparse.entries"] == present
    assert stats["gbdt.entries"] == 1000 * 40
    assert stats["gbdt.entries_missing"] == 1000 * 40 - present
    assert stats["gbdt.sparse.bins"] == flat.nbins < 40 * NBIN \
        == stats["gbdt.sparse.bins_rect"]
    assert stats["gbdt.sparse.slots"] >= present
    # a round's levels reduce 1 + 1 + 2 + 4 built slots of flat.size
    assert stats["gbdt.sparse.payload_bytes"] == 2 * 8 * flat.size * 8
    for span in ("stage.sparse_cuts", "stage.sparse_bin", "gbdt.level",
                 "gbdt.split", "gbdt.partition"):
        assert stats[span + ".n"] > 0, span


def test_the_chip_check_of_the_sparse_kernel_rehearsed(monkeypatch):
    """``tools/hist_kernel_check.py --cases sparse`` at a tiny shape with
    the kernel interpreted: every comparison it would make on the chip
    is made and passes, a timing line a width follows."""
    import importlib.util

    path = os.path.join(ROOT, "tools", "hist_kernel_check.py")
    spec = importlib.util.spec_from_file_location("hist_kernel_check", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(tool, "SLICE_ROWS", 4096)
    lines = []
    assert tool.run_sparse((3 * 4096, 8, 2, 300), 43, True, lines.append,
                           widths=(1, 16))
    checks = [ln for ln in lines if "check" in ln]
    assert [ln["slots"] for ln in checks] == [1, 16]
    assert all(ln["ok"] and ln["float64_ok"] and ln["skip_equal"]
               for ln in checks)
    assert lines[0]["sparse"] == "bucketed" and lines[0]["padding"] > 0
    # uneven tiles: the second holds no entry, the third every slot
    steps = lines[0]["steps_a_tile"]
    assert {0, steps} < set(lines[0]["live_steps_a_tile"])
    assert lines[0]["entries"] < lines[0]["worked"] < lines[0]["slots"]
    timings = [ln for ln in lines if "timing" in ln]
    assert [ln["channels"] for ln in timings] == [2, 32]
    assert all(ln["seconds_every_step"] > 0
               and ln["worked_share"] == lines[0]["worked_share"] < 1
               for ln in timings)
    assert lines[-1] == {"sparse_agrees": True}


def test_kernel_joins_the_tiles_sums_by_a_compensated_add():
    """A cell's sum of 2^24 from one tile and 1 from each of two more:
    a plain float32 add drops both ones; the kernel's compensated join
    of the tiles' sums keeps them (on the chip a drift of a few units in
    a hessian sum of 4e5 made children of no row weigh over
    ``min_child_weight``)."""
    import jax.numpy as jnp

    from rabit_tpu.ops import sparse_hist_kernel as sk

    n = 3 * sk.ROW_TILE
    c = np.full((1, n), -1, np.int32)
    c[0, :sk.ROW_TILE] = 5
    c[0, sk.ROW_TILE + 7] = c[0, 2 * sk.ROW_TILE + 9] = 5
    h = np.ones(n, np.float32)
    h[:sk.ROW_TILE] = 4096.0
    packed, fb, _real = sk.bucket_group(jnp.asarray(c), cells=40)
    got = np.asarray(sk.hist_sparse(
        packed, fb, jnp.asarray(np.stack([-h, h])), jnp.zeros(n, jnp.int32),
        tiles=3, nslots=1, cells=40, interpret=True))
    assert np.float32(2 ** 24) + np.float32(1) == np.float32(2 ** 24)
    np.testing.assert_array_equal(got[0, 5], [-(2 ** 24 + 2), 2 ** 24 + 2])
    assert not got[0, :5].any() and not got[0, 6:].any()

"""A boosting level decided in one float64 pass over its slots
(``boosting.decide_level``), and its tables and trees made from the
pass's arrays (``_level_tables``, ``_grow``), against the level decided
slot by slot as the loop did it before (``boosting_oracle``): bit for
bit."""
import numpy as np
import pytest

import boosting_oracle as oracle
from rabit_tpu.learn import boosting, histogram
from rabit_tpu.learn.boosting import TreeNode

NBIN = 32


def _rows(seed, slots, rows, totals=False, nbin=NBIN, scale=40.0):
    """A level's shortlist as fetched: ``(2, slots, rows [+ 1], nbin)``
    float32, grad and hess apart; with ``totals`` the last row holds the
    slot's sums (here: nobody absent) in bin 0."""
    rng = np.random.default_rng(seed)
    out = np.zeros((2, slots, rows + totals, nbin), np.float32)
    out[0, :, :rows] = rng.standard_normal((slots, rows, nbin)) * scale
    out[1, :, :rows] = rng.random((slots, rows, nbin)) * scale
    if totals:
        out[:, :, rows, 0] = out[:, :, 0].astype(np.float64).sum(axis=-1)
    return out


def _features(seed, slots, rows, f=54):
    rng = np.random.default_rng(seed)
    return np.sort(np.stack([rng.choice(f, rows, replace=False)
                             for _ in range(slots)]), axis=1).astype(np.int32)


def _no_totals():
    return dict(rows=_rows(1, 28, 8), features=_features(1, 28, 8))


def _totals_mass_either_side():
    """Absent rows with mass enough to matter, their gradient of either
    sign, so that both default directions win somewhere."""
    rows = _rows(2, 32, 8, totals=True)
    rng = np.random.default_rng(2)
    rows[0, :, -1, 0] += rng.standard_normal(32).astype(np.float32) * 400
    rows[1, :, -1, 0] += rng.random(32).astype(np.float32) * 300
    return dict(rows=rows, features=_features(2, 32, 8), has_missing=True,
                both_directions=True)


def _totals_under_the_floor():
    """Nobody absent: the totals are the bins' own sums to a float32
    rounding, a residue of either sign under MISSING_MASS_FLOOR."""
    rows = _rows(3, 16, 8, totals=True)
    rows[1, :, -1, 0] *= 1 + np.float32(4e-7) * np.where(
        np.arange(16) % 2, 1, -1).astype(np.float32)
    return dict(rows=rows, features=_features(3, 16, 8), has_missing=True)


def _unequal_widths():
    """Windows of a flat bin space: a row holds its column's bins and
    zeros after them; an indicator column has two."""
    rng = np.random.default_rng(4)
    rows = _rows(4, 24, 8, totals=True)
    widths = rng.choice([2, 2, 3, 7, NBIN], (24, 8))
    rows[:, :, :8][:, np.arange(NBIN) >= widths[..., None]] = 0.0
    rows[:, :, 8, 0] = rows[:, :, :8].astype(np.float64).sum(axis=-1).max(
        axis=-1) * 1.25
    return dict(rows=rows, features=_features(4, 24, 8, f=4227),
                widths=widths, has_missing=True)


def _every_feature_in_order():
    """A host engine's level: float64, ``(slots, f, nbin, 2)``, no
    shortlist and no ``features`` map."""
    return dict(hists=oracle.as_the_loop_held(
        np.moveaxis(_rows(5, 8, 40), 0, -1)))


def _every_feature_in_order_totals():
    return dict(hists=oracle.as_the_loop_held(
        np.moveaxis(_rows(6, 8, 40, totals=True), 0, -1)), has_missing=True)


def _slots_that_hold_no_node():
    """Zeros where a slot was never built, and what an earlier round
    left where the level keeps its array."""
    rows = _rows(7, 16, 8)
    rows[:, 3:9] = 0.0
    rows[:, 12] = _rows(70, 1, 8)[:, 0] * np.float32(1e-3)
    return dict(rows=rows, features=_features(7, 16, 8))


def _slots_that_hold_no_node_totals():
    rows = _rows(8, 16, 8, totals=True)
    rows[:, 2:11] = 0.0
    return dict(rows=rows, features=_features(8, 16, 8), has_missing=True)


def _equal_gains_in_two_features():
    """A feature row twice in a slot: the first wins."""
    rows = _rows(9, 12, 8)
    rows[:, :, 5] = rows[:, :, 2]
    rows[:, :, 7] = rows[:, :, 0]
    return dict(rows=rows, features=_features(9, 12, 8), first_of=(2, 5))


def _equal_gains_in_two_cuts():
    """An empty bin: the cuts before and after it split the rows the
    same way, the first wins."""
    rows = _rows(10, 12, 8)
    rows[:, :, :, 3::4] = 0.0
    return dict(rows=rows, features=_features(10, 12, 8))


def _every_candidate_barred():
    """No side reaches ``min_child_weight``: every slot stays a leaf."""
    return dict(rows=_rows(11, 8, 8, scale=1.0), min_child_weight=1e4,
                features=_features(11, 8, 8), all_leaves=True)


def _a_level_of_one_slot():
    return dict(rows=_rows(12, 1, 8), features=_features(12, 1, 8))


def _a_level_of_224_slots():
    return dict(rows=_rows(13, 224, 8, nbin=256),
                features=_features(13, 224, 8), chunks=4)


def _a_level_chunked_to_the_pool():
    """968 features a slot: megabytes, a slot a chunk, on threads."""
    return dict(hists=oracle.as_the_loop_held(np.moveaxis(
        _rows(14, 6, 968, totals=True, nbin=256), 0, -1)),
        has_missing=True, chunks=6, pooled=True)


CASES = [_no_totals, _totals_mass_either_side, _totals_under_the_floor,
         _unequal_widths, _every_feature_in_order,
         _every_feature_in_order_totals, _slots_that_hold_no_node,
         _slots_that_hold_no_node_totals, _equal_gains_in_two_features,
         _equal_gains_in_two_cuts, _every_candidate_barred,
         _a_level_of_one_slot, _a_level_of_224_slots,
         _a_level_chunked_to_the_pool]


def _equal_bits(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, what
    if want.dtype.kind == "f":
        got, want = got.view(np.int64), want.view(np.int64)
    np.testing.assert_array_equal(got, want, err_msg=what)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__.strip("_"))
def test_the_pass_decides_every_slot_as_slot_by_slot_did(case, monkeypatch):
    """Gain, feature, cut, default direction, the weight of a slot that
    stays a leaf, both children's of one that splits and the side built
    next: the oracle's to the last bit, in every slot."""
    case = case()
    has_missing = case.get("has_missing", False)
    lam, mcw = 1.0, case.get("min_child_weight", 1.0)
    hists = case["hists"] if "hists" in case \
        else np.moveaxis(case["rows"], 0, -1)       # as _fetch_shortlist's
    features, widths = case.get("features"), case.get("widths")
    chunks, scan = [], histogram.best_splits

    def seen(part, *a):
        import threading

        chunks.append(threading.current_thread().name)
        return scan(part, *a)

    monkeypatch.setattr(histogram, "best_splits", seen)
    before = np.array(hists)
    got = boosting.decide_level(hists, lam, mcw, has_missing, features,
                                widths)
    np.testing.assert_array_equal(hists, before)    # the level is not theirs
    want = oracle.decide_level(hists, lam, mcw, has_missing, features, widths)
    for name in ("gain", "feature", "cut", "default_left"):
        _equal_bits(getattr(got, name), getattr(want, name), name)
    split = want.gain > 1e-12
    _equal_bits(got.value[~split], want.value[~split], "leaf weight")
    for name in ("side", "left", "right"):
        _equal_bits(getattr(got, name)[split], getattr(want, name)[split],
                    name)
    assert len(chunks) == case.get("chunks", 1)
    assert all(name.startswith("gbdt-scan") for name in chunks) \
        == case.get("pooled", False)
    assert split.any() != case.get("all_leaves", False)
    if case.get("both_directions"):
        assert 0 < got.default_left[split].sum() < split.sum()
    if "first_of" in case:
        first, second = case["first_of"]
        row = np.array([list(f).index(v) for f, v in zip(
            features, got.feature)])
        assert (row != second).all() and (row == first).any()


@pytest.mark.parametrize("has_missing", [False, True], ids=["dense", "nan"])
def test_sums_of_the_chosen_row_add_up_in_bin_order(has_missing):
    """``_split``'s sums are ``hist[j].sum(axis=0)`` and ``hist[j, :t +
    1].sum(axis=0)`` of the loop of then, the missing mass folded in the
    same way: the leaf weights rest on their last ulp."""
    rng = np.random.default_rng(21)
    hist = (rng.standard_normal((64, 256, 2)) * 1e3).astype(
        np.float32).astype(np.float64)
    hist[:, :, 1] = np.abs(hist[:, :, 1])
    cut = rng.integers(0, 255, 64)
    left = rng.random(64) < 0.5
    total = None
    if has_missing:
        total = hist.sum(axis=1) * rng.choice([1.0, 1 + 1e-7, 1.5], (64, 1))
    got = np.stack(boosting._split(cut, left, hist, total), axis=1)
    for s in range(64):
        g_tot, h_tot = hist[s].sum(axis=0, dtype=np.float64)
        gl, hl = hist[s, :cut[s] + 1].sum(axis=0, dtype=np.float64)
        if has_missing:
            gm, hm = histogram.missing_mass(hist[s:s + 1], total[s])[0]
            g_tot, h_tot = g_tot + gm, h_tot + hm
            if left[s]:
                gl, hl = gl + gm, hl + hm
        _equal_bits(got[s], [g_tot, h_tot, gl, hl], s)


@pytest.mark.parametrize("has_missing", [False, True], ids=["dense", "nan"])
@pytest.mark.parametrize("num_class", [1, 7])
def test_tables_and_trees_from_the_arrays_are_the_loops_of_then(
        num_class, has_missing):
    """A round's levels, the trees' slots tree-major: the row-move
    tables, the slots built next, the next level's node ids, the leaf
    codes and the trees node for node are those of ``_split`` a slot and
    ``_route_round``, whatever stops where."""
    depth, lam = 5, 1.0
    new = [[TreeNode()] for _ in range(num_class)]
    old = [[TreeNode()] for _ in range(num_class)]
    slots, leaves = [0] * num_class, [[] for _ in new]
    old_slots, old_leaves = [0] * num_class, [[] for _ in old]
    for level in range(depth):
        nslots = num_class << level
        rows = _rows(100 * num_class + level, nslots, 8, totals=has_missing)
        # about a third of the slots hold too little to split
        light = np.random.default_rng(level).random(nslots) < 0.3
        rows[:, light] *= np.float32(1e-4)
        mcw = 1.0
        feats = _features(level, nslots, 8)
        hists = np.moveaxis(rows, 0, -1)
        found = boosting.decide_level(hists, lam, mcw, has_missing, feats)
        live = np.asarray(slots) >= 0
        split = live & (found.gain > 1e-12)
        tabs, build = boosting._level_tables(found, split, live, leaves)
        slots = boosting._grow(new, slots, leaves, found, split)
        want_tabs, want_build, old_slots, _ = oracle.grow_level(
            old, old_slots, old_leaves, oracle.as_the_loop_held(hists), lam,
            mcw, has_missing, feats)
        np.testing.assert_array_equal(tabs, want_tabs)
        assert tabs.dtype == want_tabs.dtype and tabs.shape == (
            num_class, 1 << level, 4)
        assert build == want_build and slots == old_slots
        assert leaves == old_leaves and new == old
        assert all(type(v) is type(w) for a, b in zip(new, old)
                   for m, n in zip(a, b)
                   for v, w in zip(vars(m).values(), vars(n).values()))
    stopped = sum(len(mine) for mine in leaves)
    assert 0 < stopped and any(nid >= 0 for nid in slots)
    np.testing.assert_array_equal(
        boosting._round_leaf_values(new, slots, leaves, depth),
        boosting._round_leaf_values(old, old_slots, old_leaves, depth))

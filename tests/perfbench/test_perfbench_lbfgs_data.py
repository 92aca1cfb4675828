"""The L-BFGS cell's generator (``perfbench/learners/lbfgs.py``): rows
of Criteo's shape from the seed, the stated skew and click rate, and an
objective that L-BFGS is still descending at its 40th iteration; the two
kernels' cost files by hand; the commits the adapter keeps."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness, readers  # noqa: E402

BENCH = os.path.join(ROOT, "perfbench")
CFG = harness.read_json(os.path.join(
    BENCH, "configs", "lbfgs-logreg-criteo-f1m.json"))
ROWS = 1 << 18


@pytest.fixture(scope="module")
def adapter():
    return harness.load_module(os.path.join(BENCH, "learners", "lbfgs.py"))


@pytest.fixture(scope="module")
def rows(adapter):
    return adapter.make_rows(2 ** 31 + 5, 0, ROWS, CFG["nnz_per_row"],
                             CFG["num_feature"], 4)


def test_rows_have_the_sources_shape(adapter, rows):
    cells, labels = rows
    assert cells.shape == (ROWS, 39) and cells.dtype == np.int32
    assert cells.min() >= 0 and cells.max() < 1_000_000
    assert set(np.unique(labels)) == {0.0, 1.0}


def test_rows_are_of_unit_length(adapter):
    data = adapter.Data({**CFG, "num_feature": 5000}, 3, 0, 1, 2, rows=64)
    assert data.values.shape == (64, 39) and data.values.dtype == np.float32
    np.testing.assert_allclose(
        (data.values.astype(np.float64) ** 2).sum(axis=1), 1.0, atol=1e-6)


def test_the_l1_is_the_configurations_a_row(adapter):
    """At the configuration's own size the job gets ``reg_L1`` itself;
    a test's few rows get the same penalty a row."""
    cfg = {**CFG, "num_feature": 5000, "rows_per_chip": 192}
    whole = adapter.Data(cfg, 3, 0, 1, 2)
    assert whole.n == 192 and str(whole.reg_l1) == str(CFG["reg_L1"])
    assert adapter.Data(cfg, 3, 0, 1, 2, rows=64).reg_l1 == CFG["reg_L1"] / 3
    full = CFG["reg_L1"] * CFG["rows_per_chip"] / CFG["rows_per_chip"]
    assert str(full) == str(CFG["reg_L1"])


def test_a_field_draws_from_its_own_vocabulary(adapter):
    cards = adapter.cardinalities(39)
    assert cards[0] == 4 and cards[-1] == 2 ** 22
    assert np.all(np.diff(cards) > 0)


def test_the_hot_cells_hold_the_stated_share(adapter, rows):
    share = adapter.hot_share(rows[0], CFG["num_feature"])
    assert share == pytest.approx(CFG["assumed"]["hot_cells_share"], abs=0.03)
    counts = np.bincount(rows[0].reshape(-1), minlength=CFG["num_feature"])
    # a few thousand cells take most updates, most cells take few
    assert np.median(counts) < 0.2 * counts.mean()


def test_about_a_quarter_are_positive(rows):
    assert rows[1].mean() == pytest.approx(0.26, abs=0.01)


def test_rows_depend_on_the_seed_and_the_shard_not_on_the_threads(adapter):
    a = adapter.make_rows(2 ** 31 + 5, 0, 40000, 39, 1_000_000, 1)
    b = adapter.make_rows(2 ** 31 + 5, 0, 40000, 39, 1_000_000, 5)
    c = adapter.make_rows(2 ** 31 + 6, 0, 40000, 39, 1_000_000, 5)
    d = adapter.make_rows(2 ** 31 + 5, 1, 40000, 39, 1_000_000, 5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert np.mean(a[0] != c[0]) > 0.3 and np.mean(a[0] != d[0]) > 0.3


def test_the_control_rounds_the_values_the_job_sees(adapter):
    cfg = {**CFG, "num_feature": 5000}
    sound = adapter.Data(cfg, 7, 0, 1, 2, rows=512)
    control = adapter.Data(cfg, 7, 0, 1, 2, rows=512, grid="bfloat16")
    assert sound.values_run is sound.values
    np.testing.assert_array_equal(control.values, sound.values)
    assert np.all(control.values_run == np.float32(0.16015625))
    np.testing.assert_array_equal(control.cells, sound.cells)


def test_lbfgs_is_still_descending_at_its_40th_iteration(adapter):
    """No window ends by convergence: at a small size (2^15 rows, 65,536
    features, the configuration's L1 a row: reg_L1 x rows /
    rows_per_chip, since the loss is a sum over rows) the 40th iteration
    still improves the objective by more than ``lbfgs_stop_tol`` x the
    first.  At the configuration's own L1 so few rows converge in under
    40 iterations: the penalty is 512 times heavier a row there."""
    import rabit_tpu
    from rabit_tpu.learn.linear import LinearObjFunction

    n, nf = 1 << 15, 1 << 16
    cells, labels = adapter.make_rows(11, 0, n, 39, nf, 4)
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    objs, commit = [], rabit_tpu.checkpoint

    def keep(glob, local=None):
        objs.append(float(glob["old_objval"]))
        commit(glob, local)

    rabit_tpu.checkpoint = keep
    try:
        obj = LinearObjFunction()
        obj.load_arrays(cells, np.full((n, 39), 39 ** -0.5, np.float32),
                        labels, nf)
        for name in ("lbfgs_stop_tol", "min_lbfgs_iter"):
            obj.set_param(name, str(CFG[name]))
        obj.set_param("reg_L1", str(CFG["reg_L1"] * n / CFG["rows_per_chip"]))
        obj.set_param("num_feature", str(nf))
        obj.set_param("max_lbfgs_iter", "40")
        obj.set_param("silent", "1")
        obj.lbfgs.run()
    finally:
        rabit_tpu.checkpoint = commit
        rabit_tpu.finalize()
    assert len(objs) == 40
    assert all(b <= a for a, b in zip(objs, objs[1:]))
    assert objs[-2] - objs[-1] > CFG["lbfgs_stop_tol"] * obj.lbfgs.init_objval


# ----------------------------------------------------------------------
SHAPE = {"rows": 16777216, "nnz_per_row": 39, "features": 1000000,
         "ops_dtype": "bfloat16"}


@pytest.mark.parametrize("kernel", ["lbfgs_margin", "lbfgs_grad"])
def test_kernel_cost_by_hand(kernel):
    c = harness.load_module(os.path.join(
        BENCH, "kernels", kernel + ".py")).cost(SHAPE)
    assert c["ops"] == 2 * 654311424 == 1308622848
    # 8 bytes a non-zero, 4 a row, 4 a weight cell
    assert c["bytes"] == 8 * 654311424 + 4 * 16777216 + 4 * 1000000 \
        == 5305600256
    peaks = harness.read_json(os.path.join(BENCH, "peaks.json"))[
        "TPU v5 lite"]
    assert readers.bound_of(BENCH, kernel, SHAPE, peaks) == "hbm"
    assert readers.floors(c, peaks)["hbm"] == pytest.approx(6.478e-3,
                                                            rel=1e-3)


def test_resident_bytes_are_those_of_the_staged_layout():
    from rabit_tpu.ops import sparse_linear_kernel as sk

    tiles = CFG["rows_per_chip"] // sk.ROW_TILE
    cap = sk.capacity(CFG["nnz_per_row"], CFG["num_feature"])
    slots = tiles * cap
    assert slots == 759169024
    assert CFG["resident_bytes_per_chip"] == (
        slots * 8 + slots // sk.STEP * sk.SUBS * 4
        + 3 * 4 * CFG["rows_per_chip"])
    assert CFG["resident_bytes_per_chip"] >= 0.25 * 16.9e9
    assert CFG["reduced"] == [] and CFG["rows_per_chip"] == 16 << 20


def test_the_adapter_rebuilds_the_history_of_any_commit(adapter):
    """It copies the first commit whole and then four rows a commit;
    held here against whole copies over 14 iterations of the solver
    itself, past the point (m = 10) where its history starts to roll."""
    import copy

    import rabit_tpu
    from rabit_tpu.learn.linear import LinearObjFunction

    n, nf = 400, 60
    cells, labels = adapter.make_rows(3, 0, n, 5, nf, 1)
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    kept, whole, commit = adapter.Commits(21, nf + 1), [], rabit_tpu.checkpoint

    def keep(glob, local=None):
        kept.keep(glob, local)
        whole.append(copy.deepcopy((glob, local)))
        commit(glob, local)

    rabit_tpu.checkpoint = keep
    try:
        obj = LinearObjFunction()
        obj.load_arrays(cells, np.full((n, 5), 5 ** -0.5, np.float32),
                        labels, nf)
        for name, value in (("reg_L1", 0.01), ("max_lbfgs_iter", 14),
                            ("lbfgs_stop_tol", 0), ("silent", 1)):
            obj.set_param(name, str(value))
        obj.lbfgs.run()
    finally:
        rabit_tpu.checkpoint = commit
        rabit_tpu.finalize()
    assert len(whole) == 14 and whole[-1][0]["offset"] > 0    # it rolled
    for k, (glob, local) in enumerate(whole):
        got = kept.at(k)
        np.testing.assert_array_equal(got["hist"], local["hist"])
        np.testing.assert_array_equal(got["weight"], glob["weight"])
        assert (got["offset"], got["num_useful"], got["old_objval"]) == (
            glob["offset"], local["num_useful"], glob["old_objval"])
    assert all(len(c["rows"]) <= 4 for c in kept.kept)
    first, last = kept.steps()
    assert first[1]["num_useful"] == 2 and first[0]["num_useful"] == 1
    np.testing.assert_array_equal(last[1]["hist"], whole[13][1]["hist"])
    np.testing.assert_array_equal(last[0]["hist"], whole[12][1]["hist"])
    assert kept.objvals == [g["old_objval"] for g, _l in whole]
    # two commits are one step, one commit none
    two = adapter.Commits(21, nf + 1)
    for glob, local in whole[:2]:
        two.keep(glob, local)
    assert len(two.steps()) == 1
    one = adapter.Commits(21, nf + 1)
    one.keep(*whole[0])
    assert one.steps() == []

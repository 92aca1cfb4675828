"""Boosting tests: single-worker learning + distributed equivalence."""
import sys

import numpy as np
import pytest

from rabit_tpu.learn import boosting


def _xor_data(n=600, seed=0):
    """Non-linearly separable data a single linear model cannot fit."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, (n, 2)).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(np.float32)
    return X, y


def test_boosting_learns_xor(empty_engine):
    X, y = _xor_data()
    model = boosting.train(X, y, num_round=20, max_depth=3, nbin=16)
    p = model.predict(X)
    acc = ((p > 0.5) == (y > 0.5)).mean()
    assert acc > 0.95, acc
    assert len(model.trees) == 20


def test_boosting_squared_loss(empty_engine):
    rng = np.random.default_rng(1)
    X = rng.uniform(-1, 1, (500, 3)).astype(np.float32)
    y = (2.0 * X[:, 0] - X[:, 1]).astype(np.float32)
    model = boosting.train(X, y, num_round=30, max_depth=3, nbin=32,
                           loss="squared", learning_rate=0.3)
    pred = model.predict(X)
    mse = float(np.mean((pred - y) ** 2))
    assert mse < 0.05, mse


def test_boosting_resume(empty_engine):
    """Training 10 rounds straight == 5 rounds, 'crash', resume to 10."""
    import rabit_tpu

    X, y = _xor_data()
    ref = boosting.train(X, y, num_round=10, max_depth=2, nbin=16)
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    boosting.train(X, y, num_round=5, max_depth=2, nbin=16)
    # same process keeps the in-memory checkpoint (world=1 empty engine)
    resumed = boosting.train(X, y, num_round=10, max_depth=2, nbin=16)
    assert len(resumed.trees) == 10
    np.testing.assert_allclose(resumed.predict(X), ref.predict(X),
                               rtol=1e-5, atol=1e-5)


def test_boosting_distributed_with_fault(tmp_path, native_lib):
    """Rank 1 dies mid-training (version 2); the restart resumes from
    the round-2 checkpoint and the job still converges with identical
    models everywhere."""
    from rabit_tpu.tracker.launch_local import launch

    X, y = _xor_data(n=400)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    code = launch(2, [sys.executable, "tests/workers/boosting_dist.py",
                      str(tmp_path)],
                  extra_env={"RABIT_ENGINE": "mock",
                             "RABIT_MOCK": "1,2,0,0"})
    assert code == 0


def test_boosting_distributed_xla_engine(tmp_path):
    """Boosting over the XLA engine: the per-level histogram allreduce
    rides the device data plane (jax.Array through the engine) while
    cuts/checkpoints use the control plane."""
    from rabit_tpu.tracker.launch_local import launch

    X, y = _xor_data(n=400)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    code = launch(2, [sys.executable, "tests/workers/boosting_dist.py",
                      str(tmp_path)],
                  extra_env={"RABIT_ENGINE": "xla"})
    assert code == 0


def test_boosting_distributed(tmp_path):
    """2-worker sharded training: identical models on every rank (all
    split decisions ride the allreduced histogram) and the ensemble
    still learns the function."""
    from rabit_tpu.tracker.launch_local import launch

    X, y = _xor_data(n=400)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    code = launch(2, [sys.executable, "tests/workers/boosting_dist.py",
                      str(tmp_path)])
    assert code == 0


def _missing_xor_data(n=600, seed=0, frac=0.25):
    """XOR data with a fraction of feature-0 entries knocked out to
    NaN: a learner that routes missing rows well keeps most accuracy."""
    X, y = _xor_data(n=n, seed=seed)
    rng = np.random.default_rng(seed + 99)
    X[rng.random(n) < frac, 0] = np.nan
    return X, y


def test_boosting_missing_values(empty_engine):
    """NaN features ride the dedicated missing bin; every split learns
    a default direction (XGBoost's sparsity-aware splits) and predict
    routes NaN rows the same way."""
    X, y = _missing_xor_data()
    model = boosting.train(X, y, num_round=25, max_depth=3, nbin=16)
    # some split actually chose to send missing rows RIGHT — the
    # direction was learned, not hardcoded
    directions = {node.default_left for tree in model.trees
                  for node in tree if node.feature >= 0}
    assert directions == {True, False}, directions
    p = model.predict(X)
    acc = ((p > 0.5) == (y > 0.5)).mean()
    # complete rows must be fit well; NaN rows on feature 0 are
    # inherently ambiguous for XOR, so measure on the complete subset
    complete = ~np.isnan(X[:, 0])
    acc_c = ((p[complete] > 0.5) == (y[complete] > 0.5)).mean()
    assert acc_c > 0.93, (acc, acc_c)


def test_boosting_subsample(empty_engine):
    """Stochastic GBDT: subsample<1 still learns XOR and resuming from
    a mid-run checkpoint replays the exact per-round sample (bit-equal
    final model)."""
    import rabit_tpu

    X, y = _xor_data()
    ref = boosting.train(X, y, num_round=20, max_depth=3, nbin=16,
                         subsample=0.7, seed=5)
    acc = ((ref.predict(X) > 0.5) == (y > 0.5)).mean()
    assert acc > 0.93, acc
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    boosting.train(X, y, num_round=9, max_depth=3, nbin=16,
                   subsample=0.7, seed=5)
    resumed = boosting.train(X, y, num_round=20, max_depth=3, nbin=16,
                             subsample=0.7, seed=5)
    np.testing.assert_allclose(resumed.predict(X), ref.predict(X),
                               rtol=1e-6)


def test_boosting_distributed_world4_vs_oracle(tmp_path, empty_engine):
    """World-4 sharded training with missing values + row subsampling
    must match a single-process oracle's quality (VERDICT r4 #8): the
    distributed ensemble's accuracy stays within 3 points of a
    full-data single-process model on the same data."""
    from rabit_tpu.tracker.launch_local import launch

    X, y = _missing_xor_data(n=800, frac=0.2)
    oracle = boosting.train(X, y, num_round=15, max_depth=3, nbin=16)
    oracle_acc = ((oracle.predict(X) > 0.5) == (y > 0.5)).mean()
    import rabit_tpu

    rabit_tpu.finalize()
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    code = launch(4, [sys.executable, "tests/workers/boosting_dist.py",
                      str(tmp_path)],
                  extra_env={"BOOST_SUBSAMPLE": "0.8",
                             "BOOST_MIN_ACC": str(oracle_acc - 0.03)})
    assert code == 0


# ----------------------------------------------------------------------
# the device arm (rows resident on the device) against the host arm
# ----------------------------------------------------------------------
def _tabular(n=3000, f=5, seed=0, missing=False):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 0] * X[:, 1] + 0.5 * X[:, 2]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    if missing:
        X[rng.random(n) < 0.2, 1] = np.nan
    return X, y


def _structure(model):
    return [[(n.feature, n.bin_threshold, n.default_left, n.left, n.right)
             for n in tree] for tree in model.trees]


def _weights(model):
    return np.array([n.value for tree in model.trees for n in tree])


@pytest.fixture
def arm(monkeypatch):
    """Returns a switch: ``arm("device")`` makes ``train`` take the arm
    it takes on an accelerator (steered here, no option of the program:
    ``on_tpu`` as the boosting module sees it)."""
    import rabit_tpu

    def switch(which: str) -> None:
        monkeypatch.setattr(boosting, "on_tpu", lambda: which == "device")
        if rabit_tpu.initialized():
            rabit_tpu.finalize()
        rabit_tpu.init(rabit_engine="empty")

    yield switch
    if rabit_tpu.initialized():
        rabit_tpu.finalize()


@pytest.mark.parametrize("missing", [False, True], ids=["dense", "nan"])
@pytest.mark.parametrize("kw", [
    {"loss": "logistic"}, {"loss": "squared"},
    {"loss": "logistic", "subsample": 0.7, "seed": 3},
    {"loss": "logistic", "use_pallas": True, "compute_dtype": "float32"},
], ids=["logistic", "squared", "subsample", "kernel-f32"])
def test_device_arm_builds_the_host_arms_forest(arm, kw, missing):
    X, y = _tabular(missing=missing)
    kw = {"use_pallas": False, **kw}
    models = []
    for which in ("host", "device"):
        arm(which)
        models.append(boosting.train(X, y, num_round=4, max_depth=4,
                                     nbin=16, **kw))
    host, device = models
    assert _structure(host) == _structure(device)
    np.testing.assert_allclose(_weights(device), _weights(host),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(device.predict(X), host.predict(X), atol=1e-5)


@pytest.mark.parametrize("which", ["host", "device"])
def test_resumed_job_commits_the_same_later_trees(arm, which):
    X, y = _tabular(n=1500)
    kw = dict(max_depth=3, nbin=16, use_pallas=False)
    arm(which)
    straight = boosting.train(X, y, num_round=6, **kw)
    arm(which)
    boosting.train(X, y, num_round=3, **kw)
    # the same process keeps the committed forest (world 1, empty engine)
    resumed = boosting.train(X, y, num_round=6, **kw)
    assert _structure(resumed) == _structure(straight)
    np.testing.assert_allclose(_weights(resumed), _weights(straight),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("min_child_weight", [1e-3, 60.0],
                         ids=["full-trees", "stops-early"])
def test_no_program_is_built_after_the_first_round(arm, monkeypatch,
                                                   min_child_weight):
    """Static shapes: 2^depth node slots a level, so a tree that stops
    splitting a node early runs the programs the first round ran."""
    import rabit_tpu
    from rabit_tpu.utils import compile_cache

    X, y = _tabular(n=1200)
    arm("device")
    clock = compile_cache.count_compiles()
    commit, asked = rabit_tpu.checkpoint, []

    def counting(model):
        took = clock.take()
        asked.append(took["misses"] + took["hits"])
        return commit(model)

    monkeypatch.setattr(rabit_tpu, "checkpoint", counting)
    model = boosting.train(X, y, num_round=5, max_depth=4, nbin=16,
                           min_child_weight=min_child_weight,
                           use_pallas=False)
    sizes = {len(t) for t in model.trees}
    assert (max(sizes) < 31) == (min_child_weight > 1)
    assert asked[1:] == [0, 0, 0, 0], asked


@pytest.mark.parametrize("which", ["host", "device"])
def test_has_missing_mismatch_is_named(arm, which):
    """ADVICE r5: a forest committed with has_missing False, resumed on
    rows that hold a NaN, fails with the cause and not with an index
    error deep in a kernel."""
    X, y = _tabular(n=800)
    arm(which)
    boosting.train(X, y, num_round=1, max_depth=2, nbin=16,
                   use_pallas=False)
    X[5, 1] = np.nan
    with pytest.raises(Exception, match="has_missing"):
        boosting.train(X, y, num_round=2, max_depth=2, nbin=16,
                       use_pallas=False)


@pytest.mark.parametrize("which", ["host", "device"])
def test_depth_limit_leaf_weights_equal_a_pass_over_the_rows(arm, which):
    """Leaf sums come from the last level's histogram (the children's
    cumulative sums); a pass over the rows gives the same weights."""
    X, y = _tabular(n=2000)
    arm(which)
    model = boosting.train(X, y, num_round=1, max_depth=3, nbin=16,
                           reg_lambda=1.0, use_pallas=False)
    tree = model.trees[0]
    out = model._tree_margin(tree, boosting.apply_cuts(X, model.cuts))
    grad, hess = 0.5 - y, np.full_like(y, 0.25)      # margin 0, logistic
    leaves = [n for n in tree if n.feature < 0]
    assert len(leaves) == 8
    for leaf in leaves:
        rows = out == np.float32(leaf.value)
        want = -grad[rows].sum(dtype=np.float64) / (
            hess[rows].sum(dtype=np.float64) + 1.0)
        assert rows.any() and leaf.value == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("which,clean", [("device", True), ("host", False)])
def test_no_host_array_of_length_n_inside_the_loop(arm, monkeypatch, which,
                                                   clean):
    """On the device arm nothing of a row's length is made or read on
    the host between the first ``learn.step`` and the last commit; the
    host arm, which does little else, shows that the probe sees it."""
    from rabit_tpu.obs import program

    n = 20000
    X, y = _tabular(n=n, f=3)
    arm(which)
    state = {"in_step": False, "largest": 0}

    class Span(program.span):
        def __enter__(self):
            if self.name == "learn.step":
                state["in_step"] = True
            return super().__enter__()

    def probe(name):
        fn = getattr(np, name)

        def seen(*a, **kw):
            out = fn(*a, **kw)
            if state["in_step"]:
                state["largest"] = max(state["largest"], np.size(out))
            return out
        monkeypatch.setattr(np, name, seen)

    monkeypatch.setattr(boosting.program, "span", Span)
    for name in ("asarray", "array", "zeros", "empty", "where", "arange",
                 "full", "ones_like", "exp", "concatenate"):
        probe(name)
    boosting.train(X, y, num_round=3, max_depth=3, nbin=8, use_pallas=False)
    assert state["in_step"]
    assert (state["largest"] < n) == clean, state

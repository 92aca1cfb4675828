"""Boosting tests: single-worker learning + distributed equivalence."""
import sys

import numpy as np
import pytest

import boosting_oracle as oracle
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


# ----------------------------------------------------------------------
# histogram subtraction: a level builds one child of every split node
# and has the sibling as parent minus built
# ----------------------------------------------------------------------
def _spy_on_loop(monkeypatch):
    """Records what ``train``'s level loop asks and uses: every
    ``shard.level(build)`` call as ``(build, local, order)`` and every
    node a level decided as ``(round, node id, histogram, child to
    build)`` (None: it stays a leaf), from ``decide_level``'s histograms
    and the slots ``_grow`` writes."""
    levels, splits, rounds, held = [], [], [-1], []
    decide, grow = boosting.decide_level, boosting._grow

    def seen_decide(hists, *a):
        held[:] = [np.array(hists, np.float64)]
        return decide(hists, *a)

    def seen_grow(trees, slots, leaves, found, split):
        if len(trees[0]) == 1:
            rounds[0] += 1
        splits.extend(
            (rounds[0], nid, held[0][s],
             int(found.side[s]) if split[s] else None)
            for s, nid in enumerate(slots) if nid >= 0)
        return grow(trees, slots, leaves, found, split)

    def seen_level(level):
        def wrapper(self, build, depth):
            local, order, calls = level(self, build, depth)
            levels.append((list(build), np.asarray(local), list(order)))
            return local, order, calls
        return wrapper

    monkeypatch.setattr(boosting, "decide_level", seen_decide)
    monkeypatch.setattr(boosting, "_grow", seen_grow)
    for cls in (boosting._HostShard, boosting._DeviceShard):
        monkeypatch.setattr(cls, "level", seen_level(cls.level))
    return levels, splits


def _node_of_rows(tree, bins, missing_bin):
    """The rows that pass through each node of a tree, id -> mask, and
    each node's depth."""
    rows, depth = {0: np.ones(bins.shape[0], bool)}, {0: 0}
    for nid, node in enumerate(tree):
        if node.feature < 0:
            continue
        b = bins[:, node.feature]
        left = np.where(b == missing_bin, node.default_left,
                        b <= node.bin_threshold)
        rows[node.left] = rows[nid] & left
        rows[node.right] = rows[nid] & ~left
        depth[node.left] = depth[node.right] = depth[nid] + 1
    return rows, depth


@pytest.mark.parametrize("subsample", [1.0, 0.5], ids=["all", "half"])
@pytest.mark.parametrize("missing", [False, True], ids=["dense", "nan"])
@pytest.mark.parametrize("which", ["host", "device"])
def test_every_histogram_the_loop_used_equals_a_pass_over_the_nodes_rows(
        arm, monkeypatch, which, missing, subsample):
    """Built or derived, the histogram a node was split on is that of
    its own rows.  The direct pass is the float32 XLA builder; a derived
    histogram carries a float32 rounding of each ancestor's cell, so the
    tolerance is a few ulp of the root's largest cell."""
    from rabit_tpu.learn import histogram

    X, y = _tabular(missing=missing)
    kw = dict(num_round=2, max_depth=4, nbin=16, subsample=subsample, seed=3,
              use_pallas=False)
    other = "device" if which == "host" else "host"
    arm(other)
    twin = boosting.train(X, y, **kw)
    arm(which)
    _levels, splits = _spy_on_loop(monkeypatch)
    model = boosting.train(X, y, **kw)
    assert _structure(model) == _structure(twin)

    bins = boosting.apply_cuts(X, model.cuts)
    missing_bin = model.cuts.shape[1] + 1
    assert model.has_missing == missing
    checked = 0
    for r, tree in enumerate(model.trees):
        before = boosting.BoostedModel(
            cuts=model.cuts, trees=model.trees[:r],
            learning_rate=model.learning_rate)
        grad, hess = boosting._grad_hess(before.margin(bins), y, "logistic")
        if subsample < 1.0:
            keep = boosting._keep_rows(3, r, len(y), subsample)
            grad, hess = grad * keep, hess * keep
        rows, depth = _node_of_rows(tree, bins, missing_bin)
        used = {nid: hist for rnd, nid, hist, _ in splits if rnd == r}
        # every node above the depth limit was scanned, once
        assert sorted(used) == sorted(nid for nid in rows if depth[nid] < 4)
        tol = 8 * np.finfo(np.float32).eps * np.abs(used[0]).max()
        for nid, hist in used.items():
            # with missing values the node's totals ride as one more
            # feature row, and an absent entry is in no bin
            want = np.asarray(histogram.build_level_local(
                bins, grad, hess, rows[nid].astype(np.int32), [1], 16,
                use_pallas=False, totals=missing))[0]
            assert want.shape == (X.shape[1] + missing, 16, 2)
            np.testing.assert_allclose(hist, want, rtol=0, atol=tol)
            if missing:
                np.testing.assert_allclose(
                    hist[-1, 0], [grad[rows[nid]].sum(dtype=np.float64),
                                  hess[rows[nid]].sum(dtype=np.float64)],
                    rtol=0, atol=tol)
                assert not hist[-1, 1:].any()
            checked += 1
    assert checked >= 2 * 15


@pytest.mark.parametrize("which", ["host", "device"])
def test_unsplit_parent_gives_no_built_slot_and_no_derived_histogram(
        arm, monkeypatch, which):
    """A tree that stops early: the children of a node that stayed a
    leaf are neither built nor derived, and its rows are in no slot."""
    from rabit_tpu.obs import program

    X, y = _tabular(n=2000)
    arm(which)
    levels, splits = _spy_on_loop(monkeypatch)
    before = program.stats()
    model = boosting.train(X, y, num_round=1, max_depth=4, nbin=16,
                           min_child_weight=60.0, use_pallas=False)
    counted = {k: program.stats().get(k, 0) - before.get(k, 0)
               for k in ("gbdt.hists_derived", "gbdt.nodes_split",
                         "gbdt.channels", "gbdt.channels_live")}
    tree = model.trees[0]
    assert len(tree) < 31 and 2 <= len(levels) <= 4
    scanned = iter(splits)
    split_above = [True]            # the root stands for its own parent
    for depth, (build, local, order) in enumerate(levels):
        # one build slot a node of the level above, taken iff it split
        assert [s >= 0 for s in build] == split_above
        assert all(s >> 1 == p for p, s in enumerate(build) if s >= 0)
        for pos, s in enumerate(order):
            if s < 0:
                assert not local[pos].any()     # no row in the slot
        split_above = [False] * (1 << depth)
        for s in sorted({t for s in build if s >= 0
                         for t in ((s, s ^ 1) if depth else (s,))}):
            split_above[s] = next(scanned)[3] is not None
    assert next(scanned, None) is None
    assert any(s < 0 for build, _, _ in levels for s in build)
    built = sum(s >= 0 for build, _, _ in levels for s in build)
    assert counted["gbdt.hists_derived"] == built - 1
    assert counted["gbdt.channels_live"] == 2 * built
    assert counted["gbdt.nodes_split"] == sum(n.feature >= 0 for n in tree)
    if which == "device":
        assert counted["gbdt.channels"] == 2 * sum(
            len(build) for build, _, _ in levels)


@pytest.mark.parametrize("has_missing", [False, True], ids=["dense", "nan"])
def test_empty_side_of_a_derived_histogram_does_not_win_the_argmax(
        has_missing):
    """Parent minus built leaves a few ulp of either sign where no row
    fell.  Feature 0's last bin is such a cell, its hessian reading
    -lambda: the cut before it has ``hr + lambda = 0`` and an unbounded
    gain, which took the argmax and then failed ``min_child_weight``,
    so that the node stayed a leaf beside feature 1's good split."""
    from rabit_tpu.learn import histogram

    lam = 1.0
    hist = np.zeros((2, 4, 2))
    hist[0, :3] = [(-3.0, 10.0), (1.0, 10.0), (2.0, 11.0)]
    hist[0, 3] = (1e-7, -lam)
    hist[1, :4] = [(-6.0, 8.0), (-5.0, 7.0), (6.0, 8.0), (5.0 + 1e-7, 7.0)]
    # nobody is absent: the node's totals are either feature's bins
    total = hist[0].sum(axis=0) if has_missing else None
    unmasked = histogram.split_candidates(hist, lam, total=total)[0]
    assert not unmasked[0, 2] < np.inf           # the trap
    gain, _ = histogram.split_candidates(hist, lam, 1e-3, total)
    assert gain[0, 2] == -np.inf and np.isfinite(gain[1]).all()
    if has_missing:                  # as the loop hands it to the pass
        hist = np.concatenate([hist, np.zeros((1, 4, 2))])
        hist[-1, 0] = total
    found = boosting.decide_level(hist[None], lam, 1e-3, has_missing)
    assert (found.feature[0], found.cut[0]) == (1, 1)
    # hl == hr: left is built
    assert found.gain[0] > 1e-12 and found.side[0] == 0
    # a node with no eligible candidate at all stays a leaf
    assert boosting.decide_level(hist[None], lam, 16.0,
                                 has_missing).gain[0] <= 1e-12


@pytest.mark.parametrize("which", ["host", "device"])
def test_counters_of_one_full_depth_3_round(arm, which):
    """Built slots 1 + 1 + 2, each but the root's with a derived
    sibling; two channels a built slot; 1 + 2 + 4 nodes split."""
    from rabit_tpu.obs import program

    X, y = _tabular(n=2000)
    arm(which)
    before = program.stats()
    model = boosting.train(X, y, num_round=1, max_depth=3, nbin=16,
                           use_pallas=False)
    assert len(model.trees[0]) == 15
    after = program.stats()
    assert [after.get(k, 0) - before.get(k, 0) for k in (
        "gbdt.hists_derived", "gbdt.channels", "gbdt.channels_live",
        "gbdt.nodes_split")] == [3, 8, 8, 7]


@pytest.mark.parametrize("which,use_pallas,crossing,chunked,calls,lane", [
    ("device", True, None, 0, 6, 2), ("device", True, 1 << 30, 1, 7, 0),
    ("host", True, None, 0, 6, 2),
    ("device", False, None, 0, 0, 0), ("host", False, None, 0, 0, 0)],
    ids=["device-kernel", "device-kernel-two-level", "host-kernel",
         "device-xla", "host-xla"])
def test_chunked_levels_of_one_depth_6_round(arm, monkeypatch, which,
                                             use_pallas, crossing, chunked,
                                             calls, lane):
    """Of a depth-6 tree's six levels the last two (8 and 16 build
    slots, 16 and 32 channels) are at or over the crossing of the
    kernel's rule and one lane-wide call each; with the two-level body
    alone the last is wider than its widest worthwhile call and is
    chunked.  Only an arm that runs the kernel issues calls at all."""
    from rabit_tpu.obs import program
    from rabit_tpu.ops import histogram_kernel as hk

    if crossing:
        monkeypatch.setattr(hk, "_LANE_CROSSING", crossing)
    X, y = _tabular(n=2000)
    arm(which)
    before = program.stats()
    model = boosting.train(X, y, num_round=1, max_depth=6, nbin=16,
                           use_pallas=use_pallas, compute_dtype="float32")
    assert len(model.trees[0]) > 63                    # reaches depth 6
    after = program.stats()
    assert [after.get(k, 0) - before.get(k, 0) for k in (
        "gbdt.levels", "gbdt.levels_chunked", "gbdt.kernel_calls",
        "gbdt.kernel_calls_lane")] == [6, chunked, calls, lane]


def test_device_arm_trains_a_forest_of_levels_wider_than_a_kernel_call(arm):
    """``max_depth`` 8: the deepest level builds 64 slots, 128 channels,
    eight kernel calls in one program (with one call a level the device
    arm's programs could not be built: the kernel takes 64 channels).
    Both arms run the kernel and grow the same forest."""
    X, y = _tabular()
    kw = dict(num_round=2, max_depth=8, nbin=16, use_pallas=True,
              compute_dtype="float32")
    models = []
    for which in ("host", "device"):
        arm(which)
        models.append(boosting.train(X, y, **kw))
    host, device = models
    assert max(len(t) for t in device.trees) > 255     # deeper than 7
    assert _structure(host) == _structure(device)
    np.testing.assert_allclose(_weights(device), _weights(host),
                               rtol=1e-4, atol=1e-5)


def test_built_child_is_the_lighter_and_the_same_on_every_rank(tmp_path):
    """World 4, NaN features and a row sample: every rank builds the
    same child of every split node, the one whose reduced hessian sum
    is the smaller (the worker checks both)."""
    from rabit_tpu.tracker.launch_local import launch

    X, y = _missing_xor_data(n=800, frac=0.2)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    code = launch(4, [sys.executable, "tests/workers/boosting_dist.py",
                      str(tmp_path)],
                  extra_env={"BOOST_SUBSAMPLE": "0.8", "BOOST_MIN_ACC": "0.5",
                             "BOOST_CHECK_BUILT": "1"})
    assert code == 0


# ----------------------------------------------------------------------
# rows absent station by station: the forest is the one the plain
# reference of the benchmark would grow
# ----------------------------------------------------------------------
def _station_rows(n=4000, seed=5):
    """Nine measurements at three stations; a part skips a station
    whole.  The label reads two measurements and whether the part went
    through the rarest station, so the default direction carries
    signal."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, (n, 9)).astype(np.float32)
    X[:, 4] = np.round(X[:, 4] * 2) / 2               # five levels
    went = rng.random((n, 3)) < np.array([0.9, 0.5, 0.15])
    X[~went[:, np.repeat(np.arange(3), 3)]] = np.nan
    z = np.nan_to_num(X)
    logit = 2.5 * z[:, 0] * z[:, 3] + 1.5 * z[:, 4] + 2.0 * went[:, 2] - 0.8
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y


@pytest.mark.parametrize("which,kw", [
    ("host", {"use_pallas": False}),
    ("device", {"use_pallas": False}),
    ("device", {"use_pallas": True, "compute_dtype": "float32"}),
], ids=["host-arm", "xla-arm", "device-arm-kernel-interpreted"])
def test_station_wise_absent_rows_commit_the_references_forest(arm, which,
                                                               kw):
    """``perfbench/reference/gbdt_missing.py`` (float64 numpy, both
    default directions scored, independent of ``learn/``) replays every
    committed tree: each split is its best (feature, cut, direction) to
    1e-6 of the gain, no leaf above the depth limit would be split, and
    each leaf weight is its sums' to 1e-5 of the leaf's sum of |g|."""
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench.reference import gbdt_missing as refm

    X, y = _station_rows()
    arm(which)
    model = boosting.train(X, y, num_round=3, max_depth=4, nbin=32,
                           min_child_weight=1.0, **kw)
    assert model.has_missing
    np.testing.assert_array_equal(model.cuts, refm.quantile_cuts(X, 32))
    nodes = max(len(t) for t in model.trees)
    f_int = np.full((3, nodes, 5), -2, np.int32)
    f_val = np.zeros((3, nodes), np.float32)
    for t, tree in enumerate(model.trees):
        for i, n in enumerate(tree):
            f_int[t, i] = (n.feature, n.bin_threshold, n.default_left,
                           n.left, n.right)
            f_val[t, i] = n.value
    got = refm.replay(X, y, model.cuts, f_int, f_val, [0, 1, 2], 32, 4, 0.3,
                      1.0, 1.0, "float32")
    assert got["splits"] >= 30 and got["leaves"] == got["splits"] + 3
    assert got["split_regret"] < 1e-6
    assert got["unsplit_above_limit"] == 0
    assert got["leaf_sum_rel_err"] < 1e-5
    # the direction is learned, and goes both ways
    assert 0 < got["default_left"] < got["splits"]
    # absent rows go the committed way in predict too
    bins = boosting.apply_cuts(X, model.cuts)
    assert (bins == 32).mean() > 0.4
    p = model.predict(X)
    assert np.isfinite(p).all() and ((p > 0.5) == (y > 0.5)).mean() > 0.7


@pytest.mark.parametrize("which", ["host", "device"])
def test_counters_of_the_missing_path(arm, which):
    """``gbdt.kernel_calls`` a level (none on the XLA path, one a level
    of the kernel here), ``gbdt.splits_default_left`` beside
    ``gbdt.nodes_split``, the entries staged and the absent among them,
    and the bytes of histograms fetched: a slot is f + 1 feature rows
    (the node's totals ride as one) of nbin (grad, hess) float32, and on
    the device arm its shortlist's rows and no other."""
    from rabit_tpu.learn import histogram
    from rabit_tpu.obs import program

    X, y = _station_rows(n=1500)
    for use_pallas, calls in ((False, 0), (True, 3)):
        arm(which)
        before = program.stats()
        model = boosting.train(X, y, num_round=1, max_depth=3, nbin=16,
                               use_pallas=use_pallas,
                               compute_dtype="float32")
        after = program.stats()
        got = {k: after.get(k, 0) - before.get(k, 0) for k in (
            "gbdt.kernel_calls", "gbdt.levels", "gbdt.nodes_split",
            "gbdt.splits_default_left", "gbdt.entries",
            "gbdt.entries_missing", "gbdt.hist_bytes_fetched")}
        tree = model.trees[0]
        assert got["gbdt.levels"] == 3 and got["gbdt.kernel_calls"] == calls
        assert got["gbdt.nodes_split"] == 7
        assert got["gbdt.splits_default_left"] == sum(
            n.default_left for n in tree if n.feature >= 0)
        assert 0 < got["gbdt.splits_default_left"] < 7
        assert got["gbdt.entries"] == X.size
        assert got["gbdt.entries_missing"] == np.count_nonzero(np.isnan(X))
        if which == "host":         # built slots 1 + 1 + 2, whole
            assert got["gbdt.hist_bytes_fetched"] == 4 * (9 + 1) * 16 * 2 * 4
        else:   # every slot, 1 + 2 + 4: its shortlist, the totals row
            k = histogram.SHORTLIST          # and the features' numbers
            assert got["gbdt.hist_bytes_fetched"] == 7 * (
                (k + 1) * 16 * 2 * 4 + k * 4)


@pytest.mark.parametrize("which", ["host", "device"])
def test_every_slot_of_a_level_is_scanned_whatever_the_tree(arm, monkeypatch,
                                                            which):
    """A round's host work is a full tree's, node or not in a slot (as
    the device's programs build every slot): 1 + 2 + 4 + 8 scans a
    depth-4 round, also where the tree stops early; and the forest is
    the one the decision made slot by slot commits."""
    X, y = _station_rows(n=1500)
    kw = dict(num_round=2, max_depth=4, nbin=16, min_child_weight=40.0,
              use_pallas=False)
    arm(which)
    with monkeypatch.context() as patch:
        patch.setattr(boosting, "decide_level", oracle.decide_level)
        plain = boosting.train(X, y, **kw)
    assert max(len(t) for t in plain.trees) < 31       # stops early
    scans, decide = [], boosting.decide_level

    def seen_decide(hists, *a):
        scans.extend(np.asarray(hists).any(axis=(1, 2, 3)))
        return decide(hists, *a)

    arm(which)
    monkeypatch.setattr(boosting, "decide_level", seen_decide)
    model = boosting.train(X, y, **kw)
    assert _structure(model) == _structure(plain)
    levels = [sum(1 for n in t if n.feature >= 0) > 0 for t in model.trees]
    assert all(levels) and len(scans) == 2 * (1 + 2 + 4 + 8)
    assert 0 < sum(1 for live in scans if not live) < len(scans)


@pytest.mark.parametrize("which", ["host", "device"])
@pytest.mark.parametrize("job", ["dense", "nan", "stations", "approx"])
def test_the_loop_commits_the_forest_decided_slot_by_slot_in_the_order_it_had(
        arm, monkeypatch, which, job):
    """A level decided in one pass and its trees written after the next
    level is handed over: the committed forest is the one the loop
    committed when it decided a slot at a time (``boosting_oracle``),
    node for node and to the last bit of every weight; one allreduce a
    level, in the order it had; the tables handed to ``shard.partition``
    are ``_route_round``'s of the trees."""
    X, y = _station_rows(n=1500) if job == "stations" \
        else _tabular(missing=job == "nan")
    kw = dict(num_round=3, max_depth=4, nbin=16, use_pallas=False)
    if job == "stations":
        kw["min_child_weight"] = 40.0           # trees that stop early
    if job == "approx":
        kw["tree_method"] = "approx"
    model = oracle.held_to_the_loop_of_then(arm, which, monkeypatch, X, y,
                                            **kw)
    assert model.has_missing == (job in ("nan", "stations"))
    full = sum(len(t) == 31 for t in model.trees)
    assert (full < 3) == (job == "stations")


@pytest.mark.parametrize("kill,classes", [("1,2,2,0", 1), ("0,3,1,0", 1),
                                          ("1,1,2,0", 3)])
def test_a_rank_killed_inside_a_round_commits_the_undisturbed_forest(
        tmp_path, native_lib, capfd, kill, classes):
    """A rank dies between two levels of a round (at its second or third
    collective of that version: a level's allreduce, issued before the
    level above's trees are written) and resumes from the round's
    checkpoint; the replay still sees the sequence the survivors saw.
    Every rank commits the forest of the job nobody disturbed, bit for
    bit."""
    from rabit_tpu.tracker.launch_local import launch

    X, y = _xor_data(n=400)
    if classes > 1:
        y = (y + (X[:, 0] > 0.5)).astype(np.float32)      # 0, 1, 2
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    cmd = [sys.executable, "tests/workers/boosting_dist.py", str(tmp_path)]
    env = {"RABIT_ENGINE": "mock", "BOOST_NUM_CLASS": str(classes),
           "BOOST_MIN_ACC": "0.8"}
    assert launch(2, cmd, extra_env={**env, "BOOST_SAVE": "calm"}) == 0
    assert launch(2, cmd, extra_env={**env, "RABIT_MOCK": kill,
                                     "BOOST_SAVE": "died"}) == 0
    said = "".join(capfd.readouterr())
    assert "killed at version=%s seq=%s" % tuple(kill.split(",")[1:3]) in said
    calm = _saved(tmp_path, "calm", 2)
    assert len(calm[0][0]) >= 15 * classes * 3
    for nodes, _cuts in calm + _saved(tmp_path, "died", 2):
        np.testing.assert_array_equal(nodes, calm[0][0])


# ----------------------------------------------------------------------
# a level's histograms stay on the device: it ranks every slot's
# features, the host decides in float64 on the shortlist it fetches
# ----------------------------------------------------------------------
def _wide(n=600, f=400, seed=9, missing=False):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = ((X[:, 3] * X[:, 211] + 0.5 * X[:, 399]
          + 0.3 * rng.standard_normal(n)) > 0).astype(np.float32)
    if missing:
        X[rng.random((n, f)) < 0.3] = np.nan
    return X, y


@pytest.mark.parametrize("missing", [False, True], ids=["dense", "nan"])
def test_device_arm_fetches_a_shortlist_and_the_host_arm_whole_levels(
        arm, missing):
    """At 400 features the device arm fetches under a twentieth of the
    histogram bytes the host arm moves (what the device arm fetched
    before its level stayed on the device: the built slots whole), and
    counts every level as scanned on the device; the host arm counts
    none; both commit one forest."""
    from rabit_tpu.obs import program

    X, y = _wide(missing=missing)
    got, models = {}, {}
    for which in ("host", "device"):
        arm(which)
        before = program.stats()
        models[which] = boosting.train(X, y, num_round=2, max_depth=3,
                                       nbin=8, use_pallas=False)
        after = program.stats()
        got[which] = {k: after.get(k, 0) - before.get(k, 0) for k in (
            "gbdt.hist_bytes_fetched", "gbdt.levels",
            "gbdt.levels_device_scan")}
    assert _structure(models["host"]) == _structure(models["device"])
    assert {n.feature for t in models["device"].trees for n in t} > {-1, 3}
    assert got["host"]["gbdt.levels"] == got["device"]["gbdt.levels"] == 6
    assert got["host"]["gbdt.levels_device_scan"] == 0
    assert got["device"]["gbdt.levels_device_scan"] == 6
    # two rounds of built slots 1 + 1 + 2, whole
    assert got["host"]["gbdt.hist_bytes_fetched"] == 2 * 4 * (
        400 + missing) * 8 * 2 * 4
    assert 0 < 20 * got["device"]["gbdt.hist_bytes_fetched"] \
        < got["host"]["gbdt.hist_bytes_fetched"]


def test_device_scan_follows_what_the_engine_does_with_a_device_array(
        arm, monkeypatch):
    """The condition is read off the engine, no option: where
    ``allreduce`` would not hand a device array back (a host engine),
    the device arm's levels cross to the host whole, as before, and the
    forest is the same."""
    from rabit_tpu import engine
    from rabit_tpu.obs import program

    X, y = _tabular(n=1200)
    kw = dict(num_round=2, max_depth=3, nbin=16, use_pallas=False)
    arm("device")
    assert engine.keeps_device_payloads()
    on_device = boosting.train(X, y, **kw)
    arm("device")
    monkeypatch.setattr(engine, "keeps_device_payloads", lambda: False)
    before = program.stats()
    through_host = boosting.train(X, y, **kw)
    after = program.stats()
    assert after.get("gbdt.levels_device_scan", 0) \
        == before.get("gbdt.levels_device_scan", 0)
    assert after["gbdt.levels"] - before["gbdt.levels"] == 6
    assert _structure(through_host) == _structure(on_device)
    np.testing.assert_allclose(_weights(on_device), _weights(through_host),
                               rtol=1e-5, atol=1e-6)


def test_allreduce_sees_the_built_slots_once_a_level_before_any_scoring(
        arm, monkeypatch):
    """World 1, device arm: ``rabit_tpu.allreduce`` is still called once
    a level, on the device array of the built slots' histograms, and
    the scan program runs on what it returned."""
    import jax

    import rabit_tpu

    X, y = _tabular(n=1200, missing=True)
    arm("device")
    events, reduce_, scan = [], rabit_tpu.allreduce, boosting._DeviceShard.scan

    def seen_allreduce(data, op=None, *a, **kw):
        if isinstance(data, jax.Array):
            events.append(("allreduce", data.shape))
            data = data + 0        # another array: the scan must take it
            events.append(("handed", id(data)))
            return data
        return reduce_(data, op, *a, **kw)

    def seen_scan(self, reduced, build, depth):
        events.append(("scan", id(reduced), depth))
        return scan(self, reduced, build, depth)

    monkeypatch.setattr(rabit_tpu, "allreduce", seen_allreduce)
    monkeypatch.setattr(boosting._DeviceShard, "scan", seen_scan)
    boosting.train(X, y, num_round=1, max_depth=3, nbin=16, use_pallas=False)
    shapes = [e[1] for e in events if e[0] == "allreduce"]
    assert shapes == [(1, 6, 16, 2), (1, 6, 16, 2), (2, 6, 16, 2)]
    for k in range(3):
        call, handed, scanned = events[3 * k:3 * k + 3]
        assert call[0] == "allreduce" and scanned == ("scan", handed[1], k)


# ----------------------------------------------------------------------
# tree_method="approx": cuts sketched from the hessians before every
# tree, rows binned again every round, a model that routes by value
# ----------------------------------------------------------------------
def _splits(model):
    return [[n.split for n in tree] for tree in model.trees]


@pytest.mark.parametrize("missing", [False, True], ids=["dense", "nan"])
@pytest.mark.parametrize("kw", [
    {"loss": "logistic"}, {"loss": "squared"},
    {"loss": "logistic", "subsample": 0.7, "seed": 3},
], ids=["logistic", "squared", "subsample"])
def test_approx_device_arm_builds_the_host_arms_forest_on_the_same_cuts(
        arm, kw, missing):
    X, y = _tabular(missing=missing)
    X[:, 3] = np.round(X[:, 3] * 2) / 2                  # ties
    models = []
    for which in ("host", "device"):
        arm(which)
        models.append(boosting.train(
            X, y, num_round=4, max_depth=4, nbin=16, use_pallas=False,
            tree_method="approx", **kw))
    host, device = models
    assert host.tree_method == device.tree_method == "approx"
    assert len(device.tree_cuts) == 4
    for a, b in zip(host.tree_cuts, device.tree_cuts):
        assert a.shape == (5, 15) and a.dtype == np.float32
        np.testing.assert_array_equal(a, b)
    # the cuts move with the hessians: no two rounds share theirs
    # (squared loss: every hessian is 1 in every round, and they do)
    moved = not np.array_equal(device.tree_cuts[0], device.tree_cuts[3])
    assert moved == (kw["loss"] == "logistic")
    assert _structure(host) == _structure(device)
    assert _splits(host) == _splits(device)
    np.testing.assert_allclose(_weights(device), _weights(host),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(device.predict(X), host.predict(X), atol=1e-5)


@pytest.mark.parametrize("which", ["host", "device"])
def test_approx_cuts_are_weighted_quantiles_under_each_rounds_hessians(
        arm, which):
    """Tree k's cuts against an exact float64 weighted quantile under
    the hessians of the forest without tree k."""
    from rabit_tpu.learn import histogram

    X, y = _tabular(n=2000)
    arm(which)
    nbin = 16
    model = boosting.train(X, y, num_round=3, max_depth=3, nbin=nbin,
                           use_pallas=False, tree_method="approx")
    for k in range(3):
        before = boosting.BoostedModel(
            cuts=model.cuts, trees=model.trees[:k], tree_method="approx",
            learning_rate=model.learning_rate)
        p = before.predict(X).astype(np.float64)
        h = p * (1 - p)
        for j in range(X.shape[1]):
            v = X[:, j].astype(np.float64)
            for i, cut in enumerate(model.tree_cuts[k][j]):
                want = (i + 1) / nbin * h.sum()
                assert h[v < cut].sum() - 1e-4 * h.sum() <= want \
                    <= h[v <= cut].sum() + 1e-4 * h.sum(), (k, j, i)
    assert histogram.sketch_eps(nbin) > 1e-4


def test_approx_at_round_0_picks_hists_splits_on_a_shard_that_is_its_sample(
        arm):
    """Logistic hessians are all 0.25 at margin 0: the first tree's
    weighted sketch is an unweighted one, and on a shard that
    ``cut_sample`` takes whole the two methods cut between the same
    neighbours to within one row a cut (hist interpolates, approx takes
    a value of the data)."""
    from rabit_tpu.learn import histogram

    X, y = _tabular(n=3000)
    arm("device")
    hist = boosting.train(X, y, num_round=1, max_depth=4, nbin=16,
                          use_pallas=False)
    arm("device")
    approx = boosting.train(X, y, num_round=1, max_depth=4, nbin=16,
                            use_pallas=False, tree_method="approx")
    assert _structure(hist) == _structure(approx)
    moved = np.count_nonzero(
        histogram.apply_cuts(X, hist.cuts)
        != histogram.apply_cuts(X, approx.tree_cuts[0]))
    assert 0 < moved <= X.shape[1] * 15
    np.testing.assert_allclose(_weights(approx), _weights(hist), atol=0.02)


@pytest.mark.parametrize("missing", [False, True], ids=["dense", "nan"])
@pytest.mark.parametrize("method", ["hist", "approx"])
def test_predict_by_split_value_is_the_training_margin(arm, method, missing):
    """Training routes a tree's rows by the bins of the cuts it was
    grown on; the committed float split values route them the same
    way, absent values included."""
    from rabit_tpu.learn import histogram

    X, y = _tabular(n=2000, missing=missing)
    arm("device")
    model = boosting.train(X, y, num_round=4, max_depth=4, nbin=16,
                           use_pallas=False, tree_method=method)
    by_bins = np.zeros(len(X), np.float32)
    for k, tree in enumerate(model.trees):
        cuts = model.tree_cuts[k] if method == "approx" else model.cuts
        for node in tree:
            if node.feature >= 0:
                assert node.split == cuts[node.feature, node.bin_threshold]
        by_bins += model.learning_rate * model._tree_margin(
            tree, histogram.apply_cuts(X, cuts))
    by_value = model.margin(X, by_value=True)
    np.testing.assert_array_equal(by_value, by_bins)
    np.testing.assert_allclose(model.predict(X),
                               1 / (1 + np.exp(-by_bins)), rtol=1e-6)
    if method == "hist":
        assert model.tree_cuts == [] and model.tree_method == "hist"


def test_a_checkpoint_from_before_the_split_values_still_loads_under_hist(
        arm):
    """A model pickled before ``TreeNode.split``, ``tree_method`` and
    ``tree_cuts`` existed: it predicts as it did, and a job resumes
    from it under ``hist``."""
    import pickle

    import rabit_tpu

    X, y = _tabular(n=1500)
    kw = dict(max_depth=3, nbin=16, use_pallas=False)
    arm("host")
    straight = boosting.train(X, y, num_round=5, **kw)
    arm("host")
    old = boosting.train(X, y, num_round=3, **kw)
    for tree in old.trees:
        for node in tree:
            del node.__dict__["split"]
    del old.__dict__["tree_method"], old.__dict__["tree_cuts"]
    old = pickle.loads(pickle.dumps(old))
    assert "split" not in old.trees[0][0].__dict__
    np.testing.assert_array_equal(old.predict(X),
                                  boosting.BoostedModel(
        cuts=straight.cuts, trees=straight.trees[:3],
        learning_rate=straight.learning_rate).predict(X))
    # a fresh store whose third version is the old model: the job
    # resumes from it and ends where the undisturbed one ends
    arm("host")
    for _ in range(3):
        rabit_tpu.checkpoint(old)
    resumed = boosting.train(X, y, num_round=5, **kw)
    assert _structure(resumed) == _structure(straight)
    np.testing.assert_array_equal(_weights(resumed), _weights(straight))
    assert _splits(resumed)[3:] == _splits(straight)[3:]


@pytest.mark.parametrize("which", ["host", "device"])
def test_resumed_approx_job_commits_the_same_later_trees_and_cuts(arm, which):
    X, y = _tabular(n=1500, missing=True)
    kw = dict(max_depth=3, nbin=16, use_pallas=False, tree_method="approx")
    arm(which)
    straight = boosting.train(X, y, num_round=6, **kw)
    arm(which)
    boosting.train(X, y, num_round=3, **kw)
    # the same process keeps the committed forest (world 1, empty engine)
    resumed = boosting.train(X, y, num_round=6, **kw)
    assert _structure(resumed) == _structure(straight)
    assert _splits(resumed) == _splits(straight)
    for a, b in zip(resumed.tree_cuts, straight.tree_cuts):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(_weights(resumed), _weights(straight))
    with pytest.raises(Exception, match="tree_method"):
        boosting.train(X, y, num_round=7, max_depth=3, nbin=16)


def test_tree_method_is_one_of_two(arm):
    X, y = _tabular(n=200)
    arm("host")
    with pytest.raises(Exception, match="tree_method"):
        boosting.train(X, y, num_round=1, tree_method="exact")


@pytest.mark.parametrize("which", ["host", "device"])
def test_counters_and_collectives_of_approx_rounds(arm, which, monkeypatch):
    """A round opens with one more allreduce than its levels (the
    summaries, at world 1 too), and counts what it sketched, merged
    and binned again."""
    import rabit_tpu
    from rabit_tpu.learn import histogram
    from rabit_tpu.obs import program

    X, y = _tabular(n=1200)
    arm(which)
    calls, allreduce = [], rabit_tpu.allreduce

    def counting(buf, op, *a, **kw):
        calls.append(tuple(buf.shape))
        return allreduce(buf, op, *a, **kw)

    monkeypatch.setattr(rabit_tpu, "allreduce", counting)
    before = program.stats()
    boosting.train(X, y, num_round=3, max_depth=3, nbin=16,
                   use_pallas=False, tree_method="approx")
    after = program.stats()

    def gained(name):
        return after.get(name, 0) - before.get(name, 0)

    entries = histogram.summary_entries(16)
    payload = [c for c in calls if c in ((1, 5, entries, 3),
                                         (5 * entries * 3,))]
    assert len(payload) == 3                         # one a round
    assert len(calls) == 1 + 3 * (1 + 3)             # has_missing, levels
    assert gained("gbdt.sketches") == 3
    assert gained("gbdt.rows_rebinned") == 3 * 1200
    assert gained("gbdt.summary_entries") == 3 * 5 * entries
    assert gained("gbdt.summary_bytes_merged") == 3 * 5 * entries * 3 * 4
    for name in ("gbdt.sketch", "gbdt.sketch.merge", "gbdt.sketch.cuts",
                 "gbdt.rebin"):
        assert gained(name + ".n") >= 3, name
    assert gained("stage.cuts.n") == 0               # nothing cut up front
    assert gained("gbdt.levels") == 9


def test_no_program_is_built_after_the_first_approx_round(arm, monkeypatch):
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
    boosting.train(X, y, num_round=5, max_depth=3, nbin=16,
                   use_pallas=False, tree_method="approx")
    assert asked[1:] == [0, 0, 0, 0], asked


def _saved(tmp_path, name, world):
    out = []
    for rank in range(world):
        with np.load(tmp_path / f"{name}-{rank}.npz") as z:
            out.append((z["nodes"], z["cuts"]))
    return out


def test_approx_distributed_resume_and_xla_commit_the_undisturbed_forest(
        tmp_path, native_lib):
    """World 2 under ``approx``: a job whose rank 1 dies at version 2
    and resumes, and a job over the XLA engine's device plane, each end
    where the undisturbed host-engine job ends: the same cuts bit for
    bit, and (the death) the same forest bit for bit."""
    from rabit_tpu.tracker.launch_local import launch

    X, y = _xor_data(n=400)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    cmd = [sys.executable, "tests/workers/boosting_dist.py", str(tmp_path)]
    env = {"BOOST_TREE_METHOD": "approx"}
    assert launch(2, cmd, extra_env={
        **env, "RABIT_ENGINE": "mock", "BOOST_SAVE": "calm"}) == 0
    assert launch(2, cmd, extra_env={
        **env, "RABIT_ENGINE": "mock", "RABIT_MOCK": "1,2,0,0",
        "BOOST_SAVE": "died"}) == 0
    assert launch(2, cmd, extra_env={
        **env, "RABIT_ENGINE": "xla", "BOOST_SAVE": "xla"}) == 0
    calm = _saved(tmp_path, "calm", 2)
    assert calm[0][1].shape == (15, 2, 15)
    for name in ("calm", "died", "xla"):
        for nodes, cuts in _saved(tmp_path, name, 2):
            np.testing.assert_array_equal(cuts, calm[0][1])
            if name != "xla":
                np.testing.assert_array_equal(nodes, calm[0][0])
            else:
                # another arm adds the histograms up in another order
                np.testing.assert_array_equal(nodes[:, :6], calm[0][0][:, :6])
                np.testing.assert_allclose(nodes, calm[0][0], rtol=1e-4,
                                           atol=1e-5)


def _level_inputs(trees, depth, fpad, missing, n=257, nbin=16, seed=0):
    """A level's move as the loop hands it over: ``(trees, n)`` node ids
    with live rows in every slot, dead rows (``node < 0``: leaf codes of
    levels above) among them, and ``_route``'s tables with splits, nodes
    that stay leaves, slots of no node with no row in them (padding) and
    one with rows in it (zeros all the same: an unsplit node)."""
    rng = np.random.default_rng((seed, trees, depth, fpad, missing))
    width = 1 << depth
    bins = rng.integers(0, nbin, (n, fpad)).astype(np.int32)
    if missing:
        bins[rng.random((n, fpad)) < 0.3] = nbin            # the code
    tabs = np.zeros((trees, width, 4), np.int32)
    node = np.zeros((trees, n), np.int32)
    for k in range(trees):
        kind = rng.choice(4, width, p=[0.55, 0.2, 0.15, 0.1])
        kind[0] = 0                                         # a split
        leaves = 0
        for s in range(width):
            if kind[s] == 0:                                # a split
                tabs[k, s] = (rng.integers(fpad), rng.integers(nbin),
                              rng.integers(2), 0)
            elif kind[s] == 1:                              # stays a leaf
                leaves += 1
                tabs[k, s, 3] = -leaves - 3
        holds_rows = np.flatnonzero(kind != 2)              # 2: padding
        node[k] = rng.choice(holds_rows, n)
        node[k, rng.random(n) < 0.2] = -rng.integers(1, 4)  # dead rows
    return bins, node, tabs


def _formula_of_before(bins, node, tabs, missing_code):
    """The row move as every arm made it before the device's was
    compiled a depth, a tree at a time, in numpy."""
    out = np.empty_like(node)
    for k, tab in enumerate(tabs):
        feat, thr, dleft, leaf = tab[np.clip(node[k], 0, len(tab) - 1)].T
        b = bins[np.arange(bins.shape[0]), feat]
        left = np.where(b == missing_code, dleft != 0, b <= thr)
        child = 2 * node[k] + 1 - left.astype(np.int32)
        out[k] = np.where(node[k] < 0, node[k],
                          np.where(leaf < 0, leaf, child))
    return out


@pytest.mark.parametrize("form", ["sliced", "whole"])
@pytest.mark.parametrize("missing", [True, False], ids=["nan", "dense"])
@pytest.mark.parametrize("depth", range(6))
@pytest.mark.parametrize("trees", [1, 7])
def test_a_depths_move_equals_the_host_arms_and_the_formula_of_before(
        trees, depth, missing, form):
    """``partition_program`` of one depth, in the form its shapes give
    it (a shard of few feature rows passes over them whole, one of many
    slices the rows the level's splits name: no switch), against
    ``_HostShard.partition`` and the formula written out: the same
    int32 array, leaf codes, dead rows, unsplit and padding entries
    included."""
    import jax.numpy as jnp

    nbin, width = 16, 1 << depth
    fpad = 8 * trees * 32 + 8 if form == "sliced" else 7
    assert boosting._move_slices(trees, width, fpad) == (form == "sliced")
    bins, node, tabs = _level_inputs(trees, depth, fpad, missing, nbin=nbin)
    n = bins.shape[0]
    lead = (trees,) if trees > 1 else ()
    got = boosting.partition_program(n, fpad, trees, width, nbin)(
        jnp.asarray(np.ascontiguousarray(bins.T)),
        jnp.asarray(node.reshape(lead + (n,))),
        jnp.asarray(tabs.reshape(lead + (width, 4))))
    assert got.dtype == jnp.int32 and got.shape == lead + (n,)
    got = np.asarray(got).reshape(trees, n)
    want = _formula_of_before(bins, node, tabs, nbin)
    host = object.__new__(boosting._HostShard)
    host.n, host.bins, host.node = n, bins, node.copy()
    host.model = boosting.BoostedModel(
        cuts=np.zeros((fpad, nbin - 1), np.float32))
    host.partition(tabs, depth)
    np.testing.assert_array_equal(host.node, want)
    np.testing.assert_array_equal(got, want)
    assert (want < 0).any() and (want >= 0).any()
    assert missing == bool((bins == nbin).any())


@pytest.mark.parametrize("kw,classes", [
    ({"loss": "logistic"}, 1), ({"loss": "softprob", "num_class": 3}, 3),
], ids=["one-tree", "three-trees"])
def test_a_resumed_job_replays_onto_the_ids_and_margins_it_left(
        arm, monkeypatch, kw, classes):
    """``_replay`` moves the rows by the same programs a depth as the
    round that grew the trees: before every leaf update the resumed
    job's node ids, and after it its margins, are those of the job that
    never stopped, bit for bit, and its last round grows the same
    trees."""
    X, y = _tabular(n=1500, missing=True)
    if classes > 1:
        y = (np.floor(3 * np.random.default_rng(1).random(len(y)))
             ).astype(np.float32)
    kw = dict(kw, max_depth=4, nbin=16, use_pallas=False)
    seen = []
    leaf = boosting._DeviceShard.leaf

    def spy(self, vals):
        ids = np.asarray(self.node)
        leaf(self, vals)
        seen.append((ids, np.asarray(self.margin)))

    monkeypatch.setattr(boosting._DeviceShard, "leaf", spy)
    arm("device")
    straight = boosting.train(X, y, num_round=3, **kw)
    never_stopped, seen[:] = list(seen), []
    arm("device")
    boosting.train(X, y, num_round=2, **kw)
    del seen[:]
    resumed = boosting.train(X, y, num_round=3, **kw)
    assert _structure(resumed) == _structure(straight)
    # two rounds replayed, one grown
    assert len(seen) == len(never_stopped) == 3
    for (ids, margin), (want_ids, want_margin) in zip(seen, never_stopped):
        assert ids.shape == ((classes, 1500) if classes > 1 else (1500,))
        assert (ids < 0).any() or (ids > 0).any()
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(margin, want_margin)


@pytest.mark.parametrize("trees,fpad,sliced", [
    (7, 56, []), (1, 32, [0, 1]), (1, 968, [0, 1, 2, 3, 4, 5]),
    (7, 968, [0, 1, 2, 3, 4]),
], ids=["covtype", "higgs", "bosch", "seven-trees-on-968"])
def test_the_moves_form_follows_from_trees_level_nodes_and_feature_rows(
        trees, fpad, sliced):
    """The one rule (``_move_slices``): a level slices where its K * W
    slices, a tile of 8 feature rows each, read less than the staged
    array.  At the boosting cells' shapes: no depth of seven trees on 56
    feature rows, the two narrowest of one tree on 32, every depth on
    968."""
    assert [d for d in range(6)
            if boosting._move_slices(trees, 1 << d, fpad)] == sliced


@pytest.mark.parametrize("which,kw,read,whole", [
    ("device", {}, 1 + 2 + 32 + 32, 4 * 32),
    ("device", {"loss": "softprob", "num_class": 3}, 3 + 3 * 32, 4 * 3 * 32),
    ("host", {}, 4, 4 * 5),
], ids=["device", "device-three-trees", "host"])
def test_the_level_loop_counts_the_feature_rows_its_moves_read(
        arm, which, kw, read, whole):
    """``gbdt.partition_rows_read`` over ``gbdt.partition_rows_whole``,
    a level of the loop at a time: on the device K * W rows where the
    level slices (5 features staged as 32 rows: the two narrowest levels
    of one tree, the root of three) and the staged rows once where it
    does not, of K passes
    over them; on the host a bin a row and tree of the K * f.  A
    resume's replayed moves count nothing."""
    from rabit_tpu.obs import program

    X, y = _tabular(n=1200)
    if kw:
        y = np.floor(3 * np.random.default_rng(2).random(len(y))).astype(
            np.float32)
    kw = dict(kw, max_depth=4, nbin=16, use_pallas=False)
    names = ("gbdt.partition_rows_read", "gbdt.partition_rows_whole",
             "gbdt.levels")

    def counted(rounds):
        before = program.stats()
        boosting.train(X, y, num_round=rounds, **kw)
        after = program.stats()
        return [after.get(k, 0) - before.get(k, 0) for k in names]

    arm(which)
    assert counted(1) == [read, whole, 4]
    # the second job replays the first's round and grows one
    assert counted(2) == [read, whole, 4]

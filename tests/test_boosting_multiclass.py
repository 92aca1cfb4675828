"""Multi-class boosting (``loss="softprob"``, XGBoost's
``multi:softprob``): the softmax gradient of both arms, a round's K
trees grown level by level together against the same trees grown one at
a time and against the plain reference, the model of K output groups
(margins, predict, an old checkpoint), and the jobs across ranks."""
import os
import pickle
import re
import sys

import numpy as np
import pytest

import boosting_oracle as oracle
import rabit_tpu
from rabit_tpu.learn import boosting, histogram

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.reference import gbdt as ref  # noqa: E402
from perfbench.reference import gbdt_softprob as refs  # noqa: E402


def _classes(n=3000, f=6, k=3, seed=0, missing=False):
    """Rows whose class is the noisy argmax of k fixed functions."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    score = np.stack([np.sin(c + X[:, c % f]) + X[:, (c + 1) % f] * (c % 2)
                      - 0.4 * c * (c > 3) for c in range(k)])
    y = np.argmax(score + 0.3 * rng.standard_normal(score.shape),
                  axis=0).astype(np.float32)
    if missing:
        X[rng.random(n) < 0.2, 1] = np.nan
    return X, y


def _as_tuples(trees):
    return [[(n.feature, n.bin_threshold, n.default_left, n.left, n.right,
              n.value, n.split) for n in tree] for tree in trees]


def _forest(model):
    return _as_tuples(model.trees)


@pytest.fixture
def arm(monkeypatch):
    """``arm("device")`` makes ``train`` take the arm it takes on an
    accelerator (steered here: ``on_tpu`` as the boosting module sees
    it), on a fresh ``empty`` engine."""
    def switch(which: str) -> None:
        monkeypatch.setattr(boosting, "on_tpu", lambda: which == "device")
        if rabit_tpu.initialized():
            rabit_tpu.finalize()
        rabit_tpu.init(rabit_engine="empty")

    yield switch
    if rabit_tpu.initialized():
        rabit_tpu.finalize()


# ----------------------------------------------------------------------
# the softmax gradient
# ----------------------------------------------------------------------
def _softmax64(margin, labels):
    m = margin.astype(np.float64)
    e = np.exp(m - m.max(axis=0))
    p = e / e.sum(axis=0)
    hit = labels[None, :] == np.arange(len(m))[:, None]
    return p - hit, np.maximum(2.0 * p * (1.0 - p), 1e-16)


@pytest.mark.parametrize("k", [3, 7])
@pytest.mark.parametrize("which", ["host", "device"])
def test_softmax_gradient_of_both_arms_equals_the_reference_and_float64(
        which, k):
    rng = np.random.default_rng(k)
    n = 4096
    margin = (3.0 * rng.standard_normal((k, n))).astype(np.float32)
    margin[:, :8] = 0.5                       # a first round's rows
    margin[0, 8:16] = 60.0                    # a class all but certain
    labels = rng.integers(0, k, n).astype(np.float32)
    if which == "host":
        g, h = boosting._grad_hess(margin, labels, "softprob")
    else:
        gh = np.asarray(boosting.softprob_grad_program(n, k)(margin, labels))
        assert gh.shape == (k, 2, n) and gh.dtype == np.float32
        g, h = gh[:, 0], gh[:, 1]
    g64, h64 = _softmax64(margin, labels)
    np.testing.assert_allclose(g, g64, atol=2e-6)
    np.testing.assert_allclose(h, h64, atol=2e-6)
    assert h.min() >= np.float32(1e-16)       # XGBoost's floor
    np.testing.assert_allclose(g.sum(axis=0), 0.0, atol=1e-5)
    want = np.asarray(refs.softmax_grad_hess(margin, labels))
    np.testing.assert_allclose(g, want[:, :, 0], atol=1e-6)
    np.testing.assert_allclose(h, want[:, :, 1], atol=1e-6)


def test_softmax_gradient_program_zeroes_the_rows_sampled_out():
    rng = np.random.default_rng(1)
    margin = rng.standard_normal((3, 512)).astype(np.float32)
    labels = rng.integers(0, 3, 512).astype(np.float32)
    keep = rng.random(512) < 0.5
    gh = np.asarray(boosting.softprob_grad_program(512, 3, True)(
        margin, labels, keep))
    full = np.asarray(boosting.softprob_grad_program(512, 3)(margin, labels))
    np.testing.assert_array_equal(gh[:, :, keep], full[:, :, keep])
    assert not gh[:, :, ~keep].any()


# ----------------------------------------------------------------------
# grown together == grown one tree at a time
# ----------------------------------------------------------------------
def _grow_one(bins, grad, hess, nbin, max_depth, reg_lambda, mcw,
              has_missing, missing_code):
    """One tree on (grad, hess), level by level, as ``train`` grew its
    one tree a round before a level was a forest's: the loop written
    out on the host arm's pieces.  Returns the tree and each row's leaf
    weight."""
    n = bins.shape[0]
    tree = [boosting.TreeNode()]
    slots, leaves, build = [0], [], [0]
    node, level_of = np.zeros(n, np.int32), {}
    for depth in range(max_depth):
        if all(nid < 0 for nid in slots):
            break
        order = [s for s in build if s >= 0]
        built = np.asarray(histogram.build_level_local(
            bins, grad, hess, node, order, nbin, use_pallas=False,
            totals=has_missing))
        hists = boosting._assemble(level_of, depth, built, order, len(slots))
        tabs, build, slots, _ = oracle.grow_level(
            [tree], slots, [leaves], hists, reg_lambda, mcw, has_missing)
        tab = tabs[0]
        live = node >= 0
        feat, thr, dleft, leaf = tab[np.where(live, node, 0)].T
        b = bins[np.arange(n), feat]
        left = np.where(b == missing_code, dleft != 0, b <= thr)
        node = np.where(live, np.where(leaf < 0, leaf, 2 * node + 1 - left),
                        node).astype(np.int32)
    vals = boosting._leaf_values(tree, slots, leaves, max_depth)
    width = 1 << max_depth
    return tree, vals[np.where(node >= 0, node, width - node - 1)]


@pytest.mark.parametrize("which", ["host", "device"])
@pytest.mark.parametrize("num_class,missing", [(3, False), (7, True)],
                         ids=["3-dense", "7-nan"])
def test_a_forests_level_decided_in_one_pass_commits_the_slot_by_slot_forest(
        arm, monkeypatch, which, num_class, missing):
    """K trees' slots in one pass, tree-major, and their trees written
    after the next level is handed over: the forest of the loop that
    decided a slot at a time, node for node and bit for bit, one
    allreduce a level in the order it had, the row-move tables
    ``_route_round``'s of the trees."""
    X, y = _classes(k=num_class, missing=missing)
    model = oracle.held_to_the_loop_of_then(
        arm, which, monkeypatch, X, y, num_round=2, max_depth=4, nbin=16,
        loss="softprob", num_class=num_class, use_pallas=False)
    assert len(model.trees) == 2 * num_class


def _one_tree_at_a_time(X, y, num_class, loss, num_round, max_depth, nbin,
                        rate=0.3, reg_lambda=1.0, mcw=1e-3):
    """The job of ``train`` on one rank with every tree of a round grown
    by itself, one after another, from the round's one set of
    gradients."""
    cuts = histogram.quantile_cuts(boosting.cut_sample(X), nbin)
    bins = boosting.apply_cuts(X, cuts)
    has_missing = bool(np.isnan(X).any())
    margin = np.full((num_class, len(X)), 0.5 if num_class > 1 else 0.0,
                     np.float32)
    forest = []
    for _round in range(num_round):
        grad, hess = boosting._grad_hess(margin, y, loss)
        for k in range(num_class):
            tree, leaf = _grow_one(bins, grad[k], hess[k], nbin, max_depth,
                                   reg_lambda, mcw, has_missing, nbin)
            boosting._fill_splits(tree, cuts)
            margin[k] += rate * leaf
            forest.append(tree)
    return forest


@pytest.mark.parametrize("missing", [False, True], ids=["dense", "nan"])
@pytest.mark.parametrize("k", [3, 7])
def test_a_rounds_trees_grown_together_are_those_grown_one_at_a_time(
        arm, k, missing):
    """Bit for bit, leaf weights and split values included: none of a
    round's K trees sees another's update, so the forest's level is
    K single levels side by side."""
    X, y = _classes(k=k, missing=missing)
    arm("host")
    model = boosting.train(X, y, num_round=3, max_depth=4, nbin=16,
                           loss="softprob", num_class=k, use_pallas=False)
    assert len(model.trees) == 3 * k and model.num_class == k
    assert model.base_score == 0.5 and model.has_missing == missing
    want = _one_tree_at_a_time(X, y, k, "softprob", 3, 4, 16)
    assert _forest(model) == _as_tuples(want)


@pytest.mark.parametrize("loss", ["logistic", "squared"])
@pytest.mark.parametrize("data", ["xor", "tabular", "tabular-nan"])
def test_one_class_builds_the_forest_of_the_loop_before_the_forests_level(
        arm, data, loss):
    """K = 1 is the same loop: on the seeds ``tests/test_boosting.py``
    uses it builds, bit for bit, the forest of the single-tree loop
    written out above (the parent's ``train``)."""
    from test_boosting import _tabular, _xor_data

    X, y = _xor_data() if data == "xor" else _tabular(
        missing=data.endswith("nan"))
    arm("host")
    model = boosting.train(X, y, num_round=4, max_depth=4, nbin=16,
                           loss=loss, use_pallas=False)
    assert model.num_class == 1 and model.base_score == 0.0
    want = _one_tree_at_a_time(X, y, 1, loss, 4, 4, 16)
    assert _forest(model) == _as_tuples(want)


# ----------------------------------------------------------------------
# both arms against the plain reference, tree by tree
# ----------------------------------------------------------------------
def _committed(model):
    """The forest as the benchmark's adapter hands it to the reference."""
    from perfbench import harness

    got = harness.load_module(os.path.join(
        ROOT, "perfbench", "learners", "gbdt.py")).committed(model)
    return got["forest_int"], got["forest_val"]


@pytest.mark.parametrize("k", [3, 7])
@pytest.mark.parametrize("which", ["host", "device", "device-kernel"])
def test_both_arms_forest_is_the_references_tree_by_tree(arm, which, k):
    """Every round of a K-class job replayed by ``perfbench/reference/
    gbdt_softprob.py``: each tree's splits are the reference's best (to
    a float32 near-tie), no node that should split was left, every leaf
    weight is the reference's sums'.  ``device-kernel`` runs the fused
    histogram kernel (interpreted) with a float32 operand."""
    X, y = _classes(n=2048, k=k, seed=5)
    arm("host" if which == "host" else "device")
    kw = {"use_pallas": False}
    if which == "device-kernel":
        kw = {"use_pallas": True, "compute_dtype": "float32"}
    rounds, depth = 3, 3
    model = boosting.train(X, y, num_round=rounds, max_depth=depth, nbin=16,
                           loss="softprob", num_class=k,
                           **kw)
    forest_int, forest_val = _committed(model)
    got = refs.replay(X, y, model.cuts, forest_int, forest_val,
                      list(range(rounds)), k, 16, depth, 0.3, 0.5, 1.0, 1e-3,
                      "float32")
    assert got["unsplit_above_limit"] == 0
    assert got["split_regret"] < 1e-4, got
    assert got["leaf_sum_rel_err"] < 1e-4, got
    assert got["splits"] + got["leaves"] == sum(
        1 for tree in model.trees for _ in tree)
    assert len(got["by_class"]) == k
    # the margins the reference computes are the model's own
    shard = ref.Shard(X, y, model.cuts, 16)
    (margins,) = refs.class_margins(shard, forest_int, forest_val, rounds, k,
                                    0.3, 0.5, depth)
    np.testing.assert_allclose(
        np.asarray(margins), model.margin(boosting.apply_cuts(X, model.cuts)),
        rtol=1e-5, atol=1e-6)


def test_device_arm_runs_one_program_a_level_whatever_the_classes(
        arm, monkeypatch):
    """Six waits a round: per depth one level program, one allreduce,
    one scan, one fetch and one row move for all K trees, and one
    gradient and one leaf program a round."""
    from rabit_tpu.obs import program

    X, y = _classes(n=1024, k=7, seed=2)
    arm("device")
    program.reset()
    reduced = []
    allreduce = rabit_tpu.allreduce
    monkeypatch.setattr(rabit_tpu, "allreduce", lambda buf, op, *a, **kw: (
        reduced.append(np.shape(buf)), allreduce(buf, op, *a, **kw))[1])
    boosting.train(X, y, num_round=2, max_depth=3, nbin=16, loss="softprob",
                   num_class=7, use_pallas=False)
    stats = program.stats()
    assert stats["gbdt.classes"] == 7 and stats["gbdt.trees"] == 14
    assert stats["gbdt.levels"] == stats["gbdt.level.n"] == 6
    assert stats["gbdt.partition.n"] == 6 and stats["gbdt.leaf.n"] == 2
    assert stats["gbdt.grad.n"] == 2
    assert stats["gbdt.levels_device_scan"] == 6
    # one allreduce a level (and the round-0 one of has_missing): the
    # built slots of all seven trees, tree-major
    levels = [s for s in reduced if len(s) == 4]
    assert [s[0] for s in levels] == [7, 7, 14] * 2
    assert stats["gbdt.channels"] == 2 * (7 + 7 + 14) * 2
    assert stats["gbdt.channels_live"] <= stats["gbdt.channels"]


# ----------------------------------------------------------------------
# the model of K output groups
# ----------------------------------------------------------------------
def test_predict_gives_probabilities_that_sum_to_one_and_learn(arm):
    X, y = _classes(k=7, n=4000)
    arm("host")
    model = boosting.train(X, y, num_round=8, max_depth=4, nbin=32,
                           loss="softprob", num_class=7, use_pallas=False)
    p = model.predict(X)
    assert p.shape == (len(X), 7)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-5)
    assert (p >= 0).all()
    assert (p.argmax(axis=1) == y).mean() > 0.6
    m = model.margin(boosting.apply_cuts(X, model.cuts))
    assert m.shape == (7, len(X))


def test_resume_replays_k_margins(arm):
    """10 rounds straight == 4 rounds, then a resume to 10, on both
    arms: the replay rebuilds K margins from the committed forest."""
    X, y = _classes(k=3)
    kw = dict(max_depth=3, nbin=16, loss="softprob", num_class=3,
              use_pallas=False)
    for which in ("host", "device"):
        arm(which)
        straight = boosting.train(X, y, num_round=6, **kw)
        arm(which)
        boosting.train(X, y, num_round=2, **kw)
        resumed = boosting.train(X, y, num_round=6, **kw)
        assert len(resumed.trees) == 18
        assert _forest(resumed) == _forest(straight)


def test_a_checkpoint_from_before_the_field_loads_as_one_class(arm):
    X, y = _classes(k=2)
    arm("host")
    model = boosting.train(X, y, num_round=3, max_depth=3, nbin=16,
                           use_pallas=False)
    want = model.predict(X)
    del model.__dict__["num_class"]           # as an older pickle is
    old = pickle.loads(pickle.dumps(model))
    assert "num_class" not in old.__dict__ and old.num_class == 1
    np.testing.assert_array_equal(old.predict(X), want)
    assert old.margin(boosting.apply_cuts(X, old.cuts)).shape == (len(X),)
    # and a job resumes from it: the commit here is version 4
    rabit_tpu.checkpoint(old)
    more = boosting.train(X, y, num_round=6, max_depth=3, nbin=16,
                          use_pallas=False)
    assert len(more.trees) == 3 + 2 and more.num_class == 1


@pytest.mark.parametrize("kw,message", [
    ({"loss": "softprob", "num_class": 3, "tree_method": "approx"},
     "each class would sketch"),
    ({"loss": "softprob"}, "num_class >= 2"),
    ({"loss": "logistic", "num_class": 3}, "one margin a row"),
])
def test_what_the_multiclass_job_refuses_it_refuses_with_its_reason(
        arm, kw, message):
    X, y = _classes(k=3)
    arm("host")
    with pytest.raises(Exception, match=message):
        boosting.train(X, y, num_round=1, max_depth=2, nbin=8, **kw)


@pytest.mark.parametrize("labels", [[0.0, 1.0, 3.0], [0.0, 1.5, 2.0],
                                    [-1.0, 0.0, 1.0]])
def test_labels_must_be_class_ids(arm, labels):
    X = np.zeros((3, 2), np.float32)
    arm("host")
    with pytest.raises(Exception, match=r"class ids in \[0, 3\)"):
        boosting.train(X, np.asarray(labels, np.float32), num_round=1,
                       loss="softprob", num_class=3)


def test_a_job_of_another_class_count_does_not_resume_the_forest(arm):
    X, y = _classes(k=3)
    arm("host")
    boosting.train(X, y, num_round=1, max_depth=2, nbin=8, loss="softprob",
                   num_class=3, use_pallas=False)
    with pytest.raises(Exception, match="num_class=3"):
        boosting.train(X, y, num_round=2, max_depth=2, nbin=8,
                       loss="softprob", num_class=4, use_pallas=False)


def test_subsample_is_the_rounds_shared_by_its_trees(arm):
    """The draw is a round's: both arms sample the same rows for all K
    trees and build the same forest shapes."""
    X, y = _classes(k=3, n=2000)
    kw = dict(num_round=3, max_depth=3, nbin=16, loss="softprob",
              num_class=3, subsample=0.6, seed=4, use_pallas=False)
    arm("host")
    host = boosting.train(X, y, **kw)
    arm("device")
    device = boosting.train(X, y, **kw)
    shape = lambda m: [[n[:5] for n in t] for t in _forest(m)]  # noqa: E731
    assert shape(host) == shape(device)
    np.testing.assert_allclose(
        [n[5] for t in _forest(host) for n in t],
        [n[5] for t in _forest(device) for n in t], rtol=2e-4, atol=1e-6)


# ----------------------------------------------------------------------
# across ranks
# ----------------------------------------------------------------------
def _saved(tmp_path, name, world):
    out = []
    for rank in range(world):
        with np.load(tmp_path / f"{name}-{rank}.npz") as z:
            out.append(z["nodes"])
    return out


def _dist_data(tmp_path, k=3):
    X, y = _classes(n=600, k=k, seed=3)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "y.npy", y)
    return [sys.executable, "tests/workers/boosting_dist.py", str(tmp_path)]


def test_world_two_with_a_rank_killed_ends_where_the_calm_job_ends(
        tmp_path, native_lib):
    """Rank 1 dies at version 2 and resumes from the committed forest
    of 2 x 3 trees: the job ends bit for bit where the undisturbed one
    does."""
    from rabit_tpu.tracker.launch_local import launch

    cmd = _dist_data(tmp_path)
    env = {"BOOST_NUM_CLASS": "3", "BOOST_MIN_ACC": "0.7",
           "RABIT_ENGINE": "mock"}
    assert launch(2, cmd, extra_env={**env, "BOOST_SAVE": "calm"}) == 0
    assert launch(2, cmd, extra_env={**env, "RABIT_MOCK": "1,2,0,0",
                                     "BOOST_SAVE": "died"}) == 0
    calm = _saved(tmp_path, "calm", 2)
    assert len(np.unique(calm[0][:, 0])) == 45          # 15 rounds x 3
    for nodes in calm[1:] + _saved(tmp_path, "died", 2):
        np.testing.assert_array_equal(nodes, calm[0])


@pytest.mark.parametrize("engine", ["native", "xla"])
def test_world_three_ranks_commit_one_forest(tmp_path, engine):
    from rabit_tpu.tracker.launch_local import launch

    cmd = _dist_data(tmp_path)
    env = {"BOOST_NUM_CLASS": "3", "BOOST_MIN_ACC": "0.7",
           "BOOST_SAVE": engine}
    if engine == "xla":
        env["RABIT_ENGINE"] = "xla"
    assert launch(3, cmd, extra_env=env) == 0
    saved = _saved(tmp_path, engine, 3)
    for nodes in saved[1:]:
        np.testing.assert_array_equal(nodes, saved[0])


# ----------------------------------------------------------------------
# narrow features share a product of the lane-wide call (PR 48)
# ----------------------------------------------------------------------
def _indicator_rows(n=2400, seed=48):
    """Three continuous columns and eleven indicator columns set in a
    thousandth to nine tenths of the rows, the class from both kinds."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 14)).astype(np.float32)
    shares = np.geomspace(1e-3, 0.9, 11)
    X[:, 3:] = rng.random((n, 11)) < shares
    score = np.stack([np.sin(c + X[:, c % 3]) + X[:, 3 + c] * (1 + c % 2)
                      for c in range(7)])
    y = np.argmax(score + 0.3 * rng.standard_normal(score.shape),
                  axis=0).astype(np.float32)
    return X, y


def _counted(names, job):
    from rabit_tpu.obs import program

    before = program.stats()
    model = job()
    after = program.stats()
    return model, [after.get(k, 0) - before.get(k, 0) for k in names]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_forest_is_the_same_byte_for_byte_with_and_without_the_plan(
        arm, monkeypatch, dtype):
    """A job on rows with indicator columns commits the same forest with
    the rule as with the rule answering "no feature is narrow": the
    packed product adds the same numbers in the same order.  The
    counters read 11 features of 14 packed in every lane-wide call with
    it, none without."""
    from rabit_tpu.ops import histogram_kernel as hk

    X, y = _indicator_rows()
    kw = dict(num_round=2, max_depth=4, nbin=16, loss="softprob",
              num_class=7, use_pallas=True, compute_dtype=dtype)
    names = ("gbdt.features_packed", "gbdt.features_lane",
             "gbdt.kernel_calls_lane", "gbdt.kernel_calls")
    plans = []
    rule = hk.pack_plan
    monkeypatch.setattr(hk, "pack_plan",
                        lambda cuts: plans.append(rule(cuts)) or plans[-1])
    arm("device")
    with_plan, counted = _counted(names, lambda: boosting.train(X, y, **kw))
    plan, codes = plans[0]
    assert plan.narrow == tuple(range(3, 14)) and plan.width == 4
    assert codes.shape == (11, 4)
    # two rounds of four levels, each one lane-wide call of seven trees
    assert counted == [8 * 11, 8 * 14, 8, 8]

    monkeypatch.setattr(hk, "pack_plan", lambda cuts: None)
    arm("device")
    without, counted = _counted(names, lambda: boosting.train(X, y, **kw))
    assert counted == [0, 8 * 14, 8, 8]
    assert pickle.dumps(with_plan) == pickle.dumps(without)
    assert len(with_plan.trees) == 14
    assert any(n.feature >= 3 for t in with_plan.trees for n in t)


@pytest.mark.parametrize("which,kw,lane", [
    ("device", {"loss": "softprob", "num_class": 7, "use_pallas": True}, 4),
    ("device", {"tree_method": "approx", "use_pallas": True,
                "max_depth": 5}, 1),
    ("host", {"loss": "softprob", "num_class": 7, "use_pallas": True,
              "max_depth": 5}, None),
    ("device", {"loss": "softprob", "num_class": 7, "use_pallas": False}, 0),
], ids=["continuous", "approx-on-indicator-rows", "host-arm", "xla-path"])
def test_a_job_without_a_plan_counts_no_packed_feature(arm, which, kw, lane):
    """A shard of continuous columns has no narrow feature; an ``approx``
    job's cuts are every round's own, whatever the columns; the host arm
    builds a tree a call and takes no plan; the XLA path has no kernel.
    ``gbdt.features_lane`` and ``gbdt.features_two_level`` still count
    what each body's calls built."""
    names = ("gbdt.features_packed", "gbdt.features_lane",
             "gbdt.kernel_calls_lane", "gbdt.features_two_level",
             "gbdt.kernel_calls")
    if "tree_method" in kw:
        X, y = _indicator_rows()
        y = (y > 2).astype(np.float32)
    else:
        X, y = _classes(n=1500, f=6, k=7)
    kw = dict({"num_round": 1, "max_depth": 4, "nbin": 16}, **kw)
    arm(which)
    _, counted = _counted(names, lambda: boosting.train(X, y, **kw))
    if lane is None:
        # a call a tree that builds 7 slots or more of the widest level
        lane = counted[2]
        assert 0 < lane <= 7
    assert counted[:3] == [0, lane * X.shape[1], lane]
    # the other calls' features: the two-level body's (the whole of
    # ``gbdt_packed_feature_pct`` is both bodies')
    assert counted[3] == (counted[4] - lane) * X.shape[1]
    assert (counted[4] > 0) == kw["use_pallas"]


def _without_frames(text: str) -> str:
    """A compiled program's text without the table of the stack frames
    its operations name (the caller's lines are in it)."""
    if "StackFrames" in text:
        head, rest = text.split("\n", 1)
        text = head + rest[rest.index("\n\n", rest.index("StackFrames")):]
    return re.sub(r"stack_frame_id=\d+", "", text)


def test_a_shard_without_a_plan_lowers_to_the_programs_of_before(
        arm, monkeypatch):
    """The level programs of a shard with no narrow feature, and of an
    ``approx`` job on indicator rows, are those of a shard for which the
    rule is never asked: the same lowered text, no operand more."""
    from rabit_tpu.ops import histogram_kernel as hk

    def texts(X, y, **kw):
        monkeypatch.setattr(boosting, "_PROGRAMS", {})
        model = boosting.BoostedModel(
            cuts=histogram.quantile_cuts(X, 16), base_score=0.5,
            loss=kw.get("loss", "logistic"), num_class=kw.get("k", 1),
            tree_method=kw.get("tree_method", "hist"))
        shard = boosting._DeviceShard(X, y, model, 4, 16, 1.0, 0, True, None,
                                      scan=(1.0, 1.0))
        shard.start(False)
        assert shard.pack is None and shard.codes == ()
        return {p: _without_frames(fn.as_text())
                for p, fn in shard.prog["level"].items()}

    arm("device")
    X, y = _classes(n=700, f=6, k=7)
    Xi, yi = _indicator_rows(n=700)
    got = [texts(X, y, loss="softprob", k=7),
           texts(Xi, (yi > 2).astype(np.float32), tree_method="approx")]

    def never(cuts):
        raise AssertionError("the rule is not asked")

    monkeypatch.setattr(hk, "pack_plan", lambda cuts: None)
    assert got[0] == texts(X, y, loss="softprob", k=7)
    monkeypatch.setattr(hk, "pack_plan", never)
    assert got[1] == texts(Xi, (yi > 2).astype(np.float32),
                           tree_method="approx")

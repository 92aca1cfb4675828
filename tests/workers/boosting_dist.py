"""Worker: distributed GBDT — every rank must end with the identical
model (split decisions are taken on the allreduced histogram), and the
ensemble must fit the XOR function no single stump can.

argv: <data_dir with X.npy / y.npy>

``BOOST_NUM_CLASS`` > 1 runs the multi-class objective (``softprob``,
labels class ids): a round commits that many trees.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 1)


import numpy as np

import rabit_tpu
from rabit_tpu.learn import boosting


def watch_built():
    """Record which level slots every ``shard.level`` call builds and
    the hessian sum of every node's histogram a level decides on, by
    round."""
    builds, weights, held = [], [], []
    shard = boosting._HostShard
    grad_hess, level = shard.grad_hess, shard.level
    decide, grow = boosting.decide_level, boosting._grow

    def seen_grad_hess(self, round_idx):
        builds.append([])
        weights.append({})
        return grad_hess(self, round_idx)

    def seen_level(self, build, depth):
        builds[-1].append(list(build))
        return level(self, build, depth)

    def seen_decide(hists, *a):
        # the last feature row: a feature nobody misses, or, with
        # missing values, the node's totals in its bin 0
        held[:] = [np.asarray(hists)[:, -1, :, 1].sum(axis=1)]
        return decide(hists, *a)

    def seen_grow(trees, slots, *a):
        assert len(trees) == 1, "one tree a round"
        weights[-1].update((nid, float(held[0][s]))
                           for s, nid in enumerate(slots) if nid >= 0)
        return grow(trees, slots, *a)

    shard.grad_hess, shard.level = seen_grad_hess, seen_level
    boosting.decide_level, boosting._grow = seen_decide, seen_grow
    return builds, weights


def check_built(model, builds, weights) -> None:
    """Each built slot holds the child with the smaller reduced hessian
    sum, and every rank built the same slots."""
    assert len(builds) == len(model.trees), (len(builds), len(model.trees))
    pairs = 0
    for tree, levels, weight in zip(model.trees, builds, weights):
        slots = [0]
        for depth, build in enumerate(levels):
            for s in build:
                if depth and s >= 0:
                    assert weight[slots[s]] <= weight[slots[s ^ 1]], (
                        depth, s, weight[slots[s]], weight[slots[s ^ 1]])
                    pairs += 1
            slots = [c for nid in slots for c in (
                (tree[nid].left, tree[nid].right)
                if nid >= 0 and tree[nid].feature >= 0 else (-1, -1))]
    assert pairs >= len(model.trees)
    mine = np.array([s for levels in builds for build in levels
                     for s in build + [-2]], np.float64)
    for theirs in rabit_tpu.allgather(mine):
        np.testing.assert_array_equal(theirs, mine)


def main() -> int:
    data_dir = sys.argv[1]
    rabit_tpu.init()
    rank = rabit_tpu.get_rank()
    world = rabit_tpu.get_world_size()

    X = np.load(os.path.join(data_dir, "X.npy"))
    y = np.load(os.path.join(data_dir, "y.npy"))
    Xs, ys = X[rank::world], y[rank::world]

    subsample = float(os.environ.get("BOOST_SUBSAMPLE", "1.0"))
    min_acc = float(os.environ.get("BOOST_MIN_ACC", "0.9"))
    watched = watch_built() if os.environ.get("BOOST_CHECK_BUILT") else None
    tree_method = os.environ.get("BOOST_TREE_METHOD", "hist")
    num_class = int(os.environ.get("BOOST_NUM_CLASS", "1"))
    model = boosting.train(
        Xs, ys, num_round=15, max_depth=3, nbin=16, subsample=subsample,
        tree_method=tree_method, num_class=num_class,
        loss="softprob" if num_class > 1 else "logistic")
    if watched:
        check_built(model, *watched)
    assert model.tree_method == tree_method
    assert model.num_class == num_class
    assert len(model.trees) == 15 * num_class
    if tree_method == "approx":
        # every tree on cuts of its own, the same on every rank
        assert len(model.tree_cuts) == len(model.trees) == 15
        mine = np.asarray(model.tree_cuts, np.float64)
        for theirs in rabit_tpu.allgather(mine):
            np.testing.assert_array_equal(theirs, mine)
    if os.environ.get("BOOST_SAVE"):
        # what the job committed, for the test to hold two jobs together
        np.savez(os.path.join(data_dir, "%s-%d.npz" % (
            os.environ["BOOST_SAVE"], rank)),
            nodes=np.array([(t, n.feature, n.bin_threshold, n.default_left,
                             n.left, n.right, n.value, n.split)
                            for t, tree in enumerate(model.trees)
                            for n in tree], np.float64),
            cuts=np.asarray(getattr(model, "tree_cuts", []), np.float32))

    # identical predictions everywhere (same model on every rank);
    # with missing values this also pins the learned default directions
    pred = model.predict(X).astype(np.float64)
    gathered = rabit_tpu.allgather(pred)
    for r in range(world):
        np.testing.assert_allclose(gathered[r], pred, rtol=1e-6)

    if num_class > 1:
        assert pred.shape == (len(y), num_class)
        acc = (pred.argmax(axis=1) == y).mean()
    else:
        acc = ((pred > 0.5) == (y > 0.5)).mean()
    assert acc > min_acc, acc
    rabit_tpu.tracker_print(
        f"boosting_dist rank {rank}/{world} acc={acc:.3f} OK")
    rabit_tpu.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())

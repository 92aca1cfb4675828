"""A boosting level decided slot by slot, as ``boosting.train`` decided
it before a level was one pass (PR 50): ``histogram.best_split`` a slot
and the arithmetic of the ``_split`` of then, kept here as the reference
that ``boosting.decide_level`` and the loop's tables and trees are held
to bit for bit.  Not a test file: the boosting tests import it."""
import numpy as np

from rabit_tpu.learn import boosting, histogram
from rabit_tpu.learn.boosting import TreeNode


def scan(hist, reg_lambda, min_child_weight, has_missing, widths=None):
    """``(gain, row, cut, default_left)`` of one slot's ``(rows, nbin,
    2)`` float64 histogram; with ``has_missing`` its last row holds the
    node's totals in bin 0."""
    if has_missing:
        return histogram.best_split(hist[:-1], reg_lambda, min_child_weight,
                                    hist[-1, 0], widths)
    return histogram.best_split(hist, reg_lambda, min_child_weight, None,
                                widths)


def split(node, tree, hist, reg_lambda, min_child_weight, has_missing,
          best=None, features=None):
    """Choose ``node``'s split on its histogram or leave it a leaf
    (None); returns the child the next level builds, 0 left or 1
    right."""
    gain, j, t, dl = best or scan(hist, reg_lambda, min_child_weight,
                                  has_missing)
    if has_missing:
        hist, total = hist[:-1], hist[-1, 0]
    g_tot, h_tot = hist[j].sum(axis=0, dtype=np.float64)
    gl, hl = hist[j, :t + 1].sum(axis=0, dtype=np.float64)
    if has_missing:
        gm, hm = histogram.missing_mass(hist[j:j + 1], total)[0]
        g_tot, h_tot = g_tot + gm, h_tot + hm
        if dl:
            gl, hl = gl + gm, hl + hm
    gr, hr = g_tot - gl, h_tot - hl
    if gain <= 1e-12:
        node.value = float(-g_tot / (h_tot + reg_lambda))
        return None
    node.feature = int(j if features is None else features[j])
    node.bin_threshold = int(t)
    node.default_left, node.value = dl, 0.0
    node.left, node.right = len(tree), len(tree) + 1
    tree.append(TreeNode(value=float(-gl / (hl + reg_lambda))))
    tree.append(TreeNode(value=float(-gr / (hr + reg_lambda))))
    return int(hr < hl)


def as_the_loop_held(hists) -> np.ndarray:
    """A level's histograms as the loop of then held them: float64,
    ``(slots, rows, nbin, 2)`` in C order."""
    return np.asarray(hists).astype(np.float64, order="C")


def grow_level(trees, slots, leaves, hists, reg_lambda, min_child_weight,
               has_missing, features=None, widths=None):
    """One level of the loop of then on ``as_the_loop_held`` histograms:
    every slot scanned, every node split or left a leaf, the rows
    routed.  Returns ``(tabs, build, next slots, scans)``."""
    features = [None] * len(slots) if features is None else features
    widths = [None] * len(slots) if widths is None else widths
    best = [scan(hist, reg_lambda, min_child_weight, has_missing, width)
            for hist, width in zip(hists, widths)]
    build = [-1] * len(slots)
    width = len(slots) // len(trees)
    for s, nid in enumerate(slots):
        if nid < 0:
            continue
        tree = trees[s // width]
        side = split(tree[nid], tree, hists[s], reg_lambda,
                     min_child_weight, has_missing, best[s], features[s])
        if side is not None:
            build[s] = 2 * s + side
    tabs, slots = boosting._route_round(trees, slots, leaves)
    return tabs, build, slots, best


def decide_level(hists, reg_lambda, min_child_weight, has_missing,
                 features=None, widths=None) -> boosting.LevelSplits:
    """``boosting.decide_level``'s answer made slot by slot (a slot that
    stays a leaf has no children: their weights read NaN, its side 0)."""
    hists = as_the_loop_held(hists)
    out = []
    for s, hist in enumerate(hists):
        best = scan(hist, reg_lambda, min_child_weight, has_missing,
                    None if widths is None else widths[s])
        tree = [TreeNode()]
        side = split(tree[0], tree, hist, reg_lambda, min_child_weight,
                     has_missing, best)
        gain, j, t, dl = best
        kids = [n.value for n in tree[1:]] or [np.nan, np.nan]
        out.append((gain, j if features is None else features[s][j], t, dl,
                    side or 0, tree[0].value, *kids))
    return boosting.LevelSplits(*(np.array(col) for col in zip(*out)))


def watch_loop(monkeypatch) -> list:
    """Record, in order, what ``train``'s level loop hands over: every
    ``shard.level`` as ``("level", depth)``, every ``rabit_tpu.allreduce``
    the module issues as ``("allreduce",)``, every ``shard.partition`` as
    ``("partition", depth, tables)`` and every ``_grow`` as
    ``("trees",)``."""
    events = []

    def seen(kind, fn, keep=lambda a: ()):
        def wrapper(*a, **kw):
            events.append((kind,) + keep(a))
            return fn(*a, **kw)
        return wrapper

    for cls in (boosting._HostShard, boosting._DeviceShard,
                boosting._SparseShard):
        monkeypatch.setattr(cls, "level", seen(
            "level", cls.level, lambda a: (a[2],)))
        monkeypatch.setattr(cls, "partition", seen(
            "partition", cls.partition, lambda a: (a[2], np.array(a[1]))))
    monkeypatch.setattr(boosting, "_grow", seen("trees", boosting._grow))

    class Api:
        """``rabit_tpu`` as the boosting module sees it, its allreduce
        recorded."""

        def __getattr__(self, name):
            return getattr(api, name)

    api, watched = boosting.rabit_tpu, Api()
    watched.allreduce = seen("allreduce", api.allreduce)
    monkeypatch.setattr(boosting, "rabit_tpu", watched)
    return events


def check_loop(events, model, max_depth: int) -> None:
    """What :func:`watch_loop` recorded of a job run from its first
    round, against its committed forest: a level's program,
    then its one allreduce, its row move, **then the next level's
    program and allreduce and only then this level's trees**; and every
    row-move table is ``_route_round``'s of the trees as committed."""
    groups = model.num_class
    at = iter(events)
    # has_missing, agreed once, before the first round
    assert next(at) == ("allreduce",)
    for t in range(0, len(model.trees), groups):
        trees = model.trees[t:t + groups]
        slots, leaves = [0] * groups, [[] for _ in trees]
        if model.tree_method == "approx":
            assert next(at) == ("allreduce",)           # the summaries
        assert next(at) == ("level", 0) and next(at) == ("allreduce",)
        for depth in range(max_depth):
            tabs, slots = boosting._route_round(trees, slots, leaves)
            kind, d, seen = next(at)
            assert (kind, d) == ("partition", depth)
            np.testing.assert_array_equal(seen.reshape(tabs.shape), tabs)
            assert seen.dtype == tabs.dtype
            more = depth + 1 < max_depth and any(nid >= 0 for nid in slots)
            if more:
                assert next(at) == ("level", depth + 1)
                assert next(at) == ("allreduce",)
            assert next(at) == ("trees",)
            if not more:
                break
    assert next(at, None) is None


def held_to_the_loop_of_then(arm, which, monkeypatch, values, labels,
                             **kw) -> boosting.BoostedModel:
    """Train the job on the arm ``which`` twice, decided slot by slot
    and by the pass: the same forest node for node and bit for bit, its
    hand-overs in the order :func:`check_loop` holds them to."""
    arm(which)
    with monkeypatch.context() as patch:
        patch.setattr(boosting, "decide_level", decide_level)
        plain = boosting.train(values, labels, **kw)
    arm(which)
    with monkeypatch.context() as patch:
        events = watch_loop(patch)
        model = boosting.train(values, labels, **kw)
    assert forest(model) == forest(plain)
    check_loop(events, model, kw["max_depth"])
    return model


def forest(model) -> list:
    """A committed forest node for node, in the trees' own order, every
    weight to its last bit."""
    return [[(n.feature, n.bin_threshold, n.default_left, n.left, n.right,
              float(n.value).hex(), float(n.split).hex()) for n in tree]
            for tree in model.trees]

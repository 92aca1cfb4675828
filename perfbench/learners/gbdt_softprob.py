"""Adapter for multi-class boosting (XGBoost's ``multi:softprob``) on
UCI Covertype's schema: the same learner and entry point as
``learners/gbdt.py`` (``rabit_tpu.learn.boosting.train``; a version is a
boosting round), with ``loss="softprob"`` and ``num_class=7``: a round
commits seven trees, grown level by level together, from one softmax
gradient of the margins the round before left, which the reference
(``perfbench/reference/gbdt_softprob.py``) replays a round at a time,
tree by tree.  The six functions are those ``learners/kmeans.py`` lists;
the watch on the staging, the kernel, the commits and the compiles is
``learners/gbdt.py``'s own, as are the rehearsal's rules (kernel
interpreted, depth 3 off the chip).  The rows are this file's: synthetic
on Covertype's 54 columns (``make_rows``).

Nothing here reads a private name of the program: the gradients are
what the program ``boosting.softprob_grad_program`` built returned, the
forest is what ``load_checkpoint`` gave.

``--grid`` names the control of ``correct``: a float grid (the kernel's
weight operand rounded to it, as in the HIGGS cell) or ``one_vs_rest``
(the round's gradients are ``sigmoid(m_k) - [y = k]`` and ``p (1 - p)``
a class: what a binary objective a class would do, and what this cell is
there to tell from a softmax).
"""
from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import harness
from perfbench.reference import gbdt as ref
from perfbench.reference import gbdt_softprob as refs

# the one instance the harness and the tests' steering files know
gbdt = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "gbdt.py"))

ONE_VS_REST = "one_vs_rest"
GRAD_CHECK_ROWS = 1 << 16   # gradients read back: the head of the shard
# a rehearsal off the chip holds at most this many rows: a round is 21
# interpreted kernel calls over 56 staged columns there (seven trees of
# three levels), thirteen times the HIGGS cell's, and the tests' 1.5 s
# window has to hold one on a busy machine
REHEARSAL_MAX_ROWS = 4096

# ----------------------------------------------------------------------
# data: Covertype's schema
# ----------------------------------------------------------------------
QUANT, WILD, SOIL, CLASSES = 10, 4, 40, 7
FEATURES = QUANT + WILD + SOIL
# the quantitative columns (elevation, aspect, slope, three distances,
# three hillshades, a distance) lie on integer grids of this many levels
LEVELS = (2000, 360, 200, 1400, 775, 4000, 255, 255, 255, 4000)
# ... around this level, this many levels a unit of the normal score
CENTRE = (0.50, 0.50, 0.25, 0.20, 0.25, 0.30, 0.80, 0.85, 0.55, 0.28)
SPREAD = (0.14, 0.29, 0.13, 0.15, 0.08, 0.20, 0.10, 0.08, 0.15, 0.18)
WILD_SHARE = (0.449, 0.051, 0.436, 0.064)
# soil types by a power law: the first five hold 3/4 of the rows, the
# last dozen under 2e-4 of them each (a few hundred to 1,700 rows of
# 8.4M: columns that are nearly empty, whose cuts are all one value)
SOIL_POWER = 2.5
SOIL_GROUPS = 5             # soil type i is of group i % 5
TARGET_SHARE = (0.365, 0.488, 0.062, 0.005, 0.016, 0.030, 0.035)
# the fixed function: a class's logit is its bias, its weights on six
# terms of the first six quantitative columns' normal scores, and its
# entries of the wilderness and the soil-group tables
TERMS = 6
WEIGHTS = np.array([
    # z0     z0^2   z1*z2  sin2z3  z4>.4  |z5|
    [1.60, -0.30, 0.30, 0.20, -0.20, 0.10],      # spruce/fir: high
    [0.20, -0.90, -0.20, -0.10, 0.20, 0.00],     # lodgepole: the middle
    [-2.40, -0.40, 0.40, 0.30, 0.10, -0.30],     # ponderosa: low
    [-3.20, -0.60, -0.30, 0.50, 0.60, 0.20],     # cottonwood: lowest
    [-0.30, -1.20, 0.60, -0.60, -0.40, 0.50],    # aspen
    [-1.90, -0.50, -0.60, -0.30, 0.50, 0.40],    # douglas-fir
    [3.00, 0.20, 0.20, 0.40, -0.50, -0.40],      # krummholz: highest
], np.float32)
WILD_TABLE = np.array([
    [0.40, -0.30, 0.10, -1.20], [0.20, 0.30, 0.00, -0.60],
    [-1.50, -2.00, 0.30, 1.20], [-3.00, -3.00, -3.00, 1.50],
    [0.30, -2.00, 0.40, -2.00], [-1.00, -2.00, 0.50, 1.00],
    [0.30, 0.60, 0.20, -3.00]], np.float32)
SOIL_TABLE = np.array([
    [0.30, -0.20, 0.10, 0.00, -0.40], [0.10, 0.20, -0.10, 0.00, 0.10],
    [-0.50, 0.60, 0.00, 0.40, -0.30], [0.80, -0.60, 0.30, -0.40, 0.50],
    [-0.20, 0.30, 0.50, -0.50, 0.00], [0.40, 0.00, -0.60, 0.30, 0.20],
    [-0.30, -0.10, 0.40, 0.20, 0.30]], np.float32)
# set once, so that the classes' shares over many rows are TARGET_SHARE
# to the third decimal
BIAS = np.array([-0.939, 0.0, -4.235, -7.602, -3.886, -5.592, -5.156],
                np.float32)


def soil_share() -> np.ndarray:
    share = np.arange(1, SOIL + 1, dtype=np.float64) ** -SOIL_POWER
    return share / share.sum()


def class_logits(z: np.ndarray, wild: np.ndarray, soil: np.ndarray
                 ) -> np.ndarray:
    """``(rows, 7)`` float32: the one fixed function, the same for every
    seed, of the normal scores ``z`` (rows, >= 6) of the first six
    quantitative columns, the wilderness area and the soil type (by its
    group) of each row."""
    terms = np.stack([z[:, 0], z[:, 0] * z[:, 0], z[:, 1] * z[:, 2],
                      np.sin(2.0 * z[:, 3]), z[:, 4] > 0.4,
                      np.abs(z[:, 5])], axis=1).astype(np.float32)
    return (terms @ WEIGHTS.T + WILD_TABLE.T[wild]
            + SOIL_TABLE.T[soil % SOIL_GROUPS] + BIAS)


def make_rows(seed: int, shard: int, n: int, f: int, threads: int):
    """``(n, 54)`` float32 values on Covertype's schema and ``(n,)``
    float32 class ids in [0, 7).  Columns 0-9 are quantitative, each the
    normal score of the row shaped onto an integer grid of ``LEVELS[j]``
    levels (clipped at both ends, so the ends pile up as a real
    column's do); columns 10-13 hold the wilderness area and 14-53 the
    soil type, one of each set to 1 a row.  The label is a draw from the
    softmax of ``class_logits``.  A block of 2^18 rows has a generator
    of its own, so the rows are a function of ``(seed, shard)`` alone,
    not of the thread count."""
    if f != FEATURES:
        raise ValueError(f"make_rows: Covertype's schema has {FEATURES} "
                         f"columns, the configuration asks for {f}")
    values = np.zeros((n, f), np.float32)
    labels = np.empty(n, np.float32)
    wild_edges = np.cumsum(WILD_SHARE)[:-1]
    soil_edges = np.cumsum(soil_share())[:-1]
    levels = np.asarray(LEVELS, np.float32)
    centre = levels * np.asarray(CENTRE, np.float32)
    spread = levels * np.asarray(SPREAD, np.float32)

    def fill(lo: int) -> None:
        hi = min(n, lo + gbdt.GEN_BLOCK)
        rng = np.random.default_rng([seed, shard, n, f, lo])
        z = rng.standard_normal((hi - lo, QUANT), dtype=np.float32)
        u = rng.random((hi - lo, 3), dtype=np.float32)
        wild = np.searchsorted(wild_edges, u[:, 0])
        soil = np.searchsorted(soil_edges, u[:, 1])
        logits = class_logits(z, wild, soil)
        p = np.exp(logits - logits.max(axis=1, keepdims=True))
        below = np.cumsum(p, axis=1) < (u[:, 2] * p.sum(axis=1))[:, None]
        labels[lo:hi] = np.minimum(below.sum(axis=1), CLASSES - 1)
        block = values[lo:hi]
        block[:, :QUANT] = np.clip(np.rint(centre + spread * z), 0.0,
                                   levels - 1.0)
        rows = np.arange(hi - lo)
        block[rows, QUANT + wild] = 1.0
        block[rows, QUANT + WILD + soil] = 1.0

    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(fill, range(0, n, gbdt.GEN_BLOCK)))  # re-raises
    return values, labels


class Data:
    """One rank's shard.  ``grid`` is the float grid of ``learners/
    gbdt.py``'s wrapper of the kernel call (the first control);
    ``one_vs_rest`` is the second, kept apart from it.  The rows are the
    same whatever the control."""

    def __init__(self, cfg: dict, seed: int, shard: int, world: int,
                 threads: int, rows: int | None = None,
                 grid: str | None = None):
        self.n, self.f = rows or cfg["rows_per_chip"], cfg["features"]
        if rows and not gbdt.on_chip():
            self.n = min(rows, REHEARSAL_MAX_ROWS)
            print("perfbench gbdt_softprob: a rehearsal off the chip, the "
                  "histogram kernel interpreted and trees stopped at depth "
                  f"{gbdt.REHEARSAL_MAX_DEPTH} (learners/gbdt.py on_chip), "
                  f"not the configuration's {cfg['max_depth']}, on "
                  f"{self.n} rows of the {rows} asked for",
                  file=sys.stderr, flush=True)
        self.seed, self.shard, self.world = seed, shard, world
        self.one_vs_rest = grid == ONE_VS_REST
        self.grid = None if self.one_vs_rest else grid
        self.values, self.labels = make_rows(seed, shard, self.n, self.f,
                                             threads)
        self.seen = {}


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    from rabit_tpu.learn import boosting

    if not hasattr(boosting, "softprob_grad_program"):
        # the parent of the PR that added the cell: its train() holds
        # one margin a row and one tree a round
        raise harness.Refused(
            "this program cannot run the multi-class cell: "
            "rabit_tpu.learn.boosting has no softprob_grad_program")
    return Data(cfg, seed, shard, world, threads, rows, grid)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------
def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """A version is a boosting round, seven trees, over every row of
    every rank.  The kernels' shape carries the number of classes, for
    the softmax gradient's cost."""
    about = gbdt.describe(cfg, traffic, data)
    about["kernel_shape"]["num_class"] = cfg["num_class"]
    return about


def watch(data: Data, spans, trace: bool) -> list:
    """``learners/gbdt.py watch`` (what ``histogram.stage_bins`` staged,
    the kernels handed to ``pallas_call``, the commits, the compile
    requests, the kernel's operand under a float ``--grid``) and this
    cell's own eye: what the program ``boosting.softprob_grad_program``
    built returned, the head ``GRAD_CHECK_ROWS`` rows of the newest
    call, held as one small device array and read back in
    ``check`` (a slice of a few megabytes enqueued behind the program, a
round).  Under ``--grid one_vs_rest`` that program is replaced by
    one sigmoid a class."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.learn import boosting

    undo = list(gbdt.watch(data, spans, trace))
    seen, program_fn = data.seen, boosting.softprob_grad_program
    rows = min(GRAD_CHECK_ROWS, data.n)

    def one_vs_rest(margin, labels, *keep):
        p = 1.0 / (1.0 + jnp.exp(-margin))
        hit = labels[None, :] == jnp.arange(
            margin.shape[0], dtype=jnp.float32)[:, None]
        gh = jnp.stack([p - hit, p * (1.0 - p)], axis=1)
        return jnp.where(keep[0], gh, 0.0) if keep else gh

    def seen_grad_program(*a, **kw):
        fn = jax.jit(one_vs_rest) if data.one_vs_rest else program_fn(
            *a, **kw)

        def grad(margin, labels, *keep):
            gh = fn(margin, labels, *keep)
            seen["newest_grad"] = gh[:, :, :rows]
            seen["grad_calls"] = seen.get("grad_calls", 0) + 1
            return gh

        return grad

    boosting.softprob_grad_program = seen_grad_program
    undo.append((boosting, "softprob_grad_program", program_fn))
    return undo


def run_job(cfg: dict, traffic: dict, data: Data) -> None:
    """The job, through the entry point a user calls.  Returns only by
    the commit wrapper's ``WindowClosed``."""
    from rabit_tpu.learn import boosting

    seen = data.seen
    if seen["jobs"] == 1:
        # a second job (the traced run's resume) has gradients of its
        # own: the timed job's newest are kept for ``check``
        seen["timed_grad"] = seen.pop("newest_grad", None)
    seen["jobs"] += 1
    boosting.train(
        data.values, data.labels, num_round=gbdt.NUM_ROUND,
        max_depth=gbdt.depth_of(cfg), nbin=cfg["max_bin"],
        learning_rate=cfg["learning_rate"], reg_lambda=cfg["reg_lambda"],
        loss=cfg["loss"], min_child_weight=cfg["min_child_weight"],
        subsample=cfg["subsample"], seed=data.seed,
        tree_method=cfg["tree_method"], num_class=cfg["num_class"])


def committed(model) -> dict:
    """``learners/gbdt.py committed`` of the forest ``load_checkpoint``
    gave (its trees round-major, a class after another), the number of
    classes and the base score it states."""
    out = gbdt.committed(model)
    out["num_class"] = np.array([model.num_class], np.int32)
    out["base_score"] = np.array([model.base_score], np.float32)
    return out


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
def grad_err(got: np.ndarray, want: np.ndarray) -> float:
    """The largest |got - want| over (|want| + 1e-4): relative, with a
    floor under the gradients of a class a row all but rules out."""
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want) / (np.abs(want) + 1e-4)))


def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """The committed forest's first and last round replayed by the plain
    reference, each of the round's seven trees walked on the softmax
    gradients of the forest without the round (the worst of each number
    over the trees); the gradients the program made from the whole
    committed forest (the round it had opened when the window closed)
    against the reference's, on the head of the shard; the committed
    cuts against the quantiles of the stated sample, the staged bins
    read back against the reference's ``searchsorted``, and what
    ``watch`` saw of the tier, the kernel and the compiles."""
    seen, nbin, K = data.seen, cfg["max_bin"], cfg["num_class"]
    forest_int, forest_val = committed["forest_int"], committed["forest_val"]
    rounds = len(forest_int) // K
    base = float(committed["base_score"][0])
    shard_of_rank = [int(x[0]) for x in exchange(
        "shard", np.array([data.shard]))]
    cuts = committed["cuts"]
    cuts_gap = 0.0
    if shard_of_rank[0] == data.shard:        # rank 0's rows give the cuts
        want = ref.quantile_cuts(ref.cut_sample(
            data.values, cfg["cut_sample_rows"]), nbin)
        cuts_gap = float(np.max(np.abs(cuts - want))) \
            if cuts.shape == want.shape else float("inf")
    bin_gap = float("inf")                    # nothing staged: no bins
    if "bins_head" in seen:
        rows = seen["bins_head"].shape[1]
        bin_gap = float(
            np.count_nonzero(seen["bins_head"] != ref.bin_rows(
                data.values[:rows], cuts))
            + np.count_nonzero(seen["bins_tail"] != ref.bin_rows(
                data.values[-rows:], cuts)))
    # ---- the gradients of the round the job had opened last
    grad_gap = float("inf")                   # no gradient seen
    newest = seen.pop("timed_grad", None)
    if newest is None:
        newest = seen.pop("newest_grad", None)
    if newest is not None and len(forest_int) == rounds * K \
            and committed["num_class"][0] == K:
        gh = np.asarray(newest)
        rows = gh.shape[2]
        grad_gap = grad_err(gh, refs.head_grad_hess(
            data.values[:rows], data.labels[:rows], cuts, forest_int,
            forest_val, K, nbin, gbdt.depth_of(cfg), cfg["learning_rate"],
            base))
    del newest
    got = refs.replay(
        data.values, data.labels, cuts, forest_int, forest_val,
        [0, rounds - 1], K, nbin, gbdt.depth_of(cfg), cfg["learning_rate"],
        base, cfg["reg_lambda"], cfg["min_child_weight"],
        cfg["compute_dtype"], lambda tag, a: np.sum(exchange(tag, a), axis=0))
    warmup = int(traffic.get("warmup_versions", 2))
    timed = [k for job, k in seen["compile_requests"] if job == 1]
    from rabit_tpu import engine

    stats = dict(getattr(engine.get_engine(), "path_stats", {}) or {})
    at = seen["commit_at"]
    print("perfbench gbdt_softprob saw " + json.dumps({
        "staged": seen["staged"], "mosaic_kernels": seen["mosaic_kernels"],
        "compile_requests": seen["compile_requests"],
        "trees": len(forest_int), "rounds": rounds,
        "grad_calls": seen.get("grad_calls", 0),
        "splits": got["splits"], "leaves": got["leaves"],
        "by_class": [{k: (round(v, 9) if isinstance(v, float) else v)
                      for k, v in c.items()} for c in got["by_class"]],
        "commit_gaps": [round(b - a, 3) for a, b in zip(at, at[1:])],
        "longest": {k[:-len(".max_s")]: round(v, 4) for k, v in stats.items()
                    if k.endswith(".max_s") and k.startswith(
                        ("learn.", "gbdt.", "commit", "allreduce"))},
        "totals": {k[:-len(".total_s")]: round(v, 3)
                   for k, v in stats.items() if k.endswith(".total_s")
                   and k.startswith(("learn.", "gbdt."))},
        "counters": {k: v for k, v in stats.items()
                     if k.startswith("gbdt.") and "." not in k[5:]}}),
        file=sys.stderr, flush=True)
    return {
        "split_regret": got["split_regret"],
        "leaf_sum_rel_err": got["leaf_sum_rel_err"],
        "leaf_sum_rounded_rel_err": got["leaf_sum_rounded_rel_err"],
        "unsplit_above_limit": got["unsplit_above_limit"],
        "softmax_grad_err": grad_gap,
        "trees_per_round_gap": float(abs(len(forest_int) - rounds * K)
                                     + abs(int(committed["num_class"][0])
                                           - K)),
        "cuts_gap": cuts_gap,
        "bin_gap": bin_gap,
        # programs asked of the compiler (built or read from the cache)
        # between the commit that opened the window and the last
        "recompiles_in_window": float(sum(timed[warmup:])),
        "tier_mismatch": float(seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(not seen["mosaic_kernels"]),
    }

"""Adapter for the histogram boosting learner: data from the seed, the
job through the public entry point (``rabit_tpu.learn.boosting.train``),
what a version is (a boosting round), and the comparison with the plain
reference.  The six functions are those ``learners/kmeans.py`` lists.

Nothing here reads a private name of the program: the staged tier is
what ``histogram.stage_bins`` returned, the kernel is what was handed to
``jax.experimental.pallas.pallas_call`` while the job's programs were
traced, the forest is what ``load_checkpoint`` gave.
"""
from __future__ import annotations

import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.reference import gbdt as ref

GEN_BLOCK = 1 << 18
NUM_ROUND = 1 << 30         # train() never stops by itself
BIN_CHECK_ROWS = 1 << 15    # staged bins read back: the first and the last
REHEARSAL_MAX_DEPTH = 3     # see on_chip()
SCALES = (1e-3, 0.05, 1.0, 7.0, 300.0, 2e4, 0.4)


# ----------------------------------------------------------------------
# data
# ----------------------------------------------------------------------
def logit_of(z: np.ndarray) -> np.ndarray:
    """The fixed nonlinear function of 12 of the features' normal
    scores whose logistic a row's label is drawn from: products, steps,
    a square and waves, so that no depth-6 tree exhausts it and every
    node of every round finds a split worth taking."""
    return (1.1 * z[:, 0] * z[:, 1] + 0.9 * np.sin(2.0 * z[:, 2])
            + 0.8 * (z[:, 3] > 0.4) - 0.6 * z[:, 4] * z[:, 4] + 0.5 * z[:, 5]
            + 0.7 * np.abs(z[:, 6]) * (z[:, 7] > 0.0)
            - 0.5 * z[:, 8] * z[:, 9] + 0.4 * np.cos(z[:, 10] + z[:, 11]))


def shape_columns(z: np.ndarray) -> None:
    """In place, from normal scores to features of distinct scales and
    tails: column j is Gaussian, log-normal, cubed (heavy both ways) or
    a signed root (light), by j % 4, times ``SCALES[j % 7]``."""
    for j in range(z.shape[1]):
        col = z[:, j]
        if j % 4 == 1:
            np.exp(col, out=col)
        elif j % 4 == 2:
            np.power(col, 3, out=col)
        elif j % 4 == 3:
            np.multiply(np.sign(col), np.sqrt(np.abs(col)), out=col)
        np.multiply(col, np.float32(SCALES[j % len(SCALES)]), out=col)


def make_rows(seed: int, shard: int, n: int, f: int, threads: int):
    """``(n, f)`` float32 values and ``(n,)`` float32 labels in {0, 1}.
    A block of 2^18 rows has a generator of its own, so the rows are a
    function of ``(seed, shard)`` alone, not of the thread count."""
    if f < 12:
        raise ValueError(f"make_rows: {f} features < the 12 the label uses")
    values = np.empty((n, f), np.float32)
    labels = np.empty(n, np.float32)

    def fill(lo: int) -> None:
        hi = min(n, lo + GEN_BLOCK)
        rng = np.random.default_rng([seed, shard, n, f, lo])
        z = values[lo:hi]
        rng.standard_normal(out=z, dtype=np.float32)
        p = 1.0 / (1.0 + np.exp(-logit_of(z)))
        labels[lo:hi] = rng.random(hi - lo, dtype=np.float32) < p
        shape_columns(z)

    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(fill, range(0, n, GEN_BLOCK)))   # re-raises a failure
    return values, labels


class Data:
    """One rank's shard.  ``grid`` (the control of ``correct``) is the
    float grid the kernel's weight operand is rounded to in ``watch``'s
    wrapper of the kernel call; the rows are the same either way."""

    def __init__(self, cfg: dict, seed: int, shard: int, world: int,
                 threads: int, rows: int | None = None,
                 grid: str | None = None):
        self.n, self.f = rows or cfg["rows_per_chip"], cfg["features"]
        self.seed, self.shard, self.world, self.grid = seed, shard, world, grid
        self.values, self.labels = make_rows(seed, shard, self.n, self.f,
                                             threads)
        self.seen = {}


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    from perfbench.harness import Refused
    from rabit_tpu.learn import histogram

    if not hasattr(histogram, "stage_bins"):
        # the parent of the PR that added the cell: its train() walks
        # every row on the host, once a node
        raise Refused("this program cannot run the boosting cell: "
                      "rabit_tpu.learn.histogram has no stage_bins")
    return Data(cfg, seed, shard, world, threads, rows, grid)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------
def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """A version is a boosting round over every row of every rank."""
    return {"work_per_version": data.n * data.world,
            "kernel_shape": {"rows": data.n, "features": data.f,
                             "nbin": cfg["max_bin"],
                             "max_depth": depth_of(cfg),
                             "ops_dtype": "bfloat16"}}


def on_chip() -> bool:
    """False only in a rehearsal: off the chip the harness refuses to
    run, so what gets here without one is a test of ``tests/perfbench``
    with the CPU passed off for the chip.  The run-x1 tests rehearse
    every one-chip cell under the k-means cells' steering file, which
    knows nothing of this learner's kernel; what a rehearsal of this
    cell needs beyond it is therefore here, in the few lines that ask
    this function: the kernel is interpreted and counts as the Mosaic
    kernel it would be, and trees stop at depth 3, because a depth-6
    round of the interpreted kernel (2.7e11 FLOP at those tests' 16,384
    rows) outlasts their 1.5 s window."""
    import jax

    return jax.local_devices()[0].platform == "tpu"


def depth_of(cfg: dict) -> int:
    return cfg["max_depth"] if on_chip() else min(
        cfg["max_depth"], REHEARSAL_MAX_DEPTH)


def mosaic(kwargs: dict) -> bool:
    """Whether a ``pallas_call`` with these keywords lowers to a Mosaic
    kernel: on a TPU every one does that is not interpreted.  In a
    rehearsal the interpreted kernel stands for it."""
    return not kwargs.get("interpret", False) or not on_chip()


def watch(data: Data, spans, trace: bool) -> list:
    """Wrappers around the calls into the learner's layers; returns the
    undo list.  In every run: what ``histogram.stage_bins`` staged (its
    types, and two blocks of the bins read back), the kernels handed to
    ``pallas_call`` while the job's programs were traced, and the
    compile requests between commits, all kept in ``data.seen`` for
    ``check``.  The harness spans ``stage`` (from the cuts, or from the
    binning on a resume, to ``block_until_ready`` of the staged bins)
    and ``stage_bin`` (the binning alone) are recorded in every run:
    the bins are read back at that point anyway.  Under ``--grid`` the
    kernel call is wrapped: its weight operand is rounded to that grid,
    which is what computing in the next precision below would do."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas

    import rabit_tpu
    from rabit_tpu.learn import histogram
    from rabit_tpu.ops import histogram_kernel as hk
    from rabit_tpu.utils import compile_cache

    cuts_fn, stage_fn, call, commit, kernel = (
        histogram.quantile_cuts, histogram.stage_bins, pallas.pallas_call,
        rabit_tpu.checkpoint, hk.hist_fused_multi)
    seen = data.seen = {"staged": None, "mosaic_kernels": [], "jobs": 0,
                        "compile_requests": [], "commit_at": []}
    state = {}
    compiles = compile_cache.count_compiles()

    def seen_cuts(*a, **kw):
        state.setdefault("stage", spans.begin("stage"))
        return cuts_fn(*a, **kw)

    def seen_stage(values, *a, **kw):
        state.setdefault("stage", spans.begin("stage"))
        token = spans.begin("stage_bin")
        staged = stage_fn(values, *a, **kw)
        arrays = [x for x in jax.tree_util.tree_leaves(staged)
                  if isinstance(x, jax.Array)]
        jax.block_until_ready(arrays)
        spans.end("stage_bin", token)
        spans.end("stage", state.pop("stage"))
        stats = jax.local_devices()[0].memory_stats() or {}
        spans.counters.setdefault("peak_bytes_after_stage", int(
            stats.get("peak_bytes_in_use", 0)))
        seen["staged"] = staged_dtypes(arrays)
        bins_t = max(arrays, key=lambda x: x.nbytes)
        rows = min(BIN_CHECK_ROWS, values.shape[0])
        seen["bins_head"] = np.asarray(bins_t[:values.shape[1], :rows])
        seen["bins_tail"] = np.asarray(bins_t[:values.shape[1], -rows:])
        return staged

    def seen_call(kernel_fn, *a, **kw):
        if mosaic(kw):
            seen["mosaic_kernels"].append(kw.get("name") or getattr(
                getattr(kernel_fn, "func", kernel_fn), "__name__", "?"))
        return call(kernel_fn, *a, **kw)

    def seen_commit(*a, **kw):
        if compiles is not None:
            took = compiles.take()
            seen["compile_requests"].append(
                (seen["jobs"], took["misses"] + took["hits"]))
        seen["commit_at"].append(round(time.perf_counter(), 3))
        return commit(*a, **kw)

    def wrapped_kernel(bins_t, weights, *a, **kw):
        if data.grid:
            # lax.reduce_precision, not a cast there and back: the TPU
            # pipeline drops an f32 -> f8 -> f32 round trip as excess
            # precision, and the control then computes in bf16 (PR 26's
            # first control runs read exactly as the sound ones)
            grid = jnp.finfo(getattr(jnp, data.grid))
            weights = jax.lax.reduce_precision(
                jnp.asarray(weights), grid.nexp, grid.nmant)
        if not on_chip():               # a rehearsal: the CPU interprets
            kw["interpret"] = True
        return kernel(bins_t, weights, *a, **kw)

    histogram.quantile_cuts = seen_cuts
    histogram.stage_bins = seen_stage
    pallas.pallas_call = seen_call
    rabit_tpu.checkpoint = seen_commit
    undo = [(histogram, "quantile_cuts", cuts_fn),
            (histogram, "stage_bins", stage_fn),
            (pallas, "pallas_call", call),
            (rabit_tpu, "checkpoint", commit)]
    if data.grid or not on_chip():
        hk.hist_fused_multi = wrapped_kernel
        undo.append((hk, "hist_fused_multi", kernel))
    return undo


def staged_dtypes(arrays) -> list[str]:
    """The types that hold the staged shard: of every array with a
    tenth or more of the staged bytes, sorted."""
    total = sum(x.nbytes for x in arrays)
    return sorted({str(x.dtype) for x in arrays if 10 * x.nbytes >= total})


def run_job(cfg: dict, traffic: dict, data: Data) -> None:
    """The job, through the entry point a user calls.  Returns only by
    the commit wrapper's ``WindowClosed``."""
    from rabit_tpu.learn import boosting

    data.seen["jobs"] += 1
    boosting.train(
        data.values, data.labels, num_round=NUM_ROUND,
        max_depth=depth_of(cfg), nbin=cfg["max_bin"],
        learning_rate=cfg["learning_rate"], reg_lambda=cfg["reg_lambda"],
        loss=cfg["loss"], min_child_weight=cfg["min_child_weight"],
        subsample=cfg["subsample"], seed=data.seed)


def committed(model) -> dict:
    """The forest ``load_checkpoint`` gave, as arrays: a row a node,
    ``(feature, bin_threshold, default_left, left, right)`` and the leaf
    weight, trees padded to the largest with feature -2."""
    nodes = max(len(t) for t in model.trees)
    forest_int = np.full((len(model.trees), nodes, 5), -2, np.int32)
    forest_val = np.zeros((len(model.trees), nodes), np.float32)
    for t, tree in enumerate(model.trees):
        for i, node in enumerate(tree):
            forest_int[t, i] = (node.feature, node.bin_threshold,
                                node.default_left, node.left, node.right)
            forest_val[t, i] = node.value
    return {"cuts": np.asarray(model.cuts, np.float32),
            "forest_int": forest_int, "forest_val": forest_val,
            "has_missing": np.array([model.has_missing], np.int32)}


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """The committed forest replayed one step by the plain reference
    (its first and its last tree, so that every timed round is held
    against two), the committed cuts against the quantiles of the stated
    sample, the staged bins read back against the reference's
    ``searchsorted``, and what ``watch`` saw of the tier, the kernel and
    the compiles.  ``exchange(tag, array)`` returns every rank's array
    (files, not the program's collectives)."""
    seen, nbin = data.seen, cfg["max_bin"]
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
    trees = len(committed["forest_int"])
    got = ref.replay(
        data.values, data.labels, cuts, committed["forest_int"],
        committed["forest_val"], [0, trees - 1], nbin, depth_of(cfg),
        cfg["learning_rate"], cfg["reg_lambda"], cfg["min_child_weight"],
        cfg["compute_dtype"], lambda tag, a: np.sum(exchange(tag, a), axis=0))
    warmup = int(traffic.get("warmup_versions", 2))
    timed = [k for job, k in seen["compile_requests"] if job == 1]
    from rabit_tpu import engine

    stats = dict(getattr(engine.get_engine(), "path_stats", {}) or {})
    at = seen["commit_at"]
    print("perfbench gbdt saw " + json.dumps({
        "staged": seen["staged"], "mosaic_kernels": seen["mosaic_kernels"],
        "compile_requests": seen["compile_requests"], "trees": trees,
        "splits": got["splits"], "leaves": got["leaves"],
        # where a slow version was slow: seconds between commits, and the
        # longest call of each span of the loop
        "commit_gaps": [round(b - a, 3) for a, b in zip(at, at[1:])],
        "longest": {k[:-len(".max_s")]: round(v, 4) for k, v in stats.items()
                    if k.endswith(".max_s") and k.startswith(
                        ("learn.", "gbdt.", "commit", "allreduce"))}}),
        file=sys.stderr, flush=True)
    return {
        "split_regret": got["split_regret"],
        "leaf_sum_rel_err": got["leaf_sum_rel_err"],
        "leaf_sum_rounded_rel_err": got["leaf_sum_rounded_rel_err"],
        "unsplit_above_limit": got["unsplit_above_limit"],
        "cuts_gap": cuts_gap,
        "bin_gap": bin_gap,
        # programs asked of the compiler (built or read from the cache)
        # between the commit that opened the window and the last
        "recompiles_in_window": float(sum(timed[warmup:])),
        "tier_mismatch": float(seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(not seen["mosaic_kernels"]),
    }

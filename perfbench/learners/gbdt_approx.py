"""Adapter for boosting under XGBoost's ``approx`` tree method: the same
learner, entry point and rows as ``learners/gbdt.py``
(``rabit_tpu.learn.boosting.train`` on HIGGS's schema; a version is a
boosting round), with ``tree_method="approx"``: the cuts are sketched
anew before every tree from every row under that round's hessians and
the rows binned again on the device, which the reference
(``perfbench/reference/gbdt_approx.py``) holds to the sketch's
semantics cut by cut.  The six functions are those
``learners/kmeans.py`` lists; the rows, the watch on the kernel, the
commits and the compiles are ``learners/gbdt.py``'s own.

Nothing here reads a private name of the program: the staged tier is
what ``histogram.stage_values`` returned, the bins are what the program
``histogram.rebin_program`` built wrote, the forest, its cuts and its
split values are what ``load_checkpoint`` gave.

``--grid`` names the control of ``correct``: a float grid (the kernel's
weight operand rounded to it, as in the HIGGS cell) or
``unweighted_sketch`` (the sketch is handed ones for hessians: what
``tree_method="hist"`` does, and what this cell is there to tell from
``approx``).
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from perfbench import harness
from perfbench.reference import gbdt as ref
from perfbench.reference import gbdt_approx as refa

# the one instance the harness and the tests' steering files know
gbdt = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "gbdt.py"))

UNWEIGHTED = "unweighted_sketch"
BIN_CHECK_ROWS = gbdt.BIN_CHECK_ROWS


class Data(gbdt.Data):
    """``learners/gbdt.py``'s shard (the same rows from the same seed);
    ``unweighted`` is the second control, kept apart from ``grid``,
    which that file's wrapper of the kernel reads as a float type."""

    def __init__(self, cfg, seed, shard, world, threads, rows=None,
                 grid=None):
        self.unweighted = grid == UNWEIGHTED
        super().__init__(cfg, seed, shard, world, threads, rows,
                         None if self.unweighted else grid)
        if rows and not gbdt.on_chip():
            print("perfbench gbdt_approx: a rehearsal off the chip, the "
                  "histogram kernel interpreted and trees stopped at depth "
                  f"{gbdt.REHEARSAL_MAX_DEPTH} (learners/gbdt.py on_chip), "
                  f"not the configuration's {cfg['max_depth']}",
                  file=sys.stderr, flush=True)


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    from rabit_tpu.learn import histogram

    if not hasattr(histogram, "sketch_summary"):
        # the parent of the PR that added the cell: its train() knows
        # one way to cut, once, from a sample of rank 0
        raise harness.Refused(
            "this program cannot run the approx cell: "
            "rabit_tpu.learn.histogram has no sketch_summary")
    return Data(cfg, seed, shard, world, threads, rows, grid)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------
def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """A version is a boosting round over every row of every rank.  The
    kernels' shape carries the summary's size, for the sketch's cost."""
    about = gbdt.describe(cfg, traffic, data)
    about["kernel_shape"]["summary_entries"] = cfg["summary_entries"]
    return about


def read_back(data: Data, bins_t, cuts) -> dict:
    """The first and the last ``BIN_CHECK_ROWS`` rows of the device's
    bins, and the cuts they were made for, on the host."""
    rows = min(BIN_CHECK_ROWS, data.n)
    return {"head": np.asarray(bins_t[:data.f, :rows]),
            "tail": np.asarray(bins_t[:data.f, -rows:]),
            "cuts": np.asarray(cuts)}


def watch(data: Data, spans, trace: bool) -> list:
    """``learners/gbdt.py watch`` (the kernels handed to ``pallas_call``,
    the commits, the compile requests, the kernel's operand under a
    float ``--grid``) and this cell's own eyes: what
    ``histogram.stage_values`` staged (its types; the harness spans
    ``stage`` and ``stage_bin`` around it, to ``block_until_ready``),
    and what the program ``histogram.rebin_program`` built wrote: the
    first and the last ``BIN_CHECK_ROWS`` rows of the bins of the job's
    first round, read back then (inside warm-up), and the newest bins
    with the cuts they were made for, held by reference alone and read
    back in ``check``.  Under ``--grid unweighted_sketch`` the sketch is
    handed ones for weights."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.learn import histogram

    undo = list(gbdt.watch(data, spans, trace))
    seen = data.seen
    stage_fn, rebin_fn, summary_fn = (
        histogram.stage_values, histogram.rebin_program,
        histogram.sketch_summary)

    def seen_stage(values, *a, **kw):
        stage, binning = spans.begin("stage"), spans.begin("stage_bin")
        staged = stage_fn(values, *a, **kw)
        arrays = [x for x in jax.tree_util.tree_leaves(staged)
                  if isinstance(x, jax.Array)]
        jax.block_until_ready(arrays)
        spans.end("stage_bin", binning)
        spans.end("stage", stage)
        stats = jax.local_devices()[0].memory_stats() or {}
        spans.counters.setdefault("peak_bytes_after_stage", int(
            stats.get("peak_bytes_in_use", 0)))
        seen["staged"] = gbdt.staged_dtypes(arrays)
        return staged

    def seen_rebin_program(*a, **kw):
        fn = rebin_fn(*a, **kw)

        def rebin(bins_t, values_t, cuts):
            out = fn(bins_t, values_t, cuts)
            if "first_bins" not in seen and seen["jobs"] == 1:
                seen["first_bins"] = read_back(data, out, cuts)
            seen["newest_bins"] = (out, cuts)
            return out

        return rebin

    def unweighted(values_t, weights, entries):
        return summary_fn(values_t, jnp.ones_like(weights), entries)

    histogram.stage_values = seen_stage
    histogram.rebin_program = seen_rebin_program
    undo += [(histogram, "stage_values", stage_fn),
             (histogram, "rebin_program", rebin_fn)]
    if data.unweighted:
        histogram.sketch_summary = unweighted
        undo.append((histogram, "sketch_summary", summary_fn))
    return undo


def run_job(cfg: dict, traffic: dict, data: Data) -> None:
    """The job, through the entry point a user calls.  Returns only by
    the commit wrapper's ``WindowClosed``."""
    from rabit_tpu.learn import boosting

    seen = data.seen
    if "newest_bins" in seen:
        # a second job (the traced run's resume) stages a shard of its
        # own: the first job's last bins are read back and let go first
        seen.setdefault("last_bins", read_back(
            data, *seen.pop("newest_bins")))
    seen["jobs"] += 1
    boosting.train(
        data.values, data.labels, num_round=gbdt.NUM_ROUND,
        max_depth=gbdt.depth_of(cfg), nbin=cfg["max_bin"],
        learning_rate=cfg["learning_rate"], reg_lambda=cfg["reg_lambda"],
        loss=cfg["loss"], min_child_weight=cfg["min_child_weight"],
        subsample=cfg["subsample"], seed=data.seed,
        tree_method=cfg["tree_method"])


def committed(model) -> dict:
    """``learners/gbdt.py committed`` of the forest ``load_checkpoint``
    gave, and what ``approx`` adds to it: every node's float split value
    (padded as the leaf weights are) and every tree's cuts."""
    out = gbdt.committed(model)
    split = np.zeros(out["forest_val"].shape, np.float32)
    for t, tree in enumerate(model.trees):
        split[t, :len(tree)] = [node.split for node in tree]
    out["forest_split"] = split
    out["tree_cuts"] = np.asarray(model.tree_cuts, np.float32)
    return out


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
def bins_gap(values: np.ndarray, got: dict) -> float:
    """Entries of the bins read back that differ from the reference's
    ``searchsorted`` of the same rows by the cuts they were made for."""
    rows = got["head"].shape[1]
    return float(
        np.count_nonzero(got["head"] != ref.bin_rows(values[:rows],
                                                     got["cuts"]))
        + np.count_nonzero(got["tail"] != ref.bin_rows(values[-rows:],
                                                       got["cuts"])))


def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """The committed forest's first and last tree replayed one step each
    by the plain reference, each on **its own committed cuts**; those
    cuts against the weighted ranks the reference computes from the
    forest without the tree (``cut_rank_err``); every node's float split
    value against its tree's cut at its bin; the bins read back (the
    first round's, and the newest) against the reference's
    ``searchsorted`` by the cuts they were made for, and the first
    round's cuts against the first tree's committed ones; and what
    ``watch`` saw of the tier, the kernel and the compiles."""
    seen, nbin = data.seen, cfg["max_bin"]
    forest_int, tree_cuts = committed["forest_int"], committed["tree_cuts"]
    trees = len(forest_int)
    # ---- the bins
    bin_gap = float("inf")                    # nothing binned: no bins
    newest = seen.pop("newest_bins", None)    # the program's last array
    if "last_bins" not in seen and newest is not None:
        seen["last_bins"] = read_back(data, *newest)
    del newest
    if "first_bins" in seen and "last_bins" in seen:
        first = seen["first_bins"]
        bin_gap = bins_gap(data.values, first) + bins_gap(
            data.values, seen["last_bins"]) + float(
                np.count_nonzero(first["cuts"] != tree_cuts[0]))
    # ---- every split value is its tree's cut at its bin
    split_gap = 0.0
    if tree_cuts.shape[:1] == (trees,):
        for t in range(trees):
            at = forest_int[t, :, 0] >= 0
            want = tree_cuts[t][forest_int[t, at, 0], forest_int[t, at, 1]]
            split_gap = max(split_gap, float(np.max(np.abs(
                committed["forest_split"][t, at] - want), initial=0.0)))
    else:
        split_gap = float("inf")
    got = refa.replay(
        data.values, data.labels, tree_cuts, forest_int,
        committed["forest_val"], committed["forest_split"], [0, trees - 1],
        nbin, gbdt.depth_of(cfg), cfg["learning_rate"], cfg["reg_lambda"],
        cfg["min_child_weight"], cfg["compute_dtype"],
        lambda tag, a: np.sum(exchange(tag, a), axis=0))
    warmup = int(traffic.get("warmup_versions", 2))
    timed = [k for job, k in seen["compile_requests"] if job == 1]
    from rabit_tpu import engine

    stats = dict(getattr(engine.get_engine(), "path_stats", {}) or {})
    at = seen["commit_at"]
    print("perfbench gbdt_approx saw " + json.dumps({
        "staged": seen["staged"], "mosaic_kernels": seen["mosaic_kernels"],
        "compile_requests": seen["compile_requests"], "trees": trees,
        "splits": got["splits"], "leaves": got["leaves"],
        "cut_rank_err_by_tree": got["cut_rank_err_by_tree"],
        "sketch_eps": cfg["sketch_eps"],
        "commit_gaps": [round(b - a, 3) for a, b in zip(at, at[1:])],
        "longest": {k[:-len(".max_s")]: round(v, 4) for k, v in stats.items()
                    if k.endswith(".max_s") and k.startswith(
                        ("learn.", "gbdt.", "commit", "allreduce",
                         "stage."))},
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
        "cut_rank_err": got["cut_rank_err"],
        "split_value_gap": split_gap,
        "bin_gap": bin_gap,
        # programs asked of the compiler (built or read from the cache)
        # between the commit that opened the window and the last
        "recompiles_in_window": float(sum(timed[warmup:])),
        "tier_mismatch": float(seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(not seen["mosaic_kernels"]),
    }

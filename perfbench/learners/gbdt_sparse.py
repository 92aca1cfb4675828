"""Adapter for histogram boosting on sparse rows: the same learner, entry
point and timed path as ``learners/gbdt.py`` (``rabit_tpu.learn.
boosting.train``; a version is a boosting round), on rows of the
XGBoost paper's Allstate shape, thousands of one-hot columns of which a
row holds about thirty, handed over as ELL rows of ``(index, value)``
pairs with a count and held against a reference that adds up entries
(``perfbench/reference/gbdt_sparse.py``).  The six functions are those
``learners/kmeans.py`` lists; what does not depend on the rows (the
rehearsal rules, what counts as a Mosaic kernel, the staged types, the
job's keywords) is ``learners/gbdt.py``'s own.
"""
from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import harness
from perfbench.reference import gbdt_sparse as ref

# the one instance the harness and the tests' steering files know
gbdt = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "gbdt.py"))

GEN_BLOCK = 1 << 16
# (numeric columns, one-hot group widths) of a rehearsal (see
# ``gbdt.on_chip``): a round of the interpreted kernel over 32 slots a
# row outlasts the run-x1 tests' 1.5 s window at their 16,384 rows; at
# these widths (82 columns, up to 11 entries a row) it takes what the
# HIGGS cell's rehearsal takes
REHEARSAL_WIDTHS = (6, (8, 20, 40, 3, 5))
NUMERIC_PRESENT = (0.97, 1.0)      # the rarest and the commonest column
GROUP_PRESENT = (0.93, 0.99)       # the field known, by group
POWER = 3.0                        # a group's categories: floor(w * u^3)


# ----------------------------------------------------------------------
# the schema: numeric columns, one-hot groups, who reads what
# ----------------------------------------------------------------------
class Schema:
    """The table's layout, the same for every seed: ``numeric`` columns
    first, each present in nearly every row, then the indicator columns
    of the one-hot ``groups`` (their widths), a row holding at most one
    of a group; and what the label reads."""

    def __init__(self, numeric: int, groups):
        self.numeric = numeric
        self.widths = np.asarray(groups, np.int64)
        self.first = numeric + np.concatenate(
            [[0], np.cumsum(self.widths)[:-1]])
        self.f = int(numeric + self.widths.sum())
        self.slots = numeric + len(self.widths)       # entries at most
        rng = np.random.default_rng([4227, numeric, len(self.widths)])
        lo, hi = NUMERIC_PRESENT
        self.numeric_present = lo + (hi - lo) * rng.random(numeric)
        lo, hi = GROUP_PRESENT
        self.group_present = lo + (hi - lo) * rng.random(len(self.widths))
        # a calendar year, a model year and an ordered category: few
        # levels; the rest continuous, of several scales and tails
        self.levels = np.zeros(numeric, np.int64)
        self.levels[:3] = (4, 30, 9)[:min(3, numeric)]
        order = np.argsort(-self.widths)
        # the label's groups: the two widest (common and rare categories
        # of each) and two narrow ones
        self.label_groups = np.array([order[0], order[1], order[-1],
                                      order[len(order) // 2]])

    def width(self) -> int:
        """The ELL width: the entries a row can hold, to a multiple of
        eight and with a slot to spare."""
        return -(-(self.slots + 1) // 8) * 8


def logit_of(z: np.ndarray, cat: np.ndarray, known: np.ndarray,
             schema: Schema) -> np.ndarray:
    """The fixed nonlinear function whose logistic (plus a bias set for
    the positive share) a row's label is drawn from: of the first six
    numeric columns ``z`` (0 where absent), of the category drawn in the
    four label groups ``cat`` and of whether the field was ``known``.
    Common and rare indicators with both signs, and the absence of a
    field with both signs, so that present-versus-absent splits and both
    default directions are chosen.  Clipped: a log-normal column's
    square has a long tail."""
    w = schema.widths[schema.label_groups]
    rare = cat >= (w // 3)[None, :]             # the tail of the power law
    return np.clip(
        1.2 * z[:, 0] * z[:, 1] + 0.9 * np.sin(2.5 * z[:, 2])
            + 0.8 * (z[:, 3] > 0.3) - 0.7 * z[:, 4] * z[:, 4] + 0.6 * z[:, 5]
            + 1.4 * (known[:, 0] & (cat[:, 0] == 0))
            - 1.2 * (known[:, 0] & (cat[:, 0] == 2))
            + 1.1 * (known[:, 1] & rare[:, 1])
            - 0.9 * (known[:, 1] & (cat[:, 1] % 7 == 3))
            + 1.3 * (known[:, 2] & (cat[:, 2] == 1))
            - 1.0 * ~known[:, 3] + 0.8 * ~known[:, 0], -30.0, 30.0)


def fill_block(schema: Schema, rng, idx, val, counts):
    """One block of rows, in place, as ELL rows: a row's present
    entries in column order from slot 0, the rest padding (index
    ``schema.f``, value 0).  Returns what the label reads."""
    rows, nn, ng = idx.shape[0], schema.numeric, len(schema.widths)
    z = rng.standard_normal((rows, nn), dtype=np.float32)
    for j in range(nn):
        if schema.levels[j]:
            z[:, j] = np.floor((np.tanh(z[:, j]) + 1) * 0.5 * schema.levels[
                j]).clip(0, schema.levels[j] - 1)
        elif j % 3 == 1:
            np.exp(z[:, j], out=z[:, j])
    cat = np.minimum((schema.widths * rng.random(
        (rows, ng), dtype=np.float32) ** POWER).astype(np.int64),
        schema.widths - 1)
    have = np.concatenate([
        rng.random((rows, nn), dtype=np.float32) < schema.numeric_present,
        rng.random((rows, ng), dtype=np.float32) < schema.group_present],
        axis=1)
    cols = np.concatenate([np.broadcast_to(np.arange(nn), (rows, nn)),
                           schema.first + cat], axis=1)
    vals = np.concatenate([z, np.ones((rows, ng), np.float32)], axis=1)
    # present entries to the front, in column order
    order = np.argsort(~have, axis=1, kind="stable")
    kept = np.take_along_axis(have, order, axis=1)
    idx[:] = schema.f
    val[:] = 0.0
    idx[:, :schema.slots] = np.where(
        kept, np.take_along_axis(cols, order, axis=1), schema.f)
    val[:, :schema.slots] = np.where(
        kept, np.take_along_axis(vals, order, axis=1), 0.0)
    counts[:] = have.sum(axis=1)
    scale = np.where(schema.levels[:6] > 0, schema.levels[:6], 1.0)
    zl = np.where(have[:, :6], z[:, :6] / scale, 0.0)
    return zl, cat[:, schema.label_groups], \
        have[:, nn + schema.label_groups]


def make_rows(seed: int, shard: int, n: int, schema: Schema,
              positive: float, threads: int):
    """ELL rows ``(indices (n, width) int32, values float32, counts
    (n,) int32)``, ``(n,)`` float32 labels in {0, 1}, ``positive`` of
    them 1 (the bias is set on the first block), and the number of
    entries present.  A block of 2^16 rows has a generator of its own,
    so the rows are a function of ``(seed, shard)`` alone, not of the
    thread count; the layout (``Schema``) is the same for every seed."""
    width = schema.width()
    idx = np.empty((n, width), np.int32)
    val = np.empty((n, width), np.float32)
    counts = np.empty(n, np.int32)
    labels = np.empty(n, np.float32)

    def draw(lo: int):
        hi = min(n, lo + GEN_BLOCK)
        rng = np.random.default_rng([seed, shard, n, schema.f, lo])
        return rng, logit_of(*fill_block(
            schema, rng, idx[lo:hi], val[lo:hi], counts[lo:hi]), schema)

    def fill(lo: int, bias: float, drawn=None) -> None:
        rng, logit = drawn or draw(lo)
        p = 1.0 / (1.0 + np.exp(-(logit + bias)))
        labels[lo:min(n, lo + GEN_BLOCK)] = rng.random(
            len(p), dtype=np.float32) < p

    first = draw(0)
    lo_b, hi_b = -30.0, 30.0
    for _ in range(50):                  # the bias that gives the share
        bias = 0.5 * (lo_b + hi_b)
        share = float(np.mean(1.0 / (1.0 + np.exp(-(first[1] + bias)))))
        lo_b, hi_b = (bias, hi_b) if share < positive else (lo_b, bias)
    fill(0, bias, first)
    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(lambda lo: fill(lo, bias),
                      range(GEN_BLOCK, n, GEN_BLOCK)))
    return (idx, val, counts), labels, int(counts.sum(dtype=np.int64))


class Data:
    """One rank's shard.  ``grid`` is the control of ``correct``, as in
    ``learners/gbdt.py``: the float grid the kernel's weight operand is
    rounded to in ``watch``'s wrapper of the kernel call."""

    def __init__(self, cfg: dict, seed: int, shard: int, world: int,
                 threads: int, rows: int | None = None,
                 grid: str | None = None):
        self.n = rows or cfg["rows_per_chip"]
        widths = (cfg["numeric_columns"], cfg["onehot_groups"])
        if rows and not gbdt.on_chip():
            widths = REHEARSAL_WIDTHS
            print("perfbench gbdt_sparse: a rehearsal off the chip, "
                  f"{widths[0]} numeric columns and one-hot groups of "
                  f"{list(widths[1])} (REHEARSAL_WIDTHS), not the "
                  f"configuration's {cfg['features']} columns",
                  file=sys.stderr, flush=True)
        self.schema = Schema(*widths)
        self.f = self.schema.f
        self.seed, self.shard, self.world, self.grid = seed, shard, world, grid
        self.rows, self.labels, self.present = make_rows(
            seed, shard, self.n, self.schema, cfg["positive_share"], threads)
        self.seen = {}


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    from perfbench.harness import Refused
    from rabit_tpu.learn import histogram

    if not hasattr(histogram, "stage_entries"):
        # the parent of the PR that added the cell: train() takes a
        # dense (n, f) array and nothing else
        raise Refused("this program cannot run the sparse boosting cell: "
                      "rabit_tpu.learn.histogram has no stage_entries")
    return Data(cfg, seed, shard, world, threads, rows, grid)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------
def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """A version is a boosting round over every row of every rank.  The
    kernel's shape carries the entries that are present, counted here
    from the rows: what the algorithm has to add up."""
    return {"work_per_version": data.n * data.world,
            "kernel_shape": {"rows": data.n, "features": data.f,
                             "ell_width": data.rows[0].shape[1],
                             "present_entries": data.present,
                             # at most: a numeric column's max_bin bins,
                             # an indicator's two
                             "flat_bins": data.schema.numeric * cfg["max_bin"]
                             + 2 * (data.f - data.schema.numeric),
                             "max_depth": gbdt.depth_of(cfg),
                             "ops_dtype": "bfloat16"}}


def watch(data: Data, spans, trace: bool) -> list:
    """``learners/gbdt.py watch`` (the kernels handed to ``pallas_call``
    while the job's programs were traced, the compile requests between
    commits), and the sparse road's own staging in place of the dense
    one's: what ``histogram.stage_entries`` staged (its types, and two
    blocks of the cells read back).  The harness span ``stage`` runs
    from the cuts (or, on a resume, from the staging) to
    ``block_until_ready`` of the staged entries.  Under ``--grid`` the
    kernel call is wrapped: its weight operand is rounded to that
    grid."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.learn import histogram
    from rabit_tpu.ops import sparse_hist_kernel as sk

    undo = list(gbdt.watch(data, spans, trace))
    cuts_fn, stage_fn, kernel = (histogram.sparse_cuts,
                                 histogram.stage_entries, sk.hist_sparse)
    seen = data.seen
    seen["spans"] = spans
    state = {}

    def seen_cuts(*a, **kw):
        state.setdefault("stage", spans.begin("stage"))
        return cuts_fn(*a, **kw)

    def seen_stage(rows, *a, **kw):
        state.setdefault("stage", spans.begin("stage"))
        staged = stage_fn(rows, *a, **kw)
        arrays = [x for x in staged if isinstance(x, jax.Array)]
        jax.block_until_ready(arrays)
        spans.end("stage", state.pop("stage"))
        stats = jax.local_devices()[0].memory_stats() or {}
        spans.counters.setdefault("peak_bytes_after_stage", int(
            stats.get("peak_bytes_in_use", 0)))
        seen["staged"] = gbdt.staged_dtypes(arrays)
        seen["staged_bytes"] = int(sum(x.nbytes for x in arrays))
        n = rows.indices.shape[0]
        head = min(gbdt.BIN_CHECK_ROWS, n)
        seen["cells_head"] = np.asarray(staged[0][:, :head]).T
        seen["cells_tail"] = np.asarray(staged[0][:, n - head:n]).T
        return staged

    def wrapped_kernel(packed, fb, gh, slot, **kw):
        if data.grid:
            # lax.reduce_precision, not a cast there and back (see
            # learners/gbdt.py)
            grid = jnp.finfo(getattr(jnp, data.grid))
            gh = jax.lax.reduce_precision(jnp.asarray(gh), grid.nexp,
                                          grid.nmant)
        if not gbdt.on_chip():          # a rehearsal: the CPU interprets
            kw["interpret"] = True
        return kernel(packed, fb, gh, slot, **kw)

    histogram.sparse_cuts = seen_cuts
    histogram.stage_entries = seen_stage
    undo += [(histogram, "sparse_cuts", cuts_fn),
             (histogram, "stage_entries", stage_fn)]
    if data.grid or not gbdt.on_chip():
        sk.hist_sparse = wrapped_kernel
        undo.append((sk, "hist_sparse", kernel))
    return undo


def run_job(cfg: dict, traffic: dict, data: Data) -> None:
    """The job, through the entry point a user calls.  Returns only by
    the commit wrapper's ``WindowClosed``."""
    from rabit_tpu.learn import boosting
    from rabit_tpu.learn.data import EllRows

    data.seen["jobs"] += 1
    boosting.train(
        EllRows(*data.rows, data.f), data.labels, num_round=gbdt.NUM_ROUND,
        max_depth=gbdt.depth_of(cfg), nbin=cfg["max_bin"],
        learning_rate=cfg["learning_rate"], reg_lambda=cfg["reg_lambda"],
        loss=cfg["loss"], min_child_weight=cfg["min_child_weight"],
        subsample=cfg["subsample"], seed=data.seed)


def committed(model) -> dict:
    """``learners/gbdt.py committed``, and the flat bin space's column
    pointer beside its cuts."""
    return dict(gbdt.committed(model),
                cut_ptr=np.asarray(model.cut_ptr, np.int64))


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
class HostMemory:
    """Where the host's memory stands while the reference runs, on
    stderr: this process's resident set and the machine's
    ``MemAvailable``, sampled once a second, the extremes said at every
    mark (a shard of 2^25 rows is 8.9 GB of entries before anything is
    made of them, and the machine ends a process that outgrows it)."""

    def __init__(self):
        import threading

        self.peak, self.least = 0.0, float("inf")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def read(self) -> tuple[float, float]:
        """(resident, available) in GB, the extremes kept."""
        with open("/proc/self/statm") as f:
            now = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e9
        free = float("nan")
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    free = int(line.split()[1]) * 1024 / 1e9
        self.peak, self.least = max(self.peak, now), min(self.least, free)
        return now, free

    def _run(self) -> None:
        while not self._stop.wait(1.0):
            self.read()

    def mark(self, at: str) -> None:
        now, free = self.read()
        print(f"perfbench gbdt_sparse memory at {at}: resident {now:.1f} GB "
              f"(most since the check began {self.peak:.1f}), the machine's "
              f"MemAvailable {free:.1f} GB (least {self.least:.1f})",
              file=sys.stderr, flush=True)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """As ``learners/gbdt_missing.py check``, against the reference that
    adds up entries: the committed forest's first and last tree replayed
    one step each (every split's column, cut **and default direction**
    against the reference's best over both directions), the committed
    cuts against the distinct quantiles of the present entries of the
    stated sample, the staged cells read back against the reference's,
    and what ``watch`` saw of the tier, the kernel and the compiles."""
    from rabit_tpu import engine

    memory = HostMemory()
    rss = memory.mark
    seen = data.seen
    idx, val, counts = data.rows
    rss("check")
    cut_ptr, cuts = committed["cut_ptr"], committed["cuts"]
    want_ptr, want = ref.quantile_cuts(
        *ref.cut_sample((idx, val, counts), cfg["cut_sample_rows"]),
        data.f, cfg["max_bin"])
    same = cut_ptr.shape == want_ptr.shape and np.array_equal(
        cut_ptr, want_ptr)
    cuts_gap = float(np.max(np.abs(cuts - want), initial=0.0)) if same \
        else float("inf")
    bin_gap = float("inf")                    # nothing staged: no cells
    if "cells_head" in seen:
        rows = seen["cells_head"].shape[0]
        bin_gap = float(sum(
            np.count_nonzero(seen[name] != ref.bin_rows(
                idx[at], val[at], counts[at], data.f, cut_ptr, cuts))
            for name, at in (("cells_head", slice(0, rows)),
                             ("cells_tail", slice(data.n - rows, data.n)))))
    trees = len(committed["forest_int"])
    rss("cuts and bins checked")
    shard = ref.Shard(idx, val, counts, data.f, data.labels, cut_ptr, cuts)
    rss("reference's cells binned")
    # nothing reads the rows after this: 8.9 GB of a shard of 2^25 rows,
    # on a machine of 40 GiB of which the TPU runtime holds 13.9
    del idx, val, counts
    data.rows = None
    rss("rows dropped")
    got = ref.replay_shard(
        shard, committed["forest_int"], committed["forest_val"],
        [0, trees - 1],
        gbdt.depth_of(cfg), cfg["learning_rate"], cfg["reg_lambda"],
        cfg["min_child_weight"], cfg["compute_dtype"],
        lambda tag, a: np.sum(exchange(tag, a), axis=0))
    rss("replayed")
    memory.stop()
    warmup = int(traffic.get("warmup_versions", 2))
    timed = [k for job, k in seen["compile_requests"] if job == 1]
    stats = dict(getattr(engine.get_engine(), "path_stats", {}) or {})
    # the binning and the bucketing alone, from the program's own spans
    for span, name in (("stage.sparse_bin", "stage_bin"),
                       ("stage.sparse_bucket", "stage_bucket")):
        if span + ".total_s" in stats:
            seen["spans"].seconds.setdefault(name, []).append(
                stats[span + ".total_s"])
    at = seen["commit_at"]
    print("perfbench gbdt_sparse saw " + json.dumps({
        "staged": seen["staged"], "staged_bytes": seen.get("staged_bytes"),
        "mosaic_kernels": seen["mosaic_kernels"],
        "compile_requests": seen["compile_requests"], "trees": trees,
        "splits": got["splits"], "leaves": got["leaves"],
        "default_left": got["default_left"],
        "worst_split": got["worst_split"], "worst_leaf": got["worst_leaf"],
        "present_entries": data.present, "entries": data.n * data.f,
        "cuts": int(cut_ptr[-1]), "flat_bins": int(cut_ptr[-1]) + data.f,
        "commit_gaps": [round(b - a, 3) for a, b in zip(at, at[1:])],
        "longest": {k[:-len(".max_s")]: round(v, 4) for k, v in stats.items()
                    if k.endswith(".max_s") and k.startswith(
                        ("learn.", "gbdt.", "commit", "allreduce",
                         "stage."))},
        "totals": {k[:-len(".total_s")]: round(v, 3)
                   for k, v in stats.items() if k.endswith(".total_s")
                   and k.startswith(("learn.", "gbdt.", "stage."))},
        "reference_s": {k: round(v, 2) for k, v in ref.TIMES.items()},
        "counters": {k: v for k, v in stats.items()
                     if k.startswith("gbdt.") and "_s" != k[-2:]
                     and not k.endswith(".n")}}, default=float),
        file=sys.stderr, flush=True)
    return {
        "split_regret": got["split_regret"],
        "leaf_sum_rel_err": got["leaf_sum_rel_err"],
        "leaf_sum_rounded_rel_err": got["leaf_sum_rounded_rel_err"],
        "unsplit_above_limit": got["unsplit_above_limit"],
        "cuts_gap": cuts_gap,
        "bin_gap": bin_gap,
        "recompiles_in_window": float(sum(timed[warmup:])),
        "tier_mismatch": float(seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(not seen["mosaic_kernels"]),
    }

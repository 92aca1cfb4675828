"""Adapter for histogram boosting on wide rows with missing values: the
same learner, entry point and timed path as ``learners/gbdt.py``
(``rabit_tpu.learn.boosting.train``; a version is a boosting round), on
rows of the Bosch production line's shape, most of whose entries are
absent station by station, held against a reference that scores both
default directions (``perfbench/reference/gbdt_missing.py``).  The six
functions are those ``learners/kmeans.py`` lists; what does not depend
on the rows (the watch on the program's layers, the job, the forest as
arrays) is ``learners/gbdt.py``'s own.
"""
from __future__ import annotations

import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import harness
from perfbench.reference import gbdt as ref
from perfbench.reference import gbdt_missing as refm

# the one instance the harness and the tests' steering files know
gbdt = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "gbdt.py"))

GEN_BLOCK = 1 << 15
FILL_ROWS = 1 << 9
PATH_SHARE = 0.6            # of a station's visits follow the line's draw
PRESENCE = (0.004, 0.995)   # the rarest and the commonest station
LEVELS = (0, 33, 0, 3, 0, 17, 0, 9, 0, 129, 0, 5)   # 0: continuous
# (features, stations, share of entries present, share of positive
# labels) of a rehearsal (see ``gbdt.on_chip``): a round of the
# interpreted kernel over 968 features (121 feature groups a call)
# outlasts the run-x1 tests' 1.5 s window at their 16,384 rows many
# times over, and 0.58% of so few rows are 95 positives, too few for a
# depth-3 tree to find more than noise; at these widths a round takes
# what the HIGGS cell's rehearsal takes
REHEARSAL_WIDTHS = (24, 6, 0.45, 0.2)

committed = gbdt.committed
run_job = gbdt.run_job


# ----------------------------------------------------------------------
# the line: stations, their features, who visits them
# ----------------------------------------------------------------------
class Line:
    """The production line's layout for ``features`` columns over
    ``stations`` stations on 4 lines, the same for every seed: station
    widths (uneven, from 2 columns to dozens), the share of parts that
    visit each (from ``PRESENCE[0]`` to ``PRESENCE[1]``, placed so that
    ``present`` of all entries are present), the station of every
    column, and the twelve columns and four stations the label reads."""

    def __init__(self, features: int, stations: int, present: float):
        rng = np.random.default_rng([968, 52, features, stations])
        if features < 2 * stations or stations < 4:
            raise ValueError(f"Line: {features} columns over {stations} "
                             "stations")
        spare = features - 2 * stations
        share = rng.dirichlet(np.full(stations, 0.7))
        extra = np.floor(share * spare).astype(np.int64)
        extra[np.argsort(-share)[:spare - int(extra.sum())]] += 1
        self.width = 2 + extra
        self.station_of = np.repeat(np.arange(stations), self.width)
        self.line_of = np.sort(rng.integers(0, 4, stations))
        # visiting shares: a fixed ladder from the rarest to the
        # commonest, bent by one exponent until the width-weighted mean
        # is the share of entries present
        rank = rng.permutation(stations) / (stations - 1.0)
        lo, hi = PRESENCE

        def shares(bend: float) -> np.ndarray:
            return lo * (hi / lo) ** (rank ** bend)

        a, b = 0.02, 50.0
        for _ in range(80):
            bend = (a * b) ** 0.5
            mean = float(shares(bend) @ self.width) / features
            a, b = (a, bend) if mean < present else (bend, b)
        self.visit = shares(bend)
        # the label's columns: four from the commonest stations, four
        # from the middle, four from rare ones; its four stations: two
        # common, two rare
        order = np.argsort(-self.visit)
        first = np.concatenate([[0], np.cumsum(self.width)[:-1]])
        at = dict.fromkeys([0, 1, stations // 3, stations // 2,
                            (2 * stations) // 3, stations - 2, *range(6)])
        self.label_cols = np.array([first[order[k]] + c
                                    for k in list(at)[:6] for c in (0, 1)])
        self.label_stations = np.array([order[2], order[stations // 4],
                                        order[stations // 2 + 1],
                                        order[stations - 1]])
        levels = np.array([LEVELS[j % len(LEVELS)] for j in range(features)])
        # a column of L levels holds the multiples of 2 / (L - 1)
        self.few = levels > 0
        self.half = np.where(self.few, (levels - 1) / 2.0, 1.0).astype(
            np.float32)


def logit_of(z: np.ndarray, went: np.ndarray) -> np.ndarray:
    """The fixed nonlinear function whose logistic (plus a bias set for
    the positive share) a part's label is drawn from: of twelve
    measurements ``z`` (rows, 12), each 0 where the part skipped the
    station, and of whether it went through four stations ``went``
    (rows, 4).  Products, steps, a square and waves, as
    ``learners/gbdt.py logit_of`` has them, so that no depth-6 tree
    exhausts it; the visits carry signal of both signs, so that where
    the absent rows of a split go is learned, and goes both ways."""
    return (2.2 * z[:, 0] * z[:, 1] + 1.4 * np.sin(3.0 * z[:, 2])
            + 1.3 * (z[:, 3] > 0.3) - 1.6 * z[:, 4] * z[:, 4] + 1.1 * z[:, 5]
            + 1.5 * np.abs(z[:, 6]) * (z[:, 7] > 0.0)
            - 1.8 * z[:, 8] * z[:, 9] + 1.2 * np.cos(2.0 * (z[:, 10]
                                                           + z[:, 11]))
            + 1.6 * went[:, 0] - 1.3 * went[:, 1]
            + 1.4 * went[:, 2] * (1.0 - went[:, 0]) - 1.7 * went[:, 3])


def fill_block(line: Line, rng, values: np.ndarray) -> np.ndarray:
    """One block of parts, in place: a value in [-1, 1] for every
    measurement, on the column's few levels where it has few, and NaN
    for every measurement of a station the part did not visit.  Returns
    the visits (rows, stations) as bool.  ``FILL_ROWS`` parts at a time,
    so that the temporaries stay in the cache and off fresh pages."""
    stations = len(line.width)
    visit = line.visit.astype(np.float32)
    went = np.empty((values.shape[0], stations), bool)
    for r in range(0, values.shape[0], FILL_ROWS):
        part = values[r:r + FILL_ROWS]
        rows = part.shape[0]
        # a part follows one draw a line for PATH_SHARE of its stations
        # and a draw of its own for the rest: stations of a line go
        # together
        path = rng.random((rows, 4), dtype=np.float32)[:, line.line_of]
        own = rng.random((rows, stations), dtype=np.float32)
        follows = rng.random((rows, stations), dtype=np.float32) < PATH_SHARE
        went[r:r + rows] = np.where(follows, path, own) < visit
        rng.random(out=part, dtype=np.float32)
        np.multiply(part, 2.0, out=part)
        np.subtract(part, 1.0, out=part)
        grid = part * line.half
        np.round(grid, out=grid)
        np.divide(grid, line.half, out=grid)
        np.copyto(part, grid, where=line.few[None, :])
        np.copyto(part, np.float32(np.nan),
                  where=~went[r:r + rows][:, line.station_of])
    return went


def make_rows(seed: int, shard: int, n: int, features: int, stations: int,
              present: float, positive: float, threads: int):
    """``(n, features)`` float32 values, NaN where absent, ``(n,)``
    float32 labels in {0, 1}, ``positive`` of them 1 (the bias is set on
    the first block), and the number of entries present.  A block of
    2^15 parts has a generator of its own, so the rows are a function of
    ``(seed, shard)`` alone, not of the thread count."""
    line = Line(features, stations, present)
    values = np.empty((n, features), np.float32)
    labels = np.empty(n, np.float32)
    entries = {}

    def draw(lo: int):
        hi = min(n, lo + GEN_BLOCK)
        rng = np.random.default_rng([seed, shard, n, features, lo])
        went = fill_block(line, rng, values[lo:hi])
        entries[lo] = int(went.sum(axis=0) @ line.width)
        z = np.nan_to_num(values[lo:hi, line.label_cols], nan=0.0)
        return rng, logit_of(z, went[:, line.label_stations].astype(
            np.float32))

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
    return values, labels, sum(entries.values())


class Data:
    """One rank's shard, with the attributes ``learners/gbdt.py``'s
    ``watch`` and ``run_job`` read.  ``grid`` is the control of
    ``correct``, as there."""

    def __init__(self, cfg: dict, seed: int, shard: int, world: int,
                 threads: int, rows: int | None = None,
                 grid: str | None = None):
        self.n = rows or cfg["rows_per_chip"]
        widths = (cfg["features"], cfg["stations"],
                  1.0 - cfg["missing_share"], cfg["positive_share"])
        if rows and not gbdt.on_chip():
            widths = REHEARSAL_WIDTHS
            print("perfbench gbdt_missing: a rehearsal off the chip, "
                  f"{widths[0]} features over {widths[1]} stations, "
                  f"{widths[2]:.0%} present, {widths[3]:.0%} positive "
                  "(REHEARSAL_WIDTHS), not the configuration's "
                  f"{cfg['features']}", file=sys.stderr, flush=True)
        self.f = widths[0]
        self.seed, self.shard, self.world, self.grid = seed, shard, world, grid
        self.values, self.labels, self.present = make_rows(
            seed, shard, self.n, *widths, threads)
        self.seen = {}


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    from perfbench.harness import Refused
    from rabit_tpu.learn import histogram

    if not hasattr(histogram, "missing_mass"):
        # the parent of the PR that added the cell: its histograms keep
        # a slot for the absent entries, 257 padded to 512, and a round
        # at these widths is another job
        raise Refused("this program cannot run the wide boosting cell: "
                      "rabit_tpu.learn.histogram has no missing_mass")
    return Data(cfg, seed, shard, world, threads, rows, grid)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------
def watch(data: Data, spans, trace: bool) -> list:
    """``learners/gbdt.py watch``, and the harness span ``stage_cuts``
    around the quantiles of the cut sample (968 columns of it)."""
    from rabit_tpu.learn import histogram

    cuts_fn = histogram.quantile_cuts
    histogram.quantile_cuts = spans.wrap(cuts_fn, "stage_cuts")
    return [(histogram, "quantile_cuts", cuts_fn)] + list(
        gbdt.watch(data, spans, trace))


def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """A version is a boosting round over every row of every rank.  The
    kernel's shape carries the entries that are present, counted here
    from the rows: what the algorithm has to add up."""
    return {"work_per_version": data.n * data.world,
            "kernel_shape": {"rows": data.n, "features": data.f,
                             "present_entries": data.present,
                             "nbin": cfg["max_bin"],
                             "max_depth": gbdt.depth_of(cfg),
                             "ops_dtype": "bfloat16"}}


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """As ``learners/gbdt.py check``, against the reference that knows
    absent entries: the committed forest's first and last tree replayed
    one step each (every split's feature, cut **and default direction**
    against the reference's best over both directions), the committed
    cuts against the quantiles of the present entries of the stated
    sample, the staged bins read back against the reference's, the
    missing code included, and what ``watch`` saw of the tier, the
    kernel and the compiles."""
    seen, nbin = data.seen, cfg["max_bin"]
    shard_of_rank = [int(x[0]) for x in exchange(
        "shard", np.array([data.shard]))]
    cuts = committed["cuts"]
    cuts_gap = 0.0
    if shard_of_rank[0] == data.shard:        # rank 0's rows give the cuts
        want = refm.quantile_cuts(ref.cut_sample(
            data.values, cfg["cut_sample_rows"]), nbin)
        cuts_gap = float(np.max(np.abs(cuts - want))) \
            if cuts.shape == want.shape else float("inf")
    bin_gap = float("inf")                    # nothing staged: no bins
    if "bins_head" in seen:
        rows = seen["bins_head"].shape[1]
        bin_gap = float(
            np.count_nonzero(seen["bins_head"] != refm.bin_rows(
                data.values[:rows], cuts, nbin))
            + np.count_nonzero(seen["bins_tail"] != refm.bin_rows(
                data.values[-rows:], cuts, nbin)))
    trees = len(committed["forest_int"])
    got = refm.replay(
        data.values, data.labels, cuts, committed["forest_int"],
        committed["forest_val"], [0, trees - 1], nbin, gbdt.depth_of(cfg),
        cfg["learning_rate"], cfg["reg_lambda"], cfg["min_child_weight"],
        cfg["compute_dtype"], lambda tag, a: np.sum(exchange(tag, a), axis=0))
    warmup = int(traffic.get("warmup_versions", 2))
    timed = [k for job, k in seen["compile_requests"] if job == 1]
    from rabit_tpu import engine

    stats = dict(getattr(engine.get_engine(), "path_stats", {}) or {})
    at = seen["commit_at"]
    print("perfbench gbdt_missing saw " + json.dumps({
        "staged": seen["staged"], "mosaic_kernels": seen["mosaic_kernels"],
        "compile_requests": seen["compile_requests"], "trees": trees,
        "splits": got["splits"], "leaves": got["leaves"],
        "default_left": got["default_left"],
        "worst_split": got["worst_split"], "worst_leaf": got["worst_leaf"],
        "present_entries": data.present, "entries": data.n * data.f,
        "has_missing": int(committed["has_missing"][0]),
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
        "cuts_gap": cuts_gap,
        "bin_gap": bin_gap,
        "recompiles_in_window": float(sum(timed[warmup:])),
        "tier_mismatch": float(seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(not seen["mosaic_kernels"]),
    }

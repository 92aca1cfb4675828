"""Adapter for sparse logistic regression by vector-free L-BFGS: data
from the seed, the job through the entry points a user calls
(``rabit_tpu.learn.linear.LinearObjFunction`` on
``rabit_tpu.learn.lbfgs.LBFGSSolver.run``), what a version is (an outer
iteration), and the comparison with the plain reference.  The six
functions are those ``learners/kmeans.py`` lists.

Nothing here reads a private name of the program: the staged shard is
what ``linear.stage_rows`` returned, the kernels are what was handed to
``jax.experimental.pallas.pallas_call`` while the job's programs were
traced, the solver's state is what it handed ``rabit_tpu.checkpoint``.
"""
from __future__ import annotations

import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.reference import lbfgs as ref

GEN_BLOCK = 1 << 14
ZIPF_EXPONENT = 1.1
HOT_CELLS = 8192
PLANTED_SHARE = 0.1         # of the cells carry a planted weight
PLANTED_SCALE = 2.5
POSITIVE_SHARE = 0.26       # Criteo's click rate
KERNELS = ("lbfgs_margin", "lbfgs_grad")
ROUNDING = 1e-6             # of |f|: what a float32 objective can hide
MAX_HALVINGS = 24           # of the step, that a replay tries
POOL_ROWS = 64              # history rows kept without a new allocation
# (non-zeros a row, features) of a rehearsal (see ``on_chip``): an
# iteration of the interpreted kernels at 39 non-zeros with a 176 MB
# commit (3 s at those tests' 16,384 rows) outlasts their 1.5 s window;
# at these widths one takes 0.15 s
REHEARSAL_WIDTHS = (3, 1 << 14)


def on_chip() -> bool:
    """False only in a rehearsal: off the chip the harness refuses to
    run, so what gets here without one is a test of ``tests/perfbench``
    with the CPU passed off for the chip.  The run-x1 tests rehearse
    every one-chip cell under the k-means cells' steering file, which
    is the benchmark's and knows nothing of this learner; what a
    rehearsal of this cell needs beyond it is therefore here, in the
    few lines that ask this function (as ``learners/gbdt.py`` has it):
    the two kernels are interpreted and count as the Mosaic kernels
    they would be, and a run that names its own ``--rows`` takes
    ``REHEARSAL_WIDTHS`` and says so."""
    import jax

    return jax.local_devices()[0].platform == "tpu"


# ----------------------------------------------------------------------
# data
# ----------------------------------------------------------------------
def cardinalities(fields: int) -> np.ndarray:
    """Vocabulary sizes spread geometrically from 4 to 2^22 over the
    fields, as Criteo's 13 counts and 26 categories are (a handful of
    values to millions)."""
    return np.round(4.0 * (2.0 ** 20) ** (
        np.arange(fields) / max(1, fields - 1))).astype(np.int64)


def mix(x: np.ndarray) -> np.ndarray:
    """A 64-bit finaliser (splitmix64's), in place on uint64."""
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def planted(cells: np.ndarray) -> np.ndarray:
    """The planted weight of each cell (float32): a tenth of the cells,
    chosen and signed by a hash of the cell.  One model for every seed
    (as ``learners/gbdt.py logit_of`` is one function): the seed draws
    the rows and the labels, so every run descends the same landscape
    up to sampling noise and takes the same number of line-search
    trials unless one is marginal."""
    h = mix(cells.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
            + np.uint64(1))
    chosen = (h % np.uint64(1000)) < np.uint64(int(1000 * PLANTED_SHARE))
    size = ((h >> np.uint64(20)) % np.uint64(1024)).astype(np.float32) / 1024
    sign = np.where((h >> np.uint64(40)) & np.uint64(1), 1.0, -1.0)
    return (chosen * sign * (0.25 + size) * PLANTED_SCALE).astype(np.float32)


def make_rows(seed: int, shard: int, n: int, fields: int, features: int,
              threads: int):
    """``(n, fields)`` int32 cells, ``(n,)`` float32 labels in {0, 1}.
    Field j draws a value from a power law (exponent 1.1) over its own
    vocabulary and (field, value) is hashed into ``[0, features)``
    jointly.  The label is a Bernoulli draw of the logistic of the
    planted weights' margin on the unit-length row, the bias set on the
    first block so that about 26% are positive.  A block of 2^14 rows
    has a generator of its own, so the rows are a function of
    ``(seed, shard)`` alone, not of the thread count."""
    cells = np.empty((n, fields), np.int32)
    labels = np.empty(n, np.float32)
    cards = cardinalities(fields)
    table = planted(np.arange(features))
    value = np.float32(fields ** -0.5)
    power = 1.0 - ZIPF_EXPONENT

    def draw(lo: int):
        hi = min(n, lo + GEN_BLOCK)
        rng = np.random.default_rng([seed, shard, n, fields, lo])
        u = rng.random((hi - lo, fields))
        # inverse of the continuous power law on [1, card + 1)
        v = np.floor(((((cards + 1.0) ** power - 1.0) * u + 1.0)
                      ** (1.0 / power))).astype(np.uint64) - np.uint64(1)
        v = np.minimum(v, (cards - 1).astype(np.uint64))
        key = v * np.uint64(fields) + np.arange(fields, dtype=np.uint64)
        # the hash's top 32 bits scaled into [0, features): no division
        cells[lo:hi] = (((mix(key + np.uint64(0x51ED27)) >> np.uint64(32))
                         * np.uint64(features)) >> np.uint64(32)
                        ).astype(np.int32)
        margin = table[cells[lo:hi]].sum(axis=1, dtype=np.float32) * value
        return rng, margin

    def fill(lo: int, bias: float) -> None:
        rng, margin = draw(lo)
        p = 1.0 / (1.0 + np.exp(-(margin + bias)))
        labels[lo:min(n, lo + GEN_BLOCK)] = rng.random(len(p)) < p

    _rng, first = draw(0)
    lo_b, hi_b = -20.0, 20.0
    for _ in range(40):                  # the bias that gives 26%
        bias = 0.5 * (lo_b + hi_b)
        share = float(np.mean(1.0 / (1.0 + np.exp(-(first + bias)))))
        lo_b, hi_b = (bias, hi_b) if share < POSITIVE_SHARE else (lo_b, bias)
    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(lambda lo: fill(lo, bias), range(0, n, GEN_BLOCK)))
    return cells, labels


def hot_share(cells: np.ndarray, features: int) -> float:
    """The share of non-zeros that the 8,192 most frequent cells hold."""
    counts = np.bincount(cells.reshape(-1), minlength=features)
    return float(np.sort(counts)[-HOT_CELLS:].sum() / cells.size)


class Data:
    """One rank's shard.  ``grid`` (the control of ``correct``) is the
    float grid the values handed to the job are rounded to; the
    reference keeps the true ones."""

    def __init__(self, cfg: dict, seed: int, shard: int, world: int,
                 threads: int, rows: int | None = None,
                 grid: str | None = None):
        self.n, self.k = rows or cfg["rows_per_chip"], cfg["nnz_per_row"]
        self.features = cfg["num_feature"]
        self.seed, self.shard, self.world, self.grid = seed, shard, world, grid
        self.history_rows = 2 * cfg["size_memory"] + 1
        # the configuration's L1 a row (the loss is a sum over rows):
        # its own at its own size; at a test's few rows the whole
        # penalty would end the job within a window (some 12 iterations
        # at 16,384 rows, where the shard is still descending at its 40th)
        self.reg_l1 = cfg["reg_L1"] * self.n / cfg["rows_per_chip"]
        self.cells, self.labels = make_rows(
            seed, shard, self.n, self.k, self.features, threads)
        self.values = np.full((self.n, self.k), self.k ** -0.5, np.float32)
        self.values_run = self.values
        if grid:
            import ml_dtypes

            self.values_run = self.values.astype(
                getattr(ml_dtypes, grid)).astype(np.float32)
        self.seen = {}


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    from perfbench.harness import Refused
    from rabit_tpu.learn import linear

    if not hasattr(linear, "stage_rows"):
        # the parent of the PR that added the cell: its objective pads
        # 39 slots to 128 lanes (17 GB for this shard) and takes a file
        raise Refused("this program cannot run the L-BFGS cell: "
                      "rabit_tpu.learn.linear has no stage_rows")
    # `rows` first: a run of the benchmark names none, and does not wait
    # here for the device that the main thread is opening
    if rows is not None and not on_chip():
        k, features = REHEARSAL_WIDTHS
        cfg = {**cfg, "nnz_per_row": min(cfg["nnz_per_row"], k),
               "num_feature": min(cfg["num_feature"], features)}
        print(f"perfbench lbfgs: a rehearsal off the chip, "
              f"{cfg['nnz_per_row']} non-zeros a row over "
              f"{cfg['num_feature']} features in place of the "
              "configuration's", file=sys.stderr, flush=True)
    return Data(cfg, seed, shard, world, threads, rows, grid)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------
def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """A version is an outer iteration over every row of every rank."""
    return {"work_per_version": data.n * data.world,
            "kernel_shape": {"rows": data.n, "nnz_per_row": data.k,
                             "features": data.features,
                             "ops_dtype": "bfloat16"}}


def mosaic(kwargs: dict) -> bool:
    """Whether a ``pallas_call`` with these arguments lowers to a Mosaic
    kernel: on a TPU every one does that is not interpreted.  In a
    rehearsal the interpreted kernel stands for it."""
    return not kwargs.get("interpret", False) or not on_chip()


def history_row(state: dict, i: int) -> int:
    """Where the solver's rolling history keeps logical row ``i`` (s
    vectors 0..m-1, y vectors m..2m-1, oldest first; row 2m is the
    steepest descent), from the ``offset`` it commits."""
    m = state["size_memory"]
    if i == 2 * m:
        return i
    return (i + state["offset"]) % m + (m if i >= m else 0)


class Commits:
    """What the job handed ``rabit_tpu.checkpoint``: the objective of
    every commit, and enough to rebuild the history of any of them.  The
    solver rewrites its 168 MB history in place, four rows an iteration
    (the y it completes, the steepest descent, the gradient it stores,
    the step it took), so the first commit is copied whole and every
    later one leaves the rows that changed since the one before: 32 MB
    a commit inside the window, not 168, into buffers made before the
    job."""

    def __init__(self, rows: int, width: int):
        self.kept: list[dict] = []
        self.objvals: list[float] = []
        # made and touched before the job starts: no fresh pages, and no
        # half second of them, inside a commit
        self._base = np.full((rows, width), 0.0)
        self._pool = list(np.full((POOL_ROWS, width), 0.0))

    def keep(self, glob: dict, local: dict) -> None:
        hist = local["hist"]
        state = {"weight": glob["weight"], "offset": int(glob["offset"]),
                 "old_objval": float(glob["old_objval"]),
                 "obj_state": glob["obj_state"],
                 "size_memory": int(glob["size_memory"]),
                 "num_useful": int(local["num_useful"])}
        m, n = state["size_memory"], state["num_useful"]
        if not self.kept:
            np.copyto(self._base, hist)
            state["rows"] = {}
        else:
            before = self.kept[-1]
            changed = {history_row(before, m + before["num_useful"] - 1),
                       2 * m, history_row(state, m + n - 1),
                       history_row(state, n - 1)}
            state["rows"] = {}
            for r in sorted(changed):
                into = self._pool.pop() if self._pool else np.empty_like(
                    hist[r])
                np.copyto(into, hist[r])
                state["rows"][r] = into
        self.objvals.append(state["old_objval"])
        self.kept.append(state)

    def at(self, k: int) -> dict:
        """Commit ``k`` (0 the first) with its whole history."""
        hist = self._base.copy()
        for state in self.kept[1:k + 1]:
            for r, values in state["rows"].items():
                hist[r] = values
        return {**self.kept[k], "hist": hist}

    def steps(self) -> list:
        """The (before, after) pairs to replay: the first step, and the
        last if it is another."""
        last = len(self.kept) - 1
        return [(self.at(k - 1), self.at(k))
                for k in sorted({min(1, last), last}) if k >= 1]


def watch(data: Data, spans, trace: bool) -> list:
    """Wrappers around the calls into the learner's layers; returns the
    undo list.  In every run: what ``linear.stage_rows`` staged (its
    types; the harness span ``stage`` to ``block_until_ready`` of the
    staged arrays), the kernels handed to ``pallas_call`` while the
    job's programs were traced, the compile requests between commits
    and the payloads of the commits, all kept in ``data.seen`` for
    ``check``."""
    import jax
    from jax.experimental import pallas

    import rabit_tpu
    from rabit_tpu.learn import linear
    from rabit_tpu.utils import compile_cache

    stage_fn, call, commit = (linear.stage_rows, pallas.pallas_call,
                              rabit_tpu.checkpoint)
    seen = data.seen = {"staged": None, "mosaic_kernels": [], "jobs": 0,
                        "compile_requests": [], "commit_at": [],
                        "commits": Commits(data.history_rows,
                                           data.features + 1),
                        "spans": spans,
                        "returned": 0}
    compiles = compile_cache.count_compiles()

    def seen_stage(*a, **kw):
        token = spans.begin("stage")
        shard = stage_fn(*a, **kw)
        jax.block_until_ready(shard.arrays)
        spans.end("stage", token)
        stats = jax.local_devices()[0].memory_stats() or {}
        spans.counters.setdefault("peak_bytes_after_stage", int(
            stats.get("peak_bytes_in_use", 0)))
        total = sum(x.nbytes for x in shard.arrays)
        seen["staged"] = sorted({str(x.dtype) for x in shard.arrays
                                 if 10 * x.nbytes >= total})
        seen["slots"] = (shard.nnz, shard.nnz_padded)
        return shard

    def seen_call(kernel_fn, *a, **kw):
        if mosaic(kw):
            seen["mosaic_kernels"].append(kw.get("name") or getattr(
                getattr(kernel_fn, "func", kernel_fn), "__name__", "?"))
        return call(kernel_fn, *a, **kw)

    def seen_commit(glob, local=None, *a, **kw):
        if compiles is not None:
            took = compiles.take()
            seen["compile_requests"].append(
                (seen["jobs"], took["misses"] + took["hits"]))
        seen["commit_at"].append(round(time.perf_counter(), 3))
        if seen["jobs"] == 1:             # not the traced run's resume
            seen["commits"].keep(glob, local)
        return commit(glob, local, *a, **kw)

    linear.stage_rows = seen_stage
    pallas.pallas_call = seen_call
    rabit_tpu.checkpoint = seen_commit
    undo = [(linear, "stage_rows", stage_fn),
            (pallas, "pallas_call", call),
            (rabit_tpu, "checkpoint", commit)]
    if not on_chip():                   # a rehearsal: the CPU interprets
        from rabit_tpu.ops import sparse_linear_kernel as sk

        for name in KERNELS:
            def interpreted(*a, _orig=getattr(sk, name), **kw):
                return _orig(*a, **{**kw, "interpret": True})

            undo.append((sk, name, getattr(sk, name)))
            setattr(sk, name, interpreted)
    return undo


def run_job(cfg: dict, traffic: dict, data: Data) -> None:
    """The job, through the entry points a user calls.  Returns only by
    the commit wrapper's ``WindowClosed``."""
    import gc

    from rabit_tpu.learn.linear import LinearObjFunction

    gc.collect()        # an earlier job's shard (objective and solver
    #                     hold each other) leaves the device first
    data.seen["jobs"] += 1
    obj = LinearObjFunction()
    obj.set_param("num_feature", str(data.features))
    obj.set_param("reg_L1", str(data.reg_l1))
    for name in ("base_score", "reg_L2",
                 "size_memory", "linesearch_c1", "linesearch_backoff",
                 "max_linesearch_iter", "min_lbfgs_iter", "lbfgs_stop_tol",
                 "max_lbfgs_iter"):
        obj.set_param(name, str(cfg[name]))
    obj.set_param("objective", cfg["loss"])
    obj.set_param("silent", "1")
    obj.load_arrays(data.cells, data.values_run, data.labels, data.features)
    obj.lbfgs.run()
    data.seen["returned"] += 1            # converged, or out of iterations


def committed(model) -> dict:
    """The global state ``load_checkpoint`` gave, as arrays."""
    return {"weight": np.asarray(model["weight"], np.float64),
            "dot_buf": np.asarray(model["dot_buf"], np.float64),
            "scalars": np.array([model["num_iteration"], model["offset"],
                                 model["old_objval"], model["init_objval"]],
                                np.float64)}


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
def replay(cfg: dict, data: Data, before: dict, after: dict) -> dict:
    """One step of the timed path held against the reference: from the
    state committed as ``before`` the reference computes the objective
    and the gradient at those weights and its own direction, and reads
    ``after`` against them."""
    c, c1 = data.reg_l1, float(cfg["linesearch_c1"])
    backoff = float(cfg["linesearch_backoff"])
    m = before["size_memory"]
    w0, w1 = before["weight"], after["weight"]
    base = float(before["obj_state"][0])
    rows = (data.cells, data.values, data.labels)

    def objective(w, want_gradient=False):
        f, g = ref.loss_and_gradient(*rows, w, base + w[-1], want_gradient)
        return f + c * float(np.abs(w).sum()), g

    f0, g0 = objective(w0, True)
    f1, _ = objective(w1)

    def row(state, i):
        return state["hist"][history_row(state, i)]

    n0, n1 = before["num_useful"], after["num_useful"]
    # the newest y is this gradient less the one `before` holds
    pairs = [(row(before, i),
              row(before, m + i) if i < n0 - 1
              else g0 - row(before, m + i)) for i in range(n0)]
    d, pg = ref.direction(g0, w0, pairs, c)
    slope = float(d @ pg)
    step = w1 - w0
    # the reference's own step (sign clamp included) at the length of
    # the backtracking sequence that comes closest to the program's
    own, halvings = min(
        ((ref.trial_point(w0, backoff ** h * d, c) - w0, h)
         for h in range(MAX_HALVINGS)),
        key=lambda t: float(np.linalg.norm(step - t[0])))
    alpha = backoff ** halvings
    def gap(a, b):
        return 1.0 - float(a @ b) / (float(np.linalg.norm(a))
                                     * float(np.linalg.norm(b)) + 1e-300)

    # OWL-QN's orthant tests are steps, not slopes: a weight at zero
    # moves only if its gradient cell passes reg_L1, and a component
    # that does not descend along the pseudo-gradient is zeroed.  A
    # cell within a float32 rounding of the threshold moves a whole
    # component in one step and leaves it in the other (one such in a
    # few replays on the chip, dozens under the control: PERF.md
    # section 2).  They are counted, and the cosine is of the rest
    flips = (step != 0.0) != (own != 0.0)
    longer_passes = 0.0
    if halvings > 0:
        longer = alpha / backoff
        f2, _ = objective(ref.trial_point(w0, longer * d, c))
        longer_passes = float(ref.armijo_holds(
            f2, f0, longer, slope, c1, slack=-ROUNDING * abs(f0)))
    stored = row(after, m + n1 - 1)
    return {
        "objval_rel_err": max(abs(after["old_objval"] - f1) / abs(f1),
                              abs(before["old_objval"] - f0) / abs(f0)),
        "grad_rel_err": float(np.linalg.norm(stored - g0)
                              / np.linalg.norm(g0)),
        "step_cosine_gap": gap(step[~flips], own[~flips]),
        "orthant_flips": float(flips.sum()),
        "armijo_gap": max(0.0, f1 - f0 - c1 * alpha * slope) / abs(f0),
        "step_not_first": longer_passes,
        "alpha": alpha, "f0": f0, "f1": f1,
        "cosine_gap_with_flips": gap(step, own),
    }


def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """The first and the last step of the timed path replayed by the
    plain reference (so that every timed iteration is held against
    two), the objective over every commit, and what ``watch`` saw of
    the staging, the kernels and the compiles."""
    from rabit_tpu import engine

    seen = data.seen
    commits = seen["commits"]
    stats = dict(getattr(engine.get_engine(), "path_stats", {}) or {})
    # the bucketing alone, from the program's own span
    if "stage.bucket.total_s" in stats:
        seen["spans"].seconds.setdefault("stage_bucket", []).append(
            stats["stage.bucket.total_s"])
    worst: dict = {}
    replays = [replay(cfg, data, *pair) for pair in commits.steps()]
    for name in ("objval_rel_err", "grad_rel_err", "step_cosine_gap",
                 "orthant_flips", "armijo_gap", "step_not_first"):
        worst[name] = max((r[name] for r in replays), default=float("inf"))
    warmup = int(traffic.get("warmup_versions", 2))
    objs = commits.objvals
    rises = sum(b > a for a, b in zip(objs[warmup - 1:], objs[warmup:]))
    timed = [k for job, k in seen["compile_requests"] if job == 1]
    at = seen["commit_at"]
    print("perfbench lbfgs saw " + json.dumps({
        "staged": seen["staged"], "slots": seen.get("slots"),
        "mosaic_kernels": sorted(set(seen["mosaic_kernels"])),
        "compile_requests": seen["compile_requests"],
        "objvals": [round(v, 3) for v in objs],
        "replays": [{k: (float("%.4g" % v) if isinstance(v, float) else v)
                     for k, v in r.items()} for r in replays],
        "commit_gaps": [round(b - a, 3) for a, b in zip(at, at[1:])],
        "longest": {k[:-len(".max_s")]: round(v, 4) for k, v in stats.items()
                    if k.endswith(".max_s") and k.startswith(
                        ("learn.", "lbfgs.", "commit", "allreduce"))}}),
        file=sys.stderr, flush=True)
    return {
        **worst,
        "objective_rises": float(rises),
        "stopped_by_convergence": float(seen["returned"]),
        # programs asked of the compiler (built or read from the cache)
        # between the commit that opened the window and the last
        "recompiles_in_window": float(sum(timed[warmup:])),
        "tier_mismatch": float(seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(
            not set(KERNELS) <= set(seen["mosaic_kernels"])),
    }

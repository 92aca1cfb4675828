"""Adapter for the k-means learner: data from the seed, the job through
the public entry point (``rabit_tpu.learn.kmeans.run``), what a version
is, and the comparison with the plain reference.

A configuration names this file as its ``learner``; another learner
(gbdt, lbfgs, a training step) is another file beside it, which the
harness finds by that name and which holds the same six functions:

    make_data(cfg, seed, shard, world, threads, rows, grid) -> data
    describe(cfg, traffic, data) -> {"work_per_version": <rows, tokens
        ... of the whole job a committed version covers>,
        "kernel_shape": <what kernels/<kernel>.py cost() takes; optional>}
    watch(data, spans, trace) -> [(owner, name, original), ...]
        wrappers put around the learner's layers, undone by the harness;
        in the traced run they record the span ``stage`` around staging
        (``stage_s`` reads it), and the configuration gives ``step_op``,
        the pattern of the device operation that runs once a step
        (``kernel_per_step_s`` and ``host_gap_per_step_s`` read it):
        those metrics list no cells, so every cell reports them
    run_job(cfg, traffic, data)      through the public entry point;
        a version is a call of ``rabit_tpu.checkpoint``
    committed(model) -> {name: array}  of what ``load_checkpoint`` gave
    check(cfg, traffic, data, committed, exchange) -> {name: number}
        every number of the configuration's ``correct.limits`` that is
        the learner's own (the harness adds ``version_gap``,
        ``rank_disagreement`` and ``host_ops``, which every job has)

Nothing here reads a private name of the program: the staged tier is
what ``kmeans.prepare_shard`` returned, the kernel is what was handed
to ``jax.experimental.pallas.pallas_call`` while the job's programs
were traced.
"""
from __future__ import annotations

import json
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.reference import kmeans as ref

GEN_BLOCK = 1 << 20
SIGNAL_SLOTS = 8
MAX_ITER = 1 << 40          # run() never stops by itself


# ----------------------------------------------------------------------
# data
# ----------------------------------------------------------------------
def job_seed(seed: int, n: int, k: int, world: int) -> int:
    """The seed handed to the learner: ``seed`` itself unless its init
    would draw one row twice (two equal centroids, one of them empty —
    which the per-iteration path refuses to run on), then the next that
    does not.  Same ``--seed``, same job."""
    while len(set(ref.init_draws(seed, n, k, world)[0])) != k:
        seed += 1
    return seed


def pick_row(seed: int, shard: int, i: int, dim: int, k: int, nnz: int):
    """Row ``picks[i]`` of ``shard``: made by itself, so that the
    reference can rebuild any rank's init rows without that rank's
    shard."""
    band = dim // k
    rng = np.random.default_rng([seed, shard, dim, 0x5EED, i])
    idx = rng.integers(0, dim, nnz, dtype=np.int32)
    idx[:SIGNAL_SLOTS] = i * band + rng.integers(
        0, band, SIGNAL_SLOTS, dtype=np.int32)
    val = ((rng.random(nnz, dtype=np.float32) - 0.5) * 0.34).astype(np.float32)
    val[:SIGNAL_SLOTS] = 1.0 + rng.random(SIGNAL_SLOTS, dtype=np.float32)
    return idx, val


def round_to_grid(val: np.ndarray, grid: str | None) -> np.ndarray:
    """Values rounded to a coarser float grid (the control of
    ``correct``: what a lower precision would store)."""
    if grid is None:
        return val
    import ml_dtypes

    return val.astype(getattr(ml_dtypes, grid)).astype(np.float32)


def make_rows(seed: int, shard: int, n: int, dim: int, k: int, nnz: int,
              picks: list[int], threads: int):
    """Clustered uniform-nnz sparse rows, as ``(n, nnz)`` index and
    value arrays (built as ``chip_smoke.make_rows`` builds them).

    Cluster ``c`` owns the feature band ``[c*dim/k, (c+1)*dim/k)``: a
    row carries 8 signal slots in [1, 2] inside its cluster's band and
    ``nnz - 8`` noise slots in +-0.17 anywhere (repeated indices add
    up).  Row ``r`` belongs to cluster ``r % k``, except that row
    ``picks[i]`` belongs to cluster ``i`` in every shard, so every
    cluster starts with a centroid of its own and every row's margin is
    wide: from the second iteration on every row is with its own
    cluster, rounding cannot flip an assignment, and the comparison
    with the reference measures arithmetic.

    A block of 2^20 rows has a generator of its own, so the rows do not
    depend on the thread count.  Uniform draws go straight into the
    output or into one scratch buffer per thread: fresh memory is the
    dear part on the machines this runs on, so nothing but the output
    is touched for the first time."""
    import threading

    band = dim // k
    if band < 1 or nnz <= SIGNAL_SLOTS:
        raise ValueError(f"make_rows: dim {dim} < k {k} or nnz {nnz} <= 8")
    cluster = cluster_of_rows(n, k, picks)
    idx = np.empty((n, nnz), np.int32)
    val = np.empty((n, nnz), np.float32)
    local = threading.local()
    sig = slice(0, SIGNAL_SLOTS)

    def fill(lo: int) -> None:
        hi = min(n, lo + GEN_BLOCK)
        m = hi - lo
        if getattr(local, "scratch", None) is None:
            local.scratch = np.empty((min(n, GEN_BLOCK), nnz), np.float32)
        unit = local.scratch[:m]
        rng = np.random.default_rng([seed, shard, n, dim, lo])
        rng.random(out=unit, dtype=np.float32)          # [0, 1)
        np.multiply(unit, np.float32(dim), out=unit)
        idx[lo:hi] = unit                               # floor: 0..dim-1
        np.multiply(unit[:, sig], np.float32(band / dim), out=unit[:, sig])
        idx[lo:hi, sig] = unit[:, sig]                  # 0..band-1
        idx[lo:hi, sig] += cluster[lo:hi, None] * band
        out = val[lo:hi]
        rng.random(out=out, dtype=np.float32)
        noise = out[:, SIGNAL_SLOTS:]
        np.subtract(noise, np.float32(0.5), out=noise)
        np.multiply(noise, np.float32(0.34), out=noise)
        np.add(out[:, sig], np.float32(1.0), out=out[:, sig])

    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(fill, range(0, n, GEN_BLOCK)))   # re-raises a failure
    for i, r in enumerate(picks):
        idx[r], val[r] = pick_row(seed, shard, i, dim, k, nnz)
    return idx, val


def cluster_of_rows(n: int, k: int, picks: list[int]) -> np.ndarray:
    cluster = (np.arange(n, dtype=np.int64) % k).astype(np.int32)
    cluster[picks] = np.arange(k, dtype=np.int32)
    return cluster


class Data:
    """One rank's shard and what the reference needs to know of the
    others.  ``shard`` names the rows (a rank's task id: known before
    the process group forms, so the rows are made while it forms);
    which rank holds which shard is settled after the window."""

    def __init__(self, cfg: dict, seed: int, shard: int, world: int,
                 threads: int, rows: int | None = None,
                 grid: str | None = None):
        self.dim, self.k, self.nnz = cfg["dim"], cfg["k"], cfg["nnz"]
        self.n = rows or cfg["rows_per_chip"]
        self.shard, self.world = shard, world
        self.seed = job_seed(seed, self.n, self.k, world)
        self.picks, self.roots = ref.init_draws(
            self.seed, self.n, self.k, world)
        self.idx, self.val = make_rows(
            self.seed, shard, self.n, self.dim, self.k, self.nnz,
            self.picks, threads)
        # what the job is given: the rows themselves, or (the control
        # of `correct`) the rows as a lower precision would store them;
        # the reference always takes the rows themselves
        self.val_run = round_to_grid(self.val, grid)

    def sparse_mat(self):
        """The CSR view ``kmeans.run`` takes (no copy)."""
        from rabit_tpu.learn.data import SparseMat

        return SparseMat(
            indptr=np.arange(self.n + 1, dtype=np.int64) * self.nnz,
            findex=self.idx.reshape(-1), fvalue=self.val_run.reshape(-1),
            labels=np.zeros(self.n, np.float32), feat_dim=self.dim)

    def init_rows(self, shard_of_rank=None):
        """The rows the job's centroids start from: centroid ``i`` is
        row ``picks[i]`` of the shard that rank ``roots[i]`` holds."""
        shard_of_rank = shard_of_rank or list(range(self.world))
        return [pick_row(self.seed, shard_of_rank[self.roots[i]], i,
                         self.dim, self.k, self.nnz) for i in range(self.k)]

    def cluster_sizes(self) -> np.ndarray:
        """Rows of each cluster in the whole job, as the generator dealt
        them (every shard deals alike)."""
        return self.world * np.bincount(
            cluster_of_rows(self.n, self.k, self.picks), minlength=self.k)


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    return Data(cfg, seed, shard, world, threads, rows, grid)


# ----------------------------------------------------------------------
# the job
# ----------------------------------------------------------------------
def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """The rows a version covers (all ranks, every iteration of a
    chain), and the shapes the kernels' cost functions take."""
    chain = int(traffic.get("device_chain", 0))
    dtype = traffic.get("compute_dtype", cfg["compute_dtype"])
    return {
        "work_per_version": data.n * data.world * (chain if chain > 1 else 1),
        "kernel_shape": {
            "rows": data.n, "k": data.k, "nnz": data.nnz,
            "dim_staged": -(-data.dim // 128) * 128,
            "row_itemsize": 2 if dtype == "bfloat16" else 4,
            "ops_dtype": "bfloat16",   # both kernels multiply in bf16
        },
    }


def mosaic(kwargs: dict) -> bool:
    """Whether a ``pallas_call`` with these keywords lowers to a Mosaic
    kernel (``tpu_custom_call``): on a TPU every one does that is not
    interpreted, and the harness runs on nothing else."""
    return not kwargs.get("interpret", False)


def watch(data: Data, spans, trace: bool) -> list:
    """Wrappers around the calls into the learner's layers; returns the
    undo list.  In every run: what ``kmeans.prepare_shard`` staged (the
    tier, as the arrays it returned) and the kernels handed to
    ``pallas_call`` while the job's programs were traced, both kept in
    ``data.seen`` for ``check``.  In the traced run also the host span
    ``stage`` (``SparseMat.to_ell`` opens it, ``prepare_shard`` closes
    it on ``block_until_ready``)."""
    import jax
    from jax.experimental import pallas

    from rabit_tpu.learn import kmeans
    from rabit_tpu.learn.data import SparseMat

    to_ell, prepare, call = (SparseMat.to_ell, kmeans.prepare_shard,
                             pallas.pallas_call)
    seen = data.seen = {"staged": None, "mosaic_kernels": []}
    state = {}

    def seen_to_ell(self, *a, **kw):
        state["t0"] = spans.begin("stage")
        return to_ell(self, *a, **kw)

    def seen_prepare(*a, **kw):
        shard = prepare(*a, **kw)
        arrays = [x for x in jax.tree_util.tree_leaves(shard)
                  if isinstance(x, jax.Array)]
        if trace:
            jax.block_until_ready(arrays)
            spans.end("stage", state.pop("t0"))
            stats = jax.local_devices()[0].memory_stats() or {}
            spans.counters.setdefault("peak_bytes_after_stage", int(
                stats.get("peak_bytes_in_use", 0)))
        seen["staged"] = staged_dtypes(arrays)
        return shard

    def seen_call(kernel, *a, **kw):
        if mosaic(kw):
            seen["mosaic_kernels"].append(kw.get("name") or getattr(
                getattr(kernel, "func", kernel), "__name__", "?"))
        return call(kernel, *a, **kw)

    kmeans.prepare_shard = seen_prepare
    pallas.pallas_call = seen_call
    undo = [(kmeans, "prepare_shard", prepare),
            (pallas, "pallas_call", call)]
    if trace:
        SparseMat.to_ell = seen_to_ell
        undo.append((SparseMat, "to_ell", to_ell))
    return undo


def staged_dtypes(arrays) -> list[str]:
    """The types that hold the staged shard: of every array with a
    tenth or more of the staged bytes, sorted.  That is the tier in the
    only sense a later PR cannot rename: ``["bfloat16"]`` is rows stored
    dense at half width, ``["float32", "int32"]`` is ELL slots,
    ``["float32"]`` is rows densified in float32."""
    total = sum(x.nbytes for x in arrays)
    return sorted({str(x.dtype) for x in arrays if 10 * x.nbytes >= total})


def run_job(cfg: dict, traffic: dict, data: Data) -> None:
    """The job, through the entry point a user calls.  Returns only by
    the commit wrapper's ``WindowClosed``."""
    from rabit_tpu.learn import kmeans

    kw = {}
    if traffic.get("hash_dim") is not None:
        kw["hash_dim"] = int(traffic["hash_dim"])
    kmeans.run(data.sparse_mat(), data.k, MAX_ITER, seed=data.seed,
               device_chain=int(traffic.get("device_chain", 0)),
               compute_dtype=traffic.get("compute_dtype",
                                         cfg["compute_dtype"]), **kw)


def committed(model) -> dict:
    """What the job committed, of the model ``load_checkpoint`` gave."""
    return {"centroids": np.asarray(model.centroids, np.float32)}


# ----------------------------------------------------------------------
# correct
# ----------------------------------------------------------------------
def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """The committed centroids against the plain reference on the same
    rows, and the tier and kernel the configuration states against what
    ``watch`` saw.  ``exchange(tag, array)`` returns the list of every
    rank's array (the reference's own way across ranks: files, not the
    program's collectives).

    The number compared is the relative error times the square root of
    the job's rows: the centroids are means, the error of a mean of N
    roundings falls as 1/sqrt(N), and this product reads the same at
    16 thousand rows and at 96 million (PERF.md), so one limit holds
    a configuration's cells of every size."""
    iters = int(cfg["correct"]["reference_iterations"])
    shard_of_rank = [int(x[0]) for x in exchange(
        "shard", np.array([data.shard]))]
    shard = ref.ShardStats(data.idx, data.val, data.dim, data.k)
    counts_seen = []

    def combine(it, sums, counts):
        parts = exchange(f"ref{it}", np.concatenate(
            [sums, counts[:, None]], axis=1))
        total = np.sum(parts, axis=0)          # float64, rank order
        counts_seen.append(total[:, -1])
        return total[:, :-1], total[:, -1]

    try:
        cents = ref.run(shard, ref.init_centroids(
            data.init_rows(shard_of_rank), data.dim), iters, combine)
    finally:
        shard.free()
    rows = data.n * data.world
    err = ref.rel_err(committed["centroids"], cents[-1])
    print("perfbench kmeans saw " + json.dumps(data.seen), file=sys.stderr,
          flush=True)
    return {
        "centroid_err_x_sqrt_rows": err * rows ** 0.5,
        "centroid_rel_err": err,
        # the reference's last pass put every row with the cluster the
        # generator dealt it to: its centroids are the fixed point the
        # job has been at since its 2nd iteration
        "reference_count_gap": float(np.max(np.abs(
            counts_seen[-1] - data.cluster_sizes()))),
        "tier_mismatch": float(
            data.seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(not data.seen["mosaic_kernels"]),
    }

"""Adapter for k-means on a shard larger than the chip: the same
learner, entry point, rows and watch as ``learners/kmeans.py``
(``rabit_tpu.learn.kmeans.run`` on libsvm-shaped ELL rows; a version is
an iteration), at a size whose fused-ELL form the device cannot hold, so
that ``kmeans.prepare_shard`` stages what fits and every iteration
streams the rest from the host under the kernel.  The six functions are
those ``learners/kmeans.py`` lists; the rows, the job, what it committed
and the watch on the staged tier and the kernel are that file's own.

What is this file's: the shape of ONE kernel call (``describe``), read
off the arrays ``prepare_shard`` returned once it has returned (the
shard is taken a chunk a call, and ``readers.roofline`` multiplies a
call's need by the calls the trace holds); the reference taken piece by
piece (``reference/kmeans_blocks.py``: neither do the rows fit the
device for the reference); and ``streamed_rows_gap``, which holds the
program's own count of the rows that crossed the link against the rows
of the arrays it kept on the device.

Nothing here reads a private name of the program, with one exception
that no chip run reaches: a rehearsal off the chip that names its own
``--rows`` (``tests/perfbench``: 16,384 rows fit any budget) is steered
into the streamed tier by the budget and the chunk size the tier's rule
reads, and says so on stderr (``rehearse``).
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from perfbench import harness
from perfbench.reference import kmeans as ref
from perfbench.reference import kmeans_blocks as ref_blocks

# the one instance the harness and the tests' steering files know
base = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "kmeans.py"))

run_job = base.run_job
committed = base.committed

# a rehearsal's shard: chunks of one kernel block, three of them
# resident beside the program's ring
REHEARSAL_CHUNK_ROWS = 2048
REHEARSAL_RESIDENT = 3


class Data(base.Data):
    """``learners/kmeans.py``'s shard (the same rows from the same
    seed).  Under the control of ``correct`` (``grid``) the values as
    made are let go once their rounded copy stands, and ``check`` makes
    the rows again from the seed for the reference when the job is gone:
    beside the 19.3 GB the job is given and the 14 GB more its process
    holds while it runs (PERF.md section 4), a second 9.7 GB of values
    does not fit the one-chip machine."""

    def __init__(self, cfg, seed, shard, world, threads, rows=None,
                 grid=None):
        super().__init__(cfg, seed, shard, world, threads, rows, grid)
        self.rows_named = rows is not None
        self.threads = threads
        if grid is not None:
            self.val = None

    def reference_rows(self):
        """The rows as made, for the reference."""
        if self.val is None:
            self.idx = self.val_run = None      # the job's, and it is gone
            self.idx, self.val = base.make_rows(
                self.seed, self.shard, self.n, self.dim, self.k, self.nnz,
                self.picks, self.threads)
        return self.idx, self.val


def on_chip() -> bool:
    """False only in a rehearsal: off the chip the harness refuses to
    run, so what gets here without one is a test of ``tests/perfbench``
    with the CPU passed off for the chip (as ``learners/gbdt.py`` has
    it)."""
    import jax

    return jax.local_devices()[0].platform == "tpu"


def describe(cfg: dict, traffic: dict, data: Data) -> dict:
    """A version is an iteration over every row of every rank.  The
    kernel's shape is one chunk call's: ``rows`` is filled in by
    ``watch`` when ``prepare_shard`` has returned (the harness keeps
    this dict and writes it out after the window)."""
    about = base.describe(cfg, traffic, data)
    about["kernel_shape"]["rows"] = None
    data.kernel_shape = about["kernel_shape"]
    return about


def rehearse(data: Data) -> list:
    """Off the chip and under ``--rows`` only: the budget the tier's
    rule reads holds ``REHEARSAL_RESIDENT`` chunks of
    ``REHEARSAL_CHUNK_ROWS`` rows beside the ring, so that tiny rows
    stream as the configuration's do."""
    from rabit_tpu.learn import kmeans

    chunk_bytes = REHEARSAL_CHUNK_ROWS * (data.nnz * 8 + 4)
    budget = (REHEARSAL_RESIDENT + kmeans._STREAM_RING) * chunk_bytes
    print("perfbench kmeans_stream: a rehearsal off the chip, the device's "
          f"budget taken as {budget} bytes and a chunk as "
          f"{REHEARSAL_CHUNK_ROWS} rows, so that {data.n} rows stream",
          file=sys.stderr, flush=True)
    undo = [(kmeans, "_stream_budget", kmeans._stream_budget),
            (kmeans, "_STAGE_CHUNK_ROWS", kmeans._STAGE_CHUNK_ROWS)]
    kmeans._stream_budget = lambda: budget
    kmeans._STAGE_CHUNK_ROWS = REHEARSAL_CHUNK_ROWS
    return undo


def watch(data: Data, spans, trace: bool) -> list:
    """``learners/kmeans.py``'s wrappers, and around its own of
    ``prepare_shard`` one more: the rows of one kernel call and the
    rows kept on the device, both read off the validity vectors (the
    arrays of one dimension) among what was returned, a chunk's or the
    whole shard's; and the staged types by their share of the bytes
    (``staged_dtypes``)."""
    import jax

    from rabit_tpu.learn import kmeans

    undo = list(base.watch(data, spans, trace))
    if data.rows_named and not on_chip():
        undo += rehearse(data)
    prepare = kmeans.prepare_shard
    data.seen.update(call_rows=None, resident_rows=None)

    def seen_prepare(*a, **kw):
        shard = prepare(*a, **kw)
        leaves = jax.tree_util.tree_leaves(shard)
        data.seen["staged"] = staged_dtypes(
            [x for x in leaves if isinstance(x, jax.Array)])
        vectors = [x for x in leaves if getattr(x, "ndim", 0) == 1]
        data.seen["call_rows"] = min(len(x) for x in vectors)
        data.seen["resident_rows"] = sum(
            len(x) for x in vectors if isinstance(x, jax.Array))
        data.kernel_shape["rows"] = data.seen["call_rows"]
        return shard

    kmeans.prepare_shard = seen_prepare
    undo.append((kmeans, "prepare_shard", prepare))
    return undo


def staged_dtypes(arrays) -> list[str]:
    """The types that hold the staged shard, as ``learners/kmeans.py``
    has them, taken type by type and not array by array: a type that
    holds a tenth or more of the staged bytes, sorted.  The resident
    part is some fifty chunks here, each array a hundredth of the
    whole."""
    total = sum(x.nbytes for x in arrays)
    held = {}
    for x in arrays:
        held[str(x.dtype)] = held.get(str(x.dtype), 0) + x.nbytes
    return sorted(t for t, nbytes in held.items() if 10 * nbytes >= total)


def make_data(cfg, seed, shard, world, threads, rows=None, grid=None) -> Data:
    return Data(cfg, seed, shard, world, threads, rows, grid)


def check(cfg: dict, traffic: dict, data: Data, committed: dict,
          exchange) -> dict:
    """``learners/kmeans.py``'s comparison with the reference taken
    piece by piece, and the program's count of streamed rows against
    the arrays it staged."""
    from rabit_tpu import engine

    iters = int(cfg["correct"]["reference_iterations"])
    shard_of_rank = [int(x[0]) for x in exchange(
        "shard", np.array([data.shard]))]
    shard = ref_blocks.PiecewiseStats(*data.reference_rows(), data.dim,
                                      data.k)
    counts_seen = []

    def combine(it, sums, counts):
        parts = exchange(f"ref{it}", np.concatenate(
            [sums, counts[:, None]], axis=1))
        total = np.sum(parts, axis=0)          # float64, rank order
        counts_seen.append(total[:, -1])
        return total[:, :-1], total[:, -1]

    cents = ref.run(shard, ref.init_centroids(
        data.init_rows(shard_of_rank), data.dim), iters, combine)
    rows = data.n * data.world
    err = ref.rel_err(committed["centroids"], cents[-1])
    stats = engine.get_engine().path_stats
    versions = stats.get("learn.versions", 0)
    streamed = stats.get("stream.rows", 0) / versions if versions else None
    resident = data.seen["resident_rows"]
    print("perfbench kmeans_stream saw " + json.dumps(
        {**data.seen, "streamed_rows_per_version": streamed,
         **{k: v for k, v in stats.items()
            if k.startswith(("stream.", "stage.resident", "stage.host",
                             "learn.step.", "learn.stream."))
            and not k.endswith(".self_s")}}),
        file=sys.stderr, flush=True)
    return {
        "centroid_err_x_sqrt_rows": err * rows ** 0.5,
        "centroid_rel_err": err,
        "reference_count_gap": float(np.max(np.abs(
            counts_seen[-1] - data.cluster_sizes()))),
        "tier_mismatch": float(
            data.seen["staged"] != sorted(cfg["staged_dtypes"])),
        "kernel_missing": float(not data.seen["mosaic_kernels"]),
        "streamed_rows_gap": (
            float("inf") if not streamed or resident is None
            else abs(streamed - (data.n - resident))),
    }

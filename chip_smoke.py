"""chip_smoke.py — the quickest proof that the device plane runs on the chip.

    python chip_smoke.py [--seed N]        one chip, one process
    python chip_smoke.py --chips 4         the cross-chip paths, nothing else

It drives the main path through the entry points a user calls, at real
sizes, on data made from ``--seed``, and checks every result against a
plain float32/float64 reference written here, independent of the code
under test.  It measures nothing: seconds are printed so a reader can
see where a run's time went, and no rate is derived from them.

One chip (the default, what the driver runs):

* k-means through ``rabit_tpu.learn.kmeans.run`` — init, load_checkpoint,
  the feature-dimension allreduce, ``prepare_shard`` into the bf16
  ``dense16`` tier, chained ``device_iterations`` on the fused Pallas
  kernel, checkpoint commits, finalize — at d=256, k=64, 32-nnz rows and
  as many rows as the chip's reported memory holds (up to the 24M of
  ``tools/big_kmeans.py dense``); then a second ``run`` that resumes from
  the committed version;
* the sparse ``ell_fused`` tier through ``kmeans.run`` at d=512;
* one GBDT level through ``histogram.build_level_local`` at 8 nodes x 64
  features x 256 bins x 262k rows, and a boosting round through
  ``boosting.train``;
* both sparse products of the wide linear path (``X w``, ``X^T g``)
  through ``linear.stage_rows`` at 262k rows x 39 non-zeros over a
  million weights.

``--chips 4`` (the builder runs it; four chips cost four times as much):

* one process per chip under the tracker: ``launch_local -n 4`` of
  ``tests/workers/check_xla_chip.py`` (XLA engine, device-plane
  allreduce/allgather of 64 KB, 4 MB and 64 MB, broadcast, checkpoint),
  once with the engine's allreduce lowered to ``psum`` and once to the
  Pallas ring;
* then, in this process over the four local chips: a data-parallel
  k-means step against its one-chip result, and psum against the Pallas
  remote-DMA ring, bit for bit.

The chip belongs to one process at a time, so this process stays off JAX
until its children are gone.  No path may quietly leave the chip: the
script refuses to start where ``jax.devices()[0].platform`` is not
``tpu``, every library default that chooses "Pallas or not" asks
``rabit_tpu.ops.on_tpu`` (asserted here), and a phase that fails raises —
nothing catches it, so the last line is only ever printed by a run whose
every phase passed.  That line is the contract with the driver:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from rabit_tpu.utils.checks import check  # noqa: E402 — raises, -O safe

K = 64                      # clusters (the flagship's published shape)
NNZ = 32                    # non-zeros per row
CHAIN = 8                   # device-chained iterations per checkpoint
DENSE_DIM = 256
DENSE_ROWS_MIN = 4 << 20    # 2 GB resident in bf16: past the f32 tier
DENSE_ROWS_MAX = 23 << 20   # ~24M, tools/big_kmeans.py dense (12.3 GB)
ELL_DIM = 512
ELL_ROWS = 4 << 20          # 8 GB dense in f32: over the densify budget
GBDT_ROWS, GBDT_FEATS, GBDT_BINS, GBDT_NODES = 1 << 18, 64, 256, 8
LBFGS_ROWS, LBFGS_FEATS, LBFGS_NNZ = 1 << 18, 1_000_000, 39
MESH_ROWS_PER_CHIP = 1 << 20
ALLREDUCE_BYTES = (64 << 10, 4 << 20, 64 << 20)
WORKER_PHASE_TIMEOUT_SEC = 300

# Stated tolerances.  Both k-means tiers round each stored or
# reconstructed value to bf16 (relative 2^-9 = 2e-3 at worst) and
# accumulate in float32; a centroid is a mean over >10^4 such values, so
# the rounding averages down — 3e-5 for the XLA bf16 path on 262k rows
# of this data (CPU, PR 21).  The data is built so that no assignment
# can flip (make_rows), which is what lets the bound be this tight.
KMEANS_TOL = 1e-3           # relative Frobenius error of the centroids
MESH_TOL = 1e-5             # 4 chips vs 1 chip: f32 summation order only
BF16_EPS = 2.0 ** -8        # per-element weight rounding in the histogram


def emit(**fields) -> None:
    print(json.dumps(fields, sort_keys=True), flush=True)


class Laps:
    """Host-clock seconds between named points.  Every lap ends on a
    fetched result or a ``block_until_ready``, so device work is in."""

    def __init__(self) -> None:
        self._t = time.perf_counter()
        self.seconds: dict[str, float] = {}

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = round(now - self._t, 2)
        self._t = now


# ----------------------------------------------------------------------
# data and references
# ----------------------------------------------------------------------
def init_rows(seed: int, n: int, k: int) -> list[int]:
    """The rows ``kmeans.init_centroids`` seeds its centroids from: its
    first ``k`` draws of ``default_rng(seed).integers(n)``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [int(rng.integers(n)) for _ in range(k)]


def make_rows(seed: int, n: int, dim: int, k: int, nnz: int = NNZ):
    """Clustered uniform-nnz sparse rows as a CSR ``SparseMat``.

    Cluster ``c`` owns the feature band ``[c*dim/k, (c+1)*dim/k)``: a
    row carries 8 positive signal slots inside its cluster's band and
    ``nnz - 8`` small noise slots anywhere (repeated indices add up, as
    the loaders define).  Row ``r`` belongs to cluster ``r % k``, except
    that the rows ``init_centroids`` will draw are dealt one to each
    cluster.  That makes the problem well-conditioned on purpose: every
    cluster starts with one centroid of its own and every row's margin
    between its cluster and the next is wide, so rounding the stored
    rows to bf16 cannot flip an assignment and the comparison with the
    float32 reference measures arithmetic, not the chaos of k-means on
    overlapping blobs (where a bf16 and a float32 run of the SAME code
    drift 5-15% apart within eight iterations)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from rabit_tpu.learn.data import SparseMat

    band = dim // k
    check(band >= 1 and nnz > 8, f"make_rows: dim {dim} < k {k}")
    picks = init_rows(seed, n, k)
    check(len(set(picks)) == k,
          f"seed {seed} draws an init row twice; pick another seed")
    cluster = (np.arange(n) % k).astype(np.int32)
    cluster[picks] = np.arange(k, dtype=np.int32)
    findex = np.empty((n, nnz), np.int32)
    fvalue = np.empty((n, nnz), np.float32)
    step = 1 << 20

    def fill(lo: int) -> None:
        # a generator per block, so blocks fill in parallel (numpy
        # draws outside the GIL) and the rows do not depend on how many
        # threads there were
        rng = np.random.default_rng([seed, n, dim, lo])
        hi = min(n, lo + step)
        m = hi - lo
        findex[lo:hi] = rng.integers(0, dim, (m, nnz), dtype=np.int32)
        findex[lo:hi, :8] = (cluster[lo:hi, None] * band
                             + rng.integers(0, band, (m, 8), dtype=np.int32))
        rng.standard_normal(dtype=np.float32, out=fvalue[lo:hi])
        fvalue[lo:hi] *= 0.1
        fvalue[lo:hi, :8] = 1.0 + rng.random((m, 8), dtype=np.float32)

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(fill, range(0, n, step)))   # re-raises a failure
    return SparseMat(indptr=np.arange(n + 1, dtype=np.int64) * nnz,
                     findex=findex.reshape(-1), fvalue=fvalue.reshape(-1),
                     labels=np.zeros(n, np.float32), feat_dim=dim)


def reference_kmeans(data, k: int, seed: int, at_iters: tuple[int, ...],
                     block: int = 1 << 14) -> dict:
    """Cosine k-means in plain float32 ``jax.numpy`` at the highest
    matmul precision — nothing from ``rabit_tpu`` but the seeded row
    picks of its init (:func:`init_rows`).  Returns the centroids after
    each iteration count in ``at_iters``.

    The rows stay sparse on the device ((n/4, 4*nnz) so the minor
    dimension fills the 128 lanes) and are densified block by block
    each iteration: the dense float32 matrix would not fit beside
    anything at the sizes this runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, dim = data.num_row, data.feat_dim
    nnz = data.nnz // n
    check(n % block == 0, f"reference: {n} rows not a multiple of {block}")
    idx = data.findex.reshape(n, nnz)
    val = data.fvalue.reshape(n, nnz)

    cent = np.zeros((k, dim), np.float32)
    for i, r in enumerate(init_rows(seed, n, k)):
        np.add.at(cent[i], idx[r], val[r])
    cent /= np.maximum(np.linalg.norm(cent, axis=1, keepdims=True), 1e-30)

    grouped = (n // block, block // 4, 4 * nnz)
    idx_d = jax.device_put(idx.reshape(grouped))
    val_d = jax.device_put(val.reshape(grouped))

    @jax.jit
    def iteration(cent, idx_d, val_d):
        cn = cent / (jnp.linalg.norm(cent, axis=1, keepdims=True) + 1e-12)
        features = jnp.arange(dim, dtype=jnp.int32)

        def body(acc, blk):
            bi = blk[0].reshape(block, nnz)
            bv = blk[1].reshape(block, nnz)
            dense = jnp.einsum(
                "rj,rjd->rd", bv,
                (bi[:, :, None] == features).astype(jnp.float32))
            member = jax.nn.one_hot(jnp.argmax(dense @ cn.T, axis=1), k,
                                    dtype=jnp.float32)
            return (acc[0] + member.T @ dense,
                    acc[1] + member.sum(axis=0)), None

        (sums, counts), _ = jax.lax.scan(
            body, (jnp.zeros((k, dim), jnp.float32),
                   jnp.zeros((k,), jnp.float32)), (idx_d, val_d))
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts[:, None], 1.0), cent)
        norm = jnp.linalg.norm(new, axis=1, keepdims=True)
        return jnp.where(norm < 1e-6, new, new / jnp.maximum(norm, 1e-30))

    out = {}
    c = jnp.asarray(cent)
    with jax.default_matmul_precision("highest"):
        for it in range(1, max(at_iters) + 1):
            c = iteration(c, idx_d, val_d)
            if it in at_iters:
                out[it] = np.asarray(c)
    return out


def rel_err(got, want) -> float:
    import numpy as np

    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite centroids")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def kernel_in_program(fn, *shapes) -> bool:
    """Does the program ``fn`` lowers to for ``shapes`` call a Mosaic
    kernel?  Read off the lowered module — the compiler's input, not a
    flag the library set about itself."""
    return "tpu_custom_call" in fn.lower(*shapes).as_text()


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------
def dense_rows(device_budget: int, host_bytes: int) -> int:
    """Rows for the dense16 phase: as many whole 2^20 blocks as fit the
    library's own dense16 budget on the device (bf16 row + f32 validity)
    and half of host memory (3 x 8 bytes per non-zero: the CSR and, for
    a shard with an index out of range, the clamp's copy, with room to
    spare), capped at the 24M of
    ``tools/big_kmeans.py dense``."""
    per_row_device = DENSE_DIM * 2 + 4
    per_row_host = NNZ * 8 * 3
    rows = min(device_budget // per_row_device,
               host_bytes // 2 // per_row_host, DENSE_ROWS_MAX)
    rows -= rows % (1 << 20)
    check(rows >= DENSE_ROWS_MIN,
          f"only {rows} rows fit (device budget {device_budget}, host "
          f"{host_bytes}); the dense16 tier needs {DENSE_ROWS_MIN}")
    return rows


def phase_kmeans_dense16(seed: int, rows: int) -> dict:
    import jax
    import jax.numpy as jnp

    import rabit_tpu
    from rabit_tpu.learn import kmeans

    laps = Laps()
    data = make_rows(seed, rows, DENSE_DIM, K)
    laps.lap("generate")
    kw = dict(seed=seed, device_chain=CHAIN, compute_dtype="bfloat16")
    rabit_tpu.init(rabit_engine="empty")
    first = kmeans.run(data, K, CHAIN, **kw).centroids.copy()
    check(rabit_tpu.version_number() == 1, "first chain did not commit")
    laps.lap("run_one_chain")
    resumed = kmeans.run(data, K, 3 * CHAIN, **kw).centroids.copy()
    check(rabit_tpu.version_number() == 3,
          f"resume committed version {rabit_tpu.version_number()}, want 3 "
          "(one per chain, continuing from the committed one)")
    laps.lap("run_resume_two_chains")
    rabit_tpu.finalize()

    loop = kmeans._STEP_CACHE.get(
        ("loop", CHAIN, True, kmeans._DENSE16_ROW_TILE, "bfloat16"))
    check(loop is not None, "run() did not take the dense16 Pallas chain")
    n16 = -(-rows // kmeans._DENSE16_ROW_TILE) * kmeans._DENSE16_ROW_TILE
    check(kernel_in_program(
        loop, jax.ShapeDtypeStruct((K, DENSE_DIM), jnp.float32),
        jax.ShapeDtypeStruct((n16, DENSE_DIM), jnp.bfloat16),
        jax.ShapeDtypeStruct((n16,), jnp.float32)),
        "the chained dense16 program holds no Pallas kernel")

    ref = reference_kmeans(data, K, seed, (CHAIN, 3 * CHAIN))
    laps.lap("reference")
    errs = {"after_first_chain": rel_err(first, ref[CHAIN]),
            "after_resume": rel_err(resumed, ref[3 * CHAIN])}
    check(max(errs.values()) <= KMEANS_TOL,
          f"dense16 centroids differ from the float32 reference: {errs} "
          f"> {KMEANS_TOL}")
    return {"rows": rows, "dim": DENSE_DIM, "k": K, "nnz": NNZ,
            "tier": "dense16", "kernel": "kmeans_stats_fused (Pallas)",
            "resident_bytes": n16 * (DENSE_DIM * 2 + 4),
            "chains": 3, "committed_version": 3, "rel_err": errs,
            "tol": KMEANS_TOL, "seconds": laps.seconds}


def phase_kmeans_ell(seed: int, rows: int) -> dict:
    import rabit_tpu
    from rabit_tpu.learn import kmeans

    laps = Laps()
    data = make_rows(seed, rows, ELL_DIM, K)
    laps.lap("generate")
    rabit_tpu.init(rabit_engine="empty")
    got = kmeans.run(data, K, 2 * CHAIN, seed=seed, device_chain=CHAIN,
                     compute_dtype="float32").centroids.copy()
    check(rabit_tpu.version_number() == 2, "ELL run did not commit twice")
    rabit_tpu.finalize()
    laps.lap("run_two_chains")
    chained = [key for key in kmeans._STEP_CACHE
               if key[:4] == ("ellchain", CHAIN, K, ELL_DIM)]
    check(bool(chained), "prepare_shard did not stage ell_fused: run() "
          "never built the fused-ELL chain")

    ref = reference_kmeans(data, K, seed, (2 * CHAIN,))
    laps.lap("reference")
    err = rel_err(got, ref[2 * CHAIN])
    check(err <= KMEANS_TOL, f"ELL centroids differ from the float32 "
          f"reference: {err} > {KMEANS_TOL}")
    return {"rows": rows, "dim": ELL_DIM, "k": K, "nnz": NNZ,
            "tier": "ell_fused",
            "kernel": "kmeans_ell_stats_fused (Pallas)", "chains": 2,
            "rel_err": err, "tol": KMEANS_TOL, "seconds": laps.seconds}


def phase_gbdt(seed: int, rows: int) -> dict:
    import numpy as np

    import rabit_tpu
    from rabit_tpu.learn import boosting, histogram

    laps = Laps()
    rng = np.random.default_rng(seed + 2)
    values = rng.standard_normal((rows, GBDT_FEATS), dtype=np.float32)
    # a planted signal: the root must split on feature 3
    labels = (values[:, 3] + 0.5 * values[:, 10]
              + 0.1 * rng.standard_normal(rows, dtype=np.float32)
              > 0).astype(np.float32)
    bins = histogram.apply_cuts(
        values, histogram.quantile_cuts(values, GBDT_BINS))
    grad = rng.standard_normal(rows, dtype=np.float32)
    hess = rng.random(rows, dtype=np.float32)
    node_of_row = rng.integers(0, GBDT_NODES, rows).astype(np.int32)
    laps.lap("generate")

    got = np.asarray(histogram.build_level_local(
        bins, grad, hess, node_of_row, list(range(GBDT_NODES)), GBDT_BINS))
    laps.lap("level_histogram")
    # float64 reference: one bincount over (node, feature, bin) cells
    cell = ((node_of_row[:, None] * GBDT_FEATS + np.arange(GBDT_FEATS))
            * GBDT_BINS + bins).reshape(-1)
    ncell = GBDT_NODES * GBDT_FEATS * GBDT_BINS
    shape = (GBDT_NODES, GBDT_FEATS, GBDT_BINS)
    want = np.empty(shape + (2,))
    bound = np.empty(shape + (2,))
    for c, w in enumerate((grad, hess)):
        w64 = np.repeat(w.astype(np.float64), GBDT_FEATS)
        want[..., c] = np.bincount(cell, w64, ncell).reshape(shape)
        bound[..., c] = np.bincount(cell, np.abs(w64), ncell).reshape(shape)
    check(got.shape == want.shape, f"histogram shape {got.shape}")
    # the kernel rounds each weight to bf16 and accumulates in f32: a
    # cell's error is at most eps * sum|w| over the cell
    excess = float(np.max(np.abs(got - want) - BF16_EPS * bound))
    check(excess <= 1e-4, "level histogram exceeds the bf16 rounding "
          f"bound by {excess}")
    laps.lap("reference")

    rabit_tpu.init(rabit_engine="empty")
    model = boosting.train(values, labels, num_round=1, max_depth=4,
                           nbin=GBDT_BINS, seed=seed)
    check(rabit_tpu.version_number() == 1, "boosting did not commit")
    rabit_tpu.finalize()
    laps.lap("train_one_round")
    tree = model.trees[0]
    check(tree[0].feature == 3,
          f"root split on feature {tree[0].feature}, planted signal is 3")
    p = np.clip(model.predict(values), 1e-7, 1 - 1e-7)
    loss = float(-np.mean(labels * np.log(p)
                          + (1 - labels) * np.log(1 - p)))
    check(np.isfinite(loss) and loss < np.log(2.0),
          f"one boosting round left the log-loss at {loss}")
    return {"rows": rows, "features": GBDT_FEATS, "bins": GBDT_BINS,
            "nodes": GBDT_NODES, "kernel": "hist_fused_multi (Pallas)",
            "max_abs_err": float(np.max(np.abs(got - want))),
            "bound": "2^-8 * sum|w| per cell", "tree_nodes": len(tree),
            "logloss_after_round": round(loss, 4),
            "seconds": laps.seconds}


def phase_lbfgs_products(seed: int, rows: int) -> dict:
    """Both sparse products of the wide linear path (``X w`` with the
    loss, ``X^T g``) through ``linear.stage_rows`` and its two programs,
    against float64 numpy."""
    import numpy as np

    from rabit_tpu.learn import linear

    laps = Laps()
    rng = np.random.default_rng(seed + 3)
    idx = rng.integers(0, LBFGS_FEATS, (rows, LBFGS_NNZ)).astype(np.int32)
    idx[:, 0] %= 4096                     # hot cells, as hashed logs have
    val = rng.standard_normal((rows, LBFGS_NNZ)).astype(np.float32) / 6
    labels = (rng.random(rows) < 0.26).astype(np.float32)
    w = (rng.standard_normal(LBFGS_FEATS) * 0.3).astype(np.float32)
    laps.lap("generate")
    shard = linear.stage_rows(idx, val, labels, LBFGS_FEATS)
    margins, loss = shard.evaluate(w, np.float32(-0.5))
    grad, gsum = (np.asarray(a) for a in shard.gradient(margins))
    loss = float(np.asarray(loss).astype(np.float64).sum())
    laps.lap("stage_and_products")
    m = -0.5 + (val.astype(np.float64) * w[idx]).sum(axis=1)
    want_loss = float(np.where(labels > 0, np.logaddexp(0, -m),
                               np.logaddexp(0, m)).sum())
    g = 1.0 / (1.0 + np.exp(-m)) - labels
    want = np.bincount(idx.reshape(-1), (val * g[:, None]).reshape(-1),
                       LBFGS_FEATS)
    laps.lap("reference")
    err_m = float(np.max(np.abs(np.asarray(margins)[:rows] - m)))
    check(err_m < 1e-5, f"margins off by {err_m}")
    # the chip's float32 exp and log1p against float64's: 8e-6 of the
    # loss on a v5e (PR 31), and as much of g through the sigmoid
    check(abs(loss - want_loss) < 5e-5 * want_loss,
          f"loss {loss} against {want_loss}")
    err_g = rel_err(grad, want)
    check(err_g < 1e-4, f"X^T g off by {err_g} (relative)")
    check(abs(float(gsum.sum()) - g.sum()) < 1e-3 * np.abs(g).sum(),
          "bias gradient")
    return {"rows": rows, "features": LBFGS_FEATS, "nnz_per_row": LBFGS_NNZ,
            "slots": shard.nnz_padded, "nnz": shard.nnz,
            "kernel": "lbfgs_margin, lbfgs_grad (Pallas)",
            "margin_max_abs_err": err_m, "grad_rel_err": err_g,
            "seconds": laps.seconds}


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------
def run_worker_phase(device_impl: str) -> int:
    """One process per chip under the tracker, with the engine's
    device-plane allreduce lowered as ``device_impl`` (the existing
    ``RABIT_DEVICE_IMPL`` setting).  Runs the launcher as a child with a
    deadline, so a formation that hangs costs a bounded wait, and kills
    the whole process group when it ends."""
    cmd = [sys.executable, "-m", "rabit_tpu.tracker.launch_local", "-n", "4",
           sys.executable, os.path.join(REPO, "tests", "workers",
                                        "check_xla_chip.py")]
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        cmd, cwd=REPO, start_new_session=True,
        env={**os.environ, "RABIT_DEVICE_IMPL": device_impl})
    try:
        code = proc.wait(timeout=WORKER_PHASE_TIMEOUT_SEC)
    except subprocess.TimeoutExpired:
        code = 124
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    emit(phase="process_per_chip", workers=4, device_impl=device_impl,
         command=" ".join(cmd[1:]), exit_code=code,
         seconds=round(time.perf_counter() - t0, 2))
    return code


def phase_mesh_kmeans(seed: int, rows_per_chip: int) -> dict:
    """The data-parallel k-means step of ``__graft_entry__.py`` (fused
    kernel -> framework allreduce -> centroid update, one program over
    the mesh) against the same step on one chip holding all the rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from rabit_tpu.learn import kmeans as km
    from rabit_tpu.ops import ReduceOp
    from rabit_tpu.ops.kmeans_kernel import kmeans_stats_fused
    from rabit_tpu.parallel import collectives as C
    from rabit_tpu.parallel.mesh import (DATA_AXIS, make_mesh, replicated,
                                         sharded_batch)

    laps = Laps()
    devs = jax.devices()
    n = rows_per_chip * len(devs)

    def kmeans_step(cent, x, v):
        stats = kmeans_stats_fused(cent, x, v)
        stats = C.allreduce(stats, DATA_AXIS, ReduceOp.SUM)
        return km.centroid_update(cent, stats)

    def step_on(devices):
        mesh = make_mesh(devices=devices)
        fn = C.shard_collective(
            mesh, kmeans_step,
            in_specs=(P(), P(DATA_AXIS, None), P(DATA_AXIS)),
            out_specs=P(), check_vma=False)  # pallas in the body
        return mesh, fn

    mesh, step = step_on(devs)
    kx, kc = jax.random.split(jax.random.key(seed))
    x = jax.jit(lambda key: jax.random.normal(
        key, (n, DENSE_DIM), jnp.bfloat16),
        out_shardings=sharded_batch(mesh))(kx)
    valid = jax.device_put(jnp.ones((n,), jnp.float32),
                           sharded_batch(mesh, ndim=1))
    cent = jax.device_put(
        jax.random.normal(kc, (K, DENSE_DIM), jnp.float32),
        replicated(mesh))
    holders = {s.device for s in x.addressable_shards}
    check(holders == set(devs) and len(holders) == len(devs),
          f"rows live on {holders}, not on all of {devs}")
    check(all(s.data.shape == (rows_per_chip, DENSE_DIM)
              for s in x.addressable_shards), "uneven row shards")
    check(kernel_in_program(step, cent, x, valid),
          "the mesh step's program holds no Pallas kernel")
    check("all_reduce" in step.lower(cent, x, valid).as_text(),
          "the mesh step's program holds no all-reduce")
    got = np.asarray(step(cent, x, valid))
    laps.lap("mesh_step")

    mesh1, step1 = step_on(devs[:1])
    want = np.asarray(step1(
        jax.device_put(cent, replicated(mesh1)),
        jax.device_put(x, sharded_batch(mesh1)),
        jax.device_put(valid, sharded_batch(mesh1, ndim=1))))
    laps.lap("one_chip_step")
    err = rel_err(got, want)
    check(err <= MESH_TOL,
          f"{len(devs)}-chip step differs from one chip: {err} > {MESH_TOL}")
    return {"chips": len(devs), "rows_per_chip": rows_per_chip,
            "dim": DENSE_DIM, "k": K, "rel_err_vs_one_chip": err,
            "tol": MESH_TOL, "seconds": laps.seconds}


def phase_mesh_allreduce(seed: int, sizes: tuple[int, ...],
                         interpret: bool = False) -> dict:
    """psum against the Pallas remote-DMA ring on integer-valued float32
    payloads: every partial sum is exact, so the two must agree bit for
    bit, with each other and with the host's sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from rabit_tpu.ops import ReduceOp
    from rabit_tpu.ops.ring_allreduce import ring_allreduce_pallas
    from rabit_tpu.parallel import collectives as C
    from rabit_tpu.parallel.mesh import DATA_AXIS, make_mesh, sharded_batch

    laps = Laps()
    devs = jax.devices()
    mesh = make_mesh(devices=devs)
    rng = np.random.default_rng(seed + 3)

    def launcher(body):
        return C.shard_collective(
            mesh, lambda s: body(s[0])[None], in_specs=(P(DATA_AXIS, None),),
            out_specs=P(DATA_AXIS, None), check_vma=False)

    psum = launcher(lambda v: C.allreduce(v, DATA_AXIS, ReduceOp.SUM))
    ring = launcher(lambda v: ring_allreduce_pallas(
        v, DATA_AXIS, ReduceOp.SUM, interpret=interpret))
    for nbytes in sizes:
        host = rng.integers(-8, 9, (len(devs), nbytes // 4)).astype(
            np.float32)
        payload = jax.device_put(host, sharded_batch(mesh))
        check({s.device for s in payload.addressable_shards} == set(devs),
              "allreduce payload is not spread over every chip")
        want = host.sum(axis=0)
        for name, fn in (("psum", psum), ("pallas_ring", ring)):
            out = np.asarray(fn(payload))
            for r in range(len(devs)):
                check(np.array_equal(out[r], want),
                      f"{name} at {nbytes} bytes: chip {r} disagrees "
                      "with the exact sum")
        laps.lap(f"{nbytes}_bytes")
    return {"chips": len(devs), "bytes_per_chip": list(sizes),
            "impls": ["psum", "pallas_ring"], "agreement": "bit-for-bit",
            "seconds": laps.seconds}


# ----------------------------------------------------------------------
def open_device(want_chips: int):
    """Initialise JAX — the first touch of the chip in this process —
    refuse anything but TPUs, turn the one compile cache on."""
    import jax

    from rabit_tpu.ops import on_tpu
    from rabit_tpu.utils import compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu" or not on_tpu():
        raise SystemExit(f"chip_smoke: no accelerator — jax.devices() = "
                         f"{devs}; this script does not run off the chip")
    if len(devs) != want_chips:
        raise SystemExit(f"chip_smoke: needs {want_chips} chip(s), JAX "
                         f"sees {len(devs)}: {devs}")
    stats = devs[0].memory_stats() or {}
    emit(phase="start", platform=devs[0].platform,
         kind=devs[0].device_kind, count=len(devs),
         bytes_limit=stats.get("bytes_limit"),
         compile_cache_dir=compile_cache.enable(),
         compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    return devs, compile_cache.count_compiles()


def run_phase(name: str, clock, fn, *args) -> None:
    t0 = time.perf_counter()
    out = fn(*args)
    emit(phase=name, ok=True, total_seconds=round(
        time.perf_counter() - t0, 2), compile=clock.take(), **out)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated dataset")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run the cross-chip paths and nothing else")
    args = ap.parse_args(argv)

    worker_code = 0
    if args.chips == 4:
        # children first: this process must not hold the chips they need
        worker_code = (run_worker_phase("psum")
                       or run_worker_phase("pallas_ring"))
    devs, clock = open_device(args.chips)
    if args.chips == 4:
        run_phase("mesh_kmeans_step", clock, phase_mesh_kmeans,
                  args.seed, MESH_ROWS_PER_CHIP)
        run_phase("mesh_allreduce", clock, phase_mesh_allreduce,
                  args.seed, ALLREDUCE_BYTES)
        if worker_code != 0:
            raise SystemExit(
                "chip_smoke: the process-per-chip phase failed (exit "
                f"{worker_code}); its output is above")
    else:
        from rabit_tpu.learn.kmeans import _dense16_budget

        host_bytes = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        run_phase("kmeans_dense16", clock, phase_kmeans_dense16, args.seed,
                  dense_rows(_dense16_budget(), host_bytes))
        run_phase("kmeans_ell", clock, phase_kmeans_ell, args.seed, ELL_ROWS)
        run_phase("gbdt_histogram", clock, phase_gbdt, args.seed, GBDT_ROWS)
        run_phase("lbfgs_products", clock, phase_lbfgs_products, args.seed,
                  LBFGS_ROWS)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

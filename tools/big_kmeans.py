"""Biggest-that-fits single-chip kmeans: the measured anchor behind
doc/scaling.md's pod arithmetic (BASELINE.md "kmeans on 1B points").

Runs `learn/kmeans.py run()` END-TO-END — staging, per-iteration stats
pass, allreduce (world 1), per-iteration in-memory checkpoint — on the
largest synthetic dataset one chip's HBM holds, and reports measured
points/s and effective bytes/s against the HBM roofline.

Two shapes, mirroring the reference's workloads:

  sparse   50M rows x 32 nnz ELL (the libsvm shape the reference's
           kmeans consumes; reference: rabit-learn/utils/data.h) —
           ~13 GB on device (int32 idx + f32 val) of a v5e's 16 GB
  dense    ~24M rows x 256 features, bf16, device-chained iterations
           (the bench.py path) — 12.3 GB resident, ~77% of HBM

Above "biggest that fits" (PR 46): a sparse shard over the budget the
chip reports is not refused any more.  `run()` keeps resident as many
chunks of 2^20 rows as fit and streams the rest from host memory under
the kernel every iteration (`prepare_shard`'s tier `ell_stream`), so
`sparse --points 75497472` (19.3 GB of slots) runs here too, an
iteration a commit; the benchmark's cell of that shape is
`kmeans-sparse-stream-x1` (PERF.md section 4).  `--chain` needs the
whole shard on the device and is refused for such a shard.

Timing: sparse mode takes the median gap between the per-iteration
checkpoint calls inside ONE run (in-run timestamps are immune to the
multi-GB staging variance); dense mode difference-times two chained
fori_loop programs, each ended by ``block_until_ready``.

Usage: python tools/big_kmeans.py [sparse|dense] [--points N] [--iters N]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")

GEN_BLOCK = 1 << 20


def gen_sparse(n: int, nnz: int, dim: int, k_true: int, seed: int = 0):
    """Clustered ELL data, generated block-wise to bound peak RAM.

    Cluster signal: row r of cluster c gets its first few slots on
    c's signature features with positive values, the rest uniform
    noise — enough structure that centroids separate, cheap to make.
    """
    from rabit_tpu.learn.data import SparseMat

    if nnz < 9 or dim <= 9:
        raise ValueError(
            f"gen_sparse needs nnz >= 9 and dim > 9 (got nnz={nnz}, "
            f"dim={dim}): 8 slots carry the shared cluster signal and "
            "the rest must draw from features above it")
    rng = np.random.default_rng(seed)
    findex = np.empty((n, nnz), np.int32)
    fvalue = np.empty((n, nnz), np.float32)
    # 8 features common to every row with continuous positive weights
    # (cluster centers + per-row noise): similarities vary continuously,
    # so no argmax ties -> no empty Voronoi cells at init
    centers = np.abs(rng.standard_normal((k_true, 8))) + 0.5
    for lo in range(0, n, GEN_BLOCK):
        hi = min(n, lo + GEN_BLOCK)
        m = hi - lo
        cluster = (np.arange(lo, hi) % k_true)
        findex[lo:hi] = rng.integers(8, dim, (m, nnz), dtype=np.int32)
        findex[lo:hi, :8] = np.arange(8, dtype=np.int32)
        fvalue[lo:hi] = (rng.standard_normal((m, nnz))
                         .astype(np.float32) * 0.2)
        fvalue[lo:hi, :8] = centers[cluster] + rng.standard_normal(
            (m, 8)).astype(np.float32) * 0.3
    return SparseMat(
        indptr=np.arange(n + 1, dtype=np.int64) * nnz,
        findex=findex.reshape(-1), fvalue=fvalue.reshape(-1),
        labels=np.zeros(n, np.float32), feat_dim=dim)


def gen_dense_bf16(n: int, dim: int, k_true: int, seed: int = 0):
    """Clustered dense rows, bf16 on host (half the HBM footprint —
    the TPU idiom the fused stats kernel is built for)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    x = np.empty((n, dim), dtype=jnp.bfloat16)
    centers = rng.standard_normal((k_true, dim), dtype=np.float32) * 3
    for lo in range(0, n, GEN_BLOCK):
        hi = min(n, lo + GEN_BLOCK)
        cluster = (np.arange(lo, hi) % k_true)
        blk = centers[cluster] + rng.standard_normal(
            (hi - lo, dim), dtype=np.float32)
        x[lo:hi] = blk.astype(jnp.bfloat16)
    return x


def timed_run(data, k: int, iters: int, **kw):
    """One end-to-end run(); per-iteration time = gaps between the
    per-iteration checkpoint calls (median, first gap dropped — it
    carries the XLA compile).  In-run gaps are immune to the multi-GB
    staging variance that breaks whole-run difference timing."""
    import rabit_tpu
    from rabit_tpu.learn import kmeans

    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    stamps: list[float] = [time.perf_counter()]
    orig = rabit_tpu.checkpoint

    def stamping_checkpoint(model):
        stamps.append(time.perf_counter())
        orig(model)

    rabit_tpu.checkpoint = stamping_checkpoint
    try:
        model = kmeans.run(data, num_cluster=k, max_iter=iters, **kw)
    finally:
        rabit_tpu.checkpoint = orig
    gaps = np.diff(np.asarray(stamps))[1:]  # drop the compile gap
    # iterations per checkpoint gap, derived from what run() actually
    # did (device_chain only engages on the dense/ell_fused single-
    # worker path — never guess from the requested chain)
    iters_per_gap = iters / max(len(gaps) + 1, 1)
    return float(np.median(gaps) / iters_per_gap), model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", nargs="?", default="sparse",
                    choices=["sparse", "dense", "hashed"])
    ap.add_argument("--points", type=int, default=None)
    ap.add_argument("--nnz", type=int, default=32)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--chain", type=int, default=1,
                    help="sparse mode: device-chain this many iterations per checkpoint (amortizes the per-iteration host fetch; checkpoint granularity coarsens to match)")
    args = ap.parse_args()

    import rabit_tpu
    from rabit_tpu.utils import compile_cache

    compile_cache.enable()
    rabit_tpu.init(rabit_engine="empty")
    if args.mode == "hashed":
        # the SAME 50M-row sparse dataset as sparse mode, clustered via
        # run(hash_dim=128, compute_dtype="bfloat16"): signed hashing +
        # half-width dense staging put the whole run on the
        # HBM-roofline dense kernel (doc/benchmarks.md "Feature-hashed
        # sparse k-means"); approximate where sparse mode is exact
        n = args.points or 50_000_000
        dim = args.dim or 512
        hash_dim = 128
        print(f"generating {n} x {args.nnz}-nnz rows (dim {dim}), "
              f"hash_dim {hash_dim}...", flush=True)
        t0 = time.perf_counter()
        data = gen_sparse(n, args.nnz, dim, args.k)
        print(f"  generated in {time.perf_counter() - t0:.1f}s", flush=True)
        per_iter, model = timed_run(data, args.k, args.iters,
                                    device_chain=args.chain,
                                    hash_dim=hash_dim,
                                    compute_dtype="bfloat16")
        bytes_per_iter = n * hash_dim * 2   # one bf16 read of the rows
    elif args.mode == "sparse":
        n = args.points or 50_000_000
        # moderate width: the ELL stats pass densifies per row block, so
        # width trades against block size; 512 ~ a dense-ish ads/ctr shape
        dim = args.dim or 512
        print(f"generating {n} x {args.nnz}-nnz rows (dim {dim})...",
              flush=True)
        t0 = time.perf_counter()
        data = gen_sparse(n, args.nnz, dim, args.k)
        print(f"  generated in {time.perf_counter() - t0:.1f}s", flush=True)
        per_iter, model = timed_run(data, args.k, args.iters,
                                    device_chain=args.chain)
        bytes_per_iter = n * args.nnz * 8  # idx int32 + val f32, read once
    else:
        # biggest dense shape: device-chained iterations (the bench.py
        # path) on a bf16 shard filling most of a v5e's 16 GB
        import jax
        import jax.numpy as jnp
        from rabit_tpu.learn import kmeans

        # exact multiple of the fused kernel's 16384 row block: the
        # kernel's row padding is then a no-op instead of a second
        # 12 GB copy that overflows HBM
        n = args.points or 16384 * 1464   # 23,986,176
        dim = args.dim or 256
        print(f"generating {n} dense bf16 rows (dim {dim})...", flush=True)
        t0 = time.perf_counter()
        x_host = gen_dense_bf16(n, dim, args.k)
        print(f"  generated in {time.perf_counter() - t0:.1f}s", flush=True)
        x = jax.device_put(jnp.asarray(x_host))
        del x_host
        valid = jnp.ones((n,), jnp.float32)
        rng = np.random.default_rng(1)
        cent = jnp.asarray(rng.standard_normal((args.k, dim)),
                           dtype=jnp.float32)

        def chain(iters):
            t0 = time.perf_counter()
            out = kmeans.device_iterations(
                cent, x, valid, iters,
                compute_dtype="bfloat16").block_until_ready()
            return time.perf_counter() - t0, out

        iters = max(args.iters, 50)  # enough work to beat dispatch jitter
        chain(2)            # compile short chain
        chain(2 + iters)    # compile long chain
        t_s, _ = chain(2)
        t_l, out = chain(2 + iters)
        per_iter = (t_l - t_s) / iters

        class _M:  # minimal shim for the shared report below
            centroids = np.asarray(out)
        model = _M()
        bytes_per_iter = n * dim * 2
    assert np.isfinite(model.centroids).all()
    note = ("device-chained, no checkpoint" if args.mode == "dense"
            else "per-iteration checkpoint included" if args.chain <= 1
            else f"checkpoint every {args.chain} device-chained iters")
    print(f"mode={args.mode} n={n} k={args.k}: {per_iter * 1e3:.1f} ms/iter, "
          f"{n / per_iter / 1e6:.0f} Mpoints/s, "
          f"{bytes_per_iter / per_iter / 1e9:.0f} GB/s effective "
          f"({note})", flush=True)


if __name__ == "__main__":
    main()

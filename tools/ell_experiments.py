"""Microbench: k-means stats-pass formulations for ELL (padded sparse) data.

The round-3 probe established that the *densify-by-one-hot* algorithm is
VPU-bound: vectorised scatter costs ~2·nnz·d lane-ops per row however it
is written (doc/benchmarks.md "ELL densify bound").  This harness
measures algorithm changes, per VERDICT r3 item 2:

  scan        the shipped `_stats_fn` ELL scan pass (baseline)
  batched:H   two-level densify — split f = lo_idx·H + hi_idx, build the
              (nnz, hi) and weighted (nnz, lo) one-hots (VPU cost
              nnz·(hi+lo) per row instead of 2·nnz·d), then contract
              them on the MXU as a per-row batched matmul
  band:G:H    same two-level split, but G rows share one matmul: the
              weighted lo one-hot is laid out block-diagonally as
              (G·nnz, G·lo) so Lᵀ@H is a single well-tiled MXU matmul
              per group whose (G·lo, hi) output reshapes directly to
              (G, d) — G-fold FLOP inflation traded for MXU tiling
  gather:G:H  gather-based similarity (sim[r,:] = Σ_s val·cnorm[idx,:],
              nnz·k MACs per row, no densify for the assignment pass)
              + band densify for the stats accumulation only
  pallas:G:H  fully fused Pallas kernel: band densify + similarity +
              stats in ONE kernel — the dense block lives only in VMEM,
              so the per-block HBM round trip of the dense intermediate
              (the dominant cost of band:* at these shapes) disappears

All modes run the FULL k-means iteration (assignment + stats + centroid
update) as a data-dependent device chain (centroids feed back), and are
difference-timed so the fixed per-execution cost cancels — the same
discipline as bench.py.  Each variant is checked against the f32 scan
oracle before timing.

Usage: python tools/ell_experiments.py [mode ...]
"""
from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, ".")

N, D, K, NNZ = 1 << 19, 512, 64, 32     # the 50M-run's row shape
BLOCK = 4096
CHAINS = {"scan": (3, 30)}
DEFAULT_CHAIN = (20, 200)
GUARD_TOL = 2e-2


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from rabit_tpu.learn import kmeans

    specs = sys.argv[1:] or [
        "scan", "batched:128", "batched:32",
        "band:8:64", "band:8:128", "band:4:128", "band:16:32",
        "gather:8:64",
    ]
    rng = np.random.default_rng(0)
    idx = rng.integers(0, D, (N, NNZ)).astype(np.int32)
    val = rng.standard_normal((N, NNZ)).astype(np.float32)
    cent0 = rng.standard_normal((K, D)).astype(np.float32)
    valid = np.ones(N, np.float32)
    c0 = jax.device_put(jnp.asarray(cent0))
    print("backend:", jax.default_backend(), flush=True)

    nb = N // BLOCK
    di = jax.device_put(jnp.asarray(idx.reshape(nb, BLOCK, NNZ)))
    dv = jax.device_put(jnp.asarray(val.reshape(nb, BLOCK, NNZ)))
    dvl = jax.device_put(jnp.asarray(valid.reshape(nb, BLOCK)))

    def stats_scan(cent):
        fn = kmeans._stats_fn(K, D, BLOCK, NNZ)
        return fn(cent, di, dv, dvl)

    def two_level_onehots(bi, bv, hi, lo, G=None):
        """Per-block (B, nnz) idx/val → hi one-hot and weighted lo
        one-hot.  ``f = lo_idx*hi + hi_idx``; pad entries carry val=0 so
        their one-hot rows contribute nothing wherever they land."""
        hi_idx = bi % hi
        lo_idx = bi // hi
        hio = (hi_idx[..., None] ==
               lax.broadcasted_iota(jnp.int32, (1, 1, hi), 2))
        if G is None:
            loo = (lo_idx[..., None] ==
                   lax.broadcasted_iota(jnp.int32, (1, 1, lo), 2))
            return (hio.astype(jnp.bfloat16),
                    (loo * bv[..., None]).astype(jnp.bfloat16))
        # band layout: row g of each G-group owns columns [g*lo, (g+1)*lo)
        B = bi.shape[0]
        g = (jnp.arange(B, dtype=jnp.int32) % G)[:, None]
        col = g * lo + lo_idx                                # (B, nnz)
        loo = (col[..., None] ==
               lax.broadcasted_iota(jnp.int32, (1, 1, G * lo), 2))
        return (hio.astype(jnp.bfloat16),
                (loo * bv[..., None]).astype(jnp.bfloat16))

    def densify_batched(bi, bv, hi):
        lo = D // hi
        hio, loo = two_level_onehots(bi, bv, hi, lo)
        # per-row (lo, hi) = looᵀ @ hio, batched over rows
        dense = lax.dot_general(
            loo, hio, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)              # (B, lo, hi)
        return dense.reshape(bi.shape[0], D)

    def densify_band(bi, bv, G, hi):
        lo = D // hi
        B = bi.shape[0]
        hio, loo = two_level_onehots(bi, bv, hi, lo, G=G)
        hio = hio.reshape(B // G, G * NNZ, hi)
        loo = loo.reshape(B // G, G * NNZ, G * lo)
        dense = lax.dot_general(
            loo, hio, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # (B/G, G*lo, hi)
        return dense.reshape(B, D)

    def iter_with_densify(densify):
        def one(cent):
            cn = kmeans._normalize_rows(cent).astype(jnp.bfloat16)

            def body(acc, blk):
                bi, bv, bvl = blk
                dense = densify(bi, bv)
                onehot = kmeans._dense_assign(cn, dense.astype(jnp.bfloat16),
                                              bvl)
                sums = lax.dot_general(
                    onehot.astype(jnp.bfloat16), dense.astype(jnp.bfloat16),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                counts = jnp.sum(onehot, axis=0)
                return acc + jnp.concatenate(
                    [sums, counts[:, None]], axis=1), None

            acc0 = jnp.zeros((K, D + 1), jnp.float32)
            stats, _ = lax.scan(body, acc0, (di, dv, dvl))
            return kmeans.centroid_update(cent, stats)
        return one

    def iter_gather(G, hi):
        def one(cent):
            cn = kmeans._normalize_rows(cent).astype(jnp.bfloat16)
            cn_ext = jnp.concatenate(
                [cn, jnp.zeros((1, D), jnp.bfloat16)], axis=0)  # pad row

            def body(acc, blk):
                bi, bv, bvl = blk
                safe = jnp.minimum(bi, D)      # pad index D → zero row
                gath = jnp.take(cn_ext.T, safe, axis=1)   # (k, B, nnz)
                sim = jnp.einsum("kbs,bs->bk", gath.astype(jnp.float32),
                                 bv)
                assign = jnp.argmax(sim, axis=1)
                onehot = (jax.nn.one_hot(assign, K, dtype=jnp.float32)
                          * bvl[:, None])
                dense = densify_band(bi, bv, G, hi)
                sums = lax.dot_general(
                    onehot.astype(jnp.bfloat16), dense.astype(jnp.bfloat16),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                counts = jnp.sum(onehot, axis=0)
                return acc + jnp.concatenate(
                    [sums, counts[:, None]], axis=1), None

            acc0 = jnp.zeros((K, D + 1), jnp.float32)
            stats, _ = lax.scan(body, acc0, (di, dv, dvl))
            return kmeans.centroid_update(cent, stats)
        return one

    def iter_pallas(G, hi):
        from rabit_tpu.ops.kmeans_kernel import kmeans_ell_stats_fused

        idx_flat = di.reshape(N, NNZ)
        val_flat = dv.reshape(N, NNZ)
        valid_flat = dvl.reshape(N)

        def one(cent):
            stats = kmeans_ell_stats_fused(
                cent, idx_flat, val_flat, valid_flat, D,
                group=G, hi=hi)
            return kmeans.centroid_update(cent, stats)
        return one

    def one_iter_scan(cent):
        return kmeans.centroid_update(cent, stats_scan(cent))

    def chained(one_iter, iters):
        @jax.jit
        def run(cent):
            return lax.fori_loop(0, iters, lambda _, c: one_iter(c), cent)
        return run

    oracle = None

    for spec in specs:
        mode, _, arg = spec.partition(":")
        if mode == "scan":
            one = one_iter_scan
        elif mode == "batched":
            hi = int(arg)
            one = iter_with_densify(
                lambda bi, bv, hi=hi: densify_batched(bi, bv, hi))
        elif mode == "band":
            gs, hs = arg.split(":")
            G, hi = int(gs), int(hs)
            one = iter_with_densify(
                lambda bi, bv, G=G, hi=hi: densify_band(bi, bv, G, hi))
        elif mode == "gather":
            gs, hs = arg.split(":")
            one = iter_gather(int(gs), int(hs))
        elif mode == "pallas":
            gs, hs = arg.split(":")
            one = iter_pallas(int(gs), int(hs))
        else:
            print(f"{spec}: unknown mode", flush=True)
            continue

        try:
            got = np.asarray(chained(one, 5)(c0), np.float32)
            if oracle is None:
                oracle = got  # scan runs first by default
                rel = 0.0
            else:
                rel = float(np.linalg.norm(got - oracle)
                            / np.linalg.norm(oracle))
            tag = "OK" if rel < GUARD_TOL else "NUMERICS-FAIL"
            short, long_ = CHAINS.get(mode, DEFAULT_CHAIN)
            fs, fl = chained(one, short), chained(one, long_)
            np.asarray(fs(c0)); np.asarray(fl(c0))
            ts = []
            for _ in range(3):
                t0 = time.perf_counter(); np.asarray(fs(c0))
                t_s = time.perf_counter() - t0
                t0 = time.perf_counter(); np.asarray(fl(c0))
                t_l = time.perf_counter() - t0
                ts.append((t_l - t_s) / (long_ - short))
            ts.sort()
            dt = ts[len(ts) // 2]
            print(f"{spec:14} {dt * 1e3:8.3f} ms/iter  "
                  f"{N / dt / 1e6:7.1f} Mpoints/s  rel_err={rel:.2e} {tag}",
                  flush=True)
        except Exception as exc:  # noqa: BLE001 — survey harness
            print(f"{spec:14} FAILED: {type(exc).__name__}: {exc}",
                  flush=True)


if __name__ == "__main__":
    main()

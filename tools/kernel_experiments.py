"""Microbench: isolate per-step cost of the fused kmeans stats kernel.

Each variant runs ITERS chained stats passes (fori_loop; centroids fed
back so nothing is DCE'd), one host sync.  Measurement only — variant
"maxcmp" allows argmax ties (not for production).
"""
from __future__ import annotations

import functools
import sys
import time

import numpy as np

sys.path.insert(0, ".")

N, D, K, ITERS = 1 << 19, 256, 64, 50


def build_kernel(mode: str):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    def make_kernel_t(classify):
        """Transposed-one-hot kernel (both matmuls natural layout, no
        relayout) with a pluggable classify stage, so the decomposition
        probes share EVERY line with the production formulation except
        the stage under test.  ``classify(sim, valid_ref, block, k)``
        returns ``(onehot_t, keep)``; ``keep`` (folded into counts) is
        the probe's device-side anchor that stops the sim matmul from
        being DCE'd when onehot_t does not depend on it."""

        def kernel_t(x_ref, cn_ref, valid_ref, sums_ref, counts_ref):
            i = pl.program_id(0)
            x = x_ref[:]
            block, _ = x.shape
            k = cn_ref.shape[0]
            sim = jnp.dot(x, cn_ref[:].T,
                          preferred_element_type=jnp.float32)
            onehot_t, keep = classify(sim, valid_ref, block, k)
            part_sums = jnp.dot(onehot_t.astype(x.dtype), x,
                                preferred_element_type=jnp.float32)
            part_counts = jnp.sum(onehot_t, axis=1)[:, None] + keep

            @pl.when(i == 0)
            def _():
                sums_ref[:] = part_sums
                counts_ref[:] = part_counts

            @pl.when(i != 0)
            def _():
                sums_ref[:] = sums_ref[:] + part_sums
                counts_ref[:] = counts_ref[:] + part_counts

        return kernel_t

    def classify_argmax(sim, valid_ref, block, k):
        # the production stage (ops/kmeans_kernel.py _stats_kernel)
        assign = jnp.argmax(sim, axis=1)                     # (block,)
        rows = lax.broadcasted_iota(jnp.int32, (k, block), 0)
        onehot_t = (rows == assign[None, :]).astype(jnp.float32)
        return onehot_t * valid_ref[:], jnp.float32(0)       # (1, block)

    def classify_none(sim, valid_ref, block, k):
        # simonlyT: both matmuls, NO classify — isolates matmuls + DMA;
        # the thin-slice reduce keeps the sim matmul alive
        onehot_t = jnp.broadcast_to(valid_ref[:], (k, block)
                                    ).astype(jnp.float32)
        return onehot_t, jnp.sum(sim[:, :1])

    def classify_cheap(sim, valid_ref, block, k):
        # cheapassignT: one-hot build kept, argmax replaced by a free
        # iota%k assignment — isolates the argmax reduce (same
        # thin-slice keep-alive as classify_none: an integer *0 would
        # be constant-folded and let the sim matmul be DCE'd)
        assign = lax.broadcasted_iota(jnp.int32, (block,), 0) % k
        rows = lax.broadcasted_iota(jnp.int32, (k, block), 0)
        onehot_t = (rows == assign[None, :]).astype(jnp.float32)
        return onehot_t * valid_ref[:], jnp.sum(sim[:, :1])

    if mode in ("argmaxT", "simonlyT", "cheapassignT"):
        return make_kernel_t({"argmaxT": classify_argmax,
                              "simonlyT": classify_none,
                              "cheapassignT": classify_cheap}[mode])

    def kernel(x_ref, cn_ref, valid_ref, sums_ref, counts_ref):
        i = pl.program_id(0)
        x = x_ref[:]
        block, _ = x.shape
        k = cn_ref.shape[0]
        sim = jnp.dot(x, cn_ref[:].T, preferred_element_type=jnp.float32)
        if mode == "maxcmp":
            rowmax = jnp.max(sim, axis=1, keepdims=True)
            onehot = (sim >= rowmax).astype(jnp.float32)
        elif mode == "simonly":
            onehot = jnp.clip(sim, 0.0, 1.0)
        else:
            assign = jnp.argmax(sim, axis=1)
            cols = lax.broadcasted_iota(jnp.int32, (block, k), 1)
            onehot = (cols == assign[:, None]).astype(jnp.float32)
        if mode != "novalid":
            onehot = onehot * valid_ref[:]
        part_sums = lax.dot_general(
            onehot.astype(x.dtype), x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        part_counts = jnp.sum(onehot, axis=0)[None, :]

        @pl.when(i == 0)
        def _():
            sums_ref[:] = part_sums
            counts_ref[:] = part_counts

        @pl.when(i != 0)
        def _():
            sums_ref[:] = sums_ref[:] + part_sums
            counts_ref[:] = counts_ref[:] + part_counts

    return kernel


def build_loop(mode: str, block: int, dtype: str, vmem_mb: int,
               iters: int = ITERS):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cdt = jnp.dtype(dtype)
    kernel = build_kernel(mode)

    def stats(cnorm, x, valid):
        nb = N // block
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_mb << 20)
        if mode in ("argmaxT", "simonlyT", "cheapassignT"):
            sums, counts = pl.pallas_call(
                kernel,
                grid=(nb,),
                in_specs=[
                    pl.BlockSpec((block, D), lambda i: (i, 0)),
                    pl.BlockSpec((K, D), lambda i: (0, 0)),
                    pl.BlockSpec((1, block), lambda i: (0, i)),
                ],
                out_specs=(
                    pl.BlockSpec((K, D), lambda i: (0, 0)),
                    pl.BlockSpec((K, 1), lambda i: (0, 0)),
                ),
                out_shape=(
                    jax.ShapeDtypeStruct((K, D), jnp.float32),
                    jax.ShapeDtypeStruct((K, 1), jnp.float32),
                ),
                compiler_params=params,
            )(x, cnorm, valid.reshape(1, N))
            return sums, counts.T
        sums, counts = pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((block, D), lambda i: (i, 0)),
                pl.BlockSpec((K, D), lambda i: (0, 0)),
                pl.BlockSpec((block, 1), lambda i: (i, 0)),
            ],
            out_specs=(
                pl.BlockSpec((K, D), lambda i: (0, 0)),
                pl.BlockSpec((1, K), lambda i: (0, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((K, D), jnp.float32),
                jax.ShapeDtypeStruct((1, K), jnp.float32),
            ),
            compiler_params=params,
        )(x, cnorm, valid.reshape(N, 1))
        return sums, counts

    @jax.jit
    def run(cent, x, valid):
        x = x.astype(cdt)

        def one(_, c):
            cn = c / (jnp.linalg.norm(c, axis=1, keepdims=True) + 1e-12)
            sums, counts = stats(cn.astype(cdt), x, valid)
            new = jnp.where(counts.T > 0, sums / jnp.maximum(counts.T, 1.0),
                            c)
            return new

        return jax.lax.fori_loop(0, iters, one, cent)

    return run


def main():
    import jax
    import jax.numpy as jnp

    specs = sys.argv[1:] or [
        "argmax:2048:bfloat16:16", "maxcmp:2048:bfloat16:16",
        "simonly:2048:bfloat16:16", "argmax:4096:bfloat16:64",
        "argmax:8192:bfloat16:64", "maxcmp:8192:bfloat16:64",
        "argmax:8192:float32:100", "simonly:8192:bfloat16:64",
    ]
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((N, D)).astype(np.float32)))
    c = jax.device_put(jnp.asarray(
        rng.standard_normal((K, D)).astype(np.float32)))
    v = jax.device_put(jnp.ones(N, dtype=jnp.float32))
    print("backend:", jax.default_backend())
    # difference timing: every fetched execution pays a fixed dispatch
    # and fetch cost, and loop-invariant bodies get hoisted — so
    # time (long - short) chained runs of the REAL recurrent loop and
    # divide by the iteration difference to cancel the fixed cost.
    # bench.py's measurement discipline: candidates interleaved across
    # trials (a load burst hits every spec, not one), MEDIAN of the
    # per-trial difference timings, non-positive/absurd diffs dropped —
    # a min over differences of noisy pairs is biased low and once
    # measured an impossible 4.8 TB/s here.
    short, long_, trials = 50, 500, 5
    import statistics

    fns = {}
    for spec in specs:
        mode, block, dtype, vmem = spec.split(":")
        try:
            fs = build_loop(mode, int(block), dtype, int(vmem), short)
            fl = build_loop(mode, int(block), dtype, int(vmem), long_)
            np.asarray(fs(c, x, v)); np.asarray(fl(c, x, v))
            fns[spec] = (fs, fl)
        except Exception as e:
            msg = str(e).split("\n")[0][:120]
            print(f"{spec:28s} FAILED: {type(e).__name__}: {msg}")
    samples: dict = {s: [] for s in fns}
    for _ in range(trials):
        for spec, (fs, fl) in fns.items():
            try:
                t0 = time.perf_counter(); np.asarray(fs(c, x, v))
                ts = time.perf_counter() - t0
                t0 = time.perf_counter(); np.asarray(fl(c, x, v))
                tl = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 — keep other specs' data
                msg = str(e).split("\n")[0][:120]
                print(f"{spec:28s} trial FAILED: {type(e).__name__}: {msg}")
                continue
            dt = (tl - ts) / (long_ - short)
            if dt > 0:
                samples[spec].append(dt)
    for spec, xs in samples.items():
        if not xs:
            print(f"{spec:28s} no valid trials")
            continue
        med = statistics.median(xs)
        spread = 100.0 * (max(xs) - min(xs)) / med
        print(f"{spec:28s} {med*1e3:8.3f} ms/iter  "
              f"{N/med/1e6:8.1f} Mpoints/s  "
              f"(n={len(xs)} spread {spread:.0f}%)")


if __name__ == "__main__":
    main()

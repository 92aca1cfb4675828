"""Microbench: gradient-histogram formulations on the real chip.

Measurement discipline (see doc/benchmarks.md): independent dispatches
need not serialize, so the timing is a DATA-DEPENDENT chain inside one
jitted program (each iteration's weights perturbed by the previous
histogram so nothing can be hoisted or overlapped), difference-timed
between a long and a short chain with one fetch each so the fixed
per-execution cost cancels.  This is the same recipe
`kernel_experiments.py` uses for the kmeans kernel.

Modes:

  xla1        XLA per-feature one-hot contraction, one histogram/iter
  pallas1     fused two-level kernel, single grad/hess pair
  pallasM:m   fused kernel, m-node level build: (2m, n) weight channels
              sharing ONE bins pass
  xlaM:m      m XLA passes per iteration (the per-node pattern pallasM
              replaces)
  plan:hi:lo:fpg  pallas1 with an overridden two-level plan — measures
              the inflation/occupancy frontier (default 16:16:8 is
              8x-inflated at full M=128 tiles; 32:8:4 and 64:4:2 shrink
              the inflation at shrinking M = 2*fpg^2 tiles)

Usage: python tools/hist_experiments.py [mode[:m] ...]
"""
from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, ".")

N, F, NBIN = 262144, 64, 256
# slow modes get a short chain (enough signal at ~30 ms/iter); fast
# ones need hundreds of iterations to rise above dispatch jitter
CHAINS = {"xla1": (5, 50), "xlaM": (2, 12)}
DEFAULT_CHAIN = (50, 500)


def main():
    import jax
    import jax.numpy as jnp

    from rabit_tpu.learn import histogram
    from rabit_tpu.ops.histogram_kernel import hist_fused_multi

    specs = sys.argv[1:] or [
        "xla1", "pallas1", "pallasM:2", "pallasM:4", "pallasM:8",
        "pallasM:16", "xlaM:8",
    ]
    rng = np.random.default_rng(0)
    bins = rng.integers(0, NBIN, (N, F)).astype(np.int32)
    db = jax.device_put(jnp.asarray(bins))
    dbt = jax.device_put(jnp.asarray(bins.T))
    dg = jax.device_put(jnp.asarray(
        rng.standard_normal(N).astype(np.float32)))
    dh = jax.device_put(jnp.asarray(rng.random(N).astype(np.float32)))
    node = jnp.asarray(rng.integers(0, 16, N).astype(np.int32))
    print("backend:", jax.default_backend(), flush=True)

    def weights(m):
        nid = jnp.arange(m, dtype=jnp.int32)
        mask = (node[None, :] % m == nid[:, None]).astype(jnp.float32)
        return jnp.concatenate([mask * dg[None, :], mask * dh[None, :]])

    def chained(one_hist, w0, iters):
        """iters histogram passes, each perturbing the next weights so
        the chain is a true data dependency."""

        @jax.jit
        def run(w):
            def body(_, w):
                h = one_hist(w)
                return w * (1.0 + 1e-30 * h.sum())
            return jax.lax.fori_loop(0, iters, body, w)

        return run, w0

    def time_chain(one_hist, w0, mode):
        short, long_ = CHAINS.get(mode, DEFAULT_CHAIN)
        fs, w = chained(one_hist, w0, short)
        fl, _ = chained(one_hist, w0, long_)
        np.asarray(fs(w))
        np.asarray(fl(w))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(fs(w))
            ts = time.perf_counter() - t0
            t0 = time.perf_counter()
            np.asarray(fl(w))
            tl = time.perf_counter() - t0
            best = min(best, (tl - ts) / (long_ - short))
        return best

    for spec in specs:
        mode, _, arg = spec.partition(":")
        m = int(arg) if arg and mode != "plan" else 1
        if mode == "xla1":
            w0 = jnp.stack([dg, dh])

            def one(w):
                return histogram.build_local(
                    db, w[0], w[1], NBIN, use_pallas=False)
        elif mode == "pallas1":
            w0 = jnp.stack([dg, dh])

            def one(w):
                return hist_fused_multi(dbt, w, NBIN)
        elif mode == "plan":
            hi, lo, fpg = (int(x) for x in arg.split(":"))
            w0 = jnp.stack([dg, dh])

            def one(w, plan=(hi, lo, fpg)):
                return hist_fused_multi(dbt, w, NBIN, plan_override=plan)
        elif mode == "pallasM":
            w0 = weights(m)

            def one(w):
                return hist_fused_multi(dbt, w, NBIN)
        elif mode == "xlaM":
            w0 = weights(m)

            def one(w, m=m):
                outs = [histogram.build_local(
                    db, w[v], w[m + v], NBIN, use_pallas=False)
                    for v in range(m)]
                return jnp.stack(outs)
        else:
            print(f"{spec}: unknown mode")
            continue
        try:
            t = time_chain(one, w0, mode)
        except Exception as e:  # noqa: BLE001
            print(f"{spec:12s} FAILED: {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:100]}")
            continue
        print(f"{spec:12s} {t*1e3:8.3f} ms   "
              f"({N * F * 4 / t / 1e9:6.1f} GB/s bins-read rate)",
              flush=True)


if __name__ == "__main__":
    main()

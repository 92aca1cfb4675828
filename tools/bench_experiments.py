"""Scratch experiment harness for the flagship kmeans bench (round 2).

Times one-iteration variants chained device-side (ITERS iterations in a
single fori_loop program, one host sync): sync by fetching the result,
time the second run of the exact jitted program.

Usage: python tools/bench_experiments.py [variant ...]
"""
from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, ".")

N, D, K, ITERS = 1 << 19, 256, 64, 50


def make_data():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((N, D)).astype(np.float32)
    c = rng.standard_normal((K, D)).astype(np.float32)
    v = np.ones(N, np.float32)
    return x, c, v


def run_variant(name: str, x, c, v) -> float:
    import jax
    from rabit_tpu.learn import kmeans

    if name.startswith("xla-"):
        dtype = name.split("-")[1]
        fn = lambda: kmeans.device_iterations(
            c, x, v, ITERS, use_pallas=False, compute_dtype=dtype)
    elif name.startswith("pallas-"):
        parts = name.split("-")
        dtype = parts[1]
        block = int(parts[2]) if len(parts) > 2 else 2048
        fn = lambda: kmeans.device_iterations(
            c, x, v, ITERS, use_pallas=True, block=block,
            compute_dtype=dtype)
    else:
        raise ValueError(name)

    np.asarray(fn())          # compile + warm
    np.asarray(fn())          # drain any pending work
    t0 = time.perf_counter()
    out = fn()
    np.asarray(out)
    dt = (time.perf_counter() - t0) / ITERS
    return dt


def main():
    variants = sys.argv[1:] or [
        "xla-float32", "xla-bfloat16",
        "pallas-float32-2048", "pallas-bfloat16-2048",
    ]
    x, c, v = make_data()
    import jax
    import jax.numpy as jnp
    x = jax.device_put(jnp.asarray(x))
    c = jax.device_put(jnp.asarray(c))
    v = jax.device_put(jnp.asarray(v))
    print("backend:", jax.default_backend())
    for name in variants:
        try:
            dt = run_variant(name, x, c, v)
            print(f"{name:28s} {dt*1e3:8.3f} ms/iter  "
                  f"{N/dt/1e6:8.1f} Mpoints/s")
        except Exception as e:
            print(f"{name:28s} FAILED: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()

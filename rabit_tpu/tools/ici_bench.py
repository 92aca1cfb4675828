"""In-program collective bandwidth sweep — the device data plane.

The reference measures its TCP allreduce with test/speed_test.cc; the
TPU build's hot path is an XLA collective inside one compiled program,
so this harness times exactly that: ``reps`` chained allreduces inside a
single ``jit``ed ``shard_map`` program (no per-op dispatch, the compiler
schedules the ICI ring), over a payload sweep mirroring the reference
grid (reference: test/speed_runner.py:13-18).  Reports bus bandwidth
with the standard 2(n-1)/n normalisation — the figure BASELINE.md's
v5p-64 target is quoted in.

Implementations: ``psum`` (XLA's native ring), ``ring`` (explicit
ppermute reduce-scatter/all-gather from rabit_tpu.parallel), ``pallas``
(remote-DMA ring kernel from rabit_tpu.ops.ring_allreduce).

Usage:
    python -m rabit_tpu.tools.ici_bench [--ndev N] [--reps R]
        [--impls psum,ring,ringunroll,pallas] [--sizes 4096,1048576]
Uses all visible devices by default; for a virtual CPU mesh export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before launch.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def bench_impl(impl: str, ndev: int, size: int, reps: int) -> float:
    """Seconds per allreduce of `size` float32s, chained in-program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from rabit_tpu.ops import ReduceOp, on_tpu

    avail = len(jax.devices())
    if ndev > avail:
        raise ValueError(
            f"ici_bench: --ndev {ndev} but only {avail} devices are "
            "visible (on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={ndev})")
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("x",))
    interpret = not on_tpu()

    def one(x):
        if impl == "psum":
            return jax.lax.psum(x, "x")
        if impl == "ring":
            from rabit_tpu.parallel.collectives import ring_allreduce

            return ring_allreduce(x, "x")
        if impl == "ringunroll":
            from rabit_tpu.parallel.collectives import ring_allreduce

            return ring_allreduce(x, "x", unroll=True)
        if impl == "pallas":
            from rabit_tpu.ops.ring_allreduce import ring_allreduce_pallas

            return ring_allreduce_pallas(x, "x", op=ReduceOp.SUM,
                                         interpret=interpret)
        raise ValueError(impl)

    if impl == "pallas" and interpret:
        # The distributed interpreter is a correctness tool, not a fast
        # path — run one op (wiring check) instead of a timed chain.
        reps = 1

    def chained(x):
        def body(_, acc):
            return one(acc) * (1.0 / ndev)  # keep magnitude stable
        return jax.lax.fori_loop(0, reps, body, x)

    fn = jax.jit(jax.shard_map(chained, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
    x = jnp.ones((size,), jnp.float32)
    np.asarray(fn(x))  # compile + warm
    t0 = time.perf_counter()
    np.asarray(fn(x))
    return (time.perf_counter() - t0) / reps


def main(argv: list[str] | None = None) -> int:
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--ndev", type=int, default=0,
                    help="mesh size (default: all devices)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--impls", default="psum,ring")
    ap.add_argument("--sizes", default="4096,65536,1048576")
    args = ap.parse_args(argv)

    ndev = args.ndev or len(jax.devices())
    for impl in args.impls.split(","):
        for size in map(int, args.sizes.split(",")):
            nbytes = size * 4
            try:
                dt = bench_impl(impl, ndev, size, args.reps)
            except Exception as e:  # noqa: BLE001 — report and continue sweep
                print(f"{impl:7s} n={size:>9d}: FAILED {str(e)[:80]}")
                continue
            bus = ((2.0 * (ndev - 1) / ndev) * nbytes / dt if ndev > 1
                   else nbytes / dt)
            print(f"{impl:7s} n={size:>9d} ({nbytes/1e6:8.2f} MB): "
                  f"{dt*1e6:10.1f} us/op, bus {bus/1e9:8.3f} GB/s",
                  flush=True)
    return 0


def cli() -> int:
    """Console-script entry point."""
    return main()


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:]))

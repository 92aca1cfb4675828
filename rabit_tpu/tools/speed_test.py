"""Collective micro-benchmark worker.

Equivalent of reference: test/speed_test.cc:1-107 — times Allreduce(max),
Allreduce(sum) and Broadcast over a payload of n floats for nrep
repetitions, allreduces the per-rank timing mean/std, and prints MB/s.
Works against whichever engine RABIT_ENGINE selects (native / pysocket /
mock / xla), so it doubles as the rabit-vs-MPI comparison harness the
reference drives via test/speed_runner.py — here the comparison axis is
host-TCP engine vs XLA/ICI device path.

Usage (as a launched worker):
    python -m rabit_tpu.tracker.launch_local -n 4 -- \
        python -m rabit_tpu.tools.speed_test <ndata> <nrepeat> [device]

With ``device`` the buffers are jax Arrays riding the device data plane;
otherwise numpy host buffers.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

import rabit_tpu
from rabit_tpu.ops import MAX, SUM


def _stats(dt: float):
    """Allreduce (sum, sum^2) of the per-rank time, like the reference's
    mean/std aggregation (reference: test/speed_test.cc:53-70)."""
    world = rabit_tpu.get_world_size()
    agg = rabit_tpu.allreduce(np.array([dt, dt * dt], np.float64), SUM)
    mean = agg[0] / world
    var = max(agg[1] / world - mean * mean, 0.0)
    return mean, float(np.sqrt(var))


def run(ndata: int, nrep: int, device: bool = False,
        checkpoint_every: int = 0) -> dict:
    """``checkpoint_every > 0`` commits an in-memory checkpoint every
    that many ops — the reference apps' usage pattern (kmeans checkpoints
    per iteration).  Each commit clears the robust result cache and
    recycles its buffers (HarvestCache), so this mode measures the
    steady state a real application sees, where even the retention
    regime fresh-allocates no payload memory."""
    rank = rabit_tpu.get_rank()
    if device:
        import jax.numpy as jnp

        make = lambda: jnp.full((ndata,), float(rank + 1), jnp.float32)  # noqa: E731
    else:
        make = lambda: np.full(ndata, float(rank + 1), np.float32)  # noqa: E731

    nbytes = ndata * 4
    results = {}
    for name, op in (("allreduce_max", MAX), ("allreduce_sum", SUM)):
        buf = make()
        rabit_tpu.allreduce(buf, op)  # warmup (and XLA compile)
        t0 = time.perf_counter()
        for i in range(nrep):
            buf = make()
            out = rabit_tpu.allreduce(buf, op)
            if checkpoint_every and (i + 1) % checkpoint_every == 0:
                rabit_tpu.checkpoint({"op": name, "i": i})
        if device:
            import jax

            jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / nrep
        mean, std = _stats(dt)
        world = rabit_tpu.get_world_size()
        # bus bandwidth: the standard 2(n-1)/n normalisation that makes
        # allreduce numbers comparable across world sizes (each byte must
        # cross the bus twice, minus the local share) — the figure the
        # v5p-64 ≥90%-of-MPI target in BASELINE.md is quoted in.
        bus = (2.0 * (world - 1) / world) * nbytes / mean if world > 1 \
            else nbytes / mean
        results[name] = {"sec_mean": mean, "sec_std": std,
                         "mbps": nbytes / mean / 1e6,
                         "bus_gbps": bus / 1e9}

    payload = np.full(ndata, 7.0, np.float32).tobytes()
    rabit_tpu.broadcast(payload if rank == 0 else None, 0)
    t0 = time.perf_counter()
    for _ in range(nrep):
        rabit_tpu.broadcast(payload if rank == 0 else None, 0)
    dt = (time.perf_counter() - t0) / nrep
    mean, std = _stats(dt)
    results["broadcast"] = {"sec_mean": mean, "sec_std": std,
                            "mbps": nbytes / mean / 1e6}
    return results


def main(argv: list[str]) -> int:
    ndata = int(argv[1]) if len(argv) > 1 else 100000
    nrep = int(argv[2]) if len(argv) > 2 else 100
    device = len(argv) > 3 and argv[3] == "device"
    checkpoint_every = int(os.environ.get("RABIT_SPEED_CHECKPOINT", "0"))
    if device:
        from rabit_tpu.utils import compile_cache

        compile_cache.enable()
    rabit_tpu.init()
    results = run(ndata, nrep, device, checkpoint_every)
    if rabit_tpu.get_rank() == 0:
        for name, r in results.items():
            line = ("%s: %.6f +/- %.6f sec, %.2f MB/s"
                    % (name, r["sec_mean"], r["sec_std"], r["mbps"]))
            if "bus_gbps" in r:
                line += ", bus %.3f GB/s" % r["bus_gbps"]
            rabit_tpu.tracker_print(line)
    # Telemetry: with RABIT_OBS_DIR set, rank 0 drops the benchmark
    # results next to the per-rank metric summaries the engines ship at
    # finalize (the tracker then writes the aggregated obs_report.json).
    obs_dir = os.environ.get("RABIT_OBS_DIR")
    if obs_dir and rabit_tpu.get_rank() == 0:
        import json

        os.makedirs(obs_dir, exist_ok=True)
        with open(os.path.join(obs_dir, "speed_results.json"), "w") as f:
            json.dump({"ndata": ndata, "nrep": nrep, "device": device,
                       "world": rabit_tpu.get_world_size(),
                       "results": results}, f, indent=2, sort_keys=True)
    rabit_tpu.finalize()
    return 0


def cli() -> int:
    """Console-script entry point."""
    return main(sys.argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Worker program: the XLA engine with one device per process and NO
backend pinned — the process-per-chip phase of ``chip_smoke.py
--chips 4`` (on a TPU host ``launch_local`` gives each child one chip),
and the same program on the CPU backend when ``JAX_PLATFORMS=cpu`` is in
the environment (the rehearsal in tests/test_chip_smoke.py).

Unlike check_xla.py nothing here may ride the host transport unnoticed:
the engine must not have started degraded, every ``jax.Array``
collective must count as a device op, and none as a host op.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

import rabit_tpu
from rabit_tpu import engine as engine_mod
from rabit_tpu.ops import on_tpu
from rabit_tpu.utils import compile_cache

# bytes per rank: latency-bound, bandwidth-bound, and past the Pallas
# ring's VMEM segmentation threshold
SIZES = (64 << 10, 4 << 20, 64 << 20)


def main() -> None:
    compile_cache.enable()
    # the inner host engine is named, not auto-detected: which one runs
    # must not depend on an untracked librabit_tpu.so lying on disk
    rabit_tpu.init(rabit_engine="xla", rabit_inner_engine="pyrobust",
                   rabit_form_timeout_sec="120")
    rank = rabit_tpu.get_rank()
    world = rabit_tpu.get_world_size()
    eng = engine_mod.get_engine()
    assert world > 1, "check_xla_chip expects a multi-process run"
    assert not eng._degraded, "engine started degraded (host transport)"
    assert jax.process_count() == world, (jax.process_count(), world)
    assert len(jax.devices()) >= world, jax.devices()
    mine = jax.local_devices()[0]
    # the process mesh is ordered by tracker rank, whatever process
    # index the chip runtime gave this process
    assert eng.mesh.devices[rank] == mine, (eng.mesh, rank, mine)
    rabit_tpu.tracker_print(
        f"check_xla_chip rank {rank}/{world} on {mine.platform} "
        f"{mine.device_kind} id={mine.id} "
        f"process_index={jax.process_index()} "
        f"(local {len(jax.local_devices())}, global {len(jax.devices())})")

    rng = np.random.default_rng(rank)
    all_rngs = [np.random.default_rng(r) for r in range(world)]
    for nbytes in SIZES:
        n = nbytes // 4
        # integer-valued float32: every partial sum is exact, so the
        # result is bit-equal whatever order the device reduces in
        local = rng.integers(-8, 9, n).astype(np.float32)
        want = sum(g.integers(-8, 9, n).astype(np.float32)
                   for g in all_rngs)
        out = rabit_tpu.allreduce(jnp.asarray(local), rabit_tpu.SUM)
        assert isinstance(out, jax.Array)
        np.testing.assert_array_equal(np.asarray(out), want)
    ops = len(SIZES)

    def path_ops():
        return {k: eng.path_stats[k] for k in ("device_ops", "host_ops")}

    assert path_ops() == {"device_ops": ops, "host_ops": 0}, path_ops()
    # the program's own spans saw the same calls (obs/program.py)
    assert eng.path_stats["allreduce.dispatch.n"] == ops, eng.path_stats
    assert eng.path_stats["allreduce.programs_built"] == ops
    # forming the group ran its four phases once each, and they are
    # most of it
    phases = ["init.group." + p
              for p in ("service", "barrier", "connect", "mesh")]
    assert [eng.path_stats[p + ".n"] for p in phases] == [1] * 4
    formed = eng.path_stats["init.group.total_s"]
    inside = sum(eng.path_stats[p + ".total_s"] for p in phases)
    assert 0.5 * formed <= inside <= formed, eng.path_stats
    assert abs(eng.path_stats["init.group.self_s"]
               - (formed - inside)) < 1e-6
    # under RABIT_DEVICE_IMPL=pallas_ring the two large payloads rode
    # the remote-DMA kernel (multi-process meshes only do on a TPU)
    ring = [eng._use_pallas_ring((n // 4,), "float32", rabit_tpu.SUM)
            for n in SIZES]
    assert ring == [eng._device_impl == "pallas_ring" and on_tpu()
                    and n >= eng._pallas_min_bytes for n in SIZES], ring

    out = rabit_tpu.allreduce(jnp.full((8,), float(rank)), rabit_tpu.MAX)
    np.testing.assert_array_equal(np.asarray(out), world - 1)
    g = np.asarray(rabit_tpu.allgather(
        jnp.array([rank, 2 * rank], dtype=jnp.int32)))
    assert g.tolist() == [[r, 2 * r] for r in range(world)], g
    assert path_ops() == {"device_ops": ops + 2, "host_ops": 0}, path_ops()

    # control plane: object broadcast from every root, checkpoint trio
    for root in range(world):
        obj = {"root": root} if rank == root else None
        assert rabit_tpu.broadcast(obj, root) == {"root": root}
    version, model = rabit_tpu.load_checkpoint()
    assert version == 0 and model is None
    rabit_tpu.checkpoint({"iter": 1, "sum0": float(np.asarray(out)[0])})
    version, model = rabit_tpu.load_checkpoint()
    assert version == 1 and model == {"iter": 1, "sum0": world - 1.0}
    assert not eng._degraded, "engine degraded during the run"

    rabit_tpu.tracker_print(
        f"check_xla_chip rank {rank}/{world} OK path_stats="
        f"{path_ops()} device_impl={eng._device_impl} ring={ring}")
    rabit_tpu.finalize()


if __name__ == "__main__":
    main()

"""Worker: ``kmeans.run`` over the XLA engine on a shard that streams
(tier ``ell_stream``: some chunks resident, the others handed over every
iteration), with an optional death as in ``kmeans_run_xla.py``.

The chip's arm of ``prepare_shard`` is steered here as
``tests/test_kmeans_stream.py`` steers it: ``on_tpu``, the kernel
interpreted, and a budget that holds two of a rank's six chunks beside
the ring.

argv: <out_prefix>
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 1)

import numpy as np

import rabit_tpu
import rabit_tpu.ops.kmeans_kernel as kk
from rabit_tpu import engine
from rabit_tpu.learn import kmeans
from rabit_tpu.learn.data import SparseMat

K, DIM, ROWS, BLOCK, CHUNK, RESIDENT = 3, 8, 192, 16, 32, 2


def steer() -> None:
    fused = kk.kmeans_ell_stats_fused
    kk.kmeans_ell_stats_fused = lambda *a, **kw: fused(
        *a, **{**kw, "interpret": True})
    kmeans.on_tpu = lambda: True
    kmeans._ELL_FUSED_BLOCK = BLOCK
    kmeans._STAGE_CHUNK_ROWS = CHUNK
    kmeans._stream_budget = lambda: (
        (RESIDENT + kmeans._STREAM_RING) * CHUNK * (DIM * 8 + 4))
    stage = kmeans.prepare_shard
    kmeans.prepare_shard = lambda *a, **kw: stage(*a, **{**kw, "budget": 0})


def shard_of(rank: int) -> SparseMat:
    """Three blobs on the first three axes; every feature stored."""
    rng = np.random.default_rng([17, rank])
    x = rng.normal(0.0, 0.3, (ROWS, DIM)).astype(np.float32)
    x[np.arange(ROWS), np.arange(ROWS) % K] += 5.0
    return SparseMat(
        indptr=np.arange(0, ROWS * DIM + 1, DIM, dtype=np.int64),
        findex=np.tile(np.arange(DIM, dtype=np.int32), ROWS),
        fvalue=x.reshape(-1), labels=np.zeros(ROWS, np.float32),
        feat_dim=DIM)


def main() -> int:
    out = sys.argv[1]
    trial = int(os.environ.get("RABIT_NUM_TRIAL", "0") or 0)
    steer()
    rabit_tpu.init(rabit_engine="xla",
                   rabit_inner_engine=os.environ.get("RABIT_INNER",
                                                     "pysocket"))
    rank = rabit_tpu.get_rank()
    die = os.environ.get("RABIT_KMEANS_DIE")
    if die and trial == 0:
        die_rank, die_version = map(int, die.split(":"))
        orig_checkpoint = rabit_tpu.checkpoint

        def checkpoint_with_killpoint(model):
            if (rabit_tpu.get_rank() == die_rank
                    and rabit_tpu.version_number() + 1 >= die_version):
                os._exit(254)
            orig_checkpoint(model)

        rabit_tpu.checkpoint = checkpoint_with_killpoint
    model = kmeans.run(shard_of(rank), num_cluster=K, max_iter=5,
                       row_block=BLOCK)
    stats = engine.get_engine().path_stats
    chunks = ROWS // CHUNK
    # every pass took the chunks that are not resident off the ring
    assert stats["stream.chunks"] >= chunks - RESIDENT, stats
    assert stats["stage.resident_rows"] == RESIDENT * CHUNK, stats
    assert stats["stream.inflight_max"] <= kmeans._STREAM_RING, stats
    assert stats["learn.device_updates"] == stats["learn.versions"], stats
    if die and trial == 0 and rabit_tpu.device_epoch() > 0:
        # a survivor of the re-formation: the resident chunks were put
        # a second time, and the host's chunks were not made again
        assert stats["stage.put.n"] == 2, stats
        assert stats["stage.resident.n"] == 2, stats
        assert stats["stage.host_chunks"] == chunks, stats
        assert stats.get("learn.ahead_discarded", 0) >= 1, stats
    else:
        assert stats["stage.put.n"] == 1, stats
    gathered = rabit_tpu.allgather(model.centroids.reshape(-1))
    for r in range(rabit_tpu.get_world_size()):
        np.testing.assert_array_equal(gathered[r],
                                      model.centroids.reshape(-1))
    if rank == 0:
        np.save(out + ".npy", model.centroids)
    rabit_tpu.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())

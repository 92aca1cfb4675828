"""Worker: the kmeans *app* (kmeans.run) over the XLA engine — the full
TPU-native slice: staged device shard → device stats pass → stats
allreduce riding the device data plane → checkpoint via control plane.

argv: <data_pattern(%d)> <k> <max_iter> <out_prefix>
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 1)

import numpy as np

import rabit_tpu
from rabit_tpu import engine
from rabit_tpu.learn import kmeans, load_libsvm


def main() -> int:
    pattern, k, max_iter, out = (sys.argv[1], int(sys.argv[2]),
                                 int(sys.argv[3]), sys.argv[4])
    trial = int(os.environ.get("RABIT_NUM_TRIAL", "0") or 0)
    rabit_tpu.init(rabit_engine="xla",
                   rabit_inner_engine=os.environ.get("RABIT_INNER",
                                                     "pysocket"))
    rank = rabit_tpu.get_rank()
    # Optional death injection RABIT_KMEANS_DIE="rank:version": die just
    # before committing that checkpoint version (first life only) — the
    # survivors degrade mid-iteration, the relaunch resumes from the
    # checkpoint, and the next checkpoint boundary re-forms the device
    # plane; kmeans.run must then re-upload its device shard (epoch
    # change) and keep full numeric agreement.
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
    data = load_libsvm(pattern, rank=rank)
    model = kmeans.run(data, num_cluster=k, max_iter=max_iter,
                       row_block=32)
    # the loop ran its stats programs a commit ahead (in a relaunched
    # life too: max_iter leaves it versions enough), and a survivor of
    # a re-formation dropped the result it had queued in the old epoch
    stats = engine.get_engine().path_stats
    assert stats.get("learn.ahead", 0) >= 1, stats
    # every version's centroids were computed on the device, from the
    # reduced statistics that never left it (in a degraded version too:
    # the host transport's sum goes back to the device)
    assert stats["learn.device_updates"] == stats["learn.versions"], stats
    if die and trial == 0 and rabit_tpu.device_epoch() > 0:
        assert stats.get("learn.ahead_discarded", 0) >= 1, stats

    # all ranks must agree on the final model
    gathered = rabit_tpu.allgather(model.centroids.reshape(-1))
    for r in range(rabit_tpu.get_world_size()):
        np.testing.assert_allclose(gathered[r],
                                   model.centroids.reshape(-1), rtol=1e-5)
    if rank == 0:
        np.save(out + ".npy", model.centroids)
    rabit_tpu.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Distributed learn-layer tests: kmeans and linear over real multi-process
jobs (tracker + socket engine), checked against single-process oracles.

Mirrors how the reference exercises its apps through the demo launcher
(reference: rabit-learn/kmeans run scripts, test/test.mk) with numeric
self-verification in the workers.
"""
import sys

import numpy as np
import pytest


def _write_libsvm(path, X, y):
    with open(path, "w") as f:
        for row, label in zip(X, y):
            items = " ".join(
                f"{j}:{v:g}" for j, v in enumerate(row) if v != 0.0)
            f.write(f"{label:g} {items}\n")


def _shard_files(tmp_path, X, y, world):
    for r in range(world):
        _write_libsvm(tmp_path / f"part{r}.libsvm", X[r::world], y[r::world])
    _write_libsvm(tmp_path / "full.libsvm", X, y)
    return str(tmp_path / "part%d.libsvm"), str(tmp_path / "full.libsvm")


def _blobs(n=240, d=8, k=3, seed=0):
    rng = np.random.default_rng(seed)
    centers = np.zeros((k, d), np.float32)
    centers[np.arange(k), np.arange(k)] = 4.0
    X = np.concatenate(
        [centers[i] + 0.1 * rng.standard_normal((n // k + 1, d))
         for i in range(k)])[:n].astype(np.float32)
    rng.shuffle(X)
    return X


@pytest.mark.parametrize("engine", ["pysocket", "native"])
def test_kmeans_distributed(tmp_path, engine, native_lib):
    from rabit_tpu.tracker.launch_local import launch

    world = 4
    X = _blobs()
    pattern, full = _shard_files(tmp_path, X, np.zeros(len(X)), world)
    out = str(tmp_path / "cent")
    code = launch(world, [sys.executable, "tests/workers/kmeans_dist.py",
                          pattern, full, "3", "5", out],
                  extra_env={"RABIT_ENGINE": engine})
    assert code == 0
    cent = np.load(out + ".npy")
    assert cent.shape == (3, 8)
    # blobs are axis-aligned: each centroid should be dominated by one axis
    cn = cent / np.linalg.norm(cent, axis=1, keepdims=True)
    axes = sorted(np.argmax(cn, axis=1))
    assert axes == [0, 1, 2]


def test_kmeans_app_on_xla_engine(tmp_path):
    """kmeans.run over the XLA engine: the stats allreduce rides the
    device data plane (jax.Array through the engine), the checkpoint
    the control plane."""
    from rabit_tpu.tracker.launch_local import launch

    world = 2
    X = _blobs()
    pattern, _full = _shard_files(tmp_path, X, np.zeros(len(X)), world)
    out = str(tmp_path / "cent_xla")
    code = launch(world, [sys.executable,
                          "tests/workers/kmeans_run_xla.py",
                          pattern, "3", "5", out])
    assert code == 0
    cent = np.load(out + ".npy")
    cn = cent / np.linalg.norm(cent, axis=1, keepdims=True)
    assert sorted(np.argmax(cn, axis=1)) == [0, 1, 2]


def test_kmeans_app_on_xla_engine_death_reform(tmp_path, native_lib):
    """kmeans.run over the XLA engine with a mid-run death: the relaunch
    resumes from the checkpoint, the device plane re-forms at the next
    checkpoint boundary, and kmeans re-uploads its device shard (epoch
    change) — final centroids still agree across all ranks, and are the
    same job's without a death: the relaunch resumes from the committed
    centroids, which are the device's own, bit for bit."""
    from rabit_tpu.tracker.launch_local import launch

    world = 3
    X = _blobs()
    pattern, _full = _shard_files(tmp_path, X, np.zeros(len(X)), world)
    cents = {}
    for name, die in (("undisturbed", {}),
                      ("reform", {"RABIT_KMEANS_DIE": "1:2"})):
        out = str(tmp_path / ("cent_xla_" + name))
        code = launch(world, [sys.executable,
                              "tests/workers/kmeans_run_xla.py",
                              pattern, "3", "5", out],
                      extra_env={"RABIT_INNER": "native", **die},
                      watchdog_sec=20)
        assert code == 0
        cents[name] = np.load(out + ".npy")
    cent = cents["reform"]
    cn = cent / np.linalg.norm(cent, axis=1, keepdims=True)
    assert sorted(np.argmax(cn, axis=1)) == [0, 1, 2]
    np.testing.assert_array_equal(cent, cents["undisturbed"])


def test_kmeans_distributed_with_faults(tmp_path, native_lib):
    """kmeans keeps its numeric guarantees across a mid-iteration death
    (the app-level version of the reference's model_recover matrix)."""
    from rabit_tpu.tracker.launch_local import launch

    world = 4
    X = _blobs()
    pattern, full = _shard_files(tmp_path, X, np.zeros(len(X)), world)
    out = str(tmp_path / "cent_fault")
    code = launch(world, [sys.executable, "tests/workers/kmeans_dist.py",
                          pattern, full, "3", "5", out],
                  extra_env={"RABIT_ENGINE": "mock",
                             "RABIT_MOCK": "1,1,0,0;2,3,0,0"})
    assert code == 0
    cent = np.load(out + ".npy")
    cn = cent / np.linalg.norm(cent, axis=1, keepdims=True)
    assert sorted(np.argmax(cn, axis=1)) == [0, 1, 2]


def test_linear_distributed_matches_single(tmp_path, native_lib):
    """Distributed logistic training must match full-data single-process
    training (shard gradients sum exactly to the full gradient)."""
    import rabit_tpu
    from rabit_tpu.learn import LinearModel, LinearObjFunction
    from rabit_tpu.tracker.launch_local import launch

    world = 4
    rng = np.random.default_rng(7)
    n, d = 240, 10
    X = rng.standard_normal((n, d)).astype(np.float32)
    w_true = rng.standard_normal(d)
    # noisy labels + real L2 keep the optimum well-conditioned so the
    # distributed and single-process trajectories stay numerically close
    y = (1 / (1 + np.exp(-(X @ w_true))) > rng.random(n)).astype(np.float32)
    pattern, full = _shard_files(tmp_path, X, y, world)

    out_model = str(tmp_path / "dist.model")
    code = launch(world, [sys.executable, "tests/workers/linear_dist.py",
                          pattern, "logistic", out_model,
                          "reg_L2=0.1", "max_lbfgs_iter=25"],
                  extra_env={"RABIT_ENGINE": "native"})
    assert code == 0

    # single-process oracle on the full data
    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    obj = LinearObjFunction()
    obj.load_data(full)
    obj.set_param("objective", "logistic")
    obj.set_param("reg_L2", "0.1")
    obj.set_param("max_lbfgs_iter", "25")
    obj.set_param("silent", "1")
    obj.set_param("row_block", "64")
    obj.set_param("model_out", str(tmp_path / "single.model"))
    obj.run()
    rabit_tpu.finalize()

    dist = LinearModel()
    dist.load(out_model)
    single = LinearModel()
    single.load(str(tmp_path / "single.model"))
    assert dist.num_feature == single.num_feature
    np.testing.assert_allclose(dist.weight, single.weight,
                               rtol=1e-3, atol=1e-3)


def test_linear_distributed_with_faults(tmp_path, native_lib):
    """L-BFGS under deaths: the solver checkpoints a (global, local)
    state pair every iteration (reference: lbfgs.h:119,192 — the
    local-model path the reference exercises via local_recover); two
    workers dying at different versions must replay/reload and still
    land on the single-process optimum."""
    import rabit_tpu
    from rabit_tpu.learn import LinearModel, LinearObjFunction
    from rabit_tpu.tracker.launch_local import launch

    world = 4
    rng = np.random.default_rng(11)
    n, d = 240, 10
    X = rng.standard_normal((n, d)).astype(np.float32)
    w_true = rng.standard_normal(d)
    y = (1 / (1 + np.exp(-(X @ w_true))) > rng.random(n)).astype(np.float32)
    pattern, full = _shard_files(tmp_path, X, y, world)

    out_model = str(tmp_path / "dist_fault.model")
    code = launch(world, [sys.executable, "tests/workers/linear_dist.py",
                          pattern, "logistic", out_model,
                          "reg_L2=0.1", "max_lbfgs_iter=25"],
                  extra_env={"RABIT_ENGINE": "mock",
                             "RABIT_MOCK": "1,2,0,0;3,5,1,0"})
    assert code == 0

    if rabit_tpu.initialized():
        rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    obj = LinearObjFunction()
    obj.load_data(full)
    obj.set_param("objective", "logistic")
    obj.set_param("reg_L2", "0.1")
    obj.set_param("max_lbfgs_iter", "25")
    obj.set_param("silent", "1")
    obj.set_param("row_block", "64")
    obj.set_param("model_out", str(tmp_path / "single_fault.model"))
    obj.run()
    rabit_tpu.finalize()

    dist = LinearModel()
    dist.load(out_model)
    single = LinearModel()
    single.load(str(tmp_path / "single_fault.model"))
    np.testing.assert_allclose(dist.weight, single.weight,
                               rtol=1e-3, atol=1e-3)

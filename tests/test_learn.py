"""Learn-layer tests: data utils, kmeans, L-BFGS, linear models.

Mirrors the reference's app-level coverage (kmeans/linear binaries +
solver, reference: rabit-learn/) with numeric self-verification in the
style of its recovery tests (reference: test/model_recover.cc:29-70).
Single-process here; the distributed paths are covered by the worker
tests in test_learn_dist.py.
"""
import io
import sys

import numpy as np
import pytest


# ---------------------------------------------------------------- data utils
def _write_libsvm(path, X, y):
    with open(path, "w") as f:
        for row, label in zip(X, y):
            items = " ".join(
                f"{j}:{v:g}" for j, v in enumerate(row) if v != 0.0)
            f.write(f"{label:g} {items}\n")


def test_libsvm_roundtrip(tmp_path):
    from rabit_tpu.learn import load_libsvm

    rng = np.random.default_rng(0)
    X = rng.standard_normal((20, 7)).astype(np.float32)
    X[rng.random(X.shape) < 0.5] = 0.0
    X[:, -1] = 1.0  # ensure full feat_dim observed
    y = rng.integers(0, 2, 20).astype(np.float32)
    f = tmp_path / "data.libsvm"
    _write_libsvm(f, X, y)

    mat = load_libsvm(str(f))
    assert mat.num_row == 20
    assert mat.feat_dim == 7
    np.testing.assert_allclose(mat.labels, y)
    np.testing.assert_allclose(mat.to_dense(), X, rtol=1e-5)


def test_libsvm_per_rank_filename(tmp_path):
    from rabit_tpu.learn import load_libsvm

    for r in range(2):
        _write_libsvm(tmp_path / f"part{r}.txt",
                      np.eye(3, dtype=np.float32) * (r + 1),
                      np.full(3, r, np.float32))
    mat = load_libsvm(str(tmp_path / "part%d.txt"), rank=1)
    np.testing.assert_allclose(mat.labels, [1, 1, 1])
    assert mat.to_dense()[0, 0] == 2.0


def test_ell_layout():
    from rabit_tpu.learn.data import SparseMat

    mat = SparseMat(
        indptr=np.array([0, 2, 3, 3], np.int64),
        findex=np.array([0, 4, 2], np.int32),
        fvalue=np.array([1.0, 2.0, 3.0], np.float32),
        labels=np.array([1, 0, 1], np.float32),
        feat_dim=5,
    )
    idx, val, labels, valid = mat.to_ell(row_block=4)
    assert idx.shape == (4, 2)
    assert valid.tolist() == [1, 1, 1, 0]
    # row 0: features 0,4; row 2 all padding (sentinel = feat_dim)
    assert idx[0].tolist() == [0, 4]
    assert idx[2].tolist() == [5, 5]
    np.testing.assert_allclose(val[1], [3.0, 0.0])


# ------------------------------------------------------------------- kmeans
def _blob_data(n=256, d=8, k=3, seed=0):
    """Blobs on orthogonal axes — cosine-separable by construction.

    Rows are shuffled so the random-row centroid init (seeded like the
    reference's srand(0), kmeans.cc:96) sees a mixed sample.
    """
    rng = np.random.default_rng(seed)
    centers = np.zeros((k, d), np.float32)
    centers[np.arange(k), np.arange(k)] = 4.0
    X = np.concatenate(
        [centers[i] + 0.1 * rng.standard_normal((n // k + 1, d))
         for i in range(k)])[:n].astype(np.float32)
    rng.shuffle(X)
    from rabit_tpu.learn.data import SparseMat

    nnz = n * d
    return SparseMat(
        indptr=np.arange(0, nnz + 1, d, dtype=np.int64),
        findex=np.tile(np.arange(d, dtype=np.int32), n),
        fvalue=X.reshape(-1),
        labels=np.zeros(n, np.float32),
        feat_dim=d,
    ), X


def _kmeans_oracle(X, cent, iters):
    """Pure-numpy twin of the framework's kmeans loop."""
    c = cent.astype(np.float32).copy()
    k, d = c.shape
    for _ in range(iters):
        cn = c / (np.linalg.norm(c, axis=1, keepdims=True) + 1e-12)
        assign = (X @ cn.T).argmax(axis=1)
        stats = np.zeros((k, d + 1), np.float32)
        for i, a in enumerate(assign):
            stats[a, :d] += X[i]
            stats[a, d] += 1
        assert (stats[:, d] != 0).all(), "oracle hit empty cluster"
        c = (stats[:, :d] / stats[:, d:]).astype(np.float32)
        n = np.linalg.norm(c, axis=1, keepdims=True)
        c = np.where(n < 1e-6, c, c / np.maximum(n, 1e-30)).astype(np.float32)
    return c


def test_kmeans_converges(empty_engine):
    from rabit_tpu.learn import kmeans

    data, X = _blob_data()
    model = kmeans.run(data, num_cluster=3, max_iter=8, row_block=64)
    assert model.centroids.shape == (3, 8)
    # must agree with the numpy twin run from the identical init
    init = kmeans.init_centroids(data, 3, 8, seed=0)
    oracle = _kmeans_oracle(X, init.centroids, 8)
    np.testing.assert_allclose(model.centroids, oracle, rtol=1e-3, atol=1e-3)
    # and the clustering itself must be tight (blobs are separable)
    cn = model.centroids / np.linalg.norm(
        model.centroids, axis=1, keepdims=True)
    xn = X / np.linalg.norm(X, axis=1, keepdims=True)
    assert (xn @ cn.T).max(axis=1).mean() > 0.97


def test_kmeans_device_chain_matches_loop(empty_engine):
    """The device-resident chained path (run(device_chain=...)) must give
    the same centroids as the per-iteration host loop.

    Differences are allowed only where an empty cluster appears (the
    chained path keeps the old centroid instead of erroring), which the
    separable blobs avoid."""
    from rabit_tpu.learn import kmeans

    data, _X = _blob_data()
    ref = kmeans.run(data, num_cluster=3, max_iter=8, row_block=64)
    import rabit_tpu
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    chained = kmeans.run(data, num_cluster=3, max_iter=8, row_block=64,
                         device_chain=3)  # 3+3+2 split exercises resume
    np.testing.assert_allclose(chained.centroids, ref.centroids,
                               rtol=1e-4, atol=1e-4)


def test_kmeans_checkpoint_resume(empty_engine):
    """Interrupting after version v and rerunning must give the identical
    model (the reference's recovery semantics at app level)."""
    import rabit_tpu
    from rabit_tpu.learn import kmeans

    data, _ = _blob_data()
    full = kmeans.run(data, num_cluster=3, max_iter=6, row_block=64)
    # fresh engine: run 3 iters, "crash", resume to 6
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    kmeans.run(data, num_cluster=3, max_iter=3, row_block=64)
    resumed = kmeans.run(data, num_cluster=3, max_iter=6, row_block=64)
    np.testing.assert_allclose(
        resumed.centroids, full.centroids, rtol=1e-5, atol=1e-6)


def test_kmeans_chained_resume_counts_chains(empty_engine):
    """The chained path commits one version per CHAIN, so a resume
    continues at version * device_chain iterations — not at `version`
    iterations, which re-ran most of the committed work and committed
    one version too many."""
    import rabit_tpu
    from rabit_tpu.learn import kmeans

    data, _ = _blob_data()
    full = kmeans.run(data, num_cluster=3, max_iter=6, row_block=64,
                      device_chain=3)
    assert rabit_tpu.version_number() == 2
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    kmeans.run(data, num_cluster=3, max_iter=3, row_block=64,
               device_chain=3)
    resumed = kmeans.run(data, num_cluster=3, max_iter=6, row_block=64,
                         device_chain=3)
    assert rabit_tpu.version_number() == 2
    np.testing.assert_allclose(
        resumed.centroids, full.centroids, rtol=1e-5, atol=1e-6)


def test_kmeans_stats_against_numpy(empty_engine):
    from rabit_tpu.learn import kmeans

    data, X = _blob_data(n=100, d=8)
    rng = np.random.default_rng(1)
    model = kmeans.KMeansModel(
        rng.standard_normal((4, 8)).astype(np.float32))
    idx, val, _, valid = data.to_ell(pad_index=8, row_block=32)
    stats = kmeans.compute_stats(model, idx, val, valid, row_block=32)
    # numpy oracle
    cn = model.centroids / np.linalg.norm(
        model.centroids, axis=1, keepdims=True)
    assign = (X @ cn.T).argmax(axis=1)
    expect = np.zeros((4, 9), np.float32)
    for i, a in enumerate(assign):
        expect[a, :8] += X[i]
        expect[a, 8] += 1
    np.testing.assert_allclose(stats, expect, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- L-BFGS
from rabit_tpu.learn import lbfgs  # noqa: E402


class _Quadratic(lbfgs.ObjFunction):
    """f(w) = 0.5||w - t||^2 — exact minimum known."""

    def __init__(self, target):
        self.target = target

    def eval(self, w):
        return 0.5 * float((w - self.target) @ (w - self.target))

    def calc_grad(self, w):
        return w - self.target

    def init_num_dim(self):
        return len(self.target)

    def init_model(self, w):
        w[:] = 0.0

    def save_state(self):
        return None

    def load_state(self, state):
        pass


def test_lbfgs_quadratic(empty_engine):
    from rabit_tpu.learn import LBFGSSolver

    rng = np.random.default_rng(0)
    target = rng.standard_normal(32)
    solver = LBFGSSolver(_Quadratic(target))
    solver.silent = 1
    solver.lbfgs_stop_tol = 1e-10
    solver.run()
    np.testing.assert_allclose(solver.get_weight(), target, atol=1e-4)


def test_lbfgs_logistic_l1_sparsity(empty_engine, tmp_path):
    """OWL-QN: with L1, irrelevant features must be driven to exact zero."""
    from rabit_tpu.learn import LinearObjFunction

    rng = np.random.default_rng(0)
    n, d = 400, 12
    X = rng.standard_normal((n, d)).astype(np.float32)
    w_true = np.zeros(d)
    w_true[:3] = [2.0, -3.0, 1.5]
    y = (1 / (1 + np.exp(-(X @ w_true))) > 0.5).astype(np.float32)
    f = tmp_path / "train.libsvm"
    _write_libsvm(f, X, y)

    obj = LinearObjFunction()
    obj.load_data(str(f))
    obj.set_param("objective", "logistic")
    obj.set_param("reg_L1", "2.0")
    obj.set_param("max_lbfgs_iter", "60")
    obj.set_param("silent", "1")
    obj.lbfgs.run()
    w = obj.lbfgs.get_weight()
    # relevant features survive, most irrelevant ones are exactly zero
    assert abs(w[0]) > 0.1 and abs(w[1]) > 0.1
    assert np.sum(w[3:d] == 0.0) >= 5


# ------------------------------------------------------------------- linear
def _train_linear(tmp_path, objective, seed=0, n=500, d=10, reg_L2="0.01"):
    from rabit_tpu.learn import LinearObjFunction

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    w_true = rng.standard_normal(d)
    margin = X @ w_true
    if objective == "logistic":
        y = (1 / (1 + np.exp(-margin)) > 0.5).astype(np.float32)
    else:
        y = (margin + 0.01 * rng.standard_normal(n)).astype(np.float32)
    f = tmp_path / "train.libsvm"
    _write_libsvm(f, X, y)

    obj = LinearObjFunction()
    obj.load_data(str(f))
    obj.set_param("objective", objective)
    obj.set_param("reg_L2", reg_L2)
    obj.set_param("max_lbfgs_iter", "80")
    obj.set_param("silent", "1")
    obj.set_param("model_out", str(tmp_path / "final.model"))
    obj.run()
    return obj, X, y, w_true


def test_linear_regression_recovers_weights(empty_engine, tmp_path):
    obj, X, y, w_true = _train_linear(tmp_path, "linear", reg_L2="0")
    w = obj.model.weight
    np.testing.assert_allclose(w[:10], w_true, atol=0.05)


def test_logistic_classifies(empty_engine, tmp_path):
    obj, X, y, _ = _train_linear(tmp_path, "logistic")
    preds = obj.predict()
    acc = ((preds > 0.5) == (y > 0.5)).mean()
    assert acc > 0.97


def test_model_io_roundtrip(empty_engine, tmp_path):
    from rabit_tpu.learn import LinearModel

    obj, _, _, _ = _train_linear(tmp_path, "logistic", n=100)
    for b64 in (False, True):
        path = tmp_path / ("m.b64" if b64 else "m.bin")
        obj.model.save(str(path), base64_=b64)
        loaded = LinearModel()
        loaded.load(str(path))
        assert loaded.num_feature == obj.model.num_feature
        assert loaded.loss_type == obj.model.loss_type
        np.testing.assert_allclose(
            loaded.weight, obj.model.weight.astype(np.float32), rtol=1e-6)


def test_pred_task_writes_file(empty_engine, tmp_path):
    from rabit_tpu.learn import LinearObjFunction

    obj, X, y, _ = _train_linear(tmp_path, "logistic", n=100)
    pred_obj = LinearObjFunction()
    pred_obj.load_data(str(tmp_path / "train.libsvm"))
    pred_obj.set_param("task", "pred")
    pred_obj.set_param("model_in", str(tmp_path / "final.model"))
    pred_obj.set_param("name_pred", str(tmp_path / "pred.txt"))
    pred_obj.run()
    preds = np.loadtxt(tmp_path / "pred.txt")
    assert len(preds) == 100
    acc = ((preds > 0.5) == (y > 0.5)).mean()
    assert acc > 0.9


def test_hash_features():
    """Signed feature hashing: deterministic, in-range, seed-salted,
    sign-balanced, and inner-product-preserving in expectation (the
    property that makes hashed k-means work)."""
    from rabit_tpu.learn.data import hash_features

    rng = np.random.default_rng(0)
    idx = rng.integers(0, 100_000, (4096, 32)).astype(np.int32)
    val = rng.standard_normal((4096, 32)).astype(np.float32)

    h1, v1 = hash_features(idx, val, 256)
    h2, v2 = hash_features(idx, val, 256)
    np.testing.assert_array_equal(h1, h2)          # deterministic
    np.testing.assert_array_equal(v1, v2)
    assert h1.min() >= 0 and h1.max() < 256
    assert np.array_equal(np.abs(v1), np.abs(val))  # sign-only change
    # the same feature id always lands in the same bucket with the
    # same sign (consistency across rows is what preserves geometry)
    flat = {}
    for f, b, s in zip(idx.ravel(), h1.ravel(), np.sign(v1 / val).ravel()):
        assert flat.setdefault(int(f), (int(b), float(s))) == (int(b), float(s))
    # roughly balanced signs and buckets
    signs = np.array([s for _, s in flat.values()])
    assert 0.4 < (signs > 0).mean() < 0.6
    # a different seed remaps
    h3, _ = hash_features(idx, val, 256, seed=7)
    assert (h3 != h1).mean() > 0.9
    # power-of-two enforcement
    import pytest
    from rabit_tpu.utils.checks import RabitError
    with pytest.raises(RabitError):
        hash_features(idx, val, 200)


def test_kmeans_hashed(empty_engine):
    """hash_dim routes the whole run through signed-hashed feature
    space: the model lives at that width, staging/stats/checkpoints all
    work, and on separable blobs the clustering stays tight (collisions
    are zero-mean under the signed hash)."""
    from rabit_tpu.learn import kmeans
    from rabit_tpu.learn.data import hash_features

    data, X = _blob_data(d=16)
    model = kmeans.run(data, num_cluster=3, max_iter=8, row_block=64,
                       hash_dim=8)
    assert model.centroids.shape == (3, 8)
    # score rows the way the docstring prescribes: hash them identically
    # (to_dense sums the collision duplicates — shipped path)
    from rabit_tpu.learn.data import SparseMat
    hidx, hval = hash_features(data.findex, data.fvalue, 8)
    Xh = SparseMat(indptr=data.indptr, findex=hidx, fvalue=hval,
                   labels=data.labels, feat_dim=8).to_dense()
    cn = model.centroids / (np.linalg.norm(
        model.centroids, axis=1, keepdims=True) + 1e-12)
    xn = Xh / (np.linalg.norm(Xh, axis=1, keepdims=True) + 1e-12)
    assert (xn @ cn.T).max(axis=1).mean() > 0.9


def test_dense16_staging_matches_f32(empty_engine):
    """The half-width dense staging tier (compute_dtype="bfloat16" with
    a shard too big for the exact f32 blocks) must produce the same
    stats as the f32 tier within bf16 rounding, including the padded
    tail rows the 16384-row tile introduces."""
    from rabit_tpu.learn import kmeans

    data, X = _blob_data(n=256, d=16)
    idx, val, _, valid = data.to_ell(pad_index=16, row_block=64)
    rng = np.random.default_rng(3)
    model = kmeans.KMeansModel(
        rng.standard_normal((4, 16)).astype(np.float32))

    exact = kmeans.prepare_shard(idx, val, valid, 16, row_block=64)
    assert exact[0] == "dense"
    ref = np.asarray(kmeans.shard_stats_device(model.centroids, exact))

    half = kmeans.prepare_shard(idx, val, valid, 16, row_block=64,
                                budget=0, compute_dtype="bfloat16")
    assert half[0] == "dense16"
    x, v16 = half[2]
    assert x.shape[0] % 16384 == 0 and str(x.dtype) == "bfloat16"
    # features staged at the lane-padded width so stats calls never
    # re-pad the array
    assert x.shape[1] == 128
    got = np.asarray(kmeans.shard_stats_device(model.centroids, half))
    np.testing.assert_allclose(got, ref, rtol=3e-2, atol=3e-2)
    # padded tail must be inert: counts equal
    np.testing.assert_allclose(got[:, -1], ref[:, -1])

    # a row_block that does not divide the 16384 tile must still stage
    # (rows round to lcm(row_block, tile))
    idx3, val3, _, valid3 = data.to_ell(pad_index=16, row_block=96)
    odd = kmeans.prepare_shard(idx3, val3, valid3, 16, row_block=96,
                               budget=0, compute_dtype="bfloat16")
    assert odd[0] == "dense16"
    got3 = np.asarray(kmeans.shard_stats_device(model.centroids, odd))
    np.testing.assert_allclose(got3, ref, rtol=3e-2, atol=3e-2)


def test_kmeans_hash_dim_pinned_by_checkpoint(empty_engine, monkeypatch):
    """Resuming with a different hash_dim than the checkpoint was trained
    with must fail loudly (ADVICE r4): the feat_dim clamp would otherwise
    silently drop out-of-range hashed features."""
    import pytest

    import rabit_tpu
    from rabit_tpu.learn import kmeans
    from rabit_tpu.utils.checks import RabitError

    data, _X = _blob_data(n=64, d=16)
    trained = kmeans.run(data, 3, 2, hash_dim=8)
    assert trained.hash_dim == 8
    monkeypatch.setattr(rabit_tpu, "load_checkpoint",
                        lambda: (2, trained))
    with pytest.raises(RabitError, match="hash_dim"):
        kmeans.run(data, 3, 4, hash_dim=16)
    # the matching width resumes fine
    ok = kmeans.run(data, 3, 4, hash_dim=8)
    assert ok.hash_dim == 8 and ok.centroids.shape == (3, 8)


def test_dense16_staging_fully_padded_chunk(empty_engine, monkeypatch):
    """Regression (ADVICE r4): with row_block not dividing the 16384
    tile, rows pad to lcm(row_block, tile) and a whole staging chunk can
    start PAST the real row count.  That chunk must be skipped (the
    output is zero-initialized), not padded to a negative real-row
    count — the old code computed pad > rows and the jitted writer died
    at dense.reshape."""
    import math

    from rabit_tpu.learn import kmeans

    # shrink the chunk so the >n16-vs-chunk geometry is cheap to build:
    # lcm(96, 16384) = 49152; chunk = (16384 // 96) * 96 = 16320, so
    # chunk starts 65280 and 81600 land inside [n, n16) = [49162, 98304)
    monkeypatch.setattr(kmeans, "_STAGE_CHUNK_ROWS", 16384)
    rb, d = 96, 16
    n = math.lcm(rb, kmeans._DENSE16_ROW_TILE) + 10
    rng = np.random.default_rng(7)
    idx = rng.integers(0, d, size=(n, 1)).astype(np.int32)
    val = rng.standard_normal((n, 1)).astype(np.float32)
    valid = np.ones(n, np.float32)

    x, v16 = kmeans._stage_dense16(idx, val, valid, d, rb, "bfloat16")
    n16 = x.shape[0]
    assert n16 == 2 * math.lcm(rb, kmeans._DENSE16_ROW_TILE)
    v16 = np.asarray(v16)
    assert v16[:n].all() and not v16[n:].any()
    xh = np.asarray(x).astype(np.float32)
    # padded rows are inert zeros; real rows carry their single feature
    assert not xh[n:].any()
    dense = np.zeros((n, d), np.float32)
    np.add.at(dense, (np.arange(n), idx[:, 0]), val[:, 0])
    np.testing.assert_allclose(xh[:n, :d], dense, rtol=2e-2, atol=2e-2)

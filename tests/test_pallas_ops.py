"""Pallas kernel tests (interpret mode on the virtual CPU mesh).

The fused k-means stats kernel is checked against a plain-XLA reference;
the ring allreduce runs under shard_map on the 8-device CPU mesh via the
distributed TPU interpreter and is checked against psum/pmax.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from rabit_tpu.ops import ReduceOp
from rabit_tpu.ops.kmeans_kernel import kmeans_stats_fused
from rabit_tpu.ops.ring_allreduce import ring_allreduce_pallas


def _xla_stats(centroids, x, valid):
    cn = centroids / (np.linalg.norm(centroids, axis=1, keepdims=True)
                      + 1e-12)
    sim = x @ cn.T
    assign = sim.argmax(axis=1)
    k = centroids.shape[0]
    onehot = np.zeros((x.shape[0], k), np.float32)
    onehot[np.arange(x.shape[0]), assign] = valid
    sums = onehot.T @ x
    counts = onehot.sum(axis=0)
    return np.concatenate([sums, counts[:, None]], axis=1)


@pytest.mark.parametrize("n,d,k", [(512, 256, 64), (300, 100, 10)])
def test_kmeans_stats_fused_matches_xla(n, d, k):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, d)).astype(np.float32)
    cent = rng.standard_normal((k, d)).astype(np.float32)
    valid = (rng.random(n) > 0.1).astype(np.float32)

    got = np.asarray(kmeans_stats_fused(
        jnp.asarray(cent), jnp.asarray(x), jnp.asarray(valid), block=256))
    want = _xla_stats(cent, x, valid)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_kmeans_stats_fused_all_negative_sim():
    # all similarities negative: padded zero-centroids must not win
    rng = np.random.default_rng(1)
    d, k, n = 100, 3, 64
    cent = np.abs(rng.standard_normal((k, d))).astype(np.float32)
    x = -np.abs(rng.standard_normal((n, d))).astype(np.float32)
    valid = np.ones(n, np.float32)
    got = np.asarray(kmeans_stats_fused(
        jnp.asarray(cent), jnp.asarray(x), jnp.asarray(valid), block=64))
    want = _xla_stats(cent, x, valid)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert got[:, -1].sum() == n  # every point assigned to a real cluster


def _mesh(ndev):
    return Mesh(np.array(jax.devices()[:ndev]), ("x",))


@pytest.mark.parametrize("ndev,size,op", [
    (4, 4 * 128, ReduceOp.SUM),
    (4, 1000, ReduceOp.SUM),       # non-aligned, padded
    (8, 2048, ReduceOp.MAX),
    (2, 257, ReduceOp.MIN),
])
def test_ring_allreduce_pallas(ndev, size, op):
    if len(jax.devices()) < ndev:
        pytest.skip("not enough virtual devices")
    mesh = _mesh(ndev)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((ndev, size)).astype(np.float32)

    def fn(shard):
        return ring_allreduce_pallas(shard[0], "x", op=op,
                                     interpret=True)[None]

    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                              out_specs=P("x"), check_vma=False))
    out = np.asarray(f(x))
    red = {ReduceOp.SUM: np.sum, ReduceOp.MAX: np.max,
           ReduceOp.MIN: np.min}[op]
    want = red(x, axis=0)
    for i in range(ndev):
        np.testing.assert_allclose(out[i], want, rtol=1e-5, atol=1e-5)


def test_ring_allreduce_world1():
    mesh = _mesh(1)
    x = jnp.arange(64, dtype=jnp.float32)

    def fn(shard):
        return ring_allreduce_pallas(shard, "x", interpret=True)

    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(),
                              out_specs=P(), check_vma=False))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


def test_ring_allreduce_2d_shape():
    ndev = 4
    if len(jax.devices()) < ndev:
        pytest.skip("not enough virtual devices")
    mesh = _mesh(ndev)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((ndev, 17, 9)).astype(np.float32)

    def fn(shard):
        return ring_allreduce_pallas(shard[0], "x", interpret=True)[None]

    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                              out_specs=P("x"), check_vma=False))
    out = np.asarray(f(x))
    want = x.sum(axis=0)
    for i in range(ndev):
        np.testing.assert_allclose(out[i], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.MAX, ReduceOp.MIN,
                                ReduceOp.PROD])
def test_ring_allreduce_pallas_bit_equal_psum_world8(op):
    """The production routing contract (rabit_device_impl=pallas_ring):
    at world 8 the kernel's result is BIT-equal to the psum lowering for
    every supported op.  Bitwise, not allclose: the ring combines in a
    fixed rank order and XLA's allreduce must agree exactly for the
    engine to treat the two lowerings as interchangeable — float sums
    are kept associativity-safe by using values with exact float32
    representations."""
    ndev = 8
    if len(jax.devices()) < ndev:
        pytest.skip("not enough virtual devices")
    from rabit_tpu.ops import apply_op_jax

    mesh = _mesh(ndev)
    rng = np.random.default_rng(11)
    # integers in float32: every partial result is exact, so any
    # combining order yields the same bits
    x = rng.integers(-32, 33, size=(ndev, 1000)).astype(np.float32)
    if op == ReduceOp.PROD:
        x = rng.choice(np.array([0.5, 1.0, 2.0], np.float32),
                       size=(ndev, 1000))

    def ring_fn(shard):
        return ring_allreduce_pallas(shard[0], "x", op=op,
                                     interpret=True)[None]

    def psum_fn(shard):
        return apply_op_jax(op, shard[0], "x")[None]

    ring = jax.jit(jax.shard_map(ring_fn, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x"), check_vma=False))
    psum = jax.jit(jax.shard_map(psum_fn, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x")))
    got = np.asarray(ring(x))
    want = np.asarray(psum(x))
    np.testing.assert_array_equal(got, want)


def _ell_to_dense(idx, val, d):
    n = idx.shape[0]
    dense = np.zeros((n, d + 1), np.float32)
    np.add.at(dense, (np.arange(n)[:, None], idx), val)
    return dense[:, :d]


@pytest.mark.parametrize("n,d,k,nnz", [(4096, 512, 64, 32),
                                       (2048, 384, 10, 16)])
def test_kmeans_ell_stats_fused_matches_xla(n, d, k, nnz):
    """The fused two-level ELL kernel must agree with the dense oracle
    (float32 compute keeps the comparison exact-ish)."""
    from rabit_tpu.ops.kmeans_kernel import kmeans_ell_stats_fused

    rng = np.random.default_rng(1)
    idx = rng.integers(0, d, (n, nnz)).astype(np.int32)
    val = rng.standard_normal((n, nnz)).astype(np.float32)
    # sprinkle pad slots (index d, value 0) like to_ell emits
    pad = rng.random((n, nnz)) < 0.2
    idx[pad] = d
    val[pad] = 0.0
    valid = (rng.random(n) > 0.1).astype(np.float32)

    # pad features to a multiple of hi=128 the way prepare_shard does
    d_pad = -(-(d + 1) // 128) * 128
    cent = rng.standard_normal((k, d)).astype(np.float32)
    cent_p = np.pad(cent, ((0, 0), (0, d_pad - d)))

    got = np.asarray(kmeans_ell_stats_fused(
        jnp.asarray(cent_p), jnp.asarray(idx), jnp.asarray(val),
        jnp.asarray(valid), d_pad, group=8, hi=128, block=512,
        compute_dtype=jnp.float32))
    got = np.concatenate([got[:, :d], got[:, -1:]], axis=1)

    dense = _ell_to_dense(idx, val, d)
    want = _xla_stats(cent, dense, valid)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


def test_kmeans_ell_stats_fused_validation():
    from rabit_tpu.ops.kmeans_kernel import kmeans_ell_stats_fused

    cent = jnp.zeros((8, 256))
    idx = jnp.zeros((512, 24), jnp.int32)  # nnz not a power of two
    val = jnp.zeros((512, 24))
    with pytest.raises(ValueError, match="powers of two"):
        kmeans_ell_stats_fused(cent, idx, val, jnp.ones(512), 256,
                               hi=128, block=512)


def test_prepare_shard_ell_fused_path(monkeypatch):
    """On a (faked) TPU backend an over-budget shard takes the fused
    path with slot/row padding, and its stats match the scan path."""
    import jax as _jax

    from rabit_tpu.learn import kmeans as km

    rng = np.random.default_rng(2)
    n, d, nnz, k = 3000, 200, 24, 8
    # well-separated clusters: each row's slots live in its cluster's
    # feature band, so bf16 similarity rounding cannot flip assignments
    owner = rng.integers(0, k, n)
    band = d // k
    idx = (owner[:, None] * band
           + rng.integers(0, band, (n, nnz))).astype(np.int32)
    val = (1.0 + rng.random((n, nnz))).astype(np.float32)
    valid = np.ones(n, np.float32)

    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    # the fused ELL tier asks what the device holds (over that a shard
    # streams), and a CPU passed off for a TPU reports nothing: a v5e's
    monkeypatch.setattr(km, "_dense16_budget", lambda: 14795669056)
    shard = km.prepare_shard(idx, val, valid, d, budget=0)
    assert shard[0] == "ell_fused"
    di, dv, dvl, d_pad, nnz_p = shard[2]
    # grouped layout: (n/G, G*nnz_pow2) — the minor dim tiles the 128
    # lanes exactly instead of padding 4x
    assert nnz_p == 32 and di.shape[1] == km._ELL_FUSED_GROUP * 32
    assert (di.shape[0] * km._ELL_FUSED_GROUP) % 2048 == 0
    assert d_pad % 128 == 0

    # centroids aligned with the feature bands (robust assignments)
    cent = np.zeros((k, d), np.float32)
    for j in range(k):
        cent[j, j * band:(j + 1) * band] = 1.0
    model = km.KMeansModel(cent)
    model.normalize()
    # interpret mode (CPU): force it since default_backend is faked
    import rabit_tpu.ops.kmeans_kernel as kk
    orig = kk.kmeans_ell_stats_fused

    def interp(*a, **kw):
        kw["interpret"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(kk, "kmeans_ell_stats_fused", interp)
    got = np.asarray(km.shard_stats_device(model.centroids, shard))

    dense = _ell_to_dense(idx, val, d)
    want = _xla_stats(model.centroids, dense, valid)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-1)

"""Data from the seed, and the plain reference against brute force."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.learners import kmeans as adapter  # noqa: E402
from perfbench.reference import kmeans as ref  # noqa: E402

CFG = {"dim": 64, "k": 8, "nnz": 16, "rows_per_chip": 4096}
BIG_SEED = 2 ** 31 + 12345


def data(seed=7, rank=0, world=1, threads=2, rows=None, grid=None, cfg=CFG):
    return adapter.make_data(cfg, seed, rank, world, threads, rows, grid)


def dense(d, val=None):
    out = np.zeros((d.n, d.dim), np.float64)
    rows = np.repeat(np.arange(d.n), d.nnz)
    np.add.at(out, (rows, d.idx.reshape(-1)),
              (d.val if val is None else val).reshape(-1))
    return out


def brute_force(d, iters):
    """Cosine k-means in float64 numpy, row by row semantics."""
    x = dense(d)
    cent = np.zeros((d.k, d.dim))
    for i, (idx, val) in enumerate(d.init_rows()):
        np.add.at(cent[i], idx, val)
    cent /= np.linalg.norm(cent, axis=1, keepdims=True)
    for _ in range(iters):
        assign = np.argmax(x @ cent.T, axis=1)
        for c in range(d.k):
            if (assign == c).any():
                cent[c] = x[assign == c].mean(axis=0)
        cent /= np.linalg.norm(cent, axis=1, keepdims=True)
    return cent, assign


def test_same_seed_same_rows_whatever_the_threads():
    a, b = data(threads=1), data(threads=4)
    assert np.array_equal(a.idx, b.idx) and np.array_equal(a.val, b.val)


def test_seed_changes_the_data_not_the_shapes():
    a, b = data(seed=7), data(seed=8)
    assert a.idx.shape == b.idx.shape == (4096, 16)
    assert a.val.dtype == b.val.dtype == np.float32
    assert not np.array_equal(a.val, b.val)
    assert a.picks != b.picks


def test_a_seed_past_32_signed_bits():
    d = data(seed=BIG_SEED)
    assert d.seed == BIG_SEED and np.isfinite(d.val).all()


def test_ranks_hold_different_shards_and_the_same_picks():
    a, b = data(rank=0, world=4), data(rank=1, world=4)
    assert a.picks == b.picks and a.roots == b.roots
    assert not np.array_equal(a.val, b.val)
    assert set(a.roots) <= set(range(4))


def test_indices_in_range_and_signal_in_the_clusters_band():
    d = data()
    assert d.idx.min() >= 0 and d.idx.max() < d.dim
    band = d.dim // d.k
    cluster = np.arange(d.n) % d.k
    cluster[d.picks] = np.arange(d.k)
    signal = d.idx[:, :adapter.SIGNAL_SLOTS] // band
    assert (signal == cluster[:, None]).all()
    assert (d.val[:, :8] >= 1).all() and (d.val[:, :8] <= 2).all()
    assert np.abs(d.val[:, 8:]).max() <= 0.17


def test_init_rows_are_rebuilt_without_the_shard():
    for rank in range(2):
        d = data(rank=rank, world=2)
        for i, (idx, val) in enumerate(d.init_rows()):
            if d.roots[i] == rank:
                assert np.array_equal(idx, d.idx[d.picks[i]])
                assert np.array_equal(val, d.val[d.picks[i]])


def test_job_seed_steps_past_a_seed_that_draws_a_row_twice():
    # with 9 rows and 8 clusters nearly every seed draws one twice
    n, k = 9, 8
    seeds = [adapter.job_seed(s, n, k, 1) for s in range(20)]
    for s in seeds:
        assert len(set(ref.init_draws(s, n, k, 1)[0])) == k
    assert any(s != i for i, s in enumerate(seeds))
    assert adapter.job_seed(seeds[0], n, k, 1) == seeds[0]


def test_grid_rounds_what_the_job_sees_and_not_what_the_reference_sees():
    d = data(grid="float8_e4m3fn")
    assert not np.array_equal(d.val, d.val_run)
    # e4m3 keeps 3 bits of mantissa: at most 2^-4 relative
    sig = slice(0, adapter.SIGNAL_SLOTS)          # values in [1, 2)
    rel = np.abs(d.val_run[:, sig] - d.val[:, sig]) / d.val[:, sig]
    assert 0.01 < rel.max() <= 2 ** -4 + 1e-6
    mat = d.sparse_mat()
    assert np.array_equal(mat.fvalue, d.val_run.reshape(-1))
    assert mat.num_row == d.n and mat.feat_dim == d.dim
    plain = data()
    assert plain.val_run is plain.val


def test_sparse_mat_is_a_view_of_the_rows():
    d = data()
    mat = d.sparse_mat()
    assert np.shares_memory(mat.findex, d.idx)
    assert np.array_equal(np.diff(mat.indptr), np.full(d.n, d.nnz))


def test_reference_equals_brute_force_and_reaches_its_fixed_point():
    d = data()
    shard = ref.ShardStats(d.idx, d.val, d.dim, d.k, block=1024)
    cents = ref.run(shard, ref.init_centroids(d.init_rows(), d.dim), 3,
                    lambda it, s, c: (s, c))
    want, assign = brute_force(d, 3)
    assert ref.rel_err(cents[-1], want) < 1e-6
    assert ref.rel_err(cents[-1], cents[-2]) == 0.0
    assert len(set(assign)) == d.k


def test_reference_combines_ranks_as_one_job():
    """Two shards, stats summed across them, equal one shard holding
    both."""
    a, b = data(rank=0, world=2), data(rank=1, world=2)
    sa = ref.ShardStats(a.idx, a.val, a.dim, a.k, block=1024)
    sb = ref.ShardStats(b.idx, b.val, b.dim, b.k, block=1024)
    cent = ref.init_centroids(a.init_rows(), a.dim)
    for _ in range(2):
        (s1, c1), (s2, c2) = sa(cent), sb(cent)
        cent = ref.update(cent, s1 + s2, c1 + c2)
    both = ref.ShardStats(np.concatenate([a.idx, b.idx]),
                          np.concatenate([a.val, b.val]), a.dim, a.k,
                          block=1024)
    one = ref.init_centroids(a.init_rows(), a.dim)
    for _ in range(2):
        one = ref.update(one, *both(one))
    assert ref.rel_err(cent, one) < 1e-6


def test_rel_err_is_not_finite_for_a_broken_answer():
    want = np.ones((2, 3), np.float32)
    assert ref.rel_err(want, want) == 0.0
    assert ref.rel_err(np.full((2, 3), np.nan, np.float32), want) == np.inf
    assert ref.rel_err(np.ones((3, 3), np.float32), want) == np.inf


def test_a_lower_precision_reads_far_above_the_stated_one():
    """The control of `correct` in small: rows on the 8-bit grid move
    the centroids an order of magnitude more than rows on the bf16
    grid (the precision both configurations state)."""
    d = data(rows=16384)
    shard = ref.ShardStats(d.idx, d.val, d.dim, d.k, block=1024)
    want = ref.run(shard, ref.init_centroids(d.init_rows(), d.dim), 3,
                   lambda it, s, c: (s, c))[-1]
    errs = {}
    for grid in ("bfloat16", "float8_e4m3fn"):
        x = dense(d, adapter.round_to_grid(d.val, grid))
        assign = np.argmax(x @ want.T.astype(np.float64), axis=1)
        cent = np.stack([x[assign == c].mean(axis=0) for c in range(d.k)])
        cent /= np.linalg.norm(cent, axis=1, keepdims=True)
        errs[grid] = ref.rel_err(cent.astype(np.float32), want)
    assert errs["float8_e4m3fn"] > 8 * errs["bfloat16"], errs


def test_describe_says_what_a_version_is():
    cfg = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "kmeans-dense-d256-k64.json")))
    d = data(rows=4096, world=4, cfg={**cfg})
    chained = adapter.describe(cfg, {"device_chain": 8}, d)
    assert chained["work_per_version"] == 8 * 4 * 4096
    assert chained["kernel_shape"]["row_itemsize"] == 2
    assert adapter.describe(cfg, {"device_chain": 0},
                            d)["work_per_version"] == 4 * 4096

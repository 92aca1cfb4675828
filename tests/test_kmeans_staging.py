"""What ``kmeans.run`` does to a shard on its way to the device: a range
check in place of the clamp's copies, the ELL tier's mask only where an
index reaches ``feat_dim``, and nothing written into the caller's
arrays.  All on the CPU; the fused ELL tier is staged (``on_tpu``
steered here) but its kernel is not run."""
import jax
import numpy as np
import pytest

import rabit_tpu
from rabit_tpu.learn import kmeans
from rabit_tpu.learn.data import SparseMat
from rabit_tpu.obs import program

K, DIM, NNZ, ROWS = 4, 64, 8, 2048
NARROW = 48                 # a model three bands of 16 wide

# tier -> (compute_dtype, the budget prepare_shard is held to, on_tpu)
TIERS = {
    "dense": ("float32", None, False),
    "dense16": ("bfloat16", 0, False),
    "ell": ("float32", 0, False),
    "ell_fused": ("float32", 0, True),
}


V5E_BYTES = 16909336064      # what the chip tool's v5e reports
V5E_BUDGET = V5E_BYTES - (V5E_BYTES >> 3)


@pytest.fixture(autouse=True)
def v5e_budget(monkeypatch):
    """The fused ELL tier asks the device what it holds (over that a
    shard streams), and a CPU passed off for a TPU reports nothing."""
    monkeypatch.setattr(kmeans, "_dense16_budget", lambda: V5E_BUDGET)


@pytest.fixture
def table():
    program.reset()
    yield program
    program.reset()


def rows(n=ROWS, nnz=NNZ, top=DIM, seed=0, dtype=np.int32):
    """Uniform rows in K clusters: cluster c owns the band
    [12c, 12c + 12), so all signal lies under NARROW; the last slot of
    every row is noise anywhere in [0, top)."""
    rng = np.random.default_rng(seed)
    cluster = np.arange(n) % K
    findex = cluster[:, None] * 12 + rng.integers(0, 12, (n, nnz))
    findex[:, -1] = rng.integers(0, top, n)
    fvalue = (1.0 + rng.random((n, nnz))).astype(np.float32)
    fvalue[:, -1] *= 0.1
    return findex.astype(dtype).reshape(-1), fvalue.reshape(-1)


def sparse(findex, fvalue, feat_dim, nnz=NNZ):
    n = len(findex) // nnz
    return SparseMat(indptr=np.arange(0, n * nnz + 1, nnz, dtype=np.int64),
                     findex=findex, fvalue=fvalue,
                     labels=np.zeros(n, np.float32), feat_dim=feat_dim)


def band_centroids(width):
    cent = np.zeros((K, width), np.float32)
    for c in range(K):
        cent[c, 12 * c:12 * c + 12] = 1.0
    model = kmeans.KMeansModel(cent)
    model.normalize()
    return model


class Staging:
    """``prepare_shard`` wrapped: the arguments of every call and what
    it staged; the tier's budget and ``on_tpu`` steered."""

    def __init__(self, monkeypatch, tier):
        self.calls, self.staged = [], []
        self.dtype, budget, tpu = TIERS[tier]
        stage = kmeans.prepare_shard

        def prepare_shard(*a, **kw):
            if budget is not None:
                kw["budget"] = budget
            self.calls.append((a, dict(kw)))
            self.staged.append(stage(*a, **kw))
            return self.staged[-1]

        monkeypatch.setattr(kmeans, "prepare_shard", prepare_shard)
        monkeypatch.setattr(kmeans, "on_tpu", lambda: tpu)


def fresh_engine():
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")


def host_arrays(shard):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(shard[2])]


# ------------------------------------------ (a) in range: nothing copied
@pytest.mark.parametrize("tier", list(TIERS))
def test_in_range_shard_is_staged_from_the_callers_own_arrays(
        table, empty_engine, monkeypatch, tier):
    findex, fvalue = rows()
    before = findex.copy(), fvalue.copy()
    seen = Staging(monkeypatch, tier)
    # the fused ELL kernel does not run on a CPU: stage, iterate nowhere
    kmeans.run(sparse(findex, fvalue, DIM), K, 0 if tier == "ell_fused"
               else 2, compute_dtype=seen.dtype)
    (args, kw), = seen.calls
    idx, val = args[0], args[1]
    assert seen.staged[0][0] == tier
    assert idx.dtype == np.int32 and val.dtype == np.float32
    assert np.shares_memory(idx, findex) and np.shares_memory(val, fvalue)
    assert kw["max_index"] == findex.max() < DIM
    s = program.stats()
    assert s["stage.clamped"] == 0 and s["stage.clamp.n"] == 1
    np.testing.assert_array_equal(findex, before[0])
    np.testing.assert_array_equal(fvalue, before[1])


# ------------------------------------- (b) out of range: today's clamp
@pytest.mark.parametrize("chain", [0, 2], ids=["periter", "chain2"])
@pytest.mark.parametrize("case", ["restored", "understated"])
def test_out_of_range_shard_is_clamped_as_the_copy_clamped_it(
        table, empty_engine, monkeypatch, case, chain):
    """A model narrower than the shard's indices, restored from a
    checkpoint or started from a ``SparseMat`` that understates its
    ``feat_dim``: the clamp runs, on a copy, and commits what a shard
    clamped by ``np.minimum`` beforehand commits."""
    findex, fvalue = rows()
    assert findex.max() > NARROW
    before = findex.copy(), fvalue.copy()
    if case == "restored":
        monkeypatch.setattr(rabit_tpu, "load_checkpoint",
                            lambda: (1, band_centroids(NARROW)))
        claimed = DIM
    else:
        # init would index past the width it is given
        monkeypatch.setattr(kmeans, "init_centroids",
                            lambda *a, **kw: band_centroids(NARROW))
        claimed = NARROW

    def run(fi):
        seen = Staging(monkeypatch, "dense")
        model = kmeans.run(sparse(fi, fvalue, claimed), K, 4,
                           device_chain=chain)
        return model, seen.calls[0]

    got, ((idx, val, *_), kw) = run(findex)
    assert program.stats()["stage.clamped"] == 1
    assert idx.max() == kw["max_index"] == NARROW
    assert not np.shares_memory(idx, findex)
    assert np.shares_memory(val, fvalue)
    np.testing.assert_array_equal(findex, before[0])
    np.testing.assert_array_equal(fvalue, before[1])
    assert got.centroids.shape == (K, NARROW)

    program.reset()
    fresh_engine()
    want, ((idx, *_), kw) = run(np.minimum(findex, NARROW))
    assert program.stats()["stage.clamped"] == 0
    assert kw["max_index"] == NARROW
    np.testing.assert_array_equal(got.centroids, want.centroids)


# --------------------------- (c) the fused ELL tier's d_pad and its mask
def ell_case(name):
    """idx, val of 2,048 rows and what the tier must make of them at
    ``feat_dim`` 128: (d_pad, whether the mask may be built)."""
    rng = np.random.default_rng(3)
    nnz = 6 if name == "slots_padded" else NNZ
    idx = rng.integers(0, 128, (ROWS, nnz)).astype(np.int32)
    val = (1.0 + rng.random((ROWS, nnz))).astype(np.float32)
    if name in ("in_range", "slots_padded"):
        return idx, val, 128, False
    idx[::7, -1] = 128                   # pad slots, or clamped features
    if name == "pads_hold_zeros":
        val[::7, -1] = 0.0
        return idx, val, 128, True
    assert name == "clamped_holds_values"
    return idx, val, 256, True


@pytest.mark.parametrize("passed", [True, False], ids=["passed", "taken"])
@pytest.mark.parametrize("name", ["in_range", "slots_padded",
                                  "pads_hold_zeros", "clamped_holds_values"])
def test_ell_fused_builds_its_mask_only_where_an_index_reaches_feat_dim(
        monkeypatch, name, passed):
    idx, val, d_pad, may_mask = ell_case(name)
    monkeypatch.setattr(kmeans, "on_tpu", lambda: True)
    masks, np_any = [], np.any

    def spy(a, *args, **kw):
        masks.append(np.shape(a))
        return np_any(a, *args, **kw)

    with monkeypatch.context() as patched:
        patched.setattr(np, "any", spy)
        shard = kmeans.prepare_shard(
            idx, val, np.ones(ROWS, np.float32), 128, budget=0,
            max_index=int(idx.max()) if passed else None)
    assert shard[0] == "ell_fused"
    idx_g, val_g, _valid, got_d_pad, nnz_p = shard[2]
    assert (got_d_pad, nnz_p) == (d_pad, NNZ)
    assert bool(masks) == may_mask
    assert idx_g.dtype == np.int32 and val_g.dtype == np.float32
    flat = np.asarray(idx_g).reshape(ROWS, NNZ)
    np.testing.assert_array_equal(flat[:, :idx.shape[1]], idx)
    assert (flat[:, idx.shape[1]:] == 128).all()


@pytest.mark.parametrize("chunk_rows", [1 << 20, 1024, 800],
                         ids=["one_slice", "even_slices", "short_tail"])
def test_ell_fused_lands_its_slices_where_one_put_would(
        monkeypatch, chunk_rows):
    """The grouped arrays go to the device a slice at a time; whole
    slices or a shorter last one, the device holds the host's bytes."""
    idx, val, _d_pad, _ = ell_case("clamped_holds_values")
    monkeypatch.setattr(kmeans, "on_tpu", lambda: True)
    monkeypatch.setattr(kmeans, "_STAGE_CHUNK_ROWS", chunk_rows)
    _tier, _d, (idx_g, val_g, valid, _d_pad, _nnz) = kmeans.prepare_shard(
        idx, val, np.ones(ROWS, np.float32), 128, budget=0)
    g = kmeans._ELL_FUSED_GROUP
    assert idx_g.shape == val_g.shape == (ROWS // g, g * NNZ)
    np.testing.assert_array_equal(np.asarray(idx_g).reshape(ROWS, NNZ), idx)
    np.testing.assert_array_equal(np.asarray(val_g).reshape(ROWS, NNZ), val)
    assert np.asarray(valid).all()


# ----------------------------------------------- (d) int64 indices
@pytest.mark.parametrize("top,clamped", [(NARROW, 0), (DIM, 1)],
                         ids=["in_range", "clamped"])
def test_int64_findex_still_stages_as_int32(
        table, empty_engine, monkeypatch, top, clamped):
    monkeypatch.setattr(kmeans, "init_centroids",
                        lambda *a, **kw: band_centroids(NARROW))
    findex, fvalue = rows(top=top)
    models = []
    for dtype in (np.int64, np.int32):
        seen = Staging(monkeypatch, "ell")
        models.append(kmeans.run(
            sparse(findex.astype(dtype), fvalue, NARROW), K, 2))
        (idx, *_), _kw = seen.calls[0]
        assert idx.dtype == np.int32
        assert np.asarray(seen.staged[0][2][0]).dtype == np.int32
        assert program.stats()["stage.clamped"] == clamped
        program.reset()
        fresh_engine()
    np.testing.assert_array_equal(models[0].centroids, models[1].centroids)


# ------------------------------------ (e) the re-formation's second call
@pytest.mark.parametrize("tier", list(TIERS))
def test_restaging_the_same_host_arrays_stages_the_same_shard(
        table, empty_engine, monkeypatch, tier):
    """The per-iteration loop stages again when the device plane was
    re-formed inside a commit: from the same host arrays, with the same
    maximum, to the same device arrays."""
    findex, fvalue = rows(top=DIM)       # out of range: the clamp's copy
    seen = Staging(monkeypatch, tier)
    if tier == "ell_fused":
        # its kernel does not run here: the two calls by hand
        host = (np.minimum(findex, NARROW).reshape(ROWS, NNZ),
                fvalue.reshape(ROWS, NNZ), np.ones(ROWS, np.float32))
        for _ in range(2):
            kmeans.prepare_shard(*host, NARROW, max_index=NARROW)
    else:
        epoch, commit = [0], rabit_tpu.checkpoint

        def checkpoint(model):
            commit(model)
            epoch[0] += rabit_tpu.version_number() == 1

        monkeypatch.setattr(kmeans, "init_centroids",
                            lambda *a, **kw: band_centroids(NARROW))
        monkeypatch.setattr(rabit_tpu, "checkpoint", checkpoint)
        monkeypatch.setattr(rabit_tpu, "device_epoch", lambda: epoch[0])
        kmeans.run(sparse(findex, fvalue, NARROW), K, 3,
                   compute_dtype=seen.dtype)
        assert program.stats()["stage.clamped"] == 1
        assert program.stats()["stage.clamp.n"] == 1
    (first, kw1), (again, kw2) = seen.calls
    assert all(a is b for a, b in zip(first[:3], again[:3]))
    assert kw1 == kw2 and kw1["max_index"] == NARROW
    one, two = seen.staged
    assert one[:2] == two[:2] == (tier, NARROW)
    for a, b in zip(host_arrays(one), host_arrays(two), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


# --------------------------- (f) the tier rule at the benchmark's shapes
def kmeans_cells():
    """(cell, rows, dim, nnz, compute_dtype, tier) of every cell of
    BENCHMARK.json whose configuration is k-means', by its file; and
    the two shapes of tools/big_kmeans.py."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    out = [("big_kmeans-sparse", 50_000_000, 512, 32, "float32", "ell_fused"),
           ("big_kmeans-dense", 24_117_248, 256, 32, "bfloat16", "dense16")]
    for w in manifest["workloads"]:
        cfg = json.load(open(os.path.join(root, files[w["config"]])))
        if cfg["learner"].startswith("kmeans"):
            out.append((w["name"], cfg["rows_per_chip"], cfg["dim"],
                        cfg["nnz"], cfg["compute_dtype"], cfg["tier"]))
    return out


@pytest.mark.parametrize("cell,n,dim,nnz,dtype,tier", kmeans_cells(),
                         ids=[c[0] for c in kmeans_cells()])
def test_a_cells_shard_lands_in_the_tier_its_configuration_names(
        monkeypatch, cell, n, dim, nnz, dtype, tier):
    """At the budget a v5e reports, by the rule alone (nothing staged):
    the shards that were resident before the streamed tier still are,
    and the one that is over the budget streams."""
    monkeypatch.setattr(kmeans, "on_tpu", lambda: True)
    assert kmeans._tier(n, nnz, dim, kmeans.DENSIFY_BUDGET_BYTES,
                        dtype) == tier
    whole = n * (nnz * 8 + 4)
    budget = kmeans._stream_budget()
    assert abs(budget - V5E_BYTES * 15 // 16) < 16      # 15/16 of the chip
    assert (whole > budget) == (tier == "ell_stream")
    if tier == "ell_stream":
        # what the rule keeps resident beside the ring
        chunk = kmeans._STAGE_CHUNK_ROWS * (nnz * 8 + 4)
        assert (budget - kmeans._STREAM_RING * chunk) // chunk == 55


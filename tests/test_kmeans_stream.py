"""k-means on a shard larger than the device: what fits is resident, the
rest streams from the host every iteration (``kmeans.prepare_shard``'s
tier ``ell_stream``).  All on the CPU at a small size: the fused ELL
kernel is interpreted, ``on_tpu`` is steered, and the tier is forced
through the budget the device reports (``_stream_budget``, patched
here as ``tests/perfbench/as_if_on_chip.py`` patches ``_dense16_budget``,
which it is read off)."""
import os
import sys

import jax
import numpy as np
import pytest

import rabit_tpu
import rabit_tpu.ops.kmeans_kernel as kk
from rabit_tpu.learn import kmeans
from rabit_tpu.learn.data import SparseMat
from rabit_tpu.obs import program
from rabit_tpu.utils.checks import RabitError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402
from perfbench.reference import kmeans as ref  # noqa: E402

K, DIM, NNZ = 4, 128, 16
BLOCK, CHUNK = 256, 512             # the kernel's row block, a chunk's rows
CHUNK_BYTES = CHUNK * (NNZ * 8 + 4)
CHUNKS = 10
WHOLE, RAGGED = CHUNKS * CHUNK, (CHUNKS - 1) * CHUNK + BLOCK    # rows

adapter = harness.load_module(os.path.join(
    ROOT, "perfbench", "learners", "kmeans.py"))


@pytest.fixture
def table():
    program.reset()
    yield program
    program.reset()


@pytest.fixture
def steered(monkeypatch):
    """The chip's arm of ``prepare_shard`` on the CPU; returns the
    function that sets the budget to hold ``resident`` chunks beside
    the ring."""
    fused = kk.kmeans_ell_stats_fused
    monkeypatch.setattr(kk, "kmeans_ell_stats_fused",
                        lambda *a, **kw: fused(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kmeans, "on_tpu", lambda: True)
    monkeypatch.setattr(kmeans, "_ELL_FUSED_BLOCK", BLOCK)
    monkeypatch.setattr(kmeans, "_STAGE_CHUNK_ROWS", CHUNK)
    stage = kmeans.prepare_shard
    monkeypatch.setattr(kmeans, "prepare_shard",
                        lambda *a, **kw: stage(*a, **{**kw, "budget": 0}))

    def hold(resident: int) -> None:
        monkeypatch.setattr(
            kmeans, "_stream_budget",
            lambda: (resident + kmeans._STREAM_RING) * CHUNK_BYTES)

    return hold


def rows(n, seed=7):
    """The benchmark's clustered rows (``learners/kmeans.py make_rows``)
    and the seeded draws its centroids start from."""
    picks, _roots = ref.init_draws(seed, n, K, 1)
    idx, val = adapter.make_rows(seed, 0, n, DIM, K, NNZ, picks, 2)
    return idx, val, picks


def sparse(idx, val):
    n = len(idx)
    return SparseMat(indptr=np.arange(0, n * NNZ + 1, NNZ, dtype=np.int64),
                     findex=idx.reshape(-1), fvalue=val.reshape(-1),
                     labels=np.zeros(n, np.float32), feat_dim=DIM)


def fresh_engine():
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")


def all_resident(idx, val, valid, monkeypatch):
    """The same chunks, every one of them resident: what no budget
    gives by the rule (a shard that fits is staged whole)."""
    monkeypatch.setattr(kmeans, "_stream_budget", lambda: 1 << 40)
    payload = kmeans._stage_stream(idx, val, valid, DIM, NNZ, DIM)
    assert len(payload[0]) == len(payload[1]) and not payload[4].order
    return ("ell_stream", DIM, payload)


# ------------------------- (a) streamed against all-resident, bit for bit
@pytest.mark.parametrize("n", [WHOLE, RAGGED], ids=["whole", "ragged_tail"])
@pytest.mark.parametrize("resident", [0, 1, 3, 6])
def test_a_version_is_the_same_bits_whichever_chunks_are_resident(
        table, steered, monkeypatch, resident, n):
    idx, val, _ = rows(n)
    valid = np.ones(n, np.float32)
    before = idx.copy(), val.copy()
    cent = np.random.default_rng(1).random((K, DIM)).astype(np.float32)
    steered(resident)
    shard = kmeans.prepare_shard(idx, val, valid, DIM)
    kind, _dim, (on_device, host, _d_pad, _nnz, stream) = shard
    assert kind == "ell_stream" and len(host) == CHUNKS
    assert len(on_device) == resident
    assert sorted(set(on_device) | set(stream.order)) == list(range(CHUNKS))
    assert CHUNKS - 1 in stream.order         # the ragged one streams
    # a chunk of the caller's rows is a view; only a ragged tail is made
    for c, (ci, cv, _cvalid) in enumerate(host):
        whole = (c + 1) * CHUNK <= n
        assert np.shares_memory(ci, idx) == whole
        assert np.shares_memory(cv, val) == whole
    got = [np.asarray(kmeans.shard_stats_device(cent, shard))
           for _ in range(2)]                 # the ring wraps a pass's end
    s = program.stats()
    assert s["stream.chunks"] == 2 * (CHUNKS - resident)
    assert s["stream.rows"] == 2 * (n - resident * CHUNK)
    assert s["stage.resident_rows"] == resident * CHUNK
    assert s["stream.inflight_max"] <= kmeans._STREAM_RING
    assert s["learn.stream.n"] == 2 and s["stage.host_chunks"] == CHUNKS
    want = np.asarray(kmeans.shard_stats_device(
        cent, all_resident(idx, val, valid, monkeypatch)))
    assert want[:, -1].sum() == n
    for g in got:
        np.testing.assert_array_equal(g, want)
    np.testing.assert_array_equal(idx, before[0])     # read, never written
    np.testing.assert_array_equal(val, before[1])


def test_slots_that_are_no_power_of_two_are_padded_once(table, steered):
    """12 slots a row: the kernel splits indices with shifts, so the
    shard is padded to 16 (a copy the data forces, as in the resident
    tier) and streams all the same."""
    idx, val, _ = rows(WHOLE)
    idx, val = idx[:, :12].copy(), val[:, :12].copy()
    valid = np.ones(WHOLE, np.float32)
    cent = np.random.default_rng(1).random((K, DIM)).astype(np.float32)
    steered(2)
    shard = kmeans.prepare_shard(idx, val, valid, DIM)
    assert shard[0] == "ell_stream" and shard[2][3] == 16
    got = np.asarray(kmeans.shard_stats_device(cent, shard))
    pad = np.full((WHOLE, 4), DIM, np.int32)
    want = np.asarray(kk.kmeans_ell_stats_fused(
        jax.numpy.asarray(cent), np.concatenate([idx, pad], axis=1),
        np.concatenate([val, np.zeros((WHOLE, 4), np.float32)], axis=1),
        valid, DIM, block=BLOCK))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert got[:, -1].sum() == WHOLE


# ------------------------------------------- (b) against the reference
@pytest.mark.parametrize("resident", [0, 2])
def test_the_streamed_job_agrees_with_the_plain_reference(
        table, empty_engine, steered, resident):
    """``kmeans.run`` through its normal path, three committed versions,
    against the benchmark's float32 reference on the same rows: within
    the sparse configuration's limit, counts exact."""
    import json

    n, seed = RAGGED + 3 * BLOCK, 11          # 11 chunks, no row padding
    idx, val, picks = rows(n, seed)
    steered(resident)
    model = kmeans.run(sparse(idx, val), K, 3, seed=seed, row_block=BLOCK)
    s = program.stats()
    assert s["learn.versions"] == 3 == rabit_tpu.version_number()
    assert s["learn.rows"] == 3 * n
    assert s["stream.rows"] == 3 * (n - resident * CHUNK)
    cfg = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "kmeans-sparse-d512-k64-r72m.json")))
    limit = cfg["correct"]["limits"]["centroid_err_x_sqrt_rows"]["limit"]
    shard = ref.ShardStats(idx, val, DIM, K, block=BLOCK)
    counts = []

    def combine(_it, sums, cnt):
        counts.append(cnt)
        return sums, cnt

    cents = ref.run(shard, ref.init_centroids(
        [adapter.pick_row(seed, 0, i, DIM, K, NNZ) for i in range(K)], DIM),
        3, combine)
    assert ref.rel_err(model.centroids, cents[-1]) * n ** 0.5 <= limit
    np.testing.assert_array_equal(
        counts[-1], np.bincount(adapter.cluster_of_rows(n, K, picks)))


# ------------------------------ (c) the host never runs ahead of the ring
def test_the_host_never_hands_over_more_than_the_ring_holds(
        table, empty_engine, steered, monkeypatch):
    """With the kernel call slowed (a chain of matrix products rides on
    every call and changes no bit of its result) the host could hand
    every chunk over at once: it waits for the ring instead.  Read off
    ``stream.inflight_max``, and seen for every hand-over by itself:
    when chunk j goes to the device, the call that read chunk j - ring
    has written its result."""
    import jax.numpy as jnp

    n, seed, versions, resident = WHOLE, 5, 3, 2
    idx, val, _ = rows(n, seed)
    steered(resident)
    quick = kmeans.run(sparse(idx, val), K, versions, seed=seed,
                       row_block=BLOCK).centroids
    quick_stats = program.stats()
    program.reset()
    fresh_engine()
    kmeans._STEP_CACHE.clear()

    fused = kk.kmeans_ell_stats_fused

    def slowed(*a, **kw):
        out = fused(*a, **kw)
        burn = jax.lax.fori_loop(
            0, 40, lambda _, m: jnp.tanh(m @ m + out[0, 0]),
            jnp.full((256, 256), 1e-3, jnp.float32))
        return jnp.where(burn[0, 0] > 2.0, out + 1.0, out)   # never

    results, late = [], []
    put, taken = kmeans._ChunkStream.hand_over, kmeans._ChunkStream.taken

    def hand_over(self, wait):
        j = len(results) + len(self.ahead)       # this hand-over's number
        did = put(self, wait)
        if did and j >= kmeans._STREAM_RING:
            late.append(results[j - kmeans._STREAM_RING].is_ready())
        return did

    def seen_taken(self, result):
        results.append(result)
        taken(self, result)

    monkeypatch.setattr(kk, "kmeans_ell_stats_fused", slowed)
    monkeypatch.setattr(kmeans._ChunkStream, "hand_over", hand_over)
    monkeypatch.setattr(kmeans._ChunkStream, "taken", seen_taken)
    try:
        slow = kmeans.run(sparse(idx, val), K, versions, seed=seed,
                          row_block=BLOCK).centroids
    finally:
        kmeans._STEP_CACHE.clear()
    np.testing.assert_array_equal(slow, quick)
    s = program.stats()
    ring, streamed = kmeans._STREAM_RING, CHUNKS - resident
    assert s["stream.inflight_max"] == ring
    assert quick_stats["stream.inflight_max"] <= ring
    # the host was held: it could have handed all of them over at once
    assert s["stream.wait.n"] >= versions * streamed // 2
    assert len(results) == versions * streamed == s["stream.chunks"]
    assert len(late) == versions * streamed      # + ring ahead, - ring first
    assert all(late)


# ----------------------------------- (e) a chain cannot take such a shard
@pytest.mark.parametrize("chain", [2, 8])
def test_a_chain_over_a_streamed_shard_is_refused_in_plain_words(
        table, empty_engine, steered, chain):
    idx, val, _ = rows(WHOLE)
    steered(1)
    with pytest.raises(RabitError, match="device_chain=0"):
        kmeans.run(sparse(idx, val), K, 4, device_chain=chain,
                   row_block=BLOCK)
    assert rabit_tpu.version_number() == 0
    # the same shard under a budget that holds it chains as before
    steered(1 << 20)
    fresh_engine()
    model = kmeans.run(sparse(idx, val), K, 4, device_chain=chain,
                       row_block=BLOCK)
    assert rabit_tpu.version_number() == 4 // chain + (4 % chain > 0)
    assert np.isfinite(model.centroids).all()


# ------------------- (d) a rank killed: only the resident part put again
def test_a_killed_rank_resumes_to_the_undisturbed_centroids(
        tmp_path, native_lib):
    """World 3 on the CPU device plane, every rank's shard streamed
    (tests/workers/kmeans_stream_xla.py steers the tier and asserts, in
    a survivor, that the re-staging put the resident chunks again and
    made no host chunk anew)."""
    from rabit_tpu.tracker.launch_local import launch

    world = 3
    cents = {}
    for name, die in (("undisturbed", {}),
                      ("reform", {"RABIT_KMEANS_DIE": "1:2"})):
        out = str(tmp_path / ("cent_" + name))
        code = launch(world, [sys.executable,
                              "tests/workers/kmeans_stream_xla.py", out],
                      extra_env={"RABIT_INNER": "native", **die},
                      watchdog_sec=20)
        assert code == 0
        cents[name] = np.load(out + ".npy")
    assert np.isfinite(cents["reform"]).all()
    np.testing.assert_array_equal(cents["reform"], cents["undisturbed"])

"""Distributed k-means (cosine distance) — the reference's flagship app.

Equivalent of reference: rabit-learn/kmeans/kmeans.cc, re-designed for TPU:
the per-iteration cluster-statistics pass is a single jitted XLA program —
``lax.scan`` over fixed-size row blocks, each block scatter-densified and
pushed through two MXU matmuls (similarity, then stats accumulation) —
instead of the reference's per-row sparse loop (kmeans.cc:126-140).
Cross-rank aggregation is one framework allreduce of the (k, d+1) stats
matrix (counts in the last column), and progress is committed with an
in-memory checkpoint every iteration, exactly the reference's structure
(kmeans.cc:141-156).
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field

import numpy as np

import rabit_tpu
from rabit_tpu.learn.data import (SparseMat, fetch, load_libsvm,
                                 save_matrix_txt)
from rabit_tpu.obs import program
from rabit_tpu.ops import MAX, SUM, on_tpu
from rabit_tpu.utils import compile_cache
from rabit_tpu.utils.checks import check

DEFAULT_ROW_BLOCK = 1024


@dataclass
class KMeansModel:
    """Centroid matrix; checkpointed by value (reference: kmeans.cc:11-46).

    ``hash_dim`` records the signed-hash width the centroids live in
    (None = original feature space).  It rides the checkpoint and the
    saved-model header so a resume or scoring run in a different space
    fails loudly instead of silently clamping features away.
    """

    centroids: np.ndarray = field(
        default_factory=lambda: np.zeros((0, 0), np.float32))
    hash_dim: int | None = None

    def normalize(self) -> None:
        """L2-normalize centroid rows (reference: Model::Normalize,
        kmeans.cc:31-45; rows with ~zero norm are left unscaled)."""
        norm = np.linalg.norm(self.centroids, axis=1, keepdims=True)
        scale = np.where(norm < 1e-6, 1.0, 1.0 / np.maximum(norm, 1e-30))
        self.centroids = (self.centroids * scale).astype(np.float32)


def save_model(model: KMeansModel, fname: str) -> None:
    """Write the centroid matrix; hashed-space models get a ``#``-comment
    header (skipped by ``np.loadtxt``) naming the hash width, so a scorer
    can't silently apply them in the wrong feature space."""
    header = (None if model.hash_dim is None
              else "rabit-kmeans hash_dim=%d" % model.hash_dim)
    save_matrix_txt(model.centroids, fname, header=header)


def init_centroids(data: SparseMat, num_cluster: int, feat_dim: int,
                   seed: int = 0) -> KMeansModel:
    """Seed centroids from random data rows, each broadcast from a random
    rank (reference: InitCentroids, kmeans.cc:47-60)."""
    rng = np.random.default_rng(seed)
    cent = np.zeros((num_cluster, feat_dim), np.float32)
    for i in range(num_cluster):
        fi, fv = data.row(int(rng.integers(data.num_row)))
        # add, not assign: hashed rows (hash_features) carry duplicate
        # indices whose values must sum
        np.add.at(cent, (i, fi), fv)
    for i in range(num_cluster):
        root = int(rng.integers(rabit_tpu.get_world_size()))
        cent[i] = rabit_tpu.broadcast(
            cent[i] if rabit_tpu.get_rank() == root else None, root)
    model = KMeansModel(cent)
    model.normalize()
    return model


_STEP_CACHE: dict = {}

# Pre-densify the shard when the dense copy fits this budget: the scatter
# (data-dependent, VPU-bound) then runs ONCE at load, and each iteration
# is pure MXU matmuls over dense blocks.
DENSIFY_BUDGET_BYTES = 2 << 30


def _dense16_budget() -> int:
    """Memory budget for the half-width dense staging tier
    (compute_dtype="bfloat16": x stored (n, d) bf16 plus an f32 validity
    vector — the biggest-that-fits tier, whose iterations ride the fused
    dense kernel instead of the ELL one): 7/8 of what the local device
    reports, the rest being headroom for centroids/stats/scratch.  The
    fused ELL tiers' budget is read off it (:func:`_stream_budget`).

    The size is measured, never assumed.  A TPU that reports no limit
    is an error (a guessed 16 GB would OOM a smaller chip and waste a
    larger one); the CPU backend reports none because its arrays live
    in host RAM, so there the host's physical memory is the limit."""
    import os

    import jax

    stats = jax.local_devices()[0].memory_stats()
    limit = int(stats.get("bytes_limit", 0)) if stats else 0
    if limit <= 0:
        check(not on_tpu(),
              "kmeans staging: device %s reports no memory limit "
              "(memory_stats() = %r); cannot size the tier",
              jax.local_devices()[0], stats)
        limit = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    return limit - (limit >> 3)


_DENSE16_ROW_TILE = 16384   # fused-kernel row block: stage an exact
#                             multiple so its padding never copies
_STAGE_CHUNK_ROWS = 1 << 20
# A shard whose fused-ELL form does not fit the device is taken a chunk
# of _STAGE_CHUNK_ROWS rows at a time (at 32 slots a row 134 MB of
# indices and as much of values: the size at which the host link ran at
# 14 GB/s, PERF.md section 6, PR 32).  Of the budget (_stream_budget)
# this many chunks are kept free for the ones on their way: one the
# kernel reads, one landed, one in transfer.
_STREAM_RING = 3


def _stream_budget() -> int:
    """Memory budget of the fused ELL tiers: over it a shard streams,
    and as many of its chunks as fit in it beside the ring stay
    resident.  15/16 of what the device reports: the fused ELL kernel
    keeps its working set in VMEM (a resident shard's peak is the shard
    and 1.6 MB: PERF.md section 5), so of the eighth that
    :func:`_dense16_budget` leaves the dense kernel half is enough for
    programs and results.  Every chunk more that is resident is 273 MB
    less over the host link an iteration: of a 72-chunk shard on a v5e
    17 chunks stream at 15/16 where 21 would at 7/8, and the job reads
    183M rows/s where it read 173M (PERF.md section 6, PR 46).  Read
    off ``_dense16_budget``, which measures and which a test steers."""
    eighth_off = _dense16_budget()
    return eighth_off + eighth_off // 14     # 7/8 + 1/16 of the whole


def _densify_fn(block: int, d: int, nnz: int):
    key = ("densify", block, d, nnz)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kmeans_densify(idx, val, valid):
            def body(_, blk):
                i, v, vld = blk
                dense = _ell_densify(i, v, d)
                # pad column d becomes the validity column
                dense = dense.at[:, d].set(vld)
                return None, dense

            _, out = jax.lax.scan(body, None, (idx, val, valid))
            return out                     # (nb, block, d+1)

        fn = _STEP_CACHE[key] = kmeans_densify
    return fn


def _stage_dense16(idx, val, valid, feat_dim: int, row_block: int,
                   compute_dtype: str):
    """Densify the whole shard into a device-resident (n16, d) array of
    ``compute_dtype`` + an f32 validity vector, chunk by chunk.

    The f32 blocks tier ships everything at once; at biggest-that-fits
    scale that would hold idx+val AND the output on device together, so
    this stager streams host chunks through a donating
    ``dynamic_update_slice`` writer — peak device memory is the output
    plus one chunk.  Rows pad to the fused kernel's 16384 block so its
    padding never copies the array.
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    import math

    n, nnz = idx.shape
    # rows pad to lcm(row_block, fused-kernel tile) so chunking stays
    # row_block-aligned AND the kernel's row padding is a no-op; the
    # feature dim pads to the 128-lane tile at STAGING time — otherwise
    # every stats call would re-pad the whole multi-GB array
    row_lcm = math.lcm(row_block, _DENSE16_ROW_TILE)
    n16 = -(-n // row_lcm) * row_lcm
    dp = -(-feat_dim // 128) * 128
    cdt = jnp.dtype(compute_dtype)
    chunk = min(n16, max(row_block,
                         (_STAGE_CHUNK_ROWS // row_block) * row_block))

    def writer_fn(rows: int):
        key = ("stage16", feat_dim, dp, nnz, row_block, rows, str(cdt))
        fn = _STEP_CACHE.get(key)
        if fn is None:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def kmeans_stage_dense16(x, ci, cv, start):
                def body(_, blk):
                    bi, bv = blk
                    dense = _ell_densify(bi, bv, feat_dim)[:, :feat_dim]
                    return None, jnp.pad(
                        dense, ((0, 0), (0, dp - feat_dim))).astype(cdt)

                _, dense = jax.lax.scan(
                    body, None, (ci.reshape(-1, row_block, nnz),
                                 cv.reshape(-1, row_block, nnz)))
                return lax.dynamic_update_slice(
                    x, dense.reshape(rows, dp), (start, 0))

            fn = _STEP_CACHE[key] = kmeans_stage_dense16
        return fn

    x = jnp.zeros((n16, dp), cdt)
    for start in range(0, n16, chunk):
        rows = min(chunk, n16 - start)
        # start/chunk/n16 are all row_block multiples, so rows is too
        check(rows % row_block == 0,
              "dense16 staging: chunk misalignment (%d %% %d)",
              rows, row_block)
        stop = min(start + rows, n)
        real = max(0, stop - start)       # rows pad to lcm(row_block,
        if real == 0:                     # tile), so a whole chunk can
            continue                      # land past n: x is already 0
        ci = idx[start:stop]
        cv = val[start:stop]
        if real < rows:                   # tail: pad with inert rows
            pad = rows - real             # (index feat_dim is sliced
            ci = np.pad(ci, ((0, pad), (0, 0)),   # away; validity 0)
                        constant_values=feat_dim)
            cv = np.pad(cv, ((0, pad), (0, 0)))
        x = writer_fn(rows)(x, jnp.asarray(ci), jnp.asarray(cv),
                            jnp.int32(start))
    v16 = np.zeros(n16, np.float32)
    v16[:n] = valid
    return x, jax.device_put(jnp.asarray(v16))


def _stage_slice_fns():
    """Jitted: a zeroed device array of a shape and type, and a slice of
    rows written into such an array, donated."""
    fns = _STEP_CACHE.get("stage_slice")
    if fns is None:
        import functools

        import jax
        import jax.numpy as jnp
        from jax import lax

        @functools.partial(jax.jit, static_argnums=(0, 1))
        def kmeans_stage_alloc(shape, dtype):
            return jnp.zeros(shape, dtype)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def kmeans_stage_slice(x, c, start):
            return lax.dynamic_update_slice(x, c, (start, 0))

        fns = _STEP_CACHE["stage_slice"] = (kmeans_stage_alloc,
                                            kmeans_stage_slice)
    return fns


def _stage_sliced(host, rows: int):
    """``host`` (n, w) as one device array, handed over ``rows`` rows at
    a time through the donating writer.

    ``jax.device_put`` of one multi-GB array moves a GB a second on the
    chip's host, where the same bytes in slices of 134 MB move at 14
    (4.8 s against 0.3 s for the 4.29 GB of a 33.5M-row shard's
    indices: PERF.md section 6, PR 32).  A slice is awaited before the
    next is handed over, so the device holds the array and one slice
    and never more: the loop would otherwise run ahead of the device by
    as many slices as it has.  (A shard that streams keeps to the same
    rule every iteration: :class:`_ChunkStream` holds the host to its
    ring.)"""
    import jax

    alloc, write = _stage_slice_fns()
    try:
        x = alloc(host.shape, host.dtype)
        for start in range(0, host.shape[0], rows):
            c = jax.device_put(host[start:start + rows])
            # a host scalar: jnp.int32(start) would load a program of
            # its own, which stays on the device (34 KB) after this
            x = write(x, c, np.int32(start))
            x.block_until_ready()
            del c
        return x
    finally:
        # run once a staging: a program left loaded holds its 50-80 KB
        # of the device's memory for as long as the job runs
        alloc.clear_cache()
        write.clear_cache()


def _ell_densify(idx, val, d: int):
    """Densify a padded-ELL block to (rows, d+1).

    Expressed as a one-hot contraction rather than ``.at[].add`` — the
    TPU scatter lowering serialises updates (~15 ns each, measured),
    while the compare + einsum stays on the vector/matrix units and runs
    ~2.3x faster at d=256, nnz=32.  Pad entries (index d) land in the
    extra column, which callers overwrite or slice away.
    """
    import jax.numpy as jnp

    iota = jnp.arange(d + 1, dtype=idx.dtype)
    onehot = (idx[:, :, None] == iota).astype(jnp.float32)
    return jnp.einsum("rj,rjd->rd", val, onehot)


def _normalize_rows(m, eps: float = 1e-12):
    """L2-normalise rows on device (cosine distance prep)."""
    import jax.numpy as jnp

    return m / (jnp.linalg.norm(m, axis=1, keepdims=True) + eps)


def _dense_assign(cnorm, x, valid):
    """Shared dense stats core: similarity (MXU) → argmax → masked
    one-hot.  Returns the (rows, k) one-hot assignment matrix."""
    import jax
    import jax.numpy as jnp

    sim = x @ cnorm.T                                 # (rows, k) MXU
    assign = jnp.argmax(sim, axis=1)
    return (jax.nn.one_hot(assign, cnorm.shape[0], dtype=jnp.float32)
            * valid[:, None])


def _dense_stats_fn(k: int, d: int, block: int):
    """Stats pass over pre-densified blocks: two MXU matmuls per block."""
    key = ("dense", k, d, block)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def body(stats, dense):
            onehot = _dense_assign(stats["cnorm"], dense[:, :d],
                                   dense[:, d])
            new = stats["acc"] + onehot.T @ dense          # (k, d+1) MXU
            return {"cnorm": stats["cnorm"], "acc": new}, None

        @jax.jit
        def kmeans_dense_stats(centroids, dense_blocks):
            with jax.named_scope("kmeans/assign_stats"):
                init = {"cnorm": _normalize_rows(centroids),
                        "acc": jnp.zeros((k, d + 1), jnp.float32)}
                out, _ = jax.lax.scan(body, init, dense_blocks)
                return out["acc"]

        fn = _STEP_CACHE[key] = kmeans_dense_stats
    return fn


def _stats_fn(k: int, d: int, block: int, nnz: int):
    """Jitted pass: blocks of padded-ELL rows → (k, d+1) stats matrix."""
    key = (k, d, block, nnz)
    fn = _STEP_CACHE.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    def body(stats, blk):
        idx, val, valid = blk
        # densify via one-hot contraction; pad column d sliced away
        dense = _ell_densify(idx, val, d)[:, :d]
        onehot = _dense_assign(stats["cnorm"], dense, valid)
        ext = jnp.concatenate([dense * valid[:, None], valid[:, None]], axis=1)
        new = stats["acc"] + onehot.T @ ext               # (k, d+1) MXU
        return {"cnorm": stats["cnorm"], "acc": new}, None

    @jax.jit
    def kmeans_ell_scan_stats(centroids, idx_blocks, val_blocks,
                              valid_blocks):
        with jax.named_scope("kmeans/assign_stats"):
            init = {"cnorm": _normalize_rows(centroids),
                    "acc": jnp.zeros((k, d + 1), jnp.float32)}
            out, _ = jax.lax.scan(
                body, init, (idx_blocks, val_blocks, valid_blocks))
            return out["acc"]

    _STEP_CACHE[key] = kmeans_ell_scan_stats
    return kmeans_ell_scan_stats


def centroid_update(cent, stats):
    """New centroids from an (allreduced) (k, d+1) stats matrix: divide
    by counts (empty clusters keep their previous centroid), then
    renormalise (cosine k-means, reference: kmeans.cc:141-157).
    Jax-traceable — usable inside jit/shard_map programs."""
    import jax.numpy as jnp

    counts = stats[:, -1:]
    new = jnp.where(counts > 0,
                    stats[:, :-1] / jnp.maximum(counts, 1.0), cent)
    return _unit_rows(new)


def _unit_rows(m):
    """Rows scaled to unit L2 norm by :meth:`KMeansModel.normalize`'s
    rule (rows with ~zero norm are left unscaled), on the device."""
    import jax.numpy as jnp

    norm = jnp.linalg.norm(m, axis=1, keepdims=True)
    return jnp.where(norm < 1e-6, m, m / jnp.maximum(norm, 1e-30))


def _update_fn():
    """Jitted: the distributed loop's centroid update on the device.

    Takes the allreduced (k, d+1) stats and returns the new centroids,
    float32, and the counts column: sums over counts, then the row
    normalisation, as the host arm does them in numpy.  A cluster that
    received no row divides by zero here; the loop reads the counts
    beside the centroids and aborts before it commits such a version
    (the reference's rule), so nothing keeps the previous centroid as
    :func:`centroid_update` does for the chained loop."""
    fn = _STEP_CACHE.get("update")
    if fn is None:
        import jax

        @jax.jit
        def kmeans_update(stats):
            with jax.named_scope("kmeans/update"):
                counts = stats[:, -1:]
                return _unit_rows(stats[:, :-1] / counts), counts

        fn = _STEP_CACHE["update"] = kmeans_update
    return fn


def _device_loop_fn(iters: int, use_pallas: bool, block: int | None,
                    compute_dtype: str):
    """Jitted: run ``iters`` full k-means iterations on device.

    The single-program analogue of the reference's host loop
    (kmeans.cc:121-157): stats pass → divide → renormalise, chained
    without leaving the accelerator.  With the XLA engine the cross-rank
    allreduce also stays in-program (psum); here world-local stats.
    Clusters that receive no points keep their previous centroid.

    ``compute_dtype="bfloat16"`` stores the data and runs the similarity
    pass in bf16 (half the HBM traffic — the TPU idiom); statistics
    still accumulate in float32.  Assignments may differ near decision
    boundaries.
    """
    key = ("loop", iters, use_pallas, block, compute_dtype)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        cdt = jnp.dtype(compute_dtype)

        def one_iter(cent, xv):
            x, valid = xv
            with jax.named_scope("kmeans/assign_stats"):
                if use_pallas:
                    from rabit_tpu.ops.kmeans_kernel import \
                        kmeans_stats_fused
                    stats = kmeans_stats_fused(cent, x, valid, block=block)
                else:
                    onehot = _dense_assign(
                        _normalize_rows(cent).astype(cdt), x, valid)
                    sums = jax.lax.dot_general(
                        onehot.astype(cdt), x, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    counts = jnp.sum(onehot, axis=0)
                    stats = jnp.concatenate(
                        [sums, counts[:, None]], axis=1)
            with jax.named_scope("kmeans/update"):
                return centroid_update(cent, stats)

        @jax.jit
        def kmeans_chain(cent, x, valid):
            x = x.astype(cdt)  # one cast, reused across the chain
            return jax.lax.fori_loop(
                0, iters, lambda _, c: one_iter(c, (x, valid)), cent)

        fn = _STEP_CACHE[key] = kmeans_chain
    return fn


def device_iterations(centroids, x, valid, iters: int,
                      use_pallas: bool | None = None,
                      block: int | None = None,
                      compute_dtype: str = "float32"):
    """Run ``iters`` k-means iterations device-resident; returns the final
    centroid array (a ``jax.Array`` — not fetched)."""
    if use_pallas is None:
        use_pallas = on_tpu()
    fn = _device_loop_fn(iters, use_pallas, block, compute_dtype)
    return fn(centroids, x, valid)


_ELL_FUSED_BLOCK = 2048
_ELL_FUSED_HI = 128
_ELL_FUSED_GROUP = 4


def _next_pow2(v: int) -> int:
    p = 1
    while p < v:
        p <<= 1
    return p


def prepare_shard(idx, val, valid, feat_dim: int,
                  row_block: int = DEFAULT_ROW_BLOCK,
                  budget: int = DENSIFY_BUDGET_BYTES,
                  compute_dtype: str = "float32",
                  max_index: int | None = None):
    """Stage this rank's shard on device for repeated stats passes.

    Small-enough shards are densified once (the scatter is
    centroid-independent), making each iteration pure MXU matmuls.
    Larger shards stay in ELL form: on TPU the fused two-level Pallas
    kernel (:func:`rabit_tpu.ops.kmeans_kernel.kmeans_ell_stats_fused`)
    runs the whole stats pass without ever materialising dense rows in
    HBM — measured 4x the scan path's throughput at the 50M-point shape
    (doc/benchmarks.md "ELL densify bound", superseded in round 4);
    elsewhere the block-scan densify pass is used.

    ``idx`` and ``val`` are read, never written, and no host array of
    their size is made unless the data forces one (slots or rows that
    the fused ELL kernel needs padded): they may be the caller's own
    memory.  ``max_index`` is the largest entry of ``idx`` where the
    caller has already taken it (:func:`run` does, under
    ``stage.clamp``); the fused ELL tier takes it itself otherwise.

    A shard whose fused-ELL form is over the budget the device reports
    (:func:`_stream_budget`) is the fifth outcome, ``"ell_stream"``:
    the same kernel, a chunk of ``_STAGE_CHUNK_ROWS`` rows a call.  As
    many whole chunks as the budget holds beside a ring of
    ``_STREAM_RING`` are staged resident, each a device array triple of
    its own, spread evenly over the shard; the rest stay in the
    caller's host memory and cross the host link under the kernel every
    iteration.  The payload is ``(resident, host, d_pad, nnz, stream)``:
    ``resident`` maps a chunk's number to its grouped ``(idx, val,
    valid)`` on the device, ``host`` lists every chunk's grouped triple
    on the host (views of ``idx`` and ``val``, which are read and never
    written; a copy only of a ragged last chunk, padded once, or where
    the slots had to be padded), ``stream`` is the :class:`_ChunkStream`
    that hands the others over.

    The span ``stage.put`` closes as this returns: the dense tiers'
    transfers may still be in flight, the fused ELL tier's slices have
    landed (:func:`_stage_sliced` awaits each), and so has the resident
    part of a shard that streams (``stage.resident``, inside it).
    """
    with program.span("stage.put"):
        return _prepare_shard(idx, val, valid, feat_dim, row_block,
                              budget, compute_dtype, max_index)


def _tier(n: int, nnz: int, feat_dim: int, budget: int,
          compute_dtype: str) -> str:
    """Which of :func:`prepare_shard`'s five outcomes a shard of ``n``
    rows of ``nnz`` slots gets: its bytes in each form against the
    budget that form is held to.  Nothing is staged."""
    import jax.numpy as jnp

    if n * (feat_dim + 1) * 4 <= budget:
        return "dense"
    if compute_dtype != "float32":
        itemsize = jnp.dtype(compute_dtype).itemsize
        dp = -(-feat_dim // 128) * 128   # staged at lane-padded width
        if n * dp * itemsize + n * 4 <= _dense16_budget():
            return "dense16"
    if not on_tpu():
        return "ell"
    # slots padded to a power of two, rows to the kernel block: indices,
    # values and validity fit, or what fits is resident and the rest
    # streams
    n_p = -(-n // _ELL_FUSED_BLOCK) * _ELL_FUSED_BLOCK
    whole = n_p * (_next_pow2(nnz) * 8 + 4)
    return "ell_stream" if whole > _stream_budget() else "ell_fused"


def _prepare_shard(idx, val, valid, feat_dim: int, row_block: int,
                   budget: int, compute_dtype: str,
                   max_index: int | None):
    import jax

    n, nnz = idx.shape
    nb = n // row_block
    tier = _tier(n, nnz, feat_dim, budget, compute_dtype)
    if tier == "dense":
        fn = _densify_fn(row_block, feat_dim, nnz)
        blocks = fn(idx.reshape(nb, row_block, -1),
                    val.reshape(nb, row_block, -1),
                    valid.reshape(nb, row_block))
        return (tier, feat_dim, blocks)
    if tier == "dense16":
        return (tier, feat_dim, _stage_dense16(
            idx, val, valid, feat_dim, row_block, compute_dtype))
    if tier == "ell":
        return (tier, feat_dim, device_ell(idx, val, valid, row_block))
    # pad slots to a power of two (index shifts), rows to the kernel
    # block; pad slots carry (index=feat_dim, value=0) so they land
    # in the sliced-away validity column with zero weight
    nnz_p = _next_pow2(nnz)
    n_p = -(-n // _ELL_FUSED_BLOCK) * _ELL_FUSED_BLOCK
    if max_index is None:
        max_index = int(idx.max(initial=0))
    # Exact-d padding when possible: slots at index feat_dim with a
    # ZERO value (ELL pads) vanish through the val-weighted one-hot,
    # so only clamped out-of-range features carrying real values
    # force an extra sliced-away feature block (+hi columns = +20%
    # MACs at d=512) to absorb them.  The mask (a quarter of the
    # indices' bytes) is built only where the shard holds such an
    # index at all; the pads below carry zeros and need no look.
    contaminated = (max_index >= feat_dim
                    and bool(np.any(val[idx >= feat_dim])))
    d_base = feat_dim + 1 if contaminated else feat_dim
    d_pad = -(-d_base // _ELL_FUSED_HI) * _ELL_FUSED_HI
    if tier == "ell_stream":
        return (tier, feat_dim,
                _stage_stream(idx, val, valid, feat_dim, nnz_p, d_pad))
    if nnz_p != nnz or n_p != n:
        idx = np.pad(idx, ((0, n_p - n), (0, nnz_p - nnz)),
                     constant_values=feat_dim)
        val = np.pad(val, ((0, n_p - n), (0, nnz_p - nnz)))
        valid = np.pad(valid, (0, n_p - n))
    # Stage GROUPED (n/G, G*nnz): a device array with a 32-wide
    # minor dim is lane-padded to 128 (4x HBM — OOM at 50M rows);
    # the grouped layout is what the kernel consumes anyway.
    g = _ELL_FUSED_GROUP
    idx_g = np.ascontiguousarray(idx.reshape(n_p // g, g * nnz_p))
    val_g = np.ascontiguousarray(
        val.reshape(n_p // g, g * nnz_p).astype(np.float32, copy=False))
    rows = _STAGE_CHUNK_ROWS // g
    return (tier, feat_dim,
            (_stage_sliced(idx_g, rows), _stage_sliced(val_g, rows),
             jax.device_put(valid), d_pad, nnz_p))


def _stage_stream(idx, val, valid, feat_dim: int, nnz_p: int, d_pad: int):
    """The payload of the ``"ell_stream"`` tier (:func:`prepare_shard`):
    the host's part, done once a job, then :func:`_stream_device`.

    The shard is cut into chunks of ``_STAGE_CHUNK_ROWS`` rows (whole
    kernel blocks), each grouped ``(rows/G, G*nnz)`` as the kernel reads
    it.  A chunk of a contiguous shard is a view: only a ragged last
    chunk is copied, padded to the one shape with inert rows (index
    ``feat_dim``, value and validity 0), and a shard whose slots are no
    power of two is padded whole, as the resident tier pads it."""
    n, nnz = idx.shape
    g = _ELL_FUSED_GROUP
    rows = -(-_STAGE_CHUNK_ROWS // _ELL_FUSED_BLOCK) * _ELL_FUSED_BLOCK
    if nnz_p != nnz:
        idx = np.pad(idx, ((0, 0), (0, nnz_p - nnz)),
                     constant_values=feat_dim)
        val = np.pad(val, ((0, 0), (0, nnz_p - nnz)))
    val = val.astype(np.float32, copy=False)
    valid = valid.astype(np.float32, copy=False)
    host = []
    for lo in range(0, n, rows):
        ci, cv, cvalid = idx[lo:lo + rows], val[lo:lo + rows], \
            valid[lo:lo + rows]
        short = rows - len(ci)
        if short:
            ci = np.pad(ci, ((0, short), (0, 0)), constant_values=feat_dim)
            cv = np.pad(cv, ((0, short), (0, 0)))
            cvalid = np.pad(cvalid, (0, short))
        host.append((
            np.ascontiguousarray(ci).reshape(rows // g, g * nnz_p),
            np.ascontiguousarray(cv).reshape(rows // g, g * nnz_p),
            np.ascontiguousarray(cvalid)))
    program.count("stage.host_chunks", len(host))
    return _stream_device(host, n, d_pad, nnz_p)


def _stream_device(host, n: int, d_pad: int, nnz_p: int):
    """The device's part of the ``"ell_stream"`` payload: the chunks the
    budget holds are put resident, each awaited before the next (the
    rule of :func:`_stage_sliced`), and a new ring is set up over the
    others.  Run again after a re-formation of the device plane, on the
    ``host`` chunks the job already has.

    Chunk ``c`` of ``C`` streams where ``(c+1)*S // C`` exceeds
    ``c*S // C``, ``S`` being the number that does not fit: evenly
    spread, so that the resident chunks' kernel time lies between two
    transfers all along a pass, and the last chunk (the one that may be
    ragged) among them."""
    import jax

    device = jax.local_devices()[0]
    rows = len(host[0][2])
    chunk_bytes = sum(a.nbytes for a in host[0])
    fit = (_stream_budget() - _STREAM_RING * chunk_bytes) // chunk_bytes
    total = len(host)
    streamed = total - max(0, min(total, fit))
    order = [c for c in range(total)
             if (c + 1) * streamed // total > c * streamed // total]
    resident = {}
    with program.span("stage.resident"):
        for c in sorted(set(range(total)) - set(order)):
            resident[c] = jax.block_until_ready(
                jax.device_put(host[c], device))
    program.put("stage.resident_rows", len(resident) * rows)
    real = [min(rows, n - c * rows) for c in range(total)]
    return (resident, host, d_pad, nnz_p,
            _ChunkStream(host, order, real, device))


class _ChunkStream:
    """The chunks of a shard that are not resident, handed to the device
    in their order, round after round, through a ring of
    ``_STREAM_RING``.

    A chunk is handed over (one ``jax.device_put`` of its triple, which
    returns while the bytes move) as soon as fewer than the ring's depth
    are on the device unconsumed: handed over and not yet read to the
    end by the kernel call that takes them.  The transfers depend on no
    centroid, so the ring is filled again across a pass's end, for the
    pass to come.  When the ring is full the host waits (``stream.wait``)
    for the oldest call that read a chunk: the device never holds more
    than the resident part and the ring, however far ahead the host
    could run.  ``stream.inflight_max`` is the most it held."""

    def __init__(self, host, order, real_rows, device):
        import collections

        self.host, self.order, self.real_rows = host, order, real_rows
        self.device = device
        self.turn = 0                       # in `order`: the next to go
        self.ahead = collections.deque()    # (chunk, triple) handed over
        self.reading = collections.deque()  # results of calls on a chunk
        self.inflight_max = 0

    def _inflight(self) -> int:
        # the device runs its calls in order: the finished ones are first
        while self.reading and self.reading[0].is_ready():
            self.reading.popleft()
        return len(self.ahead) + len(self.reading)

    def hand_over(self, wait: bool) -> bool:
        """Hand the next chunk over if the ring has room, or once it
        has, if ``wait``.  Says whether it did."""
        import jax

        if self._inflight() >= _STREAM_RING:
            if not wait or not self.reading:
                return False
            with program.span("stream.wait"):
                self.reading.popleft().block_until_ready()
                program.waited()
        c = self.order[self.turn]
        self.turn = (self.turn + 1) % len(self.order)
        with program.span("stream.put"):
            self.ahead.append((c, jax.device_put(self.host[c], self.device)))
        self.inflight_max = max(self.inflight_max, self._inflight())
        program.put("stream.inflight_max", self.inflight_max)
        return True

    def take(self, c: int):
        """Chunk ``c`` on the device, for the kernel call of a pass."""
        if not self.ahead:
            self.hand_over(wait=True)
        got, triple = self.ahead.popleft()
        check(got == c, "kmeans stream: chunk %d is next on the device, "
              "the pass asked for %d", got, c)
        program.count("stream.chunks")
        program.count("stream.rows", self.real_rows[c])
        return triple

    def taken(self, result) -> None:
        """``result`` is what the call that read the last chunk taken
        writes; then every chunk the ring has room for is handed over."""
        self.reading.append(result)
        while self.hand_over(wait=False):
            pass

    def refill(self) -> None:
        """At a pass's end: the ring filled for the next pass, each
        chunk as soon as a call of this one has let go of its own."""
        while self.order and len(self.ahead) < _STREAM_RING \
                and self.hand_over(wait=True):
            pass


def _ell_chunk_fn(k: int, d: int, d_pad: int, nnz: int):
    """Jitted: the fused ELL kernel on one chunk of a shard, its (k, d+1)
    statistics added to the running sum of the pass.  One shape, so one
    program, whether the chunk is resident or was just handed over."""
    key = ("ellchunk", k, d, d_pad, nnz, _ELL_FUSED_BLOCK)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        from rabit_tpu.ops.kmeans_kernel import kmeans_ell_stats_fused

        block = _ELL_FUSED_BLOCK

        @jax.jit
        def kmeans_ell_chunk_stats(acc, cent, idx_g, val_g, valid):
            with jax.named_scope("kmeans/assign_stats"):
                cent_p = jnp.pad(cent, ((0, 0), (0, d_pad - d)))
                stats = kmeans_ell_stats_fused(
                    cent_p, idx_g, val_g, valid, d_pad, nnz=nnz,
                    group=_ELL_FUSED_GROUP, hi=_ELL_FUSED_HI, block=block)
                return acc + jnp.concatenate(
                    [stats[:, :d], stats[:, -1:]], axis=1)

        fn = _STEP_CACHE[key] = kmeans_ell_chunk_stats
    return fn


def _ell_stream_stats(centroids, payload, d: int):
    """A pass over a shard that streams: the kernel chunk by chunk in the
    shard's order, every call of one shape, each result added on the
    device to the sum of those before it.  Which chunks are resident
    changes where a call's rows come from and nothing it computes: the
    same calls in the same order give the same bits under any budget.

    The span ``learn.stream`` covers the pass's hand-overs.  The
    counters ``stream.chunks`` and ``stream.rows`` count a chunk as the
    pass takes it (what is handed over ahead, for a pass that has not
    run, is in neither)."""
    import jax

    resident, host, d_pad, nnz, stream = payload
    k = centroids.shape[0]
    call = _ell_chunk_fn(k, d, d_pad, nnz)
    # committed where the chunks are, like every operand of every call:
    # one executable (see `on_device` in run())
    cent = jax.device_put(centroids, stream.device)
    acc = jax.device_put(np.zeros((k, d + 1), np.float32), stream.device)
    with program.span("learn.stream"):
        for c in range(len(host)):
            chunk = resident.get(c)
            # the call before's result stays alive across the hand-over,
            # where the span table asks it whether the device still ran
            # (a result that was let go of counts as landed)
            before = acc
            acc = call(before, cent, *(chunk or stream.take(c)))
            program.enqueued(acc)
            if chunk is None:
                stream.taken(acc)
        stream.refill()
    return acc


def shard_stats_device(centroids, shard):
    """Per-iteration (k, d+1) stats for a staged shard, left on device
    (a ``jax.Array`` — feed it straight to the XLA engine's allreduce so
    the reduction rides ICI).  ``centroids`` is a (k, d) float32 array
    of the host or of the device: the distributed loop hands over what
    its update program left there."""
    kind, feat_dim, payload = shard
    k, d = centroids.shape
    if kind == "dense":
        fn = _dense_stats_fn(k, d, payload.shape[1])
        return fn(centroids, payload)
    if kind == "dense16":
        x, v16 = payload
        return _dense16_stats_fn(k, d, x.shape[1])(centroids, x, v16)
    if kind == "ell_fused":
        return _ell_fused_stats(centroids, payload, d)
    if kind == "ell_stream":
        return _ell_stream_stats(centroids, payload, d)
    idx, val, valid = payload  # pre-blocked by device_ell: (nb, block, nnz)
    fn = _stats_fn(k, d, idx.shape[1], idx.shape[2])
    return fn(centroids, idx, val, valid)


def _dense16_stats_fn(k: int, d: int, dp: int):
    """Single fused-kernel stats pass over a half-width staged shard.

    ``x`` is staged at the lane-padded width ``dp``; centroids pad up
    (zero columns change neither norms nor similarities) and the stats
    slice back, so the multi-GB array is never re-padded per call."""
    key = ("dense16stats", k, d, dp)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        from rabit_tpu.ops.kmeans_kernel import kmeans_stats_fused

        @jax.jit
        def kmeans_stats(centroids, x, valid):
            with jax.named_scope("kmeans/assign_stats"):
                cent_p = jnp.pad(centroids, ((0, 0), (0, dp - d)))
                stats = kmeans_stats_fused(cent_p, x, valid)  # (k, dp+1)
                return jnp.concatenate(
                    [stats[:, :d], stats[:, -1:]], axis=1)

        fn = _STEP_CACHE[key] = kmeans_stats
    return fn


def _ell_chain_fn(iters: int, k: int, d: int, d_pad: int, nnz: int):
    """Jitted: ``iters`` fused-ELL k-means iterations device-resident
    (the sparse twin of :func:`_device_loop_fn` — same checkpoint-
    granularity tradeoff, same recurrence)."""
    key = ("ellchain", iters, k, d, d_pad, nnz)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        from rabit_tpu.ops.kmeans_kernel import kmeans_ell_stats_fused

        def one_iter(cent, idx_g, val_g, valid):
            with jax.named_scope("kmeans/assign_stats"):
                cent_p = jnp.pad(cent, ((0, 0), (0, d_pad - d)))
                stats = kmeans_ell_stats_fused(
                    cent_p, idx_g, val_g, valid, d_pad, nnz=nnz,
                    group=_ELL_FUSED_GROUP, hi=_ELL_FUSED_HI,
                    block=_ELL_FUSED_BLOCK)
                stats = jnp.concatenate(
                    [stats[:, :d], stats[:, -1:]], axis=1)
            with jax.named_scope("kmeans/update"):
                return centroid_update(cent, stats)

        @jax.jit
        def kmeans_ell_chain(cent, idx_g, val_g, valid):
            return jax.lax.fori_loop(
                0, iters, lambda _, c: one_iter(c, idx_g, val_g, valid),
                cent)

        fn = _STEP_CACHE[key] = kmeans_ell_chain
    return fn


def _ell_fused_stats(centroids, payload, d: int):
    """Fused-kernel stats with feature padding folded in: centroids are
    zero-padded to the kernel's d (multiple of hi), the sliced-away
    columns absorb pad slots (index ``feat_dim`` -> column d, value 0)."""
    import jax.numpy as jnp

    from rabit_tpu.ops.kmeans_kernel import kmeans_ell_stats_fused

    idx_g, val_g, valid, d_pad, nnz = payload
    cent_p = jnp.pad(jnp.asarray(centroids), ((0, 0), (0, d_pad - d)))
    stats = kmeans_ell_stats_fused(
        cent_p, idx_g, val_g, valid, d_pad, nnz=nnz,
        group=_ELL_FUSED_GROUP, hi=_ELL_FUSED_HI, block=_ELL_FUSED_BLOCK)
    # (k, d_pad+1) -> (k, d+1): keep real features + the counts column
    return jnp.concatenate([stats[:, :d], stats[:, -1:]], axis=1)


def device_ell(idx, val, valid, row_block: int = DEFAULT_ROW_BLOCK):
    """Move ELL arrays to the accelerator once, pre-blocked.

    Feeding the returned triple to :func:`compute_stats` avoids a
    host→device copy of the whole dataset every iteration.
    """
    import jax

    nb = idx.shape[0] // row_block
    return (
        jax.device_put(idx.reshape(nb, row_block, -1)),
        jax.device_put(val.reshape(nb, row_block, -1)),
        jax.device_put(valid.reshape(nb, row_block)),
    )


def compute_stats(model: KMeansModel, idx, val, valid,
                  row_block: int = DEFAULT_ROW_BLOCK) -> np.ndarray:
    """Local (k, d+1) cluster stats for this rank's shard.

    Accepts flat (nrow, nnz) arrays or pre-blocked device arrays from
    :func:`device_ell`.
    """
    k, d = model.centroids.shape
    if idx.ndim == 2:
        nb = idx.shape[0] // row_block
        idx = idx.reshape(nb, row_block, -1)
        val = val.reshape(nb, row_block, -1)
        valid = valid.reshape(nb, row_block)
    fn = _stats_fn(k, d, idx.shape[1], idx.shape[2])
    out = fn(model.centroids, idx, val, valid)
    return np.asarray(out)


def run(data: SparseMat, num_cluster: int, max_iter: int,
        out_model: str | None = None, seed: int = 0,
        row_block: int = DEFAULT_ROW_BLOCK,
        device_chain: int = 0,
        hash_dim: int | None = None,
        compute_dtype: str = "float32") -> KMeansModel:
    """Train; mirrors the reference main loop (kmeans.cc:104-161).

    ``device_chain > 1`` enables the single-worker device-resident fast
    path: that many iterations run as one XLA program between
    checkpoints (resume granularity coarsens to the chain length: one
    committed version per chain, so a resumed run must pass the same
    ``device_chain``).  The host fetches and commits a chain's result
    while the next chain already runs.  A chain is one program over
    rows that are on the device: a shard that streams (below) is refused
    under ``device_chain > 1`` with a message that says so, and is not
    quietly run an iteration a version, because what a committed
    version counts would then follow the memory the device reports, and
    a job resumed on another chip would misread its own.

    The distributed loop on the device plane (XLA engine, several ranks)
    likewise runs a step ahead of its commit, and keeps the reduced
    statistics on the chip: the allreduce's result goes straight into
    the update program (:func:`_update_fn`: divide, normalise), the
    stats program of v+1 (local, no collective) is enqueued on the
    centroids that leaves on the device, and only then does the host
    fetch the new centroids (and the counts, for the zero-sized-cluster
    check) and commit them as fetched, so the fetch's copy and the
    commit's host rounds run under the kernel.  Every version is still
    committed before the next version's allreduce is issued, and a
    resumed job recomputes at most the one uncommitted version, from
    bit for bit the centroids the device held.  A result queued across
    a re-formation of the device plane (``rabit_tpu.device_epoch``
    moved inside the commit) is dropped unread with the centroids it
    was computed on, and dispatched afresh from the model on the
    re-staged shard.  Host engines keep the lazy path and the numpy
    update.

    ``hash_dim`` (power of two) clusters in SIGNED-HASHED feature space
    instead of the original one: every downstream stage — init,
    staging, stats, checkpoints, the saved model — then lives at that
    width, which typically routes staging onto the pre-densified
    HBM-roofline path (13.6x the exact ELL kernel at d=512→128,
    doc/benchmarks.md "Feature-hashed sparse k-means").  Approximate:
    collisions add (zero-mean under the signed hash); quality is
    data-dependent.  The saved centroids are hashed-space vectors —
    score new rows by hashing them the same way.

    ``compute_dtype="bfloat16"`` additionally unlocks the HALF-WIDTH
    dense staging tier: shards too big for the exact float32 blocks but
    within 7/8 of the device's reported memory stage as a (n, d) bf16
    array and every iteration rides the HBM-roofline fused kernel
    (similarity in bf16, accumulation in float32 — the bench.py
    numerics).

    A shard too large for the device (its fused-ELL form over the
    budget the device reports) runs all the same, through the
    per-iteration loop: :func:`prepare_shard` stages as many chunks as
    fit and every iteration streams the others from ``data``'s own
    arrays under the kernel (``learn.stream``, ``stream.put``,
    ``stream.wait``; the counters ``stream.*``).  A version's statistics
    are the same bits whichever chunks are resident.  The host's rows
    are read and never copied again; after a re-formation of the device
    plane only the resident chunks are put again.

    Every call stages the whole shard, under three spans:
    ``stage.to_ell``; ``stage.clamp``, which times one range check of
    the indices (their maximum against the model's width) and, only
    where an index lies past that width and ``stage.clamped`` counts 1,
    the copy that moves such indices to the pad column; ``stage.put``
    (:func:`prepare_shard`).  A uniform in-range ``data`` is staged from
    its own ``findex`` and ``fvalue``, which are only read.
    """
    if hash_dim is not None:
        from rabit_tpu.learn.data import hash_features

        hidx, hval = hash_features(data.findex, data.fvalue, hash_dim)
        data = SparseMat(indptr=data.indptr, findex=hidx, fvalue=hval,
                         labels=data.labels, feat_dim=hash_dim)
    model = KMeansModel()
    version, restored = rabit_tpu.load_checkpoint()
    if version == 0:
        feat_dim = int(rabit_tpu.allreduce(
            np.array([data.feat_dim], np.int64), MAX)[0])
        model = init_centroids(data, num_cluster, feat_dim, seed)
        model.hash_dim = hash_dim
        rabit_tpu.tracker_print(
            "[%d] start at %s" % (
                rabit_tpu.get_rank(), rabit_tpu.get_processor_name()))
    else:
        model = restored
        check(getattr(model, "hash_dim", None) == hash_dim,
              "kmeans resume: checkpoint was trained with hash_dim=%s "
              "but run() got hash_dim=%s — centroids live in a different "
              "feature space; pass the original value",
              getattr(model, "hash_dim", None), hash_dim)
        rabit_tpu.tracker_print(
            "[%d] restart iter=%d" % (rabit_tpu.get_rank(), version))
    k, feat_dim = model.centroids.shape
    idx, val, _labels, valid = data.to_ell(
        pad_index=feat_dim, row_block=row_block)
    # Features past the model's width (a restored model narrower than
    # this shard, a SparseMat that understates its feat_dim) go to the
    # pad column.  One read of the indices says whether any does: a
    # uniform shard's idx and val are the caller's own findex and
    # fvalue, and a copy of them is seconds of fresh pages at data
    # scale.  From here on nothing writes into either.
    with program.span("stage.clamp"):
        max_index = int(idx.max(initial=0))
        clamped = max_index > feat_dim
        if clamped:
            idx, max_index = np.minimum(idx, feat_dim), feat_dim
        program.count("stage.clamped", int(clamped))
        idx = idx.astype(np.int32, copy=False)
    # dataset lives on device across iterations; only the (k, d+1) stats
    # matrix crosses the host boundary for the fault-tolerant allreduce
    shard = prepare_shard(idx, val, valid, feat_dim, row_block,
                          compute_dtype=compute_dtype, max_index=max_index)
    rows = idx.shape[0]

    check(not (device_chain > 1 and shard[0] == "ell_stream"
               and not rabit_tpu.is_distributed()),
          "kmeans: device_chain=%d chains iterations in one program over "
          "rows that are on the device, and this shard (%d rows) is "
          "larger than the device holds and streams from the host every "
          "iteration: pass device_chain=0 (one committed version an "
          "iteration)", device_chain, rows)
    if (device_chain > 1 and not rabit_tpu.is_distributed()
            and shard[0] in ("dense", "dense16", "ell_fused")):
        # Single-worker fast path: chain iterations device-resident
        # (lax.fori_loop in one XLA program), syncing to the host only to
        # commit a checkpoint every `device_chain` iterations.  There is
        # no cross-rank allreduce at world=1, so the chain is exact.
        # Works for both staging layouts: pre-densified blocks and the
        # fused-ELL kernel (the per-iteration host fetch of the
        # centroids amortizes over the chain).
        import jax.numpy as jnp

        if shard[0] == "dense":
            blocks = shard[2]
            n_total = blocks.shape[0] * blocks.shape[1]
            x = blocks[:, :, :feat_dim].reshape(n_total, feat_dim)
            vcol = blocks[:, :, feat_dim].reshape(n_total)
        elif shard[0] == "dense16":
            x, vcol = shard[2]
        else:
            idx_g, val_g, dvalid, d_pad, nnz_p = shard[2]
        # one committed version per CHAIN here, not per iteration
        it = min(version * device_chain, max_iter)
        cent = jnp.asarray(model.centroids)
        if shard[0] == "dense16" and x.shape[1] != feat_dim:
            # the shard is staged at the lane-padded width; iterate in
            # that space (zero columns are inert) and slice on fetch
            cent = jnp.pad(cent, ((0, 0), (0, x.shape[1] - feat_dim)))

        def enqueue(cent, chain, span="learn.dispatch"):
            with program.span(span):
                if shard[0] == "dense":
                    cent = device_iterations(cent, x, vcol, chain)
                elif shard[0] == "dense16":
                    cent = device_iterations(
                        cent, x, vcol, chain, compute_dtype=compute_dtype,
                        block=_DENSE16_ROW_TILE)
                else:
                    fn = _ell_chain_fn(chain, k, feat_dim, d_pad, nnz_p)
                    cent = fn(cent, idx_g, val_g, dvalid)
                program.enqueued(cent)
                return cent

        # The chain after this one is enqueued before this one's result
        # is fetched and committed: it needs only the centroids on the
        # device, so the device never waits for the host's fetch, commit
        # and dispatch, and the rate does not follow the host's load.
        # The job's first hand-over is set-up's: where the process has
        # not run this program yet it is traced and compiled there, so
        # no `learn.step` holds a compile (a last chain that is shorter
        # compiles where it is enqueued, under the chain before it).
        chain = min(device_chain, max_iter - it)
        queued = enqueue(cent, chain, "stage.compile") if chain else None
        while chain:
            version += 1
            it += chain
            ahead = min(device_chain, max_iter - it)
            with program.span("learn.step", version=version):
                cent = queued
                queued = enqueue(cent, ahead) if ahead else None
                with program.span("learn.fetch"):
                    fetched = fetch(cent)
                # the iterations of the version the host now holds
                program.count("learn.iterations", chain)
                program.count("learn.rows", chain * rows)
                with program.span("learn.update"):
                    model.centroids = fetched[:, :feat_dim]
                program.count("learn.versions")
                rabit_tpu.checkpoint(model)
            chain = ahead
        if out_model and rabit_tpu.get_rank() == 0:
            save_model(model, out_model)
        return model

    # With the XLA engine the stats matrix can stay device-resident and
    # reduce over ICI; other engines take the fault-tolerant host path
    # with lazy preparation (replay skips the compute on recovery).
    from rabit_tpu import engine as _engine_mod

    device_plane = _engine_mod.is_device_plane()

    def enqueue_stats(centroids):
        local = shard_stats_device(centroids, shard)
        program.enqueued(local)
        return local

    def on_device(centroids):
        # committed to this rank's device, as the update program leaves
        # its result: a jitted program is compiled anew for an operand
        # that is committed where the one it first saw was not
        import jax

        return jax.device_put(centroids, jax.local_devices()[0])

    # On the device plane the reduced statistics never leave the chip
    # between two kernels: the update program takes this rank's replica
    # of the allreduce's result, and the stats program of the next
    # version is enqueued on the centroids it leaves on the device,
    # before the host has waited for anything.  Only then does the host
    # fetch the new centroids and the counts, bind the model to what it
    # fetched (bit for bit what the device iterates on, so a resumed
    # job continues from the state the undisturbed one had) and commit:
    # the fetch's copy and the commit's host rounds run under a kernel
    # that was queued before the previous one ended.  That kernel is
    # local and collective-free; the next version's allreduce is still
    # issued after this commit returns.
    # The job's first hand-over of either program is set-up's, as in
    # the chained loop: it compiles them where the process has not run
    # them yet.  (A host engine calls for the stats from inside its
    # allreduce, and skips the call in a replay: its first step holds
    # the compile.)
    epoch = rabit_tpu.device_epoch()
    queued = None
    if device_plane and version < max_iter:
        update = _update_fn()
        with program.span("stage.compile"):
            update(on_device(np.ones((k, feat_dim + 1), np.float32)))
            queued = enqueue_stats(on_device(model.centroids))
    for it in range(version, max_iter):
        if rabit_tpu.device_epoch() != epoch:
            # the device plane was re-formed at a checkpoint boundary
            # (failure recovery): arrays of the old epoch died with the
            # backends — re-upload the shard, then continue at full speed
            epoch = rabit_tpu.device_epoch()
            if queued is not None:
                # so did the result queued ahead, and the centroids it
                # was computed on: dropped unread, and the loop starts
                # again from the host's copy of the last committed
                # version
                queued = None
                program.count("learn.ahead_discarded")
            if shard[0] == "ell_stream":
                # the host's chunks are the job's own: the resident
                # ones are put again, the ring starts empty
                with program.span("stage.put"):
                    _resident, host, *widths, _stream = shard[2]
                    shard = (shard[0], feat_dim,
                             _stream_device(host, rows, *widths))
            else:
                shard = prepare_shard(idx, val, valid, feat_dim, row_block,
                                      compute_dtype=compute_dtype,
                                      max_index=max_index)
        with program.span("learn.step", version=it + 1):
            if device_plane:
                if queued is None:
                    with program.span("learn.dispatch"):
                        local = enqueue_stats(on_device(model.centroids))
                else:
                    local, queued = queued, None
                    if it > version:     # the job's first: of no commit
                        program.count("learn.ahead")
                reduced = total = rabit_tpu.allreduce(local, SUM)
                with program.span("learn.update"):
                    if not total.is_fully_addressable:
                        # replicated over the process mesh: this rank's
                        # replica, no copy and no wait
                        total = total.addressable_shards[0].data
                    cent, counts = update(on_device(total))
                    # `reduced`, the array the engine named, is alive
                    # here for the span table to ask whether the
                    # all-reduce still ran (one let go of counts as
                    # landed)
                    program.enqueued(cent)
                del reduced
                program.count("learn.device_updates")
                if it + 1 < max_iter:
                    with program.span("learn.dispatch"):
                        queued = enqueue_stats(cent)
                with program.span("learn.fetch"):
                    fetched, counts = fetch((cent, counts))
                program.count("learn.iterations")
                program.count("learn.rows", rows)
                check(bool((counts != 0).all()), "get zero sized cluster")
                model.centroids = fetched
            else:
                stats = np.zeros((k, feat_dim + 1), np.float32)

                def lazy_stats(stats=stats):
                    with program.span("learn.dispatch"):
                        local = enqueue_stats(model.centroids)
                    with program.span("learn.fetch"):
                        stats[...] = fetch(local)

                stats = rabit_tpu.allreduce(stats, SUM,
                                            prepare_fun=lazy_stats)
                program.count("learn.iterations")
                program.count("learn.rows", rows)
                with program.span("learn.update"):
                    counts = stats[:, -1:]
                    check(bool((counts != 0).all()),
                          "get zero sized cluster")
                    model.centroids = (stats[:, :-1] / counts).astype(
                        np.float32)
                    model.normalize()
            program.count("learn.versions")
            rabit_tpu.checkpoint(model)

    if out_model and rabit_tpu.get_rank() == 0:
        save_model(model, out_model)
    return model


def main(argv: list[str]) -> int:
    """CLI mirroring the reference binary:
    ``kmeans <data> num_cluster max_iter <out_model> [name=value ...]``
    (reference: kmeans.cc:84-165)."""
    if len(argv) < 5:
        rabit_tpu.init(argv[1:])
        if rabit_tpu.get_rank() == 0:
            rabit_tpu.tracker_print(
                "Usage: <data_dir> num_cluster max_iter <out_model>")
        rabit_tpu.finalize()
        return 0
    import time

    t0 = time.perf_counter()
    # app-level name=value args (everything else goes to the engine)
    app = {}
    engine_args = []
    for a in argv[5:]:
        key, _, v = a.partition("=")
        if key in ("kmeans_hash_dim", "kmeans_device_chain"):
            check(v.isdigit(), "%s needs an integer value, got %r "
                  "(usage: %s=<int>)", key, v, key)
            app[key] = int(v)
        elif key == "kmeans_compute_dtype":
            check(v in ("float32", "bfloat16"),
                  "kmeans_compute_dtype must be float32|bfloat16, got %r",
                  v)
            app[key] = v
        else:
            engine_args.append(a)
    compile_cache.enable()
    rabit_tpu.init(engine_args)
    data = load_libsvm(argv[1])
    run(data, int(argv[2]), int(argv[3]), argv[4],
        device_chain=app.get("kmeans_device_chain", 0),
        hash_dim=app.get("kmeans_hash_dim"),
        compute_dtype=app.get("kmeans_compute_dtype", "float32"))
    rabit_tpu.tracker_print(
        "[%d] Time taken: %f seconds" % (
            rabit_tpu.get_rank(), time.perf_counter() - t0))
    rabit_tpu.finalize()
    return 0


def cli() -> int:
    """Console-script entry point."""
    import sys

    return main(sys.argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv))

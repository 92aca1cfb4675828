"""Fused Pallas kernel for the k-means cluster-statistics pass.

The reference computes per-point assignment and cluster sums in a C++
row loop on the host (reference: rabit-learn/kmeans/kmeans.cc:121-140).
The XLA version in :mod:`rabit_tpu.learn.kmeans` is two MXU matmuls with
an argmax between them — each matmul streams the row data from HBM, so
the pass reads the payload twice.  This kernel fuses the whole pass:
each grid step loads one row block into VMEM, computes similarity
(MXU), argmax (VPU), builds the one-hot matrix *already transposed* as
(k, block), and folds the block's (k, d) sums and (k,) counts into VMEM
accumulators — data is read from HBM exactly once.

Two layout lessons measured on v5e (difference-timed between a short
and a long chain to cancel the fixed per-execution cost, see
doc/benchmarks.md):

* Building the one-hot as (block, k) and contracting over dim 0 forces
  a (block, k) -> (k, block) relayout inside the kernel every grid step
  (~4x slowdown).  Building it transposed makes both matmuls
  natural-layout: ``x @ cn.T`` and ``onehot_t @ x``.
* Block size 16384 with a raised scoped-VMEM limit saturates HBM
  (~860 GB/s in bf16); the 2048-row default of the old kernel left the
  DMA pipeline latency-bound.

Layout requirements (callers pad): ``d`` a multiple of 128 (lanes),
``k`` a multiple of 8 (sublanes), rows a multiple of the block size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rabit_tpu.ops import on_tpu

# Per-block VMEM footprint target: the block plus its double-buffer
# partner should stay well under the raised scoped-VMEM limit.
_BLOCK_BYTES_TARGET = 8 << 20
_VMEM_LIMIT_BYTES = 100 << 20
_MAX_BLOCK = 16384


def _stats_kernel(x_ref, cn_ref, valid_ref, sums_ref, counts_ref,
                  *, k_real: int):
    i = pl.program_id(0)
    x = x_ref[:]                                  # (block, d), compute dtype
    block, _ = x.shape
    k = cn_ref.shape[0]

    sim = jnp.dot(x, cn_ref[:].T,
                  preferred_element_type=jnp.float32)   # (block, k) MXU
    # padded centroid rows (zero vectors) would win the argmax whenever
    # every real similarity is negative — mask them out
    if k_real < k:
        col_ids = lax.broadcasted_iota(jnp.int32, (block, k), 1)
        sim = jnp.where(col_ids < k_real, sim, -jnp.inf)
    assign = jnp.argmax(sim, axis=1)                    # (block,)
    # one-hot built directly in (k, block) layout: both dots are then
    # natural-layout matmuls and Mosaic inserts no relayout
    rows = lax.broadcasted_iota(jnp.int32, (k, block), 0)
    onehot_t = (rows == assign[None, :]).astype(jnp.float32)
    onehot_t = onehot_t * valid_ref[:]                  # (1, block) bcast

    part_sums = jnp.dot(onehot_t.astype(x.dtype), x,
                        preferred_element_type=jnp.float32)  # (k, d) MXU
    part_counts = jnp.sum(onehot_t, axis=1)[:, None]         # (k, 1)

    @pl.when(i == 0)
    def _():
        sums_ref[:] = part_sums
        counts_ref[:] = part_counts

    @pl.when(i != 0)
    def _():
        sums_ref[:] = sums_ref[:] + part_sums
        counts_ref[:] = counts_ref[:] + part_counts


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "k_real"))
def _stats_call(cnorm, x, valid, block: int, interpret: bool, k_real: int):
    n, d = x.shape
    k = cnorm.shape[0]
    nb = n // block
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    sums, counts = pl.pallas_call(
        functools.partial(_stats_kernel, k_real=k_real),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((k, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ),
        compiler_params=params,
        interpret=interpret,
    )(x, cnorm, valid.reshape(1, n))
    return sums, counts


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def default_block(n: int, d: int, itemsize: int = 2) -> int:
    """Largest power-of-two row block whose VMEM footprint stays within
    the target budget (16384 rows at d=256 bf16 saturates HBM), shrunk
    further while rounding ``n`` up to the block would waste more than
    ~25% of the pass on padded rows."""
    block = _MAX_BLOCK
    while block > 512 and block * _round_up(d, 128) * itemsize \
            > _BLOCK_BYTES_TARGET:
        block //= 2
    while block > 1024 and (_round_up(n, block) - n) * 4 > n:
        block //= 2
    return block


def _ell_stats_kernel(idx_ref, val_ref, valid_ref, cn_ref,
                      sums_ref, counts_ref, *,
                      k_real: int, group: int, hi: int, lo: int,
                      nnz: int, compute_dtype):
    """Fused ELL stats step: two-level band densify (MXU) + similarity
    + argmax + one-hot stats, with the dense block living only in VMEM.

    Inputs arrive GROUPED: ``idx``/``val`` are (Bg, G·nnz) — G original
    rows per sublane row, so the band densify is one batched matmul.
    With the feature split ``f = (f // hi)·hi + (f % hi)`` the per-group
    matmul output (G·lo, hi) flattens row-major to G dense rows in
    natural feature order — no transpose, no relayout beyond the
    reshape.  Pad slots (index d, value 0) contribute zero because the
    weighted lo one-hot carries the value.

    Layout law (measured, this file's docstring + histogram_kernel.py):
    one-hots must be built with the DATA dimension in lanes and the
    class dimension in sublanes — the opposite orientation costs ~15x
    (a 3D (rows, slots, class) build measured 29 ms/pass vs sub-ms for
    this (batch, class, slots) form).  The batched densify contraction
    is therefore the MXU-native NT form (contraction dim = lanes of
    both operands)."""
    i = pl.program_id(0)
    idx = idx_ref[:]                              # (Bg, G*nnz) int32
    val = val_ref[:]                              # (Bg, G*nnz)
    bg = idx.shape[0]
    block = bg * group
    d = hi * lo
    k = cn_ref.shape[0]

    hi_bits = hi.bit_length() - 1
    hi_idx = lax.bitwise_and(idx, hi - 1)[:, None, :]   # (Bg, 1, P)
    # position p in [0, G*nnz) belongs to group-row g = p // nnz, whose
    # band is columns [g*lo, (g+1)*lo)
    g_of_p = lax.shift_right_logical(
        lax.broadcasted_iota(jnp.int32, (bg, 1, group * nnz), 2),
        nnz.bit_length() - 1)
    col = g_of_p * lo + lax.shift_right_logical(idx, hi_bits)[:, None, :]
    hio = (hi_idx ==
           lax.broadcasted_iota(jnp.int32, (bg, hi, group * nnz), 1)
           ).astype(compute_dtype)                # (Bg, hi, P)
    loo = ((col ==
            lax.broadcasted_iota(
                jnp.int32, (bg, group * lo, group * nnz), 1))
           * val[:, None, :]).astype(compute_dtype)  # (Bg, G*lo, P)
    dense3 = lax.dot_general(
        loo, hio, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)       # (Bg, G*lo, hi)
    dense = dense3.reshape(block, d).astype(compute_dtype)

    sim = jnp.dot(dense, cn_ref[:].T,
                  preferred_element_type=jnp.float32)   # (block, k) MXU
    if k_real < k:
        col_ids = lax.broadcasted_iota(jnp.int32, (block, k), 1)
        sim = jnp.where(col_ids < k_real, sim, -jnp.inf)
    assign = jnp.argmax(sim, axis=1)
    rows = lax.broadcasted_iota(jnp.int32, (k, block), 0)
    onehot_t = (rows == assign[None, :]).astype(jnp.float32)
    onehot_t = onehot_t * valid_ref[:]                  # (1, block) bcast

    part_sums = jnp.dot(onehot_t.astype(compute_dtype), dense,
                        preferred_element_type=jnp.float32)  # (k, d)
    part_counts = jnp.sum(onehot_t, axis=1)[:, None]         # (k, 1)

    @pl.when(i == 0)
    def _():
        sums_ref[:] = part_sums
        counts_ref[:] = part_counts

    @pl.when(i != 0)
    def _():
        sums_ref[:] = sums_ref[:] + part_sums
        counts_ref[:] = counts_ref[:] + part_counts


@functools.partial(jax.jit, static_argnames=(
    "d", "group", "hi", "block", "interpret", "k_real", "compute_dtype"))
def _ell_stats_call(cnorm, idx_g, val_g, valid, d: int, group: int,
                    hi: int, block: int, interpret: bool, k_real: int,
                    compute_dtype):
    n_g, p = idx_g.shape
    nnz = p // group
    n = n_g * group
    k = cnorm.shape[0]
    bg = block // group
    nb = n // block
    lo = d // hi
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    kernel = functools.partial(
        _ell_stats_kernel, k_real=k_real, group=group, hi=hi, lo=lo,
        nnz=nnz, compute_dtype=jnp.dtype(compute_dtype))
    sums, counts = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bg, p), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bg, p), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((k, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, 1), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ),
        compiler_params=params,
        interpret=interpret,
    )(idx_g, val_g, valid.reshape(1, n), cnorm)
    return sums, counts


def kmeans_ell_stats_fused(centroids: jax.Array, idx: jax.Array,
                           val: jax.Array, valid: jax.Array, d: int,
                           group: int = 4, hi: int = 128,
                           block: int = 2048,
                           compute_dtype=jnp.bfloat16,
                           nnz: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """(k, d+1) stats matrix straight from padded-ELL rows.

    The sparse-path answer to the densify bound (doc/benchmarks.md "ELL
    densify bound"): instead of scatter-densifying on the VPU
    (~2·nnz·d lane-ops per row), the kernel splits each feature index
    into (hi, lo) digits and reconstructs G-row groups of dense rows
    with ONE well-tiled MXU matmul per group batch — nnz·(hi + G·lo)
    VPU compare-ops and G·nnz·d MXU MACs per row — then finishes the
    whole k-means stats pass in VMEM.  ``d`` must be divisible by
    ``hi`` (the caller pads features); rows must divide into ``block``.

    ``idx``/``val`` are flat (n, nnz) ELL arrays (pad index ``d``, pad
    value 0), or — when ``nnz`` is passed explicitly — PRE-GROUPED
    (n/G, G·nnz) arrays.  Callers staging big shards must group on the
    host and ship the grouped layout: a device array with a 32-wide
    minor dimension is lane-padded to 128 (4x the memory — a flat
    50M x 32 int32 staging OOMed 16 GB HBM), while (n/G, G·nnz) with
    G·nnz = 128 tiles exactly.  Returns counts in the last column like
    :func:`kmeans_stats_fused`.
    """
    if interpret is None:
        interpret = not on_tpu()
    k, dc = centroids.shape
    if dc != d:
        raise ValueError(f"centroids dim {dc} != d {d}")
    if nnz is None:
        n, nnz = idx.shape
        idx = idx.reshape(n // group, group * nnz)
        val = val.reshape(n // group, group * nnz)
    else:
        if idx.shape[1] != group * nnz:
            raise ValueError(f"grouped idx width {idx.shape[1]} != "
                             f"group*nnz = {group * nnz}")
        n = idx.shape[0] * group
    lo = d // hi
    if lo * hi != d:
        raise ValueError(f"d={d} not divisible by hi={hi}")
    if nnz & (nnz - 1) or hi & (hi - 1):
        raise ValueError(f"nnz={nnz} and hi={hi} must be powers of two "
                         "(the kernel splits indices with shifts)")
    if n % block or block % group:
        raise ValueError(f"n={n} must divide into block={block} "
                         f"(multiple of group={group})")
    kp = _round_up(k, 8)

    cnorm = centroids.astype(jnp.float32)
    cnorm = cnorm / (jnp.linalg.norm(cnorm, axis=1, keepdims=True) + 1e-12)
    cnorm = jnp.pad(cnorm.astype(jnp.dtype(compute_dtype)),
                    ((0, kp - k), (0, 0)))

    sums, counts = _ell_stats_call(
        cnorm, idx, val.astype(jnp.float32), valid.astype(jnp.float32),
        d, group, hi, block, interpret, k, jnp.dtype(compute_dtype).name)
    return jnp.concatenate([sums[:k], counts[:k]], axis=1)


def kmeans_stats_fused(centroids: jax.Array, x: jax.Array,
                       valid: jax.Array, block: int | None = None,
                       interpret: bool | None = None) -> jax.Array:
    """(k, d+1) stats matrix (counts in the last column) for dense rows.

    ``centroids`` (k, d) are L2-normalised internally (cosine distance,
    reference: kmeans.cc:63-79); ``x`` is (n, d) dense rows with invalid
    rows arbitrary, ``valid`` (n,) 1/0.  The similarity pass runs in
    ``x``'s dtype (bf16 halves the single HBM read); accumulation is
    always float32.  Pads k/d/n to hardware tiles, slices the result
    back.
    """
    if interpret is None:
        interpret = not on_tpu()
    k, d = centroids.shape
    n = x.shape[0]
    cdt = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    kp, dp = _round_up(k, 8), _round_up(d, 128)
    if block is None:
        block = default_block(n, d, jnp.dtype(cdt).itemsize)
    block = min(block, _round_up(n, 8))
    npad = _round_up(n, block)

    cnorm = centroids.astype(jnp.float32)
    cnorm = cnorm / (jnp.linalg.norm(cnorm, axis=1, keepdims=True) + 1e-12)
    cnorm = jnp.pad(cnorm.astype(cdt), ((0, kp - k), (0, dp - d)))
    xp = jnp.pad(x.astype(cdt), ((0, npad - n), (0, dp - d)))
    vp = jnp.pad(valid.astype(jnp.float32), (0, npad - n))

    sums, counts = _stats_call(cnorm, xp, vp, block, interpret, k)
    stats = jnp.concatenate([sums[:k, :d], counts[:k]], axis=1)
    return stats

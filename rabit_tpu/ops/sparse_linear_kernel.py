"""The two sparse products of a wide linear model: ``X w`` and ``X^T g``.

A hashed click-through model has a million weights and a few dozen
non-zeros a row.  Both products are then a random access a non-zero (a
gather from ``w`` for the margins, a scatter into the gradient), which
XLA runs on the TPU as a serial loop (PERF.md section 5 has the times
at a million weights and 654M non-zeros on a v5e).

Here the shard is re-ordered once, at set-up (:func:`bucket_group`):
the non-zeros of a tile of ``ROW_TILE`` rows are sorted by feature block
(``FEAT_BLOCK`` features) and every (tile, block) bucket is padded to
whole sub-chunks of ``SUB`` slots, so a sub-chunk addresses one tile of
rows and one block of weights.  Inside it a row is ``rhi * 128 + rlo``
and a feature ``fhi * 40 + flo`` (four 7-bit digits packed in one
int32), and both random accesses become two-level one-hot products on
the MXU, as the k-means ELL kernel and the histogram kernel do:

* pick: ``T @ onehot(hi)`` selects a column of the table tile for every
  slot; a masked sublane sum over ``lo`` leaves the slot's one value;
* add: ``onehot(hi) @ (onehot(lo) * c)^T`` accumulates every slot's
  contribution ``c`` into its cell of the output tile.

float32 through a bfloat16 MXU, exactly: a one-hot is exact in any
type, and the float32 operand of each product (the table picked from,
the contributions added) is cut into three parts on the bfloat16 grid
that add up to it exactly (:func:`split3`, by masking mantissa bits),
so every product is exact and every sum is a float32 accumulation.

The same staged arrays serve both products and the XLA formulation
(:func:`margins_xla`, :func:`gradient_xla`) that runs off the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

R_HI = R_LO = 128
ROW_TILE = R_HI * R_LO            # rows a tile
F_HI, F_LO = 128, 40              # 3 parts x 40 = 120 of the MXU's 128
FEAT_BLOCK = F_HI * F_LO          # features a block
SUB = 512                         # slots a sub-chunk (lanes)
SUBS = 8                          # sub-chunks a grid step (sublanes)
STEP = SUB * SUBS
GROUP_TILES = 32                  # tiles bucketed by one staging call
_VMEM_LIMIT_BYTES = 100 << 20


def num_blocks(num_feature: int) -> int:
    return max(1, -(-num_feature // FEAT_BLOCK))


def capacity(nnz_row: int, num_feature: int) -> int:
    """Slots a tile takes: every slot of its ELL rows and the most that
    padding its buckets to whole sub-chunks can ask for, in whole grid
    steps."""
    slots = ROW_TILE * nnz_row + num_blocks(num_feature) * (SUB - 1)
    return -(-slots // STEP) * STEP


def split3(x):
    """Three float32 arrays on the bfloat16 grid with ``a + b + c == x``
    exactly: the mantissa cut by masks, top 8 bits each time, so no
    backend can fold a rounding away as excess precision."""
    def top(v):
        bits = lax.bitcast_convert_type(v, jnp.int32)
        return lax.bitcast_convert_type(
            lax.bitwise_and(bits, jnp.int32(-65536)), jnp.float32)

    a = top(x)
    r = x - a
    b = top(r)
    return a, b, r - b


# ----------------------------------------------------------------------
# set-up: bucket a group of tiles on the device
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("nnz_row", "num_feature"))
def bucket_group(idx, val, *, nnz_row: int, num_feature: int):
    """Flat ELL slots of ``g`` whole tiles (``g * ROW_TILE * nnz_row``,
    row-major) to ``(packed, val, fb, real)``: per tile, slots sorted by
    feature block, buckets padded to whole sub-chunks.  A slot whose
    value is 0 or whose feature is outside ``[0, num_feature)`` is
    padding (reference: linear.h:94-96 skips such features)."""
    with jax.named_scope("lbfgs_stage"):
        nfb = num_blocks(num_feature)
        per_tile = ROW_TILE * nnz_row
        g = idx.shape[0] // per_tile
        cap = capacity(nnz_row, num_feature)
        idx = idx.reshape(g, per_tile)
        val = val.reshape(g, per_tile)
        row = lax.broadcasted_iota(jnp.int32, (1, per_tile), 1) // nnz_row
        real = (val != 0) & (idx >= 0) & (idx < num_feature)
        fb = idx // FEAT_BLOCK
        within = idx - fb * FEAT_BLOCK
        fhi = within // F_LO
        packed = ((row // R_LO) << 21 | (row % R_LO) << 14
                  | fhi << 7 | (within - fhi * F_LO))
        key = jnp.where(real, fb, nfb)
        packed = jnp.where(real, packed, 0)
        val = jnp.where(real, val, 0.0)
        blocks = jnp.arange(nfb, dtype=jnp.int32)
        counts = jnp.sum((key[:, :, None] == blocks).astype(jnp.int32),
                         axis=1)                          # (g, nfb)
        # the tile's spare slots (enough to pad every bucket, then whole
        # steps): slot e pads bucket #{cum <= e}; those past the last
        # need sort behind everything (key nfb)
        cum = jnp.cumsum((-counts) % SUB, axis=1)
        spare = jnp.arange(cap - per_tile, dtype=jnp.int32)
        spare_key = jnp.sum(
            (spare[None, :, None] >= cum[:, None, :]).astype(jnp.int32),
            axis=2)
        zeros = jnp.zeros(spare_key.shape, jnp.int32)
        key, packed, val = lax.sort(
            (jnp.concatenate([key, spare_key], axis=1),
             jnp.concatenate([packed, zeros], axis=1),
             jnp.concatenate([val, zeros.astype(jnp.float32)], axis=1)),
            dimension=1, num_keys=1)
        fb_sub = key[:, ::SUB]
        fb_sub = jnp.where(fb_sub >= nfb, 0, fb_sub)
        return (packed.reshape(g * cap // SUB, SUB),
                val.reshape(g * cap // SUB, SUB),
                fb_sub.reshape(g * cap // STEP, SUBS),
                jnp.sum(counts))


@functools.partial(jax.jit, donate_argnums=(0,))
def place(whole, part, at):
    """``part`` written into ``whole`` at row ``at``, in place."""
    return lax.dynamic_update_slice(whole, part, (at, 0))


# ----------------------------------------------------------------------
# the kernels
# ----------------------------------------------------------------------
def _digits(p):
    return (lax.bitwise_and(lax.shift_right_logical(p, 21), 127),
            lax.bitwise_and(lax.shift_right_logical(p, 14), 127),
            lax.bitwise_and(lax.shift_right_logical(p, 7), 127),
            lax.bitwise_and(p, 127))


def _onehot(cls, digit):
    return (cls == digit).astype(jnp.float32).astype(jnp.bfloat16)


def _weighted(cls, digit, parts):
    hit = cls == digit
    return [jnp.where(hit, q, 0.0).astype(jnp.bfloat16) for q in parts]


_NT = (((1,), (1,)), ((), ()))    # contract the lanes of both operands
FB_STEPS = 128                    # grid steps whose blocks one SMEM tile holds


def _block_of(fb_ref, b: int):
    """Feature block of sub-chunk ``b`` of this grid step: the SMEM tile
    is ``(8, 128)`` int32, ``SUBS`` entries a step, ``FB_STEPS`` steps."""
    at = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) \
        % FB_STEPS * SUBS + b
    return fb_ref[at // 128, at % 128]


def _margin_kernel(fb_ref, idx_ref, val_ref, w_ref, out_ref):
    """One grid step: SUBS sub-chunks of one row tile.  ``w_ref[fb]`` is
    ``(3 * F_LO + 8, F_HI)`` bfloat16, row ``part * F_LO + flo``;
    ``out_ref[0]`` is ``(R_HI, 3 * R_LO)`` float32, column
    ``part * R_LO + rlo``."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    cls = lax.broadcasted_iota(jnp.int32, (128, SUB), 0)
    cls_lo = lax.broadcasted_iota(jnp.int32, (F_LO, SUB), 0)
    acc = jnp.zeros((R_HI, 3 * R_LO), jnp.float32)
    for b in range(SUBS):
        rhi, rlo, fhi, flo = _digits(idx_ref[b:b + 1, :])
        picked = jnp.dot(w_ref[_block_of(fb_ref, b)], _onehot(cls, fhi),
                         preferred_element_type=jnp.float32)  # (128, SUB)
        table = (picked[0:F_LO] + picked[F_LO:2 * F_LO]
                 + picked[2 * F_LO:3 * F_LO])
        w_of = jnp.sum(jnp.where(cls_lo == flo, table, 0.0),
                       axis=0, keepdims=True)                 # (1, SUB)
        parts = split3(val_ref[b:b + 1, :] * w_of)
        acc = acc + lax.dot_general(
            _onehot(cls, rhi),
            jnp.concatenate(_weighted(cls, rlo, parts), axis=0), _NT,
            preferred_element_type=jnp.float32)
    out_ref[0] = out_ref[0] + acc


def _grad_kernel(fb_ref, idx_ref, val_ref, g_ref, out_ref):
    """``g_ref[0]`` is ``(3 * R_LO, R_HI)`` bfloat16, row
    ``part * R_LO + rlo``; ``out_ref[fb]`` is ``(F_HI, 128)`` float32,
    column ``part * F_LO + flo``, resident for the whole call."""
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    cls = lax.broadcasted_iota(jnp.int32, (128, SUB), 0)
    cls_lo = lax.broadcasted_iota(jnp.int32, (F_LO, SUB), 0)
    table = g_ref[0]
    blank = jnp.zeros((128 - 3 * F_LO, SUB), jnp.bfloat16)
    for b in range(SUBS):
        rhi, rlo, fhi, flo = _digits(idx_ref[b:b + 1, :])
        picked = jnp.dot(table, _onehot(cls, rhi),
                         preferred_element_type=jnp.float32)  # (384, SUB)
        rows = (picked[0:R_LO] + picked[R_LO:2 * R_LO]
                + picked[2 * R_LO:3 * R_LO])
        g_of = jnp.sum(jnp.where(cls == rlo, rows, 0.0),
                       axis=0, keepdims=True)
        parts = split3(val_ref[b:b + 1, :] * g_of)
        fb = _block_of(fb_ref, b)
        out_ref[fb] = out_ref[fb] + lax.dot_general(
            _onehot(cls, fhi),
            jnp.concatenate(_weighted(cls_lo, flo, parts) + [blank],
                            axis=0), _NT,
            preferred_element_type=jnp.float32)


def _grid(staged_fb, tiles: int):
    steps = staged_fb.shape[0] // tiles
    staged_fb = staged_fb.reshape(-1)
    staged_fb = jnp.pad(staged_fb, (0, -staged_fb.shape[0] % (8 * 128))
                        ).reshape(-1, 128)
    slots = [
        pl.BlockSpec((8, 128), lambda t, s: ((t * steps + s) // FB_STEPS, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((SUBS, SUB), lambda t, s: (t * steps + s, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((SUBS, SUB), lambda t, s: (t * steps + s, 0),
                     memory_space=pltpu.VMEM),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    return (tiles, steps), slots, params, staged_fb


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def lbfgs_margin(packed, val, fb, w, *, tiles: int, interpret: bool):
    """``X w`` without the bias: ``(tiles * ROW_TILE,)`` float32 from
    ``w`` ``(num_feature,)`` float32."""
    nfb = -(-w.shape[0] // FEAT_BLOCK)
    parts = split3(jnp.pad(w, (0, nfb * FEAT_BLOCK - w.shape[0]))
                   .reshape(nfb, F_HI, F_LO))
    table = jnp.stack(parts, axis=1).transpose(0, 1, 3, 2)
    table = jnp.pad(table.reshape(nfb, 3 * F_LO, F_HI),
                    ((0, 0), (0, 128 - 3 * F_LO), (0, 0)))
    grid, slots, params, fb = _grid(fb, tiles)
    out = pl.pallas_call(
        _margin_kernel, grid=grid,
        in_specs=slots + [pl.BlockSpec((nfb, 128, F_HI),
                                       lambda t, s: (0, 0, 0),
                                       memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, R_HI, 3 * R_LO), lambda t, s: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((tiles, R_HI, 3 * R_LO), jnp.float32),
        compiler_params=params, interpret=interpret, name="lbfgs_margin",
    )(fb, packed, val, table.astype(jnp.bfloat16))
    out = out[..., :R_LO] + out[..., R_LO:2 * R_LO] + out[..., 2 * R_LO:]
    return out.reshape(tiles * ROW_TILE)


@functools.partial(jax.jit, static_argnames=(
    "tiles", "num_feature", "interpret"))
def lbfgs_grad(packed, val, fb, g, *, tiles: int, num_feature: int,
               interpret: bool):
    """``X^T g``: ``(num_feature,)`` float32 from ``g``
    ``(tiles * ROW_TILE,)`` float32."""
    nfb = num_blocks(num_feature)
    parts = split3(g.reshape(tiles, R_HI, R_LO))
    table = jnp.stack(parts, axis=1).transpose(0, 1, 3, 2)
    table = table.reshape(tiles, 3 * R_LO, R_HI).astype(jnp.bfloat16)
    grid, slots, params, fb = _grid(fb, tiles)
    out = pl.pallas_call(
        _grad_kernel, grid=grid,
        in_specs=slots + [pl.BlockSpec((1, 3 * R_LO, R_HI),
                                       lambda t, s: (t, 0, 0),
                                       memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((nfb, F_HI, 128), lambda t, s: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nfb, F_HI, 128), jnp.float32),
        compiler_params=params, interpret=interpret, name="lbfgs_grad",
    )(fb, packed, val, table)
    out = (out[..., :F_LO] + out[..., F_LO:2 * F_LO]
           + out[..., 2 * F_LO:3 * F_LO])
    return out.reshape(nfb * FEAT_BLOCK)[:num_feature]


# ----------------------------------------------------------------------
# the same products by XLA's gather and scatter (off the chip)
# ----------------------------------------------------------------------
def _coordinates(packed, fb, tiles: int):
    """Global (row, feature) of every slot."""
    rhi, rlo, fhi, flo = _digits(packed)
    subs = packed.shape[0] // tiles
    tile = lax.broadcasted_iota(jnp.int32, (packed.shape[0], 1), 0) // subs
    return (tile * ROW_TILE + rhi * R_LO + rlo,
            fb.reshape(-1, 1) * FEAT_BLOCK + fhi * F_LO + flo)


@functools.partial(jax.jit, static_argnames=("tiles",))
def margins_xla(packed, val, fb, w, *, tiles: int):
    row, feat = _coordinates(packed, fb, tiles)
    wide = jnp.pad(w, (0, num_blocks(w.shape[0]) * FEAT_BLOCK - w.shape[0]))
    return jnp.zeros(tiles * ROW_TILE, jnp.float32).at[row.reshape(-1)].add(
        (val * wide[feat]).reshape(-1))


@functools.partial(jax.jit, static_argnames=("tiles", "num_feature"))
def gradient_xla(packed, val, fb, g, *, tiles: int, num_feature: int):
    row, feat = _coordinates(packed, fb, tiles)
    return jnp.zeros(num_blocks(num_feature) * FEAT_BLOCK, jnp.float32).at[
        feat.reshape(-1)].add((val * g[row]).reshape(-1))[:num_feature]

"""A tree level's histograms of sparse rows: ``X^T [g 1(slot), h 1(slot)]``.

A libsvm shard of thousands of one-hot columns holds a few dozen
entries a row.  Dense, its level histograms are ``(slots, columns,
max_bin)`` of which all but a hundredth are bins no cut defines, and the
shard itself does not exist.  Here a present value is an **entry**: the
row it belongs to and its cell of the job's flat bin space (a column's
bins one after another's, ``learn.histogram.FlatBins``), and a level's
histogram adds the entry's row's (grad, hess), masked by the row's level
slot, into that cell.  That is the scatter ``ops.sparse_linear_kernel.
lbfgs_grad`` runs on the MXU, with the level's channels where that has
one and ``ops.histogram_kernel``'s masked bfloat16 weights where that
has three exact parts:

* set-up (:func:`bucket_group`): the entries of a tile of ``ROW_TILE``
  rows are sorted by cell block (``CELL_BLOCK`` cells) and every (tile,
  block) bucket is padded to whole sub-chunks of ``SUB`` slots.  A slot
  is one int32: the row within the tile as ``rhi * 32 + rlo`` and the
  cell within the block as ``chi * 4 + clo``; a slot of padding has
  ``PAD`` set and adds nothing;
* pick: ``T @ onehot(rhi)`` selects, for every slot, a column of the
  tile's table of (grad, hess, level slot) a row, the first two rounded
  to bfloat16 (the kernel's operand, as the dense kernel rounds it; a
  slot id is exact there); a masked sublane sum over ``rlo`` leaves the
  slot's three values, exactly;
* add: ``onehot(chi) @ W^T``, W's row ``slot * 8 + channel * 4 + lo``
  holding the entry's grad or hess where its row sits in ``slot`` and
  its cell ends in ``lo``: every entry's pair into its cell of the
  block's ``(128, 128)`` float32 tile, up to 16 slots a call.

A product is exact (a one-hot is exact in any type) and every sum a
float32 accumulation, a tile's by plain adds and the tiles' by a
compensated one (``_hist_kernel``).  What a slot costs is the two
products, whatever the level's width; what the level's width costs is
W's rows.

A tile takes :func:`capacity` slots, room for the worst case, and its
entries fill nine tenths of them: what is left of the rows' ELL slots
and of the spare ones sorts behind the last bucket, and
:func:`bucket_group` marks those sub-chunks in the blocks it stages
(block ``num_blocks``: none).  The kernel's grid step is a tile, and it
works the tile's slots a step of ``SUBS`` sub-chunks at a time in a loop
that ends where the marks begin: the tail costs nothing (PR 52).

Off the chip the same sums come from ``jax.ops.segment_sum`` over the
entries as staged for the row move (:func:`hist_sparse_xla`), in
float32 with the weights as they are.

Timed on a v5e (my chip runs, PRs 49 and 52; PERF.md sections 5 and 6):
a call over 2^24 rows of 32 slots (587.2M slots staged, 504.5M entries,
91.5% of the staged slots worked) takes 0.2978 s at 1 level slot, 0.3031
at 4, 0.3319 at 16 (0.55 to 0.62 ns a worked slot), where the grid of
before PR 52, a turn a step of every tile, tail and all, took 0.3417,
0.3484, 0.3837; the compensated join is 1.5% of a call.  A branch around
a grid step's work (``pl.when``) had given back half of what it skipped,
and one around a sub-chunk's a quarter more than all of it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rabit_tpu.ops.sparse_linear_kernel import (
    STEP, SUB, SUBS, _NT, _VMEM_LIMIT_BYTES, _onehot)

R_HI, R_LO = 128, 32
ROW_TILE = R_HI * R_LO            # rows a tile
C_HI, C_LO = 128, 4
CELL_BLOCK = C_HI * C_LO          # cells a block
CALL_SLOTS = 16                   # level slots a call: 128 rows of W
GROUP_TILES = 32                  # tiles bucketed by one staging call
PAD = 1 << 21                     # a slot of padding


def num_blocks(cells: int) -> int:
    return max(1, -(-cells // CELL_BLOCK))


def capacity(width: int, cells: int) -> int:
    """Slots a tile takes: every slot of its ELL rows and the most that
    padding its buckets to whole sub-chunks can ask for, in whole
    steps."""
    slots = ROW_TILE * width + num_blocks(cells) * (SUB - 1)
    return -(-slots // STEP) * STEP


# ----------------------------------------------------------------------
# set-up: bucket a group of tiles on the device
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cells",))
def bucket_group(cells_t, *, cells: int):
    """The entries of ``g`` whole tiles as staged for the row move,
    ``(width, g * ROW_TILE)`` int32 cells (-1: no entry), to ``(packed,
    fb, real)``: per tile, slots sorted by cell block, buckets padded to
    whole sub-chunks, and the block of every sub-chunk.  A sub-chunk
    behind the tile's last bucket (the rows' ELL slack and the spare
    slots no bucket took) holds no entry and names block ``num_blocks``,
    which does not exist: those are the tile's tail, and the kernel does
    nothing on a step of SUBS sub-chunks that starts with one."""
    with jax.named_scope("gbdt_sparse_stage"):
        width, rows = cells_t.shape
        g, nblk = rows // ROW_TILE, num_blocks(cells)
        per_tile, cap = ROW_TILE * width, capacity(width, cells)
        c = cells_t.reshape(width, g, ROW_TILE).transpose(1, 0, 2).reshape(
            g, per_tile)
        row = lax.broadcasted_iota(jnp.int32, (1, per_tile), 1) % ROW_TILE
        real = (c >= 0) & (c < cells)
        blk = c // CELL_BLOCK
        packed = ((row // R_LO) << 14 | (row % R_LO) << 9
                  | (c - blk * CELL_BLOCK))
        key = jnp.where(real, blk, nblk)
        packed = jnp.where(real, packed, PAD)
        blocks = jnp.arange(nblk, dtype=jnp.int32)
        counts = jnp.sum((key[:, :, None] == blocks).astype(jnp.int32),
                         axis=1)                          # (g, nblk)
        # the tile's spare slots (enough to pad every bucket, then whole
        # steps): slot e pads bucket #{cum <= e}; those past the last
        # need sort behind everything (key nblk)
        cum = jnp.cumsum((-counts) % SUB, axis=1)
        spare = jnp.arange(cap - per_tile, dtype=jnp.int32)
        spare_key = jnp.sum(
            (spare[None, :, None] >= cum[:, None, :]).astype(jnp.int32),
            axis=2)
        key, packed = lax.sort(
            (jnp.concatenate([key, spare_key], axis=1),
             jnp.concatenate([packed, jnp.full(spare_key.shape, PAD,
                                               jnp.int32)], axis=1)),
            dimension=1, num_keys=1)
        return (packed.reshape(g * cap // SUB, SUB),
                key[:, ::SUB].reshape(g * cap // STEP, SUBS),
                jnp.sum(counts))


@functools.partial(jax.jit, static_argnames=("cells",))
def steps_worked(fb, *, cells: int):
    """Steps (rows of ``fb``: ``SUBS`` sub-chunks) of the bucketed
    entries the kernel works: those whose first sub-chunk names a block
    that exists."""
    return jnp.sum(fb[:, 0] < num_blocks(cells))


# ----------------------------------------------------------------------
# the kernel
# ----------------------------------------------------------------------
def _bits(p, shift: int, mask: int):
    return lax.bitwise_and(lax.shift_right_logical(p, shift), mask)


def _hist_kernel(fb_ref, idx_ref, tab_ref, out_ref, tile_ref, lost_ref, *,
                 rows: int):
    """One grid step: one row tile, its live steps of SUBS sub-chunks in
    a loop.  ``idx_ref`` is the tile's slots, a sub-chunk a row;
    ``fb_ref`` (SMEM) the block of each of its sub-chunks and, behind
    them, the number of its steps that hold an entry (``bucket_group``'s
    marks are a tile's tail: the loop ends where it begins, and a step
    of the tail costs nothing, not even a turn of the grid).
    ``tab_ref[0]`` is ``(3 * R_LO, R_HI)`` bfloat16, row ``q * R_LO +
    rlo`` the grad (q = 0), hess (1) or level slot (2) of row ``rhi *
    R_LO + rlo``; ``out_ref[block]`` is ``(C_HI, 128)`` float32, column
    ``slot * 8 + channel * 4 + clo``, resident for the whole call.
    ``rows`` of W's 128 are the call's (its slots' eight each); the rest
    are zeros.

    A cell most rows hold takes a third of a million adds over 2^25
    rows, and a float32 sum of 4e5 that has drifted by a few units
    reads a child of no row as one of weight over ``min_child_weight``
    (found on the chip, PR 49: splits the reference finds ineligible).
    So a tile's sub-chunks add into the tile's own sums (``tile_ref``,
    small numbers, a few dozen adds a cell), and a tile's sums join the
    call's by a compensated add (Kahan: ``lost_ref`` keeps what the last
    add rounded away): the result is good to an ulp whatever the rows.
    Every tile zeroes its sums and joins them, whatever it holds."""
    nblk, subs = out_ref.shape[0], idx_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        lost_ref[...] = jnp.zeros_like(lost_ref)

    tile_ref[...] = jnp.zeros_like(tile_ref)

    def step(s, carry):
        cls = lax.broadcasted_iota(jnp.int32, (128, SUB), 0)
        cls_lo = lax.broadcasted_iota(jnp.int32, (R_LO, SUB), 0)
        q = lax.broadcasted_iota(jnp.int32, (rows, SUB), 0)
        key_q = lax.shift_right_logical(q, 3) * C_LO + lax.bitwise_and(q, 3)
        takes_hess = _bits(q, 2, 1) == 1
        table = tab_ref[0]
        blank = jnp.zeros((128 - rows, SUB), jnp.bfloat16)
        chunk = idx_ref[pl.ds(pl.multiple_of(s * SUBS, SUBS), SUBS), :]
        for b in range(SUBS):
            p = chunk[b:b + 1, :]
            picked = jnp.dot(table, _onehot(cls, _bits(p, 14, 127)),
                             preferred_element_type=jnp.float32)  # (96, SUB)
            mine = cls_lo == _bits(p, 9, 31)
            g, h, at = (jnp.sum(
                jnp.where(mine, picked[k * R_LO:(k + 1) * R_LO], 0.0),
                axis=0, keepdims=True) for k in range(3))
            # a row at no slot reads -1 and a slot of padding is given it:
            # neither matches a row of W
            key = jnp.where(
                p >= PAD, -1,
                at.astype(jnp.int32) * C_LO + lax.bitwise_and(p, 3))
            w = jnp.where(key_q == key, jnp.where(takes_hess, h, g),
                          0.0).astype(jnp.bfloat16)
            if rows < 128:
                w = jnp.concatenate([w, blank], axis=0)
            # a sub-chunk of the tile's tail in a step that is part live
            # adds its zeros to a block that exists
            sub = s * SUBS + b
            blk = jnp.minimum(fb_ref[sub // 128, sub % 128], nblk - 1)
            tile_ref[blk] = tile_ref[blk] + lax.dot_general(
                _onehot(cls, _bits(p, 2, 127)), w, _NT,
                preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, fb_ref[subs // 128, subs % 128], step, 0)

    def join(blk, carry):
        add = tile_ref[blk] - lost_ref[blk]
        total = out_ref[blk] + add
        lost_ref[blk] = (total - out_ref[blk]) - add
        out_ref[blk] = total
        return carry

    lax.fori_loop(0, nblk, join, 0)


@functools.partial(jax.jit, static_argnames=(
    "tiles", "nslots", "cells", "interpret"))
def hist_sparse(packed, fb, gh, slot, *, tiles: int, nslots: int,
                cells: int, interpret: bool):
    """``(nslots, num_blocks(cells) * CELL_BLOCK, 2)`` float32: for
    level slot ``s`` and cell ``c`` the (grad, hess) sums, the weights
    rounded to bfloat16, over the entries in ``c`` of the rows with
    ``slot == s``.  ``gh`` is ``(2, tiles * ROW_TILE)`` float32, ``slot``
    the rows' level slots (-1: none), ``nslots <= CALL_SLOTS``."""
    assert nslots <= CALL_SLOTS, nslots
    nblk, rows = num_blocks(cells), max(16, 8 * nslots)
    table = jnp.concatenate([gh, slot.astype(jnp.float32)[None]]).astype(
        jnp.bfloat16)
    table = table.reshape(3, tiles, R_HI, R_LO).transpose(1, 0, 3, 2).reshape(
        tiles, 3 * R_LO, R_HI)
    # a tile's SMEM rows: its sub-chunks' blocks, then its live steps
    subs = fb.shape[0] // tiles * SUBS
    fb = fb.reshape(tiles, subs)
    live = jnp.sum(fb[:, ::SUBS] < nblk, axis=1, dtype=jnp.int32)
    fb_rows = -(-(subs + 1) // (8 * 128)) * 8
    fb = jnp.pad(jnp.concatenate([fb, live[:, None]], axis=1),
                 ((0, 0), (0, fb_rows * 128 - subs - 1)))
    out = pl.pallas_call(
        functools.partial(_hist_kernel, rows=rows), grid=(tiles,),
        in_specs=[
            pl.BlockSpec((fb_rows, 128), lambda t: (t, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((subs, SUB), lambda t: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 3 * R_LO, R_HI), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((nblk, C_HI, 128), lambda t: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nblk, C_HI, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nblk, C_HI, 128), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name="hist_sparse",
    )(fb.reshape(tiles * fb_rows, 128), packed, table)
    out = out[..., :8 * nslots].reshape(nblk, C_HI, nslots, 2, C_LO)
    return out.transpose(2, 0, 1, 4, 3).reshape(
        nslots, nblk * CELL_BLOCK, 2)


# ----------------------------------------------------------------------
# the same sums by XLA's scatter (off the chip)
# ----------------------------------------------------------------------
def hist_sparse_xla(cells_t, gh, slot, nslots: int, cells: int):
    """:func:`hist_sparse` from the entries as the row move holds them,
    ``(width, n)`` int32 cells (-1: none), traceable, in float32 with
    the weights unrounded: one ``segment_sum`` over the entries."""
    size = num_blocks(cells) * CELL_BLOCK
    at = jnp.where((cells_t >= 0) & (slot >= 0)[None, :],
                   slot[None, :] * size + cells_t, nslots * size).reshape(-1)
    # a channel at a time: updates of two would be tiled out to 128 lanes
    return jnp.stack([jax.ops.segment_sum(
        jnp.broadcast_to(w[None, :], cells_t.shape).reshape(-1), at,
        num_segments=nslots * size + 1)[:-1] for w in gh],
        axis=-1).reshape(nslots, size, 2)


def coordinates(packed, fb, tiles: int):
    """Global (row, cell) of every slot of the bucketed entries, and
    whether it is one (not padding): what the kernel reads, for tests."""
    subs = packed.shape[0] // tiles
    tile = lax.broadcasted_iota(jnp.int32, (packed.shape[0], 1), 0) // subs
    return (tile * ROW_TILE + _bits(packed, 9, 4095),
            fb.reshape(-1, 1) * CELL_BLOCK + lax.bitwise_and(packed, 511),
            packed < PAD)

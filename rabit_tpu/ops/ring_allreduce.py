"""Pallas TPU ring allreduce — explicit ICI ring with remote DMA.

The reference's allreduce is a pipelined tree over TCP with per-link ring
buffers and chunked streaming (reference: src/allreduce_base.cc:326-491,
ring buffers src/allreduce_base.h:256-295).  On TPU the same
bandwidth-optimal idea is a ring over the ICI torus: ``ndev - 1``
reduce-scatter hops followed by ``ndev - 1`` all-gather hops, each hop a
remote DMA to the right neighbour overlapping the VPU combine.  XLA's
built-in ``psum`` already schedules rings; this kernel is the explicit
version for cases XLA does not fuse well (very large payloads, custom
hop/compute overlap) and the blueprint for hand-scheduled collectives.

Flow control: the naive two-slot double buffer in a ring can be clobbered
when a sender runs more than two hops ahead of its right neighbour (the
progress chain around the ring only bounds the lead by ``ndev - 1``).
Each hop therefore acknowledges consumption: after folding slot ``s`` into
the accumulator the receiver signals the sender's capacity semaphore, and
a sender re-entering slot ``s`` first waits for that ack — the same
credit scheme the reference gets implicitly from TCP flow control on its
per-link ring buffers (reference: src/allreduce_base.cc:399-441).

Works under ``shard_map`` on a real TPU mesh, and on the CPU backend via
the distributed TPU interpreter (``pltpu.InterpretParams``) for tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rabit_tpu.ops import on_tpu
from rabit_tpu.ops.reduce_ops import ReduceOp

_LOGICAL = pltpu.DeviceIdType.LOGICAL
_NSLOTS = 2
_LANES = 128

_COMBINE = {
    ReduceOp.SUM: jnp.add,
    ReduceOp.MAX: jnp.maximum,
    ReduceOp.MIN: jnp.minimum,
    ReduceOp.PROD: jnp.multiply,
}

# Budget for the two full-payload VMEM buffers (x + out); the comm slots
# add 2/ndev of one of them on top.  Larger payloads are segmented by the
# wrapper.  The kernel asks Mosaic for exactly what it uses plus
# headroom (``vmem_limit_bytes``) rather than relying on the scoped
# default (16 MiB on v5e), so the check against the chip's limit is the
# compiler's own.
_VMEM_BUDGET_BYTES = 8 << 20
_VMEM_HEADROOM_BYTES = 4 << 20


def supported_ops():
    """Ops the ring kernel can combine (the engine's pallas_ring
    device-impl routes only these through the kernel)."""
    return frozenset(_COMBINE)


def _ring_kernel(x_ref, out_ref, comm_ref, send_sem, recv_sem, cap_sem,
                 *, ndev: int, combine, axis_name: str):
    """One full allreduce: reduce-scatter then all-gather on a ring.

    Refs: ``x_ref``/``out_ref`` are (ndev, rows, 128) in VMEM;
    ``comm_ref`` is the (_NSLOTS, rows, 128) landing pad written by the
    left neighbour.  The per-hop chunk is selected on the LEADING,
    untiled dimension: Mosaic tiles the last two dimensions (sublanes x
    lanes), so a one-row slice of a 2-D (ndev, chunk) buffer is refused
    on the chip ("Slice shape along dimension 0 must be aligned to
    tiling") although the interpreter accepts it.
    """
    my_id = lax.axis_index(axis_name)
    right = lax.rem(my_id + 1, ndev)
    left = lax.rem(my_id + ndev - 1, ndev)

    out_ref[:] = x_ref[:]

    # Neighbour barrier: both sides' comm buffers must exist before any
    # remote DMA lands (guide pattern; collective_id scopes the sem).
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                           device_id_type=_LOGICAL)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                           device_id_type=_LOGICAL)
    pltpu.semaphore_wait(barrier, 2)

    nphase = ndev - 1  # hops per phase

    def hop(step, _):
        slot = lax.rem(step, _NSLOTS)
        is_rs = step < nphase
        s2 = step - nphase
        # reduce-scatter walks chunks backwards from my own; all-gather
        # then circulates the finished chunks (device i finishes chunk
        # (i+1) % ndev after the RS phase).
        send_idx = jnp.where(is_rs,
                             lax.rem(my_id - step + 2 * ndev, ndev),
                             lax.rem(my_id + 1 - s2 + 2 * ndev, ndev))
        recv_idx = jnp.where(is_rs,
                             lax.rem(my_id - step - 1 + 2 * ndev, ndev),
                             lax.rem(my_id - s2 + 2 * ndev, ndev))

        # credit: slot must have been drained by the right neighbour
        @pl.when(step >= _NSLOTS)
        def _():
            pltpu.semaphore_wait(cap_sem.at[slot], 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[send_idx],
            dst_ref=comm_ref.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=right,
            device_id_type=_LOGICAL,
        )
        rdma.start()
        rdma.wait()

        incoming = comm_ref[slot]
        out_ref[recv_idx] = jnp.where(
            is_rs, combine(out_ref[recv_idx], incoming), incoming)

        # ack to the sender (my left neighbour): slot drained
        pltpu.semaphore_signal(cap_sem.at[slot], inc=1, device_id=left,
                               device_id_type=_LOGICAL)
        return 0

    lax.fori_loop(0, 2 * nphase, hop, 0)

    # Drain outstanding acks from the right neighbour so no semaphore is
    # left non-zero at kernel exit (the last _NSLOTS sends are never
    # re-entered, but their acks still arrive).
    def drain(slot, _):
        pltpu.semaphore_wait(cap_sem.at[slot], 1)
        return 0

    lax.fori_loop(0, min(_NSLOTS, 2 * nphase), drain, 0)


def _segment_allreduce(seg, axis_name, op, interpret, collective_id):
    """Ring-allreduce one (ndev, rows, 128) segment resident in VMEM."""
    ndev, rows, _ = seg.shape
    kern = functools.partial(_ring_kernel, ndev=ndev, combine=_COMBINE[op],
                             axis_name=axis_name)
    vmem_bytes = (2 * ndev + _NSLOTS) * rows * _LANES * seg.dtype.itemsize
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(seg.shape, seg.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((_NSLOTS, rows, _LANES), seg.dtype),
            pltpu.SemaphoreType.DMA((_NSLOTS,)),
            pltpu.SemaphoreType.DMA((_NSLOTS,)),
            pltpu.SemaphoreType.REGULAR((_NSLOTS,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id,
            vmem_limit_bytes=vmem_bytes + _VMEM_HEADROOM_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seg)


def ring_allreduce_pallas(x: jax.Array, axis_name: str,
                          op: ReduceOp = ReduceOp.SUM,
                          interpret: bool | None = None,
                          collective_id: int = 7) -> jax.Array:
    """Allreduce ``x`` (same shape on every device) along ``axis_name``.

    Call inside ``shard_map``.  Pads the flattened payload to
    ``ndev`` chunks of whole (sublane, 128-lane) tiles, runs the ring
    kernel per VMEM-sized segment, and restores the original shape.
    ``interpret`` defaults to :func:`rabit_tpu.ops.on_tpu` (interpreted
    off-TPU so tests run on the CPU mesh).
    """
    if op not in _COMBINE:
        raise ValueError(f"ring_allreduce_pallas: unsupported op {op}")
    ndev = lax.axis_size(axis_name)
    if ndev == 1:
        return x
    if interpret is None:
        interpret = not on_tpu()

    flat = x.reshape(-1)
    size = flat.shape[0]
    itemsize = flat.dtype.itemsize
    # one chunk per device, in whole tiles: 8 sublanes of 32-bit words,
    # 16 of 16-bit, 32 of 8-bit — so no hop moves a partial tile
    sublanes = 8 * max(1, 4 // itemsize)
    rows = -(-size // (ndev * _LANES))
    rows = -(-rows // sublanes) * sublanes

    # segment so (x + out) stays inside the VMEM budget
    nseg = max(1, -(-2 * ndev * rows * _LANES * itemsize
                    // _VMEM_BUDGET_BYTES))
    seg_rows = -(-rows // (sublanes * nseg)) * sublanes
    nseg = -(-rows // seg_rows)

    padded = jnp.zeros((ndev * nseg * seg_rows * _LANES,), flat.dtype
                       ).at[:size].set(flat)
    segs = padded.reshape(ndev, nseg, seg_rows, _LANES)
    outs = [_segment_allreduce(segs[:, s], axis_name, op, interpret,
                               collective_id)
            for s in range(nseg)]
    out = jnp.stack(outs, axis=1).reshape(-1)[:size]
    return out.reshape(x.shape)

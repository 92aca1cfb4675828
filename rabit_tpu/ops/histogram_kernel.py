"""Fused Pallas kernel for the XGBoost gradient-histogram pass.

Measured with chained difference timing (a data-dependent chain inside
one program, long minus short — doc/benchmarks.md): the XLA one-hot
formulation takes ~30 ms for 262k x 64 x 256 (N=2 output lanes leave
the MXU ~2% occupied); this kernel runs the same histogram in ~0.8 ms
(~37x) at ~100% MXU occupancy of its fpg-fold-inflated FLOPs, and
generalizes to an (nw, n) weight matrix (any number of grad/hess/node
channels) that builds every channel's histogram in ONE bins pass: the
bin one-hots are built once per feature group and contracted against
each weight row, so a GBDT tree level costs ~0.4 ms per channel
instead of a 30 ms XLA pass per node.

MXU structure (per feature group, per row block):

* **Two-level bins.**  Split each bin index ``b`` into ``b = bh*lo+bl``
  (``hi`` x ``lo``, powers of two, e.g. 16x16 for 256 bins).  The
  histogram of feature ``j`` is an outer product of two small one-hots:
  ``hist_j[bh, bl] = sum_r w_r * [hi_rj==bh] * [lo_rj==bl]``.
* **Feature packing.**  Stack ``fpg = 128//lo`` features' hi-one-hots
  along M and lo-one-hots along N: ``C = (A*w) @ B^T`` with A
  ``(fpg*hi, block)``, B ``(fpg*lo, block)`` -> C ``(fpg*hi, fpg*lo)``.
  Only the diagonal feature blocks of C are wanted (cross-feature
  terms are discarded), an ``fpg``-fold compute inflation — but at
  ~100% MXU tile occupancy, far better than the N=2 exact formulation.
* **Layout.**  Both one-hots are built directly in transposed
  ``(class, row)`` layout from a pre-transposed ``(f, n)`` bins array
  (broadcast-iota compare; the kmeans-kernel lesson — never relayout
  inside the kernel), and the matmul is the MXU-native NT form.  The
  raw per-group C products are accumulated in VMEM across row blocks;
  the cheap diagonal-block extraction runs in XLA afterwards.

Like the kmeans kernel the weight operand is rounded to a compute
dtype (default bf16; one-hots are exact in bf16).  Summing n values
each with independent ~2^-9 relative rounding error gives a relative
error on a bin sum of ~2^-9/sqrt(n_bin) — invisible to split-gain
comparisons except at exactly-cancelling bins, where the absolute
error is what matters and stays tiny.  ``compute_dtype=float32`` uses
``Precision.HIGHEST`` (the MXU rounds f32 matmul operands to bf16 at
default precision) for an exact path at ~3x the MXU cost.

Reference analogue: the histogram allreduce is the headline XGBoost
config in BASELINE.md; the reference ships only the collective
(reference: src/allreduce_base.cc) — the builder itself is the app's
job, done here the TPU way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rabit_tpu.ops import on_tpu

_VMEM_LIMIT_BYTES = 100 << 20
_DEFAULT_BLOCK = 2048
_MAX_CHANNELS = 64
# The widest call that stays on the kernel's own line.  Measured on a
# v5e at 32 x 33.6M rows, 256 bins (builders' chip runs, PR 27 and
# PR 30): a call costs 10.3 ms + 22.45 ms a channel at 2, 4, 8, 16, 20,
# 24 and 28 channels (0.3698 s at 16) and leaves that line at 32
# (0.9444 s where two calls of 16 cost 0.7397 s) and 64 (1.6315 s, four
# of 16 1.4794 s).  A tree level's channels are a power of two, so 16
# splits every wider level into equal calls of one shape.
_LINE_CHANNELS = 16
# feature groups up to which the kernel's body holds a copy a group (28
# to 64 features: the shapes measured above); past it the body loops
_UNROLL_GROUPS = 8
# the weight operand's type where a caller names none (one-hots are
# exact in it; every sum is accumulated in float32)
DEFAULT_COMPUTE_DTYPE = jnp.bfloat16


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _next_pow2(v: int) -> int:
    p = 1
    while p < v:
        p <<= 1
    return p


def max_channels(nbin: int, f: int) -> int:
    """The widest call worth issuing for this shape, in weight channels:
    the smaller of what the (ngroups, nw, fpg*hi, fpg*lo) f32 VMEM
    accumulator's budget holds and the width up to which a call's time
    is linear in its channels (``_LINE_CHANNELS``: a 32-channel call
    costs 0.2 s more than two of 16).  ``learn.histogram.level_hist``
    builds a wider level in calls of this width, so wide-feature deep
    levels chunk harder rather than failing the accumulator bound."""
    hi, lo, fpg, ngroups = plan(nbin, f)
    per_channel = ngroups * fpg * hi * fpg * lo * 4
    return max(1, min(_LINE_CHANNELS,
                      (_VMEM_LIMIT_BYTES // 2) // per_channel))


def plan(nbin: int, f: int):
    """(hi, lo, fpg, ngroups) decomposition for an (f, nbin) histogram.

    ``hi*lo`` is ``nbin`` padded to a power of two with ``hi >= lo``;
    ``fpg = 128 // lo`` features share one matmul so the N dimension
    fills 128 lanes exactly.

    ``nbin`` counts the bins of present values only, with and without
    missing values in the data: an absent entry carries the code
    ``nbin`` (``learn.histogram.apply_cuts``), which either matches no
    one-hot row (256 under the 16 x 16 plan: ``256 >> 4`` is no ``hi``
    class) or lands in the power-of-two padding that the caller slices
    off, so it is added to no bin and costs no slot.  A 257th slot
    would pad to 512 and double every product of the kernel.
    """
    nbp = _next_pow2(max(nbin, 4))
    bits = nbp.bit_length() - 1
    lo = 1 << (bits // 2)
    hi = nbp // lo
    fpg = max(1, 128 // lo)
    ngroups = -(-f // fpg)
    return hi, lo, fpg, ngroups


def _hist_kernel(bins_t_ref, w_ref, *rest,
                 hi: int, lo: int, fpg: int, ngroups: int, nslots: int):
    """One row block: the one-hots of every feature group against every
    weight channel, added into the VMEM-resident output.

    With ``nslots`` > 0 a node operand follows the weights and channel
    ``s * nw + c`` is weight row ``c`` of the rows whose node is ``s``
    (everything else weighs 0): the level's node masks are made here,
    a row block at a time, and never exist in HBM."""
    node_ref, out_ref = rest if nslots else (None, rest[0])
    i = pl.program_id(0)
    block = w_ref.shape[1]
    w = w_ref[:]                                   # (nw, block) compute dtype
    lo_shift = lo.bit_length() - 1
    lo_mask = lo - 1
    cdt = w.dtype
    prec = (lax.Precision.HIGHEST if cdt == jnp.float32
            else lax.Precision.DEFAULT)
    rows = [w[c:c + 1, :] for c in range(w.shape[0])]
    if nslots:
        node = node_ref[:]                         # (1, block) int32
        zero = jnp.zeros((), cdt)
        rows = [jnp.where(node == s, r, zero)
                for s in range(nslots) for r in rows]

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def group(grp, carry=None):
        start = grp * fpg
        if not isinstance(grp, int):
            start = pl.multiple_of(start, fpg)
        bt = bins_t_ref[pl.ds(start, fpg), :]                # (fpg, block)
        bh = lax.shift_right_logical(bt, lo_shift)
        bl = lax.bitwise_and(bt, lo_mask)
        # one-hots built once per group in (class, row) layout, shared
        # by every weight channel — no relayout, no extra HBM traffic
        hi_iota = lax.broadcasted_iota(jnp.int32, (fpg, hi, block), 1)
        a = (bh[:, None, :] == hi_iota).astype(cdt)
        a = a.reshape(fpg * hi, block)                       # (M, block)
        lo_iota = lax.broadcasted_iota(jnp.int32, (fpg, lo, block), 1)
        b = (bl[:, None, :] == lo_iota).astype(cdt)
        b = b.reshape(fpg * lo, block)                       # (N, block)
        for c, row in enumerate(rows):
            # MXU-native NT matmul: contract over the row dimension
            out_ref[grp, c] += lax.dot_general(
                a * row, b, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
        return carry

    if ngroups <= _UNROLL_GROUPS:
        for grp in range(ngroups):
            group(grp)
    else:
        # a wide shard (121 groups at 968 features): one body, looped.
        # Unrolled, each of a boosting job's 13 kernel instances took
        # the chip's compiler half a minute and the lowering seconds
        lax.fori_loop(0, ngroups, group, None)


@functools.partial(
    jax.jit,
    static_argnames=("nbin", "block", "interpret", "compute_dtype",
                     "plan_override", "nslots"))
def _hist_multi(bins_t, weights, node, nbin: int, block: int,
                interpret: bool, compute_dtype,
                plan_override=None, nslots: int = 0) -> jax.Array:
    f, n = bins_t.shape
    nw = weights.shape[0] * max(nslots, 1)
    if plan_override is None:
        hi, lo, fpg, ngroups = plan(nbin, f)
    else:
        hi, lo, fpg = plan_override
        if hi * lo < nbin:
            raise ValueError(f"plan {plan_override}: hi*lo < nbin={nbin}")
        if lo & (lo - 1):
            # the kernel decomposes bins with shift/mask — a non-pow2 lo
            # would silently scatter counts into wrong bins
            raise ValueError(f"plan {plan_override}: lo must be a "
                             "power of two")
        ngroups = -(-f // fpg)
    # The whole (ngroups, nw, fpg*hi, fpg*lo) f32 accumulator is one
    # VMEM-resident output block: validate the combined bound up front
    # (wide-feature many-node levels can exceed it) with a clear error
    # instead of a compile-time OOM.
    out_bytes = ngroups * nw * fpg * hi * fpg * lo * 4
    if out_bytes > _VMEM_LIMIT_BYTES // 2:
        raise ValueError(
            f"histogram accumulator needs {out_bytes >> 20} MB of VMEM "
            f"(ngroups={ngroups} x nw={nw} x {fpg * hi} x {fpg * lo} f32) "
            f"> {(_VMEM_LIMIT_BYTES // 2) >> 20} MB budget — chunk the "
            "channels (build_level_local does) or the features across "
            "calls")
    fpad = ngroups * fpg
    npad = _round_up(n, block)
    cdt = jnp.dtype(compute_dtype)

    # no copy for input staged at (fpad, n) int32: zero-width pads and
    # same-type casts return their input.  The rows are not padded to
    # the block: the last block of a ragged n reads past the array, and
    # whatever it finds there meets a weight of 0 and a node of -1,
    # which the (small) operands below are padded with
    bt = jnp.pad(bins_t.astype(jnp.int32), ((0, fpad - f), (0, 0)))
    operands = [bt, jnp.pad(weights.astype(cdt), ((0, 0), (0, npad - n)))]
    in_specs = [
        pl.BlockSpec((fpad, block), lambda i: (0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((weights.shape[0], block), lambda i: (0, i),
                     memory_space=pltpu.VMEM),
    ]
    if nslots:
        # padded rows sit at node -1: in no slot
        operands.append(jnp.pad(
            node.astype(jnp.int32).reshape(1, n), ((0, 0), (0, npad - n)),
            constant_values=-1))
        in_specs.append(pl.BlockSpec((1, block), lambda i: (0, i),
                                     memory_space=pltpu.VMEM))

    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    raw = pl.pallas_call(
        functools.partial(_hist_kernel, hi=hi, lo=lo, fpg=fpg,
                          ngroups=ngroups, nslots=nslots),
        grid=(npad // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (ngroups, nw, fpg * hi, fpg * lo), lambda i: (0, 0, 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (ngroups, nw, fpg * hi, fpg * lo), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(*operands)

    # diagonal-block extraction (tiny, plain XLA): feature j of group g,
    # channel c lives at raw[g, c, j*hi:(j+1)*hi, j*lo:(j+1)*lo]
    c = raw.reshape(ngroups, nw, fpg, hi, fpg, lo)
    idx = jnp.arange(fpg)
    diag = c[:, :, idx, :, idx, :]         # (fpg, ngroups, nw, hi, lo)
    diag = diag.transpose(2, 1, 0, 3, 4)   # (nw, ngroups, fpg, hi, lo)
    return diag.reshape(nw, fpad, hi * lo)[:, :f, :nbin]


def default_block(n: int) -> int:
    """Row-block size: 2048 saturates the MXU pipeline; shrink for
    small inputs so padding stays bounded."""
    return min(_DEFAULT_BLOCK, _round_up(max(n, 1), 128))


def hist_fused_multi(bins_t, weights, nbin: int, block: int | None = None,
                     interpret: bool | None = None,
                     compute_dtype=DEFAULT_COMPUTE_DTYPE,
                     plan_override: tuple | None = None,
                     node_of_row=None, nslots: int = 0) -> jax.Array:
    """(nw, f, nbin) histograms of ``nw`` weight channels in one pass.

    ``bins_t`` is the TRANSPOSED (f, n) int32 bins array (the layout
    the kernel streams; keep it resident on device across calls —
    boosting reuses it for every node, level and round).  ``weights``
    is (nw, n); each row gets its own (f, nbin) histogram.  Extra
    channels share the single bins read, so per-level node histograms
    cost one HBM pass instead of one per node.

    A tree level passes ``node_of_row`` (n,) int32 and ``nslots``: the
    result is then ``(nslots * nw, f, nbin)``, slot-major, channel
    ``s * nw + c`` being weight row ``c`` over the rows at node ``s``
    (a row at any other node, -1 say, is in no histogram).  The masks
    are made inside the kernel from 4 bytes a row; no (channels, n)
    weight matrix is written to HBM.
    """
    if interpret is None:
        interpret = not on_tpu()
    f, n = bins_t.shape
    nw = weights.shape[0] * max(nslots, 1)
    if not 1 <= nw <= _MAX_CHANNELS:
        raise ValueError(f"nw={nw} out of range [1, {_MAX_CHANNELS}]")
    if block is None:
        block = default_block(n)
    block = min(block, _round_up(n, 128))
    return _hist_multi(jnp.asarray(bins_t), jnp.asarray(weights),
                       None if not nslots else jnp.asarray(node_of_row),
                       nbin, block, interpret,
                       jnp.dtype(compute_dtype).name,
                       plan_override=plan_override, nslots=nslots)


def hist_fused(bins, grad, hess, nbin: int, block: int | None = None,
               interpret: bool | None = None,
               compute_dtype=DEFAULT_COMPUTE_DTYPE) -> jax.Array:
    """(f, nbin, 2) gradient/hessian histogram of binned features.

    ``bins`` is (n, f) int32 in [0, nbin); ``grad``/``hess`` are (n,)
    weights.  Convenience wrapper over :func:`hist_fused_multi` with
    two channels (transposes ``bins`` internally — callers with the
    (f, n) layout at hand should call the multi variant directly).
    """
    bins = jnp.asarray(bins)
    w = jnp.stack([jnp.asarray(grad), jnp.asarray(hess)])
    out = hist_fused_multi(bins.T, w, nbin, block=block,
                           interpret=interpret,
                           compute_dtype=compute_dtype)
    return out.transpose(1, 2, 0)

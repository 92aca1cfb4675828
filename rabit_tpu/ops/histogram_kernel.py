"""Fused Pallas kernels for the XGBoost gradient-histogram pass.

One sum, two tilings of it on the MXU, and a rule that picks between
them from a call's static shapes (:func:`level_plan`).  Both build
every channel's histogram in ONE bins pass, a tree level's node masks
folded into the weights inside the kernel a row block at a time.  One
operand layout and one way in: a tree level's ``(2, n)`` or ``(T, 2,
n)`` weights, its node ids and ``nslots``, through
``learn.histogram.level_hist`` to :func:`hist_fused_multi`.

**The two-level body** (``_hist_kernel``; narrow calls).  The plain
one-hot product ``onehot(bins) @ w`` with w of N=2 channels leaves the
MXU's lanes 2% occupied (XLA's takes ~30 ms for 262k x 64 x 256;
chained difference timing, doc/benchmarks.md); this body runs the same
histogram in ~0.8 ms (~37x) at ~100% MXU occupancy of its
fpg-fold-inflated FLOPs.  Per feature group, per row block:

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

Its price is a product a feature group AND CHANNEL: on a v5e 10.3 ms +
22.45 ms a channel over 33.6M rows of 28 features (1.67e-10 s a row,
group and channel: the MXU's peak, of products seven eighths of which
are thrown away), linear in the call's width.

**The lane-wide body** (``_lane_kernel``; wide levels).  The plain
product after all, for callers that fill its lanes: a level of 16
slots is 32 channels, a round of seven trees 14 to 224.  Per feature
``hist_f (classes, lanes) = onehot_f (classes, block) @ W (lanes,
block)^T``, W the level's masked weights with the CHANNELS ON THE LANES
(every tree, slot, grad and hess of the level a lane; zero rows up to
a multiple of 128), one NT product a feature whatever the width and no
diagonal to throw away: 3.44e-10 s a row and feature for up to 128
lanes (0.324 s over the same 33.6M rows of 28 features at 2 channels
as at 128), the MXU's own 4,096 cycles a feature and block.  Its f32
accumulator is ``(features, classes, lanes)`` in VMEM; of a shard of
more features than the budget holds (968 x 256 x 128 x 4 B is 127 MB)
it is a chunk's, the chunks a second grid axis outside the row blocks
(:func:`lane_chunk`): the bins are still read once a call, a feature's
adds are the same adds in the same order, and a shard whose features
fit one chunk has the one-axis call it always had.  The two bodies
cross at 14 channels (``_LANE_CROSSING``, with the measured table).

**The packed segment** (``_packed_product``; the lane-wide body's
narrow features).  That body charges by the class: 256 rows of one-hot
a feature whatever the feature holds, and a column of two values fills
two of them.  Which codes a feature can take is written in its cuts
(:func:`feature_codes`: 0 and the end of every run of equal cuts), so a
second rule, from the cuts only (:func:`pack_plan`), names the features
of at most ``_NARROW_CODES`` codes, and a one-axis lane-wide call builds
them all in ONE product: feature ``k`` has rows ``[k * w, (k + 1) * w)``
of a shared class axis, row ``k * w + i`` of the multi-hot operand being
``bins == codes[k, i]``, no cross terms and nothing thrown away (an
indicator column costs 4 rows where it cost 256).  The indices and
``w`` are shapes of the program; the codes are a small runtime operand,
so a job's cuts key no compile.  XLA scatters the segment's rows back to
``(features, nbin, lanes)`` after the call, and everything downstream
reads the layout it always read, bit for bit.  Its price is its rows':
a call of 10 features and 44 narrow ones of 4 codes costs 11 products
and 3 ms, 0.0352 s on 128 lanes over 8.4M rows where 54 products cost
0.1585 (``_NARROW_CODES``, with the measured table).  A wide shard's
chunked call and the two-level body build every feature's own product
still.

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
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rabit_tpu.ops import on_tpu

_VMEM_LIMIT_BYTES = 100 << 20
_DEFAULT_BLOCK = 2048
# The widest call that stays on the kernel's own line, and the widest
# the two-level body takes.  Measured on a v5e at 32 x 33.6M rows, 256
# bins (builders' chip runs, PR 27 and PR 30): a call costs 10.3 ms +
# 22.45 ms a channel at 2, 4, 8, 16, 20, 24 and 28 channels (0.3698 s
# at 16) and left that line at 32 (0.9444 s where two calls of 16 cost
# 0.7397 s) and 64 (1.6315 s, four of 16 1.4794 s), which the body took
# until PR 45.  A tree level's channels are a power of two, so 16
# splits every wider level into equal calls of one shape.
_LINE_CHANNELS = 16
# feature groups up to which the kernel's body holds a copy a group (28
# to 64 features: the shapes measured above); past it the body loops
_UNROLL_GROUPS = 8
# The lane-wide body (``_lane_kernel``): the widest call in lanes, the
# features of one looped step of its body, and the lanes a tree's
# channels are padded to (the bf16 sublane tile, so that the trees'
# rows of the weight operand join on tile boundaries).
_LANE_WIDTH = 256
_LANE_FEATURES = 8
_LANE_TREE_ROWS = 16
# The lane-wide body's f32 accumulator, one grid step's: a third of the
# VMEM limit, because the pipeline holds the output block twice and the
# last third is the bins' blocks (twice too) and the body's one-hots.
# Measured on a v5e at 968 features x 1,183,747 rows, 128 lanes
# (builder's chip run, PR 44): chunks of 88, 168, 248 and 328 features
# (11 to 43 MB) read 0.3922 to 0.3933 s a call alike and equal bit for
# bit; 400 (twice 50 MB) do not fit.
_LANE_ACC_BYTES = _VMEM_LIMIT_BYTES // 3
# The level width, in channels, from which the lane-wide body is the
# cheaper one.  Measured on a v5e (tools/hist_kernel_check.py, builder's
# chip run, PR 43; seconds a level, host clock around the call), 256
# bins.  28 features staged as 32, 33.6M rows, one tree: the two-level
# body 0.0603, 0.1058, 0.1956, 0.3754 at 2, 4, 8, 16 channels, then
# calls of 16: 0.7487, 1.4957, 2.9890 at 32, 64, 128 (its line, above);
# the lane-wide body 0.3237, 0.3232, 0.3236, 0.3235, 0.3236, 0.3247,
# 0.3262 at the same widths: one price to 128 lanes, 3.44e-10 s a row
# and feature, the MXU's own 4,096 cycles a feature and block of 2,048
# (0.313 s).  The lines cross at 13.7 channels.  54 features staged as
# 56, 8.4M rows: one tree 0.0274, 0.0467, 0.0865, 0.1652, 0.3273,
# 0.6511, 1.2988 against 0.1556 to 0.1567 (crossing 15.0: the two-level
# body pays for 56 features there, the lane-wide one for 54); seven
# trees of 1, 2, 4, 8, 16 slots (14 to 224 channels) 0.1760, 0.3140,
# 0.5890, 1.1390, 2.2740 in 7 to 14 calls against 0.1589, 0.1586,
# 0.1585, 0.1585 in one call of 128 lanes and 0.3094 in one of 256.  A
# tree's level is a power of two and a forest's a multiple of its trees,
# so 14 sends 16 channels of one tree and 14 of seven to the lane-wide
# body and leaves 8 and 12 to the two-level one, which is right at both
# shapes.  968 features, 1,183,747 rows, 81% of the entries absent
# (builder's chip run, PR 44): the two-level body 0.0721, 0.1220, 0.2363,
# 0.4506, 0.8949, 1.7681, 3.5297 in 1, 1, 2, 3, 6, 11, 22 calls of at
# most 6 channels; the lane-wide body 0.3928, 0.3925, 0.3925, 0.3921,
# 0.3926, 0.3936, 0.3953, its features in chunks (3.43e-10 s a row and
# feature again).  Both costs are linear in the features and the lines
# cross at 13.8 channels: the same 14 serves.
_LANE_CROSSING = 14
# The most codes a feature may hold to be *narrow*: to share the packed
# product of the lane-wide body (:func:`pack_plan`), in a segment as tall
# as the next power of two.  Measured on a v5e (tools/hist_kernel_check.py
# --cases packed, builder's chip run, PR 48; seconds a call, host clock
# around the call with its scatter back), 8.4M rows x 54 features of 256
# bins, seven trees, 10 features with a product of their own and 44 in
# segments of w rows: 0.0339, 0.0350, 0.0367, 0.0407 at w = 2, 4, 8, 16
# on 128 lanes and 0.0634, 0.0657, 0.0688, 0.0772 on 256, where the call
# without a plan reads 0.1585 and 0.3095 and equals each of them bit for
# bit.  That is 10 + 44 w / 256 products of the body's 2.88 ms (5.6 ms
# on 256 lanes) and about 3 ms: a code costs a 256th of a product and
# of its compares, so a segment pays as long as it is shorter than the
# feature's own 256 rows.  What bounds the constant is the body's size,
# 8 rows of the shared axis an unrolled tile (compiled in 2 to 3 s at
# every w measured); past 16 nothing was measured.
_NARROW_CODES = 16
# the code that pads a narrow feature's list: no row holds it (the bins
# end at ``nbin``, the absent code) and the scatter back drops it
_NO_CODE = 1 << 30
# rows of the shared class axis one product of the packed segment takes
# (a wide feature's product is ``classes`` = 256 of them)
_PACK_ROWS = 256
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
    """The widest call of the two-level body worth issuing for this
    shape, in weight channels: the smaller of what the (ngroups, nw,
    fpg*hi, fpg*lo) f32 VMEM accumulator's budget holds and the width
    up to which a call's time is linear in its channels
    (``_LINE_CHANNELS``: a 32-channel call costs 0.2 s more than two of
    16).  Where a level is that body's (:func:`level_plan`: under the
    lane-wide body's crossing), ``learn.histogram.level_hist`` builds a
    wider one in calls of this width, so a wide shard's levels (6
    channels a call at 968 features) chunk harder rather than failing
    the accumulator bound."""
    hi, lo, fpg, ngroups = plan(nbin, f)
    per_channel = ngroups * fpg * hi * fpg * lo * 4
    return max(1, min(_LINE_CHANNELS,
                      (_VMEM_LIMIT_BYTES // 2) // per_channel))


def lane_width(nbin: int, f: int) -> int:
    """Lanes of the widest lane-wide call at this shape, a multiple of
    128 up to ``_LANE_WIDTH``: what the f32 VMEM accumulator's budget
    holds of the features one grid step has.  A wide shard's features go
    chunk by chunk (:func:`lane_chunk`), so the smallest chunk decides
    and 968 features read what 28 do; 0 only where ``nbin`` is so large
    that ``_LANE_FEATURES`` features of 128 lanes do not fit."""
    per_lane = min(f, _LANE_FEATURES) * _round_up(nbin, 16) * 4
    return min(_LANE_WIDTH, _LANE_ACC_BYTES // per_lane // 128 * 128)


def lane_chunk(nbin: int, f: int, lanes: int) -> int:
    """Features one grid step of a lane-wide call of ``lanes`` lanes
    holds, in whole groups of ``_LANE_FEATURES``: ``f`` shared out
    evenly over the fewest chunks whose ``(chunk, classes, lanes)`` f32
    accumulator fits ``_LANE_ACC_BYTES``.  All of them where they fit
    (28 to 56 features at 256 lanes: one chunk, no second grid axis);
    968 features at 128 lanes go in 4 chunks of 248."""
    groups = -(-f // _LANE_FEATURES)
    per_group = _LANE_FEATURES * _round_up(nbin, 16) * lanes * 4
    chunks = -(-groups // max(1, _LANE_ACC_BYTES // per_group))
    return -(-groups // chunks) * _LANE_FEATURES


def lane_rows(nslots: int) -> int:
    """Lanes a tree of ``nslots`` level slots takes in a lane-wide call:
    its (grad, hess) channels padded to ``_LANE_TREE_ROWS``."""
    return _round_up(2 * nslots, _LANE_TREE_ROWS)


def call_lanes(trees: int, nslots: int) -> int:
    """Lanes of the lane-wide call that holds ``trees`` trees of
    ``nslots`` slots: whole MXU widths of 128."""
    return _round_up(trees * lane_rows(nslots), 128)


class LevelPlan(NamedTuple):
    """How a tree level's channels go to the kernel: the body, and the
    trees and the level slots of each that one call builds."""
    lane: bool
    trees: int
    slots: int


def level_plan(nbin: int, f: int, nslots: int, trees: int = 1) -> LevelPlan:
    """The one rule, from shapes only: a level of ``trees`` trees of
    ``nslots`` slots of (grad, hess) is ``2 * trees * nslots`` channels.
    The two-level body costs a call's channels (calls of
    :func:`max_channels`, a tree a call); the lane-wide body costs the
    same for any width up to 128 lanes.  Both are linear in the
    features, so the crossing is one number of channels at 28 features
    and at 968.  Under ``_LANE_CROSSING`` channels (and wherever
    :func:`lane_width` is 0) the level is the two-level body's; from it
    on the lane-wide body's, as many trees a call as its lanes hold at
    ``_LANE_TREE_ROWS`` lanes a tree or more (or, of a tree wider than
    a call, ``lane_width // 2`` slots)."""
    width = lane_width(nbin, f)
    if width and 2 * trees * nslots >= _LANE_CROSSING:
        slots = min(nslots, width // 2)
        return LevelPlan(True, min(trees, width // lane_rows(slots)), slots)
    return LevelPlan(False, 1, max(1, max_channels(nbin, f) // 2))


class PackPlan(NamedTuple):
    """Which features of a job share one product of the lane-wide body
    (:func:`pack_plan`): their indices, ascending, and the rows of the
    shared class axis each takes (a power of two)."""
    narrow: tuple
    width: int


def feature_codes(cuts) -> list:
    """For every feature of ``cuts (f, nbin - 1)`` the sorted codes its
    present values can take under ``learn.histogram.apply_cuts`` (and
    ``stage_bins``, which counts the cuts at or under a value): 0, and
    for every cut the number of the feature's cuts at or under it, which
    for sorted cuts is ``searchsorted(cuts[j], c, "right")``, the end of
    the run of cuts equal to ``c``.  A value between two cuts takes the
    lower one's code, so nothing else is reachable: at most
    ``distinct cuts + 1`` codes (an indicator column's quantiles are
    zeros and then ones: 2 to 4 codes of 256).  The absent code
    (``nbin``) is no bin and in no list.  Host, numpy."""
    import numpy as np

    cuts = np.asarray(cuts, np.float32)
    return [np.union1d([0], np.count_nonzero(
        row[None, :] <= row[:, None], axis=1)).astype(np.int32)
        for row in cuts]


def pack_plan(cuts):
    """The second rule, from the cuts only: ``(PackPlan, codes)`` of the
    features whose reachable codes (:func:`feature_codes`) are at most
    ``_NARROW_CODES`` (and at most half the bins: a segment as tall as
    the feature's own product saves nothing), or None where there is
    none.  ``codes`` is ``(narrow, width)`` int32, each narrow feature's
    codes in ascending order and then ``_NO_CODE``: a runtime operand, so
    that a job's cuts (a seed's) do not key a program, while the indices
    and the width are shapes.  The cuts are broadcast, so every rank has the same plan."""
    import numpy as np

    held = feature_codes(cuts)
    most = min(_NARROW_CODES, (np.shape(cuts)[1] + 1) // 2)
    narrow = tuple(j for j, c in enumerate(held) if len(c) <= most)
    if not narrow:
        return None
    width = _next_pow2(max(len(held[j]) for j in narrow))
    codes = np.full((len(narrow), width), _NO_CODE, np.int32)
    for k, j in enumerate(narrow):
        codes[k, :len(held[j])] = held[j]
    return PackPlan(narrow, width), codes


def lane_packs(nbin: int, features: int, lanes: int) -> bool:
    """Whether a lane-wide call of this shape takes a :class:`PackPlan`:
    the one-axis call, every feature in one grid step.  A wide shard's
    chunked call (:func:`lane_chunk`) builds every feature's own
    product."""
    return lane_chunk(nbin, features, lanes) >= features


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


def _hist_kernel(bins_t_ref, w_ref, node_ref, out_ref, *,
                 hi: int, lo: int, fpg: int, ngroups: int, nslots: int):
    """One row block: the one-hots of every feature group against every
    weight channel, added into the VMEM-resident output.

    Channel ``s * nw + c`` is weight row ``c`` of the rows whose node is
    ``s`` (everything else weighs 0): the level's node masks are made
    here, a row block at a time, and never exist in HBM."""
    i = pl.program_id(0)
    block = w_ref.shape[1]
    w = w_ref[:]                                   # (nw, block) compute dtype
    lo_shift = lo.bit_length() - 1
    lo_mask = lo - 1
    cdt = w.dtype
    prec = (lax.Precision.HIGHEST if cdt == jnp.float32
            else lax.Precision.DEFAULT)
    rows = [w[c:c + 1, :] for c in range(w.shape[0])]
    node = node_ref[:]                             # (1, block) int32
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


def _runs(features) -> tuple:
    """``(first, end)`` of every maximal run of consecutive indices in
    the ascending ``features``."""
    runs: list = []
    for j in features:
        if runs and runs[-1][1] == j:
            runs[-1][1] = j + 1
        else:
            runs.append([j, j + 1])
    return tuple(map(tuple, runs))


def _lane_kernel(bins_t_ref, w_ref, node_ref, *rest,
                 classes: int, trees: int, tree_rows: int, groups: int = 0,
                 wide: tuple = (), narrow: tuple = (), width: int = 0):
    """One row block, channels on the MXU's lanes: each feature's plain
    one-hot ``(classes, block)`` against the level's masked weights
    ``(lanes, block)``, one NT product a feature whatever the level's
    width, added into the VMEM-resident ``(features, classes, lanes)``
    output.  A wide shard's call names its feature ``groups``: the
    features are then a chunk's (grid axis 0, the row blocks inside it
    on axis 1), the output block that chunk's, zeroed at its first row
    block and written back when the chunk changes, and the last chunk's
    loop ends with the shard's groups.

    Tree ``t`` holds lanes ``t * tree_rows`` on, lane ``2 * s + c`` of
    them being weight row ``c`` of the tree's rows at its node ``s``:
    the masks are made here from the trees' ``(2, block)`` weights and
    node ids, once a block.  The three rules of :func:`_hist_kernel`
    hold: an absent entry's code matches no class (or one the caller
    slices off), a row at node -1 no slot, a padded row weighs 0.

    With ``narrow`` (a :class:`PackPlan`'s, a one-axis call's) the refs
    end ``codes, out, packed``: only the features of the runs ``wide``
    build a product of their own (a narrow one's block of ``out`` stays
    zero),
    the same adds as without a plan, and the narrow ones share one.  Row
    ``k * width + i`` of its ``(rows, block)`` multi-hot operand is
    ``bins[narrow[k]] == codes[k, i]``, built a sublane tile of 8 rows
    at a time: the bins of the tile's features (``8 // width`` of them,
    joined by selects on the sublane index; one where ``width`` is 8 or
    more) compared with the tile of the ``(rows, 1)`` code column, the
    tiles joined on tile boundaries and cast once, as ``wm`` is.  No
    relayout, and a bin's sum is the same products added in the same
    order along the block as in a product of the feature's own."""
    if narrow:
        codes_ref, out_ref, packed_ref = rest
    else:
        out_ref, = rest
    i = pl.program_id(1 if groups else 0)
    block = w_ref.shape[2]
    features, _, lanes = out_ref.shape
    cdt = w_ref.dtype
    prec = (lax.Precision.HIGHEST if cdt == jnp.float32
            else lax.Precision.DEFAULT)
    # selects in float32 (exact from the compute dtype and back)
    w = w_ref[:].astype(jnp.float32)               # (trees, 2, block)
    node = node_ref[:]                             # (trees, block) int32
    row = lax.broadcasted_iota(jnp.int32, (tree_rows, block), 0)
    slot = lax.shift_right_logical(row, 1)
    hess = lax.bitwise_and(row, 1) == 1
    parts = []
    for t in range(trees):
        gh = jnp.where(hess, w[t, 1:2, :], w[t, 0:1, :])
        parts.append(jnp.where(node[t:t + 1, :] == slot, gh, 0.0))
    if lanes > trees * tree_rows:
        parts.append(jnp.zeros((lanes - trees * tree_rows, block),
                               jnp.float32))
    wm = jnp.concatenate(parts, axis=0).astype(cdt)        # (lanes, block)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros(out_ref.shape, out_ref.dtype)
        if narrow:
            packed_ref[:] = jnp.zeros(packed_ref.shape, packed_ref.dtype)

    cls = lax.broadcasted_iota(jnp.int32, (classes, block), 0)

    def product(operand):
        return lax.dot_general(operand, wm, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)

    def some(start, count, first=0):
        bt = bins_t_ref[pl.ds(start, _LANE_FEATURES), :]
        for j in range(first, count):
            out_ref[start + j] += product(
                (bt[j:j + 1, :] == cls).astype(cdt))

    def group(grp, carry):
        some(pl.multiple_of(grp * _LANE_FEATURES, _LANE_FEATURES),
             _LANE_FEATURES)
        return carry

    # one body of _LANE_FEATURES products, looped: a copy a feature is
    # 28 to 54 of them, and PR 33 paid minutes for an unrolled wide body
    whole, left = divmod(features, _LANE_FEATURES)
    if narrow:
        # a run of features with a product of their own: up to a group's
        # boundary, then whole groups looped, then what is left
        for first, end in wide:
            start = first // _LANE_FEATURES * _LANE_FEATURES
            head = min(end, _round_up(first, _LANE_FEATURES))
            tail = max(head, end // _LANE_FEATURES * _LANE_FEATURES)
            if first < head:
                some(start, head - start, first - start)
            if head < tail:
                lax.fori_loop(head // _LANE_FEATURES, tail // _LANE_FEATURES,
                              group, None)
            if tail < end:
                some(tail, end - tail)
        _packed_product(bins_t_ref, codes_ref, packed_ref, product, narrow,
                        width, cdt)
        return
    if groups:
        # the last chunk's loop ends with the shard's groups: what its
        # blocks hold past them is never read (run whole, that loop cost
        # 1.6% of a call at 968 features in chunks of 328; chip, PR 44)
        lax.fori_loop(0, jnp.minimum(whole, groups - pl.program_id(0) * whole),
                      group, None)
    elif whole:
        lax.fori_loop(0, whole, group, None)
    if left:
        some(whole * _LANE_FEATURES, left)


def _packed_product(bins_t_ref, codes_ref, packed_ref, product, narrow: tuple,
                    width: int, cdt) -> None:
    """The narrow features' one product of a row block (:func:`_lane_kernel`),
    in pieces of ``_PACK_ROWS`` rows of the shared class axis."""
    rows, block = packed_ref.shape[0], bins_t_ref.shape[1]
    tile = 8                                    # int32 sublanes
    sub = lax.broadcasted_iota(jnp.int32, (tile, block), 0)
    used = len(narrow) * width

    def bins_of(k):                             # (1, block)
        j = narrow[min(k, len(narrow) - 1)]
        return bins_t_ref[pl.ds(j, 1), :]

    def tile_at(at):
        """Rows ``[at, at + 8)`` of the multi-hot operand, float32."""
        if at >= used:
            return jnp.zeros((tile, block), jnp.float32)
        if width >= tile:
            held = bins_of(at // width)
        else:
            # rows [q * width, (q + 1) * width) of the tile are feature
            # at // width + q's; past the last feature the codes match
            # no row, whosever bins stand there
            per = tile // width
            held = bins_of(at // width + per - 1)
            for q in range(per - 2, -1, -1):
                held = jnp.where(sub < (q + 1) * width,
                                 bins_of(at // width + q), held)
        return (held == codes_ref[pl.ds(at, tile), :]).astype(jnp.float32)

    for lo in range(0, rows, _PACK_ROWS):
        hi = min(rows, lo + _PACK_ROWS)
        multihot = jnp.concatenate(
            [tile_at(at) for at in range(lo, hi, tile)], axis=0).astype(cdt)
        packed_ref[pl.ds(lo, hi - lo), :] += product(multihot)


@functools.partial(
    jax.jit,
    static_argnames=("nbin", "block", "interpret", "compute_dtype",
                     "nslots", "lanes", "features", "pack"))
def _hist_multi(bins_t, weights, node, nbin: int, block: int,
                interpret: bool, compute_dtype, nslots: int,
                lanes: int = 0, features: int = 0, codes=None,
                pack: PackPlan | None = None) -> jax.Array:
    """Both bodies' calls, so that the device operation has one name
    whichever body a call took: ``lanes`` > 0 is the lane-wide body over
    ``(trees, 2, n)`` weights and ``(trees, n)`` node ids, 0 the
    two-level body over one tree's ``(2, n)`` and ``(n,)``.  A one-axis
    lane-wide call with ``pack`` and its ``codes`` (:func:`pack_plan`)
    builds the narrow features in one shared product, and XLA scatters
    its rows back to the ``(features, nbin)`` layout every caller
    reads."""
    f, n = bins_t.shape
    if lanes:
        trees = weights.shape[0]
        classes = _round_up(nbin, 16)
        tree_rows = lane_rows(nslots)
        fpad = _round_up(f, _LANE_FEATURES)
        npad = _round_up(n, block)
        cdt = jnp.dtype(compute_dtype)
        # as below: the bins are neither copied nor padded to the block,
        # and what the last block reads past them meets weight 0, node -1
        operands = [
            jnp.pad(bins_t.astype(jnp.int32), ((0, fpad - f), (0, 0))),
            jnp.pad(weights.astype(cdt), ((0, 0), (0, 0), (0, npad - n))),
            jnp.pad(node.astype(jnp.int32), ((0, 0), (0, npad - n)),
                    constant_values=-1)]
        # a shard of more features than the accumulator's budget holds
        # goes chunk by chunk on a grid axis outside the row blocks; the
        # last chunk's blocks may reach past the staged rows and the
        # features: never read, left zero and sliced off below
        chunk = lane_chunk(nbin, features, lanes)
        chunks = -(-features // chunk)
        if chunks == 1:
            grid, held, kept, groups = (npad // block,), fpad, features, 0
        else:
            grid, held, kept = (chunks, npad // block), chunk, chunk
            groups = -(-features // _LANE_FEATURES)

        def part(*at):      # of grid indices (chunk, row block) or (row block,)
            return at[0] if len(at) > 1 else 0

        out_specs = pl.BlockSpec((kept, classes, lanes),
                                 lambda *at: (part(*at), 0, 0),
                                 memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((chunks * kept, classes, lanes),
                                         jnp.float32)
        packed = {}
        if pack is not None:
            # the shared class axis: a narrow feature's segment of
            # ``width`` rows after another's, zero rows up to a multiple
            # of 128; its codes a column, _NO_CODE on the rows of no code
            used = len(pack.narrow) * pack.width
            rows = _round_up(used, 128)
            operands.append(jnp.pad(
                codes.astype(jnp.int32).reshape(used, 1),
                ((0, rows - used), (0, 0)), constant_values=_NO_CODE))
            packed = dict(
                narrow=pack.narrow, width=pack.width,
                wide=_runs(sorted(set(range(features)) - set(pack.narrow))))
            out_specs = [out_specs, pl.BlockSpec(
                (rows, lanes), lambda i: (0, 0), memory_space=pltpu.VMEM)]
            out_shape = [out_shape,
                         jax.ShapeDtypeStruct((rows, lanes), jnp.float32)]
        raw = pl.pallas_call(
            functools.partial(_lane_kernel, classes=classes, trees=trees,
                              tree_rows=tree_rows, groups=groups, **packed),
            grid=grid,
            in_specs=[
                pl.BlockSpec((held, block), lambda *at: (part(*at), at[-1]),
                             memory_space=pltpu.VMEM),
                # (trees, 2, n) as it is: folding the trees into the
                # rows is a copy, and one XLA takes minutes to compile
                pl.BlockSpec((trees, 2, block), lambda *at: (0, 0, at[-1]),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((trees, block), lambda *at: (0, at[-1]),
                             memory_space=pltpu.VMEM)] + [
                pl.BlockSpec(op.shape, lambda i: (0, 0),
                             memory_space=pltpu.VMEM)
                for op in operands[3:]],
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * len(grid),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(*operands)
        if pack is not None:
            # back to the layout everyone reads: segment row (k, i) is
            # bin codes[k, i] of feature narrow[k], whose own block the
            # kernel left zero; a padded code is out of range and dropped
            raw, shared = raw
            raw = raw.at[jnp.asarray(pack.narrow)[:, None], codes].set(
                shared[:used].reshape(len(pack.narrow), pack.width, lanes),
                mode="drop")
        # lane t * tree_rows + 2 * s + c -> channel (t, s, c); tiny, XLA
        out = raw[:features, :nbin, :trees * tree_rows].reshape(
            features, nbin, trees, tree_rows)[..., :2 * nslots]
        return out.transpose(2, 3, 0, 1).reshape(
            trees * 2 * nslots, features, nbin)
    nw = weights.shape[0] * nslots
    hi, lo, fpg, ngroups = plan(nbin, f)
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
            "channels (learn.histogram.level_hist does) or the features "
            "across calls")
    fpad = ngroups * fpg
    npad = _round_up(n, block)
    cdt = jnp.dtype(compute_dtype)

    # no copy for input staged at (fpad, n) int32: zero-width pads and
    # same-type casts return their input.  The rows are not padded to
    # the block: the last block of a ragged n reads past the array, and
    # whatever it finds there meets a weight of 0 and a node of -1,
    # which the (small) operands below are padded with
    bt = jnp.pad(bins_t.astype(jnp.int32), ((0, fpad - f), (0, 0)))
    operands = [bt, jnp.pad(weights.astype(cdt), ((0, 0), (0, npad - n))),
                # padded rows sit at node -1: in no slot
                jnp.pad(node.astype(jnp.int32).reshape(1, n),
                        ((0, 0), (0, npad - n)), constant_values=-1)]
    in_specs = [
        pl.BlockSpec((fpad, block), lambda i: (0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((weights.shape[0], block), lambda i: (0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block), lambda i: (0, i),
                     memory_space=pltpu.VMEM),
    ]

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
                     node_of_row=None, nslots: int = 0,
                     features: int | None = None, pack=None) -> jax.Array:
    """``(nslots * 2, f, nbin)`` histograms of a tree level in one pass
    over the bins, slot-major: channel ``s * 2 + c`` is weight row ``c``
    over the rows at node ``s``.

    ``bins_t`` is the TRANSPOSED (f, n) int32 bins array (the layout
    the kernel streams; keep it resident on device across calls —
    boosting reuses it for every node, level and round), ``weights`` the
    tree's ``(2, n)`` (grad, hess), ``node_of_row`` its ``(n,)`` int32
    slots (a row at no slot, -1 say, is in no histogram).  The masks
    are made inside the kernel from 4 bytes a row; no (channels, n)
    weight matrix is written to HBM.  Of a staged array padded to whole
    feature groups, ``features`` says how many leading rows are
    features: the result has that many.

    A level of several trees passes ``(T, 2, n)`` weights and ``(T, n)``
    node ids: ``(T * nslots * 2, f, nbin)``, tree-major, tree ``t``'s
    channels those of a call on ``weights[t]`` and ``node_of_row[t]``.

    The body is the one :func:`level_plan` names for the shape, and a
    call holds what that plan's call holds: the lane-wide body the
    trees at once, the two-level body a tree of :func:`max_channels`
    channels.  ``pack`` is a job's :func:`pack_plan`, which a one-axis
    lane-wide call takes and every other call leaves: the result is the
    same, bit for bit.  More, or no level at all (no node ids, weights
    that are no (grad, hess) pairs), is a ``ValueError``:
    ``learn.histogram.level_hist`` shares a level of any width out over
    such calls.  Both bodies round the weights to the compute dtype once
    and add exact products in float32; the order of those adds is each
    body's own, so they are held to float32 rounding, not to equality
    bit for bit (which the chip showed at the boosting cells' shapes all
    the same: tools/hist_kernel_check.py).
    """
    if interpret is None:
        interpret = not on_tpu()
    weights = jnp.asarray(weights)
    f, n = bins_t.shape
    features = f if features is None else features
    if block is None:
        block = default_block(n)
    block = min(block, _round_up(n, 128))
    cdt = jnp.dtype(compute_dtype).name
    forest = weights.ndim == 3
    pairs = weights.ndim in (2, 3) and weights.shape[-2] == 2
    if not pairs or node_of_row is None or nslots < 1:
        raise ValueError(
            "a tree level takes (2, n) weights, or (T, 2, n) of several "
            "trees, with their node ids and nslots >= 1, not "
            f"{weights.shape} with nslots={nslots}: call "
            "learn.histogram.level_hist (or build_level_local)")
    trees = weights.shape[0] if forest else 1
    plan = level_plan(nbin, features, nslots, trees)
    if plan.lane:
        if trees > plan.trees or nslots > plan.slots:
            raise ValueError(
                f"{trees} trees of {nslots} slots out of range of a "
                f"lane-wide call: {plan.trees} trees of {plan.slots}")
        node = jnp.asarray(node_of_row)
        lanes = call_lanes(trees, nslots)
        packed = {}
        if pack is not None and lane_packs(nbin, features, lanes):
            packed = dict(pack=pack[0], codes=jnp.asarray(pack[1]))
        return _hist_multi(
            jnp.asarray(bins_t), weights if forest else weights[None],
            node if forest else node[None], nbin, block, interpret, cdt,
            nslots=nslots, features=features, lanes=lanes, **packed)
    if forest:
        return jnp.concatenate([
            hist_fused_multi(bins_t, weights[t], nbin, block, interpret,
                             compute_dtype, node_of_row=node_of_row[t],
                             nslots=nslots, features=features)
            for t in range(trees)])
    if nslots > plan.slots:
        raise ValueError(
            f"{nslots} slots out of range of a two-level call: "
            f"{plan.slots} at {features} features of {nbin} bins "
            "(max_channels); learn.histogram.level_hist chunks a wider "
            "level")
    return _hist_multi(jnp.asarray(bins_t), weights, jnp.asarray(node_of_row),
                       nbin, block, interpret, cdt,
                       nslots=nslots)[:, :features]

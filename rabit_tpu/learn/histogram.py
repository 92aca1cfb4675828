"""XGBoost-style gradient-histogram building.

The reference's historical raison d'être is the histogram allreduce
inside XGBoost: each worker bins its feature shard, accumulates per
(feature, bin) gradient/hessian sums for the tree node being split, and
Allreduce<Sum>'s the flat histogram so every worker sees the global
statistics (the pattern BASELINE.md lists under "configs to reproduce";
the reference itself only ships the collective, the histogram is the
app's job — same split here).

TPU-native design: binned features live on device as int32, for a
boosting job staged once as the transposed, feature-padded ``(fpad, n)``
array the fused kernel streams (:func:`stage_bins`, binned on the device
from the float values); the builder expands bins to one-hots and
contracts them with the (grad, hess) pair on the MXU — compiler-friendly
fixed shapes, no scatter (TPU scatters serialize; the one-hot contraction
keeps the FLOPs on the matrix unit).  A tree level builds its node
slots' histograms in one pass (:func:`level_hist`), node membership folded
into the weights inside the kernel; the booster gives it one child of
every split node and subtracts for the sibling.  The cross-worker step is
the booster's (``boosting.train``): one framework allreduce of the flat
level, exactly the XGBoost wire pattern.
"""
from __future__ import annotations

import numpy as np

from rabit_tpu.ops import on_tpu

_CACHE: dict = {}


def _writable(arr) -> "np.ndarray":
    """Host-allreduce input prep: the collective is in-place by
    contract (include/rabit.h:134-137) but jax arrays export read-only
    buffers — copy exactly when the local build handed us one."""
    arr = np.asarray(arr)
    if not arr.flags.writeable:
        arr = arr.copy()
    return arr


# columns sorted together by :func:`quantile_cuts`: a cache line of a
# row-major float32 sample, so that the transpose reads each line once
CUT_BATCH_COLS = 16
_CUT_BLOCK_ROWS = 4096


def _quantiles(take, cnt: np.ndarray, qs: np.ndarray) -> np.ndarray:
    """``(columns, len(qs))`` quantiles ``qs`` of columns whose present
    values are sorted: ``cnt`` of them a column, ``take(at)`` the values
    at positions ``at`` (columns, len(qs)) of each.  numpy's own
    arithmetic (``linear``: float32 neighbours, float64 weight); zeros
    for a column with none."""
    last = np.maximum(cnt - 1, 0)[:, None]
    virtual = last * qs[None, :]                       # float64
    # a quantile at or past the last present value takes it whole
    # (numpy: both neighbours the last entry, weight virtual + 1)
    above, below = virtual >= last, np.floor(virtual)
    lo = np.where(above, last, below).astype(np.intp)
    gamma = virtual - np.where(above, -1.0, below)
    a = take(lo)
    b = take(np.minimum(lo + 1, last))
    diff = b - a                                       # float32
    out = np.where(gamma >= 0.5, b - diff * (1 - gamma), a + diff * gamma)
    out[cnt == 0] = 0.0                                # all absent
    return out


def quantile_cuts(values: np.ndarray, nbin: int) -> np.ndarray:
    """Per-column quantile cut points, shape (f, nbin - 1) — the
    host-side analogue of XGBoost's quantile sketch (per-shard; callers
    needing globally consistent cuts broadcast/allreduce them).

    NaN entries are missing values: cuts come from the present entries
    only.  An all-NaN column gets zero cuts (every present-at-predict-
    time value bins to 0; its rows are absent anyway).

    Equal to ``np.nanquantile(values, qs, axis=0)`` bit for bit, which
    walks the columns one at a time (2 s for 28 columns of 2^20 rows, a
    minute for a thousand): here a batch of columns is transposed and
    sorted once (NaNs sort last), the present count a column places the
    quantiles, and the interpolation is numpy's own arithmetic
    (``linear``: float32 neighbours, float64 weight), batches on a few
    threads."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    values = np.asarray(values, np.float32)
    m, f = values.shape
    qs = np.linspace(0, 1, nbin + 1)[1:-1]
    cuts = np.zeros((f, nbin - 1), np.float32)
    if m == 0:
        return cuts

    def batch(j0: int) -> None:
        j1 = min(f, j0 + CUT_BATCH_COLS)
        cols = np.empty((j1 - j0, m), np.float32)
        for r in range(0, m, _CUT_BLOCK_ROWS):
            cols[:, r:r + _CUT_BLOCK_ROWS] = values[
                r:r + _CUT_BLOCK_ROWS, j0:j1].T
        cols.sort(axis=1)                                  # NaNs last
        cnt = m - np.count_nonzero(np.isnan(cols), axis=1)
        rows = np.arange(j1 - j0)[:, None]
        cuts[j0:j1] = _quantiles(lambda at: cols[rows, at], cnt, qs)

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(batch, range(0, f, CUT_BATCH_COLS)))
    return cuts


def apply_cuts(values: np.ndarray, cuts: np.ndarray) -> np.ndarray:
    """Bin raw feature values with quantile cuts → int32 in [0, nbin);
    a NaN (missing) value takes the code ``nbin``, one past the regular
    range.  The code marks the entry as absent and is no bin: every
    histogram builder here has ``nbin`` slots and adds such an entry to
    none of them (XGBoost's layout).  A feature's missing mass at a
    node is the node's total less the feature's own bins
    (:func:`missing_mass`), from which the booster learns a per-split
    default direction (XGBoost's sparsity-aware split semantics); the
    row move and ``predict`` read the code to send the row that way."""
    n, f = values.shape
    bins = np.empty((n, f), np.int32)
    for j in range(f):
        bins[:, j] = np.searchsorted(cuts[j], values[:, j], side="right")
    nan = np.isnan(values)
    if nan.any():
        bins[nan] = cuts.shape[1] + 1
    return bins


# a chunk of float rows on its way to the device: at most this many
# rows and this many bytes, so that a wide shard (968 columns: 4 GB a
# 2^20 rows, its transpose another) crosses in pieces the device holds
# beside the bins, and a narrow one keeps its 2^20 rows
STAGE_CHUNK_ROWS = 1 << 20
STAGE_CHUNK_BYTES = 1 << 28


def stage_chunk_rows(n: int, f: int) -> int:
    """Rows of one chunk of an ``(n, f)`` float32 shard on its way to
    the device."""
    return max(1, min(n, STAGE_CHUNK_ROWS, STAGE_CHUNK_BYTES // (4 * f)))


def staged_features(f: int, nbin: int) -> int:
    """Feature rows of the staged bins array: ``f`` rounded up to the
    fused kernel's feature group, so that a call pads nothing."""
    from rabit_tpu.ops.histogram_kernel import plan

    _hi, _lo, fpg, ngroups = plan(nbin, f)
    return fpg * ngroups


def _bin_program(n: int, c: int, f: int, fpad: int, ncut: int):
    """Compiled ``gbdt_bin``: bins ``c`` rows of float values and writes
    them into columns ``[lo, lo + c)`` of the staged array in place."""
    key = ("bin", n, c, f, fpad, ncut)
    fn = _CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def gbdt_bin(bins_t, seen, absent, vals, cuts_t, lo):
            with jax.named_scope("gbdt/bin"):
                v = vals.T                                   # (f, c)
                # cuts <= value, counted: searchsorted(side="right");
                # a NaN is below every cut and takes the missing code
                b = jnp.sum(cuts_t[:, :, None] <= v[None, :, :], axis=0,
                            dtype=jnp.int32)
                nan = jnp.isnan(v)
                b = jnp.where(nan, ncut + 1, b)
                b = jnp.pad(b, ((0, fpad - f), (0, 0)))
                seen = jnp.maximum(seen, jnp.stack(
                    [jnp.any(nan).astype(jnp.int32), jnp.max(b)]))
                absent = absent + jnp.sum(nan, axis=1, dtype=jnp.int32)
                return jax.lax.dynamic_update_slice(
                    bins_t, b, (jnp.int32(0), lo)), seen, absent

        sds = jax.ShapeDtypeStruct
        fn = jax.jit(gbdt_bin, donate_argnums=(0, 1, 2)).lower(
            sds((fpad, n), jnp.int32), sds((2,), jnp.int32),
            sds((f,), jnp.int32), sds((c, f), jnp.float32),
            sds((ncut, f), jnp.float32), sds((), jnp.int32)).compile()
        _CACHE[key] = fn
    return fn


def stage_bins(values: np.ndarray, cuts: np.ndarray, nbin: int):
    """Bin a shard on the device and keep it there: returns the
    ``(fpad, n)`` int32 array :func:`level_hist` streams (features
    padded to the kernel's group with bin 0, whose histograms are
    dropped) and a (2,) int32 device array ``[any NaN, largest bin]``.

    Equal to :func:`apply_cuts` bit for bit, NaN included.  The float
    values cross to the device a chunk of rows at a time (at most
    ``STAGE_CHUNK_ROWS`` rows and ``STAGE_CHUNK_BYTES`` bytes) and only
    the bins stay; nothing of length n is made on the host.  Counts the
    staged entries and the absent ones among them (``gbdt.entries``,
    ``gbdt.entries_missing``), a feature at a time on the device."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.obs import program

    n, f = values.shape
    fpad, ncut = staged_features(f, nbin), cuts.shape[1]
    cuts_t = jnp.asarray(np.ascontiguousarray(cuts.T, np.float32))
    bins_t = jnp.zeros((fpad, n), jnp.int32)
    seen = jnp.zeros((2,), jnp.int32)
    absent = jnp.zeros((f,), jnp.int32)
    chunk = stage_chunk_rows(n, f)
    for lo in range(0, n, chunk):
        c = min(chunk, n - lo)
        with program.span("stage.compile"):
            fn = _bin_program(n, c, f, fpad, ncut)
        with program.span("stage.put"):
            vals = jax.device_put(
                np.ascontiguousarray(values[lo:lo + c], np.float32))
        with program.span("stage.bin"):
            bins_t, seen, absent = fn(bins_t, seen, absent, vals, cuts_t,
                                      np.int32(lo))
    program.count("gbdt.entries", n * f)
    program.count("gbdt.entries_missing",
                  int(np.asarray(absent).sum(dtype=np.int64)))
    return bins_t, seen


# ----------------------------------------------------------------------
# the weighted quantile sketch (``tree_method="approx"``)
# ----------------------------------------------------------------------
# XGBoost sizes its summaries by a factor of 8 over ``max_bin``: a cut's
# weighted rank is within ``sketch_eps`` = 1 / (8 * nbin) of its target
SKETCH_EPS_FACTOR = 8
# entries of one rank's summary of one feature: its exact weighted
# quantiles at this spacing.  Between two neighbours lies under 1 / K of
# the rank's weight, which is all a merge does not know of the rank:
# the cuts of a merged summary are within 2 / K = sketch_eps / 2 of
# their targets whatever the number of ranks, and exact at world 1
SUMMARY_FACTOR = 4 * SKETCH_EPS_FACTOR
# weights are added up in float32, a block of this many at a time and
# the blocks' totals after: no chain is longer than a block or than the
# number of blocks (XLA's scans are trees besides), so that a prefix is
# good to a few ulp of the total where a running float32 sum of 2^25
# quarters would stall at 2^22
SKETCH_SCAN_BLOCK = 4096


def sketch_eps(nbin: int) -> float:
    """The weighted rank error a cut may have, as a share of the
    feature's summed weight."""
    return 1.0 / (SKETCH_EPS_FACTOR * nbin)


def summary_entries(nbin: int) -> int:
    """Entries of a rank's summary of one feature."""
    return SUMMARY_FACTOR * nbin


def _prefix(w):
    """Inclusive prefix sums of ``(n,)`` float32 weights, traceable."""
    import jax.numpy as jnp

    n = w.shape[0]
    block = min(SKETCH_SCAN_BLOCK, n)
    pad = -n % block
    c = jnp.cumsum(jnp.pad(w, (0, pad)).reshape(-1, block), axis=1)
    ends = c[:, -1]
    return (c + (jnp.cumsum(ends) - ends)[:, None]).reshape(-1)[:n]


def _order_keys(v):
    """int32 keys whose order is the float32 values', traceable: NaN
    (absent) last, -0.0 with 0.0.  The device sorts integers a tenth
    faster than floats, and compiles the sort in two thirds of the
    time."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(jnp.where(v == 0, 0.0, v), jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jnp.where(jnp.isnan(v), jnp.int32(0x7FFFFFFF), keys)


def _key_values(keys):
    """The float32 values of :func:`_order_keys`' keys."""
    import jax
    import jax.numpy as jnp

    return jax.lax.bitcast_convert_type(
        jnp.where(keys < 0, keys ^ jnp.int32(0x7FFFFFFF), keys), jnp.float32)


def sketch_summary(values_t, weights, entries: int):
    """One rank's summary ``(f, entries, 3)`` float32 of its rows,
    traceable: for feature ``j`` the exact weighted quantiles of
    ``values_t[j]`` (``(f, n)`` float32, NaN absent) under ``weights``
    (``(n,)`` float32 >= 0: the round's hessians), entry ``k`` the
    ``(value, rmin, rmax)`` of the first value, in order, at which the
    summed weight reaches ``(k + 1) / entries`` of the feature's total:
    ``rmin`` the weight of the rows below the value and ``rmax`` that of
    the rows at or below it (a tie counts as the interval it spans), so
    that ``rmax`` of the last entry is the total.  An absent value takes
    no weight and no entry, a row of weight 0 (sampled out) no entry; a
    feature without weight reads zeros.

    A feature at a time: its values are sorted with their weights, as
    integer keys of the same order (the device's work is the same
    whatever either holds), the weights added up in float32
    (:func:`_prefix`) and the entries found by bisection.  Rows of
    equal value come out of the sort in no particular order (it is not
    stable: a stable one carries a third operand, the row's index, and
    compares it too), which nothing reads: an entry takes a tie as the
    interval it spans, so the order inside one moves only the float32
    rounding of the sums within it.
    """
    import jax
    import jax.numpy as jnp

    n = values_t.shape[1]
    share = jnp.arange(1, entries + 1, dtype=jnp.float32) / entries

    def one(v):
        keys, sw = jax.lax.sort(
            (_order_keys(v), jnp.where(jnp.isnan(v), 0.0, weights)),
            num_keys=1, is_stable=False)
        c = _prefix(sw)
        total = c[-1]
        at = jnp.minimum(jnp.searchsorted(c, share * total), n - 1)
        key = keys[at]
        first = jnp.searchsorted(keys, key, side="left")
        last = jnp.searchsorted(keys, key, side="right") - 1
        rmin = jnp.where(first > 0, c[jnp.maximum(first - 1, 0)], 0.0)
        out = jnp.stack([_key_values(key), rmin, c[last]], axis=1)
        return jnp.where(total > 0, out, 0.0)

    return jax.lax.map(one, values_t)


def sketch_cuts(summaries, nbin: int):
    """``(f, nbin - 1)`` float32 cuts from every rank's summary
    ``(ranks, f, entries, 3)`` (:func:`sketch_summary`), traceable: cut
    ``i`` is the first value, among the summaries' own, at or below
    which lies ``i / nbin`` of the weight of all ranks.  What a rank
    holds at or below a value is, from its summary, exact where the
    value is an entry of it and else the middle of what the entries on
    either side allow, which is under ``1 / entries`` of the rank's
    weight wide.  So a cut's weighted rank interval is within
    ``2 / entries`` of its target (the spacing of the candidates and
    the merge's doubt), and holds it at one rank.  Every rank computes
    this from the same bytes and gets the same cuts; cuts may repeat; a
    feature without weight reads zeros."""
    import jax
    import jax.numpy as jnp

    ranks, f, entries, _ = summaries.shape

    def one(summary):                       # (ranks, entries, 3)
        cand = summary[:, :, 0].reshape(-1)

        def held(s):                        # a rank's weight <= candidates
            value, rmin, rmax = s[:, 0], s[:, 1], s[:, 2]
            k = jnp.searchsorted(value, cand, side="right")
            below = jnp.where(k > 0, rmax[jnp.maximum(k - 1, 0)], 0.0)
            above = jnp.where(k < entries,
                              rmin[jnp.minimum(k, entries - 1)], rmax[-1])
            exact = (k > 0) & (value[jnp.maximum(k - 1, 0)] == cand)
            return jnp.where(exact, below, 0.5 * (below + above))

        upto = jnp.sum(jax.vmap(held)(summary), axis=0)
        if ranks > 1:       # one rank's entries are in order as they are
            # equal candidates hold equal ``upto``: any order of them
            cand, upto = jax.lax.sort((cand, upto), num_keys=1,
                                      is_stable=False)
        total = jnp.sum(summary[:, -1, 2])
        want = total * (jnp.arange(1, nbin, dtype=jnp.float32) / nbin)
        at = jnp.minimum(jnp.searchsorted(upto, want), cand.shape[0] - 1)
        return jnp.where(total > 0, cand[at], 0.0)

    return jax.vmap(one, in_axes=1)(summaries)


def sketch_program(n: int, f: int, nbin: int, world: int = 1, rank: int = 0):
    """Compiled ``gbdt_sketch``: from the resident values ``(f, n)`` and
    the round's ``(2, n)`` (grad, hess) to what the merge's one
    allreduce carries, ``(world, f, entries, 3)`` float32: this rank's
    :func:`sketch_summary` under the hessians in its own slot and zeros
    in the others, so that a sum over ranks is every rank's summary
    side by side, exact and the same bytes everywhere."""
    key = ("sketch", n, f, nbin, world, rank)
    fn = _CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        entries = summary_entries(nbin)

        def gbdt_sketch(values_t, gh):
            with jax.named_scope("gbdt/sketch"):
                mine = sketch_summary(values_t, gh[1], entries)
                if world == 1:
                    return mine[None]
                return jnp.zeros((world,) + mine.shape, jnp.float32).at[
                    rank].set(mine)

        sds = jax.ShapeDtypeStruct
        fn = _CACHE[key] = jax.jit(gbdt_sketch).lower(
            sds((f, n), jnp.float32), sds((2, n), jnp.float32)).compile()
    return fn


def cuts_program(world: int, f: int, nbin: int):
    """Compiled ``gbdt_cuts``: :func:`sketch_cuts` of the merged
    summaries ``(world, f, entries, 3)``."""
    key = ("cuts", world, f, nbin)
    fn = _CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def gbdt_cuts(summaries):
            with jax.named_scope("gbdt/sketch"):
                return sketch_cuts(summaries, nbin)

        fn = _CACHE[key] = jax.jit(gbdt_cuts).lower(jax.ShapeDtypeStruct(
            (world, f, summary_entries(nbin), 3), jnp.float32)).compile()
    return fn


def rebin(bins_t, values_t, cuts):
    """The resident values ``(f, n)`` binned by ``(f, ncut)`` cuts into
    the first ``f`` rows of the staged ``(fpad, n)`` array, traceable;
    the rows of padding keep what they hold (zeros).  Equal to
    :func:`apply_cuts` bit for bit, NaN included.  A feature at a time,
    each row written where it lies: the whole array at once costs a
    second one of its size."""
    import jax
    import jax.numpy as jnp

    f, ncut = cuts.shape

    def feature(j, bins_t):
        v = jax.lax.dynamic_index_in_dim(values_t, j, keepdims=False)
        c = jax.lax.dynamic_index_in_dim(cuts, j, keepdims=False)
        # cuts <= value, counted: searchsorted(side="right"); a NaN is
        # below every cut and takes the missing code
        b = jnp.sum(c[:, None] <= v[None, :], axis=0, dtype=jnp.int32)
        b = jnp.where(jnp.isnan(v), ncut + 1, b)
        return jax.lax.dynamic_update_slice(bins_t, b[None], (j, 0))

    return jax.lax.fori_loop(0, f, feature, bins_t)


def rebin_program(n: int, f: int, fpad: int, ncut: int):
    """Compiled ``gbdt_rebin``: :func:`rebin` written over the old bins
    (the argument is donated: no second array of that size)."""
    key = ("rebin", n, f, fpad, ncut)
    fn = _CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def gbdt_rebin(bins_t, values_t, cuts):
            with jax.named_scope("gbdt/rebin"):
                return rebin(bins_t, values_t, cuts)

        sds = jax.ShapeDtypeStruct
        fn = _CACHE[key] = jax.jit(gbdt_rebin, donate_argnums=(0,)).lower(
            sds((fpad, n), jnp.int32), sds((f, n), jnp.float32),
            sds((f, ncut), jnp.float32)).compile()
    return fn


def stage_values(values: np.ndarray, nbin: int):
    """Keep a shard's float values on the device for a job that bins
    them anew every round (``tree_method="approx"``): returns the
    ``(f, n)`` float32 array :func:`sketch_program` and
    :func:`rebin_program` read, the ``(fpad, n)`` int32 array the bins
    will be written to (zeros until the first round's cuts are there)
    and a (2,) int32 device array ``[any NaN, the missing code if so]``
    as :func:`stage_bins` gives it.  The values cross a chunk of rows at
    a time as there; counts the staged entries and the absent ones."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.obs import program

    n, f = values.shape
    chunk = stage_chunk_rows(n, f)

    def compiled(c: int):
        key = ("stage_values", n, c, f)
        fn = _CACHE.get(key)
        if fn is None:
            def gbdt_stage(values_t, absent, vals, lo):
                with jax.named_scope("gbdt/stage"):
                    v = vals.T
                    absent = absent + jnp.sum(jnp.isnan(v), axis=1,
                                              dtype=jnp.int32)
                    return jax.lax.dynamic_update_slice(
                        values_t, v, (jnp.int32(0), lo)), absent

            sds = jax.ShapeDtypeStruct
            fn = _CACHE[key] = jax.jit(
                gbdt_stage, donate_argnums=(0, 1)).lower(
                    sds((f, n), jnp.float32), sds((f,), jnp.int32),
                    sds((c, f), jnp.float32), sds((), jnp.int32)).compile()
        return fn

    values_t = jnp.zeros((f, n), jnp.float32)
    absent = jnp.zeros((f,), jnp.int32)
    for lo in range(0, n, chunk):
        c = min(chunk, n - lo)
        with program.span("stage.compile"):
            fn = compiled(c)
        with program.span("stage.put"):
            vals = jax.device_put(
                np.ascontiguousarray(values[lo:lo + c], np.float32))
        with program.span("stage.bin"):
            values_t, absent = fn(values_t, absent, vals, np.int32(lo))
    missing = int(np.asarray(absent).sum(dtype=np.int64))
    program.count("gbdt.entries", n * f)
    program.count("gbdt.entries_missing", missing)
    bins_t = jnp.zeros((staged_features(f, nbin), n), jnp.int32)
    return values_t, bins_t, jnp.asarray(
        [missing > 0, nbin * (missing > 0)], jnp.int32)


def _level_xla(bins_t, gh, node, nslots: int, nbin: int, block: int = 4096):
    """The level's histograms without the kernel, in float32: the exact
    path (``use_pallas=False``).  A scan over row blocks, each block's
    one-hots contracted with the node-masked weights."""
    import jax
    import jax.numpy as jnp

    fpad, n = bins_t.shape
    block = min(block, n)
    npad = -(-n // block) * block
    nb = npad // block
    bt = jnp.pad(bins_t, ((0, 0), (0, npad - n))).reshape(fpad, nb, block)
    w = jnp.pad(gh, ((0, 0), (0, npad - n))).reshape(2, nb, block)
    nd = jnp.pad(node, (0, npad - n), constant_values=-1).reshape(nb, block)
    bin_iota = jnp.arange(nbin, dtype=jnp.int32)
    slot_iota = jnp.arange(nslots, dtype=jnp.int32)

    def body(acc, xs):
        b, wb, ndb = xs
        oh = (b[:, :, None] == bin_iota).astype(jnp.float32)
        ws = ((ndb[:, None] == slot_iota)[:, :, None]
              * wb.T[:, None, :]).astype(jnp.float32)      # (block, S, 2)
        return acc + jnp.einsum("frk,rsc->sfkc", oh, ws,
                                precision=jax.lax.Precision.HIGHEST), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((nslots, fpad, nbin, 2), jnp.float32),
        (bt.transpose(1, 0, 2), w.transpose(1, 0, 2), nd))
    return acc


def slot_totals(gh, slot, nslots: int, dtype):
    """``(nslots, 2)`` float32, traceable: the (grad, hess) sums of the
    rows at each level slot, the weights rounded to ``dtype`` first as
    the histogram builder rounds its operand, so that a slot's total
    and its features' bins are sums of the same numbers and their
    difference is the mass of the rows absent from the feature."""
    import jax
    import jax.numpy as jnp

    # lax.reduce_precision, not a cast there and back: the TPU pipeline
    # drops an f32 -> bf16 -> f32 round trip as excess precision, and
    # the totals are then those of other numbers than the bins' (found
    # on the chip, PR 33: every feature read a missing mass of 2^-9 of
    # the node)
    grid = jnp.finfo(jnp.dtype(dtype))
    w = jax.lax.reduce_precision(gh.astype(jnp.float32), grid.nexp,
                                 grid.nmant)
    at = slot[None, :] == jnp.arange(nslots, dtype=jnp.int32)[:, None]
    return jnp.sum(jnp.where(at[:, None, :], w[None], 0.0), axis=-1)


def with_totals(hists, totals):
    """The level's histograms with one more feature row a slot, whose
    bin 0 holds the slot's (grad, hess) totals: they ride the level's
    one allreduce and fetch, and a sibling's come out of the same
    parent-minus-built subtraction as its bins."""
    import jax.numpy as jnp

    row = jnp.zeros(hists.shape[:1] + (1,) + hists.shape[2:], hists.dtype)
    return jnp.concatenate([hists, row.at[:, 0, 0].set(totals)], axis=1)


def slots_per_call(nbin: int, f: int, nslots: int = 1, trees: int = 1) -> int:
    """Level slots of a tree that one kernel call of :func:`level_hist`
    builds at a level of ``trees`` trees of ``nslots`` slots (a slot is
    a grad and a hess channel): ``ops.histogram_kernel.level_plan``'s,
    which for a level under the lane-wide body's crossing (the root's,
    say: the default) is half the two-level body's widest worthwhile
    call."""
    from rabit_tpu.ops.histogram_kernel import level_plan

    return level_plan(nbin, f, nslots, trees).slots


def _level_chunks(nslots: int, f: int, nbin: int, trees: int):
    """The kernel calls of a level, in the order of their channels:
    ``(first tree, trees, first slot, slots, lane-wide)`` each.  A call
    holds several trees only with all of their slots, so tree by tree
    and slot by slot is tree-major, slot-major."""
    from rabit_tpu.ops.histogram_kernel import level_plan

    plan = level_plan(nbin, f, nslots, trees)
    for t in range(0, trees, plan.trees):
        for lo in range(0, nslots, plan.slots):
            nt, ns = min(plan.trees, trees - t), min(plan.slots, nslots - lo)
            yield t, nt, lo, ns, level_plan(nbin, f, ns, nt).lane


def level_calls(nslots: int, f: int, nbin: int,
                use_pallas: bool | None = None,
                trees: int = 1) -> tuple[int, int]:
    """``(kernel calls, those of them by the lane-wide body)`` that
    :func:`level_hist` makes for a level of ``trees`` trees of
    ``nslots`` slots: none on the XLA path.  The two-level body takes a
    call a tree."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return 0, 0
    calls = lane_calls = 0
    for _, nt, _, _, lane in _level_chunks(nslots, f, nbin, trees):
        calls += 1 if lane else nt
        lane_calls += lane
    return calls, lane_calls


def level_packs(nslots: int, f: int, nbin: int,
                use_pallas: bool | None = None, trees: int = 1) -> int:
    """Those of the level's lane-wide calls (:func:`level_calls`) that
    take a job's ``ops.histogram_kernel.pack_plan`` and build its narrow
    features in one shared product: the one-axis calls
    (``lane_packs``)."""
    from rabit_tpu.ops import histogram_kernel as hk

    if use_pallas is None:
        use_pallas = on_tpu()
    return sum(use_pallas and lane
               and hk.lane_packs(nbin, f, hk.call_lanes(nt, ns))
               for _, nt, _, ns, lane in _level_chunks(nslots, f, nbin, trees))


def level_hist(bins_t, gh, node, nslots: int, f: int, nbin: int,
               use_pallas: bool | None = None, compute_dtype=None,
               totals: bool = False, pack=None):
    """``(nslots, f, nbin, 2)`` histograms of one tree level, traceable:
    slot ``s`` holds the (grad, hess) sums of the rows whose ``node`` is
    ``s``; a slot with no row reads zeros, a row at no slot (node < 0)
    is in no histogram, and an absent entry (code ``nbin``) is in no
    bin.  ``bins_t`` is the staged ``(fpad, n)`` array, ``gh`` the
    ``(2, n)`` float32 weights.  The fused kernel folds the node masks
    into the weights a row block at a time (12 bytes a row read, no
    ``(2 * nslots, n)`` matrix in HBM).  With ``totals`` (a job whose
    rows have absent entries) the result is ``(nslots, f + 1, nbin,
    2)``: :func:`with_totals` of each slot's :func:`slot_totals`.

    A round of several trees passes ``(T, 2, n)`` weights and ``(T, n)``
    node ids, each tree's slots its own: ``(T * nslots, ...)``,
    tree-major, what the trees' levels one by one would give.

    The level is built by the kernel calls ``ops.histogram_kernel.
    level_plan`` names for its width, inside the same program
    (:func:`level_calls`): a narrow level by the two-level body, a call
    a tree, and a tree of more slots than :func:`slots_per_call` by
    several, call ``k`` over the slots from ``k * slots_per_call`` on (a
    row of another call's slots matches none of this one's); a wide
    level by the lane-wide body, every tree in one call where its lanes
    hold them, a wide shard's features chunk by chunk inside that call
    (968: the two widest levels of a depth-6 round one call each, 7
    calls a round where the two-level body alone made 14).  Either way
    a channel is the float32 sum of the same
    exact products of the same rounded weights; the bodies differ in
    the order of those adds, so a level reads the same under both to
    float32 rounding (1e-5 of a channel's absolute mass), not bit for
    bit.  ``pack`` is the job's ``ops.histogram_kernel.pack_plan`` (the
    plan and its codes, the codes traceable): a one-axis lane-wide call
    then builds the features of a few codes in one shared product, and
    the level reads the same bit for bit."""
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    if use_pallas is None:
        use_pallas = on_tpu()
    if gh.ndim == 2:
        gh, node = gh[None], node[None]
    trees = gh.shape[0]
    if use_pallas:
        cdt = compute_dtype or hk.DEFAULT_COMPUTE_DTYPE
        outs = []
        for t, nt, lo, ns, _ in _level_chunks(nslots, f, nbin, trees):
            # one tree: the (2, n) and (n,) arguments a tree's call has
            # always had
            at = t if nt == 1 else slice(t, t + nt)
            outs.append(hk.hist_fused_multi(
                bins_t, gh[at], nbin,
                node_of_row=node[at] - lo if lo else node[at],
                nslots=ns, compute_dtype=cdt, features=f, pack=pack))
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        # (trees * nslots * 2, f, nbin), tree-major, slot-major
        out = out.reshape(trees * nslots, 2, f, nbin).transpose(0, 2, 3, 1)
    else:
        cdt = jnp.float32
        out = jnp.concatenate([
            _level_xla(bins_t, gh[t], node[t], nslots, nbin)[:, :f]
            for t in range(trees)])
    if totals:
        out = with_totals(out, jnp.concatenate([
            slot_totals(gh[t], node[t], nslots, cdt) for t in range(trees)]))
    return out


# a missing mass whose hessian part is within this share of the node's
# total reads as none: where nobody is absent, total less bins is the
# float32 accumulation's residue (1e-7 of the total, of either sign),
# and the direction that residue favours would differ from arm to arm
MISSING_MASS_FLOOR = 1e-5


def missing_mass(hist: np.ndarray, total) -> np.ndarray:
    """``(f, 2)`` float64: the (grad, hess) sums of a node's rows that
    are absent from each feature, as the node's ``total`` (2,) less the
    feature's own bins of its ``(f, nbin, 2)`` histogram.  XGBoost's
    layout: an absent entry is added to no bin, so its mass is kept
    nowhere and costs no histogram slot.  A mass under
    ``MISSING_MASS_FLOOR`` of the node's hessian total is no mass."""
    return _mass(np.asarray(hist, np.float64).sum(axis=1),
                 np.asarray(total, np.float64))


def _mass(present: np.ndarray, total: np.ndarray) -> np.ndarray:
    """:func:`missing_mass` from the features' own sums (f, 2);
    ``total`` the node's (2,), or a row a feature where the features are
    those of several nodes side by side (:func:`best_splits`)."""
    mass = total - present
    none = np.abs(mass[:, 1]) <= MISSING_MASS_FLOOR * np.abs(total[..., 1])
    mass[none] = 0.0
    return mass


def split_candidates(hist: np.ndarray, reg_lambda: float = 1.0,
                     min_child_weight: float | None = None,
                     total=None):
    """``(gain, default_left)`` of every (feature, cut) of a (f, nbin, 2)
    histogram: the XGBoost structure score, left = bins 0..cut.  With
    ``total``, the node's (grad, hess) sums over all of its rows, a
    feature's bins may fall short of them by the rows absent from it
    (:func:`missing_mass`): the gain is then the better of sending
    those rows left and right and ``default_left`` says which won
    (ties: left).  Without, every row is in a bin of every feature, the
    totals are the bins' own and ``default_left`` is None.  A candidate
    is scored from its own feature row and ``total`` alone, so the rows
    may be those of several nodes side by side, ``total`` then ``(f,
    2)``, each row's node's (:func:`best_splits`).

    A candidate that leaves a side's hessian sum under
    ``min_child_weight`` is not eligible and reads -inf (XGBoost's
    ``EnumerateSplit`` scores no other): a histogram obtained as parent
    minus sibling reads a few ulp of either sign where no row fell, an
    empty side near ``-reg_lambda`` is then an unbounded gain, and the
    argmax must not see it.  ``None`` scores every candidate."""
    # float64, and without ``total`` the totals are the cumulative sums'
    # own last entries: in float32 a node of millions of rows has sums
    # with an ulp of 0.5, a total summed in another order than the
    # prefix can then leave an empty right side at hr = -1, and
    # hr + lambda = 0 is an infinite gain.  Grad and hess are summed
    # apart and widened as they are: where the caller holds them apart
    # (a fetched shortlist seen channel-last) each sum runs along
    # contiguous memory and no float64 copy of the histogram is made
    hist = np.asarray(hist)
    sg = np.cumsum(hist[..., 0], axis=1, dtype=np.float64)
    sh = np.cumsum(hist[..., 1], axis=1, dtype=np.float64)
    mass = None
    if total is not None:
        mass = _mass(np.stack([sg[:, -1], sh[:, -1]], axis=1),
                     np.asarray(total, np.float64))
        mass = mass[:, :1], mass[:, 1:]
    return _score_sides(sg[:, :-1], sh[:, :-1], sg[:, -1:], sh[:, -1:], mass,
                        reg_lambda, min_child_weight)


def _score_sides(gl, hl, gt, ht, mass, reg_lambda: float,
                 min_child_weight: float | None):
    """:func:`split_candidates` from the left sides' sums ``gl, hl``,
    the present entries' sums ``gt, ht`` of each candidate's feature and
    the features' missing ``mass`` ``(gm, hm)`` (None: no totals), all
    of one shape or broadcastable to it."""
    if mass is not None:
        gm, hm = mass
        gt, ht = gt + gm, ht + hm
    parent = gt * gt / (ht + reg_lambda)

    def score(gl_, hl_):
        # a dozen passes over a (f, nbin - 1) grid, a node: in place
        gr_, hr_ = gt - gl_, ht - hl_
        if min_child_weight is not None:
            barred = (hl_ < min_child_weight) | (hr_ < min_child_weight)
        gain = gl_ * gl_
        gain /= hl_ + reg_lambda
        gr_ *= gr_
        hr_ += reg_lambda
        gr_ /= hr_
        gain += gr_
        gain -= parent
        if min_child_weight is not None:
            gain[barred] = -np.inf
        return gain

    with np.errstate(divide="ignore", invalid="ignore"):
        gain_right = score(gl, hl)         # absent rows, if any, go right
        if mass is None:
            return gain_right, None
        gain_left = score(gl + gm, hl + hm)    # absent rows go left
    return np.maximum(gain_left, gain_right), gain_left >= gain_right


def best_split(hist: np.ndarray, reg_lambda: float,
               min_child_weight: float | None, total=None, widths=None):
    """``(gain, feature, cut, default_left)`` of the best candidate of
    :func:`split_candidates` (the first of equals, feature-major).
    ``widths`` are the bins each feature row holds where that is fewer
    than the row has room for (a window of a flat bin space,
    :func:`level_shortlist_flat`): a cut from a feature's last bin on is
    none."""
    gain, left = split_candidates(hist, reg_lambda, min_child_weight, total)
    if widths is not None:
        gain[np.arange(gain.shape[1]) >= np.asarray(widths)[:, None] - 1] \
            = -np.inf
    j, t = np.unravel_index(int(gain.argmax()), gain.shape)
    return (float(gain[j, t]), int(j), int(t),
            True if left is None else bool(left[j, t]))


def best_splits(hists: np.ndarray, reg_lambda: float,
                min_child_weight: float | None, totals: bool = False,
                widths=None):
    """:func:`best_split` of every slot of a level in one pass: ``(gain,
    row, cut, default_left)``, an entry a slot, of ``(slots, rows, nbin,
    2)`` histograms of any float type and layout (with ``totals`` a
    slot's last row is no feature: its bin 0 holds the slot's (grad,
    hess) totals, :func:`with_totals`; ``widths`` ``(slots, rows)`` as
    :func:`best_split` has a slot's).  The slots' feature rows go
    through :func:`split_candidates` side by side, one cumulative sum
    and one scoring for the level, and each slot takes the first of its
    own best candidates, feature-major: the same float64 operations a
    candidate as slot by slot, so the same decisions to the last bit."""
    hists = np.asarray(hists)
    nslots, rows, nbin, _ = hists.shape
    f = rows - totals
    total = None
    if totals:
        total = np.repeat(np.asarray(hists[:, f, 0], np.float64), f, axis=0)
    # grad and hess apart, a row a (slot, feature): a view of a
    # shortlist as fetched, else the one copy of the pass
    planes = np.moveaxis(hists[:, :f], -1, 0).reshape(2, nslots * f, nbin)
    gain, left = split_candidates(np.moveaxis(planes, 0, -1), reg_lambda,
                                  min_child_weight, total)
    if widths is not None:
        gain[np.arange(nbin - 1) >= np.reshape(widths, (-1, 1)) - 1] = -np.inf
    gain = gain.reshape(nslots, f * (nbin - 1))
    at = gain.argmax(axis=1)
    slot = np.arange(nslots)
    return (gain[slot, at], at // (nbin - 1), at % (nbin - 1),
            np.ones(nslots, bool) if left is None
            else left.reshape(gain.shape)[slot, at])


# features a slot's shortlist holds (:func:`level_shortlist`): the host
# decides in float64 among these, so the float32 ranking has to be wrong
# about this many features at once to lose the best; 8 rows of 256 bins
# are 16 KB a slot
SHORTLIST = 8


def split_candidates_device(g, h, reg_lambda: float = 1.0,
                            min_child_weight: float | None = None,
                            total=None):
    """:func:`split_candidates` in float32, traceable: what ranks a
    slot's features on the device.  It chooses which rows the host
    looks at and decides nothing.  The grad and the hess bins come apart
    (``(..., f, nbin)`` each, the bins on the lanes) and ``total`` as
    ``(gt, ht)`` of shape ``(...)``; returned are the gain and
    ``default_left`` (None without ``total``) of every (feature, cut),
    **the last bin included**, which is no cut and reads -inf.  The
    rules are the host's: the totals are the cumulative sums' own last
    entries plus the missing mass, a mass under ``MISSING_MASS_FLOOR``
    of the node is none, a side under ``min_child_weight`` is barred,
    ties go left."""
    import jax.numpy as jnp

    sg, sh = jnp.cumsum(g, axis=-1), jnp.cumsum(h, axis=-1)
    gt, ht = sg[..., -1:], sh[..., -1:]
    if total is not None:
        total = tuple(t[..., None, None] for t in total)
    return _rank_sides(
        sg, sh, gt, ht, total,
        lambda: jnp.arange(g.shape[-1]) == g.shape[-1] - 1,
        reg_lambda, min_child_weight)


def _rank_sides(sg, sh, gt, ht, total, no_cut, reg_lambda: float,
                min_child_weight: float | None):
    """:func:`split_candidates_device` from the left sides' sums ``sg,
    sh``, the present entries' sums ``gt, ht`` of each candidate's
    feature, the node's ``total`` (shaped to them; None: no totals) and
    the candidates that are no cut (a function that gives the mask: it
    is traced where the rectangle's ranking always traced it, so that
    the dense jobs' ``gbdt_scan`` stays the program it was, operation
    for operation: ``tests/test_boosting_programs_pinned.py``)."""
    import jax.numpy as jnp

    if total is not None:
        tg, th = total
        gm, hm = tg - gt, th - ht
        none = jnp.abs(hm) <= MISSING_MASS_FLOOR * jnp.abs(th)
        gm, hm = jnp.where(none, 0.0, gm), jnp.where(none, 0.0, hm)
        gt, ht = gt + gm, ht + hm
    parent = gt * gt / (ht + reg_lambda)
    no_cut = no_cut()

    def score(gl, hl):
        gr, hr = gt - gl, ht - hl
        gain = gl * gl / (hl + reg_lambda) + gr * gr / (hr + reg_lambda) \
            - parent
        barred = no_cut
        if min_child_weight is not None:
            barred = barred | (hl < min_child_weight) \
                | (hr < min_child_weight)
        return jnp.where(barred, -jnp.inf, gain)

    right = score(sg, sh)                  # absent rows, if any, go right
    if total is None:
        return right, None
    left = score(sg + gm, sh + hm)
    return jnp.maximum(left, right), left >= right


def assemble_level(built, above, build):
    """A level's histograms from what it built, traceable, in the
    layout they keep on the device, ``(2, slots, rows, nbin)`` (grad
    and hess apart, the bins on the lanes).  ``built`` is the level
    program's reduced ``(p, rows, nbin, 2)``; at the root (``above``
    None) it is the level.  Below, ``above`` is the level above so
    assembled and ``build[pos]`` the child of its slot ``pos`` that
    was built (-1: none, the slot was not split): the built child as it
    is, its sibling as parent minus built (the sum over ranks is
    linear: this IS the sibling's reduced histogram, to a float32
    rounding of the parent's cell), and zeros in both children of a
    slot that was not split.

    The level of a round of several trees (a multi-class job's K trees,
    grown together) is this same call on K times the slots, numbered
    tree-major: slot ``s`` of tree ``k`` at a level ``w`` wide is entry
    ``k * w + s``, whose children are entries ``2e`` and ``2e + 1`` of
    the level below as within one tree, so that neither this function
    nor :func:`level_shortlist` knows of trees, no array gains an axis,
    and the subtraction stays within a tree because a parent and its
    children are the same tree's."""
    import jax.numpy as jnp

    b = jnp.moveaxis(built, -1, 0)
    if above is None:
        return b
    sibling = above - b
    live = (build >= 0)[None, :, None, None]
    right = ((build & 1) == 1)[None, :, None, None]
    pair = jnp.stack([jnp.where(right, sibling, b),
                      jnp.where(right, b, sibling)], axis=2)
    return jnp.where(live[:, :, None], pair, 0.0).reshape(
        (2, 2 * b.shape[1]) + b.shape[2:])


def level_shortlist(level, f: int, reg_lambda, min_child_weight,
                    totals: bool, k: int = SHORTLIST):
    """For **every** slot of an assembled level (:func:`assemble_level`;
    node or not: a static shape), traceable: the ``min(k, f)`` features
    of highest best gain by :func:`split_candidates_device` (of equals
    the first), in feature order, as ``(slots, k)`` int32, and their
    histogram rows ``(2, slots, k, nbin)``; with ``totals`` the slot's
    totals row (row ``f`` of the level) rides as one more."""
    import jax
    import jax.numpy as jnp

    total = (level[0, :, f, 0], level[1, :, f, 0]) if totals else None
    gain, _left = split_candidates_device(
        level[0, :, :f], level[1, :, :f], reg_lambda, min_child_weight, total)
    _best, feats = jax.lax.top_k(jnp.max(gain, axis=-1), min(k, f))
    feats = jnp.sort(feats, axis=-1).astype(jnp.int32)
    take = feats
    if totals:
        take = jnp.concatenate(
            [feats, jnp.full(feats.shape[:1] + (1,), f, jnp.int32)], axis=1)
    return feats, jnp.take_along_axis(level, take[None, :, :, None], axis=2)


def _builder(n: int, f: int, nbin: int):
    """Jitted float32 builder of one node's (f, nbin, 2) histogram from
    (n, f) bins and the node's (grad, hess): what
    :func:`build_level_local` builds a level from, node by node, off
    the chip (the host arm's trees on CPU).

    Formulation chosen by measurement on TPU: one (n, nbin) one-hot per
    feature contracted with the packed (n, 2) grad/hess operand on the
    MXU.  Scatter-adds are ~1000x slower on TPU (they serialize) and a
    blocked einsum defeats XLA's fusion; per-feature matmuls stream at
    HBM bandwidth.  Features are processed ``feat_block`` at a time
    inside a ``lax.scan`` — unrolled within a chunk for speed, scanned
    across chunks to bound compile time.
    """
    key = ("node", n, f, nbin)
    feat_block = 8
    fn = _CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        nfb = -(-f // feat_block)
        fpad = nfb * feat_block

        @jax.jit
        def build(bins, grad, hess):
            # pad features with bin -1 (matches no one-hot lane); pack
            # (grad, hess) as one (n, 2) operand for a single contraction
            b = jnp.full((n, fpad), -1, jnp.int32).at[:, :f].set(bins)
            gh = jnp.stack([grad, hess], axis=1)       # (n, 2)
            iota = jnp.arange(nbin, dtype=jnp.int32)

            def chunk(_, bcols):                        # (n, feat_block)
                parts = []
                for j in range(feat_block):
                    oh = (bcols[:, j][:, None] == iota).astype(jnp.float32)
                    parts.append(jax.lax.dot_general(
                        oh, gh, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                return None, jnp.stack(parts)           # (feat_block, nbin, 2)

            _, out = jax.lax.scan(
                chunk, None,
                b.reshape(n, nfb, feat_block).transpose(1, 0, 2))
            return out.reshape(fpad, nbin, 2)[:f]

        _CACHE[key] = build
        fn = build
    return fn


def build_level_local(bins, grad, hess, node_of_row, node_ids,
                      nbin: int, bins_t=None, use_pallas: bool | None = None,
                      compute_dtype=None, totals: bool = False):
    """(m, f, nbin, 2) per-node histograms for one tree level, of the
    nodes ``node_ids`` names in any order.

    Level-wise boosting needs one histogram per live node; building
    them one at a time re-reads the (n, f) bins array per node.  On
    TPU this is :func:`level_hist` on the ids' places: every node
    through one fused-kernel bins pass a call, the node masks folded
    into the grad/hess channels inside the kernel (no (2m, n) weight
    matrix), in as many calls as a level that wide takes (what a call
    costs by its width: ``ops.histogram_kernel._LANE_CROSSING``'s
    table).  ``bins_t`` optionally supplies the resident transposed
    (f, n) device array so the transpose isn't redone per level.
    Off the chip (``use_pallas`` false) the level is built node by
    node in float32 by :func:`_builder`: the host arm's trees on CPU.
    ``totals`` as :func:`level_hist` has it: (m, f + 1, nbin, 2).
    """
    import jax.numpy as jnp

    if use_pallas is None:
        use_pallas = on_tpu()
    nor = jnp.asarray(np.asarray(node_of_row, np.int32))
    g = jnp.asarray(grad)
    h = jnp.asarray(hess)
    m = len(node_ids)
    # node id -> position in node_ids, -1 for a row of another node
    ids = np.asarray(node_ids, np.int64)
    lut = np.full(int(ids.max(initial=0)) + 2, -1, np.int32)
    lut[ids] = np.arange(m, dtype=np.int32)
    slot = jnp.asarray(lut)[jnp.clip(nor, -1, len(lut) - 1)]
    gh = jnp.stack([g, h]).astype(jnp.float32)
    if use_pallas:
        if bins_t is None:
            bins_t = jnp.asarray(bins).T
        return level_hist(bins_t, gh, slot, m, bins_t.shape[0], nbin,
                          use_pallas=True, compute_dtype=compute_dtype,
                          totals=totals)
    g_np, h_np, nor_np = np.asarray(g), np.asarray(h), np.asarray(nor)
    build, b = _builder(*bins.shape, nbin), jnp.asarray(bins)
    out = jnp.stack([build(b, jnp.asarray(g_np * (nor_np == v)),
                           jnp.asarray(h_np * (nor_np == v)))
                     for v in np.asarray(node_ids)])
    return with_totals(out, slot_totals(gh, slot, m, jnp.float32)) \
        if totals else out


# ----------------------------------------------------------------------
# sparse rows: entries, and a histogram of each column's own bins
# ----------------------------------------------------------------------
# rows binned by one thread's call of :func:`bin_entries`
BIN_CHUNK_ROWS = 1 << 16


class FlatBins:
    """The bin space of a job on sparse rows: a column's bins follow
    the column before's, as many as its cuts and one (XGBoost's
    ``HistogramCuts``: ``cut_ptr[j]`` is where column ``j``'s cuts begin
    in ``cut_vals``, ``ptr[j]`` where its cells do).  An indicator
    column is two cells where the rectangle gives it ``nbin``.  One more
    cell, ``nbins``, holds a level slot's (grad, hess) totals, as the
    rectangle's extra feature row does (:func:`with_totals`), and the
    axis is padded to whole blocks of the kernel's (``size``): that is
    what a level reduces and keeps."""

    def __init__(self, cut_ptr, cut_vals, nbin: int):
        from rabit_tpu.ops.sparse_hist_kernel import CELL_BLOCK, num_blocks

        self.cut_ptr = np.asarray(cut_ptr, np.int64)
        self.cut_vals = np.asarray(cut_vals, np.float32)
        self.nbin, self.f = nbin, len(self.cut_ptr) - 1
        self.widths = np.diff(self.cut_ptr) + 1         # bins a column
        assert self.f == 0 or self.widths.max() <= nbin, (self.widths.max(),
                                                           nbin)
        self.ptr = self.cut_ptr + np.arange(self.f + 1)
        self.nbins = int(self.ptr[-1])
        self.cells = self.nbins + 1                     # and the totals'
        self.size = num_blocks(self.cells) * CELL_BLOCK
        col = np.full(self.size, self.f, np.int32)
        col[:self.nbins] = np.repeat(np.arange(self.f, dtype=np.int32),
                                     self.widths)
        # every cell past the columns' is a column of its own to a scan
        self.col_of_cell = col
        at = np.arange(self.size)
        self.first = (at >= self.nbins) | np.isin(at, self.ptr[:-1])
        self.last = (at >= self.nbins) | np.isin(at, self.ptr[1:] - 1)

    def __getitem__(self, at) -> float:
        """``flat[feature, threshold]``: the cut a split at that bin of
        that column holds, as the rectangle of cuts is indexed."""
        feature, threshold = at
        return float(self.cut_vals[self.cut_ptr[feature] + threshold])


def _order_bits(v: np.ndarray) -> np.ndarray:
    """uint64 keys whose order is the float32 values' (-0.0 with 0.0),
    in the low 32 bits."""
    bits = (np.asarray(v, np.float32) + np.float32(0.0)).view(np.uint32)
    return np.where(bits >> 31, ~bits, bits | np.uint32(1 << 31)).astype(
        np.uint64)


def sparse_cuts(sample, nbin: int):
    """``(cut_ptr, cut_vals)`` of a sample's entries (``data.EllRows``):
    a column's cuts are the distinct values among the ``nbin - 1``
    quantiles of its present entries, :func:`quantile_cuts` of the
    column with NaN for the absent ones, bit for bit, each kept once (a
    repeated cut bounds a bin no value can fall in).  A column of one
    distinct value has one cut, and so has one without an entry (0.0, as
    there)."""
    f = sample.feat_dim
    held = sample.present()
    cols = sample.indices[held].astype(np.uint64)
    keys = np.sort(cols << np.uint64(32) | _order_bits(sample.values[held]))
    del cols
    bits = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    flat = np.where(bits >> 31, bits & np.uint32(0x7FFFFFFF), ~bits).view(
        np.float32)
    cnt = np.bincount((keys >> np.uint64(32)).astype(np.int64), minlength=f)
    start = (np.cumsum(cnt) - cnt)[:, None]
    qs = np.linspace(0, 1, nbin + 1)[1:-1]
    if flat.size == 0:
        flat = np.zeros(1, np.float32)
    rect = _quantiles(lambda at: flat[np.minimum(start + at, flat.size - 1)],
                      cnt, qs).astype(np.float32)
    keep = np.ones(rect.shape, bool)
    keep[:, 1:] = rect[:, 1:] != rect[:, :-1]
    cut_ptr = np.concatenate([[0], np.cumsum(keep.sum(axis=1))])
    return cut_ptr.astype(np.int32), rect[keep]


def bin_entries(rows, flat: FlatBins) -> np.ndarray:
    """``(n, width)`` int32: the cell of every entry of ``rows``
    (``data.EllRows``), -1 where a slot holds none.  An entry of column
    ``j`` lies in cell ``ptr[j] +`` the number of the column's cuts at
    or below its value (:func:`apply_cuts`' rule).  One ``searchsorted``
    of (column, value) keys among the cuts', a block of rows a thread:
    the device's gather is 8 ns an element and a binary search over
    ragged cuts eight of them an entry."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    cut_keys = (np.repeat(np.arange(flat.f, dtype=np.uint64),
                          np.diff(flat.cut_ptr)) << np.uint64(32)
                | _order_bits(flat.cut_vals))
    n = rows.indices.shape[0]
    out = np.empty(rows.indices.shape, np.int32)

    def block(lo: int) -> None:
        part = rows.rows(slice(lo, lo + BIN_CHUNK_ROWS))
        held = part.present()
        cols = np.where(held, part.indices, 0).astype(np.uint64)
        below = np.searchsorted(
            cut_keys, cols << np.uint64(32) | _order_bits(part.values),
            side="right")
        out[lo:lo + BIN_CHUNK_ROWS] = np.where(
            held, below + cols.astype(np.int64), -1)

    with ThreadPoolExecutor(min(12, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(0, n, BIN_CHUNK_ROWS)))
    return out


def level_hist_flat(entries, gh, node, nslots: int, flat: FlatBins,
                    use_pallas: bool | None = None, compute_dtype=None):
    """``(trees * nslots, flat.size, 2)`` histograms of one tree level
    of sparse rows, traceable: :func:`level_hist` on the flat bin space,
    a slot's totals in cell ``flat.nbins``.  ``entries`` are the shard's
    as staged, ``(cells_t, packed, fb)``: the row move's ``(width, n)``
    cells and, on the kernel's road, the bucketed slots and their blocks
    (``ops.sparse_hist_kernel``); ``gh`` and ``node`` as there.  The
    kernel takes 16 slots a call and a tree a call; its operand is
    bfloat16 and a ``compute_dtype`` of float32 takes the XLA road,
    which adds the weights as they are."""
    import jax.numpy as jnp

    from rabit_tpu.ops import sparse_hist_kernel as sk

    if use_pallas is None:
        use_pallas = on_tpu()
    cells_t, packed, fb = entries
    if gh.ndim == 2:
        gh, node = gh[None], node[None]
    kernel = use_pallas and jnp.dtype(
        compute_dtype or jnp.bfloat16) == jnp.bfloat16
    outs = []
    for t in range(gh.shape[0]):
        if kernel:
            parts = []
            for lo in range(0, nslots, sk.CALL_SLOTS):
                ns = min(sk.CALL_SLOTS, nslots - lo)
                mine = node[t] if ns == nslots else jnp.where(
                    (node[t] >= lo) & (node[t] < lo + ns), node[t] - lo, -1)
                parts.append(sk.hist_sparse(
                    packed, fb, gh[t], mine,
                    tiles=cells_t.shape[1] // sk.ROW_TILE, nslots=ns,
                    cells=flat.cells, interpret=not on_tpu()))
            out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        else:
            out = sk.hist_sparse_xla(cells_t, gh[t], node[t], nslots,
                                     flat.cells)
        outs.append(out.at[:, flat.nbins].set(slot_totals(
            gh[t], node[t], nslots,
            jnp.bfloat16 if kernel else jnp.float32)))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _segment_scan(x, starts, op, reverse: bool = False):
    """Inclusive scan of ``x`` along its last axis by ``op`` that begins
    anew at every cell ``starts`` marks (in a reverse scan: the cells a
    segment ends at), traceable."""
    import jax
    import jax.numpy as jnp

    def combine(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], op(a[1], b[1]))

    return jax.lax.associative_scan(
        combine, (jnp.broadcast_to(starts, x.shape), x), reverse=reverse,
        axis=x.ndim - 1)[1]


def _over_columns(x, flat: FlatBins, op):
    """Every cell's column's ``op`` over ``x`` (last axis: the flat
    cells), traceable: a scan each way."""
    import jax.numpy as jnp

    first, last = jnp.asarray(flat.first), jnp.asarray(flat.last)
    return (_segment_scan(x, first, op),
            _segment_scan(x, last, op, reverse=True))


def level_shortlist_flat(level, flat: FlatBins, reg_lambda,
                         min_child_weight, k: int = SHORTLIST):
    """:func:`level_shortlist` of a level on the flat bin space
    (``(2, slots, 1, flat.size)``, :func:`assemble_level` of
    :func:`level_hist_flat`'s), traceable: for every slot the ``min(k,
    f)`` columns of highest best gain, in column order, ``(slots, k)``
    int32, and their histogram rows as **windows** of ``nbin`` cells
    from each column's first, zeros past its last, with the totals row
    after them: ``(2, slots, k + 1, nbin)``, what the rectangle's
    shortlist hands the host, so that the host decides on it as it does
    there (given the columns' widths: ``best_split``).  A column's
    prefix sums are a scan that begins anew at its first cell; its
    present entries' sums that scan and the one from its last cell
    back, less the cell."""
    import jax
    import jax.numpy as jnp

    g, h = level[0, :, 0], level[1, :, 0]               # (slots, size)
    total = (g[:, flat.nbins, None], h[:, flat.nbins, None])
    inside = jnp.arange(flat.size) < flat.nbins
    x = jnp.where(inside, level[:, :, 0], 0.0)
    pre, suf = _over_columns(x, flat, jnp.add)
    sums = pre + suf - x
    gain, _left = _rank_sides(
        pre[0], pre[1], sums[0], sums[1], total,
        lambda: jnp.asarray(flat.last) | ~inside, reg_lambda,
        min_child_weight)
    best = jnp.maximum(*_over_columns(gain, flat, jnp.maximum))
    heads = jnp.where(jnp.asarray(flat.first) & inside, best, -jnp.inf)
    _best, at = jax.lax.top_k(heads, min(k, flat.f))
    feats = jnp.sort(jnp.asarray(flat.col_of_cell)[at], axis=-1)
    # the totals' cell is a column of one bin after the last
    take = jnp.concatenate(
        [feats, jnp.full(feats.shape[:1] + (1,), flat.f, jnp.int32)], axis=1)
    ptr = jnp.asarray(np.append(flat.ptr[:-1], flat.nbins).astype(np.int32))
    width = jnp.asarray(np.append(flat.widths, 1).astype(np.int32))
    lane = jnp.arange(flat.nbin, dtype=jnp.int32)
    held = lane < width[take][..., None]                # (slots, k + 1, nbin)
    cell = jnp.where(held, ptr[take][..., None] + lane, flat.nbins)
    rows = jnp.take_along_axis(level[:, :, 0][:, :, None, :], cell[None],
                               axis=3)
    return feats, jnp.where(held[None], rows, 0.0)


def flat_shortlist(hists: np.ndarray, flat: FlatBins, reg_lambda: float,
                   min_child_weight: float | None, k: int = SHORTLIST):
    """:func:`level_shortlist_flat` on the host, in float64, of a
    level's ``(slots, flat.size, 2)`` histograms: the columns ``(slots,
    k)`` and their windows ``(slots, k + 1, nbin, 2)`` as
    ``boosting._fetch_shortlist`` hands them on."""
    k = min(k, flat.f)
    starts, ends = flat.ptr[:-1], flat.ptr[1:] - 1
    feats = np.zeros((len(hists), k), np.int32)
    rows = np.zeros((len(hists), k + 1, flat.nbin, 2))
    for s, hist in enumerate(np.asarray(hists, np.float64)):
        x, total = hist[:flat.nbins], hist[flat.nbins]
        run = np.cumsum(x, axis=0)
        pre = run - np.repeat(run[starts] - x[starts], flat.widths, axis=0)
        sums = pre[ends]
        mass = np.repeat(_mass(sums, total), flat.widths, axis=0)
        sums = np.repeat(sums, flat.widths, axis=0)
        gain, _left = _score_sides(
            pre[:, 0], pre[:, 1], sums[:, 0], sums[:, 1],
            (mass[:, 0], mass[:, 1]), reg_lambda, min_child_weight)
        gain[ends] = -np.inf
        best = np.maximum.reduceat(gain, starts)
        feats[s] = np.sort(np.argsort(-best, kind="stable")[:k])
        for r, j in enumerate(feats[s]):
            rows[s, r, :flat.widths[j]] = x[flat.ptr[j]:flat.ptr[j + 1]]
        rows[s, k, 0] = total
    return feats, rows


def _place_rows_program(n: int, c: int, width: int):
    """Compiled ``gbdt_sparse_place``: ``c`` rows of binned entries
    written, transposed, into columns ``[lo, lo + c)`` of the staged
    ``(width, n)`` cells in place."""
    key = ("sparse_place", n, c, width)
    fn = _CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def gbdt_sparse_place(cells_t, part, lo):
            with jax.named_scope("gbdt/bin"):
                return jax.lax.dynamic_update_slice(
                    cells_t, part.T, (jnp.int32(0), lo))

        sds = jax.ShapeDtypeStruct
        fn = _CACHE[key] = jax.jit(
            gbdt_sparse_place, donate_argnums=(0,)).lower(
                sds((width, n), jnp.int32), sds((c, width), jnp.int32),
                sds((), jnp.int32)).compile()
    return fn


def _bucket_program(n: int, width: int, tiles: int, cells: int):
    """Compiled ``gbdt_sparse_bucket``: ``ops.sparse_hist_kernel.
    bucket_group`` of the ``tiles`` tiles of the staged ``(width, n)``
    cells from row ``lo`` on."""
    key = ("sparse_bucket", n, width, tiles, cells)
    fn = _CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        from rabit_tpu.ops import sparse_hist_kernel as sk

        def gbdt_sparse_bucket(cells_t, lo):
            return sk.bucket_group(jax.lax.dynamic_slice_in_dim(
                cells_t, lo, tiles * sk.ROW_TILE, axis=1), cells=cells)

        sds = jax.ShapeDtypeStruct
        fn = _CACHE[key] = jax.jit(gbdt_sparse_bucket).lower(
            sds((width, n), jnp.int32), sds((), jnp.int32)).compile()
    return fn


def stage_entries(rows, flat: FlatBins, bucket: bool):
    """Bin a shard of sparse rows (``data.EllRows``) and keep it on the
    device as entries: returns ``(cells_t, packed, fb)``, the ``(width,
    n)`` int32 cells of the rows' entries, row-major transposed (-1: no
    entry; ``n`` the rows padded to whole tiles of the kernel's, a row
    of padding holding none), which the row move searches and the XLA
    road adds up, and with ``bucket`` the same entries once more,
    re-ordered for the kernel (``ops.sparse_hist_kernel.bucket_group``,
    ``GROUP_TILES`` tiles a call; None, None without).  That second copy
    is what the kernel's road costs in HBM: ``capacity / (ROW_TILE *
    width)`` of the first, 9% more than it at 32 entries a row over 24
    blocks.  The 9% is room for the worst case (every bucket a slot over
    a whole sub-chunk); what the buckets leave of it, with the rows' own
    empty slots, is each tile's tail, which ``bucket_group`` marks and
    the kernel does not work.  No array of rows by columns is made
    anywhere.

    The entries are binned on the host, a chunk of ``STAGE_CHUNK_ROWS``
    rows at a time on its threads (:func:`bin_entries`), and cross as
    their cells, 4 bytes where index and value are 8.  Counts the
    entries of the rows-by-columns matrix and the absent among them
    (``gbdt.entries``, ``gbdt.entries_missing``, as :func:`stage_bins`),
    the present ones, the slots staged for the kernel, those of the
    steps it works (``slots_worked``: all of them without ``bucket``) and
    the bins a slot has and the rectangle would (``gbdt.sparse.*``)."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.obs import program
    from rabit_tpu.ops import sparse_hist_kernel as sk
    from rabit_tpu.ops.sparse_linear_kernel import place

    rows_n, width = rows.indices.shape
    tiles = -(-max(rows_n, 1) // sk.ROW_TILE)
    n = tiles * sk.ROW_TILE
    cells_t = jnp.full((width, n), -1, jnp.int32)
    present = 0
    for lo in range(0, rows_n, STAGE_CHUNK_ROWS):
        c = min(STAGE_CHUNK_ROWS, rows_n - lo)
        with program.span("stage.sparse_bin"):
            part = bin_entries(rows.rows(slice(lo, lo + c)), flat)
            present += int(np.count_nonzero(part >= 0))
        with program.span("stage.compile"):
            fn = _place_rows_program(n, c, width)
        with program.span("stage.put"):
            part = jax.device_put(part)
        cells_t = fn(cells_t, part, np.int32(lo))
    packed = fb = None
    slots = worked = n * width
    if bucket:
        with program.span("stage.sparse_bucket"):
            cap = sk.capacity(width, flat.cells)
            slots = tiles * cap
            packed = jnp.zeros((slots // sk.SUB, sk.SUB), jnp.int32)
            fb = jnp.zeros((slots // sk.STEP, sk.SUBS), jnp.int32)
            for t in range(0, tiles, sk.GROUP_TILES):
                g = min(sk.GROUP_TILES, tiles - t)
                part, part_fb, _real = _bucket_program(
                    n, width, g, flat.cells)(cells_t,
                                             np.int32(t * sk.ROW_TILE))
                packed = place(packed, part, np.int32(t * cap // sk.SUB))
                fb = place(fb, part_fb, np.int32(t * cap // sk.STEP))
            jax.block_until_ready((packed, fb))
            worked = int(sk.steps_worked(fb, cells=flat.cells)) * sk.STEP
    program.count("gbdt.entries", rows_n * flat.f)
    program.count("gbdt.entries_missing", rows_n * flat.f - present)
    program.count("gbdt.sparse.entries", present)
    program.count("gbdt.sparse.slots", slots)
    program.count("gbdt.sparse.slots_worked", worked)
    program.count("gbdt.sparse.bins", flat.nbins)
    program.count("gbdt.sparse.bins_rect", flat.f * flat.nbin)
    return cells_t, packed, fb

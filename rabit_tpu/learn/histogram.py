"""XGBoost-style gradient-histogram building + allreduce.

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
every split node and subtracts for the sibling.  The cross-worker step is one
framework allreduce of the flat histogram, exactly the XGBoost wire
pattern.
"""
from __future__ import annotations

import numpy as np

import rabit_tpu
from rabit_tpu.ops import SUM, on_tpu

_CACHE: dict = {}


def _writable(arr) -> "np.ndarray":
    """Host-allreduce input prep: the collective is in-place by
    contract (include/rabit.h:134-137) but jax arrays export read-only
    buffers — copy exactly when the local build handed us one."""
    arr = np.asarray(arr)
    if not arr.flags.writeable:
        arr = arr.copy()
    return arr

DEFAULT_ROW_BLOCK = 8192
DEFAULT_FEAT_BLOCK = 8


def quantile_cuts(values: np.ndarray, nbin: int) -> np.ndarray:
    """Per-column quantile cut points, shape (f, nbin - 1) — the
    host-side analogue of XGBoost's quantile sketch (per-shard; callers
    needing globally consistent cuts broadcast/allreduce them).

    NaN entries are missing values: cuts come from the present entries
    only (``nanquantile``) — plain ``quantile`` would poison a whole
    column's cuts to NaN.  An all-NaN column gets zero cuts (every
    present-at-predict-time value bins to 0; its rows ride the missing
    bin anyway)."""
    qs = np.linspace(0, 1, nbin + 1)[1:-1]
    with np.errstate(all="ignore"):
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            cuts = np.nanquantile(values, qs, axis=0).T
    return np.nan_to_num(cuts, nan=0.0).astype(np.float32)


def apply_cuts(values: np.ndarray, cuts: np.ndarray) -> np.ndarray:
    """Bin raw feature values with quantile cuts → int32 in [0, nbin);
    NaN (missing) values map to the dedicated bin ``nbin`` one past the
    regular range, so histogram builders can tally missing-row gradient
    mass per feature and the booster can learn a per-split default
    direction (XGBoost's sparsity-aware split semantics)."""
    n, f = values.shape
    bins = np.empty((n, f), np.int32)
    for j in range(f):
        bins[:, j] = np.searchsorted(cuts[j], values[:, j], side="right")
    nan = np.isnan(values)
    if nan.any():
        bins[nan] = cuts.shape[1] + 1
    return bins


STAGE_CHUNK_ROWS = 1 << 20


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

        def gbdt_bin(bins_t, seen, vals, cuts_t, lo):
            with jax.named_scope("gbdt/bin"):
                v = vals.T                                   # (f, c)
                # cuts <= value, counted: searchsorted(side="right");
                # a NaN is below every cut and takes the missing bin
                b = jnp.sum(cuts_t[:, :, None] <= v[None, :, :], axis=0,
                            dtype=jnp.int32)
                nan = jnp.isnan(v)
                b = jnp.where(nan, ncut + 1, b)
                b = jnp.pad(b, ((0, fpad - f), (0, 0)))
                seen = jnp.maximum(seen, jnp.stack(
                    [jnp.any(nan).astype(jnp.int32), jnp.max(b)]))
                return jax.lax.dynamic_update_slice(
                    bins_t, b, (jnp.int32(0), lo)), seen

        sds = jax.ShapeDtypeStruct
        fn = jax.jit(gbdt_bin, donate_argnums=(0, 1)).lower(
            sds((fpad, n), jnp.int32), sds((2,), jnp.int32),
            sds((c, f), jnp.float32), sds((ncut, f), jnp.float32),
            sds((), jnp.int32)).compile()
        _CACHE[key] = fn
    return fn


def stage_bins(values: np.ndarray, cuts: np.ndarray, nbin: int):
    """Bin a shard on the device and keep it there: returns the
    ``(fpad, n)`` int32 array :func:`level_hist` streams (features
    padded to the kernel's group with bin 0, whose histograms are
    dropped) and a (2,) int32 device array ``[any NaN, largest bin]``.

    Equal to :func:`apply_cuts` bit for bit, NaN included.  The float
    values cross to the device a chunk of rows at a time and only the
    bins stay; nothing of length n is made on the host."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.obs import program

    n, f = values.shape
    fpad, ncut = staged_features(f, nbin), cuts.shape[1]
    cuts_t = jnp.asarray(np.ascontiguousarray(cuts.T, np.float32))
    bins_t = jnp.zeros((fpad, n), jnp.int32)
    seen = jnp.zeros((2,), jnp.int32)
    chunk = min(n, STAGE_CHUNK_ROWS)
    for lo in range(0, n, chunk):
        c = min(chunk, n - lo)
        with program.span("stage.compile"):
            fn = _bin_program(n, c, f, fpad, ncut)
        with program.span("stage.put"):
            vals = jax.device_put(
                np.ascontiguousarray(values[lo:lo + c], np.float32))
        with program.span("stage.bin"):
            bins_t, seen = fn(bins_t, seen, vals, cuts_t, np.int32(lo))
    return bins_t, seen


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


def slots_per_call(nbin: int, f: int) -> int:
    """Level slots one kernel call of :func:`level_hist` builds: half
    the kernel's widest worthwhile call (a slot is a grad and a hess
    channel)."""
    from rabit_tpu.ops.histogram_kernel import max_channels

    return max(1, max_channels(nbin, f) // 2)


def level_calls(nslots: int, f: int, nbin: int,
                use_pallas: bool | None = None) -> int:
    """Kernel calls :func:`level_hist` makes for a level of ``nslots``:
    none on the XLA path."""
    if use_pallas is None:
        use_pallas = on_tpu()
    return -(-nslots // slots_per_call(nbin, f)) if use_pallas else 0


def level_hist(bins_t, gh, node, nslots: int, f: int, nbin: int,
               use_pallas: bool | None = None, compute_dtype=None):
    """``(nslots, f, nbin, 2)`` histograms of one tree level, traceable:
    slot ``s`` holds the (grad, hess) sums of the rows whose ``node`` is
    ``s``; a slot with no row reads zeros, a row at no slot (node < 0)
    is in no histogram.  ``bins_t`` is the staged ``(fpad, n)`` array,
    ``gh`` the ``(2, n)`` float32 weights.  The fused kernel folds the
    node masks into the weights a row block at a time (12 bytes a row
    read, no ``(2 * nslots, n)`` matrix in HBM).

    A level of more slots than :func:`slots_per_call` is built by
    several kernel calls inside the same program, call ``k`` over the
    slots from ``k * slots_per_call`` on (a row of another call's slots
    matches none of this one's), and their results joined: a channel
    sees the same rows in the same order either way, so the histograms
    are those of one wide call bit for bit, at the kernel's time a
    channel of a narrow one."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return _level_xla(bins_t, gh, node, nslots, nbin)[:, :f]
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    kw = {} if compute_dtype is None else {"compute_dtype": compute_dtype}
    per_call = slots_per_call(nbin, bins_t.shape[0])
    outs = [hk.hist_fused_multi(bins_t, gh, nbin,
                                node_of_row=node - lo if lo else node,
                                nslots=min(per_call, nslots - lo), **kw)
            for lo in range(0, nslots, per_call)]
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    # (2 * nslots, fpad, nbin), slot-major
    return out.reshape(nslots, 2, -1, nbin).transpose(0, 2, 3, 1)[:, :f]


def split_candidates(hist: np.ndarray, reg_lambda: float = 1.0,
                     min_child_weight: float | None = None,
                     has_missing: bool = False):
    """``(gain, default_left)`` of every (feature, cut) of a (f, nbin, 2)
    histogram: the XGBoost structure score, left = bins 0..cut.  With
    ``has_missing`` the LAST bin holds the missing-value rows, the gain
    is the better of sending them left and right and ``default_left``
    says which won (None without).

    A candidate that leaves a side's hessian sum under
    ``min_child_weight`` is not eligible and reads -inf (XGBoost's
    ``EnumerateSplit`` scores no other): a histogram obtained as parent
    minus sibling reads a few ulp of either sign where no row fell, an
    empty side near ``-reg_lambda`` is then an unbounded gain, and the
    argmax must not see it.  ``None`` scores every candidate."""
    # float64, and the totals are the cumulative sums' own last entries:
    # in float32 a node of millions of rows has sums with an ulp of 0.5,
    # a total summed in another order than the prefix can then leave an
    # empty right side at hr = -1, and hr + lambda = 0 is an infinite gain
    hist = np.asarray(hist, np.float64)
    reg = hist[:, :-1] if has_missing else hist
    gc, hc = np.cumsum(reg[:, :, 0], axis=1), np.cumsum(reg[:, :, 1], axis=1)
    gl, hl = gc[:, :-1], hc[:, :-1]
    gt, ht = gc[:, -1:], hc[:, -1:]
    if has_missing:
        gm, hm = hist[:, -1:, 0], hist[:, -1:, 1]
        gt, ht = gt + gm, ht + hm
    parent = gt * gt / (ht + reg_lambda)

    def score(gl_, hl_):
        gr_, hr_ = gt - gl_, ht - hl_
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = (gl_ * gl_ / (hl_ + reg_lambda)
                    + gr_ * gr_ / (hr_ + reg_lambda) - parent)
        if min_child_weight is None:
            return gain
        return np.where((hl_ >= min_child_weight) & (hr_ >= min_child_weight),
                        gain, -np.inf)

    gain_right = score(gl, hl)             # missing rows, if any, go right
    if not has_missing:
        return gain_right, None
    gain_left = score(gl + gm, hl + hm)    # missing rows go left
    return np.maximum(gain_left, gain_right), gain_left >= gain_right


def split_gain_missing(hist: np.ndarray, reg_lambda: float = 1.0):
    """Sparsity-aware split gain: the LAST bin of ``hist`` (f, nbin, 2)
    holds the missing-value rows.  For every (feature, cut) the gain is
    evaluated with the missing mass sent left and sent right; returns
    ``(gain, default_left)`` where gain is the better of the two and
    default_left says which direction won (XGBoost's learned default
    direction, one bool per candidate split)."""
    return split_candidates(hist, reg_lambda, has_missing=True)


def quantize(values: np.ndarray, nbin: int):
    """Quantile-bin each feature column; returns (bins, cuts)."""
    cuts = quantile_cuts(values, nbin)
    return apply_cuts(values, cuts), cuts


def _builder(n: int, f: int, nbin: int, row_block: int, feat_block: int):
    """Jitted histogram builder.

    Formulation chosen by measurement on TPU: one (n, nbin) one-hot per
    feature contracted with the packed (n, 2) grad/hess operand on the
    MXU.  Scatter-adds are ~1000x slower on TPU (they serialize) and a
    blocked einsum defeats XLA's fusion; per-feature matmuls stream at
    HBM bandwidth.  Features are processed ``feat_block`` at a time
    inside a ``lax.scan`` — unrolled within a chunk for speed, scanned
    across chunks to bound compile time.  ``row_block`` is accepted for
    API stability but the contraction is over all rows at once.
    """
    key = (n, f, nbin, row_block, feat_block)
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


def build_local(bins, grad, hess, nbin: int,
                row_block: int = DEFAULT_ROW_BLOCK,
                feat_block: int = DEFAULT_FEAT_BLOCK,
                use_pallas: bool | None = None,
                compute_dtype=None) -> np.ndarray:
    """Local (f, nbin, 2) histogram of (grad, hess) sums on device.

    Measured on TPU with chained difference timing
    (doc/benchmarks.md): the fused Pallas
    kernel (:mod:`rabit_tpu.ops.histogram_kernel`) runs a single
    histogram in ~0.8 ms vs ~30 ms for the XLA one-hot contraction
    (~37x), so it is the default on TPU; off-TPU the XLA path is used
    (``use_pallas=True`` forces interpret mode for tests).  Per-node
    level builds share one bins pass — see :func:`build_level_local`.
    ``compute_dtype`` bounds the kernel's weight rounding (default
    bf16; one-hots are exact).
    """
    import jax.numpy as jnp

    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        from rabit_tpu.ops.histogram_kernel import hist_fused
        kw = {} if compute_dtype is None else {"compute_dtype": compute_dtype}
        return hist_fused(bins, grad, hess, nbin, **kw)
    n, f = bins.shape
    fn = _builder(n, f, nbin, row_block, feat_block)
    return fn(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess))


def build_level_local(bins, grad, hess, node_of_row, node_ids,
                      nbin: int, bins_t=None, use_pallas: bool | None = None,
                      compute_dtype=None):
    """(m, f, nbin, 2) per-node histograms for one tree level.

    Level-wise boosting needs one histogram per live node; building
    them one at a time re-reads the (n, f) bins array per node.  On
    TPU this routes every node through ONE fused-kernel bins pass
    (measured ~25x over per-node XLA passes at 8 nodes,
    doc/benchmarks.md):
    :func:`rabit_tpu.ops.histogram_kernel.hist_fused_multi`, which
    folds the node masks into the grad/hess channels itself (no (2m, n)
    weight matrix), in as many kernel calls as :func:`level_hist`
    makes of a level that wide.
    ``bins_t`` optionally supplies the resident transposed (f, n)
    device array so the transpose isn't redone per level.  Off-TPU,
    falls back to the XLA builder per node.
    """
    import jax.numpy as jnp

    if use_pallas is None:
        use_pallas = on_tpu()
    nor = jnp.asarray(np.asarray(node_of_row, np.int32))
    g = jnp.asarray(grad)
    h = jnp.asarray(hess)
    m = len(node_ids)
    if use_pallas:
        if bins_t is None:
            bins_t = jnp.asarray(bins).T
        # node id -> position in node_ids, -1 for a row of another node
        ids = np.asarray(node_ids, np.int64)
        lut = np.full(int(ids.max(initial=0)) + 2, -1, np.int32)
        lut[ids] = np.arange(m, dtype=np.int32)
        slot = jnp.asarray(lut)[jnp.clip(nor, -1, len(lut) - 1)]
        gh = jnp.stack([g, h]).astype(jnp.float32)
        return level_hist(bins_t, gh, slot, m, bins_t.shape[0], nbin,
                          use_pallas=True, compute_dtype=compute_dtype)
    g_np, h_np, nor_np = np.asarray(g), np.asarray(h), np.asarray(nor)
    parts = [build_local(bins, g_np * (nor_np == v), h_np * (nor_np == v),
                         nbin, use_pallas=False)
             for v in np.asarray(node_ids)]
    return jnp.stack([jnp.asarray(p) for p in parts])


def build_level_allreduce(bins, grad, hess, node_of_row, node_ids,
                          nbin: int, **kw) -> np.ndarray:
    """Global per-node level histograms: one local fused pass + ONE
    framework Allreduce<Sum> for the whole level (vs one per node).

    Under the XLA engine the payload stays a device array so the
    reduction rides the device data plane (ICI) like the kmeans stats
    matrix does; host engines take the fault-tolerant numpy path."""
    from rabit_tpu import engine as _engine_mod

    local = build_level_local(
        bins, grad, hess, node_of_row, node_ids, nbin, **kw)
    if not _engine_mod.is_device_plane():
        local = _writable(local)  # fault-tolerant host path
    shape = local.shape
    out = rabit_tpu.allreduce(local.reshape(-1), SUM)
    return np.asarray(out).reshape(shape)


def build_allreduce(bins, grad, hess, nbin: int, **kw) -> np.ndarray:
    """Global histogram: local build + framework Allreduce<Sum> of the
    flat payload (the XGBoost per-split wire pattern).

    Histogram sums deliberately stay opted IN to an armed lossy wire
    codec (``rabit_wire_codec``, doc/performance.md): split decisions
    compare aggregate (g, h) sums whose ordering survives one
    quantization step, and the error-feedback stream compensates
    across the repeated per-level allreduces — this is the bulk
    traffic the codec exists for."""
    local = _writable(build_local(bins, grad, hess, nbin, **kw))
    shape = local.shape
    out = rabit_tpu.allreduce(local.reshape(-1), SUM)
    return out.reshape(shape)


class HistogramHandle:
    """Waitable result of :func:`build_allreduce_async`; ``wait()``
    returns the reduced (f, nbin, 2) histogram."""

    def __init__(self, handle, shape):
        self._handle = handle
        self._shape = shape

    def wait(self) -> np.ndarray:
        return np.asarray(self._handle.wait()).reshape(self._shape)


def build_allreduce_async(bins, grad, hess, nbin: int, fuse: bool = False,
                          **kw) -> HistogramHandle:
    """Async :func:`build_allreduce`: the flat histogram rides an engine
    handle so the caller overlaps independent compute (the next node's
    local build, gain scans of already-reduced histograms) with the
    wire.  ``fuse`` defaults to False — the single-call pattern
    (issue, compute, wait) needs eager dispatch, since a bucketed op
    only reaches the wire when its bucket flushes; pass ``fuse=True``
    when issuing a back-to-back stream of per-node histograms so they
    coalesce under ``rabit_bucket_bytes`` (doc/performance.md).
    Host-path variant: the payload is pulled to numpy, so on the XLA
    engine it routes through the inner host transport rather than ICI —
    use :func:`build_level_allreduce` for the device-plane level
    batch."""
    local = _writable(build_local(bins, grad, hess, nbin, **kw))
    handle = rabit_tpu.allreduce_async(local.reshape(-1), SUM, fuse=fuse)
    return HistogramHandle(handle, local.shape)


def split_gain(hist: np.ndarray, reg_lambda: float = 1.0) -> np.ndarray:
    """Per (feature, cut) split gain from a (f, nbin, 2) histogram —
    the standard XGBoost structure score, vectorized over all cuts."""
    return split_candidates(hist, reg_lambda)[0]

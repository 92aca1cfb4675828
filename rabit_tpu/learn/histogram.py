"""XGBoost-style gradient-histogram building + allreduce.

The reference's historical raison d'être is the histogram allreduce
inside XGBoost: each worker bins its feature shard, accumulates per
(feature, bin) gradient/hessian sums for the tree node being split, and
Allreduce<Sum>'s the flat histogram so every worker sees the global
statistics (the pattern BASELINE.md lists under "configs to reproduce";
the reference itself only ships the collective, the histogram is the
app's job — same split here).

TPU-native design: binned features live on device as an (n, f) int32
array; the builder is a single jitted program that scans (row-block,
feature-block) tiles, expanding bins to a one-hot against a bin iota and
contracting with the (grad, hess) pair on the MXU — compiler-friendly
fixed shapes, no scatter (TPU scatters serialize; the one-hot contraction
keeps the FLOPs on the matrix unit).  The cross-worker step is one
framework allreduce of the flat (f * nbin * 2) histogram, exactly the
XGBoost wire pattern.
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


def split_gain_missing(hist: np.ndarray, reg_lambda: float = 1.0):
    """Sparsity-aware split gain: the LAST bin of ``hist`` (f, nbin, 2)
    holds the missing-value rows.  For every (feature, cut) the gain is
    evaluated with the missing mass sent left and sent right; returns
    ``(gain, default_left)`` where gain is the better of the two and
    default_left says which direction won (XGBoost's learned default
    direction, one bool per candidate split)."""
    g, h = hist[:, :-1, 0], hist[:, :-1, 1]
    gm = hist[:, -1:, 0]
    hm = hist[:, -1:, 1]
    gl = np.cumsum(g, axis=1)[:, :-1]
    hl = np.cumsum(h, axis=1)[:, :-1]
    gt = g.sum(axis=1, keepdims=True) + gm
    ht = h.sum(axis=1, keepdims=True) + hm
    parent = gt * gt / (ht + reg_lambda)

    def score(gl_, hl_):
        gr_, hr_ = gt - gl_, ht - hl_
        return (gl_ * gl_ / (hl_ + reg_lambda)
                + gr_ * gr_ / (hr_ + reg_lambda) - parent)

    gain_left = score(gl + gm, hl + hm)    # missing goes left
    gain_right = score(gl, hl)             # missing goes right
    return np.maximum(gain_left, gain_right), gain_left >= gain_right


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
    :func:`rabit_tpu.ops.histogram_kernel.hist_fused_multi` with a
    (2m, n) weight matrix — node masks folded into grad/hess channels,
    chunked when a level exceeds the kernel's channel budget.
    ``bins_t`` optionally supplies the resident transposed (f, n)
    device array so the transpose isn't redone per level.  Off-TPU,
    falls back to the XLA builder per node.
    """
    import jax.numpy as jnp

    if use_pallas is None:
        use_pallas = on_tpu()
    nid = jnp.asarray(np.asarray(node_ids, np.int32))
    nor = jnp.asarray(np.asarray(node_of_row, np.int32))
    g = jnp.asarray(grad)
    h = jnp.asarray(hess)
    m = len(node_ids)
    if use_pallas:
        from rabit_tpu.ops import histogram_kernel as hk
        if bins_t is None:
            bins_t = jnp.asarray(bins).T
        kw = {} if compute_dtype is None else {"compute_dtype": compute_dtype}
        # chunk derived from the kernel's VMEM accumulator budget (2
        # channels per node: grad + hess), not a fixed constant — wide
        # features shrink it so deep levels still compile
        chunk = max(1, hk.max_channels(nbin, bins.shape[1]) // 2)
        outs = []
        for lo_i in range(0, m, chunk):
            nids = nid[lo_i:lo_i + chunk]
            mc = len(nids)
            mask = (nor[None, :] == nids[:, None]).astype(g.dtype)
            w = jnp.concatenate([mask * g[None, :], mask * h[None, :]])
            out = hk.hist_fused_multi(bins_t, w, nbin, **kw)  # (2mc, f, nbin)
            outs.append(jnp.stack([out[:mc], out[mc:]], axis=-1))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)
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
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    gl = np.cumsum(g, axis=1)[:, :-1]
    hl = np.cumsum(h, axis=1)[:, :-1]
    gt = g.sum(axis=1, keepdims=True)
    ht = h.sum(axis=1, keepdims=True)
    gr, hr = gt - gl, ht - hl
    parent = gt * gt / (ht + reg_lambda)
    return (gl * gl / (hl + reg_lambda)
            + gr * gr / (hr + reg_lambda) - parent)

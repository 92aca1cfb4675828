"""Data utilities: LibSVM loader and TPU-friendly layouts.

Equivalent of the reference's CSR SparseMat + dense Matrix
(reference: rabit-learn/utils/data.h:23-136), re-designed for XLA:

* Host side the matrix is CSR (numpy ``indptr``/``findex``/``fvalue``).
* For device compute it converts to **padded ELL blocks** — every row
  padded to the same nnz with a sentinel column — so shapes are static
  and kernels jit once regardless of sparsity structure.  The sentinel
  column indexes a zero slot appended to weight/centroid buffers, which
  turns "skip padding" into plain gathers/scatter-adds XLA can fuse.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from rabit_tpu.obs import program
from rabit_tpu.utils.checks import check


@dataclass
class SparseMat:
    """CSR sparse matrix with labels (reference: rabit-learn/utils/data.h:24-100)."""

    indptr: np.ndarray = field(
        default_factory=lambda: np.zeros(1, np.int64))    # (nrow+1,)
    findex: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.int32))    # (nnz,)
    fvalue: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.float32))  # (nnz,)
    labels: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.float32))  # (nrow,)
    feat_dim: int = 0

    @property
    def num_row(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return len(self.findex)

    def row(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """(findex, fvalue) of row i."""
        lo, hi = self.indptr[i], self.indptr[i + 1]
        return self.findex[lo:hi], self.fvalue[lo:hi]

    # ---- device layouts --------------------------------------------------
    def to_ell(self, pad_index: int | None = None,
               row_block: int | None = None):
        """Padded ELL arrays ``(indices, values, labels)``.

        ``indices``/``values`` have shape (nrow_padded, max_nnz); padding
        entries carry ``pad_index`` (default: ``feat_dim``, i.e. one past
        the last real feature) and value 0.  When ``row_block`` is given,
        nrow is padded up to a multiple of it (padded rows get label 0 and
        all-padding features) so the data splits into equal static blocks.
        """
        with program.span("stage.to_ell"):
            return self._to_ell(pad_index, row_block)

    def _to_ell(self, pad_index, row_block):
        if pad_index is None:
            pad_index = self.feat_dim
        nrow = self.num_row
        counts = np.diff(self.indptr)
        max_nnz = max(1, int(counts.max()) if nrow else 1)
        nrow_pad = nrow
        if row_block:
            nrow_pad = -(-max(nrow, 1) // row_block) * row_block
        uniform = bool(nrow) and self.nnz == nrow * max_nnz
        if uniform and nrow_pad == nrow:
            # Every row has max_nnz entries and no row padding is needed:
            # CSR *is* ELL — reshape, zero copies (matters at the
            # biggest-that-fits scale, where the scatter path below would
            # materialize three extra nnz-sized temporaries).
            idx = np.ascontiguousarray(
                self.findex.reshape(nrow, max_nnz), np.int32)
            val = np.ascontiguousarray(
                self.fvalue.reshape(nrow, max_nnz), np.float32)
        elif uniform:
            idx = np.full((nrow_pad, max_nnz), pad_index, np.int32)
            val = np.zeros((nrow_pad, max_nnz), np.float32)
            idx[:nrow] = self.findex.reshape(nrow, max_nnz)
            val[:nrow] = self.fvalue.reshape(nrow, max_nnz)
        else:
            idx = np.full((nrow_pad, max_nnz), pad_index, np.int32)
            val = np.zeros((nrow_pad, max_nnz), np.float32)
            # CSR→ELL without a Python row loop: flat positions per nnz.
            if self.nnz:
                rows = np.repeat(np.arange(nrow), counts)
                offs = (np.arange(self.nnz)
                        - np.repeat(self.indptr[:-1], counts))
                idx[rows, offs] = self.findex
                val[rows, offs] = self.fvalue
        labels = np.zeros(nrow_pad, np.float32)
        labels[:nrow] = self.labels
        valid = np.zeros(nrow_pad, np.float32)
        valid[:nrow] = 1.0
        return idx, val, labels, valid

    def to_dense(self) -> np.ndarray:
        """Densify (small data / tests only).  Duplicate indices within
        a row ADD — required for hashed features (hash_features), and a
        no-op for ordinary LibSVM rows."""
        out = np.zeros((self.num_row, self.feat_dim), np.float32)
        rows = np.repeat(np.arange(self.num_row), np.diff(self.indptr))
        np.add.at(out, (rows, self.findex), self.fvalue)
        return out


class EllRows(NamedTuple):
    """Sparse rows in ELL form, as a learner that keeps entries takes
    them (``boosting.train``): row ``i`` holds the ``counts[i]`` entries
    ``(indices[i, j], values[i, j])``, ``j < counts[i]``, of a matrix of
    ``feat_dim`` columns.  Without ``counts`` a slot is an entry where
    its index is a column's (``0 <= index < feat_dim``:
    :meth:`SparseMat.to_ell` pads with ``feat_dim``).  An entry whose
    value is NaN is no entry; one whose value is 0 is (libsvm's
    ``3:0``)."""

    indices: np.ndarray             # (n, width) int32
    values: np.ndarray              # (n, width) float32
    counts: np.ndarray | None       # (n,) or None
    feat_dim: int

    @property
    def shape(self) -> tuple[int, int]:
        """``(rows, columns)`` of the matrix the entries are of."""
        return self.indices.shape[0], self.feat_dim

    def rows(self, at) -> "EllRows":
        """The rows a slice names."""
        return EllRows(self.indices[at], self.values[at],
                       None if self.counts is None else self.counts[at],
                       self.feat_dim)

    def present(self) -> np.ndarray:
        """``(n, width)`` bool: the slots that hold an entry."""
        idx = self.indices
        held = (idx >= 0) & (idx < self.feat_dim) & ~np.isnan(self.values)
        if self.counts is not None:
            held &= np.arange(idx.shape[1]) < np.asarray(self.counts)[:, None]
        return held

    def to_dense(self) -> np.ndarray:
        """``(n, feat_dim)`` float32 with NaN for an absent entry (small
        data and tests only)."""
        out = np.full(self.shape, np.nan, np.float32)
        held = self.present()
        rows = np.broadcast_to(np.arange(self.shape[0])[:, None], held.shape)
        out[rows[held], self.indices[held]] = self.values[held]
        return out


def ell_rows(rows) -> EllRows:
    """``rows`` as :class:`EllRows`: one, or a :class:`SparseMat`."""
    if isinstance(rows, EllRows):
        return rows
    idx, val, _labels, _valid = rows.to_ell()
    return EllRows(idx[:rows.num_row], val[:rows.num_row], None,
                   rows.feat_dim)


def fetch(result, convert=None):
    """A device result on the host, in two spans inside the caller's
    own fetch span: ``learn.fetch.wait`` until the device has written it
    (the kernel's time as the host sees it), then ``learn.fetch.copy``
    around the copy back and ``convert`` of the numpy arrays (the
    host's time, with nothing in flight unless the loop runs ahead).
    ``result`` is an array or a tuple of them.

    The host sleeps twice here where ``np.asarray`` of an unfinished
    array sleeps once, which costs a fetch some 40 microseconds on a
    shared host (PERF.md section 6, PR 35): the price of knowing, in
    every run and on every rank, which of the two a loop waits for."""
    arrays = result if isinstance(result, tuple) else (result,)
    # the transfer is asked for before the wait and follows the kernel
    # by itself, as `np.asarray` of an unfinished array has it do: asked
    # for after the wait it costs one more round trip to the runtime
    for a in arrays:
        a.copy_to_host_async()
    with program.span("learn.fetch.wait"):
        for a in arrays:
            a.block_until_ready()
        program.waited()
    with program.span("learn.fetch.copy"):
        host = (tuple(np.asarray(a) for a in arrays) if arrays is result
                else np.asarray(result))
        return host if convert is None else convert(host)


def hash_features(findex: np.ndarray, fvalue: np.ndarray, d_out: int,
                  seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Signed feature hashing: map feature ids into ``[0, d_out)`` with a
    pseudo-random sign on the value (Weinberger et al., "Feature Hashing
    for Large Scale Multitask Learning" — the standard hashing trick;
    the sign makes collision cross-terms zero-mean).

    ``d_out`` must be a power of two (the hash mixes then masks).  Works
    on any integer index array (CSR ``findex`` or padded-ELL blocks —
    pad slots hash somewhere harmless because their value is 0).
    Returns ``(hashed_index, signed_value)``; collisions within a row
    are additive, which every consumer here (dense staging, ELL stats,
    linear models) already handles.

    Why it exists: the sparse k-means kernel's VPU floor is
    ``nnz x 128`` lane-ops/row (doc/benchmarks.md, "ELL kernel plan
    sweep"), while DENSE rows at a hashed width ride the HBM-roofline
    stats kernel — hashing to d_out <= 256 converts the bandwidth-rich
    dense path into an approximate sparse recipe.  Measured tradeoff:
    ``tools/hash_experiments.py``.
    """
    check(d_out > 0 and (d_out & (d_out - 1)) == 0,
          "hash_features: d_out must be a power of two, got %d", d_out)
    h = findex.astype(np.uint32)
    # xorshift-multiply mix (Murmur3 finalizer constants), seed-salted
    h ^= np.uint32((seed * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    idx = (h & np.uint32(d_out - 1)).astype(np.int32)
    sign = np.where((h >> np.uint32(31)) & np.uint32(1),
                    np.float32(-1.0), np.float32(1.0))
    return idx, (fvalue.astype(np.float32) * sign)


def load_libsvm(fname: str, rank: int | None = None) -> SparseMat:
    """Load LibSVM-format data (reference: rabit-learn/utils/data.h:47-91).

    Mirrors the reference conventions: ``fname == "stdin"`` reads standard
    input, and a ``%d`` (or any printf int field) in the name is substituted
    with the caller's rank for per-rank shards.  ``feat_dim`` is the max
    feature index + 1 **of this shard** — callers allreduce(MAX) it, same as
    the reference apps do.
    """
    if fname == "stdin":
        text = sys.stdin.read()
    else:
        if "%" in fname:
            if rank is None:
                import rabit_tpu

                rank = rabit_tpu.get_rank()
            fname = fname % rank
        with open(fname) as f:
            text = f.read()

    indptr = [0]
    findex: list[int] = []
    fvalue: list[float] = []
    labels: list[float] = []
    feat_dim = 0
    for tok in text.split():
        if ":" in tok:
            fi, fv = tok.split(":", 1)
            fi = int(fi)
            findex.append(fi)
            fvalue.append(float(fv))
            feat_dim = max(feat_dim, fi)
        else:
            if labels:
                indptr.append(len(findex))
            labels.append(float(tok))
    check(bool(labels), "load_libsvm: no rows in %s", fname)
    indptr.append(len(findex))
    return SparseMat(
        indptr=np.asarray(indptr, np.int64),
        findex=np.asarray(findex, np.int32),
        fvalue=np.asarray(fvalue, np.float32),
        labels=np.asarray(labels, np.float32),
        feat_dim=feat_dim + 1,
    )


def save_matrix_txt(mat: np.ndarray, fname: str,
                    header: str | None = None) -> None:
    """Write a dense matrix as whitespace text, ``stdout`` supported
    (reference: Matrix::Print, rabit-learn/utils/data.h:115-132).
    ``header`` prepends one ``#``-comment line (skipped by
    ``np.loadtxt``) — used for model metadata like the k-means hash
    width."""
    out = sys.stdout if fname == "stdout" else open(fname, "w")
    try:
        if header is not None:
            out.write(f"# {header}\n")
        for row in np.atleast_2d(mat):
            out.write(" ".join(f"{v:g}" for v in row) + "\n")
    finally:
        if out is not sys.stdout:
            out.close()

"""Plain cosine k-means: the benchmark's yardstick for ``correct``.

Float32 ``jax.numpy`` at the highest matmul precision, no kernel, no
cache, nothing imported from the program under test and nothing the
program made.  What it shares with ``rabit_tpu.learn.kmeans`` is the
algorithm's definition only (reference: rabit-learn/kmeans/kmeans.cc):

* init: centroid ``i`` is row ``picks[i]`` of rank ``roots[i]``'s shard,
  where ``picks`` are the first k draws of ``default_rng(seed).integers(n)``
  and ``roots`` the next k draws of ``integers(world)``; L2-normalised;
* an iteration assigns every row to the centroid of largest cosine
  similarity, sums rows and counts per cluster over ALL ranks, divides,
  and L2-normalises (a cluster that got no row keeps its centroid).

The rows stay sparse on the device ((n/4, 4*nnz) so the minor dimension
fills the 128 lanes) and are densified block by block: the dense float32
matrix would not fit beside anything at the sizes the cells run.
"""
from __future__ import annotations

import numpy as np


def init_draws(seed: int, n: int, k: int, world: int):
    """(picks, roots): the seeded draws that define the initial
    centroids (see the module docstring)."""
    rng = np.random.default_rng(seed)
    picks = [int(rng.integers(n)) for _ in range(k)]
    roots = [int(rng.integers(world)) for _ in range(k)]
    return picks, roots


def init_centroids(pick_rows, dim: int) -> np.ndarray:
    """``pick_rows[i]`` is the (indices, values) of the row centroid i
    starts from; repeated indices add up, as the loaders define."""
    cent = np.zeros((len(pick_rows), dim), np.float32)
    for i, (idx, val) in enumerate(pick_rows):
        np.add.at(cent[i], idx, val)
    return normalize(cent)


def normalize(cent: np.ndarray) -> np.ndarray:
    norm = np.linalg.norm(cent, axis=1, keepdims=True)
    scale = np.where(norm < 1e-6, 1.0, 1.0 / np.maximum(norm, 1e-30))
    return (cent * scale).astype(np.float32)


class ShardStats:
    """One rank's rows on the device and the jitted pass over them that
    returns that shard's per-cluster sums and counts."""

    def __init__(self, idx: np.ndarray, val: np.ndarray, dim: int, k: int,
                 block: int = 1 << 14):
        import jax
        import jax.numpy as jnp

        n, nnz = idx.shape
        block = min(block, n)
        if n % block or block % 4:
            raise ValueError(f"reference: {n} rows do not split into "
                             f"blocks of {block} (a multiple of 4)")
        grouped = (n // block, block // 4, 4 * nnz)
        self._idx = jax.device_put(idx.reshape(grouped))
        self._val = jax.device_put(val.reshape(grouped))

        @jax.jit
        def stats(cent, idx_d, val_d):
            cn = cent / (jnp.linalg.norm(cent, axis=1, keepdims=True) + 1e-12)
            features = jnp.arange(dim, dtype=jnp.int32)

            def body(acc, blk):
                bi = blk[0].reshape(block, nnz)
                bv = blk[1].reshape(block, nnz)
                dense = jnp.einsum(
                    "rj,rjd->rd", bv,
                    (bi[:, :, None] == features).astype(jnp.float32))
                member = jax.nn.one_hot(jnp.argmax(dense @ cn.T, axis=1), k,
                                        dtype=jnp.float32)
                return (acc[0] + member.T @ dense,
                        acc[1] + member.sum(axis=0)), None

            out, _ = jax.lax.scan(
                body, (jnp.zeros((k, dim), jnp.float32),
                       jnp.zeros((k,), jnp.float32)), (idx_d, val_d))
            return out

        self._stats = stats

    def __call__(self, cent: np.ndarray):
        import jax

        with jax.default_matmul_precision("highest"):
            sums, counts = self._stats(cent, self._idx, self._val)
        return np.asarray(sums, np.float64), np.asarray(counts, np.float64)

    def free(self) -> None:
        self._idx.delete()
        self._val.delete()


def update(cent: np.ndarray, sums: np.ndarray, counts: np.ndarray):
    """New centroids from the job-wide sums and counts."""
    new = np.where(counts[:, None] > 0,
                   sums / np.maximum(counts[:, None], 1.0), cent)
    return normalize(new.astype(np.float32))


def run(shard: ShardStats, cent0: np.ndarray, iters: int, combine):
    """``iters`` iterations from ``cent0``.  ``combine(it, sums, counts)``
    returns the sums and counts of the whole job (the identity where
    there is one rank).  Returns the centroids after each iteration."""
    out = []
    cent = cent0
    for it in range(iters):
        sums, counts = combine(it, *shard(cent))
        cent = update(cent, sums, counts)
        out.append(cent)
    return out


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Relative Frobenius error; not finite where ``got`` is not."""
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got.astype(np.float64) - want)
                 / np.linalg.norm(want))

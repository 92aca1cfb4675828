"""The plain cosine k-means of ``reference/kmeans.py`` for a shard that
the device does not hold: the reference of the streamed configuration.

One thing differs, and nothing of the arithmetic: ``ShardStats`` there
puts the whole shard on the device before its first pass; here the rows
stay on the host and a pass takes them piece by piece through the very
program that class builds (same blocks, same float32 sums inside a
piece, ``highest`` matmul precision), the pieces' sums and counts added
up in float64 on the host.  Nothing is imported from the program under
test.
"""
from __future__ import annotations

import numpy as np

from perfbench.reference.kmeans import ShardStats

PIECE_ROWS = 1 << 20        # 134 MB of indices a piece at 32 slots a row


class PiecewiseStats(ShardStats):
    """``ShardStats`` over rows that stay on the host.  The device holds
    the piece being summed and the one on its way."""

    def __init__(self, idx: np.ndarray, val: np.ndarray, dim: int, k: int,
                 piece: int = PIECE_ROWS, block: int = 1 << 14):
        n = idx.shape[0]
        self._piece = min(piece, n)
        if n % self._piece:
            raise ValueError(f"reference: {n} rows do not split into "
                             f"pieces of {self._piece}")
        # the parent's program, built on the first piece's shape
        super().__init__(idx[:self._piece], val[:self._piece], dim, k, block)
        self._grouped = self._idx.shape
        super().free()                  # a pass puts its own pieces
        self._host = idx, val

    def _put(self, lo: int):
        import jax

        return tuple(jax.device_put(
            a[lo:lo + self._piece].reshape(self._grouped))
            for a in self._host)

    def __call__(self, cent: np.ndarray):
        import jax

        k, dim = cent.shape
        sums, counts = np.zeros((k, dim)), np.zeros(k)
        owed = None
        with jax.default_matmul_precision("highest"):
            for lo in range(0, self._host[0].shape[0], self._piece):
                ahead = self._stats(cent, *self._put(lo))
                if owed is not None:       # the piece before: two at most
                    sums += np.asarray(owed[0], np.float64)
                    counts += np.asarray(owed[1], np.float64)
                owed = ahead
        return (sums + np.asarray(owed[0], np.float64),
                counts + np.asarray(owed[1], np.float64))

    def free(self) -> None:
        """Nothing of the shard stays on the device between passes."""

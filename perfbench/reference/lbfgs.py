"""The plain reference of the L-BFGS logistic-regression cell.

Written from the published algorithms, not from the program, and
importing nothing of it: the summed logistic loss and its gradient by
the textbook formulas (``jnp.take`` for ``X w``, ``jax.ops.segment_sum``
for ``X^T g``) in float32 at matmul precision ``highest``, a block of
rows at a time so that it fits beside nothing else, the blocks' partial
sums added up in float64 on the host; and one step of OWL-QN (Andrew &
Gao 2007: the pseudo-gradient, the two-loop L-BFGS recursion of Nocedal
1980 on full vectors, the orthant projection of the direction, the sign
clamp of the trial point, Armijo backtracking) in float64 numpy.
"""
from __future__ import annotations

import numpy as np

BLOCK_ROWS = 1 << 20


def loss_and_gradient(indices, values, labels, weight, offset: float,
                      want_gradient: bool = True):
    """``(f, gradient)`` of ``sum_i loss(offset + x_i . w[:-1], y_i)``
    over padded-ELL rows ``(n, k)``; ``weight`` has the bias last, and
    the gradient (float64, same length) its derivative there.  Weights
    and margins are float32, as the configuration states."""
    import jax
    import jax.numpy as jnp

    nf = len(weight) - 1
    w = jnp.asarray(np.asarray(weight[:nf], np.float32))
    base = jnp.float32(offset)

    @jax.jit
    def block(idx, val, y):
        m = base + jnp.sum(jnp.take(w, idx, axis=0) * val, axis=1)
        nlogprob = jnp.where(m > 0.0, jnp.log1p(jnp.exp(-m)),
                             -m + jnp.log1p(jnp.exp(m)))
        loss = y * nlogprob + (1.0 - y) * (m + nlogprob)
        g = jax.nn.sigmoid(m) - y
        if not want_gradient:
            return jnp.sum(loss), jnp.zeros((0,), jnp.float32), jnp.sum(g)
        grad = jax.ops.segment_sum((val * g[:, None]).reshape(-1),
                                   idx.reshape(-1), num_segments=nf)
        return jnp.sum(loss), grad, jnp.sum(g)

    f, grad = 0.0, np.zeros(nf + 1, np.float64)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(labels), BLOCK_ROWS):
            hi = min(len(labels), lo + BLOCK_ROWS)
            loss, part, gsum = block(indices[lo:hi], values[lo:hi],
                                     labels[lo:hi])
            f += float(loss)
            if want_gradient:
                grad[:nf] += np.asarray(part, np.float64)
                grad[nf] += float(gsum)
    return f, (grad if want_gradient else None)


def pseudo_gradient(grad: np.ndarray, weight: np.ndarray, c: float):
    """OWL-QN's pseudo-gradient of ``f + c |w|_1``."""
    if c == 0.0:
        return grad.copy()
    at_zero = np.where(grad + c < 0.0, grad + c,
                       np.where(grad - c > 0.0, grad - c, 0.0))
    return np.where(weight > 0.0, grad + c,
                    np.where(weight < 0.0, grad - c, at_zero))


def direction(grad: np.ndarray, weight: np.ndarray, pairs, c: float):
    """The quasi-Newton direction from the ``(s, y)`` pairs, oldest
    first, and the pseudo-gradient it was built from: the two-loop
    recursion on ``-pseudo_gradient``, the initial Hessian scaled by the
    newest pair, then (``c`` > 0) every component that does not descend
    along the pseudo-gradient set to zero."""
    pg = pseudo_gradient(grad, weight, c)
    q = -pg
    alphas = []
    for s, y in reversed(pairs):
        a = (s @ q) / (s @ y)
        q = q - a * y
        alphas.append(a)
    if pairs:
        s, y = pairs[-1]
        q = q * ((s @ y) / (y @ y))
    for (s, y), a in zip(pairs, reversed(alphas)):
        q = q + (a - (y @ q) / (s @ y)) * s
    if c != 0.0:
        q = np.where(q * pg >= 0.0, 0.0, q)
    return q, pg


def trial_point(weight: np.ndarray, step: np.ndarray, c: float):
    """``weight + step``, a component that changed sign set to zero."""
    new = weight + step
    if c != 0.0:
        new = np.where(new * weight < 0.0, 0.0, new)
    return new


def armijo_holds(f_new: float, f_old: float, alpha: float, slope: float,
                 c1: float, slack: float = 0.0) -> bool:
    """The sufficient-decrease test of the backtracking line search,
    ``slope`` the direction's product with the pseudo-gradient."""
    return f_new - f_old <= c1 * alpha * slope + slack

"""The plain reference of the L-BFGS cell (``perfbench/reference/
lbfgs.py``) against a dense float64 logistic regression written out by
hand: the objective, the gradient, and one OWL-QN step (the
pseudo-gradient a coordinate at a time, the quasi-Newton direction
against the BFGS matrix the two-loop recursion stands for, the
projection, the sign clamp, the sufficient-decrease test)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.reference import lbfgs as ref  # noqa: E402


def problem(seed=0, n=300, k=5, nf=40):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, nf, (n, k)).astype(np.int32)
    val = rng.standard_normal((n, k)).astype(np.float32)
    y = (rng.random(n) < 0.3).astype(np.float32)
    w = rng.standard_normal(nf + 1) * 0.3
    dense = np.zeros((n, nf))
    np.add.at(dense, (np.repeat(np.arange(n), k), idx.reshape(-1)),
              val.reshape(-1).astype(np.float64))
    return idx, val, y, w, dense


def dense_loss_and_gradient(dense, y, w, offset):
    m = offset + dense @ w[:-1].astype(np.float32).astype(np.float64)
    loss = np.where(y > 0, np.logaddexp(0.0, -m), np.logaddexp(0.0, m))
    g = 1.0 / (1.0 + np.exp(-m)) - y
    return loss.sum(), np.concatenate([dense.T @ g, [g.sum()]])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_objective_and_gradient_are_the_dense_float64_ones(seed):
    idx, val, y, w, dense = problem(seed)
    f, grad = ref.loss_and_gradient(idx, val, y, w, 0.25 + w[-1])
    want_f, want_g = dense_loss_and_gradient(dense, y, w, 0.25 + w[-1])
    assert f == pytest.approx(want_f, rel=2e-6)
    np.testing.assert_allclose(grad, want_g,
                               atol=3e-6 * np.abs(want_g).max())
    only_f, none = ref.loss_and_gradient(idx, val, y, w, 0.25 + w[-1],
                                         want_gradient=False)
    assert none is None and only_f == pytest.approx(f, rel=1e-7)


def test_rows_are_summed_in_blocks(monkeypatch):
    idx, val, y, w, dense = problem(3, n=1000)
    whole = ref.loss_and_gradient(idx, val, y, w, 0.0)
    monkeypatch.setattr(ref, "BLOCK_ROWS", 128)
    blocks = ref.loss_and_gradient(idx, val, y, w, 0.0)
    assert blocks[0] == pytest.approx(whole[0], rel=1e-6)
    np.testing.assert_allclose(blocks[1], whole[1], atol=1e-4)


def test_pseudo_gradient_a_coordinate_at_a_time():
    g = np.array([-3.0, -0.5, 0.5, 3.0, -3.0, 3.0, 0.2, -0.2])
    w = np.array([0.0, 0.0, 0.0, 0.0, 1.0, 1.0, -1.0, -1.0])
    want = [-2.0, 0.0, 0.0, 2.0, -2.0, 4.0, -0.8, -1.2]
    np.testing.assert_allclose(ref.pseudo_gradient(g, w, 1.0), want)
    np.testing.assert_array_equal(ref.pseudo_gradient(g, w, 0.0), g)


def bfgs_matrix(pairs, dim):
    """The inverse-Hessian approximation the two-loop recursion applies:
    BFGS updates of gamma * I, oldest pair first."""
    s, y = pairs[-1]
    h = np.eye(dim) * (s @ y) / (y @ y)
    for s, y in pairs:
        rho = 1.0 / (s @ y)
        left = np.eye(dim) - rho * np.outer(s, y)
        h = left @ h @ left.T + rho * np.outer(s, s)
    return h


@pytest.mark.parametrize("c", [0.0, 0.7])
@pytest.mark.parametrize("npairs", [0, 1, 4])
def test_direction_is_the_bfgs_matrix_times_the_steepest_descent(npairs, c):
    rng = np.random.default_rng(npairs)
    dim = 12
    a = rng.standard_normal((dim, dim))
    hess = a @ a.T + np.eye(dim)          # y = H s keeps s.y positive
    pairs = []
    for _ in range(npairs):
        s = rng.standard_normal(dim)
        pairs.append((s, hess @ s))
    grad = rng.standard_normal(dim) * 2
    w = rng.standard_normal(dim) * (rng.random(dim) < 0.6)
    d, pg = ref.direction(grad, w, pairs, c)
    np.testing.assert_allclose(pg, ref.pseudo_gradient(grad, w, c))
    want = -(bfgs_matrix(pairs, dim) @ pg) if pairs else -pg
    if c:
        want = np.where(want * pg >= 0.0, 0.0, want)
    np.testing.assert_allclose(d, want, rtol=1e-9, atol=1e-12)
    assert d @ pg <= 0.0                  # a descent direction


def test_trial_point_clamps_a_sign_change_and_armijo_is_the_textbook_test():
    w = np.array([1.0, -1.0, 0.0, 2.0])
    step = np.array([-3.0, 0.5, -4.0, 1.0])
    np.testing.assert_array_equal(ref.trial_point(w, step, 1.0),
                                  [0.0, -0.5, -4.0, 3.0])
    np.testing.assert_array_equal(ref.trial_point(w, step, 0.0), w + step)
    assert ref.armijo_holds(9.0, 10.0, 0.5, -4.0, 0.25)       # -1 <= -0.5
    assert not ref.armijo_holds(9.8, 10.0, 0.5, -4.0, 0.25)
    assert not ref.armijo_holds(9.0, 10.0, 0.5, -4.0, 0.25, slack=-0.6)


def test_one_owlqn_step_descends_the_dense_objective():
    idx, val, y, w, dense = problem(5)
    c = 0.5
    w[np.arange(len(w)) % 3 == 0] = 0.0

    def total(v):
        return dense_loss_and_gradient(dense, y, v, v[-1])[0] + c * np.abs(
            v).sum()

    f0, g0 = ref.loss_and_gradient(idx, val, y, w, w[-1])
    d, pg = ref.direction(g0, w, [], c)
    alpha = 1.0 / np.sqrt(-(d @ pg))
    while not ref.armijo_holds(total(ref.trial_point(w, alpha * d, c)),
                               f0 + c * np.abs(w).sum(), alpha, d @ pg, 1e-4):
        alpha *= 0.5
    new = ref.trial_point(w, alpha * d, c)
    assert total(new) < total(w)
    assert not np.any(new * w < 0.0)

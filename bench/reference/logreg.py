"""Plain reference of the paper's logistic regression and of Fed-PLT on
it, in float64 NumPy (``dtype="f64"``), or rounded to bfloat16 after
every operation (``dtype="bf16"``, the control one precision step below
the configuration's float32).

    f_i(x) = mean_h log(1 + exp(-b_ih <a_ih, x>)) + eps ||x||^2 / 2
    criterion(x_1..x_N) = || sum_i grad f_i(mean_i x_i) ||^2
"""

from __future__ import annotations

import numpy as np


def _rounder(dtype: str):
    if dtype == "f64":
        return lambda a: a
    if dtype == "bf16":
        import ml_dtypes

        return lambda a: np.asarray(a).astype(ml_dtypes.bfloat16)\
            .astype(np.float64)
    raise ValueError(f"unknown reference dtype {dtype!r}")


def grads(A, b, X, eps, rnd=lambda a: a):
    """Per-agent gradients at per-agent points ``X (N, n)``."""
    q = A.shape[1]
    m = rnd(np.einsum("nqd,nd->nq", A, X) * b)
    s = rnd(-b / (1.0 + np.exp(m)))                  # d/dm log(1+e^-m) * b
    return rnd(rnd(np.einsum("nq,nqd->nd", s, A) / q) + rnd(eps * X))


def criterion(A, b, X, eps) -> float:
    xbar = X.mean(axis=0)
    g = grads(A, b, np.broadcast_to(xbar, X.shape), eps)
    return float(np.sum(np.sum(g, axis=0) ** 2))


def moduli(A, eps):
    """(mu, L): eps, and max_i ||A_i||_2^2 / (4 q) + eps."""
    q = A.shape[1]
    lam = max(np.linalg.norm(Ai, ord=2) ** 2 for Ai in A) / (4.0 * q)
    return eps, lam + eps


def fed_plt(A, b, eps, rho, n_epochs, n_rounds, damping=1.0, dtype="f64"):
    """Fed-PLT from x = z = 0 with full participation and local GD at the
    step 2 / (L_d + mu_d); returns ``(X, criterion per round)``."""
    rnd = _rounder(dtype)
    A = rnd(np.asarray(A, np.float64))
    b = np.asarray(b, np.float64)
    mu, L = moduli(np.asarray(A), eps)
    gamma = 2.0 / (L + 1.0 / rho + mu + 1.0 / rho)
    N, _, n = A.shape
    X = np.zeros((N, n))
    Z = np.zeros((N, n))
    crit = []
    for _ in range(n_rounds):
        y = rnd(Z.mean(axis=0))
        V = rnd(2.0 * y - Z)
        W = X
        for _ in range(n_epochs):
            g = grads(A, b, W, eps, rnd)
            W = rnd(W - gamma * rnd(g + (W - V) / rho))
        Z = rnd(Z + 2.0 * damping * (W - y))
        X = W
        crit.append(criterion(A, b, X, eps))
    return X, np.asarray(crit)


def solution(A, b, eps, iters: int = 50) -> np.ndarray:
    """argmin_x sum_i f_i(x) by Newton's method in float64."""
    A = np.asarray(A, np.float64)
    b = np.asarray(b, np.float64)
    N, q, n = A.shape
    x = np.zeros(n)
    for _ in range(iters):
        m = np.einsum("nqd,d->nq", A, x) * b
        sig = 1.0 / (1.0 + np.exp(m))
        g = np.einsum("nq,nqd->d", -b * sig, A) / q + N * eps * x
        w = sig * (1.0 - sig) / q
        H = np.einsum("nq,nqd,nqe->de", w, A, A) + N * eps * np.eye(n)
        step = np.linalg.solve(H, g)
        x = x - step
        if np.max(np.abs(step)) < 1e-15:
            break
    return x

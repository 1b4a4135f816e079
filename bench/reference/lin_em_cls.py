"""Reference LIN-EM-CLS fit (arXiv:1512.07716, Sec 2 and Eq. 9).

From w = 0, each iteration sets gamma_d = max(|y_d - w . x_d|, eps) for
every row, Sigma = sum_d x_d x_d^T / gamma_d, b = sum_d y_d (1 + 1/gamma_d)
x_d, and w = (lam I + Sigma)^-1 b with the configuration's relative ridge.
Rows carry the bias feature as their last column.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.reference import common

FAULTS = ("unchanged", "half_batch", "no_exchange", "altered_answer")


def _aug(m, rho, beta, rows, aux):
    gamma = jnp.maximum(jnp.abs(rho - m), aux["eps"])
    return common.hinge_coef(gamma, rho, beta)


def _loss(F, y):
    return 2.0 * jnp.maximum(0.0, 1.0 - y * F[..., 0])


def fit(X3, y3, cfg: dict, iters: int, fit_seed: int,
        prec=common.REFERENCE, fault: str | None = None):
    """(weights (K,), objective per iteration) of ``iters`` EM
    iterations. Iteration t's objective, as the fit reports it, is
    0.5 lam |w_t|^2 plus the hinge loss at the margins w_{t-1} . x that
    iteration t's statistic used. ``fault`` plants one of FAULTS: w left
    at 0, the statistic of half the rows doubled, the statistic of the
    first shard alone, or each M-step's largest weight negated."""
    del fit_seed
    S, n, K = X3.shape
    aux = {"eps": jnp.float32(cfg["eps"])}
    lam = jnp.float32(cfg["lam"])
    jitter = jnp.float32(cfg["jitter"])
    Xs, ys = (X3[:1], y3[:1]) if fault == "no_exchange" else (X3, y3)
    w = jnp.zeros((K,), common.F32)
    trace = []
    for _ in range(iters):
        loss = common.objective(X3, y3, w, 0.0, _loss)
        kw = {"n_rows": n // 2} if fault == "half_batch" else {}
        Sg, b = common.statistic(Xs, ys, ys, w, aux, aug=_aug, prec=prec,
                                   **kw)
        if fault == "half_batch":
            Sg, b = 2.0 * Sg, 2.0 * b
        if fault != "unchanged":
            w = common.posterior(Sg, b, lam, jitter)[1]
        if fault == "altered_answer":
            w = common.negate_largest(w)
        w64 = np.asarray(w, np.float64)
        trace.append(0.5 * cfg["lam"] * float(w64 @ w64) + loss)
    return np.asarray(w, np.float32), trace


def objective(X3, y3, w, cfg: dict) -> float:
    return common.objective(X3, y3, w, cfg["lam"], _loss)

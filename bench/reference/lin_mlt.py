"""Reference LIN-EM-MLT fit: the Crammer-Singer block updates of
arXiv:1512.07716, Sec 3.3 (Eq. 34-39), with the EM augmentation.

One sweep visits the classes y = 0..M-1 in order. With scores
F = X W^T (column y refreshed after class y's update) and the 0/1 cost
Delta, class y's conditional is a binary hinge problem with
rho_d = max_{y' != y}(F_dy' + Delta_d(y')) - Delta_d(y) and
beta_d = +1 if y_d = y else -1. EM sets gamma_d = max(|rho_d - w_y . x_d|,
eps) and w_y = P^-1 b with P = lam I + Sigma (plus the relative ridge).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common

FAULTS = ("unchanged", "half_batch", "altered_answer")
NEG = -1e30


def _aug(m, rho, beta, rows, aux):
    gamma = jnp.maximum(jnp.abs(rho - m), aux["eps"])
    return common.hinge_coef(gamma, rho, beta)


def _rho_beta(F, lab, y, M):
    ids = jnp.arange(M)
    onehot = (lab[..., None] == ids).astype(jnp.float32)
    A = jnp.where(ids == y, NEG, F + 1.0 - onehot)
    rho = jnp.max(A, axis=-1) - (lab != y).astype(jnp.float32)
    beta = jnp.where(lab == y, 1.0, -1.0)
    return rho, beta


@functools.partial(jax.jit, static_argnames=("prec", "n_rows", "fault"))
def _class_pass(X3, lab3, W, F, y, lam, jitter, eps, *, prec, n_rows=None,
                fault=None):
    rho, beta = _rho_beta(F, lab3, y, W.shape[0])
    Sg, b = common.statistic(X3, rho, beta, W[y], {"eps": eps}, aug=_aug,
                             prec=prec, n_rows=n_rows)
    if n_rows is not None:
        Sg, b = Sg * (X3.shape[1] / n_rows), b * (X3.shape[1] / n_rows)
    w = common.posterior(Sg, b, lam, jitter)[1]
    if fault == "altered_answer":
        w = common.negate_largest(w)
    if fault == "unchanged":
        w = W[y]
    W = W.at[y].set(w)
    F = F.at[..., y].set(common.dot("snk,k->sn", X3, w, prec).astype(
        jnp.float32))
    return W, F


@functools.partial(jax.jit, static_argnames=("prec",))
def _scores(X3, W, *, prec):
    return common.dot("snk,mk->snm", X3, W, prec).astype(jnp.float32)


@jax.jit
def _loss_sum(F, lab3):
    return jnp.sum(_loss(F, lab3))


def fit(X3, lab3, cfg: dict, iters: int, fit_seed: int,
        prec=common.REFERENCE, fault: str | None = None):
    """(weights (M, K), objective per sweep) of ``iters`` sweeps; sweep
    t's objective is 0.5 lam |W_t|^2 plus the Crammer-Singer loss of
    W_t. ``fault`` plants one of FAULTS: W left at 0, each class
    statistic from half the rows, doubled, or each update's largest
    weight negated."""
    del fit_seed
    S, n, K = X3.shape
    M = int(cfg["num_classes"])
    lam, jitter, eps = (jnp.float32(cfg[k]) for k in ("lam", "jitter",
                                                      "eps"))
    n_rows = n // 2 if fault == "half_batch" else None
    W = jnp.zeros((M, K), jnp.float32)
    trace = []
    for _ in range(iters):
        F = _scores(X3, W, prec=prec)
        for y in range(M):
            W, F = _class_pass(X3, lab3, W, F, jnp.int32(y), lam, jitter,
                               eps, prec=prec, n_rows=n_rows, fault=fault)
        W64 = np.asarray(W, np.float64)
        trace.append(0.5 * cfg["lam"] * float(np.sum(W64 * W64))
                     + float(_loss_sum(F, lab3)))
    return np.asarray(W), trace


def _loss(F, lab):
    M = F.shape[-1]
    onehot = (lab[..., None] == jnp.arange(M)).astype(jnp.float32)
    true = jnp.sum(F * onehot, axis=-1)
    worst = jnp.max(F + 1.0 - onehot, axis=-1)
    return 2.0 * jnp.maximum(0.0, worst - true)


def objective(X3, lab3, W, cfg: dict) -> float:
    return common.objective(X3, lab3, W, cfg["lam"], _loss)

"""KRN-{EM,MC}-CLS: kernelized SVM via data augmentation (paper Sec 3.1).

The dual weight omega (N,) replaces w; the Gram matrix K replaces X, and
the prior precision becomes lam*K (pseudo-prior N(0, (lam K)^{-1})):

  gamma_d  <- |1 - y_d K_d omega|                       (Eq. 19)
  Sigma^p  =  sum_d (1/gamma_d) K_d^T K_d               (N x N)
  mu^p     =  sum_d y_d (1 + 1/gamma_d) K_d^T
  P        =  lam*K + sum_p Sigma^p,  mu = P^{-1} mu^p  (Eq. 18)

Distribution shards *rows* of K (each row d belongs to datum d, exactly the
paper's data partitioning); omega is replicated. Iteration time is the
paper's O(N^2[N/P + log P + log N]) — KRN is for modest N (Sec 4.3).

Padding: the Gram matrix is padded as blockdiag(K, I) with masked rows.
Padded components see prior precision lam*I and zero statistics, so their
posterior is centered at 0 and they never touch real components.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops
from . import augment, objective, stats
from .linear import SVMData


def gram_matrix(X1: jnp.ndarray, X2: jnp.ndarray, *, kind: str = "rbf",
                sigma: float = 1.0, backend: str | None = None) -> jnp.ndarray:
    """Gram block between two sets of rows."""
    if kind == "rbf":
        return ops.rbf_gram(X1, X2, sigma=sigma, backend=backend)
    if kind == "linear":
        return X1.astype(jnp.float32) @ X2.astype(jnp.float32).T
    raise ValueError(f"unknown kernel kind {kind!r}")


def pad_gram(K: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """blockdiag(K, I_pad): keeps the padded prior well-conditioned."""
    if n_pad == 0:
        return K
    N = K.shape[0]
    out = jnp.zeros((N + n_pad, N + n_pad), K.dtype)
    out = out.at[:N, :N].set(K)
    return out.at[jnp.arange(N, N + n_pad), jnp.arange(N, N + n_pad)].set(1.0)


@partial(jax.jit, static_argnames=("mode", "lam", "eps", "jitter", "axes",
                                   "triangle", "backend", "reduce_dtype"))
def krn_step(data: SVMData, K_prior: jnp.ndarray, omega: jnp.ndarray,
             key: jax.Array, *, mode: str = "EM", lam: float = 1.0,
             eps: float = 1e-6, jitter: float = 1e-6,
             axes: Sequence[str] = (), triangle: bool = True,
             backend: str | None = None,
             reduce_dtype: str | None = None,
             live: jnp.ndarray | None = None):
    """One KRN-*-CLS iteration.

    data.X holds this shard's *rows of the padded Gram matrix* (N_loc, N);
    K_prior is the full padded Gram (replicated; the lam*K prior term).
    Returns (omega_new, aux dict).
    """
    K_rows, y, mask = data

    # Identical structure to LIN with X := K_rows, w := omega.
    # Masked rows contribute: their K-row is e_d (blockdiag identity), but
    # y = 0 there, so b gets 0; S would get (1/gamma_pad) e_d e_d^T — a
    # positive diagonal on padded components only. gamma_pad = |0 - omega_d|
    # stays near 0 -> clamp; suppress via the explicit Sigma weight mask.
    # Both modes stream the Gram rows ONCE: MC pre-draws per-GLOBAL-row
    # (nu, u) noise (fold_in(iter_key, row index) — the sampled chain is
    # independent of the mesh layout) and the IG transform runs inside
    # the kernel epilogue (DESIGN.md §Perf/MC-SVR).
    if mode == "EM":
        epilogue, noise = "em_hinge", None
    else:
        row0 = stats.shard_row_offset(K_rows.shape[0], axes)
        epilogue = "mc_hinge"
        noise = augment.draw_ig_noise(key, K_rows.shape[0], row0)
    margin, gamma, b, S = ops.fused_stats(K_rows, y, y, omega, mask,
                                          noise, epilogue=epilogue,
                                          eps=eps, backend=backend)
    S, b = stats.reduce(S, b, axes, triangle=triangle,
                        reduce_dtype=reduce_dtype, live=live)
    omega_new = stats.m_step(S, b, key, mode=mode, lam=lam, jitter=jitter,
                             prior_precision=K_prior)

    K_omega = K_prior @ omega_new
    obj = objective.kernel_reg(omega_new, K_omega, lam) + stats.preduce(
        objective.hinge_obj_terms(margin, y, mask), axes, live)
    return omega_new, {"objective": obj,
                       "gamma_mean": stats.masked_mean(
                           jnp.sum(gamma * mask), jnp.sum(mask), axes,
                           live)}


def decision_function(omega: jnp.ndarray, X_train: jnp.ndarray,
                      X_test: jnp.ndarray, *, kind: str = "rbf",
                      sigma: float = 1.0,
                      backend: str | None = None) -> jnp.ndarray:
    """f(x) = sum_d omega_d k(x_d, x)."""
    K_cross = gram_matrix(X_test, X_train, kind=kind, sigma=sigma,
                          backend=backend)
    return K_cross @ omega.astype(jnp.float32)

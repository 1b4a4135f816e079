"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth used by tests (assert_allclose vs interpret-mode
Pallas) and the default CPU execution path of ``ops.py``. Each oracle's
dots run at the precision of the kernel it stands for: the Nystrom map
at HIGHEST, the LIN statistic at ``precision`` (None: the TPU's
default, one bf16 pass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import epilogues, rng


def seed_noise(seed, n: int, n_chains: int, epilogue: str):
    """Materialize the in-kernel counter stream for ``n`` rows.

    ``seed`` is the (4,) uint32 [k0, k1, row0, chain0] operand
    (``rng.pack_seed``).  Returns the epilogue's noise tuple with (n,)
    arrays for a single chain, (n, n_chains) for a multichain call —
    bitwise identical to the values the fused kernels derive in-body,
    because both sides run the same elementwise ``rng`` code.
    """
    rows = seed[2].astype(jnp.int32) + jnp.arange(n, dtype=jnp.int32)
    chains = seed[3].astype(jnp.int32)
    if n_chains > 1:
        rows = rows[:, None]
        chains = chains + jnp.arange(n_chains, dtype=jnp.int32)[None, :]
    return rng.counter_noise(seed[0], seed[1], rows, chains,
                             epilogues.noise_arity(epilogue))


HIGHEST = jax.lax.Precision.HIGHEST


def weighted_gram(X: jnp.ndarray, w: jnp.ndarray,
                  precision=None) -> jnp.ndarray:
    """S = X^T diag(w) X  == sum_d w_d x_d x_d^T.

    The paper's rate-limiting statistic (its Table-9 GPU kernel).

    Args:
      X: (N, K) design matrix.
      w: (N,) per-datum weights (1/gamma_d in the paper).

    Returns:
      (K, K) float32 matrix.
    """
    Xf = X.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    return jnp.matmul((Xf * wf[:, None]).T, Xf, precision=precision)


def fused_estep(X: jnp.ndarray, rho: jnp.ndarray, beta: jnp.ndarray,
                wvec: jnp.ndarray, eps: float
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused E-step for the generic hinge max(0, beta*(rho - w^T x)).

    Computes, in one logical pass over X:
      margin_d = w^T x_d
      gamma_d  = max(eps, |rho_d - margin_d|)          (paper Eq. 9 / 36 + 5.7.3 clamp)
      b        = sum_d (rho_d/gamma_d + beta_d) x_d    (paper Eq. 6 / 39 numerator)

    Binary CLS is the special case rho = beta = y in {+1,-1}:
      gamma = |1 - y w^T x|, b = sum y(1+1/gamma) x.

    Returns:
      (margin (N,), gamma (N,), b (K,)), all float32.
    """
    Xf = X.astype(jnp.float32)
    wf = wvec.astype(jnp.float32)
    margin = Xf @ wf
    gamma = jnp.maximum(jnp.abs(rho.astype(jnp.float32) - margin), eps)
    coef = rho.astype(jnp.float32) / gamma + beta.astype(jnp.float32)
    b = Xf.T @ coef
    return margin, gamma, b


def syrk_tri(X: jnp.ndarray, w: jnp.ndarray,
             precision=None) -> jnp.ndarray:
    """Oracle for the triangle-blocked SYRK — identical mathematical
    content to ``weighted_gram``; the Pallas flavor merely skips the
    redundant upper-triangle block computations."""
    return weighted_gram(X, w, precision)


def fused_stats(X: jnp.ndarray, rho: jnp.ndarray, beta: jnp.ndarray,
                wvec: jnp.ndarray, wmask: jnp.ndarray | None,
                eps: float, epilogue: str = "em_hinge",
                noise: tuple | None = None, eps_ins: float = 0.0,
                col_window: tuple | None = None,
                seed: jnp.ndarray | None = None, precision=None):
    """One-sweep iteration statistic under any augmentation epilogue:
    margin -> (aug, sigma_weight, coef) -> (b, Sigma) in one logical
    pass (``kernels/epilogues.py`` holds the epilogue family; MC
    flavors consume pre-drawn per-row ``noise``).

    S = X^T diag(wmask * sigma_weight) X with the weights from THIS
    sweep's epilogue; wmask defaults to ones (the KRN path passes its
    row mask, the phi-space paths their row-validity mask).

    ``col_window = (start, blk)`` narrows Sigma to its column block
    X^T diag(w) X[:, start:start+blk] — the 2-D (data x model)
    ``k_shard_axis`` statistic. ``start`` may be TRACED (it is
    ``axis_index * blk`` inside shard_map); ``blk`` is static.

    ``seed`` (the (4,) uint32 [k0, k1, row0, chain0] from
    ``rng.pack_seed``) replaces pre-drawn ``noise`` with the counter
    stream (rng mode 'fused'); a 2-D (K, C) ``wvec`` then runs C chains
    at once — margin/aug become (N, C), b (K, C) and S (C, K, K).

    Returns:
      (margin (N,), *aug (N,) each, b (K,), S), all float32 — aug =
      (gamma,) for the hinge epilogues, (gamma, omega) for SVR; S is
      (K, K) full or (K, blk) windowed.
    """
    dot = functools.partial(jnp.matmul, precision=precision)
    Xf = X.astype(jnp.float32)
    if wvec.ndim == 2:
        assert seed is not None, "multichain fused_stats requires seed"
        assert col_window is None, (
            "multichain fused_stats does not compose with a column "
            "window")
        C = wvec.shape[1]
        margin = dot(Xf, wvec.astype(jnp.float32))        # (N, C)
        noise = seed_noise(seed, X.shape[0], C, epilogue)
        aug, weight, coef = epilogues.apply_epilogue(
            epilogue, margin, rho.astype(jnp.float32)[:, None],
            beta.astype(jnp.float32)[:, None], noise, eps, eps_ins)
        w = (weight if wmask is None
             else wmask.astype(jnp.float32)[:, None] * weight)
        b = dot(Xf.T, coef)                               # (K, C)
        S = jnp.stack([dot((Xf * w[:, c:c + 1]).T, Xf) for c in range(C)])
        return (margin, *aug, b, S)
    if seed is not None:
        noise = seed_noise(seed, X.shape[0], 1, epilogue)
    margin = dot(Xf, wvec.astype(jnp.float32))
    aug, weight, coef = epilogues.apply_epilogue(
        epilogue, margin, rho.astype(jnp.float32),
        beta.astype(jnp.float32), noise, eps, eps_ins)
    w = weight if wmask is None else wmask.astype(jnp.float32) * weight
    b = dot(Xf.T, coef)
    if col_window is None:
        return (margin, *aug, b, weighted_gram(X, w, precision))
    start, blk = col_window
    Xc = jax.lax.dynamic_slice_in_dim(Xf, jnp.asarray(start, jnp.int32),
                                      blk, axis=1)
    return (margin, *aug, b, dot((Xf * w[:, None]).T, Xc))


def nystrom_phi(X: jnp.ndarray, landmarks: jnp.ndarray, proj: jnp.ndarray,
                mask: jnp.ndarray | None, sigma: float, kind: str,
                add_bias: bool) -> jnp.ndarray:
    """Oracle for the fused Nystrom featurizer (nystrom_phi.py).

    phi = k(X, landmarks) @ proj, rows zeroed by ``mask``, with an
    optional mask-valued bias column appended (M = proj cols + bias).
    A zero X row is NOT a zero phi row under rbf, so the mask is load-
    bearing here — unlike the LIN kernels' zero-row convention.
    """
    Xf = X.astype(jnp.float32)
    if kind == "rbf":
        kmat = rbf_gram(Xf, landmarks, sigma)
    elif kind == "linear":
        kmat = jnp.matmul(Xf, landmarks.astype(jnp.float32).T,
                          precision=HIGHEST)
    else:
        raise ValueError(f"unknown kernel kind {kind!r}")
    phi = jnp.matmul(kmat, proj.astype(jnp.float32), precision=HIGHEST)
    maskv = (jnp.ones((X.shape[0], 1), jnp.float32) if mask is None
             else mask.astype(jnp.float32)[:, None])
    if add_bias:
        phi = jnp.concatenate([phi, jnp.ones_like(maskv)], axis=1)
    return phi * maskv


def nystrom_score(X: jnp.ndarray, landmarks: jnp.ndarray,
                  proj: jnp.ndarray, W: jnp.ndarray,
                  mask: jnp.ndarray | None, sigma: float, kind: str,
                  add_bias: bool) -> jnp.ndarray:
    """Oracle for the fused scoring epilogue (serving): (N, C) f32
    scores = nystrom_phi(X, ...) @ W — C score columns per row (one per
    tenant/class/uncertainty direction). Masked rows score 0."""
    phi = nystrom_phi(X, landmarks, proj, mask, sigma, kind, add_bias)
    return phi @ W.astype(jnp.float32)


def nystrom_fused_stats(X: jnp.ndarray, landmarks: jnp.ndarray,
                        proj: jnp.ndarray, rho: jnp.ndarray,
                        beta: jnp.ndarray, wvec: jnp.ndarray,
                        mask: jnp.ndarray | None, sigma: float, kind: str,
                        add_bias: bool, eps: float,
                        epilogue: str = "em_hinge",
                        noise: tuple | None = None, eps_ins: float = 0.0,
                        col_window: tuple | None = None,
                        seed: jnp.ndarray | None = None):
    """Oracle for the featurize-and-accumulate kernel: fused_stats on
    nystrom_phi, i.e. the whole phi-space iteration statistic under any
    augmentation epilogue (EM/MC hinge, SVR's double mixture).
    ``col_window`` narrows Sigma to a PHI-column block (the
    ``k_shard_axis`` composition; see ``fused_stats``).

    Returns (margin (N,), *aug (N,) each, b (M,), S (M, M) or
    (M, blk)), all f32.
    """
    phi = nystrom_phi(X, landmarks, proj, mask, sigma, kind, add_bias)
    return fused_stats(phi, rho, beta, wvec, mask, eps,
                       epilogue=epilogue, noise=noise, eps_ins=eps_ins,
                       col_window=col_window, seed=seed, precision=HIGHEST)


def rbf_gram(X1: jnp.ndarray, X2: jnp.ndarray, sigma: float) -> jnp.ndarray:
    """RBF Gram block: K_ij = exp(-||x_i - x_j||^2 / (2 sigma^2)).

    Args:
      X1: (N1, K), X2: (N2, K).

    Returns:
      (N1, N2) float32.
    """
    X1f = X1.astype(jnp.float32)
    X2f = X2.astype(jnp.float32)
    sq1 = jnp.sum(X1f * X1f, axis=-1, keepdims=True)
    sq2 = jnp.sum(X2f * X2f, axis=-1, keepdims=True)
    d2 = sq1 - 2.0 * jnp.matmul(X1f, X2f.T, precision=HIGHEST) + sq2.T
    d2 = jnp.maximum(d2, 0.0)
    return jnp.exp(-d2 / (2.0 * sigma * sigma))

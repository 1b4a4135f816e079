"""Reference KRN-EM-CLS fit with Nystrom features (arXiv:1512.07716,
Sec 4.3): the LIN-EM-CLS reference (``lin_em_cls``) on
phi(x) = k_m(x) K_mm^{-1/2} with a bias column last.

The m landmarks are rows ``np.random.default_rng(fit_seed).choice(N, m,
replace=False)`` of the shard-major rows. K_mm and its eigendecomposition
are float64 on the host; eigenvalues at or below the relative spectral
floor times the largest are dropped. phi is computed on the device in row
blocks, k(x, l) = exp(-|x - l|^2 / 2 sigma^2) with the distance expanded
as |x|^2 - 2 x.l + |l|^2, in the precision ``prec`` names (float32 at
HIGHEST for the reference, bfloat16 for the control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common, lin_em_cls

FAULTS = lin_em_cls.FAULTS + ("other_landmarks",)
SPECTRAL_FLOOR = 1e-6
FEATURE_BLOCK = 8192     # rows featurized at once


def landmark_rows(n_rows: int, m: int, fit_seed: int) -> np.ndarray:
    """Indices of the m landmark rows among n_rows."""
    return np.random.default_rng(fit_seed).choice(n_rows, size=m,
                                                  replace=False)


def projection(landmarks: np.ndarray, sigma: float) -> np.ndarray:
    """K_mm^{-1/2} (m, m) in float64 over the kept eigenvalues."""
    L = np.asarray(landmarks, np.float64)
    sq = np.sum(L * L, axis=1)
    d2 = np.maximum(sq[:, None] - 2.0 * L @ L.T + sq[None, :], 0.0)
    K = np.exp(-d2 / (2.0 * sigma ** 2))
    w, V = np.linalg.eigh(0.5 * (K + K.T))
    keep = w > SPECTRAL_FLOOR * w.max()
    return (V[:, keep] / np.sqrt(w[keep])) @ V[:, keep].T


@functools.partial(jax.jit, static_argnames=("sigma", "prec", "n_rows"))
def features(X3, landmarks, proj, *, sigma, prec, n_rows):
    """phi (S, n, m + 1) with the bias column last, in row blocks of
    FEATURE_BLOCK; rows past ``n_rows`` (padding) are zero."""
    S, n, _ = X3.shape
    L = landmarks.astype(prec.store)
    sql = jnp.sum(L * L, axis=1)

    def one(X):                                  # (S, block, D)
        X = X.astype(prec.store)
        d2 = (jnp.sum(X * X, axis=2)[..., None] + sql
              - 2.0 * common.dot("sbd,md->sbm", X, L, prec))
        k = jnp.exp(-jnp.maximum(d2, 0.0) / (2.0 * sigma ** 2))
        phi = common.dot("sbm,mk->sbk", k, proj, prec)
        return jnp.concatenate([phi, jnp.ones_like(phi[..., :1])], axis=2)

    block = min(n, FEATURE_BLOCK)
    nb = -(-n // block)
    X3 = jnp.pad(X3, ((0, 0), (0, nb * block - n), (0, 0)))
    blocks = X3.reshape(S, nb, block, -1).swapaxes(0, 1)
    phi = jax.lax.map(one, blocks)               # (nb, S, block, K)
    phi = phi.swapaxes(0, 1).reshape(S, nb * block, -1)[:, :n]
    phi = phi.astype(common.F32)
    return jnp.where((jnp.arange(n) < n_rows)[None, :, None], phi, 0.0)


def fit(X3, y3, cfg: dict, iters: int, fit_seed: int,
        prec=common.REFERENCE, fault: str | None = None):
    """(phi-space weights (m + 1,), objective per iteration) of
    ``iters`` EM iterations of ``lin_em_cls.fit`` on the features of the
    raw rows ``X3``. ``fault`` plants one of FAULTS: ``lin_em_cls``'s
    own, or ``other_landmarks``, landmarks drawn from ``fit_seed + 1``.

    ``lin_em_cls`` sums a shard of more than ``common.BLOCK`` rows in
    blocks of that many; rows of zeros with target 0 pad each such shard
    to a multiple of it. Such a row adds nothing to Sigma or b, and
    exactly 2 (the hinge at margin 0) to every iteration's loss, which
    is taken off again."""
    S, n, _ = X3.shape
    seed = fit_seed + 1 if fault == "other_landmarks" else fit_seed
    idx = landmark_rows(S * n, cfg["n_landmarks"], seed)
    landmarks = np.asarray(X3.reshape(S * n, -1)[idx])
    proj = jnp.asarray(projection(landmarks, cfg["sigma"]), common.F32)
    pad = -n % common.BLOCK if n > common.BLOCK else 0
    phi3 = features(jnp.pad(X3, ((0, 0), (0, pad), (0, 0))),
                    jnp.asarray(landmarks), proj, sigma=cfg["sigma"],
                    prec=prec, n_rows=n)
    y3 = jnp.pad(y3, ((0, 0), (0, pad)))
    w, trace = lin_em_cls.fit(phi3, y3, cfg, iters, fit_seed, prec=prec,
                              fault=None if fault == "other_landmarks"
                              else fault)
    return w, [t - 2.0 * S * pad for t in trace]

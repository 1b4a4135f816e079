"""LIN-{EM,MC}-SVR: support vector regression via the *double* scale
mixture (paper Sec 3.2, Lemma 3).

Two augmentation variables per datum for the eps-insensitive loss
max(0, |y - w^T x| - eps_ins):

  gamma_d <- |y_d - w^T x_d - eps_ins|     (Eq. 25)
  omega_d <- |y_d - w^T x_d + eps_ins|     (Eq. 26)

  Sigma^p = X^T diag(1/gamma + 1/omega) X               (Eq. 27)
  mu^p    = X^T ((y - eps)/gamma + (y + eps)/omega)     (Eq. 28; the paper's
            "lambda_d" in Eq. 28 is a typo for gamma_d)

Iteration cost is the paper's "constant factor of 2" over CLS (Sec 4.3).

``svr_local_stats`` is the fused statistic over a row block and
``svr_chunk_stats`` the E-step built on it (exact row sums), run by the
in-memory step, the mesh SPMD step and the streaming driver's per-chunk
accumulation alike — same pattern as ``linear.cls_chunk_stats``.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops
from . import augment, objective
from .linear import PhiSpec, SVMData, resident_step


def svr_local_stats(X: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray, *,
                    mode: str, key: jax.Array | None, eps: float,
                    eps_ins: float, backend: str | None,
                    row0: jnp.ndarray | int = 0,
                    phi=None, phi_spec: PhiSpec | None = None,
                    mask: jnp.ndarray | None = None,
                    col_window: tuple | None = None,
                    rng: str = "host", chain0: int = 0):
    """(pred, gamma, omega, Sigma^p, mu^p) over one row block.

    BOTH mixtures now run as a ``fused_stats`` epilogue (``em_svr`` /
    ``mc_svr``): the kernel computes gamma and omega from the margin
    tile, the combined weights 1/gamma + 1/omega and the coef
    (y-eps)/gamma + (y+eps)/omega, so the whole Eq. 25-28 statistic is
    ONE X stream per iteration instead of the pre-fusion three (pred
    matmul, b matmul, SYRK) — DESIGN.md §Perf/MC-SVR. MC pre-draws both
    mixtures' (nu, u) noise per global row (two independent streams via
    a key split — gamma's mixture from the low key, omega's from the
    high, exactly the split-key rowwise oracle), so the chain stays
    invariant to chunking and sharding layout. Padded rows (X-row = 0,
    y = 0) contribute exactly zero to Sigma and b.

    ``phi``/``phi_spec`` switch to Nystrom phi-space through
    ``ops.nystrom_fused_stats`` under the same SVR epilogues: the block
    featurizes in VMEM and no phi block is materialized, for EM and MC
    alike; ``mask`` zeroes phi rows (a zero X row is not a zero phi
    row) and scales the Sigma weights.

    ``col_window`` narrows Sigma to this model-shard's column block
    (the 2-D ``k_shard_axis`` statistic), composing with both modes
    and the phi path — see ``linear.accumulate_stats``.

    ``rng``/``chain0`` select the MC noise source (see
    ``linear.accumulate_stats``): under the counter modes BOTH
    mixtures come from ONE key — the gamma mixture is counter plane
    2m=0, omega's 2m=2 — replacing the host path's key split; a 2-D
    (K, C) ``w`` under 'fused' runs C chains over the one X stream."""
    epilogue = "em_svr" if mode == "EM" else "mc_svr"
    noise, seed = None, None
    if mode == "MC":
        if rng == "host":
            k_lo, k_hi = jax.random.split(key)
            nu_g, u_g = augment.draw_ig_noise(k_lo, X.shape[0], row0)
            nu_o, u_o = augment.draw_ig_noise(k_hi, X.shape[0], row0)
            noise = (nu_g, u_g, nu_o, u_o)
        elif rng == "fused_predraw":
            noise = augment.draw_fused_noise(key, X.shape[0], row0,
                                             chain0, 4)
        else:
            assert rng == "fused", rng
            seed = augment.pack_seed(key, row0, chain0)
    beta0 = jnp.zeros((X.shape[0],), jnp.float32)  # hinge sign: unused
    if phi_spec is not None:
        landmarks, proj = phi
        if mask is None:
            mask = jnp.ones((X.shape[0],), jnp.float32)
        pred, gamma, omega, b, S = ops.nystrom_fused_stats(
            X, landmarks, proj, y, beta0, w, mask, noise,
            sigma=phi_spec.sigma, kind=phi_spec.kind,
            add_bias=phi_spec.add_bias, epilogue=epilogue, eps=eps,
            eps_ins=eps_ins, col_window=col_window, seed=seed,
            backend=backend)
    else:
        pred, gamma, omega, b, S = ops.fused_stats(
            X, y, beta0, w, None, noise, epilogue=epilogue, eps=eps,
            eps_ins=eps_ins, col_window=col_window, seed=seed,
            backend=backend)
    return pred, gamma, omega, S, b


def svr_chunk_stats(chunk: SVMData, w: jnp.ndarray, key: jax.Array,
                    row0: jnp.ndarray, *, mode: str, eps: float,
                    eps_ins: float, backend: str | None, phi=None,
                    phi_spec: PhiSpec | None = None,
                    rng: str = "host", n_chains: int = 1,
                    chain0: int = 0,
                    col_window: tuple | None = None) -> dict:
    """THE SVR E-step over one row block: its additive contributions,
    tree-summed across chunks by the stream driver and run over the
    whole shard by ``svr_step``. Multichain blocks carry
    S (C, K, K) / b (K, C) and chain-summed scalars — see
    ``linear.cls_chunk_stats``."""
    X, y, mask = chunk
    multi = n_chains > 1
    pred, gamma, omega, S, b = svr_local_stats(
        X, y, w.T if multi else w, mode=mode, key=key, eps=eps,
        eps_ins=eps_ins, backend=backend, row0=row0, phi=phi,
        phi_spec=phi_spec, mask=mask, col_window=col_window, rng=rng,
        chain0=chain0)
    if multi:
        y, mask = y[:, None], jnp.broadcast_to(mask[:, None], pred.shape)
    return {
        "S": S,
        "b": b,
        "loss": objective.svr_obj_terms(pred, y, eps_ins, mask),
        "gamma_sum": jnp.sum(gamma * mask),
        "omega_sum": jnp.sum(omega * mask),
        "mask_sum": jnp.sum(mask),
    }


@partial(jax.jit, static_argnames=("mode", "lam", "eps", "eps_ins", "jitter",
                                   "axes", "triangle", "backend",
                                   "k_shard_axis", "reduce_dtype",
                                   "phi_spec", "rng", "n_chains", "chain0"))
def svr_step(data: SVMData, w: jnp.ndarray, key: jax.Array, *,
             mode: str = "EM", lam: float = 1.0, eps: float = 1e-6,
             eps_ins: float = 1e-3, jitter: float = 1e-6,
             axes: Sequence[str] = (), triangle: bool = True,
             backend: str | None = None,
             k_shard_axis: str | None = None,
             reduce_dtype: str | None = None,
             phi=None, phi_spec: PhiSpec | None = None,
             live: jnp.ndarray | None = None,
             rng: str = "host", n_chains: int = 1, chain0: int = 0):
    """One LIN-*-SVR iteration. Returns (w_new, aux dict) with aux keys
    ``objective``, ``gamma_mean`` and ``omega_mean``. ``live``
    renormalizes the reductions around dropped replicas (stats.preduce).
    ``rng``/``n_chains``/``chain0`` mirror ``linear.cls_step``: the
    weight state is chain-major (C, K) when n_chains > 1."""
    return resident_step(
        svr_chunk_stats, data, w, key, mode=mode, lam=lam, jitter=jitter,
        axes=axes, triangle=triangle, k_shard_axis=k_shard_axis,
        reduce_dtype=reduce_dtype, live=live, rng=rng, n_chains=n_chains,
        chain0=chain0, eps=eps, eps_ins=eps_ins, backend=backend, phi=phi,
        phi_spec=phi_spec)

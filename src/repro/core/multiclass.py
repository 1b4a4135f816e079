"""LIN-{EM,MC}-MLT: Crammer-Singer multiclass SVM (paper Sec 3.3).

Hierarchical block update (paper's 2-layer structure): the outer loop
cycles over classes y = 1..M; given the other classes' weights w_{-y}, the
class-y conditional is a *binary-style* augmented problem with

  zeta_d(y) = max_{y' != y} (w_{y'}^T x_d + Delta_d(y'))   (indep. of w_y)
  rho_d^y   = zeta_d(y) - Delta_d(y)
  beta_d^y  = +1 if y == y_d else -1                        (Eq. 34-35)

then gamma_{yd} = |rho_d^y - w_y^T x_d| (Eq. 36) and the Gaussian step
Eq. 38-39 — i.e. exactly ``linear.accumulate_stats`` with per-class
(rho, beta). Delta is the standard 0/1 cost. Iteration time is M x LIN
(paper Sec 4.3).

Each class conditional IS ``linear.accumulate_stats``, so the fused
epilogue family applies per class: an MC sweep issues M single-stream
fused passes (margin, Gibbs gamma via in-kernel IG transform, b, Sigma
per class) instead of the pre-fusion 3M X streams — the M-class Gibbs
sweep itself stays inherently sequential (class y's rho depends on the
already-updated w_{<y}), so M streams per sweep is the floor
(DESIGN.md §Perf/MC-SVR, ROADMAP Open items).

The class loop maintains the score matrix F = X W^T and refreshes only
column y after updating w_y (one GEMV instead of a full GEMM per class).
The streaming path (``mlt_class_chunk_stats``) instead *recomputes* the
chunk's F from the current W each pass — mathematically identical,
because the incrementally-maintained F's columns are exactly X w_c for
each class c at its current value — trading O(NKM^2) extra margin
FLOPs per sweep (each of the M class passes rebuilds the (N, M) score
matrix) for never holding N rows at once; Sigma's O(NK^2 M) still
dominates while M < K. So the two drivers keep their own class E-step,
and share the class statistic (``accumulate_stats``), the reduce
(``stats.reduce``) and the M-step (``stats.m_step``).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops
from . import objective, stats
from .linear import PhiSpec, SVMData, _k_block, accumulate_stats

_NEG = -1e30


def _maybe_featurize(X: jnp.ndarray, mask: jnp.ndarray, phi,
                     phi_spec: PhiSpec | None, backend: str | None):
    """Nystrom phi-space entry for MLT: featurize the block and run the
    per-class conditional on the (rows, M_phi) result.

    In the in-memory step the block is the whole (local) set, so one
    featurize serves all M class passes (scores + M stats sweeps) —
    cheaper than M fused featurize passes, the opposite trade from
    binary CLS where the fused kernel's single pass wins (DESIGN.md
    §Perf/Nystrom). The STREAMING driver re-streams chunks per class
    pass, so it pays this featurize (M + 1) times per chunk per
    iteration — inherent to not holding phi resident, and the same
    recompute-vs-residency trade the LIN stream path already makes for
    MLT's score matrix (module docstring): at most ~(1 + D/m) extra
    work over each pass's O(rows · m^2) Sigma statistic. Zeroed phi
    rows keep padded rows exact no-ops for Sigma/b even though the
    Crammer-Singer rho of a padded row is nonzero."""
    if phi_spec is None:
        return X
    landmarks, proj = phi
    return ops.nystrom_phi(X, landmarks, proj, mask, sigma=phi_spec.sigma,
                           kind=phi_spec.kind, add_bias=phi_spec.add_bias,
                           backend=backend)


def _rho_beta(F: jnp.ndarray, labels: jnp.ndarray, y: jnp.ndarray,
              M: int):
    """Per-class hinge parameters for class y (traced int)."""
    N = F.shape[0]
    class_ids = jnp.arange(M)
    onehot_lbl = (labels[:, None] == class_ids[None, :]).astype(jnp.float32)
    delta = 1.0 - onehot_lbl                             # Delta_d(y') 0/1 cost
    A = F + delta
    A_excl = jnp.where(class_ids[None, :] == y, _NEG, A)
    zeta = jnp.max(A_excl, axis=1)                       # zeta_d(y)
    delta_y = (labels != y).astype(jnp.float32)          # Delta_d(y)
    rho = zeta - delta_y
    beta = jnp.where(labels == y, 1.0, -1.0)
    return rho, beta


def mlt_class_chunk_stats(chunk: SVMData, W: jnp.ndarray, key: jax.Array,
                          row0: jnp.ndarray, y: jnp.ndarray, *,
                          num_classes: int, mode: str, eps: float,
                          backend: str | None, phi=None,
                          phi_spec: PhiSpec | None = None,
                          rng: str = "host", chain0: int = 0) -> dict:
    """Streaming class-y E-step body: one chunk's (Sigma, b) contribution.

    Recomputes the chunk's score matrix from the *current* W (classes
    before y already updated this sweep), reproducing the in-memory
    step's incrementally-maintained F exactly — see module docstring.
    The gamma key is ``fold_in(key, y)`` + rowwise (counter rng modes
    build their seed from the same per-class key), matching
    ``mlt_step``'s per-class keying, so MC chains agree bitwise with the
    in-memory drivers."""
    X, labels, mask = chunk
    X = _maybe_featurize(X, mask, phi, phi_spec, backend)
    F = X.astype(jnp.float32) @ W.T.astype(jnp.float32)
    rho, beta = _rho_beta(F, labels, y, num_classes)
    _, _, S, b = accumulate_stats(
        X, rho, beta, W[y], mode=mode, key=jax.random.fold_in(key, y),
        eps=eps, backend=backend, row0=row0, rng=rng, chain0=chain0)
    return {"S": S, "b": b}


def mlt_chunk_obj(chunk: SVMData, W: jnp.ndarray, phi=None,
                  phi_spec: PhiSpec | None = None,
                  backend: str | None = None) -> dict:
    """Streaming objective body: the chunk's Crammer-Singer loss terms
    at the end-of-sweep W, plus the valid-row count (both additive)."""
    X, labels, mask = chunk
    X = _maybe_featurize(X, mask, phi, phi_spec, backend)
    F = X.astype(jnp.float32) @ W.T.astype(jnp.float32)
    return {"loss": objective.cs_obj_terms(F, labels, mask),
            "mask_sum": jnp.sum(mask)}


@partial(jax.jit, static_argnames=("num_classes", "mode", "lam", "eps",
                                   "jitter", "axes", "triangle", "backend",
                                   "k_shard_axis", "reduce_dtype",
                                   "phi_spec", "rng", "chain0"))
def mlt_step(data: SVMData, W: jnp.ndarray, key: jax.Array, *,
             num_classes: int, mode: str = "EM", lam: float = 1.0,
             eps: float = 1e-6, jitter: float = 1e-6,
             axes: Sequence[str] = (), triangle: bool = True,
             backend: str | None = None,
             k_shard_axis: str | None = None,
             reduce_dtype: str | None = None,
             phi=None, phi_spec: PhiSpec | None = None,
             live: jnp.ndarray | None = None,
             rng: str = "host", chain0: int = 0):
    """One outer MLT iteration = one block sweep over all M classes.

    W: (M, K). Returns (W_new, aux dict). ``k_shard_axis`` switches
    every class conditional to the 2-D (data x model) column-windowed
    statistic (one window per shard, shared by all M passes — the
    class sweep stays M single-stream fused passes).

    ``rng``/``chain0``: the counter modes key class y's in-kernel noise
    from ``pack_seed(fold_in(key, y), row0, chain0)`` and its weight
    draw from ``fold_in(fold_in(key, y), chain0)`` — MLT runs a single
    chain (n_chains > 1 is CLS/SVR-only), so chain0 just addresses
    which counter plane this fit occupies.
    """
    X, labels, mask = data
    X = _maybe_featurize(X, mask, phi, phi_spec, backend)
    M = num_classes
    # A lane-padded model X (distributed.upload_rows): the scores read
    # its first K columns, the statistic reads it whole.
    Xf = X[:, :W.shape[1]].astype(jnp.float32)
    row0 = stats.shard_row_offset(X.shape[0], axes)
    col_window = (_k_block(W.shape[1], k_shard_axis)
                  if k_shard_axis is not None else None)

    with jax.named_scope("score"):
        F0 = Xf @ W.T.astype(jnp.float32)                # (N, M)

    def body(y, carry):
        W, F = carry
        rho, beta = _rho_beta(F, labels, y, M)
        # Padding rows: X-row == 0 => margin 0 and zero stats contribution.
        ky = jax.random.fold_in(key, y)
        _, _, S, b = accumulate_stats(
            X, rho, beta, W[y], mode=mode, key=ky, eps=eps,
            backend=backend, row0=row0, col_window=col_window, rng=rng,
            chain0=chain0)
        S, b = stats.reduce(S, b, axes, k_shard_axis=k_shard_axis,
                            triangle=triangle, reduce_dtype=reduce_dtype,
                            live=live)
        w_new = stats.m_step(S, b, ky, mode=mode, lam=lam, jitter=jitter,
                             rng=rng, chain0=chain0)
        W = W.at[y].set(w_new)
        with jax.named_scope("score"):
            F = F.at[:, y].set(Xf @ w_new)
        return (W, F)

    W_new, F = jax.lax.fori_loop(0, M, body, (W.astype(jnp.float32), F0))

    obj = objective.l2_reg(W_new, lam) + stats.preduce(
        objective.cs_obj_terms(F, labels, mask), axes, live)
    return W_new, {"objective": obj}


def predict(W: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """argmax_y w_y^T x (paper Eq. 29)."""
    return jnp.argmax(X.astype(jnp.float32) @ W.T.astype(jnp.float32), axis=1)

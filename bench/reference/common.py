"""Plain jax.numpy pieces of the reference fits. Imports nothing of the
program under test.

Rows are laid out (S, n, K): S data shards (1 on one chip) of n rows,
with the shard axis spread over the mesh, so the statistic of each shard
is summed in row blocks on its own chip and the S partial sums are added
once at the end. ``prec`` is the precision the statistic is computed
in: ``REFERENCE``, float32 at HIGHEST matmul precision; ``CONTROL``,
bfloat16 storage, products and sums; ``BF16_PRODUCTS``, bfloat16
operands with float32 sums, as a float32 matmul at the TPU's default
precision takes them. The per-row augmentation and the M-step are
float32 in all three.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BLOCK = 16384          # rows per block


class Precision(NamedTuple):
    store: object      # dtype of X's copy, the row weights and the sums
    operand: object    # dtype the products are taken in


REFERENCE = Precision(F32, F32)
CONTROL = Precision(jnp.bfloat16, jnp.bfloat16)
BF16_PRODUCTS = Precision(F32, jnp.bfloat16)


def dot(spec: str, a, b, prec: Precision):
    return jnp.einsum(spec, a.astype(prec.operand), b.astype(prec.operand),
                      precision=HIGHEST, preferred_element_type=prec.store)


def _blocks(n_rows: int, block: int) -> tuple[int, int]:
    block = min(block, n_rows)
    if n_rows % block:
        raise ValueError(f"{n_rows} rows are not a multiple of the "
                         f"block of {block}")
    return block, n_rows // block


@functools.partial(jax.jit, static_argnames=("aug", "prec", "n_rows",
                                             "block"))
def statistic(X3, rho3, beta3, w, aux, *, aug, prec, n_rows=None,
              block=BLOCK):
    """Sigma = sum_d s_d x_d x_d^T and b = sum_d c_d x_d over the first
    ``n_rows`` rows of each shard (all by default), where
    (s, c) = aug(margin, rho, beta, global row, aux) per row and
    margin = w . x. Returns float32 (Sigma, b)."""
    S, n, K = X3.shape
    block, nb = _blocks(n if n_rows is None else n_rows, block)
    shard0 = (jnp.arange(S, dtype=jnp.int32) * n)[:, None]

    def body(j, carry):
        Sg, bg = carry
        r0 = j * block
        Xj = jax.lax.dynamic_slice_in_dim(X3, r0, block, axis=1)
        rj = jax.lax.dynamic_slice_in_dim(rho3, r0, block, axis=1)
        bj = jax.lax.dynamic_slice_in_dim(beta3, r0, block, axis=1)
        rows = shard0 + r0 + jnp.arange(block, dtype=jnp.int32)[None, :]
        m = dot("sbk,k->sb", Xj, w, prec).astype(F32)
        s, c = aug(m, rj, bj, rows, aux)
        Xd = Xj.astype(prec.store)
        Sg = Sg + dot("sbk,sbl->skl", Xd * s.astype(prec.store)[..., None],
                      Xd, prec)
        bg = bg + dot("sbk,sb->sk", Xd, c, prec)
        return Sg, bg

    Sg, bg = jax.lax.fori_loop(
        0, nb, body, (jnp.zeros((S, K, K), prec.store),
                      jnp.zeros((S, K), prec.store)))
    return Sg.sum(0).astype(F32), bg.sum(0).astype(F32)


@functools.partial(jax.jit, static_argnames=("loss", "block"))
def _loss_blocks(X3, t3, W, *, loss, block):
    S, n, K = X3.shape
    block, nb = _blocks(n, block)

    def one(j):
        Xj = jax.lax.dynamic_slice_in_dim(X3, j * block, block, axis=1)
        tj = jax.lax.dynamic_slice_in_dim(t3, j * block, block, axis=1)
        F = jnp.einsum("sbk,mk->sbm", Xj, W.reshape(-1, K),
                       precision=HIGHEST)
        return jnp.sum(loss(F, tj))

    return jax.lax.map(one, jnp.arange(nb))


def objective(X3, t3, W, lam: float, loss, block=BLOCK) -> float:
    """0.5 lam ||W||^2 + sum of loss(scores, target) over all rows,
    with per-block float32 sums added in float64."""
    W = jnp.asarray(W, F32)
    parts = np.asarray(_loss_blocks(X3, t3, W, loss=loss, block=block),
                       np.float64)
    w64 = np.asarray(W, np.float64)
    return 0.5 * lam * float(np.sum(w64 * w64)) + float(parts.sum())


@jax.jit
def posterior(S, b, lam, jitter):
    """(L, mu) of the Gaussian conditional: P = lam I + Sigma, plus the
    relative ridge jitter * trace(P) / K; mu = P^-1 b."""
    K = S.shape[0]
    eye = jnp.eye(K, dtype=F32)
    P = S + lam * eye
    P = 0.5 * (P + P.T)
    P = P + (jitter * jnp.trace(P) / K) * eye
    L = jnp.linalg.cholesky(P)
    mu = jax.scipy.linalg.cho_solve((L, True), b)
    return L, mu


def hinge_coef(gamma, rho, beta):
    """(Sigma weight, b weight) of a row of the generic hinge."""
    return 1.0 / gamma, rho / gamma + beta


def negate_largest(w):
    """w with its largest-magnitude entry negated."""
    flat = w.reshape(-1)
    i = jnp.argmax(jnp.abs(flat))
    return flat.at[i].set(-flat[i]).reshape(w.shape)


def rel_l2(a, ref) -> float:
    a = np.asarray(a, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))

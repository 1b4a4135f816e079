"""Triangle-blocked weighted SYRK: S = X^T diag(w) X touching only the
lower-triangle block pairs.

The paper notes (Sec 4.1) that Sigma is symmetric so "it suffices to
compute only the upper or lower triangle". ``weighted_gram`` exploits that
on the wire (triangle-packed psum) but still runs the full (K/bk)^2 block
grid — 2x the necessary FLOPs on the rate-limiting statistic. Here the
grid enumerates only the T = nb(nb+1)/2 block pairs with bk-row-index
i >= j, flattened to a 1-D triangular index t:

    i(t) = floor((sqrt(8t + 1) - 1) / 2),   j(t) = t - i(i+1)/2

``tri_ij`` computes that mapping in pure integer-exact arithmetic (fp32
sqrt seed + two integer corrections). The kernel itself consumes it as a
precomputed (T, 2) lookup table through ``PrefetchScalarGridSpec`` — the
TPU idiom for data-dependent block grids: the table is prefetched to
SMEM and each BlockSpec index map is a single scalar gather. (The
arithmetic-in-index-map variant recomputes ~a dozen scalar ops per spec
per grid step, which measurably erodes the FLOP win — the scalar stream
runs ahead of the MXU and any extra latency there stalls DMA issue; in
interpret mode it actually made the kernel *slower* than dense.)

Grid is (T, N/bn) with the N dimension innermost so the (bk, bk) fp32
output tile stays VMEM-resident across the N sweep, exactly like the
dense kernel (DESIGN.md §Perf).

The kernel writes only lower-triangle blocks; the full matrix is rebuilt
afterwards with a block-level where/transpose mirror (diagonal blocks are
computed in full, so the element-level upper triangle inside them is
already correct).

VMEM per step = 2*bn*bk (input tiles) + bn (weights) + bk*bk*4B
(accumulator); defaults (bn=512, bk=256) stay well under ~4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tri(i):
    return i * (i + 1) // 2


def tri_ij(t):
    """Flattened lower-triangle index t -> block pair (i, j), i >= j.

    Integer-exact for any practical grid (fp32 sqrt seed, then two
    exact integer corrections). Used to *derive* the lookup table and
    by tests; the kernel reads the table via scalar prefetch."""
    tf = t.astype(jnp.float32) if hasattr(t, "astype") else jnp.float32(t)
    i = ((jnp.sqrt(8.0 * tf + 1.0) - 1.0) * 0.5).astype(jnp.int32)
    i = jnp.where(_tri(i) > t, i - 1, i)
    i = jnp.where(_tri(i + 1) <= t, i + 1, i)
    return i, t - _tri(i)


def _kernel(ij_ref, x_lhs_ref, w_ref, x_rhs_ref, out_ref, *, precision):
    del ij_ref  # consumed by the index maps only
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xl = x_lhs_ref[...].astype(jnp.float32) * w_ref[...].astype(jnp.float32)
    xr = x_rhs_ref[...].astype(jnp.float32)
    # (bk, bn) @ (bn, bk) on the MXU, fp32 accumulation.
    out_ref[...] += jax.lax.dot_general(
        xl, xr, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k",
                                             "precision", "interpret"))
def syrk_tri(X: jnp.ndarray, w: jnp.ndarray, *,
             block_n: int = 512, block_k: int = 256, precision=None,
             interpret: bool = False) -> jnp.ndarray:
    """S = X^T diag(w) X via the triangle-blocked Pallas SYRK.

    X: (N, K); w: (N,). Returns the full symmetric (K, K) f32 matrix
    (mirrored from the computed lower block triangle). Inputs are
    zero-padded to block multiples; zero-weight rows are exact no-ops.
    ``precision`` is the block dot's (None: the TPU's default).
    """
    N, K = X.shape
    bn = min(block_n, _round_up(N, 8))
    bk = min(block_k, _round_up(K, 128))
    Np, Kp = _round_up(N, bn), _round_up(K, bk)
    if (Np, Kp) != (N, K):
        X = jnp.pad(X, ((0, Np - N), (0, Kp - K)))
        w = jnp.pad(w, (0, Np - N))
    w2 = w.reshape(Np, 1)

    nb = Kp // bk
    ii, jj = np.tril_indices(nb)            # == tri_ij(arange(T)), exact
    ij = jnp.asarray(np.stack([ii, jj], axis=1).astype(np.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # the (T, 2) block-pair table
        grid=(_tri(nb), Np // bn),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda t, n, ij: (n, ij[t, 0])),  # lhs
            pl.BlockSpec((bn, 1), lambda t, n, ij: (n, 0)),          # w
            pl.BlockSpec((bn, bk), lambda t, n, ij: (n, ij[t, 1])),  # rhs
        ],
        out_specs=pl.BlockSpec((bk, bk),
                               lambda t, n, ij: (ij[t, 0], ij[t, 1])),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, precision=precision),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Kp, Kp), jnp.float32),
        interpret=interpret,
    )(ij, X, w2, X)
    # Mirror: upper-triangle blocks come from the transposed lower
    # blocks; diagonal blocks were computed in full and pass through.
    bi = jnp.arange(Kp) // bk
    lower = bi[:, None] >= bi[None, :]
    S = jnp.where(lower, out, out.T)
    return S[:K, :K]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m

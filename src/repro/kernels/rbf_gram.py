"""Pallas TPU kernel: tiled RBF Gram blocks for the KRN formulation.

    K_ij = exp(-||x_i - x_j||^2 / (2 sigma^2))        (paper Sec 3.1)

||x_i - x_j||^2 is expanded as sq_i - 2 x_i.x_j + sq_j so the inner product
runs on the MXU; the squared norms are computed inside the tile (recomputing
them per tile is cheaper than an extra HBM stream at these shapes). Grid is
(N1/b1, N2/b2); each step holds one (b1, K) and one (b2, K) strip in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM for the kernels whose RBF tile runs its dots at HIGHEST
# (this Gram, and the Nystrom kernels in ``nystrom_phi.py``), in place
# of the compiler's 16 MiB default (v5e has 128 MiB): the least whole
# 8 MiB that holds ``ops._nystrom_vmem_words`` (double-buffered blocks,
# the HIGHEST dots' operand splits) at the landmark cap, m = 1024, and
# D = 256. ``ops.nystrom_fused_fits`` budgets against this figure.
VMEM_LIMIT_BYTES = 48 * 2 ** 20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def rbf_tile(x1: jnp.ndarray, x2: jnp.ndarray,
             inv_two_sigma_sq: float) -> jnp.ndarray:
    """The RBF Gram tile body: K_ij = exp(-||x1_i - x2_j||^2 / 2 sigma^2)
    for one (b1, K) x (b2, K) VMEM tile pair, inner product on the MXU.

    Shared by ``rbf_gram`` and the fused Nystrom featurize kernel
    (``nystrom_phi.py``), so the two paths cannot drift numerically.
    The cross term runs at HIGHEST precision: ``sq1 - 2 x1.x2 + sq2``
    cancels for nearby rows, and one bf16 pass (the TPU's default for
    a float32 dot) would leave errors the size of the distance itself.
    """
    sq1 = jnp.sum(x1 * x1, axis=1, keepdims=True)          # (b1, 1)
    sq2 = jnp.sum(x2 * x2, axis=1, keepdims=True)          # (b2, 1)
    cross = jax.lax.dot_general(                            # (b1, b2)
        x1, x2, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    d2 = jnp.maximum(sq1 - 2.0 * cross + sq2.T, 0.0)
    return jnp.exp(-d2 * inv_two_sigma_sq)


def _make_kernel(inv_two_sigma_sq: float):
    def _kernel(x1_ref, x2_ref, out_ref):
        x1 = x1_ref[...].astype(jnp.float32)      # (b1, K)
        x2 = x2_ref[...].astype(jnp.float32)      # (b2, K)
        out_ref[...] = rbf_tile(x1, x2, inv_two_sigma_sq)
    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("sigma", "block_n", "interpret"))
def rbf_gram(X1: jnp.ndarray, X2: jnp.ndarray, *, sigma: float = 1.0,
             block_n: int = 256, interpret: bool = False) -> jnp.ndarray:
    """RBF Gram matrix (N1, N2) f32 via Pallas tiles.

    Padding note: padded rows produce garbage Gram entries (exp of a real
    number, not 0) in the padded region only; they are sliced off before
    return, so callers always see exact values.
    """
    N1, K = X1.shape
    N2, K2 = X2.shape
    assert K == K2, (K, K2)
    b1 = min(block_n, _round_up(N1, 8))
    b2 = min(block_n, _round_up(N2, 128))
    Kp = _round_up(K, 128)
    N1p, N2p = _round_up(N1, b1), _round_up(N2, b2)
    if (N1p, Kp) != (N1, K):
        X1 = jnp.pad(X1, ((0, N1p - N1), (0, Kp - K)))
    if (N2p, Kp) != (N2, K):
        X2 = jnp.pad(X2, ((0, N2p - N2), (0, Kp - K)))

    out = pl.pallas_call(
        _make_kernel(1.0 / (2.0 * float(sigma) ** 2)),
        grid=(N1p // b1, N2p // b2),
        in_specs=[
            pl.BlockSpec((b1, Kp), lambda i, j: (i, 0)),
            pl.BlockSpec((b2, Kp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((b1, b2), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N1p, N2p), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(X1, X2)
    return out[:N1, :N2]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m

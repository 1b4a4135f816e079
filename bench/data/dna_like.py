"""dna-shaped binary problem, made on the device from a key.

The distribution of ``repro.data.synthetic.make_dna_like``, copied here so
that the yardstick does not move with the program: features are 0/1 with
density 0.25, labels the sign of a planted hyperplane's margin, centred
on its median, plus Gaussian noise of 0.45.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SPARSITY = 0.25
MARGIN_NOISE = 0.45


def make(key, n: int, k: int, num_classes: int, sharding=None):
    """-> (X (n, k) f32, y (n,) f32 in {-1, +1}) on the device."""
    del num_classes

    def gen(key):
        kx, kw, kn = jax.random.split(key, 3)
        X = (jax.random.uniform(kx, (n, k)) < SPARSITY).astype(jnp.float32)
        w = jax.random.normal(kw, (k,)) / jnp.sqrt(k * SPARSITY)
        m = jnp.dot(X, w, precision=jax.lax.Precision.HIGHEST)
        logits = m - jnp.median(m) + MARGIN_NOISE * jax.random.normal(kn, (n,))
        y = jnp.where(logits > 0, 1.0, -1.0).astype(jnp.float32)
        return X, y

    out = None if sharding is None else (sharding.x, sharding.rows)
    return jax.jit(gen, out_shardings=out)(key)

"""mnist8m-shaped 10-class problem, made on the device from a key.

The distribution of ``repro.data.synthetic.make_mnist8m_like``, copied
here so that the yardstick does not move with the program: each row is
half its class prototype (uniform in [0, 1]) and half uniform noise, and
8% of the labels are redrawn uniformly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FLIP = 0.08


def make(key, n: int, k: int, num_classes: int, sharding=None):
    """-> (X (n, k) f32 in [0, 1], y (n,) int32 in [0, num_classes))."""
    m = num_classes

    def gen(key):
        kp, kl, kx, kf, kr = jax.random.split(key, 5)
        protos = jax.random.uniform(kp, (m, k))
        labels = jax.random.randint(kl, (n,), 0, m, jnp.int32)
        X = 0.5 * protos[labels] + 0.5 * jax.random.uniform(kx, (n, k))
        flip = jax.random.uniform(kf, (n,)) < FLIP
        labels = jnp.where(flip, jax.random.randint(kr, (n,), 0, m, jnp.int32),
                           labels)
        return X.astype(jnp.float32), labels

    out = None if sharding is None else (sharding.x, sharding.rows)
    return jax.jit(gen, out_shardings=out)(key)

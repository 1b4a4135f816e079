"""covtype.binary-shaped problem, made on the device from a key.

The columns of LIBSVM's ``covtype.binary.scale`` (UCI Covertype,
Blackard 1998): 10 continuous features scaled to [0, 1] (elevation,
aspect, slope, distances, hillshades), a one-hot over the 4 wilderness
areas and a one-hot over the 40 soil types. The continuous features are
Beta-distributed, the wilderness areas skewed as in the source (about
45/5/44/6%), the soil types Zipf-like. The label is the sign of a
planted RBF expansion over 64 centres drawn from the same rows, plus
Gaussian noise, centred so that 48.8% of the rows are positive (class 2
against the rest, as in covtype.binary).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

N_CONTINUOUS = 10
WILDERNESS = (0.45, 0.05, 0.44, 0.06)
N_SOIL = 40
ZIPF = 1.1               # soil type s is drawn with weight (s + 1)^-ZIPF
BETA = ((2.0, 2.0), (1.0, 1.0), (1.5, 4.0), (1.2, 3.0), (1.5, 5.0),
        (1.2, 3.0), (6.0, 1.5), (6.0, 2.0), (3.0, 3.0), (1.2, 3.0))
N_CENTRES = 64
SIGMA = 1.0              # width of the planted expansion's RBF
NOISE = 0.5              # label noise, in units of the expansion's std
POSITIVE = 0.488         # share of +1 labels
N_FEATURES = N_CONTINUOUS + len(WILDERNESS) + N_SOIL


def _rows(key, n: int):
    kc, kw, ks = jax.random.split(key, 3)
    a, b = (jnp.array(v, jnp.float32) for v in zip(*BETA))
    cont = jax.random.beta(kc, a, b, (n, N_CONTINUOUS)).astype(jnp.float32)
    wild = jax.random.categorical(kw, jnp.log(jnp.array(WILDERNESS)),
                                  shape=(n,))
    soil_w = (jnp.arange(N_SOIL, dtype=jnp.float32) + 1.0) ** -ZIPF
    soil = jax.random.categorical(ks, jnp.log(soil_w), shape=(n,))
    return jnp.concatenate(
        [cont, jax.nn.one_hot(wild, len(WILDERNESS), dtype=jnp.float32),
         jax.nn.one_hot(soil, N_SOIL, dtype=jnp.float32)], axis=1)


def make(key, n: int, k: int, num_classes: int, sharding=None):
    """-> (X (n, 54) f32 in [0, 1], y (n,) f32 in {-1, +1}) on the
    device."""
    del num_classes
    if k != N_FEATURES:
        raise ValueError(f"covtype_like makes {N_FEATURES} columns, "
                         f"not {k}")

    def gen(key):
        kx, kc, ka, kn = jax.random.split(key, 4)
        X = _rows(kx, n)
        centres = _rows(kc, N_CENTRES)
        d2 = (jnp.sum(X * X, 1)[:, None] + jnp.sum(centres ** 2, 1)[None]
              - 2.0 * jnp.dot(X, centres.T,
                              precision=jax.lax.Precision.HIGHEST))
        f = jnp.exp(-jnp.maximum(d2, 0.0) / (2.0 * SIGMA ** 2)) @ (
            jax.random.normal(ka, (N_CENTRES,)))
        logits = (f - f.mean()) / f.std() + NOISE * jax.random.normal(
            kn, (n,))
        y = jnp.where(logits > jnp.quantile(logits, 1.0 - POSITIVE),
                      1.0, -1.0).astype(jnp.float32)
        return X, y

    out = None if sharding is None else (sharding.x, sharding.rows)
    return jax.jit(gen, out_shardings=out)(key)

"""stats.py: triangle packing, posterior params, weight draws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import stats


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(0, 2 ** 20))
def test_triangle_roundtrip(k, seed):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(k, k)).astype(np.float32)
    S = A + A.T
    packed = stats.triangle_pack(jnp.asarray(S))
    assert packed.shape == (k * (k + 1) // 2,)
    back = stats.triangle_unpack(packed, k)
    np.testing.assert_allclose(np.asarray(back), S, rtol=1e-6)


def test_posterior_params_matches_numpy_solve():
    rng = np.random.default_rng(1)
    K = 24
    X = rng.normal(size=(200, K)).astype(np.float32)
    S = (X.T @ X).astype(np.float32)
    b = rng.normal(size=(K,)).astype(np.float32)
    lam = 0.7
    L, mu = stats.posterior_params(jnp.asarray(S), jnp.asarray(b), lam)
    want = np.linalg.solve(S + lam * np.eye(K), b)
    np.testing.assert_allclose(np.asarray(mu), want, rtol=2e-3, atol=1e-4)


def test_draw_weight_covariance():
    """w ~ N(mu, P^{-1}): empirical covariance must match P^{-1}."""
    rng = np.random.default_rng(2)
    K = 6
    A = rng.normal(size=(K, K))
    P = (A @ A.T + 2 * np.eye(K)).astype(np.float32)
    L = jnp.linalg.cholesky(jnp.asarray(P))
    mu = jnp.zeros((K,))
    keys = jax.random.split(jax.random.PRNGKey(0), 30_000)
    draws = jax.vmap(lambda k: stats.draw_weight(k, L, mu))(keys)
    emp = np.cov(np.asarray(draws).T)
    np.testing.assert_allclose(emp, np.linalg.inv(P), atol=0.06)


def test_reduce_stats_identity_off_mesh():
    S = jnp.eye(5)
    b = jnp.arange(5.0)
    S2, b2 = stats.reduce_stats(S, b, axes=())
    np.testing.assert_allclose(np.asarray(S2), np.eye(5))
    np.testing.assert_allclose(np.asarray(b2), np.arange(5.0))


def test_posterior_scaled_jitter_handles_bad_conditioning():
    """fp32 Gram noise (slightly negative eigenvalues) must not break the
    Cholesky once the relative ridge is applied."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(400, 2)).astype(np.float32)
    from repro.core.kernel import gram_matrix
    G = np.asarray(gram_matrix(jnp.asarray(X), jnp.asarray(X), sigma=0.7))
    S = (G.T @ G).astype(np.float32)
    L, mu = stats.posterior_params(jnp.asarray(S), jnp.asarray(G[:, 0]),
                                   0.1, prior_precision=jnp.asarray(G),
                                   jitter=1e-4)
    assert bool(jnp.all(jnp.isfinite(L))) and bool(jnp.all(jnp.isfinite(mu)))


def _spd_stats(C, K, seed):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(C, 3 * K, K)).astype(np.float32)
    S = np.einsum("cnk,cnj->ckj", A, A)
    b = rng.normal(size=(K, C)).astype(np.float32)
    return jnp.asarray(S), jnp.asarray(b)


@pytest.mark.parametrize("mode,rng,n_chains,class_key", [
    ("EM", "host", 1, False),
    ("MC", "host", 1, False),
    ("MC", "fused", 1, False),
    ("MC", "fused_predraw", 1, False),
    ("MC", "fused", 2, False),
    ("MC", "fused", 1, True),
])
def test_m_step_key_rule(mode, rng, n_chains, class_key):
    """``stats.m_step`` is the posterior solve plus the EM mean or the
    MC draw, under the key each step drew with before the M-step was
    shared: the iteration key under rng='host'; under the counter modes
    chain c's key is fold_in(key, chain0 + c) in the vmapped form the
    CLS/SVR steps used, and the MLT class sweep passes its class key
    fold_in(key, y) and folds chain0 into that. Bitwise."""
    lam, jitter, chain0, K = 0.7, 1e-6, 3, 6
    key = jax.random.PRNGKey(11)
    if class_key:
        key = jax.random.fold_in(key, 2)   # class y = 2
    S, b = _spd_stats(n_chains, K, seed=4)

    def cls_chain_key(c):
        ids = jnp.asarray(chain0, jnp.int32) + jnp.arange(n_chains,
                                                          dtype=jnp.int32)
        return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, ids)[c]

    def parent_key(c):
        if rng == "host":
            return key
        if class_key:
            return jax.random.fold_in(key, chain0)
        return cls_chain_key(c)

    want = []
    for c in range(n_chains):
        L, mu = stats.posterior_params(S[c], b[:, c], lam, jitter=jitter)
        want.append(mu if mode == "EM"
                    else stats.draw_weight(parent_key(c), L, mu))
    multi = n_chains > 1
    got = stats.m_step(S if multi else S[0], b if multi else b[:, 0], key,
                       mode=mode, lam=lam, jitter=jitter, rng=rng,
                       chain0=chain0, n_chains=n_chains)
    want = jnp.stack(want) if multi else want[0]
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis-generated shapes."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _data(n, k, dtype):
    X = RNG.normal(size=(n, k)).astype(dtype)
    w = RNG.uniform(0.1, 2.0, size=(n,)).astype(np.float32)
    y = RNG.choice([-1.0, 1.0], size=(n,)).astype(np.float32)
    wv = RNG.normal(size=(k,)).astype(np.float32)
    return X, w, y, wv


@pytest.mark.parametrize("n,k", [(64, 32), (100, 37), (512, 256),
                                 (1000, 130), (9, 513)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_weighted_gram_matches_ref(n, k, dtype):
    X, w, _, _ = _data(n, k, np.float32)
    X = jnp.asarray(X, dtype)
    got = ops.weighted_gram(X, jnp.asarray(w), backend="interpret",
                            block_n=128, block_k=128)
    want = ref.weighted_gram(X, jnp.asarray(w))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("n,k", [(64, 32), (257, 100), (512, 256)])
def test_fused_estep_matches_ref(n, k):
    X, _, y, wv = _data(n, k, np.float32)
    m_p, g_p, b_p = ops.fused_estep(jnp.asarray(X), jnp.asarray(y),
                                    jnp.asarray(y), jnp.asarray(wv),
                                    eps=1e-6, backend="interpret",
                                    block_n=128)
    m_r, g_r, b_r = ref.fused_estep(jnp.asarray(X), jnp.asarray(y),
                                    jnp.asarray(y), jnp.asarray(wv), 1e-6)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_r), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_r), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(b_p), np.asarray(b_r), rtol=2e-3,
                               atol=2e-3 * max(1.0, np.abs(b_r).max()))


@pytest.mark.parametrize("n,k", [(64, 32), (100, 37), (512, 256),
                                 (1000, 130), (9, 513), (300, 600)])
def test_syrk_tri_matches_ref(n, k):
    """Triangle-blocked SYRK == dense oracle on non-block-aligned shapes
    (exercises the flattened-triangular-index block maps + the mirror)."""
    X, w, _, _ = _data(n, k, np.float32)
    got = ops.syrk_tri(jnp.asarray(X), jnp.asarray(w), backend="interpret",
                       block_n=128, block_k=128)
    want = ref.weighted_gram(jnp.asarray(X), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3 * np.abs(want).max())
    # off-diagonal blocks are mirrored (bit-exact); within diagonal
    # blocks (w*a)*b vs (w*b)*a rounding leaves fp32-epsilon asymmetry
    # (posterior_params symmetrizes before factorizing).
    S = np.asarray(got)
    np.testing.assert_allclose(S, S.T, rtol=1e-5,
                               atol=1e-5 * max(1.0, np.abs(S).max()))


def test_tri_ij_enumerates_lower_triangle():
    """The integer-arithmetic flattened-index mapping must agree with
    np.tril_indices (the lookup-table generator) for large grids."""
    from repro.kernels.syrk import _tri, tri_ij
    nb = 100
    i, j = tri_ij(jnp.arange(_tri(nb), dtype=jnp.int32))
    ii, jj = np.tril_indices(nb)
    np.testing.assert_array_equal(np.asarray(i), ii)
    np.testing.assert_array_equal(np.asarray(j), jj)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 200), st.integers(1, 70), st.integers(0, 2 ** 20))
def test_syrk_tri_hypothesis_shapes(n, k, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, k)).astype(np.float32)
    w = rng.uniform(0.01, 5.0, size=(n,)).astype(np.float32)
    got = ops.syrk_tri(jnp.asarray(X), jnp.asarray(w),
                       backend="interpret", block_n=64, block_k=128)
    want = (X * w[:, None]).T @ X
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3,
                               atol=1e-3 * max(1.0, np.abs(want).max()))


@pytest.mark.parametrize("n,k", [(64, 32), (257, 100), (300, 600)])
@pytest.mark.parametrize("masked", [False, True])
def test_fused_stats_matches_ref(n, k, masked):
    """One-sweep (margin, gamma, b, S) == split oracle, odd shapes."""
    X, _, y, wv = _data(n, k, np.float32)
    wm = (jnp.asarray((RNG.uniform(size=n) > 0.2).astype(np.float32))
          if masked else None)
    got = ops.fused_stats(jnp.asarray(X), jnp.asarray(y), jnp.asarray(y),
                          jnp.asarray(wv), wm, eps=1e-6,
                          backend="interpret", block_n=128)
    want = ref.fused_stats(jnp.asarray(X), jnp.asarray(y), jnp.asarray(y),
                           jnp.asarray(wv), wm, 1e-6)
    for g, w_, name in zip(got, want, ("margin", "gamma", "b", "S")):
        g, w_ = np.asarray(g), np.asarray(w_)
        np.testing.assert_allclose(
            g, w_, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(w_).max()),
            err_msg=name)


def test_fused_stats_large_k_falls_back_to_split():
    """K beyond the VMEM budget must route to the tiled split pair
    (never attempt the single-pass kernel) and still match the oracle."""
    n, k = 32, ops.FUSED_STATS_MAX_K + 128
    X, _, y, _ = _data(n, 8, np.float32)
    Xw = jnp.asarray(RNG.normal(size=(n, k)).astype(np.float32))
    wv = jnp.asarray(RNG.normal(size=k).astype(np.float32))
    assert not ops.fused_stats_fits(k)
    got = ops.fused_stats(Xw, jnp.asarray(y), jnp.asarray(y), wv,
                          eps=1e-6, backend="interpret")
    want = ref.fused_stats(Xw, jnp.asarray(y), jnp.asarray(y), wv,
                           None, 1e-6)
    for g, w_, name in zip(got, want, ("margin", "gamma", "b", "S")):
        g, w_ = np.asarray(g), np.asarray(w_)
        np.testing.assert_allclose(
            g, w_, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(w_).max()),
            err_msg=name)


def test_fused_stats_padded_rows_contribute_nothing():
    """Zero rows with rho=beta=0 must be exact no-ops for b and S."""
    X, _, y, wv = _data(96, 24, np.float32)
    Xp = np.concatenate([X, np.zeros((32, 24), np.float32)])
    yp = np.concatenate([y, np.zeros(32, np.float32)])
    a = ops.fused_stats(jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(yp),
                        jnp.asarray(wv), eps=1e-6, backend="interpret",
                        block_n=64)
    b = ref.fused_stats(jnp.asarray(X), jnp.asarray(y), jnp.asarray(y),
                        jnp.asarray(wv), None, 1e-6)
    np.testing.assert_allclose(np.asarray(a[2]), np.asarray(b[2]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(a[3]), np.asarray(b[3]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["ref", "interpret"] + (
    ["pallas"] if __import__("jax").default_backend() == "tpu" else []))
@pytest.mark.parametrize("mode", ["EM", "MC"])
@pytest.mark.parametrize("n_valid", [1, 77, 128])
def test_accumulate_stats_partial_final_chunk_parity(backend, mode,
                                                     n_valid):
    """The streaming driver's padding path: a partially-valid final
    chunk must contribute exactly the stats of its valid rows, on every
    kernel backend (the padded-row no-op is a *layout* convention — zero
    X-rows and targets — and each backend must preserve it bit-exactly,
    the easy-to-miss hole being a kernel that touches gamma=eps padding
    rows through a non-zeroed term)."""
    import jax
    from repro.core.linear import accumulate_stats

    n_chunk, k = 128, 24
    rng = np.random.default_rng(n_valid)
    Xc = np.zeros((n_chunk, k), np.float32)
    yc = np.zeros((n_chunk,), np.float32)
    Xc[:n_valid] = rng.normal(size=(n_valid, k)).astype(np.float32)
    yc[:n_valid] = rng.choice([-1.0, 1.0], n_valid)
    wv = rng.normal(size=k).astype(np.float32)
    key = jax.random.PRNGKey(3)

    _, _, S_pad, b_pad = accumulate_stats(
        jnp.asarray(Xc), jnp.asarray(yc), jnp.asarray(yc),
        jnp.asarray(wv), mode=mode, key=key, eps=1e-6, backend=backend,
        row0=0)
    # oracle: valid rows only, ref backend (rowwise MC keys make the
    # draw independent of the chunk's padded tail)
    _, _, S_ref, b_ref = accumulate_stats(
        jnp.asarray(Xc[:n_valid]), jnp.asarray(yc[:n_valid]),
        jnp.asarray(yc[:n_valid]), jnp.asarray(wv), mode=mode, key=key,
        eps=1e-6, backend="ref", row0=0)
    S_pad, b_pad = np.asarray(S_pad), np.asarray(b_pad)
    S_ref, b_ref = np.asarray(S_ref), np.asarray(b_ref)
    np.testing.assert_allclose(
        S_pad, S_ref, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(S_ref).max()))
    np.testing.assert_allclose(
        b_pad, b_ref, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(b_ref).max()))


# ------------------------------------------------- fused Nystrom kernels
def _nystrom_problem(n, d, m, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    landmarks = X[rng.choice(n, size=min(m, n), replace=False)]
    if m > n:  # oversize-m cases: tile rows
        landmarks = rng.normal(size=(m, d)).astype(np.float32)
    proj = (0.2 * rng.normal(size=(m, m))).astype(np.float32)
    mask = (rng.uniform(size=n) > 0.25).astype(np.float32)
    y = rng.choice([-1.0, 1.0], n).astype(np.float32) * mask
    return X, landmarks, proj, mask, y


@pytest.mark.parametrize("n,d,m", [(64, 16, 32), (100, 7, 37),
                                   (257, 33, 65), (9, 130, 5)])
@pytest.mark.parametrize("add_bias", [False, True])
def test_nystrom_phi_matches_ref(n, d, m, add_bias):
    """Fused featurizer == host oracle on odd (N, D, m) with masked
    padded rows and the mask-valued bias column."""
    X, L, proj, mask, _ = _nystrom_problem(n, d, m)
    kw = dict(sigma=1.3, kind="rbf", add_bias=add_bias)
    got = ops.nystrom_phi(jnp.asarray(X), jnp.asarray(L),
                          jnp.asarray(proj), jnp.asarray(mask),
                          backend="interpret", block_n=64, **kw)
    want = ref.nystrom_phi(jnp.asarray(X), jnp.asarray(L),
                           jnp.asarray(proj), jnp.asarray(mask),
                           1.3, "rbf", add_bias)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (n, m + int(add_bias))
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-3 * max(1.0, np.abs(want).max()))
    # masked rows must be EXACTLY zero — a zero X row is not a zero phi
    # row under rbf, so the kernel's explicit masking is load-bearing
    assert not np.any(got[mask == 0])


@pytest.mark.parametrize("n,d,m", [(64, 16, 32), (100, 7, 37),
                                   (257, 33, 65)])
@pytest.mark.parametrize("kind", ["rbf", "linear"])
def test_nystrom_fused_stats_matches_ref(n, d, m, kind):
    """One-pass featurize-and-accumulate == featurize-then-accumulate
    oracle: all four outputs, odd shapes, masked rows, phi-space bias."""
    X, L, proj, mask, y = _nystrom_problem(n, d, m, seed=m)
    wv = np.random.default_rng(1).normal(size=m + 1).astype(np.float32)
    kw = dict(sigma=0.9, kind=kind, add_bias=True)
    got = ops.nystrom_fused_stats(
        jnp.asarray(X), jnp.asarray(L), jnp.asarray(proj), jnp.asarray(y),
        jnp.asarray(y), jnp.asarray(wv), jnp.asarray(mask), eps=1e-6,
        backend="interpret", block_n=64, **kw)
    want = ref.nystrom_fused_stats(
        jnp.asarray(X), jnp.asarray(L), jnp.asarray(proj), jnp.asarray(y),
        jnp.asarray(y), jnp.asarray(wv), jnp.asarray(mask), 0.9, kind,
        True, 1e-6)
    for g, w_, name in zip(got, want, ("margin", "gamma", "b", "S")):
        g, w_ = np.asarray(g), np.asarray(w_)
        np.testing.assert_allclose(
            g, w_, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(w_).max()),
            err_msg=name)


def test_nystrom_fused_masked_rows_contribute_nothing():
    """A block whose tail is masked must yield the stats of its valid
    rows only — the streaming driver's padded-tail path."""
    n, d, m, n_valid = 96, 12, 24, 61
    X, L, proj, _, _ = _nystrom_problem(n, d, m, seed=3)
    rng = np.random.default_rng(4)
    y = np.zeros(n, np.float32)
    y[:n_valid] = rng.choice([-1.0, 1.0], n_valid)
    mask = (np.arange(n) < n_valid).astype(np.float32)
    wv = rng.normal(size=m + 1).astype(np.float32)
    kw = dict(sigma=1.1, kind="rbf", add_bias=True, eps=1e-6)
    a = ops.nystrom_fused_stats(
        jnp.asarray(X), jnp.asarray(L), jnp.asarray(proj), jnp.asarray(y),
        jnp.asarray(y), jnp.asarray(wv), jnp.asarray(mask),
        backend="interpret", block_n=32, **kw)
    b = ops.nystrom_fused_stats(
        jnp.asarray(X[:n_valid]), jnp.asarray(L), jnp.asarray(proj),
        jnp.asarray(y[:n_valid]), jnp.asarray(y[:n_valid]),
        jnp.asarray(wv), jnp.asarray(np.ones(n_valid, np.float32)),
        backend="ref", **kw)
    np.testing.assert_allclose(np.asarray(a[2]), np.asarray(b[2]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(a[3]), np.asarray(b[3]),
                               rtol=1e-3,
                               atol=1e-3 * np.abs(np.asarray(b[3])).max())


def test_nystrom_fused_oversize_m_falls_back():
    """Past the VMEM budget the dispatch must route to
    featurize-then-accumulate (never attempt the one-pass kernel) and
    still match the oracle."""
    n, d, m = 48, 6, ops.NYSTROM_FUSED_MAX_M + 8
    assert not ops.nystrom_fused_fits(m, d)
    X, L, proj, mask, y = _nystrom_problem(n, d, m, seed=5)
    wv = np.random.default_rng(2).normal(size=m).astype(np.float32)
    got = ops.nystrom_fused_stats(
        jnp.asarray(X), jnp.asarray(L), jnp.asarray(proj), jnp.asarray(y),
        jnp.asarray(y), jnp.asarray(wv), jnp.asarray(mask),
        sigma=1.0, kind="rbf", add_bias=False, eps=1e-6,
        backend="interpret")
    want = ref.nystrom_fused_stats(
        jnp.asarray(X), jnp.asarray(L), jnp.asarray(proj), jnp.asarray(y),
        jnp.asarray(y), jnp.asarray(wv), jnp.asarray(mask), 1.0, "rbf",
        False, 1e-6)
    for g, w_, name in zip(got, want, ("margin", "gamma", "b", "S")):
        g, w_ = np.asarray(g), np.asarray(w_)
        np.testing.assert_allclose(
            g, w_, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(w_).max()),
            err_msg=name)


def test_nystrom_fused_fits_accounting():
    """The byte-budget check: paper-regime shapes fit; the landmark cap
    and a pathologically wide D do not."""
    assert ops.nystrom_fused_fits(256, 784)
    assert ops.nystrom_fused_fits(1024, 256)
    assert not ops.nystrom_fused_fits(ops.NYSTROM_FUSED_MAX_M + 1, 16)
    assert not ops.nystrom_fused_fits(1024, 8192)


def _dot_precisions(jaxpr):
    """The precision of every dot_general in ``jaxpr`` and in the
    jaxprs its equations hold (jit bodies, Pallas kernel bodies)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    found += _dot_precisions(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    found += _dot_precisions(sub)
    return found


# Where the Nystrom statistic falls back to featurize-then-accumulate:
# the LIN statistic kernel on phi, its split pair (E-step + SYRK), or,
# past the landmark cap, the featurizing oracle too.
FALLBACKS = {"kernel": (24, False), "split": (24, True),
             "past_cap": (ops.NYSTROM_FUSED_MAX_M + 8, True)}


def _forced_fallback(monkeypatch, route):
    """(n, d, m) of a problem whose Nystrom statistic takes ``route``."""
    m, split = FALLBACKS[route]
    monkeypatch.setattr(ops, "nystrom_fused_fits", lambda *a, **k: False)
    if split:
        monkeypatch.setattr(ops, "fused_stats_fits", lambda *a, **k: False)
    return 48, 6, m


@pytest.mark.parametrize("route", sorted(FALLBACKS))
def test_nystrom_fallback_runs_every_dot_at_highest(monkeypatch, route):
    """The fallback computes the statistic the fused kernel does, in
    float32: every dot it traces, the featurization's and the LIN
    statistic's, asks for HIGHEST (on the TPU a dot at the default is
    one bf16 pass, which gives another model)."""
    import jax

    n, d, m = _forced_fallback(monkeypatch, route)
    X, L, proj, mask, y = _nystrom_problem(n, d, m, seed=6)
    wv = np.random.default_rng(3).normal(size=m + 1).astype(np.float32)

    def stats(*a):
        return ops.nystrom_fused_stats(*a, sigma=1.0, add_bias=True,
                                       eps=1e-6, backend="interpret")
    args = [jnp.asarray(a) for a in (X, L, proj, y, y, wv, mask)]
    found = _dot_precisions(jax.make_jaxpr(stats)(*args).jaxpr)
    highest = (jax.lax.Precision.HIGHEST,) * 2
    assert len(found) >= 4 and set(found) == {highest}, found
    # the LIN statistic keeps the TPU's default
    lin = jax.make_jaxpr(lambda *a: ops.fused_stats(
        *a, backend="interpret"))(args[0], args[3], args[4],
                                  jnp.asarray(wv[:d]), args[6])
    assert set(_dot_precisions(lin.jaxpr)) == {None}


@pytest.mark.parametrize("route", ["kernel", "split"])
def test_nystrom_fallback_matches_fused_kernel(monkeypatch, route):
    """Forced onto the fallback, the statistic equals the fused kernel's
    at float32 tolerance: all four outputs, masked rows, phi bias."""
    n, d, m = 96, 12, FALLBACKS[route][0]
    X, L, proj, mask, y = _nystrom_problem(n, d, m, seed=8)
    wv = np.random.default_rng(5).normal(size=m + 1).astype(np.float32)
    args = [jnp.asarray(a) for a in (X, L, proj, y, y, wv, mask)]
    kw = dict(sigma=1.2, add_bias=True, eps=1e-6, backend="interpret",
              block_n=32)
    assert ops.nystrom_fused_fits(m, d)
    fused = ops.nystrom_fused_stats(*args, **kw)
    _forced_fallback(monkeypatch, route)
    got = ops.nystrom_fused_stats(*args, **kw)
    for g, w_, name in zip(got, fused, ("margin", "gamma", "b", "S")):
        g, w_ = np.asarray(g), np.asarray(w_)
        np.testing.assert_allclose(
            g, w_, rtol=1e-5, atol=1e-5 * max(1.0, np.abs(w_).max()),
            err_msg=name)


def test_nystrom_vmem_limit_is_the_least_that_holds_the_cap():
    """The Nystrom kernels' raised scoped-VMEM limit is the least whole
    8 MiB that holds the accounted working set at the landmark cap and
    D = 256, where the budget stands."""
    need = 4 * ops._nystrom_vmem_words(ops.NYSTROM_FUSED_MAX_M, 256, True,
                                       256, True)
    limit = ops._NYSTROM_VMEM_BUDGET
    assert limit % (8 * 2 ** 20) == 0
    assert need <= limit < need + 8 * 2 ** 20


@pytest.mark.parametrize("n1,n2,k,sigma", [(64, 64, 16, 1.0),
                                           (100, 37, 8, 0.5),
                                           (129, 257, 33, 2.0)])
def test_rbf_gram_matches_ref(n1, n2, k, sigma):
    X1 = RNG.normal(size=(n1, k)).astype(np.float32)
    X2 = RNG.normal(size=(n2, k)).astype(np.float32)
    got = ops.rbf_gram(jnp.asarray(X1), jnp.asarray(X2), sigma=sigma,
                       backend="interpret", block_n=64)
    want = ref.rbf_gram(jnp.asarray(X1), jnp.asarray(X2), sigma)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_rbf_gram_diagonal_is_one():
    X = RNG.normal(size=(50, 7)).astype(np.float32)
    G = np.asarray(ops.rbf_gram(jnp.asarray(X), jnp.asarray(X), sigma=1.3,
                                backend="interpret", block_n=64))
    np.testing.assert_allclose(np.diag(G), 1.0, atol=1e-5)
    np.testing.assert_allclose(G, G.T, atol=1e-5)
    assert G.max() <= 1.0 + 1e-5


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 200), st.integers(1, 70), st.integers(0, 2 ** 20))
def test_weighted_gram_hypothesis_shapes(n, k, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, k)).astype(np.float32)
    w = rng.uniform(0.01, 5.0, size=(n,)).astype(np.float32)
    got = ops.weighted_gram(jnp.asarray(X), jnp.asarray(w),
                            backend="interpret", block_n=64, block_k=128)
    want = (X * w[:, None]).T @ X
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3,
                               atol=1e-3 * max(1.0, np.abs(want).max()))


def test_weighted_gram_psd_property():
    """S = X^T diag(w) X with w > 0 must be PSD (solver precondition)."""
    X, w, _, _ = _data(300, 40, np.float32)
    S = np.asarray(ops.weighted_gram(jnp.asarray(X), jnp.asarray(w),
                                     backend="interpret"))
    eig = np.linalg.eigvalsh(S.astype(np.float64))
    assert eig.min() > -1e-3 * max(1.0, eig.max())

"""Single-stream Gibbs: the epilogue-parameterized fused statistics
(DESIGN.md §Perf/MC-SVR).

Layers, strongest first:

  1. BITWISE draw parity: the pre-drawn (nu, u) noise + in-kernel IG
     transform must reproduce the ``gamma_mc_rowwise`` / split-key
     oracles bit for bit (given the same residuals) — on odd masked
     shapes, under any chunking, and through the fused chunk-callables.
  2. Kernel parity: the mc_hinge / em_svr / mc_svr epilogues inside the
     Pallas kernels (interpret mode) match the jnp oracles. At w = 0
     the margins are exactly zero on both sides and the (nu, u) noise
     operands are bitwise-shared, so the MC draws must agree to FMA-
     contraction tolerance with ZERO accept-reject flips (the compiler
     may contract the transform's multiply-adds inside the kernel, so
     in-kernel arithmetic is lsb-close rather than bit-equal — the
     bitwise guarantee lives on the dispatch/ref path, layer 1); at
     random w the margin's own lsb noise can additionally flip the IG
     accept-reject branch on near-hinge rows (the documented discrete
     channel), so those checks assert the kernel outputs are
     *self-consistent* with the kernel's own emitted draws.
  3. Invariance: mesh layout must not change the sampled chain for the
     fused MC CLS/SVR paths (subprocess, multi-device CPU).
  4. Regression: the k_shard MC branch casts targets to f32 before the
     b statistic (a wider dtype would upcast the whole posterior solve).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import augment
from repro.core.linear import accumulate_stats
from repro.core.svr import svr_local_stats
from repro.kernels import epilogues, ops, ref

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
RNG = np.random.default_rng(0)


def _run_with_devices(code: str, n_devices: int = 8, timeout: int = 600,
                      extra_env: dict | None = None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


# ------------------------------------------------ 1. bitwise draw parity
@pytest.mark.parametrize("n,row0", [(1, 0), (77, 13), (256, 0), (301, 99)])
def test_predraw_transform_matches_rowwise_oracle_bitwise(n, row0):
    """draw_ig_noise + ig_gamma_from_noise == gamma_mc_rowwise, bit for
    bit: the vectorized pre-draw path is the same PRNG tree and the
    same arithmetic as the vmapped oracle."""
    key = jax.random.PRNGKey(n + row0)
    res = jnp.asarray(RNG.normal(size=n).astype(np.float32) * 3.0)
    want = augment.gamma_mc_rowwise(key, res, 1e-6, row0)
    nu, u = augment.draw_ig_noise(key, n, row0)
    got = epilogues.ig_gamma_from_noise(res, nu, u, 1e-6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_predraw_noise_is_chunk_slice_invariant():
    """The (nu, u) arrays for a chunk are literally slices of the full
    arrays — global-row keying makes chunking invisible, bitwise."""
    key = jax.random.PRNGKey(3)
    nu, u = augment.draw_ig_noise(key, 230, 0)
    for i0, i1 in ((0, 64), (64, 193), (193, 230)):
        nu_c, u_c = augment.draw_ig_noise(key, i1 - i0, i0)
        np.testing.assert_array_equal(np.asarray(nu_c),
                                      np.asarray(nu)[i0:i1])
        np.testing.assert_array_equal(np.asarray(u_c),
                                      np.asarray(u)[i0:i1])


@pytest.mark.parametrize("n,k,n_valid", [(100, 7, 100), (128, 24, 77),
                                         (9, 33, 9)])
def test_fused_mc_cls_draws_bitwise_vs_oracle(n, k, n_valid):
    """The fused chunk-callable's MC gamma (ref backend) equals the
    gamma_mc_rowwise oracle at the same margins, bitwise — including
    padded tails (zero rows draw too, they just contribute nothing)."""
    rng = np.random.default_rng(n * k)
    X = np.zeros((n, k), np.float32)
    y = np.zeros((n,), np.float32)
    X[:n_valid] = rng.normal(size=(n_valid, k)).astype(np.float32)
    y[:n_valid] = rng.choice([-1.0, 1.0], n_valid)
    w = rng.normal(size=k).astype(np.float32)
    key = jax.random.PRNGKey(11)
    row0 = 37
    margin = jnp.asarray(X) @ jnp.asarray(w)
    want = augment.gamma_mc_rowwise(key, jnp.asarray(y) - margin, 1e-6,
                                    row0)
    m, gamma, S, b = accumulate_stats(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(y), jnp.asarray(w),
        mode="MC", key=key, eps=1e-6, backend="ref", row0=row0)
    np.testing.assert_array_equal(np.asarray(gamma), np.asarray(want))
    # and the statistics are the split computation's, to fp32 tolerance
    g = np.asarray(want)
    S_want = (X * (1.0 / g)[:, None]).T @ X
    b_want = X.T @ (y / g + y)
    np.testing.assert_allclose(np.asarray(S), S_want, rtol=1e-4,
                               atol=1e-4 * max(1.0, np.abs(S_want).max()))
    np.testing.assert_allclose(np.asarray(b), b_want, rtol=1e-4,
                               atol=1e-4 * max(1.0, np.abs(b_want).max()))


def test_fused_svr_draws_bitwise_vs_split_key_oracle():
    """SVR's double mixture: fused gamma/omega (ref backend) equal the
    pre-fusion split-key rowwise oracles bitwise, on a masked odd
    shape; the combined statistics match the split computation."""
    rng = np.random.default_rng(5)
    n, k, eps_ins, row0 = 203, 9, 0.2, 51
    X = rng.normal(size=(n, k)).astype(np.float32)
    X[180:] = 0.0                                   # padded tail
    y = (X @ rng.normal(size=k)).astype(np.float32)
    w = rng.normal(size=k).astype(np.float32)
    key = jax.random.PRNGKey(19)
    k_lo, k_hi = jax.random.split(key)
    res = jnp.asarray(y) - jnp.asarray(X) @ jnp.asarray(w)
    g_want = augment.gamma_mc_rowwise(k_lo, res - eps_ins, 1e-6, row0)
    o_want = augment.gamma_mc_rowwise(k_hi, res + eps_ins, 1e-6, row0)
    pred, gamma, omega, S, b = svr_local_stats(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), mode="MC",
        key=key, eps=1e-6, eps_ins=eps_ins, backend="ref", row0=row0)
    np.testing.assert_array_equal(np.asarray(gamma), np.asarray(g_want))
    np.testing.assert_array_equal(np.asarray(omega), np.asarray(o_want))
    g, o = np.asarray(g_want), np.asarray(o_want)
    S_want = (X * (1.0 / g + 1.0 / o)[:, None]).T @ X
    b_want = X.T @ ((y - eps_ins) / g + (y + eps_ins) / o)
    np.testing.assert_allclose(np.asarray(S), S_want, rtol=1e-4,
                               atol=1e-4 * max(1.0, np.abs(S_want).max()))
    np.testing.assert_allclose(np.asarray(b), b_want, rtol=1e-4,
                               atol=1e-4 * max(1.0, np.abs(b_want).max()))


def test_fused_svr_em_matches_pre_fusion_split():
    """EM-SVR single-stream == the pre-fusion 3-stream computation."""
    rng = np.random.default_rng(7)
    n, k, eps_ins = 150, 11, 0.3
    X = rng.normal(size=(n, k)).astype(np.float32)
    y = (X @ rng.normal(size=k)).astype(np.float32)
    w = rng.normal(size=k).astype(np.float32)
    pred, gamma, omega, S, b = svr_local_stats(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), mode="EM",
        key=None, eps=1e-6, eps_ins=eps_ins, backend="ref", row0=0)
    # residual from the RETURNED margin (a numpy f32 matmul reassociates
    # differently at the lsb — the E-step itself is what's under test)
    res = y - np.asarray(pred)
    g = np.maximum(np.abs(res - eps_ins), 1e-6)
    o = np.maximum(np.abs(res + eps_ins), 1e-6)
    np.testing.assert_array_equal(np.asarray(gamma), g)
    np.testing.assert_array_equal(np.asarray(omega), o)
    S_want = (X * (1.0 / g + 1.0 / o)[:, None]).T @ X
    b_want = X.T @ ((y - eps_ins) / g + (y + eps_ins) / o)
    np.testing.assert_allclose(np.asarray(S), S_want, rtol=1e-5,
                               atol=1e-5 * np.abs(S_want).max())
    np.testing.assert_allclose(np.asarray(b), b_want, rtol=1e-5,
                               atol=1e-5 * max(1.0, np.abs(b_want).max()))


# --------------------------------------------------- 2. kernel parity
@pytest.mark.parametrize("epilogue", ["mc_hinge", "em_svr", "mc_svr"])
@pytest.mark.parametrize("n,k", [(64, 32), (257, 100), (9, 50)])
def test_epilogue_kernel_interpret_matches_ref_at_zero_w(epilogue, n, k):
    """At w = 0 the margin is exactly zero in kernel and oracle alike
    and the (nu, u) noise is shared, so every epilogue output —
    including the MC draws — must agree to FMA-contraction tolerance
    with no accept-reject flips between the interpret-mode Pallas
    kernel and the jnp oracle, odd masked shapes included."""
    rng = np.random.default_rng(n + k)
    X = rng.normal(size=(n, k)).astype(np.float32)
    # Keep residuals off the hinge knee: at |rho| ~ 1e-3 the IG mean
    # mu = 1/|rho| ~ 1e3 and the MSH transform x ~ 1/y cancels
    # catastrophically (relative error ~ mu^2 y^2 eps_f32), swamping
    # the rounding-difference signal this test is after. |rho +-
    # eps_ins| >= 0.15 bounds mu <= ~7 on both SVR mixtures.
    rho = (np.sign(rng.normal(size=n)) *
           (0.3 + np.abs(rng.normal(size=n)))).astype(np.float32)
    beta = rng.choice([-1.0, 1.0], n).astype(np.float32)
    wm = (rng.uniform(size=n) > 0.2).astype(np.float32)
    w0 = np.zeros(k, np.float32)
    key = jax.random.PRNGKey(k)
    n_noise = epilogues.noise_arity(epilogue)
    noise = None
    if n_noise:
        k_lo, k_hi = jax.random.split(key)
        noise = augment.draw_ig_noise(k_lo, n, 3)
        if n_noise == 4:
            noise = (*noise, *augment.draw_ig_noise(k_hi, n, 3))
    kw = dict(epilogue=epilogue, eps=1e-4, eps_ins=0.15)
    got = ops.fused_stats(jnp.asarray(X), jnp.asarray(rho),
                          jnp.asarray(beta), jnp.asarray(w0),
                          jnp.asarray(wm), noise, backend="interpret",
                          block_n=64, **kw)
    want = ref.fused_stats(jnp.asarray(X), jnp.asarray(rho),
                           jnp.asarray(beta), jnp.asarray(w0),
                           jnp.asarray(wm), 1e-4, epilogue=epilogue,
                           noise=noise, eps_ins=0.15)
    names = (("margin", "gamma", "b", "S") if n_noise != 4 and
             epilogue.endswith("hinge") else
             ("margin", "gamma", "omega", "b", "S"))
    for g, w_, name in zip(got, want, names):
        g, w_ = np.asarray(g), np.asarray(w_)
        if name in ("gamma", "omega"):
            # rtol far below any accept-reject flip's O(1) jump but
            # above the transform's cancellation-amplified lsb noise
            # (x = mu(1 + y/2 - sqrt(...)) loses ~mu in relative
            # precision near the hinge knee): draws agree, no flips.
            np.testing.assert_allclose(g, w_, rtol=1e-2, err_msg=name)
        else:
            np.testing.assert_allclose(
                g, w_, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(w_).max()),
                err_msg=name)


@pytest.mark.parametrize("epilogue", ["mc_hinge", "mc_svr"])
def test_epilogue_kernel_self_consistent_at_random_w(epilogue):
    """At random w the kernel margin's lsb noise may flip IG
    accept-reject branches vs the oracle; the kernel must still be
    SELF-consistent: S and b recomputed from its own emitted margins
    and draws match its S and b outputs."""
    rng = np.random.default_rng(23)
    n, k, eps_ins = 200, 17, 0.15
    X = rng.normal(size=(n, k)).astype(np.float32)
    rho = rng.normal(size=n).astype(np.float32)
    beta = rng.choice([-1.0, 1.0], n).astype(np.float32)
    w = rng.normal(size=k).astype(np.float32)
    key = jax.random.PRNGKey(2)
    k_lo, k_hi = jax.random.split(key)
    noise = augment.draw_ig_noise(k_lo, n, 0)
    if epilogue == "mc_svr":
        noise = (*noise, *augment.draw_ig_noise(k_hi, n, 0))
    out = ops.fused_stats(jnp.asarray(X), jnp.asarray(rho),
                          jnp.asarray(beta), jnp.asarray(w), None, noise,
                          epilogue=epilogue, eps=1e-4, eps_ins=eps_ins,
                          backend="interpret", block_n=64)
    if epilogue == "mc_hinge":
        margin, gamma, b, S = (np.asarray(v) for v in out)
        weight = 1.0 / gamma
        coef = rho / gamma + beta
    else:
        margin, gamma, omega, b, S = (np.asarray(v) for v in out)
        weight = 1.0 / gamma + 1.0 / omega
        coef = (rho - eps_ins) / gamma + (rho + eps_ins) / omega
    S_want = (X * weight[:, None]).T @ X
    b_want = X.T @ coef
    np.testing.assert_allclose(S, S_want, rtol=2e-3,
                               atol=2e-3 * np.abs(S_want).max())
    np.testing.assert_allclose(b, b_want, rtol=2e-3,
                               atol=2e-3 * max(1.0, np.abs(b_want).max()))


@pytest.mark.parametrize("epilogue", ["mc_hinge", "em_svr", "mc_svr"])
def test_nystrom_epilogue_kernel_interpret_matches_ref_at_zero_w(epilogue):
    """Phi-space flavor of the zero-w bitwise check: the fused Nystrom
    kernel under the MC/SVR epilogues, masked rows and phi bias on."""
    rng = np.random.default_rng(31)
    n, d, m = 100, 7, 37
    X = rng.normal(size=(n, d)).astype(np.float32)
    L = X[rng.choice(n, m, replace=False)]
    proj = (0.2 * rng.normal(size=(m, m))).astype(np.float32)
    mask = (rng.uniform(size=n) > 0.25).astype(np.float32)
    # off the hinge knee on every row (incl. masked ones, whose draws
    # are compared too even though their statistics are no-ops) — see
    # the X-space test for the mu-amplification rationale
    y = (np.sign(rng.normal(size=n)) *
         (0.3 + np.abs(rng.normal(size=n)))).astype(np.float32)
    w0 = np.zeros(m + 1, np.float32)
    key = jax.random.PRNGKey(5)
    n_noise = epilogues.noise_arity(epilogue)
    noise = None
    if n_noise:
        k_lo, k_hi = jax.random.split(key)
        noise = augment.draw_ig_noise(k_lo, n, 0)
        if n_noise == 4:
            noise = (*noise, *augment.draw_ig_noise(k_hi, n, 0))
    kw = dict(sigma=1.3, kind="rbf", add_bias=True, epilogue=epilogue,
              eps=1e-4, eps_ins=0.1)
    got = ops.nystrom_fused_stats(
        jnp.asarray(X), jnp.asarray(L), jnp.asarray(proj), jnp.asarray(y),
        jnp.asarray(y), jnp.asarray(w0), jnp.asarray(mask), noise,
        backend="interpret", block_n=32, **kw)
    want = ref.nystrom_fused_stats(
        jnp.asarray(X), jnp.asarray(L), jnp.asarray(proj), jnp.asarray(y),
        jnp.asarray(y), jnp.asarray(w0), jnp.asarray(mask), 1.3, "rbf",
        True, 1e-4, epilogue=epilogue, noise=noise, eps_ins=0.1)
    names = (("margin", "gamma", "b", "S") if epilogue == "mc_hinge"
             else ("margin", "gamma", "omega", "b", "S"))
    for g, w_, name in zip(got, want, names):
        g, w_ = np.asarray(g), np.asarray(w_)
        if name in ("gamma", "omega"):
            np.testing.assert_allclose(g, w_, rtol=1e-2, err_msg=name)
        else:
            np.testing.assert_allclose(
                g, w_, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(w_).max()),
                err_msg=name)


def test_mc_epilogue_large_k_falls_back_to_split():
    """K beyond the VMEM cap must route the MC epilogue to the split
    fallback (jnp E-step + K-tiled SYRK) and still match the oracle —
    bitwise on the draws (the fallback margin IS the oracle margin)."""
    n, k = 24, ops.FUSED_STATS_MAX_K + 128
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    y = jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32))
    wv = jnp.asarray(rng.normal(size=k).astype(np.float32))
    noise = augment.draw_ig_noise(jax.random.PRNGKey(0), n, 0)
    assert not ops.fused_stats_fits(k, epilogue="mc_hinge")
    got = ops.fused_stats(X, y, y, wv, None, noise, epilogue="mc_hinge",
                          eps=1e-6, backend="interpret")
    want = ref.fused_stats(X, y, y, wv, None, 1e-6, epilogue="mc_hinge",
                           noise=noise)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    for g, w_, name in zip(got, want, ("margin", "gamma", "b", "S")):
        g, w_ = np.asarray(g), np.asarray(w_)
        np.testing.assert_allclose(
            g, w_, rtol=2e-3, atol=2e-3 * max(1.0, np.abs(w_).max()),
            err_msg=name)


def test_nystrom_fused_fits_is_epilogue_aware():
    """The VMEM accounting must accept the epilogue and never report a
    LARGER working set for a cheaper epilogue."""
    for m, d in ((256, 784), (1024, 256)):
        em = ops._nystrom_vmem_words(m, d, True, 256, True, "em_hinge")
        svr = ops._nystrom_vmem_words(m, d, True, 256, True, "mc_svr")
        # mc_svr carries 4 noise + 1 extra aug per-row vectors over em,
        # each a (256, 1) block padded to 128 lanes and double-buffered
        assert svr == em + 2 * 5 * 128 * 256, (m, d)
        assert ops.nystrom_fused_fits(m, d, epilogue="em_hinge")
    assert not ops.nystrom_fused_fits(ops.NYSTROM_FUSED_MAX_M + 1, 16,
                                      epilogue="mc_svr")


# ------------------------------------------------------- 3. invariance
def test_mc_cls_svr_chain_is_mesh_layout_invariant():
    """LIN MC fused paths: a mesh fit draws the SAME gamma (and omega)
    chain as the single-device one — rowwise keying + shard row offsets
    make the layout invisible. First iteration: margins are exactly 0
    at w = 0, so the draws are bitwise-identical iff keying is
    layout-invariant (the means differ only by psum ordering)."""
    _run_with_devices("""
import numpy as np, jax
from repro.core import PEMSVM, SVMConfig
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
N, K = 1024, 16
X = rng.normal(size=(N, K)).astype(np.float32)
w_true = rng.normal(size=K)
y = np.where(X @ w_true + 0.3 * rng.normal(size=N) > 0, 1.0, -1.0)
cfg = SVMConfig(algorithm="MC", burnin=0, max_iters=1, min_iters=1)
r1 = PEMSVM(cfg).fit(X, y)
r8 = PEMSVM(cfg, mesh=mesh).fit(X, y)
np.testing.assert_allclose(r8.aux_history["gamma_mean"][0],
                           r1.aux_history["gamma_mean"][0], rtol=1e-5)
np.testing.assert_allclose(r8.objective[0], r1.objective[0], rtol=1e-4)
ys = (X @ w_true).astype(np.float32)
cfg = SVMConfig(algorithm="MC", task="SVR", eps_ins=0.3, burnin=0,
                max_iters=1, min_iters=1)
s1 = PEMSVM(cfg).fit(X, ys)
s8 = PEMSVM(cfg, mesh=mesh).fit(X, ys)
for kk in ("gamma_mean", "omega_mean"):
    np.testing.assert_allclose(s8.aux_history[kk][0],
                               s1.aux_history[kk][0], rtol=1e-5)
np.testing.assert_allclose(s8.objective[0], s1.objective[0], rtol=1e-4)
print("mesh layout invariance OK")
""")


# -------------------------------------------------------- 4. regression
def test_k_shard_mc_casts_targets_to_f32():
    """Regression: the k_shard MC branch must cast targets before the
    b statistic — with x64 enabled and f64 targets, the pre-fix
    ``y / gamma + y`` upcast b (and then the whole posterior solve and
    the returned weights) to float64."""
    _run_with_devices("""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import linear
from repro.core.linear import SVMData
mesh = jax.make_mesh((2,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
N, K = 64, 8
X = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))
y = jnp.asarray(rng.choice([-1.0, 1.0], N))            # float64 under x64
mask = jnp.ones((N,), jnp.float32)
assert y.dtype == jnp.float64, y.dtype
def step(X, y, mask, w, key):
    return linear.cls_step(SVMData(X, y, mask), w, key, mode="MC",
                           axes=(), k_shard_axis="model", backend="ref")
w0 = jnp.zeros((K,), jnp.float32)
key = jax.random.PRNGKey(0)
rep = (P(None, None), P(None), P(None), P(None), P(None))
w_new, aux = jax.jit(shard_map(
    step, mesh=mesh, in_specs=rep,
    out_specs=(P(None), {k: P() for k in ("objective", "gamma_mean",
                                          "n_sv")}),
    check_vma=False))(X, y, mask, w0, key)
assert w_new.dtype == jnp.float32, w_new.dtype
# and the statistic agrees with the fused (casting) path
w_ref, _ = linear.cls_step(SVMData(X, y.astype(jnp.float32), mask), w0,
                           key, mode="MC", axes=(), backend="ref")
rel = np.abs(np.asarray(w_new) - np.asarray(w_ref)).max() / max(
    1e-9, np.abs(np.asarray(w_ref)).max())
assert rel < 1e-4, rel
print("k_shard f32 cast OK")
""", n_devices=2)

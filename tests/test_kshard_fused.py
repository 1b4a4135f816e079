"""Column-tiled fused statistics: the single-stream 2-D (data x model)
``k_shard_axis`` path (ISSUE 5).

Layers under test:

  1. Kernel: the column-windowed ``fused_stats`` /
     ``nystrom_fused_stats`` equal the full kernel's column slice on
     odd masked shapes, across ref and interpret backends, for every
     epilogue, at aligned AND unaligned (traced) window starts.
  2. Draws: the windowed MC statistic's gamma draws are BITWISE the
     ``gamma_mc_rowwise`` oracle's on the dispatch path — margin/gamma
     stay full-width, so windowing cannot perturb the chain.
  3. Invariance (subprocess, multi-device CPU): on a 2-D (data x
     model) mesh, k_shard fits match the replicated single-device fits
     — exactly at iteration one, within the documented fp32 windows on
     short chains — for CLS/SVR/MLT, EM and MC, and the MC chain is
     the SAME chain (rowwise-keyed draws; the SVR accept-reject fork
     channel gets the streaming tests' loose long-chain band).
  4. Composition: k_shard x phi_spec (the formerly NotImplementedError
     pair) — whole-fit EM parity <= 1e-4 vs the replicated Nystrom
     path.
  5. Padding: ``pad_features_to`` + ``SVMConfig.pad_features`` make an
     indivisible K fit under k_shard with unchanged predictions;
     ``_k_block`` still hard-errors and names the helper.
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
from repro.data.pipeline import pad_features_to
from repro.kernels import ops

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

WINDOWS = ((0, 29), (5, 7), (22, 7), (13, 1), (0, 1))


def _problem(n=37, k=29, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    y = jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32))
    ys = jnp.asarray((np.asarray(X) @ rng.normal(size=k))
                     .astype(np.float32))
    w = jnp.asarray(rng.normal(size=k).astype(np.float32))
    wm = jnp.asarray((rng.random(n) > 0.2).astype(np.float32))
    return X, y, ys, w, wm


# ------------------------------------------------ 1. windowed == slice
@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("epilogue", ["em_hinge", "mc_hinge", "em_svr",
                                      "mc_svr"])
def test_windowed_equals_full_column_slice(backend, epilogue):
    X, y, ys, w, wm = _problem()
    key = jax.random.PRNGKey(3)
    svr = epilogue.endswith("svr")
    rho = ys if svr else y
    beta = jnp.zeros_like(y) if svr else y
    if epilogue == "mc_hinge":
        noise = augment.draw_ig_noise(key, X.shape[0], 11)
    elif epilogue == "mc_svr":
        k_lo, k_hi = jax.random.split(key)
        noise = (*augment.draw_ig_noise(k_lo, X.shape[0], 11),
                 *augment.draw_ig_noise(k_hi, X.shape[0], 11))
    else:
        noise = None
    kw = dict(epilogue=epilogue, eps=1e-4, eps_ins=0.2, backend=backend)
    full = ops.fused_stats(X, rho, beta, w, wm, noise, **kw)
    for start, blk in WINDOWS:
        # traced start: the in-mesh reality (axis_index * blk)
        win = ops.fused_stats(X, rho, beta, w, wm, noise,
                              col_window=(jnp.int32(start), blk), **kw)
        np.testing.assert_allclose(
            np.asarray(win[-1]),
            np.asarray(full[-1])[:, start:start + blk],
            rtol=2e-6, atol=2e-6, err_msg=f"S window ({start}, {blk})")
        # margin / aug / b are full-width and UNCHANGED by windowing
        for a, b_ in zip(win[:-1], full[:-1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_nystrom_windowed_equals_full_phi_column_slice(backend):
    rng = np.random.default_rng(1)
    n, m, d = 37, 13, 9
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    L = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    proj = jnp.asarray(rng.normal(size=(m, m)).astype(np.float32))
    y = jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32))
    wm = jnp.asarray((rng.random(n) > 0.3).astype(np.float32))
    wphi = jnp.asarray(rng.normal(size=m + 1).astype(np.float32))
    noise = augment.draw_ig_noise(jax.random.PRNGKey(5), n, 3)
    for epilogue, nz in (("em_hinge", None), ("mc_hinge", noise)):
        kw = dict(sigma=0.9, add_bias=True, epilogue=epilogue, eps=1e-4,
                  backend=backend)
        full = ops.nystrom_fused_stats(X, L, proj, y, y, wphi, wm, nz,
                                       **kw)
        for start, blk in ((0, 14), (3, 5), (9, 5), (7, 7), (13, 1)):
            win = ops.nystrom_fused_stats(
                X, L, proj, y, y, wphi, wm, nz,
                col_window=(jnp.int32(start), blk), **kw)
            np.testing.assert_allclose(
                np.asarray(win[-1]),
                np.asarray(full[-1])[:, start:start + blk],
                rtol=2e-5, atol=2e-5)
            for a, b_ in zip(win[:-1], full[:-1]):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b_))


def test_windowed_vmem_fallback_matches_kernel():
    """Past the windowed byte budget the dispatch falls back to the
    plain-XLA column block; outputs must match the kernel route."""
    X, y, _, w, wm = _problem()
    assert not ops.fused_stats_fits(X.shape[1], 7, block_n=10 ** 6)
    assert ops.fused_stats_fits(X.shape[1], 7)
    kw = dict(epilogue="em_hinge", eps=1e-4)
    win = ops.fused_stats(X, y, y, w, wm, None, col_window=(5, 7),
                          backend="interpret", **kw)
    fb = ops.fused_stats(X, y, y, w, wm, None, col_window=(5, 7),
                         backend="interpret", block_n=10 ** 6, **kw)
    for a, b_ in zip(win, fb):
        # different routes (Pallas tile vs XLA matmul): fp32
        # reassociation tolerance, not bitwise
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=1e-5)


def test_windowed_budget_unlocks_k_beyond_full_cap():
    """The narrowed accumulator is the point of the windowed budget: a
    K past FUSED_STATS_MAX_K (full-width fallback regime) still FUSES
    when only a column block is accumulated."""
    K = ops.FUSED_STATS_MAX_K + 512
    assert not ops.fused_stats_fits(K)
    assert ops.fused_stats_fits(K, col_blk=K // 16)


# ------------------------------------------------ 2. bitwise MC draws
def test_windowed_mc_draws_bitwise_vs_oracle():
    X, y, _, w, wm = _problem(64, 16, seed=7)
    key, row0, eps = jax.random.PRNGKey(9), 17, 1e-6
    margin = X @ w
    want = augment.gamma_mc_rowwise(key, y - margin, eps, row0)
    noise = augment.draw_ig_noise(key, X.shape[0], row0)
    out = ops.fused_stats(X, y, y, w, None, noise,
                          col_window=(jnp.int32(4), 4),
                          epilogue="mc_hinge", eps=eps, backend="ref")
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(want))


# ------------------------------------------------ 5. feature padding
def test_pad_features_to():
    X = np.ones((5, 7), np.float32)
    P = pad_features_to(X, 4)
    assert P.shape == (5, 8)
    np.testing.assert_array_equal(P[:, 7:], 0.0)
    assert pad_features_to(X, 7) is X          # already divisible
    assert pad_features_to(X, 1) is X
    Pj = pad_features_to(jnp.asarray(X), 4)    # jax arrays too
    assert isinstance(Pj, jnp.ndarray) and Pj.shape == (5, 8)


def test_k_block_error_names_the_pad_helper():
    from jax import make_mesh, shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.linear import _k_block

    mesh = make_mesh((1,), ("model",))

    def f(x):
        return jnp.asarray(_k_block(x.shape[-1], "model")[0])

    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(None, None),),
                          out_specs=P(), check_vma=False))
    assert int(g(jnp.zeros((4, 6)))) == 0
    # the real refusal needs axis size > 1 -> exercised in the
    # subprocess tests below; here check the message contract directly
    import repro.core.linear as linear_mod
    orig = jax.lax.axis_size
    try:
        jax.lax.axis_size = lambda a: 2
        with pytest.raises(ValueError) as ei:
            linear_mod._k_block(7, "model")
    finally:
        jax.lax.axis_size = orig
    msg = str(ei.value)
    assert "does not divide" in msg
    assert "pad_features_to" in msg


# ------------------------ 3./4. subprocess multi-device fit invariance
def run_with_devices(code: str, n_devices: int = 4, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


HEADER = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import PEMSVM, SVMConfig
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
N, K = 1024, 23                       # +bias -> 24, model axis 2 | 24
w_true = rng.normal(size=K)
X = rng.normal(size=(N, K)).astype(np.float32)
y = np.where(X @ w_true + 0.3 * rng.normal(size=N) > 0, 1.0, -1.0)
ys = (X @ w_true).astype(np.float32)
lab = rng.integers(0, 3, N).astype(np.int32)
def trace_rel(a, b):
    a, b = np.array(a.objective), np.array(b.objective)
    return np.abs(a - b) / np.maximum(np.abs(b), 1.0)
"""


def test_kshard_2d_mesh_em_parity_all_tasks():
    """EM on the 2-D mesh: CLS, SVR and MLT (the two newly-enabled
    tasks) match the replicated fit — deterministic, so tight."""
    run_with_devices(HEADER + """
for task, tgt in (("CLS", y), ("SVR", ys), ("MLT", lab)):
    cfg = dict(task=task, max_iters=15, min_iters=15, eps=1e-2,
               num_classes=3)
    r1 = PEMSVM(SVMConfig(**cfg)).fit(X, tgt)
    rk = PEMSVM(SVMConfig(k_shard_axis="model", **cfg), mesh=mesh,
                data_axes=("data",)).fit(X, tgt)
    rel = np.abs(rk.weights - r1.weights).max() / np.abs(r1.weights).max()
    assert rel < 1e-3, (task, rel)
print("EM k_shard parity OK")
""")


def test_kshard_2d_mesh_mc_chain_invariance():
    """MC on the 2-D mesh draws the SAME chain as the replicated fit:
    iteration one is exact (same rowwise-keyed draws), short chains
    stay in the documented fp32 windows (CLS tight; SVR gets the
    streaming tests' loose long-chain band — the IG accept-reject fork
    channel, DESIGN.md §Perf/Streaming)."""
    run_with_devices(HEADER + """
bands = {"CLS": 2e-3, "SVR": 5e-2, "MLT": 2e-3}
for task, tgt in (("CLS", y), ("SVR", ys), ("MLT", lab)):
    cfg = dict(task=task, algorithm="MC", max_iters=12, min_iters=12,
               eps=1e-2, burnin=6, num_classes=3)
    r1 = PEMSVM(SVMConfig(**cfg)).fit(X, tgt)
    rk = PEMSVM(SVMConfig(k_shard_axis="model", **cfg), mesh=mesh,
                data_axes=("data",)).fit(X, tgt)
    rel = trace_rel(rk, r1)
    assert rel[0] < 1e-6, (task, rel[0])          # same draws at iter 1
    assert rel.max() < bands[task], (task, rel)
print("MC k_shard chain invariance OK")
""")


def test_kshard_mesh_layout_invariance():
    """The sampled MC chain must not depend on HOW the 2-D mesh is
    laid out: (2, 2) and (1, 4) (data x model) give the same chain up
    to fp32 psum reassociation."""
    run_with_devices(HEADER + """
mesh14 = jax.make_mesh((1, 4), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = dict(task="CLS", algorithm="MC", max_iters=10, min_iters=10,
           eps=1e-2, burnin=5)
a = PEMSVM(SVMConfig(k_shard_axis="model", **cfg), mesh=mesh,
           data_axes=("data",)).fit(X, y)
b = PEMSVM(SVMConfig(k_shard_axis="model", **cfg), mesh=mesh14,
           data_axes=("data",)).fit(X, y)
rel = trace_rel(a, b)
assert rel.max() < 2e-3, rel
print("mesh layout invariance OK")
""")


def test_kshard_phi_spec_whole_fit_parity():
    """The formerly-NotImplementedError composition: k_shard_axis x
    phi_spec (Nystrom). Whole-fit EM parity <= 1e-4 vs the replicated
    Nystrom path; MC iteration one exact."""
    run_with_devices(HEADER + """
from repro.core.nystrom import NystromSVM
def kcfg(**kw):
    return SVMConfig(formulation="KRN", sigma=1.2, eps=1e-2,
                     max_iters=15, min_iters=15, **kw)
n1 = NystromSVM(kcfg(), n_landmarks=31)           # phi width 32 -> | 2
r1 = n1.fit(X, y)
nk = NystromSVM(kcfg(k_shard_axis="model"), n_landmarks=31, mesh=mesh,
                data_axes=("data",))
rk = nk.fit(X, y)
rel = np.abs(rk.weights - r1.weights).max() / np.abs(r1.weights).max()
assert rel < 1e-4, rel
assert abs(n1.score(X, y) - nk.score(X, y)) < 1e-2
mc1 = NystromSVM(kcfg(algorithm="MC", burnin=5), n_landmarks=31)
a = mc1.fit(X, y)
mck = NystromSVM(kcfg(algorithm="MC", burnin=5, k_shard_axis="model"),
                 n_landmarks=31, mesh=mesh, data_axes=("data",))
b = mck.fit(X, y)
rel = trace_rel(b, a)
assert rel[0] < 1e-6, rel[0]
assert rel.max() < 5e-3, rel
print("k_shard x phi_spec parity OK")
""")


def test_kshard_pad_features_whole_fit():
    """Indivisible width (K=23 + bias = 24... use model=4 -> 24 | 4 is
    fine, so go through a 23-wide no-bias fit: 23 % 2 != 0): the
    config plumb pads to a k_shard-divisible width, predictions match
    the unpadded replicated fit, and WITHOUT the pad _k_block raises
    the pad-helper error."""
    run_with_devices(HEADER + """
base = PEMSVM(SVMConfig(max_iters=15, min_iters=15, eps=1e-2,
                        add_bias=False)).fit(X, y)
padded = PEMSVM(SVMConfig(max_iters=15, min_iters=15, eps=1e-2,
                          add_bias=False, k_shard_axis="model",
                          pad_features=2),
                mesh=mesh, data_axes=("data",))
rp = padded.fit(X, y)
assert rp.weights.shape == (24,)
rel = np.abs(rp.weights[:K] - base.weights).max() / np.abs(
    base.weights).max()
assert rel < 1e-3, rel
assert rp.weights[K:].max() == 0.0          # zero columns stay zero
b1 = PEMSVM(SVMConfig(max_iters=15, eps=1e-2, add_bias=False))
b1._weights = base.weights
assert abs(padded.score(X, y) - b1.score(X, y)) < 1e-6
try:
    PEMSVM(SVMConfig(max_iters=2, min_iters=1, eps=1e-2,
                     add_bias=False, k_shard_axis="model"),
           mesh=mesh, data_axes=("data",)).fit(X, y)
except ValueError as e:
    assert "pad_features_to" in str(e), e
else:
    raise SystemExit("expected ValueError for K=23 over 2-way axis")
print("pad_features whole-fit OK")
""")

"""In-kernel counter-based RNG (``SVMConfig.rng``) and multichain Gibbs.

The contract under test (DESIGN.md §Perf/RNG):

  1. The counter stream itself: ``draw_fused_noise`` (the host oracle)
     is a pure function of (key words, global row, chain id) — chunk
     slices are literal slices, chain planes are independent, and the
     kernel-tile generator (``tile_noise``) emits the SAME bits.
  2. Kernel parity, BITWISE: ``ops.fused_stats`` /
     ``ops.nystrom_fused_stats`` with the (4,) ``seed`` operand equal
     the same call fed the materialized ``noise`` operands — every
     output, every backend, odd masked shapes included.  (This is a
     sharper claim than the host-rng kernel tests can make: the noise
     VALUES are bitwise shared by construction, and everything
     downstream is the same code.)
  3. Operand elimination: under ``seed`` the jaxpr's pallas_call has NO
     (N,)-shaped noise inputs — the (4,) uint32 seed replaces
     ``n_noise`` full-length streams.  Mixed configs (both sources)
     fail loudly, naming the operand and the config knob.
  4. Whole-fit parity: ``rng='fused'`` fits are bitwise equal to
     ``rng='fused_predraw'`` (same driver + backend) for
     {CLS, SVR, MLT} x {linear, Nystrom} x {loop, scan, stream}, on a
     mesh, and at a shifted chain0.  Cross-driver/backend equality is
     NOT claimed — those fits reassociate fp32 sums and were never
     bitwise in host mode either.
  5. Multichain (``n_chains``): C chains ride one X stream; the fit
     exposes per-chain weights, their mean and ddof-1 std, and the
     serving export turns the chain spread into score_with_std.
  6. The rng / n_chains / chain0 fields are SEMANTIC for resume: a
     checkpoint from one counter stream refuses to continue another.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import NystromSVM, PEMSVM, SVMConfig, augment
from repro.core.linear import accumulate_stats
from repro.kernels import epilogues, ops, ref
from repro.kernels import rng as rng_mod
from repro.runtime import faults
from repro.runtime.policy import FaultPolicy
from repro.serving import SVMScorer

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_rng = np.random.default_rng(0)
N, D = 201, 7
X = _rng.normal(size=(N, D)).astype(np.float32)
_w_true = _rng.normal(size=D)
Y_CLS = np.where(X @ _w_true > 0, 1.0, -1.0).astype(np.float32)
Y_SVR = (X @ _w_true).astype(np.float32)
Y_MLT = _rng.integers(0, 3, size=N)


def _run_with_devices(code: str, n_devices: int = 4, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def _fit(task, targets, **kw):
    defaults = dict(algorithm="MC", task=task, max_iters=8, min_iters=8,
                    burnin=2)
    if task == "MLT":
        defaults["num_classes"] = 3
    defaults.update(kw)
    return PEMSVM(SVMConfig(**defaults)).fit(X, targets)


# --------------------------------------------- 1. the counter stream
def test_counter_draws_are_chunk_slice_invariant():
    """Rows [i0, i1) of the full stream are literally the chunk draw at
    row0=i0 — global-row keying makes chunk boundaries invisible,
    bitwise, for both the 2- and 4-stream (SVR) arities."""
    key = jax.random.PRNGKey(7)
    for n_noise in (2, 4):
        full = rng_mod.draw_fused_noise(key, 230, 0, 0, n_noise)
        for i0, i1 in ((0, 64), (64, 193), (193, 230)):
            part = rng_mod.draw_fused_noise(key, i1 - i0, i0, 0, n_noise)
            for f, p in zip(full, part):
                np.testing.assert_array_equal(np.asarray(f)[i0:i1],
                                              np.asarray(p))


def test_counter_chain_planes_independent_and_replayable():
    """Same (key, row, chain) coordinate -> same bits, always; distinct
    chain ids -> distinct streams.  The uniform stays strictly inside
    (0, 1) (the Box-Muller log must never see 0) and the normal stream
    is standard-normal-shaped."""
    key = jax.random.PRNGKey(3)
    draws = [rng_mod.draw_fused_noise(key, 4096, 0, c, 2)
             for c in range(4)]
    again = rng_mod.draw_fused_noise(key, 4096, 0, 2, 2)
    np.testing.assert_array_equal(np.asarray(draws[2][0]),
                                  np.asarray(again[0]))
    for a in range(4):
        for b in range(a + 1, 4):
            assert not np.array_equal(np.asarray(draws[a][0]),
                                      np.asarray(draws[b][0])), (a, b)
    for nu, u in draws:
        u = np.asarray(u)
        assert (u > 0).all() and (u < 1).all()
        nu = np.asarray(nu)
        assert abs(nu.mean()) < 0.1 and abs(nu.std() - 1.0) < 0.05


def test_tile_noise_matches_host_oracle_per_chain():
    """The kernel-body generator (seed words + tile row offset +
    broadcasted iota) emits, per chain column, exactly the host
    oracle's stream for that chain id — the bitwise bridge every
    kernel-parity test below stands on."""
    key = jax.random.PRNGKey(11)
    row0, chain0, bn, C = 37, 5, 64, 3
    seed = np.asarray(rng_mod.pack_seed(key, row0, chain0))
    for n_noise in (2, 4):
        tile = rng_mod.tile_noise(seed, 128, (bn, C), n_noise)
        for c in range(C):
            want = rng_mod.draw_fused_noise(key, bn, row0 + 128,
                                            chain0 + c, n_noise)
            for t, w in zip(tile, want):
                np.testing.assert_array_equal(np.asarray(t)[:, c],
                                              np.asarray(w))


# ------------------------------------- 2. kernel parity, seed vs operand
@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("epilogue", ["mc_hinge", "mc_svr"])
@pytest.mark.parametrize("n,k,n_valid", [(100, 7, 100), (128, 24, 77),
                                         (9, 33, 9)])
def test_seed_equals_noise_operands_bitwise(backend, epilogue, n, k,
                                            n_valid):
    """ops.fused_stats with the (4,) counter seed == the same call fed
    the materialized noise operands, bitwise on EVERY output — margins,
    draws, b, Sigma — for both MC epilogues, both backends, odd masked
    shapes, and a nonzero row0/chain0."""
    rng = np.random.default_rng(n * k)
    Xb = np.zeros((n, k), np.float32)
    y = np.zeros((n,), np.float32)
    Xb[:n_valid] = rng.normal(size=(n_valid, k)).astype(np.float32)
    y[:n_valid] = rng.choice([-1.0, 1.0], n_valid)
    w = rng.normal(size=k).astype(np.float32)
    key, row0, chain0 = jax.random.PRNGKey(n + k), 37, 2
    n_noise = epilogues.noise_arity(epilogue)
    noise = rng_mod.draw_fused_noise(key, n, row0, chain0, n_noise)
    seed = rng_mod.pack_seed(key, row0, chain0)
    kw = dict(epilogue=epilogue, eps=1e-6, eps_ins=0.2, backend=backend,
              block_n=64)
    args = (jnp.asarray(Xb), jnp.asarray(y), jnp.asarray(y),
            jnp.asarray(w), None)
    got = ops.fused_stats(*args, None, seed=seed, **kw)
    want = ops.fused_stats(*args, noise, **kw)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("epilogue", ["mc_hinge", "mc_svr"])
def test_nystrom_seed_equals_noise_operands_bitwise(backend, epilogue):
    """Phi-space flavor: the fused Nystrom kernel under the counter
    seed == the operand path, bitwise, masked rows and phi bias on."""
    rng = np.random.default_rng(31)
    n, d, m = 100, 7, 37
    Xb = rng.normal(size=(n, d)).astype(np.float32)
    L = Xb[rng.choice(n, m, replace=False)]
    proj = (0.2 * rng.normal(size=(m, m))).astype(np.float32)
    mask = (rng.uniform(size=n) > 0.25).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    w = rng.normal(size=m + 1).astype(np.float32)
    key, row0 = jax.random.PRNGKey(5), 19
    n_noise = epilogues.noise_arity(epilogue)
    noise = rng_mod.draw_fused_noise(key, n, row0, 0, n_noise)
    seed = rng_mod.pack_seed(key, row0, 0)
    kw = dict(sigma=1.3, kind="rbf", add_bias=True, epilogue=epilogue,
              eps=1e-6, eps_ins=0.1, backend=backend, block_n=32)
    args = (jnp.asarray(Xb), jnp.asarray(L), jnp.asarray(proj),
            jnp.asarray(y), jnp.asarray(y), jnp.asarray(w),
            jnp.asarray(mask))
    got = ops.nystrom_fused_stats(*args, None, seed=seed, **kw)
    want = ops.nystrom_fused_stats(*args, noise, **kw)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


# ------------------------------- 3. operand elimination + loud failures
def _pallas_calls(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                out.extend(_pallas_calls(v.jaxpr))
    return out


def test_seed_mode_eliminates_row_noise_operands():
    """Jaxpr walk: under rng='fused' the pallas_call takes NO (n,)
    noise inputs — its operand list is exactly the predraw list minus
    the n_noise full-length streams, plus one (4,) uint32 seed."""
    n, k = 128, 16
    Xb = jnp.asarray(_rng.normal(size=(n, k)).astype(np.float32))
    y = jnp.asarray(_rng.choice([-1.0, 1.0], n).astype(np.float32))
    w = jnp.zeros((k,), jnp.float32)
    key = jax.random.PRNGKey(0)

    def run(rng):
        return lambda X_, y_, w_: accumulate_stats(
            X_, y_, y_, w_, mode="MC", key=key, eps=1e-6,
            backend="interpret", row0=0, rng=rng)

    seeded = _pallas_calls(jax.make_jaxpr(run("fused"))(Xb, y, w).jaxpr)
    predrawn = _pallas_calls(
        jax.make_jaxpr(run("fused_predraw"))(Xb, y, w).jaxpr)
    assert len(seeded) == 1 and len(predrawn) == 1
    s_in = [v.aval for v in seeded[0].invars]
    p_in = [v.aval for v in predrawn[0].invars]
    # the kernels carry row streams as (n, 1) columns
    n_row = lambda avals: sum(a.shape in ((n,), (n, 1)) for a in avals)
    assert n_row(p_in) - n_row(s_in) == epilogues.noise_arity("mc_hinge")
    assert sum(a.shape == (4,) and a.dtype == jnp.uint32
               for a in s_in) == 1
    assert not any(a.shape == (4,) for a in p_in)


def test_mixed_noise_and_seed_rejected_naming_both_knobs():
    """Exactly one noise source: passing pre-drawn operands AND the
    counter seed fails loudly, pointing at both the operand and the
    SVMConfig.rng knob."""
    n, k = 32, 8
    Xb = jnp.zeros((n, k), jnp.float32)
    y = jnp.zeros((n,), jnp.float32)
    w = jnp.zeros((k,), jnp.float32)
    key = jax.random.PRNGKey(0)
    noise = rng_mod.draw_fused_noise(key, n, 0, 0, 2)
    seed = rng_mod.pack_seed(key)
    with pytest.raises(ValueError, match=r"noise=.*rng='host'"):
        ops.fused_stats(Xb, y, y, w, None, noise, seed=seed,
                        epilogue="mc_hinge", eps=1e-6, backend="ref")


def test_config_rejects_unreachable_rng_combinations():
    with pytest.raises(AssertionError, match="MC"):
        SVMConfig(algorithm="EM", rng="fused")
    with pytest.raises(AssertionError, match="rng='fused'"):
        SVMConfig(algorithm="MC", rng="host", n_chains=2)
    with pytest.raises(AssertionError, match="CLS/SVR"):
        SVMConfig(algorithm="MC", task="MLT", num_classes=3, rng="fused",
                  n_chains=2)
    # exact-Gram KRN has no counter plumbing; NystromSVM (which builds
    # a LIN delegate) is the supported kernel route
    with pytest.raises(ValueError, match="NystromSVM"):
        PEMSVM(SVMConfig(formulation="KRN", algorithm="MC", rng="fused"))


# ----------------------------------------------- 4. whole-fit parity
@pytest.mark.parametrize("driver", ["loop", "scan", "stream"])
@pytest.mark.parametrize("task", ["CLS", "SVR", "MLT"])
def test_fit_fused_equals_predraw_bitwise(task, driver):
    """The headline gate: rng='fused' reproduces the materialized-
    noise oracle fit bit for bit — every task, every driver (same
    driver on both sides; drivers reassociate sums and are not
    bitwise-comparable to EACH OTHER, in any rng mode)."""
    tgt = {"CLS": Y_CLS, "SVR": Y_SVR, "MLT": Y_MLT}[task]
    kw = dict(driver=driver)
    if driver == "stream":
        kw["chunk_rows"] = 64
    a = _fit(task, tgt, rng="fused", **kw)
    b = _fit(task, tgt, rng="fused_predraw", **kw)
    h = _fit(task, tgt, rng="host", **kw)
    assert np.array_equal(a.weights, b.weights)
    assert np.array_equal(a.objective, b.objective)
    # distinct source from the host tree (counter bits != fold_in tree)
    assert not np.array_equal(a.weights, h.weights)


def test_fit_fused_equals_predraw_at_shifted_chain0():
    """chain0 relocates the whole fit to another counter plane: still
    bitwise vs the oracle there, and a different chain than plane 0."""
    a0 = _fit("CLS", Y_CLS, rng="fused")
    a = _fit("CLS", Y_CLS, rng="fused", chain0=3)
    b = _fit("CLS", Y_CLS, rng="fused_predraw", chain0=3)
    assert np.array_equal(a.weights, b.weights)
    assert not np.array_equal(a.weights, a0.weights)


@pytest.mark.parametrize("driver", ["loop", "stream"])
@pytest.mark.parametrize("task", ["CLS", "SVR", "MLT"])
def test_nystrom_fit_fused_equals_predraw_bitwise(task, driver):
    """Same gate through the Nystrom phi route (featurize-in-kernel):
    the user-facing KRN config carries rng through to the LIN
    delegate."""
    tgt = {"CLS": Y_CLS, "SVR": Y_SVR, "MLT": Y_MLT}[task]
    kw = dict(formulation="KRN", algorithm="MC", task=task, sigma=1.2,
              max_iters=6, min_iters=6, burnin=2, driver=driver)
    if task == "MLT":
        kw["num_classes"] = 3
    if driver == "stream":
        kw["chunk_rows"] = 64
    fits = {}
    for rng in ("fused", "fused_predraw"):
        ny = NystromSVM(SVMConfig(**kw, rng=rng), n_landmarks=16, seed=1)
        fits[rng] = ny.fit(X, tgt)
    assert np.array_equal(fits["fused"].weights,
                          fits["fused_predraw"].weights)


def test_fused_fit_is_mesh_layout_invariant():
    """A (2, 2) and a (1, 4) mesh run the SAME counter stream: fused
    == predraw bitwise on each mesh, and the two meshes' draws agree
    (gamma_mean to psum-reassociation tolerance at w=0, where margins
    are exactly zero on every layout)."""
    _run_with_devices("""
import jax
import numpy as np
from repro.core import PEMSVM, SVMConfig
mesh_a = jax.make_mesh((2, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh_b = jax.make_mesh((1, 4), ("model", "data"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
N, K = 512, 16
Xm = rng.normal(size=(N, K)).astype(np.float32)
w_true = rng.normal(size=K)
ym = np.where(Xm @ w_true > 0, 1.0, -1.0)
for task, tgt in (("CLS", ym), ("SVR", (Xm @ w_true).astype(np.float32))):
    kw = dict(algorithm="MC", task=task, burnin=0, max_iters=1,
              min_iters=1, eps_ins=0.3)
    outs = {}
    for name, mesh, axes in (("a", mesh_a, ("data",)),
                             ("b", mesh_b, ("data",))):
        f = PEMSVM(SVMConfig(**kw, rng="fused"), mesh=mesh,
                   data_axes=axes).fit(Xm, tgt)
        p = PEMSVM(SVMConfig(**kw, rng="fused_predraw"), mesh=mesh,
                   data_axes=axes).fit(Xm, tgt)
        assert np.array_equal(f.weights, p.weights), (task, name)
        outs[name] = f
    r1 = PEMSVM(SVMConfig(**kw, rng="fused")).fit(Xm, tgt)
    for name, r in outs.items():
        np.testing.assert_allclose(r.aux_history["gamma_mean"][0],
                                   r1.aux_history["gamma_mean"][0],
                                   rtol=1e-5, err_msg=(task, name))
print("fused mesh invariance OK")
""")


# ------------------------------------------------------- 5. multichain
@pytest.mark.parametrize("task,tgt", [("CLS", Y_CLS), ("SVR", Y_SVR)])
def test_multichain_fit_exposes_chain_ensemble(task, tgt):
    """n_chains=C: FitResult carries the (C, K) per-chain weights,
    weights == their float64 mean, chain_std == their ddof-1 std, and
    the chains are distinct (independent counter planes)."""
    C = 3
    res = _fit(task, tgt, rng="fused", n_chains=C)
    K = res.weights.shape[0]
    assert res.chain_weights.shape == (C, K)
    assert res.chain_std.shape == (K,)
    cw = res.chain_weights.astype(np.float64)
    np.testing.assert_array_equal(
        res.weights, cw.mean(axis=0).astype(np.float32))
    np.testing.assert_array_equal(
        res.chain_std, cw.std(axis=0, ddof=1).astype(np.float32))
    for a in range(C):
        for b in range(a + 1, C):
            assert not np.array_equal(res.chain_weights[a],
                                      res.chain_weights[b])
    # single-chain fits keep the legacy surface
    single = _fit(task, tgt, rng="fused")
    assert single.chain_weights is None and single.chain_std is None


@pytest.mark.parametrize("driver", ["scan", "stream"])
def test_multichain_drivers_agree(driver):
    """The multichain state threads every driver; loop vs {scan,
    stream} is the usual whole-fit reassociation band, and fused ==
    predraw stays OUT of reach here on purpose (fused_predraw is the
    single-chain operand path — only the in-kernel counter can
    address C planes)."""
    kw = dict(rng="fused", n_chains=3)
    if driver == "stream":
        kw["chunk_rows"] = 64
    a = _fit("CLS", Y_CLS, driver="loop", **kw)
    b = _fit("CLS", Y_CLS, driver=driver, **kw)
    assert a.chain_weights.shape == b.chain_weights.shape == (3, D + 1)
    # Not bitwise on purpose: the (N, K) @ (K, C) margin matmul tiles
    # differently inside lax.scan / per-chunk jits than in the loop
    # step's XLA program (same reassociation channel as stream's
    # chunk-summed S), and the chain amplifies the lsb over iterations.
    rel = (np.abs(a.chain_weights - b.chain_weights).max()
           / np.abs(a.chain_weights).max())
    assert rel < 5e-2, rel


def test_multichain_serving_scores_with_chain_spread():
    """export_servable of a multichain fit serves the chain ensemble:
    margins from the mean weights, score_with_std's band == the ddof-1
    std of the per-chain margins."""
    C = 4
    svm = PEMSVM(SVMConfig(algorithm="MC", max_iters=8, min_iters=8,
                           burnin=2, rng="fused", n_chains=C))
    res = svm.fit(X, Y_CLS)
    sc = SVMScorer(svm.export_servable())
    margin, std = sc.score_with_std(X[:64])
    Xb = np.concatenate([X[:64], np.ones((64, 1), np.float32)], axis=1)
    chain_scores = (Xb.astype(np.float64)
                    @ res.chain_weights.astype(np.float64).T)
    np.testing.assert_allclose(margin, chain_scores.mean(axis=1),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(std, chain_scores.std(axis=1, ddof=1),
                               rtol=1e-3, atol=1e-5)
    assert np.all(std > 0)


# ------------------------------------------- 6. resume semantics
def test_resume_rejects_other_counter_stream(tmp_path):
    """rng / n_chains / chain0 are inside the config fingerprint: a
    checkpoint is a position in ONE counter stream, and resuming it
    under another stream fails naming the mismatched field."""
    kw = dict(algorithm="MC", task="CLS", driver="loop", max_iters=6,
              min_iters=6, burnin=2, rng="fused", n_chains=2)
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=2)
    PEMSVM(SVMConfig(**kw, fault=pol)).fit(X, Y_CLS)
    for field, other in (("rng", dict(rng="fused_predraw", n_chains=1)),
                         ("n_chains", dict(n_chains=3)),
                         ("chain0", dict(chain0=7))):
        with pytest.raises(ValueError, match=field):
            PEMSVM(SVMConfig(**{**kw, **other}, fault=pol)).fit(
                X, Y_CLS, resume_from=str(tmp_path))

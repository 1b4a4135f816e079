"""Kill it. Resume it. Get the same bits.

Every reliability claim in DESIGN.md §Reliability is proven here by
actually preempting a fit (``runtime.faults``) and resuming from the
last committed snapshot:

  * same config + same driver -> resume is BITWISE equal to the
    uninterrupted fit (EM and MC: the checkpoint carries the PRNG
    carry key, and mid-pass snapshots carry the iteration subkey);
  * checkpoints restore across drivers and meshes (the elastic
    contract) to the corresponding whole-fit reassociation band —
    resuming adds no error beyond what changing the layout already
    costs;
  * the budget can be EXTENDED on resume (max_iters is outside the
    config fingerprint); everything semantic is inside it and
    mismatches fail loudly;
  * straggler reactions (record / drop / raise) and the live-weighted
    renormalized reduction behave as documented.

Single-device tests run inline; mesh tests run in subprocesses with
``--xla_force_host_platform_device_count`` (same pattern as
test_kshard_fused.py).
"""
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.core import NystromSVM, PEMSVM, SVMConfig
from repro.core import resume as resume_mod
from repro.core.linear import SVMData
from repro.runtime import faults
from repro.runtime.policy import FaultPolicy, StragglerError

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_rng = np.random.default_rng(0)
N, K = 257, 9
X = _rng.normal(size=(N, K)).astype(np.float32)
_w_true = _rng.normal(size=K + 1)
Y_CLS = np.where(X @ _w_true[:K] + _w_true[K] > 0, 1.0, -1.0).astype(
    np.float32)
Y_SVR = (X @ _w_true[:K]).astype(np.float32)


def _kill_fit(svm, X, y, hook, **fit_kw):
    """Run a fit that MUST be preempted by ``hook``."""
    with pytest.raises(faults.SimulatedPreemption):
        svm.fit(X, y, fault_hook=hook, **fit_kw)


# ------------------------------------------- same-driver bitwise parity
@pytest.mark.parametrize("algo", ["EM", "MC"])
@pytest.mark.parametrize("task", ["CLS", "SVR"])
def test_stream_kill_resume_bitwise(algo, task, tmp_path):
    """Stream driver, killed between iterations: the resumed trajectory
    is the uninterrupted one, bit for bit — EM (deterministic) AND MC
    (the checkpointed carry key continues the exact chain)."""
    tgt = Y_CLS if task == "CLS" else Y_SVR
    kw = dict(algorithm=algo, task=task, driver="stream", chunk_rows=64,
              max_iters=12, min_iters=12, burnin=3)
    ref = PEMSVM(SVMConfig(**kw)).fit(X, tgt)

    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=2, ckpt_chunks=2)
    cfg = SVMConfig(**kw, fault=pol)
    _kill_fit(PEMSVM(cfg), X, tgt, faults.kill_at_iteration(7))
    res = PEMSVM(cfg).fit(X, tgt, resume_from=str(tmp_path))

    assert res.resumed_at is not None and res.resumed_at >= 6
    assert np.array_equal(ref.weights, res.weights)
    assert np.allclose(ref.objective, res.objective)


def _five_chunks():
    """A restartable fit_chunks source: 257 rows padded to 5 x 64."""
    Xp = np.concatenate([X, np.zeros((63, K), np.float32)])
    yp = np.concatenate([Y_CLS, np.zeros(63, np.float32)])
    mp = np.concatenate([np.ones(N, np.float32),
                         np.zeros(63, np.float32)])
    for i0 in range(0, 320, 64):
        yield SVMData(Xp[i0:i0 + 64], yp[i0:i0 + 64], mp[i0:i0 + 64])


def test_midpass_kill_resume_bitwise(tmp_path):
    """Preempt INSIDE a pass (chunk 12 of a 5-chunk/pass stream) with
    per-chunk snapshots on: resume skips the already-folded chunks,
    consumes the saved iteration subkey without re-splitting, and the
    MC chain continues bitwise."""
    kw = dict(algorithm="MC", task="CLS", driver="stream", chunk_rows=64,
              max_iters=8, min_iters=8, burnin=2)
    ref = PEMSVM(SVMConfig(**kw)).fit_chunks(_five_chunks, K)

    d = str(tmp_path)
    pol = FaultPolicy(ckpt_dir=d, ckpt_every=100, ckpt_chunks=1)
    cfg = SVMConfig(**kw, fault=pol)
    with pytest.raises(faults.SimulatedPreemption):
        PEMSVM(cfg).fit_chunks(faults.kill_after_chunks(_five_chunks, 12),
                               K)

    ck = Checkpointer(d)
    payload = resume_mod.load_snapshot(ck)
    assert payload["in_pass"] and payload["chunk_idx"] > 0

    # a mid-pass snapshot is stream-only and chunk_rows-pinned
    with pytest.raises(ValueError, match="driver='stream'"):
        PEMSVM(SVMConfig(algorithm="MC", task="CLS", driver="scan",
                         max_iters=8, min_iters=8, burnin=2, fault=pol)
               ).fit(X, Y_CLS, resume_from=d)
    with pytest.raises(ValueError, match="chunk_rows"):
        PEMSVM(SVMConfig(**{**kw, "chunk_rows": 32}, fault=pol)
               ).fit(X, Y_CLS, resume_from=d)

    res = PEMSVM(cfg).fit_chunks(_five_chunks, K, resume_from=d)
    assert np.array_equal(ref.weights, res.weights)


@pytest.mark.parametrize("algo", ["EM", "MC"])
def test_scan_kill_resume_bitwise(algo, tmp_path):
    """Scan driver checkpoints at host-sync boundaries; killed there,
    it resumes bitwise."""
    kw = dict(algorithm=algo, task="CLS", driver="scan", scan_chunk=4,
              max_iters=12, min_iters=12, burnin=3)
    ref = PEMSVM(SVMConfig(**kw)).fit(X, Y_CLS)
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=4)
    cfg = SVMConfig(**kw, fault=pol)
    _kill_fit(PEMSVM(cfg), X, Y_CLS, faults.kill_at_iteration(8))
    res = PEMSVM(cfg).fit(X, Y_CLS, resume_from=str(tmp_path))
    assert np.array_equal(ref.weights, res.weights)


def test_loop_kill_resume_bitwise(tmp_path):
    kw = dict(algorithm="MC", task="CLS", driver="loop", max_iters=10,
              min_iters=10, burnin=2)
    ref = PEMSVM(SVMConfig(**kw)).fit(X, Y_CLS)
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=3)
    cfg = SVMConfig(**kw, fault=pol)
    _kill_fit(PEMSVM(cfg), X, Y_CLS, faults.kill_at_iteration(7))
    res = PEMSVM(cfg).fit(X, Y_CLS, resume_from=str(tmp_path))
    assert np.array_equal(ref.weights, res.weights)
    assert res.n_checkpoints >= 1


def test_extend_budget_bitwise(tmp_path):
    """max_iters is OUTSIDE the fingerprint: a finished 5-iteration fit
    resumes into a 10-iteration budget and lands exactly where the
    one-shot 10-iteration fit does."""
    kw = dict(algorithm="EM", task="CLS", driver="loop", min_iters=1,
              tol=1e-12)
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=5)
    r1 = PEMSVM(SVMConfig(**kw, max_iters=5, fault=pol)).fit(X, Y_CLS)
    r2 = PEMSVM(SVMConfig(**kw, max_iters=10, fault=pol)).fit(
        X, Y_CLS, resume_from=str(tmp_path))
    ref = PEMSVM(SVMConfig(**kw, max_iters=10)).fit(X, Y_CLS)
    assert (r1.n_iters, r2.n_iters) == (5, 10)
    assert r2.resumed_at == 5
    assert np.array_equal(ref.weights, r2.weights)


def test_resume_step_pins_snapshot(tmp_path):
    """``resume_step`` picks a specific committed step (its id is
    it * 1_000_000 for boundary saves); replaying from iteration 6
    reproduces the donor run bitwise — including the objective
    history carried through the snapshot."""
    kw = dict(algorithm="EM", task="CLS", driver="loop", max_iters=10,
              min_iters=10)
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=3, keep_k=10)
    cfg = SVMConfig(**kw, fault=pol)
    ref = PEMSVM(cfg).fit(X, Y_CLS)                 # commits 3, 6, 9, 10
    res = PEMSVM(cfg).fit(X, Y_CLS, resume_from=str(tmp_path),
                          resume_step=resume_mod.step_id(6))
    assert res.resumed_at == 6
    assert np.array_equal(ref.weights, res.weights)
    assert np.allclose(ref.objective, res.objective)


# ----------------------------------------------- cross-layout elasticity
@pytest.mark.parametrize("target_driver", ["scan", "loop"])
def test_cross_driver_resume(target_driver, tmp_path):
    """A checkpoint written by the stream driver restores into scan and
    loop. Chunked fp32 accumulation reassociates the sums, so parity is
    the stream-vs-resident whole-fit band, not bitwise."""
    kw = dict(algorithm="MC", task="CLS", burnin=2, max_iters=10,
              min_iters=10)
    ref = PEMSVM(SVMConfig(**kw, driver="loop")).fit(X, Y_CLS)
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=3)
    _kill_fit(PEMSVM(SVMConfig(**kw, driver="stream", chunk_rows=64,
                               fault=pol)),
              X, Y_CLS, faults.kill_at_iteration(6))
    res = PEMSVM(SVMConfig(**kw, driver=target_driver, scan_chunk=4,
                           fault=pol)).fit(X, Y_CLS,
                                           resume_from=str(tmp_path))
    rel = (np.abs(ref.weights - res.weights).max()
           / np.abs(ref.weights).max())
    assert res.resumed_at is not None
    assert rel < 2e-3, rel


# -------------------------------------------- warm start + decayed stats
def test_warm_start_decay_stream():
    """decay > 0 (stream): the donor's accumulated (S, b) are folded
    into every M-step of the new fit, down-weighted by decay — the
    online/continual-fit warm start. The effective statistics ride on
    FitResult.stats so fits can be chained."""
    kw = dict(algorithm="EM", task="CLS", driver="stream", chunk_rows=64,
              max_iters=6, min_iters=6, decay=0.5)
    donor = PEMSVM(SVMConfig(**kw)).fit(X, Y_CLS)
    assert donor.stats is not None
    assert donor.stats["S"].shape == (K + 1, K + 1)
    assert donor.stats["b"].shape == (K + 1,)

    fresh = PEMSVM(SVMConfig(**kw)).fit(X, -Y_CLS)
    warm = PEMSVM(SVMConfig(**kw)).fit(X, -Y_CLS, warm_start=donor)
    assert warm.stats is not None
    assert not np.allclose(fresh.weights, warm.weights)


def test_warm_start_decay_multiclass():
    kw = dict(algorithm="EM", task="MLT", num_classes=3, driver="stream",
              chunk_rows=64, max_iters=4, min_iters=4, decay=0.3)
    ym = _rng.integers(0, 3, size=N)
    donor = PEMSVM(SVMConfig(**kw)).fit(X, ym)
    warm = PEMSVM(SVMConfig(**kw)).fit(X, ym, warm_start=donor)
    assert warm.stats["S"].shape == (3, K + 1, K + 1)
    assert warm.stats["b"].shape == (3, K + 1)


# --------------------------------------------------- guard-rail errors
def test_resume_and_warm_start_mutually_exclusive():
    donor = PEMSVM(SVMConfig(driver="loop", max_iters=2, min_iters=2)
                   ).fit(X, Y_CLS)
    with pytest.raises(ValueError):
        PEMSVM(SVMConfig(driver="loop", max_iters=2, min_iters=2)).fit(
            X, Y_CLS, resume_from="/tmp/anywhere", warm_start=donor)


def test_fingerprint_mismatch_names_field(tmp_path):
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=2)
    kw = dict(algorithm="EM", task="CLS", driver="loop", max_iters=4,
              min_iters=4)
    PEMSVM(SVMConfig(**kw, fault=pol)).fit(X, Y_CLS)
    with pytest.raises(ValueError, match="lam"):
        PEMSVM(SVMConfig(**kw, lam=2.0, fault=pol)).fit(
            X, Y_CLS, resume_from=str(tmp_path))


def test_decay_requires_donor_stats():
    donor = PEMSVM(SVMConfig(algorithm="EM", driver="stream",
                             chunk_rows=64, max_iters=4, min_iters=4)
                   ).fit(X, Y_CLS)           # decay=0 -> no stats kept
    with pytest.raises(ValueError, match="stats"):
        PEMSVM(SVMConfig(algorithm="EM", driver="stream", chunk_rows=64,
                         max_iters=4, min_iters=4, decay=0.5)).fit(
            X, Y_CLS, warm_start=donor)


def test_decay_requires_stream_driver():
    with pytest.raises(AssertionError):
        SVMConfig(driver="scan", decay=0.5)


# --------------------------------------------------- straggler reactions
def test_straggler_record_events(tmp_path):
    """on_straggler='record': a delayed iteration lands in
    FitResult.straggler_events without touching the trajectory."""
    kw = dict(algorithm="EM", task="CLS", driver="loop", max_iters=10,
              min_iters=10)
    pol = FaultPolicy(on_straggler="record", straggler_threshold=1.5,
                      straggler_warmup=2)
    res = PEMSVM(SVMConfig(**kw, fault=pol)).fit(
        X, Y_CLS, fault_hook=faults.delay_iterations([6], 0.5))
    assert any(e.get("it") == 6 for e in res.straggler_events)
    ref = PEMSVM(SVMConfig(**kw)).fit(X, Y_CLS)
    assert np.array_equal(ref.weights, res.weights)


def test_straggler_raise(tmp_path):
    """on_straggler='raise' hands control to an outer controller — and
    the last committed checkpoint makes the restart lossless."""
    kw = dict(algorithm="EM", task="CLS", driver="loop", max_iters=10,
              min_iters=10)
    pol = FaultPolicy(ckpt_dir=str(tmp_path), ckpt_every=2,
                      on_straggler="raise", straggler_threshold=3.0,
                      straggler_warmup=2)
    # a uniform floor delay dominates sub-ms timing noise, so only the
    # injected spike at iteration 6 crosses 3 x EMA
    floor = faults.delay_iterations(range(1, 11), 0.05)
    with pytest.raises(StragglerError):
        PEMSVM(SVMConfig(**kw, fault=pol)).fit(
            X, Y_CLS, fault_hook=faults.compose_hooks(
                floor, faults.delay_iterations([6], 0.5)))
    res = PEMSVM(SVMConfig(**kw, fault=pol)).fit(
        X, Y_CLS, resume_from=str(tmp_path), fault_hook=floor)
    ref = PEMSVM(SVMConfig(**kw)).fit(X, Y_CLS)
    assert np.array_equal(ref.weights, res.weights)


# -------------------------------------------------- mesh tests (subproc)
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


MESH_HEADER = """
import jax
import numpy as np, tempfile
from repro.core import PEMSVM, SVMConfig
from repro.runtime.policy import FaultPolicy, StragglerError
from repro.runtime import faults
mesh_a = jax.make_mesh((2, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
mesh_b = jax.make_mesh((4,), ("data",),
                       axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
N, K = 512, 23
w_true = rng.normal(size=K)
X = rng.normal(size=(N, K)).astype(np.float32)
y = np.where(X @ w_true + 0.3 * rng.normal(size=N) > 0, 1.0, -1.0)
"""


def test_remesh_resume_parity():
    """The elastic headline: kill a fit on a (2,2) mesh with the 2-D
    k-sharded statistic, resume on a flat (4,) mesh. Cross-mesh error
    equals the WHOLE-FIT mesh-reassociation band (EM ~1e-6, MC ~1e-2
    fp32) — resuming adds nothing on top. Resuming onto the SAME mesh
    is bitwise."""
    run_with_devices(MESH_HEADER + """
for algo, band in (("EM", 1e-4), ("MC", 2e-2)):
    kw = dict(algorithm=algo, task="CLS", driver="loop", max_iters=10,
              min_iters=10, burnin=3, eps=1e-2)
    with tempfile.TemporaryDirectory() as d:
        pol = FaultPolicy(ckpt_dir=d, ckpt_every=3, keep_k=10)
        ref_b = PEMSVM(SVMConfig(**kw), mesh=mesh_b,
                       data_axes=("data",)).fit(X, y)
        ref_a = PEMSVM(SVMConfig(**kw, k_shard_axis="model"),
                       mesh=mesh_a, data_axes=("data",)).fit(X, y)
        svm1 = PEMSVM(SVMConfig(**kw, k_shard_axis="model", fault=pol),
                      mesh=mesh_a, data_axes=("data",))
        try:
            svm1.fit(X, y, fault_hook=faults.kill_at_iteration(7))
            raise SystemExit("kill did not fire")
        except faults.SimulatedPreemption:
            pass
        res_b = PEMSVM(SVMConfig(**kw, fault=pol), mesh=mesh_b,
                       data_axes=("data",)).fit(X, y, resume_from=d)
        rel = (np.abs(res_b.weights - ref_b.weights).max()
               / np.abs(ref_b.weights).max())
        assert res_b.resumed_at == 6, res_b.resumed_at
        assert rel < band, (algo, rel)
        res_a = PEMSVM(SVMConfig(**kw, k_shard_axis="model",
                                 fault=pol), mesh=mesh_a,
                       data_axes=("data",)).fit(X, y, resume_from=d,
                                                resume_step=6_000_000)
        assert np.array_equal(res_a.weights, ref_a.weights), algo
print("remesh parity OK")
""")


def test_straggler_drop_and_live_renormalization():
    """on_straggler='drop': a flagged shard is zeroed out of the
    reduction via the live-weighted psum; the renormalized statistic
    targets the full-data sums, so the fit stays close to the
    surviving-rows fit (they differ only in regularizer weighting)."""
    run_with_devices(MESH_HEADER + """
kw = dict(algorithm="EM", task="CLS", driver="loop", max_iters=10,
          min_iters=10, eps=1e-2)
full = PEMSVM(SVMConfig(**kw), mesh=mesh_b, data_axes=("data",)).fit(X, y)

pol = FaultPolicy(on_straggler="drop", straggler_threshold=1.5,
                  straggler_warmup=2)
svm = PEMSVM(SVMConfig(**kw, fault=pol), mesh=mesh_b,
             data_axes=("data",))
svm.report_slow_shard(3)
res = svm.fit(X, y, fault_hook=faults.delay_iterations([6], 0.5))
assert len(res.straggler_events) >= 1
assert np.isfinite(res.weights).all()
assert not np.allclose(res.weights, full.weights)

live = np.array([1, 1, 1, 0], np.float32)
r_live = PEMSVM(SVMConfig(**kw), mesh=mesh_b,
                data_axes=("data",)).fit(X, y, live=live)
shard = N // 4
r_sub = PEMSVM(SVMConfig(**kw)).fit(X[:3 * shard], y[:3 * shard])
rel = (np.abs(r_live.weights - r_sub.weights).max()
       / np.abs(r_sub.weights).max())
assert rel < 5e-2, rel
print("drop/live OK")
""")


# --------------------------------------- in-kernel counter RNG + chains
def test_fused_multichain_midpass_kill_resume_bitwise(tmp_path):
    """The counter-RNG elastic claim: a 3-chain rng='fused' stream fit
    killed INSIDE a pass (mid-chunk snapshot) resumes bitwise — the
    (C, K) chain state, the partial chunk totals (S is (C, K, K) here)
    and the iteration subkey all ride the snapshot, and the in-kernel
    counter re-derives identical noise for the replayed rows."""
    kw = dict(algorithm="MC", task="CLS", driver="stream", chunk_rows=64,
              max_iters=8, min_iters=8, burnin=2, rng="fused", n_chains=3)
    ref = PEMSVM(SVMConfig(**kw)).fit_chunks(_five_chunks, K)

    d = str(tmp_path)
    pol = FaultPolicy(ckpt_dir=d, ckpt_every=100, ckpt_chunks=1)
    cfg = SVMConfig(**kw, fault=pol)
    with pytest.raises(faults.SimulatedPreemption):
        PEMSVM(cfg).fit_chunks(faults.kill_after_chunks(_five_chunks, 18),
                               K)
    payload = resume_mod.load_snapshot(Checkpointer(d))
    assert payload["in_pass"] and payload["chunk_idx"] > 0
    assert payload["state"].shape == (3, K)   # chunk width, chain-major

    res = PEMSVM(cfg).fit_chunks(_five_chunks, K, resume_from=d)
    assert res.resumed_at is not None
    assert np.array_equal(ref.weights, res.weights)
    assert np.array_equal(ref.chain_weights, res.chain_weights)
    assert np.array_equal(ref.chain_std, res.chain_std)


# --------------------------------------------------------- Nystrom path
def test_nystrom_stream_kill_resume_bitwise(tmp_path):
    """The nonlinear path inherits elasticity: landmark selection is
    seed-deterministic and skipped when continuing, so the resumed
    phi-space fit matches the uninterrupted one bitwise."""
    rng = np.random.default_rng(0)
    Xc = rng.normal(size=(300, 6)).astype(np.float32)
    yc = np.where(np.linalg.norm(Xc[:, :2], axis=1) > 1.1, 1.0,
                  -1.0).astype(np.float32)
    kw = dict(formulation="KRN", algorithm="MC", task="CLS",
              driver="stream", chunk_rows=64, max_iters=10, min_iters=10,
              burnin=3, sigma=1.5)
    ref = NystromSVM(SVMConfig(**kw), n_landmarks=32, seed=1)
    rref = ref.fit(Xc, yc)

    d = str(tmp_path)
    pol = FaultPolicy(ckpt_dir=d, ckpt_every=2, ckpt_chunks=2)
    svm1 = NystromSVM(SVMConfig(**kw, fault=pol), n_landmarks=32, seed=1)
    with pytest.raises(faults.SimulatedPreemption):
        svm1.fit(Xc, yc, fault_hook=faults.kill_at_iteration(6))
    svm2 = NystromSVM(SVMConfig(**kw, fault=pol), n_landmarks=32, seed=1)
    res = svm2.fit(Xc, yc, resume_from=d)

    assert np.array_equal(rref.weights, res.weights)
    assert svm2.score(Xc, yc) == ref.score(Xc, yc) > 0.8

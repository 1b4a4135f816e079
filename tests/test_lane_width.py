"""The resident LIN path stores the model's X at the kernel's lane width.

``distributed.upload_rows`` appends zero columns to the model's X up to
``round_up(K, 128)``, and ``fused_stats`` reads that X in place with a
K-long w where a K-wide X is padded on every call. The kernel's outputs
on the lane-padded X are bitwise those on the K-wide X (interpret mode),
and a whole fit returns K-wide weights bitwise equal to the same fit
with X stored K wide: on one device, and on a (4,) data mesh of virtual
CPU devices in a child process (this process keeps its single device).
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src")]

from repro.core import NystromSVM, PEMSVM, SVMConfig  # noqa: E402
from repro.core.augment import pack_seed  # noqa: E402
from repro.data import make_blobs  # noqa: E402
from repro.kernels import ops  # noqa: E402

RNG = np.random.default_rng(7)

# (epilogue, chains, column window, rows): rows 1024 fill whole row
# blocks, so the lane-padded X is read with no pad at all; 1000 rows
# pad rows on both sides.
KERNEL_CASES = [
    ("em_hinge", 1, None, 1024),
    ("em_hinge", 1, None, 1000),
    ("mc_hinge", 1, None, 1024),
    ("em_svr", 1, None, 1024),
    ("mc_svr", 1, None, 1024),
    ("em_hinge", 1, (150, 100), 1024),
    ("mc_hinge", 2, None, 1024),
]


def _stats(X, rho, beta, w, epilogue, window, seed):
    return ops.fused_stats(
        X, rho, beta, w, epilogue=epilogue, eps_ins=0.3,
        col_window=window, seed=seed, backend="interpret")


@pytest.mark.parametrize("epilogue,chains,window,n", KERNEL_CASES,
                         ids=[f"{e}-c{c}-{'win' if w else 'full'}-{n}"
                              for e, c, w, n in KERNEL_CASES])
def test_lane_padded_x_is_bitwise_the_k_wide_call(epilogue, chains,
                                                  window, n):
    K = 300
    Kp = -(-K // ops.LANE) * ops.LANE
    X = RNG.normal(size=(n, K)).astype(np.float32)
    Xw = np.concatenate([X, np.zeros((n, Kp - K), np.float32)], axis=1)
    rho = RNG.normal(size=(n,)).astype(np.float32)
    beta = RNG.choice([-1.0, 1.0], size=(n,)).astype(np.float32)
    shape = (K, chains) if chains > 1 else (K,)
    w = (0.05 * RNG.normal(size=shape)).astype(np.float32)
    seed = (pack_seed(jax.random.PRNGKey(3)) if epilogue.startswith("mc")
            else None)
    win = None if window is None else (jnp.int32(window[0]), window[1])
    want = _stats(jnp.asarray(X), rho, beta, w, epilogue, win, seed)
    got = _stats(jnp.asarray(Xw), rho, beta, w, epilogue, win, seed)
    assert len(got) == len(want)
    for g, v in zip(got, want):
        g, v = np.asarray(g), np.asarray(v)
        assert g.shape == v.shape
        assert np.array_equal(g.view(np.uint32), v.view(np.uint32))
    if n % 512 == 0:
        # The lane-padded X reaches the kernel as it is: nothing pads it.
        hlo = jax.jit(lambda X: _stats(X, rho, beta, w, epilogue, win,
                                       seed)).lower(Xw).as_text()
        assert not re.search(rf"stablehlo\.pad.*tensor<{n}x{Kp}xf32>", hlo)


D = 12
ITERS = 4
MC = {"algorithm": "MC", "rng": "fused", "burnin": 1}
FIT_CASES = [
    ("scan", {}),
    ("loop", {}),
    ("scan", MC),
    ("scan", dict(MC, n_chains=2)),
    ("scan", {"task": "MLT", "num_classes": 3}),
    ("scan", {"task": "SVR", **MC}),
    ("scan", {"pad_features": 8}),
]


def _labels(X, task):
    if task == "MLT":
        return (np.abs(3 * X[:, 0]).astype(np.int32) % 3)
    if task == "SVR":
        return 0.5 * X[:, 1]
    return None


def weight_pair(driver, n, mesh=None, **kw):
    """The weights of the fit with X stored at the lane width, and of
    the same fit with X stored K wide."""
    X, y = make_blobs(n, D, seed=5)
    t = _labels(X, kw.get("task", "CLS"))
    y = y if t is None else t
    cfg = SVMConfig(max_iters=ITERS, min_iters=ITERS, tol=0.0,
                    scan_chunk=2, driver=driver, backend="interpret", **kw)
    w = np.asarray(PEMSVM(cfg, mesh=mesh).fit(X, y).weights)
    with mock.patch.object(ops, "fused_stats_width", lambda k, **_: k):
        w_ref = np.asarray(PEMSVM(cfg, mesh=mesh).fit(X, y).weights)
    return w, w_ref


def bitwise(a, b):
    return a.shape == b.shape and bool(
        np.array_equal(a.view(np.uint32), b.view(np.uint32)))


@pytest.mark.parametrize("driver,kw", FIT_CASES,
                         ids=[f"{d}-" + ("-".join(f"{k}{v}" for k, v
                                                  in kw.items()) or "em")
                              for d, kw in FIT_CASES])
def test_fit_weights_bitwise_the_k_wide_fit(driver, kw):
    w, w_ref = weight_pair(driver, 600, **kw)
    K = D + 1 + (-(D + 1)) % kw.get("pad_features", 1)
    want = {"MLT": (3, K)}.get(kw.get("task"), (K,))
    assert w.shape == want
    assert bitwise(w, w_ref)


def mesh_summary(n):
    """The four-device checks, in JSON's types (run in the child)."""
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    out = {}
    for name, kw in [("em", {}), ("mlt", {"task": "MLT", "num_classes": 3}),
                     ("chains", dict(MC, n_chains=2))]:
        w, w_ref = weight_pair("scan", n, mesh, **kw)
        out[name] = [list(w.shape), bitwise(w, w_ref)]
    return out


def test_fit_weights_bitwise_the_k_wide_fit_four_device_mesh():
    code = (
        "import json, sys; sys.path[:0] = [%r, %r]\n"
        "import test_lane_width as t\n"
        "print(json.dumps(t.mesh_summary(1003)))\n"
        % (str(ROOT / "src"), str(ROOT / "tests")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    s = json.loads(p.stdout.strip().splitlines()[-1])
    assert s == {"em": [[D + 1], True], "mlt": [[3, D + 1], True],
                 "chains": [[D + 1], True]}, s


def test_fused_stats_width_follows_the_route():
    """X is stored at the lane width where ``fused_stats`` takes the
    full-width kernel, and K wide where it takes the jnp oracle or the
    split fallback past the VMEM cap."""
    assert ops.fused_stats_width(801, backend="pallas") == 896
    assert ops.fused_stats_width(785, backend="interpret") == 896
    assert ops.fused_stats_width(896, backend="pallas") == 896
    assert ops.fused_stats_width(801, backend="ref") == 801
    K = ops.FUSED_STATS_MAX_K + 1
    assert ops.fused_stats_width(K, backend="pallas") == K
    assert ops.fused_stats_width(1000, backend="pallas",
                                 epilogue="mc_hinge", n_chains=8) == 1000


def test_resident_x_is_stored_at_the_width_the_kernel_reads():
    """``PEMSVM._prepare`` stores X-space LIN rows at the lane width on a
    kernel backend, and keeps the state K wide; the jnp oracle's X and
    a Nystrom fit's raw rows keep their widths."""
    X, y = make_blobs(64, D, seed=5)
    for backend, width in [("interpret", ops.LANE), ("ref", D + 1)]:
        data, _, state = PEMSVM(SVMConfig(backend=backend))._prepare(X, y)
        assert data.X.shape == (64, width) and state.shape == (D + 1,)
    svm = NystromSVM(SVMConfig(formulation="KRN", backend="interpret",
                               max_iters=2), n_landmarks=8)
    svm.fit(X, y)
    data, _, _ = svm.svm._prepare(X, y)
    assert data.X.shape == (64, D)

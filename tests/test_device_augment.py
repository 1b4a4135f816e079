"""The resident LIN path builds the model's X on the device.

``PEMSVM.fit`` uploads the caller's rows once and appends the bias
column, any ``pad_features`` zero columns and the zero pad rows there
(``distributed.upload_rows``). The oracle is the same fit with the bias
column appended by hand on the host and ``add_bias=False``: the weights
must agree bitwise, on one device and on a (4,) data mesh of virtual CPU
devices in a child process (this process keeps its single device). The
``host_bytes`` arg of the ``pemsvm.bias`` and ``pemsvm.pad_rows`` spans
counts the bytes of X the host copied.
"""
import glob
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src")]

from repro.core import PEMSVM, SVMConfig  # noqa: E402
from repro.data import make_blobs  # noqa: E402

D = 12
ITERS = 5
MC = {"algorithm": "MC", "rng": "fused", "burnin": 2}


def config(driver, **kw):
    return SVMConfig(max_iters=ITERS, min_iters=ITERS, tol=0.0,
                     scan_chunk=2, driver=driver, chunk_rows=256, **kw)


def bitwise_pair(driver, n, mesh=None, **kw):
    """(device-built fit, host-built oracle) weights agree bitwise."""
    X, y = make_blobs(n, D, seed=3)
    w = PEMSVM(config(driver, **kw), mesh=mesh).fit(X, y).weights
    Xb = np.hstack([X, np.ones((n, 1), np.float32)])
    w_ref = PEMSVM(config(driver, add_bias=False, **kw),
                   mesh=mesh).fit(Xb, y).weights
    w, w_ref = np.asarray(w), np.asarray(w_ref)
    return w.shape == w_ref.shape and bool(
        np.array_equal(w.view(np.uint32), w_ref.view(np.uint32)))


def host_bytes(log_dir, driver, n, X_dtype=np.float32, mesh=None):
    """{span name: host_bytes} of one profiled fit."""
    X, y = make_blobs(n, D, seed=3)
    svm = PEMSVM(config(driver), mesh=mesh)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        svm.fit(X.astype(X_dtype), y)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("pemsvm.bias", "pemsvm.pad_rows"):
                    out[e.name] = dict(e.stats)["host_bytes"]
    return out


# (driver, rows, config): rows 1000 pads 0 rows, 1003 pads 5.
ONE_DEVICE = [
    ("scan", 1000, {}),
    ("scan", 1003, {}),
    ("loop", 1000, {}),
    ("loop", 1003, {}),
    ("scan", 1003, {"pad_features": 8}),
    ("scan", 1000, MC),
    ("loop", 1003, MC),
]


@pytest.mark.parametrize("driver,n,kw", ONE_DEVICE,
                         ids=[f"{d}-{n}-{'-'.join(kw) or 'em'}"
                              for d, n, kw in ONE_DEVICE])
def test_device_bias_matches_host_bias(driver, n, kw):
    assert bitwise_pair(driver, n, **kw)


def mesh_summary(n, log_dir):
    """The four-device checks, in JSON's types (run in the child)."""
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    return {"em": bitwise_pair("scan", n, mesh),
            "mc": bitwise_pair("scan", n, mesh, **MC),
            "pad_features": bitwise_pair("scan", n, mesh, pad_features=8),
            "host_bytes": host_bytes(log_dir, "scan", n, mesh=mesh)}


@pytest.mark.parametrize("n", [1024, 1003])
def test_device_bias_matches_host_bias_four_device_mesh(n, tmp_path):
    code = (
        "import json, sys; sys.path[:0] = [%r, %r]\n"
        "import test_device_augment as t\n"
        "print(json.dumps(t.mesh_summary(%d, %r)))\n"
        % (str(ROOT / "src"), str(ROOT / "tests"), n, str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    s = json.loads(p.stdout.strip().splitlines()[-1])
    assert s["em"] and s["mc"] and s["pad_features"], s
    # Rows pad to a multiple of 4 x 8: 1024 rows pad none; 1003 pad to
    # 1024, and the last shard of 256 rows alone is copied into zeros.
    Np = -(-n // 32) * 32
    last_block = 0 if Np == n else (Np // 4) * D * 4
    assert s["host_bytes"] == {"pemsvm.bias": 0,
                               "pemsvm.pad_rows": last_block}, s


def test_host_bytes_zero_on_resident_float32(tmp_path):
    assert host_bytes(tmp_path, "scan", 1000) == {
        "pemsvm.bias": 0, "pemsvm.pad_rows": 0}


def test_host_bytes_counts_the_dtype_cast(tmp_path):
    hb = host_bytes(tmp_path, "loop", 1003, X_dtype=np.float64)
    assert hb == {"pemsvm.bias": 1003 * D * 4, "pemsvm.pad_rows": 0}


def test_host_bytes_on_stream_driver(tmp_path):
    hb = host_bytes(tmp_path, "stream", 1000)
    # the bias column's copy, then pad_rows' copy to 4 chunks of 256 rows
    assert hb == {"pemsvm.bias": 1000 * (D + 1) * 4,
                  "pemsvm.pad_rows": 1024 * (D + 1) * 4}

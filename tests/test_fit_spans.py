"""``PEMSVM.fit`` writes its host spans into the profiler's trace.

A small scan fit is profiled on the CPU, inside a ``bench.fit``
annotation as the benchmark writes it, and read back with the
benchmark's own trace loader: on one device here, and on a (4,) data
mesh of virtual CPU devices in a child process (this process keeps its
single device).
"""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracefile  # noqa: E402
from repro.core import NystromSVM, PEMSVM, SVMConfig  # noqa: E402
from repro.data import make_blobs  # noqa: E402

MAX_ITERS, SCAN_CHUNK = 7, 3
PREP = ("pemsvm.bias", "pemsvm.labels", "pemsvm.pad_rows", "pemsvm.upload")


def fit_once(driver, mesh, log_dir=None):
    """(weights, n_host_syncs, the fit's host spans in start order); the
    spans are read from a profile of the fit when ``log_dir`` is given."""
    X, y = make_blobs(1000, 12, seed=3)
    svm = PEMSVM(SVMConfig(max_iters=MAX_ITERS, min_iters=MAX_ITERS,
                           scan_chunk=SCAN_CHUNK, tol=0.0, driver=driver),
                 mesh=mesh)
    if log_dir is None:
        res = svm.fit(X, y)
        return np.asarray(res.weights), res.n_host_syncs, []
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with TraceAnnotation("bench.fit"):
            res = svm.fit(X, y)
    finally:
        jax.profiler.stop_trace()
    tr = tracefile.load(str(log_dir))
    (fit,) = tr.fits
    spans = sorted(((e.name, e.start, e.end) for e in tr.host
                    if e.name.startswith("pemsvm.")
                    and fit.start <= e.start and e.end <= fit.end),
                   key=lambda s: s[1])
    return np.asarray(res.weights), res.n_host_syncs, spans


def summary(driver, mesh, log_dir):
    """Everything the checks below need, in JSON's types."""
    w_off, _, _ = fit_once(driver, mesh)
    w_on, syncs, spans = fit_once(driver, mesh, log_dir)
    return {"bitwise": bool(np.array_equal(w_off.view(np.uint32),
                                           w_on.view(np.uint32))),
            "syncs": syncs, "spans": spans}


def check_scan_spans(s):
    spans = [tuple(x) for x in s["spans"]]
    names = [n for n, _, _ in spans]
    fits = [x for x in spans if x[0] == "pemsvm.fit"]
    assert len(fits) == 1, names
    _, lo, hi = fits[0]
    assert all(lo <= a and b <= hi for _, a, b in spans), spans

    def first(name):
        return next(a for n, a, _ in spans if n == name)

    def last_end(name):
        return max(b for n, _, b in spans if n == name)

    for name in PREP + ("pemsvm.chunk", "pemsvm.dispatch", "pemsvm.sync",
                        "pemsvm.finalize"):
        assert name in names, (name, names)
    order = list(PREP) + ["pemsvm.chunk", "pemsvm.finalize"]
    for a, b in zip(order, order[1:]):
        assert last_end(a) <= first(b), (a, b, spans)
    chunks = [x for x in spans if x[0] == "pemsvm.chunk"]
    assert len(chunks) == math.ceil(MAX_ITERS / SCAN_CHUNK)
    for child in ("pemsvm.dispatch", "pemsvm.sync"):
        kids = [x for x in spans if x[0] == child]
        assert len(kids) == len(chunks)
        assert all(ca <= a and b <= cb
                   for (_, a, b), (_, ca, cb) in zip(kids, chunks))
    assert names.count("pemsvm.sync") == s["syncs"]
    assert s["bitwise"], "weights differ with the profiler on"


def test_scan_fit_spans_one_device(tmp_path):
    check_scan_spans(summary("scan", None, tmp_path))


def test_loop_fit_has_the_shared_spans(tmp_path):
    s = summary("loop", None, tmp_path)
    names = [n for n, _, _ in s["spans"]]
    assert names[0] == "pemsvm.fit"
    assert [n for n in names if n in PREP] == [
        "pemsvm.bias", "pemsvm.labels", "pemsvm.pad_rows",
        "pemsvm.upload", "pemsvm.upload"]
    assert "pemsvm.chunk" not in names
    assert s["bitwise"]


def test_scan_fit_spans_four_device_mesh(tmp_path):
    code = (
        "import json, sys; sys.path[:0] = [%r, %r, %r]\n"
        "import jax\n"
        "from jax.sharding import AxisType\n"
        "import test_fit_spans as t\n"
        "mesh = jax.make_mesh((4,), ('data',), "
        "axis_types=(AxisType.Auto,))\n"
        "print(json.dumps(t.summary('scan', mesh, %r)))\n"
        % (str(ROOT), str(ROOT / "src"), str(ROOT / "tests"),
           str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    check_scan_spans(json.loads(p.stdout.strip().splitlines()[-1]))


def upload_lane_cols(log_dir, svm, X, y) -> list:
    """The ``lane_cols`` arg of each ``pemsvm.upload`` span that has one,
    in one profiled fit."""
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        svm.fit(X, y)
    finally:
        jax.profiler.stop_trace()
    path = next(Path(log_dir).rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    return [dict(e.stats)["lane_cols"] for plane in pd.planes
            for line in plane.lines for e in line.events
            if e.name == "pemsvm.upload" and "lane_cols" in dict(e.stats)]


@pytest.mark.parametrize("kw,lane_cols", [
    ({"backend": "interpret"}, 128 - 13),
    ({"backend": "interpret", "pad_features": 8}, 128 - 16),
    ({"backend": "ref"}, 0),
    ({"backend": "interpret", "formulation": "KRN"}, 0),
])
def test_upload_span_counts_lane_columns(tmp_path, kw, lane_cols):
    """12 features and the bias column: the kernel backends store X at
    the lane width, 128; the jnp oracles and the Nystrom route's raw
    rows keep their width."""
    X, y = make_blobs(512, 12, seed=3)
    cfg = SVMConfig(max_iters=2, min_iters=2, tol=0.0, **kw)
    svm = (NystromSVM(cfg, n_landmarks=16)
           if cfg.formulation == "KRN" else PEMSVM(cfg))
    assert upload_lane_cols(tmp_path, svm, X, y) == [lane_cols]

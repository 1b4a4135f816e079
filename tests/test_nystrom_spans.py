"""``NystromSVM.fit`` writes its host phases into the profiler's trace.

A small fit is profiled on the CPU inside a ``bench.fit`` annotation, as
the benchmark writes it, and read back with the benchmark's own trace
loader: one ``nystrom.landmarks`` and one ``nystrom.projection`` span
lie inside the fit, in that order, before ``pemsvm.fit``. A fit that
continues from a warm start draws no landmarks and computes no
projection, but still writes the landmarks span.
"""
import sys
from pathlib import Path

import jax
import numpy as np
from jax.profiler import ProfileOptions, TraceAnnotation

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracefile  # noqa: E402
from repro.core import NystromSVM, SVMConfig  # noqa: E402
from repro.data import make_blobs  # noqa: E402

NYSTROM = ("nystrom.landmarks", "nystrom.projection")


def traced_fits(log_dir, warm=False):
    """(the fitted model, each of two fits' spans named nystrom.* or
    pemsvm.fit as (name, start, end) in start order); with ``warm`` the
    second fit warm-starts from the first."""
    X, y = make_blobs(600, 6, seed=5)
    svm = NystromSVM(SVMConfig.from_options("KRN-EM-CLS", max_iters=3,
                                            min_iters=3, tol=0.0),
                     n_landmarks=24, seed=4)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with TraceAnnotation("bench.fit"):
            res = svm.fit(X, y)
        with TraceAnnotation("bench.fit"):
            svm.fit(X, y, **({"warm_start": res} if warm else {}))
    finally:
        jax.profiler.stop_trace()
    tr = tracefile.load(str(log_dir))
    return svm, [sorted(((e.name, e.start, e.end) for e in tr.host
                         if (e.name in NYSTROM or e.name == "pemsvm.fit")
                         and fit.start <= e.start and e.end <= fit.end),
                        key=lambda s: s[1])
                 for fit in tr.fits]


def test_every_fit_holds_its_spans_before_pemsvm_fit(tmp_path):
    svm, fits = traced_fits(tmp_path)
    assert len(fits) == 2
    for spans in fits:
        assert [n for n, _, _ in spans] == [*NYSTROM, "pemsvm.fit"]
        for (_, _, end), (_, start, _) in zip(spans, spans[1:]):
            assert end <= start, spans
    assert svm._landmarks.shape == (24, 6)


def test_a_warm_start_draws_no_landmarks(tmp_path):
    _, fits = traced_fits(tmp_path, warm=True)
    assert [n for n, _, _ in fits[0]] == [*NYSTROM, "pemsvm.fit"]
    assert [n for n, _, _ in fits[1]] == ["nystrom.landmarks", "pemsvm.fit"]

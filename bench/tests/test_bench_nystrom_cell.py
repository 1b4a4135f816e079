"""The two one-chip cells added as files: ``covtype-nys-fit`` (NystromSVM,
KRN-EM-CLS) and ``dna-fit`` (PEMSVM on dna's rows, one chip).

At a small size on the CPU (2,048 rows x 54, m = 46, the cell's own
limits): the covtype cell resolves, fits whole windows and reads
``correct`` against ``nys_em_cls``; the reference draws the landmarks the
program draws; the control and every counted fault read above a limit;
``dna-fit``'s rows are ``dna-fit-dp4``'s; ``covtype_like`` has the
source's columns; the span readers return the spans' mean."""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks  # noqa: E402
from bench.data import covtype_like  # noqa: E402
from bench.reference import common, nys_em_cls  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402

CELL = "covtype-nys-fit"
ROWS, M, ITERS = 2048, 46, 4
SEED = 2**35 + 3


def small_job(seed=SEED):
    """The cell at ROWS rows and M landmarks; lam stays the source's 2
    (the cell's rows are the source's, so it is never scaled)."""
    job = harness.resolve(CELL, seed, rows_per_chip=ROWS, iters=ITERS)
    return dataclasses.replace(job, config=dict(job.config, n_landmarks=M,
                                                lam=2.0))


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def test_covtype_cell_resolves_at_the_source_shape():
    job = harness.resolve(CELL, SEED)
    assert job.estimator == "NystromSVM" and job.chips == 1
    assert job.rows == job.config["source_rows"] == 522_910
    assert job.config["n_landmarks"] == int(np.ceil(np.sqrt(job.rows)))
    assert job.width == 725 and job.iters == 32
    assert job.config["lam"] == 2.0
    cfg = harness.svm_config(job)
    assert cfg.options == "KRN-EM-CLS" and cfg.sigma == 1.0
    assert cfg.jitter == job.config["jitter"]
    names = [m["name"] for m in job.per_layer]
    assert {"landmarks_ms", "projection_ms", "fused_stats_ms",
            "fused_stats_roofline"} <= set(names)
    assert "allreduce_ms" not in names


def test_covtype_window_fits_whole_and_is_correct():
    job = small_job()
    result = load_run().run_cell(job, 0.01, trace=False)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["checks"]) == set(job.limits)
    assert result["correct"], result["checks"]


def test_reference_draws_the_programs_landmarks():
    job = small_job()
    X, t = harness.make_data(job, None)
    X = np.asarray(X)
    svm = harness.estimator(job, None)
    svm.fit(X, np.asarray(t))
    idx = nys_em_cls.landmark_rows(ROWS, M, job.fit_seed)
    np.testing.assert_array_equal(svm._landmarks, X[idx])


def control_and_faults():
    """(name, check numbers) for the control and each counted fault,
    against the reference, at the cell's limits."""
    job = small_job(2**34 + 11)
    X3, t3 = harness.reference_data(job, None)
    ref = harness.reference_fit(job, X3, t3)
    runs = {"control": {"prec": common.CONTROL}}
    runs.update({f: {"fault": f} for f in nys_em_cls.FAULTS
                 if f != "no_exchange"})
    for name, kw in runs.items():
        nums = harness.compare(job, *harness.reference_fit(job, X3, t3, **kw),
                               *ref)
        yield name, {k: {"value": v, "limit": job.limits[k]}
                     for k, v in nums.items()}


@pytest.fixture(scope="module")
def readings():
    return dict(control_and_faults())


@pytest.mark.parametrize("name", ["control", "unchanged", "half_batch",
                                  "altered_answer", "other_landmarks"])
def test_control_and_faults_are_not_correct(readings, name):
    assert not harness.passed(readings[name]), readings[name]


def test_dna_fit_rows_are_dna_fit_dp4s():
    one = harness.resolve("dna-fit", 7, rows_per_chip=4096)
    four = harness.resolve("dna-fit-dp4", 7, rows_per_chip=1024)
    assert one.config == four.config and one.rows == four.rows
    a, ta = harness.reference_data(one, None)
    b, tb = harness.reference_data(four, None)
    assert a.shape == (1, 4096, 801) and b.shape == (4, 1024, 801)
    np.testing.assert_array_equal(np.asarray(a).reshape(4096, -1),
                                  np.asarray(b).reshape(4096, -1))
    np.testing.assert_array_equal(np.asarray(ta).ravel(),
                                  np.asarray(tb).ravel())


def test_dna_fit_takes_dna_fit_dp4s_limits_but_a_tighter_obj_first():
    # Same data, N and lam; on one chip the half-batch fault reads nearer
    # the program at the first iteration, so obj_first is set anew.
    one = dict(harness.resolve("dna-fit", 1).limits)
    four = dict(harness.resolve("dna-fit-dp4", 1).limits)
    assert one.pop("obj_first") < four.pop("obj_first")
    assert one == four


def test_covtype_like_has_the_sources_columns():
    X, y = covtype_like.make(harness.data_key(2**40 + 5), 20_000, 54, 2)
    X, y = np.asarray(X), np.asarray(y)
    assert X.shape == (20_000, 54) and X.min() >= 0.0 and X.max() <= 1.0
    wild, soil = X[:, 10:14], X[:, 14:]
    for block in (wild, soil):
        assert set(np.unique(block).tolist()) == {0.0, 1.0}
        np.testing.assert_array_equal(block.sum(1), 1.0)
    np.testing.assert_allclose(wild.mean(0), covtype_like.WILDERNESS,
                               atol=0.01)
    assert np.all(np.diff(soil.mean(0)[:8]) < 0)          # Zipf-like
    assert set(np.unique(y).tolist()) == {-1.0, 1.0}
    assert abs((y > 0).mean() - covtype_like.POSITIVE) < 0.002
    with pytest.raises(ValueError, match="54"):
        covtype_like.make(harness.data_key(1), 16, 55, 2)


def nystrom_fit(t0, landmarks, projection):
    return [Event("bench.fit", t0, 3.0),
            Event("nystrom.landmarks", t0 + 0.01, landmarks),
            Event("nystrom.projection", t0 + 0.1, projection),
            Event("pemsvm.fit", t0 + 0.5, 2.0)]


@pytest.mark.parametrize("name,want", [("landmarks_ms", (20 + 40) / 2),
                                       ("projection_ms", (200 + 300) / 2)])
def test_span_readers_return_the_spans_mean(name, want):
    host = (nystrom_fit(0.0, 0.02, 0.2) + nystrom_fit(10.0, 0.04, 0.3)
            + [Event("nystrom.projection", 5.0, 1.0)])    # between fits
    trace = Trace({0: []}, {0: []}, host)
    ctx = harness.Context(trace, harness.resolve(CELL, 1),
                          peaks.peak("TPU v5 lite"))
    assert ctx.value(name) == pytest.approx(want)
    bare = Trace({0: []}, {0: []}, [Event("bench.fit", 0.0, 3.0)])
    ctx = harness.Context(bare, harness.resolve(CELL, 1),
                          peaks.peak("TPU v5 lite"))
    assert ctx.value(name) is None

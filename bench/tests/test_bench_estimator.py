"""A configuration names its estimator: a NystromSVM configuration in a
root of its own resolves, builds, lays out its reference rows, counts its
work and fits whole windows on the CPU; the harness refuses what it
cannot build, and ``svm_config`` applies or checks every SVMConfig key a
file states."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "nys-tiny"
ROWS, D, M = 512, 8, 16

NYSTROM = {
    "source": "arXiv:1512.07716 (Perkins et al.), Sec 4.3, KRN-EM-CLS "
              "with Nystrom features",
    "factory": "news20_krn_em_cls",
    "estimator": "NystromSVM",
    "n_landmarks": M,
    "options": "KRN-EM-CLS",
    "dataset": "dna_like",
    "reference": "lin_em_cls",
    "n_features": D,
    "add_bias": True,
    "num_classes": 2,
    "C": 1.0,
    "source_rows": ROWS,
    "kernel": "rbf",
    "sigma": 1.0,
    "tol": 0.0,
    "dtype": "float32",
    "reduced": {"lam": "scaled by rows", "tol": "fixed work"},
}


def write_root(root: Path, config: dict, more: dict | None = None) -> Path:
    """A benchmark root of the cell ``CELL`` with ``config``, and one
    one-chip cell more for each of ``more`` (cell name -> configuration),
    all on one traffic; the data generators come from the checkout."""
    for sub in ("configs", "traffic", "checks"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    cells = dict({CELL: config}, **(more or {}))
    (root / "BENCHMARK.json").write_text(json.dumps(dict(
        SPEC, configs=[{"name": f"c-{w}", "source": c["source"],
                        "file": f"bench/configs/c-{w}.json", "reduced": [],
                        "why": "tiny"} for w, c in cells.items()],
        workloads=[{"name": w, "config": f"c-{w}", "traffic": "t",
                    "chips": 1, "why": "tiny"} for w in cells])))
    for w, c in cells.items():
        (root / "bench" / "configs" / f"c-{w}.json").write_text(
            json.dumps(c))
        (root / "bench" / "checks" / f"{w}.json").write_text(json.dumps(
            {"limits": {"fits_differ": 0, "nonfinite_fits": 0}}))
    (root / "bench" / "traffic" / "t.json").write_text(json.dumps(
        {"kind": "fit", "rows_per_chip": ROWS, "iters": 3,
         "mesh": {"data": 1}}))
    return root


def nystrom_job(tmp_path, seed=2**40 + 3, traffic=None, **changes):
    config = dict(NYSTROM, **changes)
    return harness.resolve(CELL, seed, root=write_root(tmp_path, config),
                           **(traffic or {}))


def test_nystrom_configuration_resolves_and_builds(tmp_path):
    from repro.core import NystromSVM

    job = nystrom_job(tmp_path)
    assert job.estimator == "NystromSVM"
    assert job.width == M + 1
    svm = harness.estimator(job, None)
    assert isinstance(svm, NystromSVM)
    assert svm.n_landmarks == M and svm.seed == job.fit_seed == 3
    assert svm.config.options == "KRN-EM-CLS" and svm.sigma == 1.0
    assert svm.svm.config.max_iters == svm.svm.config.min_iters == 3


def test_nystrom_reference_rows_carry_no_bias(tmp_path):
    job = nystrom_job(tmp_path)
    X3, t3 = harness.reference_data(job, None)
    assert X3.shape == (1, ROWS, D) and t3.shape == (1, ROWS)
    X, _ = harness.make_data(job, None)
    np.testing.assert_array_equal(np.asarray(X3[0]), np.asarray(X))


def test_nystrom_work_counts_featurisation(tmp_path):
    job = nystrom_job(tmp_path)
    assert work.iteration_flops(job) == (work.pass_flops(ROWS, M + 1)
                                         + 2 * ROWS * M * (D + M))


def test_nystrom_window_fits_whole(tmp_path):
    job = nystrom_job(tmp_path)
    X, t = harness.make_data(job, None)
    X, t = np.asarray(X), np.asarray(t)
    svm = harness.estimator(job, None)
    win = harness.run_window(lambda: svm.fit(X, t), 0.0)
    assert len(win.fit_s) == 1 and win.nonfinite == 0
    res = win.last
    assert res.n_iters == job.iters and len(res.objective) == job.iters
    assert np.asarray(res.weights).shape == (job.width,)
    again = harness.estimator(job, None).fit(X, t)
    assert harness.digest(np.asarray(again.weights)) == win.digests[0]


@pytest.mark.parametrize("changes,key", [
    ({"estimator": "SVC"}, "estimator"),
    ({"n_landmarks": None}, "n_landmarks"),
    ({"n_landmarks": ROWS + 1}, "n_landmarks"),
    ({"options": "LIN-EM-CLS", "factory": "dna_lin_em_cls"}, "options"),
    ({"add_bias": False}, "add_bias"),
])
def test_resolve_refuses_what_it_cannot_build(tmp_path, changes, key):
    with pytest.raises(ValueError, match=key):
        nystrom_job(tmp_path, **changes)


def test_svm_config_checks_a_stated_key(tmp_path):
    job = nystrom_job(tmp_path, sigma=2.0)
    with pytest.raises(ValueError, match="sigma is 1.0"):
        harness.svm_config(job)


def test_svm_config_applies_a_reduced_key(tmp_path):
    reduced = dict(NYSTROM["reduced"], sigma="1.0 -> 2.0")
    job = nystrom_job(tmp_path, sigma=2.0, reduced=reduced)
    assert harness.svm_config(job).sigma == 2.0
    assert harness.estimator(job, None).sigma == 2.0


def test_svm_config_checks_c_unless_reduced(tmp_path):
    job = nystrom_job(tmp_path, C=0.5)
    with pytest.raises(ValueError, match="lam"):
        harness.svm_config(job)
    reduced = dict(NYSTROM["reduced"], C="1 -> 0.5")
    job = nystrom_job(tmp_path, C=0.5, reduced=reduced)
    assert harness.svm_config(job).lam == pytest.approx(4.0)


@pytest.mark.parametrize("key,value", [("eps", 1e-3), ("eps_ins", 0.3),
                                       ("kernel", "linear")])
def test_svm_config_checks_every_stated_field(tmp_path, key, value):
    job = nystrom_job(tmp_path, **{key: value})
    with pytest.raises(ValueError, match=key):
        harness.svm_config(job)


def test_existing_cells_build_pemsvm():
    from repro.core import PEMSVM

    for cell in (w["name"] for w in SPEC["workloads"]):
        job = harness.resolve(cell, 1)
        assert job.estimator == "PEMSVM"
        assert type(harness.estimator(job, None)) is PEMSVM


def test_added_cells_keep_every_metric_their_estimator_reads(tmp_path):
    # A later LIN cell and a NystromSVM cell added as files report the
    # same per-layer metrics as the one-chip cell there is, the kernel's
    # among them: which kernel they read follows from the estimator.
    lin = json.loads((ROOT / "bench/configs/dna-lin-em-cls.json").read_text())
    root = write_root(tmp_path, NYSTROM, {"lin-tiny": lin})
    want = [m["name"] for m in harness.resolve("mnist8m-fit", 1).per_layer]
    assert {"fused_stats_ms", "fused_stats_roofline"} <= set(want)
    for cell, est in ((CELL, "NystromSVM"), ("lin-tiny", "PEMSVM")):
        job = harness.resolve(cell, 1, root=root)
        assert job.estimator == est
        assert [m["name"] for m in job.per_layer] == want


def test_kernel_reader_reads_the_estimators_kernel(tmp_path):
    from bench import peaks
    from bench.tracefile import Event, Trace

    lin = json.loads((ROOT / "bench/configs/dna-lin-em-cls.json").read_text())
    root = write_root(tmp_path, NYSTROM, {"lin-tiny": lin})
    ops = [Event("%fused_stats.7 = f32[8] custom-call(f32[8] %x)", 1.0, 0.3),
           Event("%nystrom_fused_stats.3 = f32[8] custom-call(f32[8] %x)",
                 2.0, 0.5),
           Event("%nystrom_phi.2 = f32[8] custom-call(f32[8] %x)", 3.0, 0.1)]
    trace = Trace({0: ops}, {0: []}, [Event("bench.fit", 0.0, 4.0)])
    peak = peaks.peak("TPU v5 lite")
    for cell, secs in ((CELL, 0.5), ("lin-tiny", 0.3)):
        ctx = harness.Context(trace, harness.resolve(cell, 1, root=root),
                              peak)
        reader = ctx.metric("fused_stats_ms")
        assert reader.per_device(ctx) == {0: (pytest.approx(secs), 1)}
        assert ctx.value("fused_stats_ms") == pytest.approx(secs / 3 * 1e3)


def test_nystrom_kernel_call_is_compute_bound_at_covtype(tmp_path):
    # covtype.binary: 522,910 rows x 54, m = ceil(sqrt(N)) = 724.
    from bench import peaks

    n, d, m = 522_910, 54, 724
    job = nystrom_job(tmp_path, n_features=d, n_landmarks=m,
                      traffic={"rows_per_chip": n})
    k = m + 1
    flops, nbytes = work.stats_call(job, n)
    assert flops == (n * k * (k + 1) + 4.0 * n * k
                     + 2.0 * n * m * d + 2.0 * n * m * m)
    assert flops == pytest.approx(0.866e12, rel=1e-3)
    assert nbytes == (4.0 * n * d + 4.0 * m * (d + m) + 20.0 * n + 8.0 * k
                      + 4.0 * k * k)
    least, bound = harness.load_metric("fused_stats_roofline").least_seconds(
        job, peaks.peak("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(flops / 197e12)
    assert least == pytest.approx(4.4e-3, rel=1e-2)

"""The work counts behind the roofline and MFU metrics (``bench/work.py``),
against values worked out by hand and, for the cells, the values the
readers gave before the counts moved there; and the table of peaks."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, peaks, work  # noqa: E402

roofline = harness.load_metric("fused_stats_roofline")
V5E = peaks.peak("TPU v5 lite")


def test_dna_per_chip():
    n, k = 1_048_576, 801
    assert work.sigma_flops(n, k) == pytest.approx(6.74e11, rel=1e-3)
    # one float32 read of X dominates the call's bytes
    assert 4.0 * n * k == pytest.approx(3.36e9, rel=1e-3)
    assert work.call_bytes(n, k) == pytest.approx(
        4 * n * k + 20 * n + 8 * k + 4 * k * k)
    least, bound = work.roofline_seconds(work.call_flops(n, k),
                                         work.call_bytes(n, k), V5E)
    assert bound == "memory"
    assert least == pytest.approx(work.call_bytes(n, k) / 819e9)
    assert least == pytest.approx(4.13e-3, rel=1e-2)


def test_mnist8m_per_class_pass():
    n, k = 524_288, 785
    assert work.sigma_flops(n, k) == pytest.approx(3.24e11, rel=2e-3)
    assert 4.0 * n * k == pytest.approx(1.65e9, rel=3e-3)
    assert work.call_flops(n, k) == pytest.approx(
        n * k * (k + 1) + 4 * n * k)
    _, bound = work.roofline_seconds(work.call_flops(n, k),
                                     work.call_bytes(n, k), V5E)
    assert bound == "memory"


def test_iteration_flops_count_every_class_pass_once():
    job = harness.resolve("mnist8m-fit", 1)
    n, k = 524_288, 785
    one = n * k * (k + 1) + 4 * n * k + k ** 3 / 3 + 2 * k ** 2
    assert work.iteration_flops(job) == pytest.approx(10 * one)
    dp4 = harness.resolve("dna-fit-dp4", 1)
    n = 4 * dp4.rows_per_chip
    assert work.iteration_flops(dp4) == pytest.approx(
        n * 801 * 802 + 4 * n * 801 + 801 ** 3 / 3 + 2 * 801 ** 2)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v9 imaginary")
    assert V5E.flops_per_s == 197e12 and V5E.hbm_bytes_per_s == 819e9


# Each cell's estimator, statistic width, step_mfu's FLOPs an iteration and
# fused_stats_roofline's least time a call, as the readers computed them
# before the counts moved to bench/work.py: the same integers give the
# same floats.
CELLS = {"mnist8m-fit": (785, 3252996811916.667, 0.0020259111843711846),
         "dna-fit-dp4": (801, 677139547725.0, 0.0010350733431013432)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_counts_read_as_before(cell):
    width, flops, least = CELLS[cell]
    job = harness.resolve(cell, 2**40 + 3)
    assert job.estimator == "PEMSVM" and job.width == width
    assert work.iteration_flops(job) == flops
    assert roofline.least_seconds(job, V5E) == (least, "memory")

"""The work a fit asks of the chip, counted from the job's shapes,
whatever implements it. Each estimator's entry in ``harness.ESTIMATORS``
names its counts here: a class pass (``*_pass_flops``, for ``step_mfu``)
and one call of its fused statistic kernel (``*_call``, for
``fused_stats_roofline``). A configuration with another estimator or
kernel adds its counts here and an entry there, and leaves the readers
as they are.
"""
from __future__ import annotations


def sigma_flops(n: int, k: int) -> float:
    """Sigma's lower triangle over n rows of width k."""
    return float(n) * k * (k + 1)


def pass_flops(n: int, k: int) -> float:
    """One class pass of the linear statistic over n rows of width K
    (bias included): Sigma's lower triangle n K (K+1), margins and b
    4 n K, the Cholesky factor K^3 / 3 and two triangular solves 2 K^2.
    The replicated M-step is counted once, not once per chip."""
    return sigma_flops(n, k) + 4.0 * n * k + k ** 3 / 3.0 + 2.0 * k ** 2


def featurize_flops(n: int, m: int, d: int) -> float:
    """Nystrom features of n rows of width d against m landmarks: the
    distance GEMM 2 n m d and the projection by K_mm^{-1/2} 2 n m^2.
    The kernel's exponentials and the row norms are not counted (n m
    and n (m + d), under 1% of these at d + m >= 50)."""
    return 2.0 * n * m * (d + m)


def call_flops(n: int, k: int) -> float:
    """One call of ``kernels/fused_stats.py`` over n rows of width K:
    Sigma's lower triangle n K (K+1) plus margins and b 4 n K."""
    return float(n) * k * (k + 1) + 4.0 * n * k


def call_bytes(n: int, k: int) -> float:
    """One call of ``kernels/fused_stats.py``: one read of X at 4 bytes a
    value (the float32 the entry point receives), five per-row vectors
    read or written once (rho, beta, mask in; margin, gamma out), w read
    and b written (4 K each) and Sigma written once (4 K^2). A later
    narrower X stream that still passes ``correct`` needs this count
    revisited by a benchmark change."""
    return 4.0 * n * k + 4.0 * 5 * n + 4.0 * 2 * k + 4.0 * k * k


def lin_pass_flops(n: int, job) -> float:
    """PEMSVM: the linear pass at the job's width."""
    return pass_flops(n, job.width)


def nystrom_pass_flops(n: int, job) -> float:
    """NystromSVM: the linear pass at its phi-space width plus the
    featurisation, which the fused kernel repeats on every pass (phi is
    never stored)."""
    return pass_flops(n, job.width) + featurize_flops(
        n, job.config["n_landmarks"], job.config["n_features"])


def lin_call(n: int, job) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``fused_stats`` call over n rows."""
    return call_flops(n, job.width), call_bytes(n, job.width)


def nystrom_call(n: int, job) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``kernels/nystrom_phi.py``
    ``nystrom_fused_stats`` call over n rows of width D: ``fused_stats``'
    FLOPs at the phi-space width K = m + 1 plus the featurisation; bytes
    one float32 read of X at its own width D, the landmark strip (m D)
    and K_mm^{-1/2} (m^2) read once, and the same five row vectors, w, b
    and Sigma as ``fused_stats``."""
    k, m, d = job.width, job.config["n_landmarks"], job.config["n_features"]
    flops = call_flops(n, k) + featurize_flops(n, m, d)
    nbytes = (4.0 * n * d + 4.0 * m * (d + m) + 4.0 * 5 * n + 4.0 * 2 * k
              + 4.0 * k * k)
    return flops, nbytes


def class_pass_flops(job) -> float:
    """One class pass over all the job's rows, by its estimator."""
    return job.spec.pass_flops(job.rows, job)


def iteration_flops(job) -> float:
    """One iteration: M class passes for MLT, one otherwise."""
    return job.classes * class_pass_flops(job)


def stats_call(job, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call of the estimator's fused statistic
    kernel over n rows."""
    return job.spec.call_counts(n, job)


def roofline_seconds(flops: float, nbytes: float,
                     peak) -> tuple[float, str]:
    """(least time the chip could take for the work, which bound sets
    it): the larger of FLOPs over peak FLOP/s and bytes over HBM
    bandwidth."""
    t_flops = flops / peak.flops_per_s
    t_bytes = nbytes / peak.hbm_bytes_per_s
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops,
                                                           "compute")

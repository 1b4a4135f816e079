"""step_mfu: the FLOPs one iteration requires over the chips' peak in
the measured ``step_ms``.

What the algorithm needs per class pass over N rows of width K (bias
included), whatever implements it: Sigma's lower triangle N K (K+1),
margins and b 4 N K, the Cholesky factor K^3 / 3 and two triangular
solves 2 K^2. An MLT sweep is M class passes. The replicated M-step is
counted once, not once per chip.
"""


def sigma_flops(n: int, k: int) -> float:
    return float(n) * k * (k + 1)


def pass_flops(n: int, k: int) -> float:
    return sigma_flops(n, k) + 4.0 * n * k + k ** 3 / 3.0 + 2.0 * k ** 2


def iteration_flops(job) -> float:
    return job.classes * pass_flops(job.rows, job.width)


def read(ctx):
    step_ms = ctx.value("step_ms")
    if step_ms is None:
        return None
    peak = ctx.peak.flops_per_s * ctx.job.chips
    return 100.0 * iteration_flops(ctx.job) / (step_ms * 1e-3 * peak)

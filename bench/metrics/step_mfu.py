"""step_mfu: the FLOPs one iteration requires (``bench/work.py``
``iteration_flops``, by the configuration's estimator) over the chips'
peak in the measured ``step_ms``.
"""
from bench import work


def read(ctx):
    step_ms = ctx.value("step_ms")
    if step_ms is None:
        return None
    peak = ctx.peak.flops_per_s * ctx.job.chips
    return 100.0 * work.iteration_flops(ctx.job) / (step_ms * 1e-3 * peak)

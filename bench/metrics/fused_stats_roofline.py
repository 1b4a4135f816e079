"""fused_stats_roofline: the least time one kernel call could take on
this chip over the time a call took (mean per call, slowest device).

The kernel is the one ``fused_stats_ms`` reads, the estimator's fused
statistic kernel. The least time is ``bench/work.py``'s roofline of one
call's FLOPs and bytes (``stats_call``) over the n rows a chip holds.
"""
from bench import work


def least_seconds(job, peak) -> tuple[float, str]:
    """(least time of one call over a chip's rows, which bound sets
    it)."""
    return work.roofline_seconds(*work.stats_call(job, job.rows_per_chip),
                                 peak)


def read(ctx):
    calls = [(s / n) for s, n in
             ctx.metric("fused_stats_ms").per_device(ctx).values() if n]
    if not calls:
        return None
    least, _ = least_seconds(ctx.job, ctx.peak)
    return 100.0 * least / max(calls)

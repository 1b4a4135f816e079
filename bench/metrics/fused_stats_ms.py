"""fused_stats_ms: device time of the fused statistic kernel per
iteration (M calls per MLT sweep), on the slowest device.

The kernel is the one the configuration's estimator runs
(``harness.ESTIMATORS``, ``kernel``): ``kernels/fused_stats.py``'s
``pallas_call`` for PEMSVM, ``kernels/nystrom_phi.py``'s
``nystrom_fused_stats`` for NystromSVM, which takes its place on that
path. The compiled HLO names each after its jitted wrapper:
``%fused_stats.<n> = ... custom-call(...)``.
"""
import re


def kernel_pattern(job):
    return re.compile(rf"^{re.escape(job.spec.kernel)}(\.\d+)?$")


def kernel_events(ctx, device):
    from bench.tracefile import op_name

    lo, hi = ctx.trace.window
    kernel = kernel_pattern(ctx.job)
    return [e for e in ctx.trace.ops.get(device, [])
            if e.start >= lo and e.end <= hi and kernel.match(op_name(e.name))]


def per_device(ctx):
    """device -> (kernel seconds, calls) in the traced window."""
    out = {}
    for d in ctx.trace.ops:
        evs = kernel_events(ctx, d)
        out[d] = (sum(e.dur for e in evs), len(evs))
    return out


def read(ctx):
    secs = [s for s, n in per_device(ctx).values() if n]
    if not secs or not ctx.iterations:
        return None
    return max(secs) / ctx.iterations * 1e3

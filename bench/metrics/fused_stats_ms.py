"""fused_stats_ms: device time of the fused statistic kernel per
iteration (M calls per MLT sweep), on the slowest device.

The kernel is ``kernels/fused_stats.py``'s ``pallas_call``, which the
compiled HLO names after its jitted wrapper: ``%fused_stats.<n> = ...
custom-call(...)``.
"""
import re

KERNEL = re.compile(r"^fused_stats(\.\d+)?$")


def kernel_events(ctx, device):
    from bench.tracefile import op_name

    lo, hi = ctx.trace.window
    return [e for e in ctx.trace.ops.get(device, [])
            if e.start >= lo and e.end <= hi and KERNEL.match(op_name(e.name))]


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

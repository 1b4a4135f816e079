"""device_idle.fit: the share of the traced window (first fit's start
to last fit's end) in which no op ran on a device, averaged over the
devices: 1 - union of busy intervals / window.
"""


def read(ctx):
    from bench.tracefile import busy_seconds

    lo, hi = ctx.trace.window
    if hi <= lo or not ctx.trace.ops:
        return None
    busy = [busy_seconds(evs, lo, hi) for evs in ctx.trace.ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))

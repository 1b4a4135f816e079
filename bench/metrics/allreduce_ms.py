"""allreduce_ms: device time of all-reduce ops per iteration (the psum
of Sigma's triangle and b, and the scalar psums of the objective and
diagnostics), on the slowest device. Nothing to read on one chip.
"""
import re

COLLECTIVE = re.compile(r"^all-reduce")


def read(ctx):
    from bench.tracefile import op_name

    lo, hi = ctx.trace.window
    per_dev = [sum(e.dur for e in evs if e.start >= lo and e.end <= hi
                   and COLLECTIVE.match(op_name(e.name)))
               for evs in ctx.trace.ops.values()]
    if not per_dev or max(per_dev) <= 0 or not ctx.iterations:
        return None
    return max(per_dev) / ctx.iterations * 1e3

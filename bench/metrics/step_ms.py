"""step_ms: device time of the scan driver's chunk program per iteration.

The program is ``solver._chunk_runner``'s jitted ``runner``, which XLA
names ``jit_runner``. Its time is the union of the ops that ran inside
its intervals ("XLA Modules" line), so a program that waits for its
input to arrive is not counted as working; summed over the traced fits
and divided by the iterations they ran. On a mesh the slowest device
counts.
"""
import re

PROGRAM = re.compile(r"^jit_runner\(")


def program_intervals(ctx, device):
    lo, hi = ctx.trace.window
    return [e for e in ctx.trace.modules.get(device, [])
            if PROGRAM.match(e.name) and e.start >= lo and e.end <= hi]


def read(ctx):
    from bench.tracefile import busy_in

    per_dev = [sum(busy_in(ctx.trace.ops.get(d, []),
                           [(m.start, m.end)
                            for m in program_intervals(ctx, d)]))
               for d in ctx.trace.modules]
    if not per_dev or max(per_dev) <= 0 or not ctx.iterations:
        return None
    return max(per_dev) / ctx.iterations * 1e3

"""prep_ms: host time from the start of a ``fit`` call to the first
device op of its chunk program (bias column, label checks, padding and
the upload of X, which the program waits for), averaged over the traced
fits.

The chunk program's intervals are ``step_ms.program_intervals``.
"""
from bench.tracefile import work


def read(ctx):
    step = ctx.metric("step_ms")
    firsts = []
    for d in ctx.trace.modules:
        ops = sorted(e.start for e in work(ctx.trace.ops.get(d, [])))
        for m in step.program_intervals(ctx, d):
            first = next((t for t in ops if m.start <= t < m.end), None)
            if first is not None:
                firsts.append(first)
    firsts.sort()
    gaps = []
    for fit in ctx.trace.fits:
        first = next((t for t in firsts if fit.start <= t < fit.end), None)
        if first is not None:
            gaps.append(first - fit.start)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3

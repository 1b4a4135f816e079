"""finalize_ms: host time per fit in the program's ``pemsvm.finalize``
span (from the scan loop's exit to the return: the last sample, the
weights, the final snapshot and the result), averaged over the traced
fits."""
from bench.spans import per_fit_ms


def read(ctx):
    return per_fit_ms(ctx.trace, "pemsvm.finalize")

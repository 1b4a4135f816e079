"""landmarks_ms: host time per fit in the program's ``nystrom.landmarks``
span (``NystromSVM.fit``: the float32 view of X and the seeded draw of
the landmark rows), averaged over the traced fits."""
from bench.spans import per_fit_ms


def read(ctx):
    return per_fit_ms(ctx.trace, "nystrom.landmarks")

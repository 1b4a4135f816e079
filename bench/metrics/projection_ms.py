"""projection_ms: host time per fit in the program's
``nystrom.projection`` span (``NystromSVM``: K_mm on the device, its
float64 eigendecomposition on the host and the cast of K_mm^{-1/2}),
averaged over the traced fits."""
from bench.spans import per_fit_ms


def read(ctx):
    return per_fit_ms(ctx.trace, "nystrom.projection")

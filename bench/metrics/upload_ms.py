"""upload_ms: host time per fit in the program's ``pemsvm.upload`` spans
(placing the rows, then the state, on the device or the mesh), summed per
fit and averaged over the traced fits. A transfer still in flight when
the span ends is not in it."""
from bench.spans import per_fit_ms


def read(ctx):
    return per_fit_ms(ctx.trace, "pemsvm.upload")

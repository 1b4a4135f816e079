"""bias_ms: host time per fit in the program's ``pemsvm.bias`` spans (the
float32 copy of X, its bias column and any zero feature columns),
averaged over the traced fits."""
from bench.spans import per_fit_ms


def read(ctx):
    return per_fit_ms(ctx.trace, "pemsvm.bias")

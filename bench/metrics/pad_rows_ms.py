"""pad_rows_ms: host time per fit in the program's ``pemsvm.pad_rows``
spans (the row-padded host copies of X, the target and the mask),
averaged over the traced fits."""
from bench.spans import per_fit_ms


def read(ctx):
    return per_fit_ms(ctx.trace, "pemsvm.pad_rows")

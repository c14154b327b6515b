"""Host entropy decode and staging: mean ms per item, in every cell that lists it."""

from smolbench.readers import hist_mean_ms as _f


def read(ctx):
    return _f(ctx, "decode")

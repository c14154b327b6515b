"""Device: share of the traced window with no operation on the chip, %, in every cell that lists it."""

from smolbench.readers import idle_pct as _f


def read(ctx):
    return _f(ctx)

"""Blocking dispatch (H2D, program, D2H): mean ms per batch, in every cell that lists it."""

from smolbench.readers import dispatch_ms as _f


def read(ctx):
    return _f(ctx)

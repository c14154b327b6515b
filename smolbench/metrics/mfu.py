"""Whole device step (network and preprocessing kernels): share of the chip's peak, % (scan)."""

from smolbench.readers import mfu_pct as _f


def read(ctx):
    return _f(ctx)

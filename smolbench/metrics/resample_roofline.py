"""Resample+normalize kernel: share of its roofline, % (scan)."""

from smolbench.readers import roofline_pct as _f


def read(ctx):
    return _f(ctx, "resample")

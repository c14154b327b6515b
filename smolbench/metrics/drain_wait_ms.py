"""Drain reorder buffer: mean batch-done-to-release wait, ms (open loop)."""

from smolbench.readers import hist_mean_ms as _f


def read(ctx):
    return _f(ctx, "drain")

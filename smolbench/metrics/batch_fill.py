"""Batcher: items per batch as a share of the maximum batch (scan)."""

from smolbench.readers import batch_fill_pct as _f


def read(ctx):
    return _f(ctx)

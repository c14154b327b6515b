"""Admission + WFQ: mean submit-to-worker-pickup wait, ms (open loop)."""

from smolbench.readers import hist_mean_ms as _f


def read(ctx):
    return _f(ctx, "queue")

"""Whole served path: 95th percentile of due-to-release latency, ms (open loop)."""

from smolbench.readers import latency_percentile_ms as _f


def read(ctx):
    return _f(ctx, "p95")

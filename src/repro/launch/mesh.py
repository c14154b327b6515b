"""Production meshes.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state, so tests that want 1 device and dry-runs that
want 512 coexist.
"""

from __future__ import annotations

import jax

from repro.distributed.sharding import MULTI_POD_RULES, SINGLE_POD_RULES

CHIPS_PER_POD = 256


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) for two
    pods — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def rules_for(multi_pod: bool) -> dict:
    return MULTI_POD_RULES if multi_pod else SINGLE_POD_RULES


def make_host_mesh():
    """Degenerate 1x1 mesh for single-device tests/examples."""
    return make_mesh((1, 1), ("data", "model"))

"""The one rule deciding whether a Pallas kernel runs compiled or interpreted."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """An explicit ``interpret`` wins; ``None`` means Pallas interpret mode
    everywhere but a real TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)

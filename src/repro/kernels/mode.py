"""The one rule deciding whether a Pallas kernel runs compiled or interpreted,
and the operand dtype a kernel is fed where it stands in for XLA matmuls."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# jax_default_matmul_precision values at which a TPU multiplies float32
# operands in one bfloat16 pass (None is the backend's default)
_ONE_PASS = (None, "default", "fastest", "bfloat16")


def resolve_interpret(interpret: bool | None = None, platform: str | None = None) -> bool:
    """An explicit ``interpret`` wins; ``None`` means Pallas interpret mode
    everywhere but a TPU: ``platform`` where given (the target of a program
    compiled for a described chip), else the default backend."""
    if interpret is None:
        return (platform or jax.default_backend()) != "tpu"
    return bool(interpret)


def matmul_operand_dtype(platform: str | None = None):
    """The dtype an XLA matmul at the caller's precision rounds float32
    operands to on ``platform`` (else the default backend): bfloat16 on a TPU
    at the default precision, float32 where the caller raised it
    (``jax.default_matmul_precision``) and on other backends."""
    tpu = (platform or jax.default_backend()) == "tpu"
    return jnp.bfloat16 if tpu and jax.config.jax_default_matmul_precision in _ONE_PASS else jnp.float32

"""Public wrapper for the fused ViT self-attention kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.mode import resolve_interpret
from repro.kernels.vit_attention.vit_attention import vit_attention_groups

LANES = 128  # one vreg row: the lanes a grid step's group of heads fills
# a step holds its whole key row: the float32 scores, their exponentials and
# the rounded probabilities of one head, beside its double-buffered blocks,
# must fit the 16 MiB a kernel gets of a v5e's VMEM by default
VMEM_BUDGET = 16 * 2**20


def heads_per_step(heads: int, head_dim: int) -> int:
    """Heads a grid step takes: as many as fill 128 lanes.  Raises
    ``ValueError`` where they do not divide ``heads``."""
    group = min(heads, LANES // head_dim)
    if group == 0 or heads % group:
        raise ValueError(f"{heads} heads of {head_dim} do not split into groups of {LANES} lanes")
    return group


def step_vmem_bytes(t: int, lanes: int, itemsize: int) -> int:
    """A grid step's VMEM: q, k, v and o blocks, each double-buffered, and
    one head's (t, t) float32 scores and exponentials and rounded P."""
    rows, cols = -(-t // 16) * 16, -(-t // LANES) * LANES  # padded to the tiling
    return 2 * 4 * rows * max(lanes, LANES) * itemsize + rows * cols * (4 + 4 + itemsize)


def vit_attention(
    qkv: jnp.ndarray,  # (n, t, 3 * d) in the dtype the attention reads
    heads: int,
    interpret: bool | None = None,  # None: compiled on TPU, interpreted elsewhere
) -> jnp.ndarray:
    """Non-causal multi-head self-attention of a packed qkv projection whose
    last axis is laid out as (3, heads, head_dim).  Returns (n, t, d) in the
    input dtype.  Raises ``ValueError`` where a whole key row does not fit a
    grid step's VMEM: there is no tiled fallback."""
    n, t, d3 = qkv.shape
    d = d3 // 3
    if d3 != 3 * d or d % heads:
        raise ValueError(f"qkv of width {d3} does not split into 3 x {heads} heads")
    group = heads_per_step(heads, d // heads)
    need = step_vmem_bytes(t, group * (d // heads), jnp.dtype(qkv.dtype).itemsize)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"vit_attention holds a whole key row: {t} tokens need {need} bytes of VMEM "
            f"per step, over {VMEM_BUDGET}"
        )
    return vit_attention_groups(qkv, heads, group, interpret=resolve_interpret(interpret))

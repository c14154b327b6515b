"""Fused non-causal self-attention over a packed qkv (Pallas TPU).

The attention of a ViT block: every token attends to every token, and the
scores and probabilities stay in VMEM.  Unfused, XLA writes the float32
``(n, heads, t, t)`` scores to HBM and reads them back twice (row max and
sum, then P·V); at ViT-L/16's 577 tokens that is about 39% of a block's
HBM traffic.

Grid ``(n, heads / g)``: one image and one group of ``g`` heads per step.
The input is the qkv projection as it comes, ``(n, t, 3·d)`` with its last
axis laid out as ``(3, heads, head_dim)``, so the group's q, k and v are
three ``(1, t, g·head_dim)`` blocks of the same array at lane offsets
``j``, ``heads/g + j`` and ``2·heads/g + j`` (in blocks): no transpose, no
pad.  ``g·head_dim`` is 128 lanes for ViT-L's head dim of 64.

Within a step each head is picked out by a lane mask rather than a lane
slice: its q lanes alone enter ``q·kᵀ`` (the other head's are zeroed), and
its output lanes alone are kept from ``P·v``.  A head dim of 64 fills half
of the MXU's 128-deep contraction either way, so the mask costs no MXU
time and needs no relayout of a 64-lane slice.

Softmax is over the whole key row in float32 (``s·scale``, row max, ``exp``,
row sum, normalise); P is rounded to the input dtype before ``P·v``, which
accumulates in float32.  With bf16 inputs these are the roundings XLA makes
at the default precision; only the order of the sums differs.  float32
inputs are multiplied at float32 precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, o_ref, *, heads, head_dim, scale):
    q, k, v = q_ref[0], k_ref[0], v_ref[0]  # (t, heads * head_dim) each
    lane_head = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) // head_dim
    prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    o = None
    for h in range(heads):
        mine = lane_head == h
        qh = jnp.where(mine, q, jnp.zeros_like(q)) if heads > 1 else q
        s = jax.lax.dot_general(
            qh, k, (((1,), (1,)), ((), ())), precision=prec, preferred_element_type=jnp.float32
        ) * scale
        e = jnp.exp(s - s.max(axis=1, keepdims=True))
        # one reciprocal per row, then a multiply: within an ulp of dividing
        p = (e * (1.0 / e.sum(axis=1, keepdims=True))).astype(v.dtype)
        oh = jnp.dot(p, v, precision=prec, preferred_element_type=jnp.float32)
        o = oh if o is None else jnp.where(mine, oh, o)
    o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "group", "interpret"))
def vit_attention_groups(
    qkv: jnp.ndarray,  # (n, t, 3 * d), last axis (3, heads, head_dim)
    heads: int,
    group: int,  # heads per grid step; divides heads
    interpret: bool = False,
) -> jnp.ndarray:
    n, t, d3 = qkv.shape
    d = d3 // 3
    head_dim = d // heads
    lanes = group * head_dim
    blocks = heads // group  # lane blocks per q, k or v

    def spec(part):
        return pl.BlockSpec((1, t, lanes), lambda i, j: (i, 0, part * blocks + j))

    return pl.pallas_call(
        functools.partial(_kernel, heads=group, head_dim=head_dim, scale=head_dim**-0.5),
        grid=(n, blocks),
        in_specs=[spec(0), spec(1), spec(2)],
        out_specs=pl.BlockSpec((1, t, lanes), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, t, d), qkv.dtype),
        interpret=interpret,
        name="vit_attention",  # the op name device traces show for this kernel
    )(qkv, qkv, qkv)

"""Pure-jnp oracle for the fused ViT self-attention kernel: the einsum and
softmax formulation the ViT blocks ran in XLA."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def vit_attention_ref(qkv: jnp.ndarray, heads: int) -> jnp.ndarray:
    """qkv: (n, t, 3 * d), last axis (3, heads, head_dim).  Scores and softmax
    in float32, P rounded to the input dtype for P·v, float32 accumulation;
    returns (n, t, d) in the input dtype."""
    n, t, d3 = qkv.shape
    hd = d3 // 3 // heads
    qkv = qkv.reshape(n, t, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k, preferred_element_type=jnp.float32) * hd**-0.5
    a = jax.nn.softmax(scores, axis=-1).astype(qkv.dtype)
    o = jnp.einsum("nhqk,nkhd->nqhd", a, v, preferred_element_type=jnp.float32)
    return o.reshape(n, t, heads * hd).astype(qkv.dtype)

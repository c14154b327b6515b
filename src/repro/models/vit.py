"""Vision Transformer (Dosovitskiy et al. 2021, arXiv:2010.11929) on the
served path.

The image classifier of the paper's Table 1: a 16x16 stride-16 patch
projection, a learned class token and learned position embeddings, then
``layers`` pre-LN blocks (LN -> multi-head self-attention with qkv bias,
non-causal -> residual; LN -> fc1 -> GELU -> fc2 -> residual), a final LN,
and the class token through a linear head.

* The blocks run as one ``lax.scan`` over weights stacked on a leading
  layer axis, so a program's size and compile time do not grow with depth.
* Weights are float32 and every matmul runs at the backend's default
  precision, as the ResNets do.
* GELU is the tanh form of the original JAX release (``flax.linen.gelu``'s
  default); PyTorch ports use the erf form.
* Attention is the ``vit_attention`` Pallas kernel (``kernels/vit_attention``):
  it reads q, k and v straight from the packed qkv projection and keeps the
  scores and probabilities in VMEM.  qkv is fed to it in the dtype XLA would
  round the attention's matmul operands to (``matmul_operand_dtype``):
  bfloat16 on a TPU at the default precision, float32 where the caller
  raised the precision and on other backends.  ``platform`` names the
  target where it is not the default backend (a program compiled for a
  described chip).
* ``jax.named_scope`` names the embedding, attention, MLP and head, so a
  profile attributes them.

Weights are a pytree: ``patch`` (``w`` HWIO (P, P, 3, D), ``b``), ``cls``
(D,), ``pos`` (T, D), ``blocks`` with each leaf stacked over layers (``ln1``,
``qkv`` (D, 3D) with its bias, ``proj``, ``ln2``, ``fc1``, ``fc2``; each LN a
``scale`` and ``bias``), the final ``ln``, and ``head`` (D, C) with its bias.
The qkv output is laid out as (3, heads, head_dim).  Input is an NCHW batch.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.mode import matmul_operand_dtype, resolve_interpret
from repro.kernels.vit_attention import vit_attention
from repro.models.layers import layernorm


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int
    patch: int
    layers: int
    hidden: int
    heads: int
    mlp: int
    num_classes: int
    eps: float = 1e-6

    @property
    def tokens(self) -> int:
        """Patches plus the class token."""
        return (self.image_size // self.patch) ** 2 + 1


def _block(cfg: ViTConfig, x, p, platform: str | None = None):
    with jax.named_scope("attention"):
        h = layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"], cfg.eps)
        qkv = (h @ p["qkv"]["w"] + p["qkv"]["b"]).astype(matmul_operand_dtype(platform))
        o = vit_attention(qkv, cfg.heads, interpret=resolve_interpret(platform=platform))
        x = x + o @ p["proj"]["w"] + p["proj"]["b"]
    with jax.named_scope("mlp"):
        h = layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"], cfg.eps)
        h = jax.nn.gelu(h @ p["fc1"]["w"] + p["fc1"]["b"], approximate=True)
        return x + h @ p["fc2"]["w"] + p["fc2"]["b"]


def vit_forward(params, cfg: ViTConfig, x, platform: str | None = None):
    """(N, 3, H, W) float32 images -> (N, num_classes) logits."""
    n = x.shape[0]
    with jax.named_scope("embed"):
        patches = jax.lax.conv_general_dilated(
            x, params["patch"]["w"], (cfg.patch, cfg.patch), "VALID",
            dimension_numbers=("NCHW", "HWIO", "NHWC"),
        )
        tokens = patches.reshape(n, -1, cfg.hidden) + params["patch"]["b"]
        cls = jnp.broadcast_to(params["cls"], (n, 1, cfg.hidden))
        x = jnp.concatenate([cls, tokens], axis=1) + params["pos"]

    def body(carry, p):
        return _block(cfg, carry, p, platform), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    with jax.named_scope("head"):
        c = layernorm(x[:, 0], params["ln"]["scale"], params["ln"]["bias"], cfg.eps)
        return c @ params["head"]["w"] + params["head"]["b"]

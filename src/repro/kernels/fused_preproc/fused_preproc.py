"""Fused resize + normalize + layout Pallas TPU kernel.

TPU adaptation of SMOL's §6.2 fusion product.  Bilinear resize is expressed
as two *matmuls* against precomputed interpolation matrices:

    out_c = (R_y @ X_c @ R_x^T) * scale_c + bias_c

R_y is (OH, H) with exactly two nonzeros per row (the bilinear weights),
R_x likewise (OW, W).  On TPU this turns a gather-heavy resample into MXU
work, and the per-channel affine (the folded ToFloat+Normalize from the DAG
optimizer, ops.FusedElementwise._folded) rides along in the same VMEM pass.
The kernel consumes *planar* (C, H, W) input — exactly what the split JPEG
decode path (kernels/idct) produces — so the ChannelsFirst layout change is
absorbed structurally rather than as a transpose.

Grid: (C, OH/TILE_OH).  Blocks: X one full plane (1, H, W); R_y a
(TILE_OH, H) row stripe; R_x^T shared (W, OW).  The per-plane scale/bias
vectors live whole in SMEM and are read at the channel grid coordinate (a
(1, 1) VMEM block of a (1, C) array is not a legal TPU tiling).

Both matmuls run at ``Precision.HIGHEST``: the TPU's default f32 matmul
rounds operands to bf16, which moves the interpolation weights by up to
2^-9 relative — enough to flip a uint8 re-quantization.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_OH = 128


def _kernel(x_ref, ry_ref, rxt_ref, scale_ref, bias_ref, o_ref, *, round_uint8):
    ci = pl.program_id(0)
    hi = jax.lax.Precision.HIGHEST
    y = jnp.dot(ry_ref[...], x_ref[0], precision=hi, preferred_element_type=jnp.float32)
    z = jnp.dot(y, rxt_ref[...], precision=hi, preferred_element_type=jnp.float32)
    if round_uint8:
        # uint8-chain variant: the reference chain resizes *before* ToFloat,
        # so the resample result re-quantizes to the integer pixel grid
        # before the folded affine applies (ops.Resize rounds uint8 inputs)
        z = jnp.clip(jnp.round(z), 0.0, 255.0)
    o_ref[0] = z * scale_ref[ci] + bias_ref[ci]


@functools.partial(jax.jit, static_argnames=("tile_oh", "interpret", "round_uint8"))
def fused_resize_normalize_planar(
    x: jnp.ndarray,  # (C, H, W) float32
    ry: jnp.ndarray,  # (OH_padded, H) float32
    rxt: jnp.ndarray,  # (W, OW) float32
    scale: jnp.ndarray,  # (C,) float32
    bias: jnp.ndarray,  # (C,) float32
    tile_oh: int = DEFAULT_TILE_OH,
    interpret: bool = False,
    round_uint8: bool = False,
) -> jnp.ndarray:
    c, h, w = x.shape
    oh_pad = ry.shape[0]
    ow = rxt.shape[1]
    assert oh_pad % tile_oh == 0, (oh_pad, tile_oh)
    grid = (c, oh_pad // tile_oh)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, round_uint8=round_uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, w), lambda ci, oi: (ci, 0, 0)),
            pl.BlockSpec((tile_oh, h), lambda ci, oi: (oi, 0)),
            pl.BlockSpec((w, ow), lambda ci, oi: (0, 0)),
            smem,
            smem,
        ],
        out_specs=pl.BlockSpec((1, tile_oh, ow), lambda ci, oi: (ci, oi, 0)),
        out_shape=jax.ShapeDtypeStruct((c, oh_pad, ow), jnp.float32),
        interpret=interpret,
    )(x, ry, rxt, scale, bias)

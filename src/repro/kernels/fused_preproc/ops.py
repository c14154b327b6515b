"""Public wrapper for the fused resize+normalize kernel."""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.kernels.fused_preproc.fused_preproc import (
    DEFAULT_TILE_OH,
    fused_resize_normalize_planar,
)
from repro.kernels.mode import resolve_interpret
from repro.preprocessing.ops import bilinear_coords


@functools.lru_cache(maxsize=64)
def bilinear_matrix(in_dim: int, out_dim: int) -> np.ndarray:
    """(out_dim, in_dim) bilinear interpolation matrix, half-pixel centers.

    Exactly two nonzeros per row, built from the shared
    ``preprocessing.ops.bilinear_coords`` arithmetic so the matmul resample
    uses bit-identical weights to the host/reference chain."""
    i0, i1, w1 = bilinear_coords(in_dim, out_dim, np)
    mat = np.zeros((out_dim, in_dim), dtype=np.float32)
    rows = np.arange(out_dim)
    mat[rows, i0] += np.float32(1.0) - w1
    mat[rows, i1] += w1
    return mat


def fused_resize_affine(
    x: jnp.ndarray,  # (B, H, W) float32 planes (B = batch*channels)
    ry: np.ndarray,  # (OH, H) row interpolation matrix (may be crop-sliced)
    rxt: np.ndarray,  # (W, OW) col interpolation matrix, transposed
    scale: jnp.ndarray,  # (B,) per-plane folded multiplier
    bias: jnp.ndarray,  # (B,) per-plane folded offset
    round_uint8: bool = False,
    tile_oh: int = DEFAULT_TILE_OH,
    interpret: bool | None = None,  # None: compiled on TPU, interpreted elsewhere
) -> jnp.ndarray:
    """Raw-matrix kernel entry for the device compiler: resize every plane
    through precomputed (possibly crop-sliced) interpolation matrices and
    apply a per-plane affine, one fused VMEM pass.  Handles output-row
    padding to the tile size internally."""
    b = x.shape[0]
    oh = ry.shape[0]
    tile = min(tile_oh, max(8, 1 << (oh - 1).bit_length()))
    oh_pad = -(-oh // tile) * tile
    if oh_pad != oh:
        ry_pad = np.zeros((oh_pad, ry.shape[1]), dtype=np.float32)
        ry_pad[:oh] = ry
        ry = ry_pad
    out = fused_resize_normalize_planar(
        x,
        jnp.asarray(ry),
        jnp.asarray(rxt),
        jnp.reshape(jnp.asarray(scale, jnp.float32), (b,)),
        jnp.reshape(jnp.asarray(bias, jnp.float32), (b,)),
        tile_oh=tile,
        interpret=resolve_interpret(interpret),
        round_uint8=round_uint8,
    )
    return out[:, :oh, :]


def fused_resize_normalize(
    x: np.ndarray | jnp.ndarray,  # (C, H, W) float input planes
    out_h: int,
    out_w: int,
    scale: np.ndarray,  # (C,) folded multiplier (e.g. 1/255/std)
    bias: np.ndarray,  # (C,) folded offset (e.g. -mean/std)
    tile_oh: int = DEFAULT_TILE_OH,
    interpret: bool | None = None,  # None: compiled on TPU, interpreted elsewhere
) -> jnp.ndarray:
    """Resize (C,H,W) -> (C,out_h,out_w) bilinearly and apply per-channel
    affine, all in one fused VMEM pass."""
    x = jnp.asarray(x, dtype=jnp.float32)
    _, h, w = x.shape
    return fused_resize_affine(
        x,
        bilinear_matrix(h, out_h),
        np.ascontiguousarray(bilinear_matrix(w, out_w).T),
        scale,
        bias,
        tile_oh=tile_oh,
        interpret=interpret,
    )

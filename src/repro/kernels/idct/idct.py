"""Fused dequantize + 8x8 IDCT as a single MXU matmul (Pallas TPU).

TPU adaptation of JPEG block decoding (DESIGN.md §3): instead of per-block
C^T @ X @ C (two K=8 matmuls — far below MXU efficiency), we flatten each
8x8 block to a 64-vector and apply the Kronecker-factored 2-D IDCT:

    vec(C^T X C) = (C^T ⊗ C^T) vec(X)        (row-major vec)

so a TILE of blocks becomes ONE (TILE, 64) @ (64, 64) matmul.  The
quantization table folds into the transform matrix for free:

    out = M2 @ (q ⊙ x)  =  (M2 · diag(q)) @ x

making dequantization zero-cost.  The wrapper (ops.py) precomputes
``M2q^T = (M2 · diag(q))^T`` once per quality setting.

Block tiling: TILE rows of 64 lanes in VMEM; TILE defaults to 512 (128 KiB
in + 128 KiB out + 16 KiB matrix — comfortably inside ~16 MiB VMEM, and
TILE is a multiple of the 8-sublane f32 tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 512


def _idct_kernel(x_ref, m_ref, o_ref):
    # x_ref: (TILE, 64) f32 coeffs; m_ref: (64, 64) fused dequant+IDCT matrix.
    # HIGHEST: the TPU's default f32 matmul rounds the coefficients and the
    # dequantized basis to bf16, off by several pixel levels
    o_ref[...] = jnp.dot(
        x_ref[...],
        m_ref[...],
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def dequant_idct_tiles(
    coeffs_flat: jnp.ndarray,  # (N, 64) float32 — N must be a multiple of tile
    m2q_t: jnp.ndarray,  # (64, 64) float32 — (kron(C^T, C^T) @ diag(q))^T
    tile: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    n = coeffs_flat.shape[0]
    assert n % tile == 0, (n, tile)
    grid = (n // tile,)
    return pl.pallas_call(
        _idct_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, 64), lambda i: (i, 0)),
            pl.BlockSpec((64, 64), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 64), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 64), jnp.float32),
        interpret=interpret,
    )(coeffs_flat, m2q_t)

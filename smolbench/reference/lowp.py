"""The preprocessing control: the plain reference's decode and preprocessing
with every product taken at a lower matmul precision than the configuration
states for them (``highest``).

``passes=3`` is the TPU's ``high`` for float32 operands (each operand split
into a bfloat16 head and a bfloat16 tail, the tail-by-tail product dropped),
the step below ``highest``; ``passes=1`` is the TPU's default, one bfloat16
pass.  The passes are spelled out on float32 arrays, so the control reads
the same on any device: the 8x8 IDCT as ``C^T B C``, YCbCr -> RGB as a 3x3
product, and the bilinear resize as its two interpolation products.  The
element-wise steps (level shift, chroma repeat, rounding, clipping, the
affine) are the reference's.
"""

from __future__ import annotations

import numpy as np

from smolbench.reference import preproc, sjpg

_JFIF = np.array([[1.0, 0.0, 1.402], [1.0, -0.344136, -0.714136], [1.0, 1.772, 0.0]])


def bf16(a: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def matmul(a: np.ndarray, b: np.ndarray, passes: int) -> np.ndarray:
    """``a @ b`` in float32 from ``passes`` (1 or 3) bfloat16 products."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ah, bh = bf16(a), bf16(b)
    out = ah @ bh
    if passes == 3:
        out = out + (ah @ bf16(b - bh) + bf16(a - ah) @ bh)
    elif passes != 1:
        raise ValueError(f"passes must be 1 or 3, not {passes}")
    return out


def decode(data: bytes, passes: int) -> np.ndarray:
    """One SJPG stream -> (H, W, 3) uint8 RGB, its products at ``passes``."""
    geom, grids, coeffs = sjpg.dequantized(data)
    c = sjpg._C.astype(np.float32)
    blocks = [matmul(matmul(c.T, b, passes), c, passes) + 128.0 for b in coeffs]

    def convert(yy, cb, cr):
        return matmul(np.stack([yy, cb, cr], -1), _JFIF.T, passes)

    return sjpg.to_uint8(sjpg.planes_to_rgb(geom, grids, blocks, convert))


def resized(rgb: np.ndarray, passes: int, size: int, resize_short: int) -> np.ndarray:
    """(H, W, 3) uint8 -> (size, size, 3) crop and resize at ``passes``,
    before the re-quantization to uint8."""
    x = preproc.crop(rgb, size, resize_short)
    r = preproc.resize_matrix(x.shape[0], size)
    return np.stack([matmul(matmul(r, x[..., ch], passes), r.T, passes) for ch in range(3)], -1)

"""Work of the dequantize + 8x8 IDCT kernel (``kernels/idct``), counted
from the algorithm and not from the kernel: each 8x8 block is one 64x64
matrix-vector product (the Kronecker-factored 2-D IDCT with the
quantization table folded in), read as 64 float32 coefficients and written
as 64 float32 pixels, plus the 64x64 float32 matrix once per call.  Tile
padding, lane padding and precision passes are not counted, so a rewrite
of the kernel leaves the count unchanged."""

from __future__ import annotations

# the op names of its calls in a device trace (trace.op_name)
MARKS = ("dequant_idct_tiles",)


def blocks_per_item(geom: dict) -> int:
    """8x8 blocks of one 4:2:0 or 4:4:4 image: luma plus both chroma planes."""
    def ceil(a: int, b: int) -> int:
        return -(-a // b)

    h, w = geom["height"], geom["width"]
    luma = ceil(h, 8) * ceil(w, 8)
    if geom["subsample"]:  # each chroma plane at half the resolution on both axes
        return luma + 2 * ceil(ceil(h, 2), 8) * ceil(ceil(w, 2), 8)
    return 3 * luma


def count(geom: dict, items: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the IDCT calls of one dispatch of ``items`` rows."""
    blocks = blocks_per_item(geom) * items
    calls = 2  # one call per quantization table: luma, chroma
    return 2.0 * 64 * 64 * blocks, 2 * 64 * 4 * blocks + calls * 64 * 64 * 4

"""Plain reference decoder for SJPG, the system's stored image format.

Written from the format's definition, not from the program's codec, and
computed in float64:

* header ``<4sBIIBBBBHH`` (magic ``SJPG``, version 2, height, width,
  channels, quality, 4:2:0 flag, luma block-rows per band, luma block
  rows, luma block cols), then ``<I`` band count and ``<I`` per band the
  payload offset;
* each band payload is one byte of method (0 stored, 1 zstd) and then, per
  plane (Y, Cb, Cr), its block rows of that band as sparse zigzag blocks:
  ``<I`` block count, int16 DC per block, uint8 non-zero AC count per block,
  uint8 zigzag position of each AC, int16 value of each AC;
* scan order: diagonals ``r + c`` ascending; along an odd diagonal by
  column, along an even one by row;
* quantization: the Annex K tables at libjpeg's quality scaling;
* the inverse transform is the orthonormal 8x8 IDCT, level shift 128,
  4:2:0 chroma repeated 2x2, JFIF YCbCr -> RGB, round and clip to uint8.
"""

from __future__ import annotations

import struct

import numpy as np

_HDR = struct.Struct("<4sBIIBBBBHH")

_LUMA = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61], [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56], [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77], [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101], [72, 92, 95, 98, 112, 100, 103, 99],
])
_CHROMA = np.full((8, 8), 99)
_CHROMA[:4, :4] = [[17, 18, 24, 47], [18, 21, 26, 66], [24, 26, 56, 99], [47, 66, 99, 99]]


def qtable(base: np.ndarray, quality: int) -> np.ndarray:
    """libjpeg quality scaling of an Annex K table."""
    quality = min(max(int(quality), 1), 100)
    scale = 5000 // quality if quality < 50 else 200 - 2 * quality
    return np.clip((base * scale + 50) // 100, 1, 255).astype(np.float64)


def _scan_order() -> np.ndarray:
    """Flat (row-major) 8x8 index of each scan position."""
    cells = sorted(((r, c) for r in range(8) for c in range(8)),
                   key=lambda rc: (rc[0] + rc[1], rc[1] if (rc[0] + rc[1]) % 2 else rc[0]))
    return np.array([r * 8 + c for r, c in cells])


SCAN = _scan_order()
_K = np.arange(8)[:, None]
_C = np.cos((2 * np.arange(8)[None, :] + 1) * _K * np.pi / 16) * np.sqrt(2 / 8)
_C[0] /= np.sqrt(2.0)  # orthonormal DCT-II matrix


def _inflate(blob: memoryview) -> bytes:
    if blob[0] == 0:
        return bytes(blob[1:])
    if blob[0] == 1:
        import zstandard

        return zstandard.ZstdDecompressor().decompress(bytes(blob[1:]))
    raise ValueError(f"unknown payload method {blob[0]}")


def _sparse_blocks(raw: bytes, off: int) -> tuple[np.ndarray, int]:
    (n,) = struct.unpack_from("<I", raw, off)
    off += 4
    dc = np.frombuffer(raw, "<i2", n, off)
    off += 2 * n
    counts = np.frombuffer(raw, np.uint8, n, off)
    off += n
    nnz = int(counts.sum())
    pos = np.frombuffer(raw, np.uint8, nnz, off).astype(np.int64)
    off += nnz
    vals = np.frombuffer(raw, "<i2", nnz, off)
    off += 2 * nnz
    scan = np.zeros((n, 64), np.float64)
    scan[:, 0] = dc
    scan[np.repeat(np.arange(n), counts), pos] = vals
    return scan, off


def geometry(data: bytes) -> dict:
    """Height, width and 4:2:0 flag of one SJPG stream."""
    _magic, _version, h, w, _ch, _q, sub, *_ = _HDR.unpack_from(data, 0)
    return {"height": h, "width": w, "subsample": bool(sub)}


def dequantized(data: bytes) -> tuple[dict, list, list]:
    """One SJPG stream -> its geometry, the (block rows, block cols) grid of
    each plane (Y, Cb, Cr), and each plane's dequantized coefficients as
    (blocks, 8, 8) float64 in row-major block order."""
    magic, version, h, w, ch, quality, sub, band_rows, n_br, n_bc = _HDR.unpack_from(data, 0)
    if magic != b"SJPG" or version != 2 or ch != 3:
        raise ValueError("not a 3-channel SJPG v2 stream")
    off = _HDR.size
    (n_bands,) = struct.unpack_from("<I", data, off)
    offsets = struct.unpack_from(f"<{n_bands}I", data, off + 4)
    start = off + 4 + 4 * n_bands
    ends = list(offsets[1:]) + [len(data) - start]
    cbr, cbc = ((n_br + 1) // 2, (n_bc + 1) // 2) if sub else (n_br, n_bc)
    grids = [(n_br, n_bc), (cbr, cbc), (cbr, cbc)]
    planes = [np.zeros((r * c, 64)) for r, c in grids]
    filled = [0, 0, 0]
    view = memoryview(data)
    for band in range(n_bands):
        raw = _inflate(view[start + offsets[band] : start + ends[band]])
        p_off = 0
        for p in range(3):
            blocks, p_off = _sparse_blocks(raw, p_off)
            planes[p][filled[p] : filled[p] + len(blocks)] = blocks
            filled[p] += len(blocks)
    tables = [qtable(_LUMA, quality), qtable(_CHROMA, quality), qtable(_CHROMA, quality)]
    coeffs = []
    for scan, q in zip(planes, tables):
        c = np.zeros_like(scan)
        c[:, SCAN] = scan
        coeffs.append(c.reshape(-1, 8, 8) * q)
    return {"height": h, "width": w, "subsample": bool(sub)}, grids, coeffs


def planes_to_rgb(geom: dict, grids: list, blocks: list, convert) -> np.ndarray:
    """Level-shifted spatial blocks of each plane -> (H, W, 3) RGB before it
    is rounded to uint8, the YCbCr -> RGB products taken by
    ``convert(y, cb, cr)``."""
    pix = [b.reshape(rows, cols, 8, 8).transpose(0, 2, 1, 3).reshape(rows * 8, cols * 8)
           for (rows, cols), b in zip(grids, blocks)]
    y = pix[0]
    if geom["subsample"]:
        pix[1:] = [np.repeat(np.repeat(c, 2, 0), 2, 1)[: y.shape[0], : y.shape[1]] for c in pix[1:]]
    rgb = convert(pix[0], pix[1] - 128.0, pix[2] - 128.0)
    return rgb[: geom["height"], : geom["width"]]


def to_uint8(rgb: np.ndarray) -> np.ndarray:
    return np.clip(np.round(rgb), 0, 255).astype(np.uint8)


def _jfif(yy, cb, cr):
    return np.stack([yy + 1.402 * cr, yy - 0.344136 * cb - 0.714136 * cr, yy + 1.772 * cb], -1)


def decode_unrounded(data: bytes) -> np.ndarray:
    """One SJPG stream -> (H, W, 3) float64 RGB before the round to uint8."""
    geom, grids, coeffs = dequantized(data)
    return planes_to_rgb(geom, grids, [_C.T @ c @ _C + 128.0 for c in coeffs], _jfif)


def decode(data: bytes) -> np.ndarray:
    """One SJPG stream -> (H, W, 3) uint8 RGB."""
    return to_uint8(decode_unrounded(data))

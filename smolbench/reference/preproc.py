"""Plain reference of the served preprocessing, in float64 on the host.

The published chain is ResizeShortSide(resize_short) -> CenterCrop(size)
-> ToFloat -> Normalize(ImageNet mean and std) -> CHW, both sizes from the
configuration (``resize_short`` and ``input_size``; 256 and 224 for
ResNet).  The system serves it reordered (paper §6.2, rule R3): a centre
crop of ``size / resize_short`` of the short side, then a bilinear resize
(half-pixel centres) to ``size`` x ``size`` that re-quantizes to uint8, then
the affine.  The reference computes that served order, which is the one
departure from the published chain; for ResNet on a 256-px item the crop is
224 px and the resize an identity.
"""

from __future__ import annotations

import numpy as np

MEAN = np.array([0.485, 0.456, 0.406])
STD = np.array([0.229, 0.224, 0.225])


def _coords(n_in: int, n_out: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    s = np.clip((np.arange(n_out) + 0.5) * (n_in / n_out) - 0.5, 0.0, n_in - 1.0)
    i0 = np.floor(s).astype(np.int64)
    return i0, np.minimum(i0 + 1, n_in - 1), s - i0


def crop_side(h: int, w: int, size: int, resize_short: int) -> int:
    """Side of the centre crop of an ``h`` x ``w`` image: ``size /
    resize_short`` of its short side."""
    return max(1, round(size / resize_short * min(h, w)))


def crop(rgb: np.ndarray, size: int, resize_short: int) -> np.ndarray:
    """The centre crop of ``size / resize_short`` of the short side, float64."""
    h, w = rgb.shape[:2]
    s = crop_side(h, w, size, resize_short)
    t, l = (h - s) // 2, (w - s) // 2
    return rgb[t : t + s, l : l + s].astype(np.float64)


def resize_matrix(n_in: int, n_out: int) -> np.ndarray:
    """(n_out, n_in) bilinear interpolation matrix of the resize below."""
    i0, i1, frac = _coords(n_in, n_out)
    m = np.zeros((n_out, n_in))
    np.add.at(m, (np.arange(n_out), i0), 1 - frac)
    np.add.at(m, (np.arange(n_out), i1), frac)
    return m


def normalize(out: np.ndarray) -> np.ndarray:
    """(size, size, 3) resized pixels -> (3, size, size) float32 network input,
    re-quantized to uint8 first."""
    out = np.clip(np.round(out), 0, 255)
    return ((out / 255.0 - MEAN) / STD).transpose(2, 0, 1).astype(np.float32)


def resized(rgb: np.ndarray, size: int, resize_short: int) -> np.ndarray:
    """(H, W, 3) uint8 -> (size, size, 3) float64 crop and resize, before
    the re-quantization to uint8."""
    x = crop(rgb, size, resize_short)
    s = x.shape[0]
    y0, y1, wy = _coords(s, size)
    x0, x1, wx = _coords(s, size)
    rows = x[y0] * (1 - wy)[:, None, None] + x[y1] * wy[:, None, None]
    return rows[:, x0] * (1 - wx)[None, :, None] + rows[:, x1] * wx[None, :, None]


def levels(x: np.ndarray, channel_axis: int) -> np.ndarray:
    """Network input values -> the uint8 levels they stand for (float64)."""
    shape = [1] * x.ndim
    shape[channel_axis] = 3
    return np.round((np.asarray(x, np.float64) * STD.reshape(shape) + MEAN.reshape(shape)) * 255.0)

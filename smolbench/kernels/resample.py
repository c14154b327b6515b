"""Work of the fused resample + normalize kernel (``kernels/fused_preproc``),
counted from the algorithm: per plane, the bilinear resize as its two
interpolation products ``R_y (OH x H) @ X (H x W)`` and ``(OH x W) @ R_x^T
(W x OW)``, reading the float32 plane and writing the float32 output, plus
both interpolation matrices once per call.  Output-row padding to the tile,
lane padding and precision passes are not counted."""

from __future__ import annotations

# the op names of its calls in a device trace (trace.op_name)
MARKS = ("fused_resize_normalize_planar",)


def count(geom: dict, items: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the resample call of one dispatch of ``items`` rows;
    ``geom`` gives the cropped input ``crop`` and the output ``size``."""
    h = w = geom["crop"]
    oh = ow = geom["size"]
    planes = 3 * items
    flops = 2.0 * planes * (oh * h * w + oh * w * ow)
    bytes_ = 4.0 * (planes * (h * w + oh * ow) + oh * h + w * ow)
    return flops, bytes_

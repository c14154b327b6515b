"""Public wrapper for the fused dequantize+IDCT kernel.

``point`` selects the IDCT size (paper §6.4, libjpeg's scaled DCT):

* ``point=8`` — the full 8x8 IDCT (one 8x8 pixel block per coefficient
  block);
* ``point=4`` / ``point=2`` — truncated-DCT-basis scaled IDCT: only the
  low-frequency ``point x point`` coefficients participate and each block
  reconstructs straight to ``point x point`` pixels (half / quarter
  resolution).  The transform is ``A X[:k,:k] A^T`` with
  ``A = sqrt(k/8) * Ck^T`` (``Ck`` the k-point orthonormal DCT-II matrix),
  which recovers the full IDCT at k=8 and the ``DC/8`` progressive
  first-scan image at k=1 — the whole family is one definition.

All variants stay ONE MXU matmul per tile: the Kronecker-factored matrix
``kron(A P_k, A P_k)`` is (k^2, 64), zero-padded to (64, 64) so the Pallas
kernel's block shape (and its TPU lane alignment) never changes — on the
MXU a 16-wide and a 64-wide matmul cost the same padded lane anyway; the
scaled win is every *downstream* stage (unblockify, chroma upsample, color
conversion, resample) touching factor^2 fewer pixels.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.kernels.idct.idct import DEFAULT_TILE, dequant_idct_tiles
from repro.kernels.mode import resolve_interpret
from repro.preprocessing import dct as dct_np

SCALED_POINTS = (8, 4, 2, 1)  # supported IDCT sizes (8 = full resolution)


def scaled_basis(point: int) -> np.ndarray:
    """(point, 8) truncated-DCT-basis row transform ``sqrt(k/8) * Ck^T P_k``.

    Applied two-sided (``A X A^T``) it maps an 8x8 coefficient block to a
    ``point x point`` pixel block at 1/(8/point) resolution.  Delegates to
    ``preprocessing.dct.scaled_idct_basis`` so the kernel and the host
    reference decode share bit-identical basis weights."""
    return dct_np.scaled_idct_basis(point)


@functools.lru_cache(maxsize=64)
def _m2q_t(qtable_bytes: bytes, point: int) -> np.ndarray:
    """(kron(A, A) @ diag(q))^T for a quant table + IDCT size (cached).

    Zero-padded on the output axis to 64 so every ``point`` shares the one
    (64, 64) kernel block shape; callers slice the first point^2 columns."""
    q = np.frombuffer(qtable_bytes, dtype=np.int32).reshape(8, 8)
    a = scaled_basis(point)
    m2 = np.kron(a, a)  # row-major vec: vec(A X A^T) = (A ⊗ A) vec(X)
    m2q = m2 * q.reshape(-1)[None, :]  # fold dequantization into the transform
    out = np.zeros((64, 64), dtype=np.float64)
    out[: point * point] = m2q
    return np.ascontiguousarray(out.T).astype(np.float32)


def dequant_idct(
    coeffs: np.ndarray | jnp.ndarray,  # (N, 8, 8) quantized coefficients
    qtable: np.ndarray,  # (8, 8) int quantization table
    point: int = 8,  # IDCT size: 8 full, 4 half-res, 2 quarter-res
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,  # None: compiled on TPU, interpreted elsewhere
) -> jnp.ndarray:
    """Dequantize + 2-D (scaled) IDCT a stack of 8x8 coefficient blocks.
    Returns (N, point, point) f32 (level-shifted pixels; caller adds 128)."""
    n = coeffs.shape[0]
    flat = jnp.asarray(coeffs, dtype=jnp.float32).reshape(n, 64)
    pad = (-n) % tile
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    m2q_t = jnp.asarray(
        _m2q_t(np.ascontiguousarray(qtable, dtype=np.int32).tobytes(), point)
    )
    out = dequant_idct_tiles(
        flat, m2q_t, tile=tile, interpret=resolve_interpret(interpret)
    )
    return out[:n, : point * point].reshape(n, point, point)

"""Public wrapper for flash-decoding attention."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    DEFAULT_BK,
    decode_attention_packed,
)
from repro.kernels.mode import resolve_interpret


def decode_attention(
    q: jnp.ndarray,  # (B, H, D)
    k: jnp.ndarray,  # (B, KVH, S, D)
    v: jnp.ndarray,  # (B, KVH, S, D)
    lengths: jnp.ndarray,  # (B,) int32 valid cache lengths
    window: int | None = None,
    scale: float | None = None,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,  # None: compiled on TPU, interpreted elsewhere
) -> jnp.ndarray:
    """Single-token decode attention over a (padded) KV cache."""
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    if scale is None:
        scale = float(d) ** -0.5

    bk_eff = min(bk, s)
    pad_s = (-s) % bk_eff
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    sp = s + pad_s

    # Pack query heads of each KV group into the sublane dim.
    qp = q.reshape(b, kvh, group, d).reshape(b * kvh, group, d)
    kf = k.reshape(b * kvh, sp, d)
    vf = v.reshape(b * kvh, sp, d)
    lens = jnp.repeat(lengths.astype(jnp.int32), kvh).reshape(b * kvh, 1)
    out = decode_attention_packed(
        qp, kf, vf, lens, scale=scale, window=window, bk=bk_eff, interpret=resolve_interpret(interpret)
    )
    return out.reshape(b, kvh, group, d).reshape(b, h, d)

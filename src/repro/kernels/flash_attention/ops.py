"""Public wrapper for blockwise flash attention."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    DEFAULT_BK,
    DEFAULT_BQ,
    flash_attention_bhsd,
)
from repro.kernels.mode import resolve_interpret


def flash_attention(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, KVH, S, D)
    v: jnp.ndarray,  # (B, KVH, S, D)
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,  # None: compiled on TPU, interpreted elsewhere
) -> jnp.ndarray:
    """Flash attention over (B, H, S, D) with GQA (KVH | H) and optional
    sliding window.  Pads S up to the block size and crops back."""
    b, h, s, d = q.shape
    kvh = k.shape[1]
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    if scale is None:
        scale = float(d) ** -0.5

    bq_eff = min(bq, max(8, s))
    bk_eff = min(bk, max(8, s))
    blk = max(bq_eff, bk_eff)
    pad = (-s) % blk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    sp = s + pad

    qf = q.reshape(b * h, sp, d)
    kf = k.reshape(b * kvh, sp, d)
    vf = v.reshape(b * kvh, sp, d)
    out = flash_attention_bhsd(
        qf,
        kf,
        vf,
        group=group,
        scale=scale,
        causal=causal,
        window=window,
        seq_len=s,
        bq=bq_eff,
        bk=bk_eff,
        interpret=resolve_interpret(interpret),
    )
    out = out.reshape(b, h, sp, d)
    return out[:, :, :s, :]

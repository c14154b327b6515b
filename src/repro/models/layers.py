"""Core layers: norms, RoPE, GQA/MLA attention, SwiGLU MLP, MoE.

Pure-functional: every layer is an (init, apply) pair over plain dict
pytrees.  Compute runs in the config dtype (bf16 by default) with f32
softmax/norm accumulations; params are stored f32 for training and cast at
the call site for serving.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard


def dense_init(key, in_dim: int, out_dim: int, scale: float | None = None) -> jnp.ndarray:
    scale = scale if scale is not None else in_dim**-0.5
    return jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * scale


# --------------------------------------------------------------------- norms
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_init(dim: int, norm_type: str = "rmsnorm"):
    if norm_type == "rmsnorm":
        return {"scale": jnp.ones((dim,), jnp.float32)}
    return {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)}


def apply_norm(params, x, norm_type: str = "rmsnorm"):
    if "bias" in params:
        return layernorm(x, params["scale"], params["bias"])
    return rmsnorm(x, params["scale"])


# ---------------------------------------------------------------------- rope
def rope_cos_sin(positions: jnp.ndarray, dim: int, theta: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (...,) int -> cos/sin of shape (..., dim/2) f32."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, hd); cos/sin: (S, hd/2) (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------- attention
def attention_scores_blockwise(
    q: jnp.ndarray,  # (B, S, H, hd)
    k: jnp.ndarray,  # (B, S, KVH, hd)
    v: jnp.ndarray,  # (B, S, KVH, hd)
    causal: bool = True,
    window: int | None = None,
    block: int = 1024,
    scale: float | None = None,
) -> jnp.ndarray:
    """Flash-style blockwise attention in pure jnp (lax.scan over KV blocks
    with an online softmax).  Same memory character as the Pallas kernel —
    the (S, S) logits never materialize — so the dry-run HLO reflects the
    deployed algorithm; on real TPU kernels/flash_attention replaces it.
    """
    b, s, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    dv = v.shape[-1]  # value head dim may differ from q/k (MLA)
    group = h // kvh
    scale = scale if scale is not None else hd**-0.5

    if sk <= block:
        return _attention_dense(q, k, v, causal, window, scale)

    nb = -(-sk // block)
    pad = nb * block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nb, block, kvh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block, kvh, dv).transpose(1, 0, 2, 3, 4)

    # GROUPED GQA: contract q heads against their shared KV head directly
    # (perf iteration H1 — the jnp.repeat formulation forced the SPMD
    # partitioner into involuntary resharding and repeat-materialization).
    qf = q.reshape(b, s, kvh, group, hd).astype(jnp.float32)
    qpos = jnp.arange(s)

    @jax.checkpoint
    def body(carry, inputs):
        # The body is the Pallas flash kernel's interior: on TPU the score
        # tiles live in VMEM and never reach HBM (kernels/flash_attention,
        # validated vs oracle).  The named scope lets the dry-run analyzer
        # model that (kernel-interior accounting — perf iteration H6).
        with jax.named_scope("vmem_flash"):
            m_prev, l_prev, acc = carry  # (B,K,G,S) x2, (B,K,G,S,dv)
            kblk, vblk, bi = inputs  # (B, block, KVH, hd/dv), scalar block idx
            kf = kblk.astype(jnp.float32)
            vf = vblk.astype(jnp.float32)
            sc = jnp.einsum("bqkgd,bmkd->bkgqm", qf, kf) * scale  # (B,K,G,S,block)
            kpos = bi * block + jnp.arange(block)
            mask = (kpos[None, :] < sk)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            sc = jnp.where(mask[None, None, None], sc, -1e30)
            m_cur = sc.max(axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_prev + p.sum(axis=-1)
            acc = corr[..., None] * acc + jnp.einsum("bkgqm,bmkd->bkgqd", p, vf)
            return (m_new, l_new, acc), None

    m0 = jnp.full((b, kvh, group, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, group, s), jnp.float32)
    a0 = jnp.zeros((b, kvh, group, s, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4)  # (B, S, K, G, dv)
    return out.reshape(b, s, h, dv).astype(q.dtype)


def _attention_dense(q, k, v, causal, window, scale):
    b, s, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    dv = v.shape[-1]
    group = h // kvh
    qg = q.reshape(b, s, kvh, group, hd).astype(jnp.float32)
    sc = jnp.einsum("bqkgd,bmkd->bkgqm", qg, k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((s, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    sc = jnp.where(mask[None, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgqm,bmkd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, dv).astype(q.dtype)


def decode_attention_jnp(
    q: jnp.ndarray,  # (B, H, hd) — one token
    k_cache: jnp.ndarray,  # (B, S, KVH, hd)
    v_cache: jnp.ndarray,  # (B, S, KVH, hd)
    lengths: jnp.ndarray,  # (B,)
    window: int | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """GQA decode in GROUPED form: query heads sharing a KV head contract
    against the cache directly (einsum 'bkgd,bskd'), so the cache is read
    once and never materialized group-times over (perf iteration H7 —
    the jnp.repeat formulation tripled decode HBM traffic)."""
    b, h, hd = q.shape
    kvh = k_cache.shape[2]
    group = h // kvh
    scale = scale if scale is not None else hd**-0.5
    qg = q.reshape(b, kvh, group, hd).astype(jnp.float32)
    # kernel interior (kernels/decode_attention on TPU): logits/probs stay
    # in VMEM; HBM traffic = the K/V cache stream (counted at the reads).
    with jax.named_scope("vmem_flash"):
        sc = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32)) * scale
        pos = jnp.arange(k_cache.shape[1])[None, None, None, :]
        mask = pos < lengths[:, None, None, None]
        if window is not None:
            mask &= pos >= lengths[:, None, None, None] - window
        sc = jnp.where(mask, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


# ------------------------------------------------------------- GQA attention
def gqa_init(key, cfg) -> dict:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "wq": dense_init(k1, cfg.d_model, cfg.num_heads * hd),
        "wk": dense_init(k2, cfg.d_model, cfg.num_kv_heads * hd),
        "wv": dense_init(k3, cfg.d_model, cfg.num_kv_heads * hd),
        "wo": dense_init(k4, cfg.num_heads * hd, cfg.d_model),
    }
    if cfg.qk_norm:
        params["q_norm"] = norm_init(hd)
        params["k_norm"] = norm_init(hd)
    return params


def gqa_project_qkv(params, cfg, x, positions):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,KVH,hd) with rope + qk-norm."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(b, s, cfg.num_heads, hd)
    k = (x @ params["wk"].astype(dt)).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ params["wv"].astype(dt)).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"]["scale"])
        k = rmsnorm(k, params["k_norm"]["scale"])
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def gqa_apply(params, cfg, x, positions, causal=True, window=None):
    """Full-sequence GQA attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = gqa_project_qkv(params, cfg, x, positions)
    q = shard(q, "batch", None, "heads", None)
    # K/V stay head-replicated when kv_heads doesn't divide the model axis
    # (H1: constraining them onto 'model' forced involuntary resharding).
    k = shard(k, "batch", None, None, None)
    v = shard(v, "batch", None, None, None)
    out = attention_scores_blockwise(q, k, v, causal=causal, window=window)
    out = out.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
    return out @ params["wo"].astype(x.dtype)


# ---------------------------------------------------------------- MLA (DSv2)
def mla_init(key, cfg) -> dict:
    ks = jax.random.split(key, 6)
    qd = cfg.nope_head_dim + cfg.rope_head_dim
    params = {
        "wkv_a": dense_init(ks[0], cfg.d_model, cfg.kv_lora_rank + cfg.rope_head_dim),
        "kv_norm": norm_init(cfg.kv_lora_rank),
        "wkv_b": dense_init(
            ks[1], cfg.kv_lora_rank, cfg.num_heads * (cfg.nope_head_dim + cfg.v_head_dim)
        ),
        "wo": dense_init(ks[2], cfg.num_heads * cfg.v_head_dim, cfg.d_model),
    }
    if cfg.q_lora_rank:
        params["wq_a"] = dense_init(ks[3], cfg.d_model, cfg.q_lora_rank)
        params["q_norm"] = norm_init(cfg.q_lora_rank)
        params["wq_b"] = dense_init(ks[4], cfg.q_lora_rank, cfg.num_heads * qd)
    else:
        params["wq"] = dense_init(ks[5], cfg.d_model, cfg.num_heads * qd)
    return params


def mla_compress(params, cfg, x, positions):
    """Host of the MLA cache: x -> (c_kv (B,S,R), k_rope (B,S,rope_hd))."""
    dt = x.dtype
    kv = x @ params["wkv_a"].astype(dt)
    c_kv, k_rope = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, params["kv_norm"]["scale"])
    cos, sin = rope_cos_sin(positions, cfg.rope_head_dim, cfg.rope_theta)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin)[..., 0, :]
    return c_kv, k_rope


def mla_queries(params, cfg, x, positions):
    b, s, _ = x.shape
    dt = x.dtype
    qd = cfg.nope_head_dim + cfg.rope_head_dim
    if cfg.q_lora_rank:
        q = x @ params["wq_a"].astype(dt)
        q = rmsnorm(q, params["q_norm"]["scale"])
        q = q @ params["wq_b"].astype(dt)
    else:
        q = x @ params["wq"].astype(dt)
    q = q.reshape(b, s, cfg.num_heads, qd)
    q_nope, q_rope = jnp.split(q, [cfg.nope_head_dim], axis=-1)
    cos, sin = rope_cos_sin(positions, cfg.rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def mla_expand_kv(params, cfg, c_kv):
    """c_kv (B,S,R) -> k_nope (B,S,H,nope_hd), v (B,S,H,v_hd)."""
    b, s, _ = c_kv.shape
    kv = c_kv @ params["wkv_b"].astype(c_kv.dtype)
    kv = kv.reshape(b, s, cfg.num_heads, cfg.nope_head_dim + cfg.v_head_dim)
    return jnp.split(kv, [cfg.nope_head_dim], axis=-1)


def mla_apply(params, cfg, x, positions, causal=True, window=None):
    """Full-sequence MLA attention (train / prefill)."""
    b, s, _ = x.shape
    q_nope, q_rope = mla_queries(params, cfg, x, positions)
    c_kv, k_rope = mla_compress(params, cfg, x, positions)
    k_nope, v = mla_expand_kv(params, cfg, c_kv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, cfg.num_heads, cfg.rope_head_dim))],
        axis=-1,
    )
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    q = shard(q, "batch", None, "heads", None)
    out = attention_scores_blockwise(q, k, v, causal=causal, window=window, scale=scale)
    out = out.reshape(b, s, cfg.num_heads * cfg.v_head_dim)
    return out @ params["wo"].astype(x.dtype)


# ----------------------------------------------------------------------- MLP
def mlp_init(key, d_model: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff),
        "w_up": dense_init(k2, d_model, d_ff),
        "w_down": dense_init(k3, d_ff, d_model),
    }


def mlp_apply(params, x, act: str = "silu"):
    dt = x.dtype
    g = x @ params["w_gate"].astype(dt)
    u = x @ params["w_up"].astype(dt)
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    names = ("batch",) + (None,) * (x.ndim - 2) + ("mlp",)
    h = shard(g * u, *names)
    return h @ params["w_down"].astype(dt)


# ----------------------------------------------------------------------- MoE
def moe_init(key, cfg) -> dict:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    scale = d**-0.5
    params = {
        "router": dense_init(ks[0], d, e, scale=scale),
        "experts": {
            "w_gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale,
            "w_up": jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale,
            "w_down": jax.random.normal(ks[3], (e, f, d), jnp.float32) * f**-0.5,
        },
    }
    if cfg.num_shared_experts:
        params["shared"] = mlp_init(ks[4], d, cfg.num_shared_experts * f)
    return params


def _moe_dispatch_compute(xt, router, experts, e, k, cap, act, dt, local_expert_range=None):
    """Token-choice top-k dispatch + expert FFNs over tokens ``xt`` (T, D).

    Sort-free ranking: per-(token,slot) assignments are ranked within
    their expert via stable argsort + segment arithmetic, scattered into
    an (E_local*C, D) buffer, FFN'd as one batched matmul, and combined.
    With ``local_expert_range=(lo, n_local)`` only that expert slice is
    computed (the expert-parallel shard_map path) and the caller psums
    partial outputs over the expert axis.
    """
    t, d = xt.shape
    gates = jax.nn.softmax(xt.astype(jnp.float32) @ router, axis=-1)  # (T, E)
    w, idx = jax.lax.top_k(gates, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    n = t * k
    flat_e = idx.reshape(n)
    flat_w = w.reshape(n)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    ranks_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(ranks_sorted)
    keep = pos < cap

    lo, n_local = local_expert_range if local_expert_range else (0, e)
    local_e = flat_e - lo
    mine = keep & (local_e >= 0) & (local_e < n_local)
    slot = jnp.where(mine, local_e * cap + pos, n_local * cap)  # OOB -> dropped

    token_of = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((n_local * cap, d), dt).at[slot].set(
        xt[token_of] * mine[:, None].astype(dt), mode="drop"
    )
    buf = buf.reshape(n_local, cap, d)

    g = jnp.einsum("ecd,edf->ecf", buf, experts["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, experts["w_up"].astype(dt))
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, experts["w_down"].astype(dt))
    out_buf = out_buf.reshape(n_local * cap, d)

    gathered = jnp.where(
        mine[:, None], out_buf[jnp.minimum(slot, n_local * cap - 1)], jnp.zeros((), dt)
    )
    return (gathered * flat_w[:, None].astype(dt)).reshape(t, k, d).sum(axis=1)


def moe_apply(params, cfg, x, act: str = "silu"):
    """Token-choice top-k MoE with per-expert capacity (Switch-style).

    Two execution paths:

    * single-device / no mesh: the plain dispatch+batched-matmul form;
    * under sharding rules (production meshes): EXPERT-PARALLEL shard_map
      (perf iteration H5) — tokens stay sharded over the data axes, each
      model shard routes its local tokens to its own E/TP experts and
      partial outputs psum over "model".  The data-dependent scatter never
      leaves the device, so the SPMD partitioner cannot replicate it (the
      baseline's dominant collective cost: replicated (T, D) dispatch
      buffers).
    """
    from repro.distributed.sharding import get_rules

    b, s, d = x.shape
    dt = x.dtype
    e, k = cfg.num_experts, cfg.experts_per_token

    rules = get_rules()
    mesh = jax.sharding.get_abstract_mesh()
    batch_axes = (rules or {}).get("batch", "data")
    if not isinstance(batch_axes, tuple):
        batch_axes = (batch_axes,)
    data_size = 1
    if not mesh.empty:
        for a in batch_axes:
            if a and a in mesh.shape:
                data_size *= mesh.shape[a]
    use_ep = (
        rules is not None
        and not mesh.empty
        and "model" in mesh.shape
        and e % mesh.shape["model"] == 0
        and b % data_size == 0
        and b >= data_size
        # EP pays a weight-degather when params are FSDP-sharded; only
        # worth it for prefill/train-sized token counts (perf note in
        # EXPERIMENTS §Perf: decode_32k regressed 12x under EP).
        and (b // data_size) * s >= 256
    )

    if not use_ep:
        t = b * s
        cap = max(int(cfg.moe_capacity_factor * t * k / e), min(t * k, 8))
        y = _moe_dispatch_compute(
            x.reshape(t, d), params["router"], params["experts"], e, k, cap, act, dt
        )
        if "shared" in params:
            y = y + mlp_apply(params["shared"], x.reshape(t, d), act)
        return y.reshape(b, s, d)

    tp = mesh.shape["model"]
    n_local_e = e // tp
    t_local = (b // data_size) * s
    # decode-sized token counts: keep enough slack that collision drops
    # stay negligible (memory cost is trivial at this scale)
    cap = max(int(cfg.moe_capacity_factor * t_local * k / e), min(t_local * k, 8))

    from jax.sharding import PartitionSpec as P

    def ep_body(xt_loc, router, experts):
        m = jax.lax.axis_index("model")
        y_partial = _moe_dispatch_compute(
            xt_loc, router, experts, e, k, cap, act, dt,
            local_expert_range=(m * n_local_e, n_local_e),
        )
        return jax.lax.psum(y_partial, "model")

    xt = x.reshape(b * s, d)
    y = jax.shard_map(
        ep_body,
        mesh=mesh,
        in_specs=(P(batch_axes), P(), P("model")),
        out_specs=P(batch_axes),
        check_vma=False,
    )(xt, params["router"], params["experts"])

    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt, act)
    return y.reshape(b, s, d)


def moe_aux_loss(params, cfg, x) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch/olmoe style)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    xt = x.reshape(b * s, d)
    gates = jax.nn.softmax(xt.astype(jnp.float32) @ params["router"], axis=-1)
    _, idx = jax.lax.top_k(gates, k)
    frac = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (b * s * k)
    prob = gates.mean(axis=0)
    return e * jnp.sum(frac * prob)

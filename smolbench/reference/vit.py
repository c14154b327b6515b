"""Plain Vision Transformer reference (Dosovitskiy et al. 2021,
arXiv:2010.11929) and the benchmark's weights.

The weights are the benchmark's, made on the device from the seed in the
configuration file in one jitted call, and laid out as the served model
takes them: ``patch`` (``w`` HWIO, ``b``), ``cls``, ``pos``, ``blocks``
with every leaf stacked over the layers (``ln1``, ``qkv``, ``proj``,
``ln2``, ``fc1``, ``fc2``), the final ``ln`` and ``head``.  The qkv output
is laid out as (3, heads, head_dim).  Matrices are drawn with standard
deviation ``fan_in ** -0.5``; biases, layer-norm offsets and the class and
position embeddings are non-zero, and layer-norm scales lie in [0.5, 1.5),
so that a lost or misapplied term shows in the logits.

``forward`` is the reference: every product in float32 at ``highest``
precision, one layer after another.  ``forward(..., quant=True)`` is the
control: the same network with both operands of every matmul (the patch
projection, qkv, the two attention products, the output projection, both
MLP layers and the head) rounded to int8 (symmetric, one scale per tensor),
the step below the served precision.

Departures from the publication, all in the configuration's ``assumed``:
random weights from a seed (the published head is zero-initialised, this
one is not, so that the logits carry the network); GELU in the tanh form of
the original JAX release; the served preprocessing is the benchmark's
(``reference/preproc.py``: bilinear resize and ImageNet mean and std).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _tokens(cfg: dict, size: int) -> int:
    return (size // cfg["patch"]) ** 2 + 1


def _build(cfg: dict, take) -> dict:
    """The weight tree, each leaf from ``take(shape, pool, scale, shift)``:
    ``scale * draw + shift`` with draws from the "normal" or the "uniform"
    [0, 1) pool."""
    d, m, n_layers, p = cfg["hidden"], cfg["mlp"], cfg["layers"], cfg["patch"]

    def dense(fan_in, fan_out, stack=()):
        return {"w": take((*stack, fan_in, fan_out), "normal", fan_in**-0.5, 0.0),
                "b": take((*stack, fan_out), "normal", 0.1, 0.0)}

    def ln(stack=()):
        return {"scale": take((*stack, d), "uniform", 1.0, 0.5), "bias": take((*stack, d), "normal", 0.1, 0.0)}

    stack = (n_layers,)
    return {
        "patch": {"w": take((p, p, 3, d), "normal", (p * p * 3) ** -0.5, 0.0),
                  "b": take((d,), "normal", 0.1, 0.0)},
        "cls": take((d,), "normal", 1.0, 0.0),
        "pos": take((_tokens(cfg, cfg["input_size"]), d), "normal", 0.5, 0.0),
        "blocks": {"ln1": ln(stack), "qkv": dense(d, 3 * d, stack), "proj": dense(d, d, stack),
                   "ln2": ln(stack), "fc1": dense(d, m, stack), "fc2": dense(m, d, stack)},
        "ln": ln(),
        "head": dense(d, cfg["num_classes"]),
    }


def _init(cfg: dict, key) -> dict:
    # one draw per pool, carved into leaves: two random ops in one program
    sizes = {"normal": 0, "uniform": 0}

    def count(shape, pool, _scale, _shift):
        sizes[pool] += int(np.prod(shape))

    _build(cfg, count)
    kn, ku = jax.random.split(key)
    pools = {"normal": jax.random.normal(kn, (sizes["normal"],), jnp.float32),
             "uniform": jax.random.uniform(ku, (sizes["uniform"],), jnp.float32)}
    offsets = {"normal": 0, "uniform": 0}

    def take(shape, pool, scale, shift):
        n, at = int(np.prod(shape)), offsets[pool]
        offsets[pool] = at + n
        return pools[pool][at : at + n].reshape(shape) * scale + shift

    return _build(cfg, take)


def init_params(cfg: dict) -> dict:
    """The configuration's weights, on the device, from its ``weights.seed``."""
    init = jax.jit(functools.partial(_init, cfg))
    return init(jax.random.PRNGKey(cfg["weights"]["seed"]))


def _fake_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


def _mm(spec: str, a, b, quant: bool):
    if quant:
        a, b = _fake_int8(a), _fake_int8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _ln(p, x, eps: float):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _forward(params, x, cfg: dict, quant: bool):
    d, heads, p, eps = cfg["hidden"], cfg["heads"], cfg["patch"], cfg["layer_norm_eps"]
    n, _c, h, w = x.shape
    # patches as (N, H/P * W/P, P * P * 3) rows in (row, col, channel) order,
    # the HWIO kernel flattened to match
    rows = x.reshape(n, 3, h // p, p, w // p, p).transpose(0, 2, 4, 3, 5, 1).reshape(n, -1, p * p * 3)
    tokens = _mm("ntk,kd->ntd", rows, params["patch"]["w"].reshape(-1, d), quant) + params["patch"]["b"]
    cls = jnp.broadcast_to(params["cls"], (n, 1, d))
    y = jnp.concatenate([cls, tokens], axis=1) + params["pos"]
    t, hd = y.shape[1], d // heads
    for i in range(cfg["layers"]):
        b = jax.tree_util.tree_map(lambda a, i=i: a[i], params["blocks"])
        u = _ln(b["ln1"], y, eps)
        qkv = (_mm("ntd,de->nte", u, b["qkv"]["w"], quant) + b["qkv"]["b"]).reshape(n, t, 3, heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        a = jax.nn.softmax(_mm("nqhd,nkhd->nhqk", q, k, quant) / np.sqrt(hd), axis=-1)
        o = _mm("nhqk,nkhd->nqhd", a, v, quant).reshape(n, t, d)
        y = y + _mm("ntd,de->nte", o, b["proj"]["w"], quant) + b["proj"]["b"]
        u = _ln(b["ln2"], y, eps)
        u = _mm("ntd,dm->ntm", u, b["fc1"]["w"], quant) + b["fc1"]["b"]
        u = 0.5 * u * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (u + 0.044715 * u**3)))
        y = y + _mm("ntm,md->ntd", u, b["fc2"]["w"], quant) + b["fc2"]["b"]
    c = _ln(params["ln"], y[:, 0], eps)
    return _mm("nd,dc->nc", c, params["head"]["w"], quant) + params["head"]["b"]


def forward(params, cfg: dict, x, quant: bool = False):
    """The reference network on a (N, 3, H, W) jax batch, traceable: with
    ``quant`` the int8 control, which a run can serve in the program's place."""
    return _forward(params, x, cfg, quant)


@functools.lru_cache(maxsize=8)
def _jitted(cfg_key: str, quant: bool):
    return jax.jit(functools.partial(_forward, cfg=json.loads(cfg_key), quant=quant))


def logits(params, cfg: dict, x: np.ndarray, quant: bool = False, block: int = 32) -> np.ndarray:
    """Logits of a (N, 3, H, W) float32 batch, ``block`` rows at a time so
    that the reference fits beside nothing else on the device."""
    keys = ("patch", "layers", "hidden", "heads", "mlp", "num_classes", "layer_norm_eps")
    fn = _jitted(json.dumps({k: cfg[k] for k in keys}, sort_keys=True), quant)
    out = []
    for lo in range(0, len(x), block):
        part = x[lo : lo + block]
        pad = block - len(part)
        if pad:  # one program shape for every block
            part = np.concatenate([part, np.zeros((pad, *part.shape[1:]), part.dtype)])
        out.append(np.asarray(fn(params, jnp.asarray(part)))[: block - pad])
    return np.concatenate(out)


def macs(cfg: dict, size: int) -> int:
    """Multiply-accumulates of one forward pass at ``size`` x ``size``
    input, matmuls only: the patch projection, per block qkv, the two
    attention products, the output projection and both MLP layers, and the
    head (normalization, softmax, GELU and bias adds excluded)."""
    d, m, p = cfg["hidden"], cfg["mlp"], cfg["patch"]
    t = _tokens(cfg, size)
    patch = (t - 1) * p * p * 3 * d
    block = t * d * 3 * d + 2 * t * t * d + t * d * d + 2 * t * d * m
    return patch + cfg["layers"] * block + d * cfg["num_classes"]


def item_flops(cfg: dict, size: int) -> int:
    """Operations of one forward pass at ``size`` x ``size`` input: two per
    multiply-accumulate of ``macs``."""
    return 2 * macs(cfg, size)

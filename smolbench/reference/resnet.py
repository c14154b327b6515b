"""Plain ResNet reference (He et al. 2016, torchvision v1.5 layout) and the
benchmark's weights.

The weights are the benchmark's, made on the device from the seed in the
configuration file in one jitted call, and laid out as the served model
takes them: ``stem``/``stem_bn``, ``stages`` of blocks with ``conv1..3``,
``bn1..3`` and a ``proj``/``proj_bn`` shortcut where the shape changes, and a
bias-free ``head``.  Convolutions are HWIO, activations NCHW; bottleneck
blocks stride on the 3x3.  Batch norm runs in inference mode from random
statistics, so that a lost or misapplied normalization shows in the logits.

``forward`` is the reference: every product in float32 at ``highest``
precision.  ``forward(..., quant=True)`` is the control: the same network with each
convolution's and the head's operands rounded to int8 (symmetric, one scale
per tensor), the step below the served precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _blocks(cfg: dict):
    """(stage, index, cin, cmid, cout, stride) of every residual block."""
    expand = 4 if cfg["block"] == "bottleneck" else 1
    cin = cfg["width"]
    for si, n in enumerate(cfg["stage_sizes"]):
        cmid = cfg["width"] * 2**si
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            yield si, bi, cin, cmid, cmid * expand, stride
            cin = cmid * expand


def _conv_shapes(cfg: dict, cin: int, cmid: int, cout: int) -> dict:
    if cfg["block"] == "bottleneck":
        return {"conv1": (1, 1, cin, cmid), "conv2": (3, 3, cmid, cmid), "conv3": (1, 1, cmid, cout)}
    return {"conv1": (3, 3, cin, cmid), "conv2": (3, 3, cmid, cout)}


def _build(cfg: dict, take) -> dict:
    """The weight tree, each leaf from ``take(shape, pool, scale, shift)``:
    ``scale * draw + shift`` with draws from the "normal" or the "uniform"
    [0, 1) pool."""

    def he(shape):  # He normal: std sqrt(2 / fan_in)
        return take(shape, "normal", (2.0 / (shape[0] * shape[1] * shape[2])) ** 0.5, 0.0)

    def bn(c):
        return {"scale": take((c,), "uniform", 1.0, 0.5), "bias": take((c,), "normal", 0.1, 0.0),
                "mean": take((c,), "normal", 0.1, 0.0), "var": take((c,), "uniform", 1.0, 0.5)}

    w = cfg["width"]
    params = {"stem": he((7, 7, 3, w)), "stem_bn": bn(w), "stages": []}
    for si, bi, cin, cmid, cout, stride in _blocks(cfg):
        if bi == 0:
            params["stages"].append([])
        p = {}
        for i, (name, shape) in enumerate(_conv_shapes(cfg, cin, cmid, cout).items(), 1):
            p[name] = he(shape)
            p[f"bn{i}"] = bn(shape[3])
        if stride != 1 or cin != cout:
            p["proj"] = he((1, 1, cin, cout))
            p["proj_bn"] = bn(cout)
        params["stages"][si].append(p)
    params["head"] = take((cout, cfg["num_classes"]), "normal", cout**-0.5, 0.0)
    return params


def _init(cfg: dict, key) -> dict:
    # one draw per pool, carved into leaves: a program of two random ops
    # compiles in seconds where one draw per leaf takes a minute
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


def _conv(x, w, stride, quant):
    if quant:
        x, w = _fake_int8(x), _fake_int8(w)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NCHW", "HWIO", "NCHW"), precision=HI
    )


def _bn_apply(p, x):
    inv = jax.lax.rsqrt(p["var"] + 1e-5)
    return (x - p["mean"][:, None, None]) * (inv * p["scale"])[:, None, None] + p["bias"][:, None, None]


def _forward(params, x, cfg: dict, quant: bool):
    y = jax.nn.relu(_bn_apply(params["stem_bn"], _conv(x, params["stem"], 2, quant)))
    y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2), "SAME")
    for si, bi, _cin, _cmid, _cout, stride in _blocks(cfg):
        p = params["stages"][si][bi]
        bottleneck = cfg["block"] == "bottleneck"
        # bottleneck (v1.5): 1x1, 3x3 with the stride, 1x1; basic: 3x3 with the stride, 3x3
        strides = (1, stride, 1) if bottleneck else (stride, 1)
        h = y
        for i, s in enumerate(strides, 1):
            h = _bn_apply(p[f"bn{i}"], _conv(h, p[f"conv{i}"], s, quant))
            if i < len(strides):
                h = jax.nn.relu(h)
        sc = y
        if "proj" in p:
            sc = _bn_apply(p["proj_bn"], _conv(y, p["proj"], stride, quant))
        y = jax.nn.relu(h + sc)
    y = y.mean(axis=(2, 3))
    head = params["head"]
    if quant:
        y, head = _fake_int8(y), _fake_int8(head)
    return jnp.matmul(y, head, precision=HI)


def forward(params, cfg: dict, x, quant: bool = False):
    """The reference network on a (N, 3, H, W) jax batch, traceable: with
    ``quant`` the int8 control, which a run can serve in the program's place."""
    return _forward(params, x, cfg, quant)


@functools.lru_cache(maxsize=8)
def _jitted(cfg_key: str, quant: bool):
    import json

    cfg = json.loads(cfg_key)
    return jax.jit(functools.partial(_forward, cfg=cfg, quant=quant))


def logits(params, cfg: dict, x: np.ndarray, quant: bool = False, block: int = 32) -> np.ndarray:
    """Logits of a (N, 3, H, W) float32 batch, ``block`` rows at a time so
    that the reference fits beside nothing else on the device."""
    import json

    fn = _jitted(json.dumps({k: cfg[k] for k in ("block", "stage_sizes", "width")}, sort_keys=True), quant)
    out = []
    for lo in range(0, len(x), block):
        part = x[lo : lo + block]
        pad = block - len(part)
        if pad:  # one program shape for every block
            part = np.concatenate([part, np.zeros((pad, *part.shape[1:]), part.dtype)])
        out.append(np.asarray(fn(params, jnp.asarray(part)))[: block - pad])
    return np.concatenate(out)


def item_flops(cfg: dict, size: int) -> int:
    """Operations of one forward pass at ``size`` x ``size`` input: two per
    multiply-accumulate of ``conv_macs``."""
    return 2 * conv_macs(cfg, size)


def conv_macs(cfg: dict, size: int) -> int:
    """Multiply-accumulates of one forward pass at ``size`` x ``size`` input:
    every convolution and the head (pooling and normalization excluded, as
    in the published counts)."""
    total = 0
    hw = -(-size // 2)  # stem, stride 2 (SAME)
    total += hw * hw * 7 * 7 * 3 * cfg["width"]
    hw = -(-hw // 2)  # max pool, stride 2
    for _si, _bi, cin, cmid, cout, stride in _blocks(cfg):
        out_hw = -(-hw // stride)
        if cfg["block"] == "bottleneck":
            total += hw * hw * cin * cmid  # 1x1 at the input resolution
            total += out_hw * out_hw * 9 * cmid * cmid  # 3x3 with the stride
            total += out_hw * out_hw * cmid * cout
        else:
            total += out_hw * out_hw * 9 * cin * cmid
            total += out_hw * out_hw * 9 * cmid * cout
        if stride != 1 or cin != cout:
            total += out_hw * out_hw * cin * cout
        hw = out_hw
    return total + cout * cfg["num_classes"]

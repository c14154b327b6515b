"""ViT on the served path (``models/vit.py``) against its plain reference
(``smolbench/reference/vit.py``) on seeded random weights at a toy size, the
argument-weights contract of ``ModelEntry`` programs, the chain's stated
short side, and the counts at the published ViT-L/16 widths.

Tolerance: on the CPU both sides compute in float32 (the CPU's default
matmul precision is full float32), so they differ only by summation order
(``lax.scan`` against a loop, einsum against reshaped products).  The
widest logit gap is held under 2e-5 of the largest logit, about 100 float32
ulps, while the same reference at int8 operands, the step below the served
precision, misses by far more than that.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smooth_image
from repro.core import dag
from repro.core.device_compiler import ModelEntry, compile_device_program
from repro.core.planner import ModelSpec, central_roi, standard_chain
from repro.models.vit import ViTConfig, vit_forward
from repro.preprocessing.formats import ImageFormat, StoredImage
from repro.preprocessing.ops import TensorMeta
from repro.runtime import DeviceCompilerConfig, RuntimeConfig, SmolRuntime
from smolbench.reference import vit as reference

TOL = 2e-5  # widest logit gap over the largest logit (see the module docstring)
TOY = {"patch": 8, "layers": 2, "hidden": 32, "heads": 4, "mlp": 64, "num_classes": 10,
       "layer_norm_eps": 1e-6, "input_size": 32, "resize_short": 32, "weights": {"seed": 5}}
VITL = json.loads((Path(__file__).parents[1] / "smolbench/configs/vitl16_384.json").read_text())


def _net(cfg):
    return ViTConfig(cfg["input_size"], cfg["patch"], cfg["layers"], cfg["hidden"], cfg["heads"],
                     cfg["mlp"], cfg["num_classes"], cfg["layer_norm_eps"])


def _gap(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def params():
    return reference.init_params(TOY)


@pytest.mark.parametrize("size", [32, 48])
def test_program_forward_matches_reference(params, size):
    cfg = {**TOY, "input_size": size}
    p = reference.init_params(cfg) if size != TOY["input_size"] else params
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(size), (5, 3, size, size)), np.float32)
    got = jax.jit(lambda p, x: vit_forward(p, _net(cfg), x))(p, x)
    want = reference.logits(p, cfg, x, block=4)
    assert got.shape == (5, cfg["num_classes"])
    assert _gap(got, want) < TOL
    # the int8 control is far outside the tolerance: it holds the precision
    assert _gap(reference.logits(p, cfg, x, quant=True, block=4), want) > 100 * TOL


def _corpus(n=10):
    fmt = ImageFormat("jpeg", None, 95)
    rng = np.random.default_rng(3)
    return fmt, [StoredImage.from_array(smooth_image(rng, 64, 64), [fmt]) for _ in range(n)]


def _runtime(model_fn, fmt, corpus, **config):
    spec = ModelSpec("vit", TOY["input_size"], 1000.0, {fmt.key: 0.9}, resize_short=TOY["resize_short"])
    return SmolRuntime(
        [spec], [fmt], {"vit": model_fn}, calibration=corpus[:2],
        config=RuntimeConfig(batch_size=4, num_workers=2, max_wait_ms=2.0,
                             device=DeviceCompilerConfig(backend="fused", split_decode="full"), **config),
        decode_time=lambda fmt: 1e-4,
    )


def _entry(params):
    """Logits with the network input appended, so the reference sees the
    input the served path made."""
    net = _net(TOY)

    def fn(p, x):
        return jnp.concatenate([vit_forward(p, net, x), x.reshape(x.shape[0], -1)], axis=1)

    return ModelEntry(fn, params)


def test_served_through_submit_drain_matches_reference(params):
    from repro.runtime import ClassificationQuery

    fmt, corpus = _corpus()
    rt = _runtime(_entry(params), fmt, corpus)
    rt.start_serving()
    try:
        for item in corpus:
            rt.submit(ClassificationQuery(image=item))
        rt.flush(timeout=120.0)
        done = rt.drain()
    finally:
        rt.stop_serving()
    assert len(done) == len(corpus) and not any(r.error for r in done)
    out = np.stack([np.asarray(r.scores, np.float32) for r in done])
    nc, size = TOY["num_classes"], TOY["input_size"]
    x = out[:, nc:].reshape(len(out), 3, size, size)
    assert _gap(out[:, :nc], reference.logits(params, TOY, x, block=4)) < TOL


def _main_args(lowered) -> int:
    (sig,) = [line for line in lowered.as_text().splitlines() if "func.func public @main(" in line]
    return sig.count("%arg")


def test_lowered_main_takes_the_params(params):
    entry = _entry(params)
    meta = TensorMeta((3, 32, 32), "float32", "CHW")
    batch = np.zeros((2, 3, 32, 32), np.float32)
    leaves = len(jax.tree_util.tree_leaves(params))
    prog = compile_device_program([], meta, entry, 2)
    assert _main_args(prog.lower(batch)) == leaves + 1
    np.testing.assert_allclose(np.asarray(prog(batch)), np.asarray(entry(batch)), rtol=1e-6, atol=1e-6)
    # a closure keeps today's program: the batch alone, the weights inside
    closure = compile_device_program([], meta, lambda x: entry(x), 2)
    assert closure.params is None
    assert _main_args(closure.lower(batch)) == 1


def test_bucket_programs_share_one_copy_of_the_weights(params):
    fmt, corpus = _corpus(4)
    entry = _entry(params)
    rt = _runtime(entry, fmt, corpus, warmup="full")
    compiled = rt.compile()
    assert rt.wait_warm(timeout=120.0)
    (ps,) = compiled.program_sets
    assert ps.buckets == (1, 2, 4)
    first = jax.tree_util.tree_leaves(ps.programs[4].params)
    for prog in ps.programs.values():
        assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(prog.params), first))
    assert rt.stats().weight_bytes == entry.nbytes == sum(leaf.nbytes for leaf in first)

    closure = _runtime(lambda x: entry(x), fmt, corpus, warmup="full")
    (ps,) = closure.compile().program_sets
    assert closure.wait_warm(timeout=120.0)
    assert all(prog.params is None for prog in ps.programs.values())
    assert closure.stats().weight_bytes == 0


@pytest.mark.parametrize("size,short,crop", [(384, 384, (0, 0, 256, 256)), (224, None, (16, 16, 240, 240))])
def test_stated_short_side_sets_the_crop(size, short, crop):
    meta = TensorMeta((256, 256, 3), "uint8", "HWC")
    chain = standard_chain(size, short)
    assert chain[0].target == (short or 256)  # None keeps ResNet's 256/224
    assert central_roi(size, short)((0, 0, 256, 256)) == crop
    # the optimized chain covers the whole short side at 384 (a 256->384
    # upsample) and 224 of it at 224
    m, covered = meta, 256
    for op in dag.optimize(chain, meta).ops:
        spec = op.lowering_spec(m)
        if spec.kind == "crop":
            covered = round(spec.crop[2] * 256 / m.spatial[0])
        m = op.out_meta(m)
    assert covered == crop[2] - crop[0]
    assert m.shape == (3, size, size)


def test_published_counts():
    shapes = jax.eval_shape(lambda k: reference._init(VITL, k), jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) == 304_715_752
    # per image at 384 px: patch projection, 24 blocks, head
    t, d, m = 577, 1024, 4096
    block = t * d * 3 * d + 2 * t * t * d + t * d * d + 2 * t * d * m
    assert 576 * 16 * 16 * 3 * d + 24 * block + d * 1000 == 191_066_300_416
    assert reference.macs(VITL, 384) == 191_066_300_416
    assert reference.item_flops(VITL, 384) == 2 * 191_066_300_416
    assert VITL["counted"] == {"params": 304_715_752, "macs_per_image": 191_066_300_416}

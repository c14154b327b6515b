"""Compile-only checks of the main path's kernels for a described TPU v5e.

Nothing runs: each test asks the installed TPU compiler to build a program
for one chip of a described (not attached) v5e, so a kernel whose tiling or
VMEM use the chip refuses fails here instead of on the chip.  Interpret mode
never sees those refusals.  The topology is described inside a fixture, so
importing this module never loads the TPU library.
"""

import collections
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dag as dag_mod
from repro.core import device_compiler as DC
from repro.core.planner import standard_chain
from repro.kernels.fused_preproc.ops import bilinear_matrix, fused_resize_affine
from repro.kernels.idct.ops import dequant_idct
from repro.models.resnet import TINY_RESNET, init_resnet, resnet_forward
from repro.preprocessing import jpeg
from repro.preprocessing.ops import TensorMeta


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _kernel_names(compiled) -> set[str]:
    """Instruction names of the Pallas calls, without their numeric suffix:
    the op names a device trace shows for the kernels."""
    return {
        re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)", ln).group(1)
        for ln in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln
    }


def test_idct_kernel_compiles_at_a_served_block_count(one_chip):
    # batch 32 of 256x256 4:2:0: 32 * (1024 luma + 512 chroma) 8x8 blocks
    n = 32 * 1536
    q = jpeg._qtables(95, 3)[0]
    compiled = _compile(
        lambda c: dequant_idct(c, q, interpret=False), one_chip, ((n, 8, 8), jnp.float32)
    )
    assert _custom_calls(compiled) == 1
    assert _kernel_names(compiled) == {"dequant_idct_tiles"}


@pytest.mark.parametrize("h,w", [(256, 256), (375, 500)])
def test_resample_kernel_compiles(one_chip, h, w):
    planes = 32 * 3
    ry = bilinear_matrix(h, 224)
    rxt = np.ascontiguousarray(bilinear_matrix(w, 224).T)
    scale = np.full(planes, 1 / 255 / 0.224, np.float32)
    bias = np.full(planes, -0.45 / 0.224, np.float32)

    def fn(x):
        return fused_resize_affine(x, ry, rxt, scale, bias, round_uint8=True, interpret=False)

    compiled = _compile(fn, one_chip, ((planes, h, w), jnp.float32))
    assert _custom_calls(compiled) == 1
    assert _kernel_names(compiled) == {"fused_resize_normalize_planar"}


def test_resample_whole_plane_exceeds_vmem_at_1080p(one_chip):
    # the kernel holds one whole input plane per grid step: at 1080p that
    # is more VMEM than the chip gives a kernel (tiling H lifts this)
    ry = bilinear_matrix(1080, 224)
    rxt = np.ascontiguousarray(bilinear_matrix(1920, 224).T)
    ones = np.ones(3, np.float32)

    def fn(x):
        return fused_resize_affine(x, ry, rxt, ones, ones, interpret=False)

    with pytest.raises(Exception, match="vmem"):
        _compile(fn, one_chip, ((3, 1080, 1920), jnp.float32))


def test_coefficient_program_compiles_with_both_kernels(one_chip):
    # the whole split-decode program: entropy-stage coefficients in, logits out
    img = np.random.default_rng(0).integers(0, 256, (256, 256, 3), dtype=np.uint8)
    header = jpeg.peek_header(jpeg.encode(img, quality=95, subsample=True))
    params = init_resnet(TINY_RESNET, jax.random.PRNGKey(0))
    ops = dag_mod.optimize(standard_chain(224), TensorMeta((256, 256, 3), "uint8", "HWC")).ops
    prog = DC.compile_coeff_program(
        header,
        ops,
        lambda x: resnet_forward(params, TINY_RESNET, x),
        8,
        layout="packed",
        impl="pallas",
        interpret=False,
    )
    assert not prog.interpret
    shape = (8, *prog.in_meta.shape)
    compiled = prog.fn.lower(jax.ShapeDtypeStruct(shape, jnp.int16, sharding=one_chip)).compile()
    # one IDCT call per quant table plus the fused resample
    assert _custom_calls(compiled) == 3
    # inside the whole program the kernels keep the op names a trace reads
    assert _kernel_names(compiled) == {"dequant_idct_tiles", "fused_resize_normalize_planar"}


def test_vit_coefficient_program_takes_its_weights_as_arguments(one_chip):
    # the ViT's split-decode program at 384 px: a 256->384 upsample in the
    # resample kernel, the weights as arguments rather than constants, and
    # the attention kernel compiled, fed bfloat16 qkv (4 heads of 64)
    from repro.models.vit import ViTConfig, vit_forward

    net = ViTConfig(384, 16, 2, 256, 4, 512, 10)
    d, m, t = net.hidden, net.mlp, net.tokens

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def dense(n_in, n_out, *stack):
        return {"w": leaf(*stack, n_in, n_out), "b": leaf(*stack, n_out)}

    def ln(*stack):
        return {"scale": leaf(*stack, d), "bias": leaf(*stack, d)}

    layers = net.layers
    params = {"patch": {"w": leaf(16, 16, 3, d), "b": leaf(d)}, "cls": leaf(d), "pos": leaf(t, d),
              "blocks": {"ln1": ln(layers), "qkv": dense(d, 3 * d, layers), "proj": dense(d, d, layers),
                         "ln2": ln(layers), "fc1": dense(d, m, layers), "fc2": dense(m, d, layers)},
              "ln": ln(), "head": dense(d, net.num_classes)}
    img = np.random.default_rng(0).integers(0, 256, (256, 256, 3), dtype=np.uint8)
    header = jpeg.peek_header(jpeg.encode(img, quality=95, subsample=True))
    ops = dag_mod.optimize(standard_chain(384, 384), TensorMeta((256, 256, 3), "uint8", "HWC")).ops
    entry = DC.ModelEntry(lambda p, x: vit_forward(p, net, x, platform="tpu"), params)
    prog = DC.compile_coeff_program(header, ops, entry, 4, layout="packed", impl="pallas", interpret=False)
    batch = jax.ShapeDtypeStruct((4, *prog.in_meta.shape), jnp.int16, sharding=one_chip)
    compiled = prog.lower(batch).compile()
    assert _custom_calls(compiled) == 4
    assert _kernel_names(compiled) == {"dequant_idct_tiles", "fused_resize_normalize_planar", "vit_attention"}
    assert re.search(r"vit_attention[.\d]* = bf16\[4,577,256\]", compiled.as_text())
    # every weight is a parameter of the entry computation, beside the batch
    leaves = jax.tree_util.tree_leaves(params)
    entry_block = compiled.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    assert entry_block.count(" parameter(") == len(leaves) + 1
    weight_bytes = sum(4 * int(np.prod(s.shape)) for s in leaves)
    assert compiled.memory_analysis().argument_size_in_bytes >= weight_bytes + 2 * int(np.prod(batch.shape))


@pytest.mark.parametrize("fused", [True, False])
def test_vitl_block_keeps_the_scores_out_of_hbm(one_chip, monkeypatch, fused):
    # one ViT-L/16 block at bucket 32: the attention runs as one vit_attention
    # call, and no float32 (32, 16, 577, 577) score tensor is left in the
    # program.  The unfused formulation (the kernel's oracle at float32)
    # compiled the same way holds that tensor, so the assertion can see it.
    from repro.kernels.vit_attention.ref import vit_attention_ref
    from repro.models import vit

    if not fused:
        monkeypatch.setattr(vit, "vit_attention",
                            lambda qkv, heads, interpret: vit_attention_ref(qkv.astype(jnp.float32), heads))
    net = vit.ViTConfig(384, 16, 24, 1024, 16, 4096, 1000)
    d, m, t = net.hidden, net.mlp, net.tokens

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def dense(n_in, n_out):
        return {"w": leaf(n_in, n_out), "b": leaf(n_out)}

    def ln():
        return {"scale": leaf(d), "bias": leaf(d)}

    block = {"ln1": ln(), "qkv": dense(d, 3 * d), "proj": dense(d, d), "ln2": ln(),
             "fc1": dense(d, m), "fc2": dense(m, d)}
    fn = jax.jit(lambda x, p: vit._block(net, x, p, platform="tpu"))
    compiled = fn.lower(leaf(32, t, d), block).compile()
    scores = "f32[32,16,577,577]" in compiled.as_text()
    if fused:
        assert _custom_calls(compiled) == 1
        assert _kernel_names(compiled) == {"vit_attention"}
        assert not scores
    else:
        assert _custom_calls(compiled) == 0
        assert scores


def test_vit_attention_compiles_for_float32_operands(one_chip):
    # a caller that raises the matmul precision feeds the kernel float32 qkv,
    # which it multiplies at float32 precision
    from repro.kernels.vit_attention import vit_attention

    compiled = _compile(lambda q: vit_attention(q, 4, interpret=False), one_chip, ((2, 577, 768), jnp.float32))
    assert _kernel_names(compiled) == {"vit_attention"}


def test_vit_scopes_probe_attributes_the_blocks_to_their_scopes(one_chip):
    # ``benchmarks/chip_probes.py vit_scopes`` splits a device trace by the
    # named scope of each compiled instruction: on a ViT compiled for the chip
    # the attention kernel falls under attention, every scope holds work, and
    # no matmul, convolution or kernel is left outside the scopes
    from repro.models.vit import ViTConfig, vit_forward

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip_probes.py")
    spec = importlib.util.spec_from_file_location("chip_probes", path)
    probes = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probes)

    net = ViTConfig(64, 16, 2, 256, 4, 512, 10)
    d, m, t = net.hidden, net.mlp, net.tokens

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def dense(n_in, n_out, *stack):
        return {"w": leaf(*stack, n_in, n_out), "b": leaf(*stack, n_out)}

    def ln(*stack):
        return {"scale": leaf(*stack, d), "bias": leaf(*stack, d)}

    layers = net.layers
    params = {"patch": {"w": leaf(16, 16, 3, d), "b": leaf(d)}, "cls": leaf(d), "pos": leaf(t, d),
              "blocks": {"ln1": ln(layers), "qkv": dense(d, 3 * d, layers), "proj": dense(d, d, layers),
                         "ln2": ln(layers), "fc1": dense(d, m, layers), "fc2": dense(m, d, layers)},
              "ln": ln(), "head": dense(d, net.num_classes)}
    fn = jax.jit(lambda p, x: vit_forward(p, net, x, platform="tpu"))
    compiled = fn.lower(params, leaf(8, 3, 64, 64)).compile()
    scope_of = probes._scope_of(compiled)
    assert [scope_of[k] for k in scope_of if k.startswith("vit_attention")] == ["attention"]
    held = collections.Counter(scope_of.values())
    assert all(held[s] for s in probes.SCOPES)
    heavy = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?(?: convolution\(| custom-call\(| fusion\(.*kind=kOutput)")
    for line in compiled.as_text().splitlines():
        match = heavy.match(line)
        if match and match.group(1) in scope_of:
            assert scope_of[match.group(1)] in probes.SCOPES, line[:120]

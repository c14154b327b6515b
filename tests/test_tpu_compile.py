"""Compile-only checks of the main path's kernels for a described TPU v5e.

Nothing runs: each test asks the installed TPU compiler to build a program
for one chip of a described (not attached) v5e, so a kernel whose tiling or
VMEM use the chip refuses fails here instead of on the chip.  Interpret mode
never sees those refusals.  The topology is described inside a fixture, so
importing this module never loads the TPU library.
"""

import os

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


def test_idct_kernel_compiles_at_a_served_block_count(one_chip):
    # batch 32 of 256x256 4:2:0: 32 * (1024 luma + 512 chroma) 8x8 blocks
    n = 32 * 1536
    q = jpeg._qtables(95, 3)[0]
    compiled = _compile(
        lambda c: dequant_idct(c, q, interpret=False), one_chip, ((n, 8, 8), jnp.float32)
    )
    assert _custom_calls(compiled) == 1


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

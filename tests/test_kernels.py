"""Pallas kernel sweeps vs. pure-jnp oracles (interpret mode)."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_preproc.ops import fused_resize_affine, fused_resize_normalize
from repro.kernels.fused_preproc.ref import fused_resize_normalize_ref
from repro.kernels.idct.ops import dequant_idct
from repro.kernels.idct.ref import dequant_idct_ref
from repro.kernels.mode import resolve_interpret
from repro.preprocessing import dct

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [1, 5, 512, 777])
@pytest.mark.parametrize("quality", [50, 95])
def test_idct_sweep(n, quality):
    coeffs = RNG.integers(-300, 300, size=(n, 8, 8)).astype(np.int16)
    q = dct.quality_scale(dct.QTABLE_LUMA, quality)
    out = np.asarray(dequant_idct(coeffs, q))
    ref = np.asarray(dequant_idct_ref(jnp.asarray(coeffs), jnp.asarray(q)))
    np.testing.assert_allclose(out, ref, atol=2e-2)


@pytest.mark.parametrize("point", [8, 4, 2, 1])
@pytest.mark.parametrize("n", [3, 512])
def test_scaled_idct_matches_ref(point, n):
    # the truncated-DCT-basis variants: kernel (one padded 64x64 matmul)
    # vs the direct two-sided A X A^T oracle
    coeffs = RNG.integers(-300, 300, size=(n, 8, 8)).astype(np.int16)
    q = dct.quality_scale(dct.QTABLE_CHROMA, 75)
    out = np.asarray(dequant_idct(coeffs, q, point=point))
    assert out.shape == (n, point, point)
    ref = np.asarray(dequant_idct_ref(jnp.asarray(coeffs), jnp.asarray(q), point=point))
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_scaled_idct_point8_is_full_and_point1_is_dc():
    coeffs = RNG.integers(-200, 200, size=(16, 8, 8)).astype(np.int16)
    q = dct.quality_scale(dct.QTABLE_LUMA, 85)
    full = np.asarray(dequant_idct(coeffs, q, point=8))
    legacy = np.asarray(dequant_idct(coeffs, q))
    np.testing.assert_array_equal(full, legacy)  # point=8 IS the old kernel
    # point=1 reproduces the progressive first-scan DC image: dc * q / 8
    dc = np.asarray(dequant_idct(coeffs, q, point=1))[:, 0, 0]
    np.testing.assert_allclose(dc, coeffs[:, 0, 0] * q[0, 0] / 8.0, atol=1e-3)


def test_scaled_idct_mean_preservation():
    # the scaled basis is DC-consistent: each point x point output block
    # has the same mean as the full-resolution block it reconstructs
    coeffs = RNG.integers(-200, 200, size=(64, 8, 8)).astype(np.int16)
    q = dct.quality_scale(dct.QTABLE_LUMA, 90)
    full = np.asarray(dequant_idct(coeffs, q, point=8))
    for point in (4, 2, 1):
        scaled = np.asarray(dequant_idct(coeffs, q, point=point))
        np.testing.assert_allclose(
            scaled.mean(axis=(1, 2)), full.mean(axis=(1, 2)), atol=1e-2
        )


@pytest.mark.parametrize(
    "h,w,oh,ow", [(161, 193, 224, 224), (64, 64, 224, 224), (300, 200, 96, 128)]
)
def test_fused_preproc_sweep(h, w, oh, ow):
    x = RNG.uniform(0, 255, size=(3, h, w)).astype(np.float32)
    scale = (1 / 255 / np.array([0.229, 0.224, 0.225])).astype(np.float32)
    bias = (-np.array([0.485, 0.456, 0.406]) / np.array([0.229, 0.224, 0.225])).astype(
        np.float32
    )
    out = np.asarray(fused_resize_normalize(x, oh, ow, scale, bias))
    ref = np.asarray(
        fused_resize_normalize_ref(jnp.asarray(x), oh, ow, jnp.asarray(scale), jnp.asarray(bias))
    )
    np.testing.assert_allclose(out, ref, atol=5e-4)


@pytest.mark.parametrize(
    "b,h,kvh,s,d,causal,window",
    [
        (1, 2, 2, 64, 32, True, None),
        (1, 4, 2, 64, 32, True, None),  # GQA
        (2, 4, 1, 96, 32, True, None),  # MQA
        (1, 2, 2, 80, 32, True, None),  # ragged padding
        (1, 2, 2, 64, 32, False, None),  # encoder
        (1, 2, 2, 128, 32, True, 64),  # sliding window
    ],
)
def test_flash_attention_sweep(b, h, kvh, s, d, causal, window):
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, kvh, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, kvh, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), jnp.bfloat16)
    out = flash_attention(q, k, v, bq=32, bk=32)
    ref = attention_ref(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


@pytest.mark.parametrize(
    "b,h,kvh,s,d,window",
    [
        (2, 8, 1, 256, 64, None),
        (2, 8, 2, 256, 64, None),
        (1, 4, 4, 100, 32, None),
        (2, 8, 2, 512, 64, 128),
    ],
)
def test_decode_attention_sweep(b, h, kvh, s, d, window):
    q = jnp.asarray(RNG.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, kvh, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, kvh, s, d)), jnp.float32)
    lengths = jnp.asarray(RNG.integers(max(1, s // 2), s + 1, size=(b,)), jnp.int32)
    out = decode_attention(q, k, v, lengths, window=window, bk=64)
    ref = decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize(
    "wrapper",
    [dequant_idct, fused_resize_affine, fused_resize_normalize, flash_attention, decode_attention],
)
def test_kernel_wrappers_follow_the_backend_rule(wrapper):
    # no wrapper hard-codes interpret mode: None resolves to compiled on a
    # TPU and interpreted everywhere else
    assert inspect.signature(wrapper).parameters["interpret"].default is None
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False and resolve_interpret(True) is True

"""The fused ViT self-attention kernel (``kernels/vit_attention``) in
interpret mode against its oracle (``ref.py``, the einsum and softmax
formulation the ViT blocks ran in XLA).

float32 inputs differ from the oracle only by summation order (about 1e-6
here, held to 1e-5).  bfloat16 inputs round P and the output to bfloat16 on
both sides, so they may differ by one bfloat16 step of the output (up to
2**-7 of its magnitude), and where a probability rounds the other way (the
kernel normalises by a reciprocal, within a float32 ulp of the oracle's
division) by one step of P times v, under 2**-10 here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.vit_attention import vit_attention
from repro.kernels.vit_attention.ops import VMEM_BUDGET, heads_per_step, step_vmem_bytes
from repro.kernels.vit_attention.ref import vit_attention_ref


def _qkv(n, t, heads, head_dim, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, t, 3 * heads * head_dim), jnp.float32)
    return (2.0 * x).astype(dtype)


def _both(qkv, heads):
    got = vit_attention(qkv, heads, interpret=True)
    assert got.shape == (qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3)
    assert got.dtype == qkv.dtype
    return np.asarray(got, np.float32), np.asarray(vit_attention_ref(qkv, heads), np.float32)


@pytest.mark.parametrize(
    "n,t,heads,head_dim",
    [(3, 17, 4, 8), (2, 65, 4, 16), (2, 50, 2, 16), (1, 577, 16, 64)],
)
def test_float32_matches_reference(n, t, heads, head_dim):
    got, want = _both(_qkv(n, t, heads, head_dim, jnp.float32), heads)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_bfloat16_at_vitl_heads_matches_reference(seed):
    # ViT-L/16 at 384 px: 577 tokens, 16 heads of 64, two heads per step
    qkv = _qkv(2, 577, 16, 64, jnp.bfloat16, seed)
    got, want = _both(qkv, 16)
    np.testing.assert_allclose(got, want, rtol=2**-7, atol=2**-10)
    # and as far from float32 arithmetic as the oracle is
    exact = np.asarray(vit_attention_ref(qkv.astype(jnp.float32), 16))
    assert np.abs(got - exact).mean() < 1.1 * np.abs(want - exact).mean()


def test_heads_each_attend_alone():
    # a head's output depends on its own q, k, v lanes only: zeroing the other
    # heads of a step leaves it as it was
    qkv = _qkv(1, 33, 4, 8, jnp.float32)
    full = np.asarray(vit_attention(qkv, 4, interpret=True))
    lanes = np.arange(qkv.shape[-1]) % 32 // 8 == 1  # head 1 of q, k and v
    alone = np.asarray(vit_attention(jnp.where(lanes, qkv, 0.0), 4, interpret=True))
    np.testing.assert_allclose(alone[..., 8:16], full[..., 8:16], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("heads,head_dim,group", [(16, 64, 2), (4, 8, 4), (2, 16, 2), (3, 64, None), (2, 256, None)])
def test_heads_per_step_fill_128_lanes(heads, head_dim, group):
    if group is None:  # the heads do not split into groups of 128 lanes
        with pytest.raises(ValueError, match="do not split"):
            heads_per_step(heads, head_dim)
    else:
        assert heads_per_step(heads, head_dim) == group


def test_a_key_row_past_vmem_fails_loudly():
    assert step_vmem_bytes(577, 128, 2) < VMEM_BUDGET  # ViT-L/16 at 384 px fits
    t = 1400
    assert step_vmem_bytes(t, 128, 2) > VMEM_BUDGET
    qkv = jax.ShapeDtypeStruct((1, t, 3 * 1024), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole key row"):
        jax.eval_shape(lambda x: vit_attention(x, 16, interpret=True), qkv)


def test_qkv_that_does_not_split_into_heads_is_refused():
    with pytest.raises(ValueError, match="does not split"):
        vit_attention(jnp.zeros((1, 4, 30)), 4, interpret=True)

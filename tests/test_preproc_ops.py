"""Preprocessing operators: host/device parity, fusion correctness."""

import numpy as np
from _hypothesis_compat import given, settings, st

from conftest import smooth_image
from repro.preprocessing import ops as P
from repro.preprocessing.ops import TensorMeta


# one uint8 quantization step through the steepest Normalize std
QSTEP = (1.0 / 255.0) / 0.224


@settings(max_examples=10, deadline=None)
@given(h=st.integers(40, 200), w=st.integers(40, 200))
def test_chain_host_device_parity(h, w):
    # the resample re-quantizes uint8 pixels mid-chain: float association
    # can flip a value sitting exactly on a rounding tie, so parity is
    # "within 1e-4 except a vanishing fraction of one-step ties"
    rng = np.random.default_rng(7)
    img = smooth_image(rng, h, w)
    chain = P.STANDARD_RESNET_CHAIN
    yh = P.apply_chain_host(chain, img)
    yd = np.asarray(P.apply_chain_device(chain, img))
    assert yh.shape == (3, 224, 224)
    diff = np.abs(yh - yd)
    assert (diff > 1e-4).mean() < 1e-3, f"{(diff > 1e-4).mean():.2e} of pixels off the host chain"
    assert diff.max() <= QSTEP + 1e-4, "difference exceeds one quantization step"


def test_out_meta_tracks_shapes(rng):
    meta = TensorMeta((300, 400, 3), "uint8", "HWC")
    out = P.chain_out_meta(P.STANDARD_RESNET_CHAIN, meta)
    assert out.shape == (3, 224, 224) and out.dtype == "float32" and out.layout == "CHW"


def test_fused_equals_unfused(rng):
    img = smooth_image(rng, 120, 140)
    tail = [P.ToFloat(), P.Normalize(), P.ChannelsFirst()]
    fused = P.FusedElementwise(tuple(tail))
    a = P.apply_chain_host(tail, img)
    b = fused.apply_host(img)
    assert np.abs(a - b).max() < 1e-5
    bd = np.asarray(fused.apply_device(img))
    assert np.abs(a - bd).max() < 1e-5


def test_fusion_reduces_cost(rng):
    meta = TensorMeta((224, 224, 3), "uint8", "HWC")
    tail = [P.ToFloat(), P.Normalize(), P.ChannelsFirst()]
    fused = [P.FusedElementwise(tuple(tail))]
    assert P.chain_flops(fused, meta) < P.chain_flops(tail, meta)


@settings(max_examples=10, deadline=None)
@given(target=st.sampled_from([64, 128, 161, 224, 256]))
def test_resize_short_side_geometry(target):
    rng = np.random.default_rng(3)
    img = smooth_image(rng, 97, 201)
    out = P.ResizeShortSide(target).apply_host(img)
    assert min(out.shape[:2]) == target
    # aspect preserved within rounding
    assert abs(out.shape[1] / out.shape[0] - 201 / 97) < 0.05

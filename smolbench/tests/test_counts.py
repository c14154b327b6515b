"""FLOP and byte counters, and the peak table."""

import pytest

from smolbench.harness import HERE, load_json
from smolbench.kernels import idct, resample
from smolbench.readers import peaks_for
from smolbench.reference import resnet as ref_net


@pytest.mark.parametrize("config", ["resnet50", "resnet18"])
def test_resnet_macs_match_published(config):
    cfg = load_json(HERE / "configs" / f"{config}.json")
    gmac = ref_net.conv_macs(cfg, cfg["input_size"]) / 1e9
    # He et al. / torchvision: 4.1 and 1.8 GMAC per 224x224 image
    assert gmac == pytest.approx(cfg["published"]["gmac_per_image"], rel=0.02)


def test_idct_count_by_hand():
    # 16x16 4:2:0: a 2x2 luma grid and one 8x8 block per chroma plane
    geom = {"height": 16, "width": 16, "subsample": True}
    assert idct.blocks_per_item(geom) == 6
    flops, bytes_ = idct.count(geom, 3)
    assert flops == 3 * 6 * 64 * 64 * 2
    assert bytes_ == 3 * 6 * 64 * 4 * 2 + 2 * 64 * 64 * 4


@pytest.mark.parametrize("geom,blocks", [
    ({"height": 256, "width": 256, "subsample": True}, 32 * 32 + 2 * 16 * 16),
    ({"height": 161, "width": 161, "subsample": True}, 21 * 21 + 2 * 11 * 11),
    ({"height": 24, "width": 16, "subsample": False}, 3 * 3 * 2),
])
def test_idct_blocks_per_item(geom, blocks):
    assert idct.blocks_per_item(geom) == blocks


def test_resample_count_by_hand():
    # 2 items, 3 planes each, 4x4 crop resized to 8x8:
    # R_y (8x4) @ X (4x4) is 8*4*4 MACs, then (8x4) @ R_x^T (4x8) is 8*4*8
    flops, bytes_ = resample.count({"crop": 4, "size": 8}, 2)
    assert flops == 2 * 6 * (8 * 4 * 4 + 8 * 4 * 8)
    assert bytes_ == 4 * (6 * (4 * 4 + 8 * 8) + 8 * 4 + 4 * 8)


def test_peaks_table():
    v5e = peaks_for("TPU v5 lite")
    assert v5e == {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")

"""Each configuration's model family, and the crop a configuration states.

The harness takes everything that depends on the model from
``families/<family>.py``: the toy size off a TPU, the program's entry and
the plain reference.  These checks hold every configuration's family to that
contract, and the reference's crop to the program's at several input sizes.
"""

import hashlib

import jax
import numpy as np
import pytest

from smolbench import harness
from smolbench.reference import preproc

CONFIGS = sorted(p.stem for p in (harness.HERE / "configs").glob("*.json"))
REFERENCE_API = ("init_params", "logits", "forward", "item_flops")


def _config(name: str) -> dict:
    return harness.load_json(harness.HERE / "configs" / f"{name}.json")


@pytest.mark.parametrize("config", CONFIGS)
def test_family_has_the_whole_contract(config):
    cfg = _config(config)
    fam = harness.family(cfg["family"])
    assert harness.family(cfg["family"]) is fam  # loaded once
    assert isinstance(fam.TINY, dict) and fam.TINY
    for name in REFERENCE_API:
        assert callable(getattr(fam.reference, name)), name
    assert isinstance(cfg["resize_short"], int) and cfg["resize_short"] >= cfg["input_size"] > 0
    spec, model_fn = fam.program(cfg, None, {})
    assert (spec.name, spec.input_size) == (cfg["name"], cfg["input_size"])
    assert callable(model_fn)


def test_family_refuses_a_crop_the_program_does_not_make():
    # at 384 px the ResNet chain resizes the short side to 439, not 256
    cfg = {**_config("resnet50"), "input_size": 384, "resize_short": 256}
    with pytest.raises(ValueError, match="439"):
        harness.family("resnet").program(cfg, None, {})


@pytest.mark.parametrize("config", ["resnet50", "resnet18"])
def test_resnet_item_flops_are_twice_the_macs(config):
    cfg = _config(config)
    ref = harness.family(cfg["family"]).reference
    assert ref.item_flops(cfg, cfg["input_size"]) == 2 * ref.conv_macs(cfg, cfg["input_size"])


# sha256 over each leaf's path and float32 bytes, as the weights were made
# before configurations named their family (CPU backend)
WEIGHT_DIGESTS = {
    ("resnet50", False): "a075d21cf3b34abf69d8b0383a8325f164be70756c44f83ea0e1742502152944",
    ("resnet50", True): "5696d853037d1ffc038523c68d00fee9a93eb1067cbde7d819f17366e798de13",
    ("resnet18", False): "4b6bcb5743e1887449e4e4cf8693f382627c2e31ee410040ad28be14823550fc",
    ("resnet18", True): "f58e9bd4954c3a4ce328b947a0fb35a13817431b2b9aed4bfc10cf784d6dfebc",
}


@pytest.mark.parametrize("config,tiny", sorted(WEIGHT_DIGESTS))
def test_init_params_unchanged(config, tiny):
    if jax.devices()[0].platform != "cpu":
        pytest.skip("the digests are of the CPU backend's draws")
    cfg = _config(config)
    fam = harness.family(cfg["family"])
    params = fam.reference.init_params({**cfg, **fam.TINY} if tiny else cfg)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[(config, tiny)]


# the short side a ResNet-style configuration states at each input size:
# its crop is 224/256 of the short side at every size
RESIZE_SHORT = {224: 256, 288: 329, 384: 439}


@pytest.mark.parametrize("size", sorted(RESIZE_SHORT))
@pytest.mark.parametrize("side", [256, 161])
def test_reference_crop_is_the_programs(size, side):
    from repro.core import dag
    from repro.core.planner import standard_chain
    from repro.preprocessing.ops import TensorMeta

    meta = TensorMeta((side, side, 3), "uint8", "HWC")
    plan = dag.optimize(standard_chain(size), meta)
    crops = [op for op in plan.ops if isinstance(op, dag.CenterCropFraction)]
    assert len(crops) == 1, plan.ops
    top, left, s, _ = crops[0].lowering_spec(meta).crop
    # a ramp image, so the slice's values give its offset
    rgb = np.arange(side * side * 3).reshape(side, side, 3)
    got = preproc.crop(rgb, size, RESIZE_SHORT[size])
    assert got.shape == (s, s, 3)
    np.testing.assert_array_equal(got, rgb[top : top + s, left : left + s])
    cfg = {**_config("resnet50"), "input_size": size, "resize_short": RESIZE_SHORT[size]}
    harness.family("resnet").program(cfg, None, {})  # the family agrees


@pytest.mark.parametrize("config,side,crop", [("resnet50", 256, 224), ("resnet18", 161, 141)])
def test_mfu_counts_the_familys_flops(config, side, crop):
    from smolbench import readers
    from smolbench.kernels import idct, resample

    cfg = _config(config)
    ref = harness.family(cfg["family"]).reference
    size = cfg["input_size"]
    assert preproc.crop_side(side, side, size, cfg["resize_short"]) == crop
    geom = {"height": side, "width": side, "subsample": True, "crop": crop, "size": size}
    ctx = {"family": harness.family(cfg["family"]), "config": cfg, "geometry": geom}
    want = 2.0 * ref.conv_macs(cfg, size) + idct.count(geom, 1)[0] + resample.count(geom, 1)[0]
    assert readers.item_flops(ctx) == want

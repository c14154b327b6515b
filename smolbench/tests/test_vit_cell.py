"""The comparison that decides ``correct`` in the ViT cell, through a whole
dry run off a TPU (the family's toy network, corpus and window), as
``test_check.py`` holds the ResNet cells:

* a sound run comes out correct;
* the DNN control -- the reference network with int8 operands of every
  matmul, served in the program's place on argument weights -- comes out
  not correct;
* the preprocessing control -- the reference's decode and preprocessing
  with its products at ``high`` (three bfloat16 passes) -- comes out not
  correct on ``pixel_off_share``.
"""

import time

import pytest

from smolbench import calibrate, harness

CELL = "vitl16_384.scan_cold"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def jax_cpu():
    jax = harness.configure_jax()
    if jax.devices()[0].platform == "tpu":
        pytest.skip("the dry run is the path off a TPU")
    return jax


@pytest.fixture(scope="module")
def sound(jax_cpu):
    return harness.execute(harness.load_cell(CELL), SEED, 1.0, False, time.perf_counter())


def test_sound_run_is_correct(sound):
    run, result = sound
    assert result["correct"], result["limits"]
    assert result["failed"] == 0
    # the toy network is served as it is published: the whole short side,
    # upsampled or downsampled to the input
    assert run.cfg["resize_short"] == run.cfg["input_size"]


def test_int8_control_is_not_correct(jax_cpu):
    cell = harness.load_cell(CELL)
    control = calibrate.int8_forward(harness.family(cell.config["family"]).reference)
    _run, result = harness.execute(cell, SEED, 1.0, False, time.perf_counter(), control)
    assert not result["correct"]
    assert result["limits"]["logit_gap"]["value"] > result["limits"]["logit_gap"]["limit"]


def test_preprocessing_control_is_not_correct(sound):
    run, _result = sound
    records = calibrate.control_records(list(run.client.records.values()), run.items, run.served,
                                        run.params, run.cfg, passes=3)
    limits, correct, _failed = harness.compare(records, run.items, run.served, run.params, run.cfg,
                                               run.cell.workload["check"])
    assert not correct
    assert limits["pixel_off_share"]["value"] > limits["pixel_off_share"]["limit"]
    assert limits["logit_gap"]["value"] <= limits["logit_gap"]["limit"]

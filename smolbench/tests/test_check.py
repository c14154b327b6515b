"""The comparison that decides ``correct``, driven through a whole dry run
off a TPU (tiny network, corpus and window; the chip check is skipped by
construction, since the dry run is what runs off a TPU).

* a sound run comes out correct;
* the timed path broken where it produces answers comes out not correct:
  one answer altered (the device program's first row), half of each batch
  left out (its rows zeroed), answers rotated within each batch, and each
  batch answered with the previous batch's rows;
* the DNN control -- the reference network with int8 operands served in
  the program's place -- comes out not correct;
* the preprocessing control -- the reference's decode and preprocessing
  with its products at ``high`` (three bfloat16 passes) in the program's
  place -- comes out not correct on ``pixel_off_share``.
"""

import time

import jax.numpy as jnp
import pytest

from smolbench import calibrate, harness

CELLS = ["resnet50.scan_cold", "resnet18.thumb_open"]
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def jax_cpu():
    jax = harness.configure_jax()
    if jax.devices()[0].platform == "tpu":
        pytest.skip("the dry run is the path off a TPU")
    return jax


def _execute(cell_name, forward=None):
    return harness.execute(harness.load_cell(cell_name), SEED, 1.0, False, time.perf_counter(), forward)


def test_sound_run_is_correct(jax_cpu):
    _run, result = _execute("resnet18.thumb_open")
    assert result["correct"], result["limits"]
    assert result["failed"] == 0
    assert result["limits"]["pixel_off_share"]["value"] <= result["limits"]["pixel_off_share"]["limit"]


class _Stale:
    """Each batch answered with the previous batch's rows: answers that
    reach the wrong requests, also where a batch holds a single item."""

    def __init__(self):
        self.last = None

    def __call__(self, out):
        prev, self.last = self.last, out
        if prev is None or prev.shape != out.shape:
            return jnp.zeros_like(out)
        return prev


FAULTS = {
    "one_answer_altered": lambda: lambda out: out.at[0].add(abs(out[0]).max()),
    "half_batch_left_out": lambda: lambda out: out.at[len(out) // 2 :].set(0.0),
    "answers_rotated": lambda: lambda out: jnp.roll(out, 1, axis=0),
    "previous_batch_answers": _Stale,
}
# a rotation needs batches of two or more: the closed loop fills them, the
# tiny open loop's single-item batches leave it nothing to misroute
CASES = [(c, f) for c in CELLS for f in sorted(FAULTS)
         if not (f == "answers_rotated" and c == "resnet18.thumb_open")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_altered_answer_is_not_correct(jax_cpu, cell, fault, monkeypatch):
    from repro.core.device_compiler import DevicePreprocProgram

    produce = DevicePreprocProgram.__call__
    alter = FAULTS[fault]()

    def altered(self, batch):
        return alter(produce(self, batch))

    monkeypatch.setattr(DevicePreprocProgram, "__call__", altered)
    _run, result = _execute(cell)
    assert not result["correct"]
    lim = result["limits"]
    assert any(lim[k]["value"] > lim[k]["limit"] for k in ("logit_gap", "pixel_off_share")), lim


@pytest.mark.parametrize("cell", CELLS)
def test_int8_control_is_not_correct(jax_cpu, cell):
    c = harness.load_cell(cell)
    _run, result = _execute(cell, calibrate.int8_forward(harness.family(c.config["family"]).reference))
    assert not result["correct"]
    assert result["limits"]["logit_gap"]["value"] > result["limits"]["logit_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_preprocessing_control_is_not_correct(jax_cpu, cell):
    run, result = _execute(cell)
    assert result["correct"], result["limits"]
    records = calibrate.control_records(list(run.client.records.values()), run.items, run.served,
                                        run.params, run.cfg, passes=3)
    limits, correct, _failed = harness.compare(records, run.items, run.served, run.params, run.cfg,
                                               run.cell.workload["check"])
    assert not correct
    assert limits["pixel_off_share"]["value"] > limits["pixel_off_share"]["limit"]
    assert limits["logit_gap"]["value"] <= limits["logit_gap"]["limit"]

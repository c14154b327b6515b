"""The trace-to-metric reduction, on a hand-made trace and on a recorded one."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from smolbench import trace as tm
from smolbench.kernels import idct, resample

KERNELS = {"idct": idct.MARKS, "resample": resample.MARKS}


def test_hand_made_trace():
    dev = [
        # overlapping ops merge: busy 100..400
        ("%fusion.1 = f32[8] fusion(f32[8] %x)", 100, 300),
        ("%copy.2 = f32[8] copy(f32[8] %y)", 200, 400),
        # the kernel, and an op that only reads its output
        ("%dequant_idct_tiles.3 = f32[512,64] custom-call(f32[512,64] %a)", 600, 700),
        ("%fusion.4 = f32[8] fusion(f32[512,64] %dequant_idct_tiles.3)", 700, 750),
        # clipped at the window's close
        ("%fused_resize_normalize_planar.1 = f32[6,224,224] custom-call(%b)", 950, 1100),
    ]
    host = [
        ("python3", tm.WINDOW, 0, 1000),
        ("python3", "client.drain", 0, 1000),
        ("python3", "PjitFunction(raw)", 400, 600),
        ("python3", "np.asarray(jax.Array)", 750, 950),
    ]
    r = tm.reduce({"device": {"/device:TPU:0": dev}, "host": host}, 1, KERNELS)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((300 + 150 + 50) * 1e-9)
    assert r["kernel_s"] == pytest.approx({"idct": 100e-9, "resample": 50e-9})
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion": 250e-9, "copy": 200e-9, "dequant_idct_tiles": 100e-9, "fused_resize_normalize_planar": 50e-9}
    )
    assert r["idle_gaps"] == [
        ["client.drain | PjitFunction(raw)", pytest.approx(200e-9)],
        ["client.drain | np.asarray(jax.Array)", pytest.approx(200e-9)],
        ["client.drain | none", pytest.approx(100e-9)],
    ]


@pytest.fixture(scope="module")
def excerpt():
    with gzip.open(Path(__file__).with_name("trace_excerpt.json.gz"), "rt") as f:
        return json.load(f)


def test_recorded_trace(excerpt):
    r = tm.reduce(excerpt, 1, KERNELS)
    lo, hi = tm.window(excerpt)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    # busy, counted again on a 1 ns timeline of the window
    evs = excerpt["device"]["/device:TPU:0"]
    timeline = np.zeros(int(hi - lo), bool)
    for _name, s, e in evs:
        timeline[int(max(s, lo) - lo) : int(min(e, hi) - lo)] = True
    assert r["busy_s"] == pytest.approx(timeline.sum() / 1e9, rel=1e-6)
    # kernels: only the events whose own instruction is the kernel's call
    for kernel, prefix in (("idct", "%dequant_idct_tiles"), ("resample", "%fused_resize_normalize_planar")):
        own = sum(min(e, hi) - max(s, lo) for n, s, e in evs
                  if n.split(" = ")[0].split(".")[0] == prefix and e > lo and s < hi)
        assert own > 0
        assert r["kernel_s"][kernel] == pytest.approx(own / 1e9)
    # the longest gaps, longest first, inside the window and idle
    lengths = [g for _label, g in r["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)
    assert len(lengths) == 10 and max(lengths) <= r["window_s"] - r["busy_s"]
    assert all(label.startswith("client.") for label, _g in r["idle_gaps"])


def test_window_must_be_annotated():
    with pytest.raises(ValueError):
        tm.reduce({"device": {"/device:TPU:0": []}, "host": []}, 1, KERNELS)

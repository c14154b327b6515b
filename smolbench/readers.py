"""Shared arithmetic of the per-layer readers in ``metrics/``.

A reader gets the run's context: two snapshots of the runtime, ``s0`` and
``s1``, taken as the measured window opened and closed (``stats()`` and the
dispatches of each bucket program), the answers released between them
(``completed``), the percentiles of the latencies of the requests due in the
window (``latency_ms``, due time to release), the reduced trace (``trace``,
None without one), the served geometry, the configuration, its model family
and the chip's peaks.  A reader that finds nothing to read returns None, and
the metric is left out of the line.
"""

from __future__ import annotations

import json
from pathlib import Path

from smolbench.kernels import idct, resample

KERNELS = {"idct": idct, "resample": resample}
PEAKS = Path(__file__).with_name("peaks.json")


def peaks_for(kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip, by JAX's ``device_kind``; a
    device that is not in the table is an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if kind not in table or kind == "source":
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]


def _sched(snap):
    return snap["stats"].scheduler.stats


def hist_mean_ms(ctx, stage: str):
    """Mean of one telemetry latency histogram over the window, in ms."""
    h0 = ctx["s0"]["stats"].latency.stages.get(stage)
    h1 = ctx["s1"]["stats"].latency.stages.get(stage)
    if h1 is None:
        return None
    c0, m0 = (h0.count, h0.mean) if h0 is not None else (0, 0.0)
    n = h1.count - c0
    if n <= 0:
        return None
    return (h1.count * h1.mean - c0 * m0) / n * 1e3


def latency_percentile_ms(ctx, key: str):
    """One percentile (``"p95"``) of the window's request latencies, in ms."""
    lat = ctx["latency_ms"]
    return None if lat is None else lat[key]


def batch_fill_pct(ctx):
    """Items per batch over the window as a share of the maximum batch."""
    batches = _sched(ctx["s1"]).batches - _sched(ctx["s0"]).batches
    if batches <= 0:
        return None
    items = _sched(ctx["s1"]).batch_items - _sched(ctx["s0"]).batch_items
    return 100.0 * items / (batches * ctx["batch_size"])


def dispatch_ms(ctx):
    """The scheduler's blocking device call (H2D, program and D2H), per
    batch over the window, in ms (``stats()``'s ``device_busy_seconds``)."""
    batches = _sched(ctx["s1"]).batches - _sched(ctx["s0"]).batches
    if batches <= 0:
        return None
    busy = _sched(ctx["s1"]).device_busy_seconds - _sched(ctx["s0"]).device_busy_seconds
    return busy / batches * 1e3


def idle_pct(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def _dispatches(ctx) -> dict:
    d0, d1 = ctx["s0"]["dispatches"], ctx["s1"]["dispatches"]
    return {b: d1[b] - d0.get(b, 0) for b in d1 if d1[b] > d0.get(b, 0)}


def roofline_pct(ctx, kernel: str):
    """The least time the chip could take for the kernel's calls in the
    window (operations over peak FLOP/s or bytes over peak bandwidth,
    whichever is larger) as a share of the kernel's traced time."""
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None or tr["kernel_s"].get(kernel, 0.0) <= 0:
        return None
    flops = bytes_ = 0.0
    for bucket, n in _dispatches(ctx).items():
        f, b = KERNELS[kernel].count(ctx["geometry"], bucket)
        flops += n * f
        bytes_ += n * b
    if flops <= 0:
        return None
    least = max(flops / peaks["flops_per_s"], bytes_ / peaks["bytes_per_s"])
    return 100.0 * least / tr["kernel_s"][kernel]


def item_flops(ctx) -> float:
    """Operations per served item: the network's, as its family's reference
    counts them, and the two preprocessing kernels' algorithmic work."""
    flops = float(ctx["family"].reference.item_flops(ctx["config"], ctx["geometry"]["size"]))
    for k in KERNELS.values():
        flops += k.count(ctx["geometry"], 1)[0]
    return flops


def mfu_pct(ctx):
    """Items completed in the window times operations per item, over the
    window and the chip's peak."""
    if ctx["peaks"] is None or ctx["completed"] <= 0:
        return None
    rate = ctx["completed"] / ctx["window_s"]
    return 100.0 * rate * item_flops(ctx) / ctx["peaks"]["flops_per_s"]

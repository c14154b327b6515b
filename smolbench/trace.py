"""Reduction from a profiler trace to device metrics.

``load`` reads a ``.xplane.pb`` into plain event lists; ``reduce`` turns
them into the numbers the result line and the per-layer readers use.  Both
work on the trace's own clock, and the window is the span of the harness's
``smolbench.window`` annotation, so the host and device timelines need no
alignment of their own.

* busy: the union of the operation intervals on a device's op line,
  clipped to the window; idle is the rest of the window;
* op and kernel time: summed durations of the events, clipped likewise.
  A TPU op event is named by its whole HLO instruction; an op is known by
  the instruction's own name without its numeric suffix
  (``%dequant_idct_tiles.3 = f32[...] custom-call(...)`` is
  ``dequant_idct_tiles``), never by the operands it reads;
* idle gaps: each stretch of the window with no operation on the device,
  named by what the client was doing (the harness's own annotations) and
  by the runtime's host event that overlaps it most.
"""

from __future__ import annotations

import collections
import glob
import os
import re

WINDOW = "smolbench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
CLIENT_PREFIX = "client."
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """The op an event belongs to: its instruction name, less ``%`` and a
    numeric suffix."""
    return _SUFFIX.sub("", event_name.split(" = ", 1)[0].strip().lstrip("%"))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{"device": {plane: [(name, start_ns, end_ns)]},
    "host": [(thread, name, start_ns, end_ns)]} -- plain lists, so that the
    reduction can be checked against a small recorded trace."""
    from jax.profiler import ProfileData

    out: dict = {"device": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["device"][plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    (line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.duration_ns > 0
                )
    return out


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def window(events: dict) -> tuple[float, float]:
    spans = [(s, e) for _t, name, s, e in events["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return spans[0]


def _most_overlap(gap: tuple[float, float], events) -> str:
    best, best_overlap = "none", 0.0
    for name, s, e in events:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def _labels(gaps: list, host: list) -> list[str]:
    lo, hi = min(g[0] for g in gaps), max(g[1] for g in gaps)
    near = [(n, s, e) for _t, n, s, e in host if e > lo and s < hi and n != WINDOW]
    client = [ev for ev in near if ev[0].startswith(CLIENT_PREFIX)]
    runtime = [ev for ev in near if not ev[0].startswith(CLIENT_PREFIX)]
    return [f"{_most_overlap(g, client)} | {_most_overlap(g, runtime)}" for g in gaps]


def reduce(events: dict, devices: int, kernels: dict, top: int = 10) -> dict:
    """Window, busy time and gaps of the first ``devices`` device planes;
    op totals; kernel times, where ``kernels`` maps a kernel name to the op
    names that are its calls."""
    lo, hi = window(events)
    planes = sorted(events["device"])[:devices]
    if len(planes) < devices:
        raise ValueError(f"trace has {len(planes)} device op lines, cell uses {devices}")
    busy = 0.0
    ops: collections.Counter = collections.Counter()
    kernel_ns: collections.Counter = collections.Counter()
    gaps: list[tuple[float, float]] = []
    for plane in planes:
        evs = events["device"][plane]
        merged = _union([(s, e) for _n, s, e in evs], lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            op = op_name(name)
            ops[op] += d
            for kernel, names in kernels.items():
                if op in names:
                    kernel_ns[kernel] += d
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    labels = _labels(longest, events["host"]) if longest else []
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / devices / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[n, v / 1e9] for n, v in ops.most_common(top)],
        "idle_gaps": [[label, (g[1] - g[0]) / 1e9] for label, g in zip(labels, longest)],
    }

"""One run of one benchmark cell on the served path.

Everything a cell is comes from files found by name: its entry in
``BENCHMARK.json``, its configuration ``configs/<config>.json``, the model
family that configuration names ``families/<family>.py`` (the program's
network, its plain reference and its toy size), its traffic mix
``traffic/<traffic>.json``, its deployment and limits
``workloads/<cell>.json``, and the reader of each per-layer metric,
``metrics/<quantity>.py`` (the metric's name up to its first dot).

A run makes the corpus from ``--seed`` and the weights (on the device) from
the configuration's fixed seed, builds ``SmolRuntime`` with every bucket
program compiled and warmed, drives ``start_serving()`` /
``submit(ClassificationQuery)`` / ``drain()`` with the traffic mix, warms
the host path with the same traffic, and then measures ``--seconds``.
Everything before the window is ``setup_s``.  After the window it reads the
device's peak memory, frees the runtime and holds every answer to the plain
reference.  Off a TPU the same path runs at a tiny size, prints no result
and exits non-zero.

The model the runtime serves returns, beside the logits, a fixed strided
sample of its own input (``pixel_sample``): the network input as the timed
path's decode and preprocessing made it, so that the check reaches those
layers and not the logits alone.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "smolbench"
CACHE_DIR = ROOT / ".jax_cache"

# off a TPU: the same path with a toy corpus and window (and the family's toy network)
TINY_RUN = {"items": 8, "batch_size": 4, "num_workers": 2, "seconds": 1.0,
            "backlog": 8, "warm_items": 8, "rate_per_s": 8.0, "warm_seconds": 0.5}
# every 7th row and column of the network input: coprime to the 8x8 block,
# so the sample meets every position within a block
PIXEL_STRIDE = 7


def pixel_sample(x):
    """(N, C, H, W) network input (numpy or jax) -> (N, C * ceil(H/7) * ceil(W/7))."""
    return x[:, :, ::PIXEL_STRIDE, ::PIXEL_STRIDE].reshape(x.shape[0], -1)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list
    per_layer: list


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    return Cell(
        name=name,
        chips=entry["chips"],
        config=load_json(HERE / "configs" / f"{entry['config']}.json"),
        traffic=load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
        workload=load_json(HERE / "workloads" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _for_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _for_cell(m, name)],
    )


def _module(kind: str, name: str):
    """The module ``<kind>/<name>.py``, loaded by path once per process."""
    key = f"smolbench_{kind}_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, HERE / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def family(name: str):
    """The module ``families/<name>.py``: everything the harness knows of one
    model family."""
    return _module("families", name)


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<quantity>.py``, the quantity
    being the metric's name up to its first dot: ``dispatch_ms.scan`` and
    ``dispatch_ms.open`` split one quantity by the end-to-end metric it moves,
    and share its reader."""
    return _module("metrics", metric.split(".", 1)[0]).read


def emit(*parts, **kv) -> None:
    print(*parts, json.dumps(kv, default=str) if kv else "", file=sys.stderr, flush=True)


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
                 forward=None):
        """``forward(params, cfg, x)`` is the network the runtime serves: the
        family's program network unless a control is put in its place."""
        import jax

        self.jax = jax
        self.cell = cell
        self.seed = seed
        self.trace = trace
        self.t_start = t_start
        self.forward = forward
        self.family = family(cell.config["family"])
        devs = jax.devices()
        self.device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
        self.on_tpu = self.device["platform"] == "tpu"
        self.cfg = dict(cell.config) if self.on_tpu else {**cell.config, **self.family.TINY}
        self.rt_cfg = dict(cell.workload["runtime"])
        self.traffic = json.loads(json.dumps(cell.traffic))
        self.seconds = seconds
        if not self.on_tpu:
            self.seconds = min(seconds, TINY_RUN["seconds"])
            self.traffic["corpus"]["items"] = TINY_RUN["items"]
            self.rt_cfg.update(batch_size=TINY_RUN["batch_size"], num_workers=TINY_RUN["num_workers"])
            arr = self.traffic["arrivals"]
            if arr["kind"] == "closed":
                arr["backlog"] = TINY_RUN["backlog"]
                self.traffic["warm"] = {"items": TINY_RUN["warm_items"]}
            else:
                arr["rate_per_s"] = TINY_RUN["rate_per_s"]
                self.traffic["warm"] = {"seconds": TINY_RUN["warm_seconds"]}
        self.cache_events: dict[str, int] = {}
        jax.monitoring.register_event_listener(self._on_event)
        self.gc_pauses: list[tuple[float, float]] = []  # (end, seconds) of each collection
        gc.callbacks.append(self._on_gc)

    def _on_event(self, name: str, **_kw) -> None:
        if "compilation_cache" in name:
            key = name.rsplit("/", 1)[-1]
            self.cache_events[key] = self.cache_events.get(key, 0) + 1

    def _on_gc(self, phase: str, _info: dict) -> None:
        t = time.perf_counter()
        if phase == "start":
            self._gc_t = t
        elif hasattr(self, "_gc_t"):
            self.gc_pauses.append((t, t - self._gc_t))

    # ----------------------------------------------------------- set-up
    def build(self) -> None:
        from smolbench import corpus

        t = time.perf_counter()
        self.items = corpus.build(self.traffic["corpus"], self.seed)
        self.formats = corpus.formats(self.traffic["corpus"])
        self.served = self.formats[self.traffic["serve_rendition"]]
        self.setup_parts = {"start_s": t - self.t_start, "corpus_s": time.perf_counter() - t}
        t = time.perf_counter()
        self.params = self.jax.block_until_ready(self.family.reference.init_params(self.cfg))
        self.setup_parts["weights_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.rt = self._runtime()
        self.compiled = self.rt.compile()
        self.rt.wait_warm(timeout=1200.0)
        self.setup_parts["compile_s"] = time.perf_counter() - t
        if self.compiled.plan.fmt != self.served:
            raise RuntimeError(f"planner chose {self.compiled.plan.key}, the cell serves {self.served.key}")
        if (self.rt_cfg["split_decode"] != "off") != (self.compiled.coeff is not None):
            raise RuntimeError("the plan's split decode differs from the cell's")
        self.rt.start_serving()

    def _runtime(self):
        from repro.runtime import DeviceCompilerConfig, MemoryConfig, RuntimeConfig, SmolRuntime

        c, r = self.cfg, self.rt_cfg
        accuracy = {self.formats[k].key: v for k, v in c["assumed"]["accuracy"].items() if k in self.formats}
        spec, model_fn = self.family.program(c, self.params, accuracy, self.forward)
        config = RuntimeConfig(
            batch_size=r["batch_size"],
            num_workers=r["num_workers"],
            max_wait_ms=r["max_wait_ms"],
            min_accuracy=r["min_accuracy"],
            warmup="full",
            program_cache_entries=64,
            # off a TPU the Pallas kernels run interpreted, so the same programs run
            device=DeviceCompilerConfig(
                fused_impl="auto" if self.on_tpu else "pallas", split_decode=r["split_decode"]
            ),
            memory=MemoryConfig(rendition_cache_bytes=r["rendition_cache_bytes"]),
        )
        return SmolRuntime(
            [spec], [self.formats[k] for k in r["formats"]], {c["name"]: model_fn},
            calibration=self.items[:4], config=config,
        )

    # ----------------------------------------------------------- window
    def snapshot(self) -> dict:
        dispatches = {}
        for ps in self.compiled.program_sets:
            for b, prog in ps.programs.items():
                dispatches[b] = dispatches.get(b, 0) + prog.dispatch_count
        return {"t": time.perf_counter(), "stats": self.rt.stats(), "dispatches": dispatches,
                "compiles": self.cache_events.get("compile_requests_use_cache", 0)}

    def on_window(self, opening: bool) -> None:
        jax = self.jax
        if opening:
            if self.trace:
                self.trace_dir = tempfile.mkdtemp(prefix="smolbench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
                self.window_mark = jax.profiler.TraceAnnotation("smolbench.window")
                self.window_mark.__enter__()
            self.snap0 = self.snapshot()
        else:
            self.snap1 = self.snapshot()
            if self.trace:
                self.window_mark.__exit__(None, None, None)
                jax.profiler.stop_trace()

    def serve(self) -> None:
        from repro.runtime import ClassificationQuery

        from smolbench import traffic

        self.client = traffic.Client(self.rt, self.items, ClassificationQuery, self.jax.profiler.TraceAnnotation)
        self.t0, self.t1 = traffic.run(
            self.client, self.traffic["arrivals"], len(self.items), self.seed,
            self.traffic["warm"], self.seconds, self.on_window,
        )
        self.setup_s = self.t0 - self.t_start
        self.setup_parts["warm_traffic_s"] = self.setup_s - sum(self.setup_parts.values())

    def finish_serving(self) -> None:
        """Read the peak, stop serving and free the runtime's programs and
        buffers, so that the reference runs on a device holding only the
        weights."""
        stats = [d.memory_stats() or {} for d in self.jax.local_devices()]
        self.device["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0) for s in stats)
        self.compile_seconds_total = self.rt.program_compile_seconds_total
        self.rt.stop_serving()
        gc.callbacks.remove(self._on_gc)
        del self.rt, self.compiled
        gc.collect()
        self.jax.clear_caches()

    # ------------------------------------------------------------ check
    def check(self) -> dict:
        limits, self.correct, self.failed = compare(
            self.client.records.values(), self.items, self.served, self.params, self.cfg,
            self.cell.workload["check"],
        )
        return limits

    # ---------------------------------------------------------- metrics
    def end_to_end(self) -> dict:
        recs = list(self.client.records.values())
        t0, t1 = self.t0, self.t1
        kind = self.traffic["arrivals"]["kind"]
        in_window = [r for r in recs if t0 <= r[1] < t1]
        self.attempted = len(in_window)
        done = sum(1 for r in recs if r[3] is not None and t0 <= r[3] <= t1 and r[4] is None)
        lat = [(r[3] - r[1]) if r[3] is not None and r[4] is None else float("inf") for r in in_window]
        values = {"setup_s": self.setup_s, "items_per_s": done / (t1 - t0)}
        if lat:
            self.latency_ms = {f"p{q}": float(np.percentile(lat, q)) * 1e3 for q in (50, 90, 95, 99)}
            values["p50_latency_ms"] = self.latency_ms["p50"]
            values["p95_latency_ms"] = self.latency_ms["p95"]
        if kind == "poisson":
            late = [r[2] - r[1] for r in in_window]
            self.generator_late_ms_p99 = float(np.percentile(late, 99)) * 1e3 if late else 0.0
            self.generator_late_ms_max = max(late, default=0.0) * 1e3
        return values

    def per_layer(self) -> tuple[dict, dict | None]:
        from smolbench import trace as trace_mod
        from smolbench.kernels import idct, resample
        from smolbench.readers import peaks_for
        from smolbench.reference import preproc, sjpg

        reduced = None
        if self.trace:
            try:
                events = trace_mod.load(trace_mod.find_xplane(self.trace_dir))
                if self.on_tpu:  # off a TPU the trace has no device plane
                    reduced = trace_mod.reduce(events, self.cell.chips,
                                               {"idct": idct.MARKS, "resample": resample.MARKS})
            finally:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
        geom = sjpg.geometry(self.items[0].variants[self.served])
        geom["crop"] = preproc.crop_side(geom["height"], geom["width"], self.cfg["input_size"],
                                         self.cfg["resize_short"])
        geom["size"] = self.cfg["input_size"]
        peaks = peaks_for(self.device["kind"]) if self.on_tpu else None
        ctx = {
            "s0": self.snap0, "s1": self.snap1,
            "window_s": self.snap1["t"] - self.snap0["t"],
            "completed": sum(1 for r in self.client.records.values()
                             if r[3] is not None and self.snap0["t"] <= r[3] <= self.snap1["t"]),
            "latency_ms": getattr(self, "latency_ms", None),
            "trace": reduced, "config": self.cfg, "family": self.family, "geometry": geom,
            "peaks": peaks, "batch_size": self.rt_cfg["batch_size"],
        }
        values = {}
        for m in self.cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = v
        return values, reduced


def reference_inputs(items, idx, served, cfg: dict, tie: float) -> tuple:
    """The plain reference's network input for each corpus index in ``idx``,
    and for each value of its ``pixel_sample`` the lowest and highest uint8
    level it may stand for: a value within ``tie`` levels of a half level, at
    the decode's rounding or at the resize's, may round either way, since
    float32 arithmetic rounds a near tie of the float64 reference either way."""
    from smolbench.reference import preproc, sjpg

    size, short = cfg["input_size"], cfg["resize_short"]

    def sample(a):
        return pixel_sample(a.transpose(2, 0, 1)[None])[0]

    xs, los, his = [], [], []
    for i in idx:
        rgb = sjpg.decode_unrounded(items[i].variants[served])
        near = np.abs(rgb - np.floor(rgb) - 0.5) < tie
        mid = np.clip(np.round(rgb), 0, 255)
        xs.append(preproc.normalize(preproc.resized(mid, size, short)))
        lo = preproc.resized(np.where(near, np.clip(np.floor(rgb), 0, 255), mid), size, short)
        hi = preproc.resized(np.where(near, np.clip(np.floor(rgb) + 1, 0, 255), mid), size, short)
        los.append(np.clip(np.ceil(sample(lo) - 0.5 - tie), 0, 255))
        his.append(np.clip(np.floor(sample(hi) + 0.5 + tie), 0, 255))
    return np.stack(xs), np.stack(los), np.stack(his)


def pixel_off_share(answered, idx, lo, hi, num_classes: int) -> float:
    """The share of the answers' sampled network-input values whose uint8
    level lies outside the reference's ``[lo, hi]`` (``reference_inputs``)."""
    from smolbench.reference import preproc

    row = {i: k for k, i in enumerate(idx)}
    n_pix, n_off = lo.shape[1], 0
    for r in answered:
        out = np.asarray(r[5], np.float32).reshape(-1)
        if out.shape[0] != num_classes + n_pix or not np.isfinite(out).all():
            n_off += n_pix
            continue
        k = row[r[0]]
        got = preproc.levels(out[num_classes:].reshape(3, -1), 0).reshape(-1)
        n_off += int(np.count_nonzero((got < lo[k]) | (got > hi[k])))
    return n_off / (len(answered) * n_pix)


def compare(records, items, served, params, cfg: dict, check: dict) -> tuple[dict, bool, int]:
    """Every answer received against the plain reference, by two numbers:

    * ``logit_gap``: the widest gap between a served logit and the
      reference's, as a share of the reference's largest logit for that item;
    * ``pixel_off_share``: the share of the answers' sampled network-input
      values (``pixel_sample``) that stand for a uint8 level outside the
      reference's (``reference_inputs``, ties within the cell's
      ``pixel_tie_levels``), which sees the decode and preprocessing layers.

    The reference logits are those of the family that ``cfg`` names.
    Returns the numbers compared with their limits, whether all hold, and
    the failed request count."""
    recs = list(records)
    missing = sum(1 for r in recs if r[3] is None)
    failed = sum(1 for r in recs if r[3] is not None and r[4] is not None)
    answered = [r for r in recs if r[5] is not None and r[4] is None]
    idx = sorted({r[0] for r in answered})
    gap = off = float("inf")
    if idx:
        x, lo, hi = reference_inputs(items, idx, served, cfg, check["pixel_tie_levels"])
        ref = dict(zip(idx, family(cfg["family"]).reference.logits(params, cfg, x)))
        nc = cfg["num_classes"]
        gaps = []
        for r in answered:
            out = np.asarray(r[5], np.float32).reshape(-1)
            ok = out.shape[0] == nc + lo.shape[1] and np.isfinite(out).all()
            gaps.append(float(np.abs(out[:nc] - ref[r[0]]).max() / np.abs(ref[r[0]]).max())
                        if ok else float("inf"))
        gap, off = max(gaps), pixel_off_share(answered, idx, lo, hi, nc)
    limits = {
        "answers_compared": {"value": len(answered), "limit": 1},
        "missing": {"value": missing, "limit": 0},
        "failed": {"value": failed, "limit": 0},
        "logit_gap": {"value": gap, "limit": check["max_logit_gap"]},
        "pixel_off_share": {"value": off, "limit": check["max_pixel_off_share"]},
    }
    correct = (bool(answered) and missing == 0 and failed == 0
               and gap <= check["max_logit_gap"] and off <= check["max_pixel_off_share"])
    return limits, correct, missing + failed


def configure_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # no eviction: a size limit from the environment would make JAX keep
    # access-time files beside the entries, and share a budget it does not own
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def execute(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            forward=None) -> tuple[Run, dict]:
    """One whole run: set-up, window, per-layer reading, check.  Returns the
    run and its result line (which a dry run off a TPU does not print).
    ``forward`` puts a control in the served network's place."""
    run = Run(cell, seed, seconds, trace, t_start, forward)
    run.build()
    run.serve()
    e2e = run.end_to_end()
    per_layer, reduced = run.per_layer() if run.trace else ({}, None)
    run.finish_serving()
    t = time.perf_counter()
    limits = run.check()
    check_s = time.perf_counter() - t
    pauses = [s for end, s in run.gc_pauses if run.t0 <= end <= run.t1]
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    wanted = cell.per_layer if run.trace else cell.end_to_end
    values = per_layer if run.trace else e2e
    metrics = {m["name"]: {"value": values[m["name"]], "unit": units[m["name"]]}
               for m in wanted if m["name"] in values}
    device = dict(run.device)
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result = {
        "correct": run.correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    result["info"] = {
        "setup_parts": run.setup_parts,
        "program_compile_seconds_total": run.compile_seconds_total,
        "compile_cache_events": run.cache_events,
        "window_s": run.t1 - run.t0,
        "compiles_in_window": run.snap1["compiles"] - run.snap0["compiles"],
        "check_s": check_s,
        "generator_late_ms_p99": getattr(run, "generator_late_ms_p99", None),
        "generator_late_ms_max": getattr(run, "generator_late_ms_max", None),
        "gc_in_window": {"count": len(pauses), "seconds": sum(pauses),
                         "longest_ms": max(pauses, default=0.0) * 1e3},
        "latency_ms": getattr(run, "latency_ms", None),
        "admission_blocked_s": (run.snap1["stats"].scheduler.stats.admission_blocked_seconds
                                - run.snap0["stats"].scheduler.stats.admission_blocked_seconds),
        "all_end_to_end": e2e,
    }
    result["limits"] = limits
    return run, result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="One run of one smolbench cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    jax = configure_jax()
    devices = jax.devices()
    if devices[0].platform == "tpu" and len(devices) < cell.chips:
        emit(f"{cell.name} needs {cell.chips} chips, JAX finds {len(devices)}")
        return 2
    run, result = execute(cell, args.seed, args.seconds, bool(args.trace), t_start)
    for name, lim in result["limits"].items():
        emit(f"check {name} {lim['value']} limit {lim['limit']}")
    if not run.on_tpu:
        # no timing from this run is printed: it measures no device
        emit("dry run off a TPU: no result", device=result["device"],
             attempted=result["attempted"], failed=result["failed"])
        return 1
    print(json.dumps(result), flush=True)
    return 0

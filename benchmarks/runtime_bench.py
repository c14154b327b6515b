"""End-to-end SmolRuntime benchmark — JSON for the perf trajectory.

Two workloads, each probing the subsystem built for it:

* **worker sweep** (host-decode-bound: large pjpeg images, tiny model) —
  worker counts x {pooled, unpooled} staging.  Each leg measures
  ``preproc_only`` (the producer pool in isolation, §8.2 protocol) and
  ``pipelined`` throughput.  Gates (full mode only): multi-worker pooled
  host-stage throughput >= 1.3x the single-worker unpooled baseline on
  2+ cores, and pooled pipelined >= unpooled at equal worker count.
* **pipeline overlap** (balanced stages: the regime where overlap pays) —
  the paper's §8.2 modes: ``preproc_only``, ``exec_only``, ``pipelined``,
  and the serial sum 1/(1/T_pre + 1/T_exec) a non-pipelined system would
  get.  Gate: pipelined >= 1.2x the serial sum.
* **device path** (the device preprocessing compiler) — the fused
  device program (placement suffix lowered + DNN, one dispatch) vs. the
  per-op reference chain on identical batches, interleaved best-of-N.
  Gate: fused >= 1.0x per-op on CPU/interpret (with a noise tolerance —
  XLA already fuses elementwise on CPU, so parity is the honest floor);
  on a real accelerator the >= 1.2x speedup gate binds instead.
* **split decode** (the coefficient-domain device programs, §6.4) —
  4:4:4 full-res vs 4:2:0 full-res vs the scaled-IDCT factor the cost
  model picks, identical coefficient batches, interleaved best-of-N.
  Gates: every variant matches the host reference decode + chain within
  one uint8 quant step, and the scaled program is never slower than the
  full-resolution 4:2:0 program (CPU parity floor / >= 1.2x accelerator
  gate — the scaled IDCT is strictly less math and factor^2 fewer pixels
  downstream).
* **cascade serving** (the typed Query API + progressive rendition
  refetch) — a 2-stage probe/heavy cascade serves every item from the
  cheap plan target (the probe model on the pre-scaled thumbnail
  rendition; see the leg docstring for why the coefficient path doesn't
  bind on a 48px stored rendition) and internally refetches the
  uncertain 25% to the heavy model at full resolution; its throughput
  must beat serving the identical corpus through the heavy model
  all-full-resolution by >= 1.3x at matched predictions, and a
  sleep-controlled 4:1 tenant window where EVERY item refetches must
  hold the weighted-fairness ratio within +/- 25%.
* **multi-tenant fairness** (the weighted-fair scheduler) — two tenants
  with 4:1 weights saturate a device-bound scheduler; the observed
  per-tenant throughput ratio must land at 4:1 +/- 25%, and the
  two-tenant aggregate must stay within 10% of a single-tenant baseline
  on the same stages (fairness must not cost throughput).  Stage times
  are sleep-controlled, so this leg measures the scheduler's policy, not
  box noise.
* **latency SLO + telemetry** (the tracing/histogram subsystem) — a
  paced latency tenant rides alongside a saturating throughput tenant;
  the latency tenant's streaming-histogram p99 must stay under a bound
  derived from its batch deadline.  The same leg prices telemetry:
  histograms-on throughput must be >= 97% of telemetry-off (full mode),
  and telemetry-off runs must allocate zero span rings.  ``--trace-out``
  additionally captures spans and writes the Perfetto trace JSON.
* **cold start** (AOT program sets, ``RuntimeConfig.warmup``) — two fresh
  runtimes (fresh model closures, fresh jit caches) serve identical
  batches; with ``warmup=full`` the first batch must land <= 1.5x the
  steady-state p50 (the program set absorbed every compile at startup),
  while ``warmup=off`` must show the problem exists (first batch >= 5x
  p50) and ``warmup=full`` must leave zero post-startup compiles.
* **dispatch overlap** (double-buffered staging) — the engine's
  double-buffered consumer vs synchronous staging on a deterministic
  fake device (sleep-controlled H2D leg + serial compute stream):
  double-buffered throughput must reach >= 1.15x synchronous on 2+
  cores, with telemetry spans showing batch N+1's staging overlapping
  batch N's in-flight dispatch.

Writes ``BENCH_runtime.json`` at the repo root (override with ``--out``).
``--check BASELINE.json`` turns the run into a **regression gate**: any
gate that passes in the committed baseline but fails in this run exits
non-zero (the CI job runs ``--smoke --check BENCH_runtime.json`` on every
PR, so perf gates *bind* instead of only uploading an artifact; smoke
mode relaxes the noisy thresholds to keep 2-core CI runners honest).

    PYTHONPATH=src python benchmarks/runtime_bench.py [--smoke] [--check BENCH_runtime.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

# Mirror the paper's resource split on CPU-only hosts: producer threads own
# the host cores, the "accelerator" stream runs single-threaded.  Without
# this, XLA's intra-op pool fights the producers for the same cores and the
# pipelined/serial comparison measures scheduler noise, not overlap.
# (Must be set before jax initializes its backend.)
if "--xla_cpu_multi_thread_eigen" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_cpu_multi_thread_eigen=false"
    ).strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.planner import ModelSpec
from repro.preprocessing.formats import ImageFormat, StoredImage
from repro.runtime import MemoryConfig, RuntimeConfig, SmolRuntime

REPO_ROOT = Path(__file__).resolve().parent.parent
# tolerance on the pooled>=unpooled gate: even best-of-N throughputs on
# small shared-CPU hosts jitter several percent, so the gate compares the
# aggregate across the whole worker sweep rather than single legs
POOLED_GATE_TOL = 0.95
# CPU floor for the fused-vs-per-op device leg: the fused program's CPU
# lowering shares the reference resample arithmetic, so its expectation is
# ~1.0x with single-digit-percent scheduler jitter around it
DEVICE_GATE_TOL = 0.90
DEVICE_ACCEL_SPEEDUP = 1.2  # the real gate when a TPU/GPU backend is present


def make_corpus(n: int, size: int, formats, seed: int = 0) -> list[StoredImage]:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        base = rng.normal(size=(size // 8, size // 8, 3))
        img = np.kron(base, np.ones((8, 8, 1))) * 40 + 128
        img += rng.normal(scale=6.0, size=img.shape)  # texture: honest decode cost
        out.append(StoredImage.from_array(np.clip(img, 0, 255).astype(np.uint8), formats))
    return out


def make_model(input_size: int, width: int = 48, seed: int = 0):
    """A conv stack big enough that the device leg does real work."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w0 = jax.random.normal(ks[0], (3, 3, 3, width), jnp.float32) * 0.15
    w1 = jax.random.normal(ks[1], (3, 3, width, width), jnp.float32) * (2.0 / (9 * width)) ** 0.5
    w2 = jax.random.normal(ks[2], (3, 3, width, width), jnp.float32) * (2.0 / (9 * width)) ** 0.5
    head = jax.random.normal(ks[3], (width, 10), jnp.float32) * width**-0.5

    def fn(x):  # (B, 3, H, W) float32
        def conv(y, w, stride):
            return jax.lax.conv_general_dilated(
                y, w, (stride, stride), "SAME", dimension_numbers=("NCHW", "HWIO", "NCHW")
            )

        y = jax.nn.relu(conv(x, w0, 2))
        y = jax.nn.relu(conv(y, w1, 1))
        y = jax.nn.relu(conv(y, w2, 2))
        return y.mean(axis=(2, 3)) @ head

    return fn


def _make_runtime(args, corpus, model_fn, exec_tput, fmt, workers: int, pooled: bool):
    models = [
        ModelSpec(
            "bench-cnn",
            args.input_size,
            exec_throughput=exec_tput,
            accuracy_by_format={fmt.key: 1.0},
        )
    ]
    return SmolRuntime(
        models,
        [fmt],
        {"bench-cnn": model_fn},
        calibration=corpus[:8],
        config=RuntimeConfig(
            batch_size=args.batch_size,
            num_workers=workers,
            recal_workers=False,  # hold the sweep variable fixed
            memory=MemoryConfig(pooling=pooled),
        ),
    )


def _run_sweep(args, corpus, model_fn, exec_tput, fmt, reps: int):
    """Best-of-``reps`` pipelined throughput per (workers, pooled) leg.

    All engines are built and warmed first and the repetitions interleave
    round-robin across legs, so box-level noise (shared-CPU neighbours,
    frequency shifts) lands on every leg instead of biasing whichever one
    ran during a slow phase.
    """
    legs = {}
    for workers in args.worker_sweep:
        for pooled in (False, True):
            runtime = _make_runtime(args, corpus, model_fn, exec_tput, fmt, workers, pooled)
            engine = runtime.engine()
            engine.run(corpus[: 2 * args.batch_size], return_outputs=False)  # warm/compile
            legs[(workers, pooled)] = {
                "runtime": runtime,
                "engine": engine,
                "best": None,
                "best_pre": None,
            }
    for _ in range(reps):
        for leg in legs.values():
            pre = leg["engine"].run_preproc_only(corpus)
            _, stats = leg["engine"].run(corpus, return_outputs=False)
            if leg["best"] is None or stats.throughput > leg["best"].throughput:
                leg["best"] = stats
            if leg["best_pre"] is None or pre.throughput > leg["best_pre"].throughput:
                leg["best_pre"] = pre
    sweep = []
    for (workers, pooled), leg in legs.items():
        piped = leg["best"]
        row = {
            "workers": workers,
            "pooled": pooled,
            "preproc_tput": round(leg["best_pre"].throughput, 2),
            "pipelined_tput": round(piped.throughput, 2),
            "host_busy_seconds": round(piped.host_busy_seconds, 4),
            "device_busy_seconds": round(piped.device_busy_seconds, 4),
        }
        if piped.pool_stats is not None:
            row["pool"] = dataclasses.asdict(piped.pool_stats)
        sweep.append(row)
    return sweep, legs


def _run_device_leg(args, reps: int) -> dict:
    """Fused device program vs. per-op reference chain, same DNN, same
    batches.  Timing interleaves fused/reference once per repetition and
    keeps the best (lowest) per-batch seconds of each, so box-level noise
    hits both legs symmetrically."""
    import time

    import jax

    from repro.core import dag as dag_mod
    from repro.core import device_compiler as DC
    from repro.core.planner import standard_chain
    from repro.preprocessing.ops import TensorMeta

    meta = TensorMeta((256, 256, 3), "uint8", "HWC")
    plan = dag_mod.optimize(standard_chain(args.input_size), meta)
    model = make_model(args.input_size, width=args.model_width)
    fused = DC.compile_device_program(
        plan.ops, meta, model, args.batch_size, backend="fused"
    )
    ref = DC.compile_device_program(
        plan.ops, meta, model, args.batch_size, backend="reference"
    )
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(args.batch_size, *meta.shape)).astype(np.uint8)
    jax.block_until_ready(fused.fn(x))  # compile both outside the clock
    jax.block_until_ready(ref.fn(x))

    def per_batch_seconds(fn, iters=12):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    best_fused = best_ref = float("inf")
    for _ in range(reps + 2):  # interleave legs so noise lands on both
        best_fused = min(best_fused, per_batch_seconds(fused.fn))
        best_ref = min(best_ref, per_batch_seconds(ref.fn))
    speedup = best_ref / best_fused if best_fused > 0 else float("inf")
    return {
        "impl": fused.impl,
        "stages": list(fused.stages),
        "fused_batch_ms": round(best_fused * 1e3, 3),
        "reference_batch_ms": round(best_ref * 1e3, 3),
        "fused_speedup": round(speedup, 3),
    }


def _run_split_decode_leg(args, reps: int) -> dict:
    """Split-decode device programs: 4:4:4 vs 4:2:0 vs scaled factor.

    Stages coefficients once on the host, then times the compiled coeff
    programs (dequant+(scaled-)IDCT -> chroma upsample -> color -> fused
    preproc -> DNN, one dispatch) on identical batches, interleaved
    best-of-N like the device leg.  Gates: (a) correctness — every
    variant's fused output matches the host reference decode + chain
    within one uint8 quant step; (b) performance — the scaled-factor
    program beats the full-resolution 4:2:0 program (CPU parity floor /
    >= 1.2x on accelerators): the scaled IDCT does strictly less math and
    every downstream stage touches factor^2 fewer pixels.
    """
    import time

    import jax

    from repro.core import dag as dag_mod
    from repro.core import device_compiler as DC
    from repro.core.cost_model import CoeffGeometry
    from repro.core.placement import choose_coeff_option
    from repro.core.planner import standard_chain
    from repro.preprocessing import jpeg
    from repro.preprocessing import ops as P
    from repro.preprocessing.ops import TensorMeta

    # native frame sized so half-resolution decode always covers the plan's
    # resize-short target (size/2 >= round(input*256/224)) — factor 2 stays
    # valid for any --input-size, keeping the scaled-variant invariant below
    resize_short = round(args.input_size * 256 / 224)
    size = max(512, -(-2 * resize_short // 8) * 8)
    rng = np.random.default_rng(7)
    base = rng.normal(size=(size // 8, size // 8, 3))
    img = np.kron(base, np.ones((8, 8, 1))) * 40 + 128
    img += rng.normal(scale=6.0, size=img.shape)
    img = np.clip(img, 0, 255).astype(np.uint8)
    meta = TensorMeta((size, size, 3), "uint8", "HWC")
    plan = dag_mod.optimize(standard_chain(args.input_size), meta)
    model = make_model(args.input_size, width=args.model_width)
    batch = max(4, args.batch_size // 2)  # coefficient batches are heavy

    qstep = (1.0 / 255.0) / 0.224  # one uint8 step through the steepest std
    variants = {}
    for name, subsample, scaled in (
        ("444_full", False, False),
        ("420_full", True, False),
        ("420_scaled", True, True),
    ):
        data = jpeg.encode(img, quality=args.quality, subsample=subsample)
        hdr = jpeg.peek_header(data)
        geom = CoeffGeometry.from_header(hdr)
        opt = choose_coeff_option(
            plan.ops, geom,
            host_entropy_time=1e-3, dnn_device_time=1e-3, device_ops_per_sec=1e11,
            policy="scaled" if scaled else "full",
        )
        assert (opt.factor > 1) == scaled, (name, opt.factor)
        prog = DC.compile_coeff_program(
            hdr, plan.ops, model, batch, factor=opt.factor, layout=opt.layout
        )
        _, planes, _, _ = jpeg.decode_to_coefficients(data)
        staged = np.stack([jpeg.stage_coefficients(planes, hdr, opt.layout)] * batch)
        jax.block_until_ready(prog.fn(staged))  # compile outside the clock
        # correctness gate: fused output vs host golden (reference decode +
        # host chain) within one quant step on every pixel
        golden = P.apply_chain_host(
            list(plan.ops),
            jpeg.decode(data) if opt.factor == 1 else jpeg.decode_scaled(data, opt.factor),
        )
        head = DC.compile_coeff_program(
            hdr, plan.ops, lambda x: x, 1, factor=opt.factor, layout=opt.layout
        )
        err = float(np.abs(np.asarray(head(staged[:1]))[0] - golden).max())
        variants[name] = {
            "prog": prog,
            "staged": staged,
            "factor": opt.factor,
            "layout": opt.layout,
            "staging_bytes": opt.staging_bytes,
            "max_err": err,
            "parity_ok": err <= qstep + 1e-4,
            "best_s": float("inf"),
        }

    def per_batch_seconds(fn, x, iters=8):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    for _ in range(reps + 2):  # interleave so box noise lands on every leg
        for v in variants.values():
            v["best_s"] = min(v["best_s"], per_batch_seconds(v["prog"].fn, v["staged"]))

    out = {"image_size": size, "batch": batch}
    for name, v in variants.items():
        out[name] = {
            "factor": v["factor"],
            "layout": v["layout"],
            "staging_bytes": v["staging_bytes"],
            "batch_ms": round(v["best_s"] * 1e3, 3),
            "max_err_vs_reference": round(v["max_err"], 5),
            "parity_ok": v["parity_ok"],
        }
    out["scaled_speedup_vs_full"] = round(
        variants["420_full"]["best_s"] / variants["420_scaled"]["best_s"], 3
    )
    out["parity_all"] = all(v["parity_ok"] for v in variants.values())
    return out


def _run_cascade_leg(args) -> dict:
    """2-stage cascade with progressive rendition refetch vs all-full-res.

    Stage 0 serves every item from the *cheap plan target* — the probe
    model's best plan, which lands on the pre-scaled thumbnail rendition
    (on CPU no reduced scaled-IDCT factor fits a 48px stored rendition,
    so the cheap stage is its pixel path; the coefficient-domain cheap
    stage is unit-tested in test_query_api).  Items whose max-softmax
    confidence clears the stage threshold exit with the probe's
    prediction; the uncertain rest are internally resubmitted to stage
    1's full-resolution target running the expensive model.  The baseline
    serves the identical corpus as ``ClassificationQuery`` items on a
    runtime that only has the expensive model at full resolution.  Both
    legs ride the typed Query API through the serving scheduler, and the
    heavy model shares the probe's brightness-driven decision function
    (plus a sub-resolution conv term that can't be folded away), so the
    gates are: cascade throughput >= 1.3x all-full-res (full mode) at
    *matched predictions*.  A second sleep-controlled window checks that
    internal refetches keep billing the submitting tenant's virtual time:
    two tenants at 4:1 weights where EVERY item refetches must still
    complete within 4:1 +/- 25%.
    """
    import threading
    import time

    import jax
    import jax.numpy as jnp

    from repro.runtime import (
        CascadeQuery,
        CascadeStageSpec,
        ClassificationQuery,
        RequestRoute,
        TenantConfig,
    )
    from repro.runtime.scheduler import RequestScheduler

    input_size = 32
    fmt_full = ImageFormat("jpeg", None, 90)
    fmt_thumb = ImageFormat("jpeg", 48, 85)
    size = 240
    n = 48 if args.smoke else 128
    n_dark = n // 4  # 25% uncertain -> refetched at full resolution
    rng = np.random.default_rng(13)

    def _img(mean):
        base = rng.normal(size=(size // 8, size // 8, 3))
        x = np.kron(base, np.ones((8, 8, 1))) * 20 + mean
        x += rng.normal(scale=4.0, size=x.shape)
        return StoredImage.from_array(
            np.clip(x, 0, 255).astype(np.uint8), [fmt_full, fmt_thumb]
        )

    dark_flags = np.zeros(n, bool)
    dark_flags[:n_dark] = True
    rng.shuffle(dark_flags)
    corpus = [_img(80 if dark else 205) for dark in dark_flags]

    def probe_model(x):  # class-0 logit rides the normalized mean: bright
        m = jnp.mean(x, axis=(1, 2, 3))  # images are confident, dark ones
        z = jnp.zeros((x.shape[0], 7), jnp.float32)  # fall through
        return z.at[:, 0].set(m * 12.0)

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    w0 = jax.random.normal(ks[0], (3, 3, 3, 32), jnp.float32) * 0.1
    w1 = jax.random.normal(ks[1], (3, 3, 32, 32), jnp.float32) * 0.05
    head = jax.random.normal(ks[2], (32, 7), jnp.float32) * 0.1

    def heavy_model(x):
        # the probe's decision function plus a deliberately expensive conv
        # term scaled below the logits' float32 resolution: predictions
        # stay bitwise comparable across stages, the cost does not
        def conv(y, w):
            return jax.lax.conv_general_dilated(
                y, w, (1, 1), "SAME", dimension_numbers=("NCHW", "HWIO", "NCHW")
            )

        y = jax.nn.relu(conv(x, w0))
        y = jax.nn.relu(conv(y, w1))
        return probe_model(x) + 1e-7 * (y.mean(axis=(2, 3)) @ head)

    probe = ModelSpec(
        "probe", input_size, exec_throughput=20_000.0,
        accuracy_by_format={fmt_full.key: 0.95, fmt_thumb.key: 0.92},
    )
    heavy = ModelSpec(
        "heavy", input_size, exec_throughput=400.0,
        accuracy_by_format={fmt_full.key: 0.97, fmt_thumb.key: 0.50},
    )
    stages = (
        CascadeStageSpec(threshold=0.6, model="probe"),
        CascadeStageSpec(model="heavy"),
    )
    cascade_rt = SmolRuntime(
        [probe, heavy], [fmt_full, fmt_thumb],
        {"probe": probe_model, "heavy": heavy_model},
        calibration=corpus[:4],
        config=RuntimeConfig(
            batch_size=16, num_workers=2, max_wait_ms=1.0, min_accuracy=0.9
        ),
    )
    base_rt = SmolRuntime(
        [heavy], [fmt_full], {"heavy": heavy_model},
        calibration=corpus[:4],
        config=RuntimeConfig(batch_size=16, num_workers=2, max_wait_ms=1.0),
    )

    def timed(rt, make_query):
        t0 = time.perf_counter()
        for img in corpus:
            rt.submit(make_query(img))
        rt.flush(timeout=300.0)
        wall = time.perf_counter() - t0
        done = rt.drain()
        preds = [r.prediction for r in done]
        return n / wall, preds

    cascade_rt.start_serving()
    base_rt.start_serving()
    try:
        # warm pass: compile the baseline program AND (via one dark item
        # that fails the threshold) both cascade stage programs + the
        # refetch path outside the clock
        warm_bright = corpus[int(np.flatnonzero(~dark_flags)[0])]
        warm_dark = corpus[int(np.flatnonzero(dark_flags)[0])]
        base_rt.submit(ClassificationQuery(image=warm_bright))
        cascade_rt.submit(CascadeQuery(image=warm_bright, stages=stages))
        cascade_rt.submit(CascadeQuery(image=warm_dark, stages=stages))
        base_rt.flush(timeout=300.0)
        cascade_rt.flush(timeout=300.0)
        base_rt.drain()
        cascade_rt.drain()
        tput_cascade = tput_full = 0.0
        for _ in range(2):  # best-of-2, interleaved
            t, preds_cascade = timed(
                cascade_rt, lambda img: CascadeQuery(image=img, stages=stages)
            )
            tput_cascade = max(tput_cascade, t)
            t, preds_full = timed(base_rt, lambda img: ClassificationQuery(image=img))
            tput_full = max(tput_full, t)
        stats = cascade_rt.stats()
    finally:
        cascade_rt.stop_serving()
        base_rt.stop_serving()
    sec = stats.cascade

    # ---- refetch fairness: 4:1 weights with every item refetching ---------
    def host_fn(item):
        return np.full((4,), float(item), np.float32)

    def device_fn(batch):
        time.sleep(0.003)  # device stream is the bottleneck
        return batch

    sched = RequestScheduler(
        host_fn, device_fn, (4,), np.float32,
        max_batch=4, num_workers=2, max_wait_ms=1.0,
        tenants=[
            TenantConfig("gold", weight=4.0, max_pending=16),
            TenantConfig("bronze", weight=1.0, max_pending=16),
        ],
    )
    sched.start()
    expensive = sched.make_binding(host_fn, device_fn, (4,), np.float32)

    def on_stage1(uid, out):
        return None

    def on_stage0(uid, out):
        return float(out[0]), RequestRoute(
            binding=expensive, on_result=on_stage1, stage=1
        )

    window_s = 0.8 if args.smoke else 1.5
    stop_at = time.perf_counter() + window_s

    def feeder(name):
        i = 0
        while time.perf_counter() < stop_at:
            sched.submit(i, tenant=name, route=RequestRoute(on_result=on_stage0))
            i += 1

    try:
        threads = [
            threading.Thread(target=feeder, args=(nm,)) for nm in ("gold", "bronze")
        ]
        for t in threads:
            t.start()
        while time.perf_counter() < stop_at:
            time.sleep(0.02)
        counts = {nm: sched.tenants[nm].completed for nm in ("gold", "bronze")}
        for t in threads:
            t.join()
        sched.flush(timeout=60.0)
        refetched = sched.stats.refetched_items
    finally:
        sched.stop()

    return {
        "items": n,
        "image_size": size,
        "dark_fraction": round(n_dark / n, 3),
        "threshold": 0.6,
        "factor": sec.factor if sec is not None else 1,
        "stage0_exits": sec.stages[0].exits if sec is not None else 0,
        "stage1_items": sec.stages[1].items if sec is not None else 0,
        "refetched_items": sec.refetched_items if sec is not None else 0,
        "cascade_tput": round(tput_cascade, 2),
        "full_res_tput": round(tput_full, 2),
        "cascade_speedup": round(tput_cascade / tput_full, 3) if tput_full else 0.0,
        "predictions_match": preds_cascade == preds_full,
        "refetch_window_s": window_s,
        "refetch_gold_completed": counts["gold"],
        "refetch_bronze_completed": counts["bronze"],
        "refetch_observed_ratio": round(counts["gold"] / max(1, counts["bronze"]), 3),
        "refetch_refetched_items": refetched,
    }


def _run_fairness_leg(args) -> dict:
    """Two tenants at 4:1 weights saturating a device-bound scheduler.

    The device stage is a fixed sleep per batch and the host stage is
    trivial, so the only thing under test is the scheduler's weighted-fair
    policy: per-tenant ``max_pending`` backpressures both feeders, batch
    slots go to the backlogged tenant with the smallest virtual time, and
    the completion ratio during saturation should track the weights.  A
    single-tenant baseline on identical stages anchors the aggregate gate.
    """
    import threading
    import time

    from repro.runtime.scheduler import RequestScheduler, TenantConfig

    per_batch_s = 0.004
    max_batch = 8
    window_s = 1.2 if args.smoke else 3.0

    def host_fn(item):
        return np.full((8,), float(item), np.float32)

    def device_fn(batch):
        time.sleep(per_batch_s)  # a deterministic "accelerator"
        return batch

    def run_window(tenant_cfgs):
        names = [c.name for c in tenant_cfgs]
        sched = RequestScheduler(
            host_fn,
            device_fn,
            (8,),
            np.float32,
            max_batch=max_batch,
            num_workers=2,
            max_wait_ms=1.0,
            tenants=tenant_cfgs,
        )
        sched.start()
        stop_at = time.perf_counter() + window_s

        def feeder(name):
            i = 0
            while time.perf_counter() < stop_at:
                sched.submit(i, tenant=name)  # blocks at max_pending
                i += 1

        threads = [threading.Thread(target=feeder, args=(n,)) for n in names]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        # sample completions while both feeders are still saturating the
        # scheduler — the post-window drain tail is excluded from the ratio
        while time.perf_counter() < stop_at:
            time.sleep(0.02)
        counts = {n: sched.tenants[n].completed for n in names}
        wall = time.perf_counter() - t0
        for t in threads:
            t.join()
        sched.flush(timeout=60.0)
        sched.stop()
        return counts, wall

    counts, wall = run_window(
        [
            TenantConfig("gold", weight=4.0, max_pending=4 * max_batch),
            TenantConfig("bronze", weight=1.0, max_pending=4 * max_batch),
        ]
    )
    base_counts, base_wall = run_window(
        [TenantConfig("solo", weight=1.0, max_pending=8 * max_batch)]
    )
    ratio = counts["gold"] / max(1, counts["bronze"])
    aggregate = sum(counts.values()) / wall
    baseline = base_counts["solo"] / base_wall
    return {
        "weights": "4:1",
        "window_s": window_s,
        "gold_completed": counts["gold"],
        "bronze_completed": counts["bronze"],
        "observed_ratio": round(ratio, 3),
        "aggregate_tput": round(aggregate, 2),
        "single_tenant_tput": round(baseline, 2),
        "aggregate_frac_of_single": round(aggregate / baseline, 4) if baseline else 0.0,
    }


def _run_replica_leg(args) -> dict:
    """Replica scaling: 2 mesh dispatchers vs 1 over the shared fair queue.

    The device model is a fixed sleep per batch (a deterministic
    "accelerator" that releases the GIL), so the leg measures the
    scheduler's ability to keep N replica dispatchers concurrently busy
    from one queue — not box throughput.  Two replicas over a
    device-bound workload should approach 2x; the gate binds at 1.6x to
    absorb dispatch overhead and scheduler jitter.
    """
    import time

    from repro.runtime.scheduler import RequestScheduler

    per_batch_s = 0.004
    max_batch = 8
    items = 256 if args.smoke else 768

    def host_fn(item):
        return np.full((8,), float(item), np.float32)

    def device_fn(batch):
        time.sleep(per_batch_s)
        return batch

    def run_once(num_replicas):
        sched = RequestScheduler(
            host_fn,
            device_fn,
            (8,),
            np.float32,
            max_batch=max_batch,
            num_workers=2,
            max_wait_ms=1.0,
            num_replicas=num_replicas,
        )
        sched.start()
        try:
            t0 = time.perf_counter()
            for i in range(items):
                sched.submit(i)
            sched.flush(timeout=120.0)
            wall = time.perf_counter() - t0
            sched.drain()
        finally:
            sched.stop()
        return items / wall

    tput_1 = max(run_once(1) for _ in range(2))  # best-of-2: warm the path
    tput_2 = max(run_once(2) for _ in range(2))
    return {
        "items": items,
        "per_batch_s": per_batch_s,
        "max_batch": max_batch,
        "tput_1_replica": round(tput_1, 2),
        "tput_2_replicas": round(tput_2, 2),
        "replica_scaling": round(tput_2 / tput_1, 3) if tput_1 else 0.0,
    }


def _run_latency_leg(args) -> dict:
    """Per-tenant p99 latency under contention + telemetry overhead.

    A latency tenant submits at a modest paced rate while a throughput
    tenant saturates the sleep-controlled scheduler through max_pending
    backpressure.  The latency tenant's streaming-histogram p99 (e2e:
    submit -> batch complete) must stay under a bound derived from its
    batch deadline: ``max_wait_ms`` of batch-formation wait, plus a few
    device batch times of queueing behind the saturating tenant, plus
    fixed slack for host/dispatch overheads.  Stage times are
    sleep-controlled, so the leg measures the scheduler's deadline + WFQ
    policy and the histogram pipeline, not box throughput.

    The same leg prices telemetry itself: a fixed-item throughput run
    with histograms on vs. everything off, interleaved best-of-2.  The
    histogram path must cost <= 3% throughput (full mode), and the
    telemetry-off runs must allocate **zero** span rings — the
    always-on default has to be unmeasurable before it ships enabled.

    With ``--trace-out`` the latency window also captures spans and
    writes the Perfetto/Chrome trace JSON there (the CI artifact).
    """
    import threading
    import time

    from repro.runtime import Telemetry, TelemetryConfig
    from repro.runtime.scheduler import RequestScheduler, TenantConfig

    per_batch_s = 0.004
    max_batch = 8
    window_s = 1.2 if args.smoke else 3.0
    lat_deadline_ms = 5.0
    # deadline wait + queueing behind in-flight saturating batches + slack
    p99_bound_s = lat_deadline_ms / 1e3 + 6 * per_batch_s + 0.02

    def host_fn(item):
        return np.full((8,), float(item), np.float32)

    def device_fn(batch):
        time.sleep(per_batch_s)  # a deterministic "accelerator"
        return batch

    def make_sched(tenants, telemetry):
        sched = RequestScheduler(
            host_fn,
            device_fn,
            (8,),
            np.float32,
            max_batch=max_batch,
            num_workers=2,
            max_wait_ms=1.0,
            tenants=tenants,
            telemetry=telemetry,
        )
        sched.start()
        return sched

    # ---- contended window: paced latency tenant vs saturating tenant ----
    tel = Telemetry(TelemetryConfig(spans=bool(args.trace_out)))
    sched = make_sched(
        [
            TenantConfig("lat", weight=1.0, max_wait_ms=lat_deadline_ms,
                         max_pending=2 * max_batch),
            TenantConfig("thru", weight=2.0, max_pending=4 * max_batch),
        ],
        tel,
    )
    stop_at = time.perf_counter() + window_s

    def thru_feeder():
        i = 0
        while time.perf_counter() < stop_at:
            sched.submit(i, tenant="thru")  # blocks at max_pending
            i += 1

    def lat_feeder():
        i = 0
        while time.perf_counter() < stop_at:
            sched.submit(i, tenant="lat")
            i += 1
            time.sleep(0.008)  # paced: an interactive client, not a firehose

    threads = [
        threading.Thread(target=thru_feeder),
        threading.Thread(target=lat_feeder),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sched.flush(timeout=60.0)
    sched.drain()
    thru_completed = sched.tenants["thru"].completed
    sched.stop()
    lat_e2e = tel.summary()["tenants"]["lat"]["e2e"]
    trace_spans = None
    if args.trace_out:
        trace_spans = tel.dump_trace(args.trace_out)

    # ---- telemetry overhead: histograms on vs everything off ------------
    items = 256 if args.smoke else 768

    def run_throughput(telemetry):
        s = make_sched(None, telemetry)
        try:
            t0 = time.perf_counter()
            for i in range(items):
                s.submit(i)
            s.flush(timeout=120.0)
            wall = time.perf_counter() - t0
            s.drain()
        finally:
            s.stop()
        return items / wall

    tput_on = tput_off = 0.0
    off_rings = 0
    for _ in range(2):  # interleave so box noise lands on both
        tput_on = max(tput_on, run_throughput(Telemetry()))
        tel_off = Telemetry(TelemetryConfig(histograms=False, spans=False))
        tput_off = max(tput_off, run_throughput(tel_off))
        off_rings += tel_off.ring_allocations

    return {
        "per_batch_s": per_batch_s,
        "max_batch": max_batch,
        "window_s": window_s,
        "lat_deadline_ms": lat_deadline_ms,
        "p99_bound_ms": round(p99_bound_s * 1e3, 2),
        "lat_completed": lat_e2e.count,
        "lat_p50_ms": round(lat_e2e.p50 * 1e3, 3),
        "lat_p95_ms": round(lat_e2e.p95 * 1e3, 3),
        "lat_p99_ms": round(lat_e2e.p99 * 1e3, 3),
        "thru_completed": thru_completed,
        "tput_telemetry_on": round(tput_on, 2),
        "tput_telemetry_off": round(tput_off, 2),
        "telemetry_on_frac_of_off": round(tput_on / tput_off, 4) if tput_off else 0.0,
        "telemetry_off_ring_allocations": off_rings,
        "trace_spans": trace_spans,
    }


def _run_coldstart_leg(args) -> dict:
    """AOT warmup vs lazy compile: first-batch latency against steady p50.

    Two fresh runtimes serve the same batched request stream through the
    scheduler.  Each gets its own model closure, so each owns a fresh jit
    cache — ``warmup="off"`` pays its jit trace + XLA compile on the first
    request batch (the cold-start tail this PR kills), ``warmup="full"``
    pays it at startup instead: the max-batch bucket warms inside
    ``start_serving()`` and the rest of the AOT program set warms on the
    background thread, with ``wait_warm()`` marking full readiness.  Both
    the inline startup cost and the full-readiness time are reported, the
    gates compare first-batch latency (measured from readiness) to the
    steady-state p50 of the remaining batches, and ``warmup=full`` must
    leave ``programs_compiled_post_warmup == 0``.
    """
    import time

    batch = 8
    n_batches = 6 if args.smoke else 8
    input_size = 64
    fmt = ImageFormat("pjpeg", round(input_size * 256 / 224), 90)
    corpus = make_corpus(n_batches * batch, 256, [fmt], seed=11)
    model_spec = ModelSpec(
        "cold-cnn", input_size, exec_throughput=3000.0,
        accuracy_by_format={fmt.key: 1.0},
    )

    def run_once(warmup: str):
        # fresh closure => fresh jit cache: every leg pays (or warms away)
        # its own compiles, nothing leaks across legs
        model = make_model(input_size, width=32, seed=31)
        runtime = SmolRuntime(
            [model_spec],
            [fmt],
            {"cold-cnn": model},
            calibration=corpus[:8],
            config=RuntimeConfig(batch_size=batch, num_workers=2, warmup=warmup),
        )
        t0 = time.perf_counter()
        runtime.start_serving()  # warmup=full warms max-batch inline here
        startup_s = time.perf_counter() - t0
        # the rest of the bucket set warms on the background thread; the
        # first-batch-vs-p50 gate is about the request path being
        # compile-free, so measure from full readiness (no-op for "off")
        runtime.wait_warm(timeout=120.0)
        ready_s = time.perf_counter() - t0
        lat = []
        try:
            for b in range(n_batches):
                group = corpus[b * batch : (b + 1) * batch]
                t0 = time.perf_counter()
                for item in group:
                    runtime.submit(item)
                runtime.flush(timeout=120.0)
                runtime.drain()
                lat.append(time.perf_counter() - t0)
        finally:
            runtime.stop_serving()
        return {
            "startup_s": startup_s,
            "ready_s": ready_s,
            "lat": lat,
            "post_compiles": runtime.programs_compiled_post_warmup,
            "compile_seconds": runtime.program_compile_seconds_total,
        }

    warm = run_once("full")
    cold = run_once("off")
    warm_p50 = float(np.median(warm["lat"][1:]))
    cold_p50 = float(np.median(cold["lat"][1:]))
    return {
        "batch": batch,
        "n_batches": n_batches,
        "warm_startup_s": round(warm["startup_s"], 3),
        "warm_ready_s": round(warm["ready_s"], 3),
        "cold_startup_s": round(cold["startup_s"], 3),
        "warm_first_batch_ms": round(warm["lat"][0] * 1e3, 2),
        "warm_steady_p50_ms": round(warm_p50 * 1e3, 2),
        "warm_first_over_p50": round(warm["lat"][0] / warm_p50, 3) if warm_p50 else 0.0,
        "cold_first_batch_ms": round(cold["lat"][0] * 1e3, 2),
        "cold_steady_p50_ms": round(cold_p50 * 1e3, 2),
        "cold_first_over_p50": round(cold["lat"][0] / cold_p50, 3) if cold_p50 else 0.0,
        "warm_post_startup_compiles": warm["post_compiles"],
        "cold_post_startup_compiles": cold["post_compiles"],
        "warm_compile_seconds": round(warm["compile_seconds"], 3),
        "cold_compile_seconds": round(cold["compile_seconds"], 3),
    }


def _run_overlap_leg(args) -> dict:
    """Double-buffered vs synchronous staging on a deterministic fake device.

    The fake device models what a real accelerator dispatch does: the call
    itself blocks for ``stage_s`` (the synchronous H2D staging leg), then
    compute completes ``compute_s`` later on a *serial* device stream
    (``done_at`` watermark), and results only block at retirement.  With
    synchronous staging the consumer thread pays fill + stage serially per
    batch; double-buffered dispatch moves the staging leg onto the
    dispatcher thread so it overlaps the consumer's filling of batch N+1.
    Host rows are real megabyte-scale memcpys so the consumer-side fill is
    honest work, and every stage time is sleep-controlled, so the leg
    measures the engine's overlap — not box throughput.  A spans-on pass
    counts stage intervals overlapping an in-flight dispatch interval.
    """
    import time

    from repro.core.engine import PipelinedEngine
    from repro.runtime import Telemetry, TelemetryConfig

    stage_s = 0.002  # the dispatch call's synchronous H2D leg
    compute_s = 0.002  # async device compute per batch (serial stream)
    batch = 8
    n_items = (24 if args.smoke else 48) * batch
    row_shape = (512, 512)  # 1 MiB/row float32: staging memcpy is real work

    class _FakeOut:
        def __init__(self, arr, ready_at):
            self._arr = arr
            self._ready_at = ready_at

        def is_ready(self):
            return time.perf_counter() >= self._ready_at

        def block_until_ready(self):
            delay = self._ready_at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            return self

        def __array__(self, dtype=None):
            self.block_until_ready()
            return self._arr if dtype is None else self._arr.astype(dtype)

    class _FakeDevice:
        def __init__(self):
            self.stream_t = 0.0

        def __call__(self, b):
            time.sleep(stage_s)  # synchronous H2D on the calling thread
            now = time.perf_counter()
            done = max(now, self.stream_t) + compute_s
            self.stream_t = done
            return _FakeOut(np.full((len(b),), float(len(b)), np.float32), done)

    row = np.zeros(row_shape, np.float32)

    def host_fn(item):
        return row  # the consumer's staging memcpy is the cost under test

    def run_once(double_buffer: bool, telemetry=None) -> float:
        eng = PipelinedEngine(
            host_fn,
            _FakeDevice(),
            row_shape,
            np.float32,
            batch_size=batch,
            num_workers=2,
            jit=False,
            double_buffer=double_buffer,
            telemetry=telemetry,
        )
        t0 = time.perf_counter()
        out, _ = eng.run(list(range(n_items)))
        wall = time.perf_counter() - t0
        assert len(out) == n_items
        return n_items / wall

    tput_db = tput_sync = 0.0
    for _ in range(2):  # interleave so box noise lands on both legs
        tput_db = max(tput_db, run_once(True))
        tput_sync = max(tput_sync, run_once(False))

    # span evidence: batch N+1's staging overlapping batch N's dispatch
    tel = Telemetry(TelemetryConfig(spans=True))
    run_once(True, telemetry=tel)
    spans = tel.spans()
    stages = [(s.t0, s.t1) for s in spans if s.kind == "batch" and s.name == "stage"]
    disps = [(s.t0, s.t1) for s in spans if s.kind == "batch" and s.name == "dispatch"]
    overlapped = sum(
        1 for s0, s1 in stages if any(d0 < s1 and s0 < d1 for d0, d1 in disps)
    )
    return {
        "stage_s": stage_s,
        "compute_s": compute_s,
        "batch": batch,
        "items": n_items,
        "tput_double_buffered": round(tput_db, 2),
        "tput_synchronous": round(tput_sync, 2),
        "db_speedup": round(tput_db / tput_sync, 3) if tput_sync else 0.0,
        "stage_spans": len(stages),
        "stage_spans_overlapping_dispatch": overlapped,
    }


def _run_hot_corpus_leg(args) -> dict:
    """Rendition cache over a hot corpus: repeat epochs vs cold decode.

    The paper's serving scenario reruns queries over the same stored
    corpus, paying the host decode again on every epoch.  This leg runs
    the decode-bound default workload three ways, interleaved best-of-2:

    * **off** — rendition cache disabled (the PR-9-shaped hot path);
    * **hot** — cache enabled, corpus already resident (epoch 2+): every
      host stage is a cache hit, decode drops off the critical path;
    * **miss** — cache enabled but every epoch submits *fresh* item
      objects, so every lookup misses and pays lookup + admission on top
      of the decode.  This bounds the cache machinery's overhead when it
      never pays off.

    Gates: hot >= 2x off (smoke: breakage-detector 1.3x) at *identical
    predictions*; miss >= 0.98x off (the <=2% overhead bound; smoke
    relaxes to 0.85 for shared-runner jitter); resident bytes stay within
    the configured MemoryBudget child at all times (the cache-off run
    allocating nothing at all is unit-tested, not timed).
    """
    import time

    input_size = 96
    decode_short = round(input_size * 256 / 224)
    fmt = ImageFormat("pjpeg", decode_short, args.quality)
    n = 32 if args.smoke else 64
    corpus = make_corpus(n, args.image_size, [fmt], seed=23)
    model_fn = make_model(input_size, width=args.model_width)
    exec_tput = SmolRuntime.measure_exec_throughput(
        model_fn, input_size, batch_size=args.batch_size
    )
    cache_bytes = 256 << 20

    def rt_for(cache):
        models = [
            ModelSpec(
                "bench-cnn",
                input_size,
                exec_throughput=exec_tput,
                accuracy_by_format={fmt.key: 1.0},
            )
        ]
        return SmolRuntime(
            models,
            [fmt],
            {"bench-cnn": model_fn},
            calibration=corpus[:8],
            config=RuntimeConfig(
                batch_size=args.batch_size,
                num_workers=2,
                recal_workers=False,
                memory=MemoryConfig(rendition_cache_bytes=cache),
            ),
        )

    rt_off, rt_on, rt_miss = rt_for(None), rt_for(cache_bytes), rt_for(cache_bytes)
    eng_off, eng_on, eng_miss = rt_off.engine(), rt_on.engine(), rt_miss.engine()

    def fresh_corpus():
        # same encoded bytes, new identities: every lookup misses, every
        # admission churns — the cache's worst case
        return [StoredImage(im.variants, im.native_shape) for im in corpus]

    # compile + warm outside the clock; the on-leg warm pass also admits
    # the full corpus so its timed epochs are pure hits
    outs_off, _ = eng_off.run(corpus)
    outs_cold, _ = eng_on.run(corpus)
    eng_miss.run(corpus[: 2 * args.batch_size], return_outputs=False)

    def ips(engine, items):
        t0 = time.perf_counter()
        engine.run(items, return_outputs=False)
        return len(items) / (time.perf_counter() - t0)

    off_ips = hot_ips = miss_ips = 0.0
    for _ in range(2):  # interleave so box noise lands on every leg
        off_ips = max(off_ips, ips(eng_off, corpus))
        hot_ips = max(hot_ips, ips(eng_on, corpus))
        miss_ips = max(miss_ips, ips(eng_miss, fresh_corpus()))
    outs_hot, _ = eng_on.run(corpus)

    cs = rt_on.stats().cache
    preds_match = all(
        int(np.argmax(np.asarray(a))) == int(np.argmax(np.asarray(b)))
        and int(np.argmax(np.asarray(a))) == int(np.argmax(np.asarray(c)))
        for a, b, c in zip(outs_off, outs_cold, outs_hot)
    )
    return {
        "items": n,
        "image_size": args.image_size,
        "cache_bytes": cache_bytes,
        "off_ips": round(off_ips, 2),
        "hot_ips": round(hot_ips, 2),
        "miss_ips": round(miss_ips, 2),
        "hot_speedup": round(hot_ips / off_ips, 3) if off_ips else 0.0,
        "miss_frac_of_off": round(miss_ips / off_ips, 3) if off_ips else 0.0,
        "predictions_match": preds_match,
        "cache_hits": cs.hits,
        "cache_admitted": cs.admitted,
        "cache_evictions": cs.evictions,
        "cache_resident_bytes": cs.resident_bytes,
        "cache_within_budget": 0 < cs.resident_bytes <= cs.capacity_bytes,
        "cache_seconds_saved": round(cs.seconds_saved, 4),
    }


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    # defaults make the workload host-decode-bound (big stored images, small
    # model), the regime the paper targets and where worker count matters
    ap.add_argument("--items", type=int, default=96)
    ap.add_argument("--image-size", type=int, default=896)
    ap.add_argument("--input-size", type=int, default=96)
    ap.add_argument("--model-width", type=int, default=16)
    ap.add_argument("--quality", type=int, default=92)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--worker-sweep", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="small/fast run for CI: relaxed gate thresholds; gates only bind "
        "when --check is also given",
    )
    ap.add_argument(
        "--check",
        type=str,
        default=None,
        metavar="BASELINE_JSON",
        help="regression gate: fail when any gate that passes in BASELINE_JSON "
        "fails in this run",
    )
    ap.add_argument(
        "--out",
        type=str,
        default=str(REPO_ROOT / "BENCH_runtime.json"),
        help="where to write the JSON report",
    )
    ap.add_argument(
        "--trace-out",
        type=str,
        default=None,
        metavar="TRACE_JSON",
        help="capture spans during the latency leg and write the "
        "Perfetto/Chrome trace-event JSON here (the CI artifact)",
    )
    args = ap.parse_args(argv)
    # the 1.3x gate compares against a true single-worker baseline — keep
    # worker count 1 in the sweep even under a custom --worker-sweep
    args.worker_sweep = sorted(set(args.worker_sweep) | {1})
    if args.smoke:
        args.items = min(args.items, 32)
        args.model_width = min(args.model_width, 32)

    # pjpeg = libjpeg via Pillow: the C decoder releases the GIL, so the
    # host stage actually scales across producer threads (the numpy codecs
    # serialize on the GIL and would measure scheduler thrash instead).
    # short_side triggers the scaled-IDCT partial decode (§6.4): the full
    # stream is entropy-decoded in C but only a small image crosses back
    # into Python, keeping the GIL-held fraction per item low.
    decode_short = round(args.input_size * 256 / 224)
    fmt = ImageFormat("pjpeg", decode_short, args.quality)
    corpus = make_corpus(args.items, args.image_size, [fmt])
    model_fn = make_model(args.input_size, width=args.model_width)
    exec_tput = SmolRuntime.measure_exec_throughput(
        model_fn, args.input_size, batch_size=args.batch_size
    )
    reps = 2 if args.smoke else 3  # best-of-N: single passes are noisy

    # ---- sweep: workers x pooled ------------------------------------------
    sweep, legs = _run_sweep(args, corpus, model_fn, exec_tput, fmt, reps)
    piped_by_key = {(s["workers"], s["pooled"]): s["pipelined_tput"] for s in sweep}
    pre_by_key = {(s["workers"], s["pooled"]): s["preproc_tput"] for s in sweep}
    # the worker subsystem is judged on the stage it owns — host-side
    # preprocessing throughput (preproc_only isolates the producer pool)
    baseline = pre_by_key[(1, False)]  # single-worker unpooled
    best_pooled_multi = max(
        (t for (w, pooled), t in pre_by_key.items() if pooled and w > 1), default=0.0
    )
    worker_speedup = best_pooled_multi / baseline if baseline > 0 else 0.0
    # staging-buffer pooling is judged on the path that uses it (pipelined),
    # aggregated over the sweep so per-leg scheduler noise can't flip it;
    # the zero-allocation-growth invariant itself is unit-tested
    pooled_sum = sum(piped_by_key[(w, True)] for w in args.worker_sweep)
    unpooled_sum = sum(piped_by_key[(w, False)] for w in args.worker_sweep)
    best_key = max(piped_by_key, key=piped_by_key.get)
    sweep_plan = legs[best_key]["runtime"].plan()
    sweep_split = legs[best_key]["runtime"].compile().placement.split

    # ---- paper §8.2 modes: balanced stages, where overlap pays ------------
    # This leg keeps the full-size model even in smoke: shrinking it makes
    # the device stage ~2x faster than the host stage, and an unbalanced
    # pipeline has (almost) no overlap to measure — the gate would track
    # startup noise.  64+ items keep enough batches in flight for the
    # overlap window to exist at all.
    bal = argparse.Namespace(
        items=max(args.items, 64),
        image_size=128,
        input_size=64,
        model_width=96,
        batch_size=args.batch_size,
    )
    bal_fmt = ImageFormat("pjpeg", None, 90)
    bal_corpus = make_corpus(bal.items, bal.image_size, [bal_fmt])
    bal_model = make_model(bal.input_size, width=bal.model_width)
    bal_exec = SmolRuntime.measure_exec_throughput(
        bal_model, bal.input_size, batch_size=bal.batch_size
    )
    workers = min(4, os.cpu_count() or 2)
    bal_runtime = _make_runtime(bal, bal_corpus, bal_model, bal_exec, bal_fmt, workers, True)
    engine = bal_runtime.engine()
    best = lambda stats: max(stats, key=lambda s: s.throughput)  # noqa: E731
    pre = best([engine.run_preproc_only(bal_corpus) for _ in range(reps)])
    ex = best([engine.run_exec_only(len(bal_corpus)) for _ in range(reps)])
    piped = best([engine.run(bal_corpus, return_outputs=False)[1] for _ in range(reps)])
    serial_sum = 1.0 / (1.0 / pre.throughput + 1.0 / ex.throughput)

    # ---- device path: fused program vs per-op reference chain ------------
    device_leg = _run_device_leg(args, reps)
    import jax as _jax

    on_accel = _jax.default_backend() not in ("cpu",)

    # ---- split decode: 4:4:4 vs 4:2:0 vs scaled factor -------------------
    split_leg = _run_split_decode_leg(args, reps)

    # ---- cascade serving: progressive rendition refetch vs all-full-res ---
    cascade_leg = _run_cascade_leg(args)

    # ---- multi-tenant fairness: weighted-fair scheduling under saturation -
    fairness = _run_fairness_leg(args)

    # ---- replica mesh: 2 dispatchers vs 1 over the shared fair queue ------
    replica_leg = _run_replica_leg(args)

    # ---- latency SLO + telemetry overhead: p99 under contention -----------
    latency_leg = _run_latency_leg(args)

    # ---- cold start: AOT program-set warmup vs compile-on-first-request ---
    coldstart_leg = _run_coldstart_leg(args)

    # ---- dispatch overlap: double-buffered vs synchronous staging ---------
    overlap_leg = _run_overlap_leg(args)

    # ---- hot corpus: rendition cache repeat-epoch speedup + overhead ------
    hot_corpus_leg = _run_hot_corpus_leg(args)

    # the typed RuntimeStats schema is what dashboards consume — read the
    # balanced runtime's snapshot through it rather than an ad-hoc dict
    rstats = bal_runtime.stats()

    # Smoke runs gate on relaxed thresholds.  The timing legs swing tens of
    # percent run-to-run on 2-core shared CI runners, so their smoke gates
    # are *breakage detectors* (a broken pool, fully lost overlap, a worker
    # pool that stopped scaling), not the acceptance thresholds — those
    # bind in full mode.  The fairness leg is sleep-controlled and keeps
    # its real tolerance in both modes.
    thr = {
        "pipeline_speedup": 1.02 if args.smoke else 1.2,
        "worker_speedup": 1.1 if args.smoke else 1.3,
        "pooled_tol": 0.75 if args.smoke else POOLED_GATE_TOL,
        "device_tol": 0.80 if args.smoke else DEVICE_GATE_TOL,
        # the telemetry-on/off runs are sleep-bound, so the full-mode gate
        # binds tight; smoke runners still jitter the host-side share
        "telemetry_tol": 0.90 if args.smoke else 0.97,
        # cold start: the warmed first batch carries scheduler ramp noise on
        # shared runners, and the cold ratio depends on how slow the box's
        # XLA compile is relative to its steady batches
        "coldstart_warm": 3.0 if args.smoke else 1.5,
        "coldstart_cold": 3.0 if args.smoke else 5.0,
        # overlap: sleep+memcpy controlled, but smoke runners time-share
        "overlap_speedup": 1.1 if args.smoke else 1.15,
        # cascade: decode-bound with a 25% refetch fraction, so the full-
        # mode expectation is well above 1.3x; smoke runners time-share the
        # decode pool, so the smoke gate is a breakage detector
        "cascade_speedup": 1.05 if args.smoke else 1.3,
        # hot corpus: a cache hit skips the whole decode-bound host stage,
        # so full mode expects >=2x; the all-miss leg pays lookup+admission
        # on top of the decode, bounded at 2% (smoke runners jitter more)
        "hot_corpus_speedup": 1.3 if args.smoke else 2.0,
        "hot_corpus_miss_tol": 0.85 if args.smoke else 0.98,
    }
    pooled_ge_unpooled = pooled_sum >= thr["pooled_tol"] * unpooled_sum
    device_gate = device_leg["fused_speedup"] >= (
        DEVICE_ACCEL_SPEEDUP if on_accel else thr["device_tol"]
    )
    # scaled decode does strictly less device work than full-res 4:2:0; on
    # CPU the parity floor binds, on accelerators the real >=1.2x speedup
    split_gate = split_leg["scaled_speedup_vs_full"] >= (
        DEVICE_ACCEL_SPEEDUP if on_accel else thr["device_tol"]
    )

    cores = os.cpu_count() or 1
    gates = {
        # host/device overlap needs 2+ cores to exist at all — on 1 core the
        # pipelined run IS the serial sum (same conditioning as the worker
        # gate below)
        "pipeline_speedup_ge_1_2": (
            (piped.throughput / serial_sum >= thr["pipeline_speedup"]) if cores >= 2 else True
        ),
        "pooled_ge_unpooled_per_worker_count": pooled_ge_unpooled,
        # acceptance: multi-worker pooled host-stage throughput >= 1.3x the
        # single-worker unpooled baseline, meaningful with 2+ cores
        "multiworker_pooled_speedup_ge_1_3": (
            (worker_speedup >= thr["worker_speedup"]) if cores >= 2 else True
        ),
        # device compiler: fused >= per-op (CPU parity floor; real >=1.2x
        # speedup gate on accelerator backends)
        "device_fused_ge_reference": device_gate,
        # split decode: every variant (4:4:4, 4:2:0, scaled) matches the
        # host reference decode within one uint8 quant step ...
        "split_decode_parity_one_quant_step": split_leg["parity_all"],
        # ... and the scaled-IDCT program is never slower than the full-res
        # 4:2:0 program (CPU parity floor / >=1.2x accelerator gate)
        "split_decode_scaled_ge_full": split_gate,
        # acceptance: a 2-stage cascade on the scaled rendition beats
        # serving everything at full resolution by >= 1.3x (full mode) ...
        "cascade_speedup_ge_1_3": (
            cascade_leg["cascade_speedup"] >= thr["cascade_speedup"]
        ),
        # ... without changing a single prediction vs the full-res baseline
        "cascade_predictions_match_full_res": cascade_leg["predictions_match"],
        # acceptance: internal refetches bill the submitting tenant — 4:1
        # weights hold within +/- 25% when every item refetches
        "cascade_refetch_fairness_4to1_within_25pct": (
            3.0 <= cascade_leg["refetch_observed_ratio"] <= 5.0
        ),
        # acceptance: 2 tenants at 4:1 weights -> observed throughput ratio
        # 4:1 +/- 25% under saturation ...
        "fairness_ratio_4to1_within_25pct": 3.0 <= fairness["observed_ratio"] <= 5.0,
        # ... while the aggregate stays within 10% of single-tenant
        "multitenant_aggregate_within_10pct": fairness["aggregate_frac_of_single"] >= 0.9,
        # acceptance: 2 replicas over the shared queue sustain >= 1.6x the
        # single-replica throughput on the sleep-controlled device model
        "replica_scaling_2x_ge_1_6": replica_leg["replica_scaling"] >= 1.6,
        # acceptance: the latency tenant's measured p99 stays under the
        # deadline-derived bound while the throughput tenant saturates
        "latency_tenant_p99_under_bound": (
            latency_leg["lat_completed"] > 0
            and latency_leg["lat_p99_ms"] <= latency_leg["p99_bound_ms"]
        ),
        # acceptance: always-on histograms cost <= 3% throughput (full mode)
        "telemetry_overhead_le_3pct": (
            latency_leg["telemetry_on_frac_of_off"] >= thr["telemetry_tol"]
        ),
        # acceptance: telemetry-off runs allocate zero span rings
        "telemetry_off_zero_ring_allocs": (
            latency_leg["telemetry_off_ring_allocations"] == 0
        ),
        # acceptance: with warmup=full the first served batch lands within
        # 1.5x the steady-state p50 — the AOT program set absorbed every
        # jit trace + XLA compile at startup
        "coldstart_warm_first_batch_le_1_5x_p50": (
            0 < coldstart_leg["warm_first_over_p50"] <= thr["coldstart_warm"]
        ),
        # ... while warmup=off shows the tail this kills: first batch >= 5x
        "coldstart_cold_first_batch_ge_5x_p50": (
            coldstart_leg["cold_first_over_p50"] >= thr["coldstart_cold"]
        ),
        # acceptance: warmup=full leaves zero request-path compiles
        "warmup_full_zero_post_startup_compiles": (
            coldstart_leg["warm_post_startup_compiles"] == 0
        ),
        # acceptance: double-buffered dispatch >= 1.15x synchronous staging;
        # overlapping the staging leg with compute needs a second core
        "double_buffer_ge_1_15x_sync": (
            (overlap_leg["db_speedup"] >= thr["overlap_speedup"])
            if cores >= 2
            else True
        ),
        # the spans must actually show stage/compute overlap (batch N+1's
        # staging interval intersecting an in-flight dispatch interval)
        "double_buffer_spans_show_overlap": (
            (overlap_leg["stage_spans_overlapping_dispatch"] > 0)
            if cores >= 2
            else True
        ),
        # acceptance: a hot corpus serves >= 2x the cold decode rate from
        # the rendition cache (full mode) ...
        "hot_corpus_cached_ge_2x_cold": (
            hot_corpus_leg["hot_speedup"] >= thr["hot_corpus_speedup"]
        ),
        # ... at bitwise-stable predictions vs the cacheless runtime
        "hot_corpus_predictions_match": hot_corpus_leg["predictions_match"],
        # acceptance: all-miss traffic pays <= 2% for the cache machinery
        "hot_corpus_miss_overhead_le_2pct": (
            hot_corpus_leg["miss_frac_of_off"] >= thr["hot_corpus_miss_tol"]
        ),
        # acceptance: cache residency stays inside its MemoryBudget child
        "hot_corpus_cache_within_budget": hot_corpus_leg["cache_within_budget"],
    }
    result = {
        "benchmark": "runtime_end_to_end",
        "smoke": args.smoke,
        "cores": cores,
        "items": args.items,
        "batch_size": args.batch_size,
        "sweep_plan": sweep_plan.key,
        "sweep_split": sweep_split,
        "worker_sweep": sweep,
        "single_worker_unpooled_preproc_tput": baseline,
        "best_multiworker_pooled_preproc_tput": best_pooled_multi,
        "worker_pool_speedup": round(worker_speedup, 3),
        "balanced_plan": bal_runtime.plan().key,
        "preproc_only_tput": round(pre.throughput, 2),
        "exec_only_tput": round(ex.throughput, 2),
        "pipelined_tput": round(piped.throughput, 2),
        "serial_sum_tput": round(serial_sum, 2),
        "pipeline_speedup": round(piped.throughput / serial_sum, 3),
        "device_path": device_leg,
        "split_decode": split_leg,
        "cascade": cascade_leg,
        "fairness": fairness,
        "replica_mesh": replica_leg,
        "latency": latency_leg,
        "coldstart": coldstart_leg,
        "overlap": overlap_leg,
        "hot_corpus": hot_corpus_leg,
        "stats_schema_version": rstats.schema_version,
        "device_program_serving": {
            "backend": rstats.device_program.backend,
            "impl": rstats.device_program.impl,
            "dispatches_per_batch": rstats.device_program.dispatches_per_batch,
        },
        "gate_thresholds": thr,
        "gates": gates,
    }
    print(json.dumps(result, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    if args.check:
        # regression gate: every gate the committed baseline passes must
        # still pass here — this is what fails the CI job on a perf break
        with open(args.check) as f:
            baseline_gates = json.load(f).get("gates", {})
        regressed = [k for k, ok in baseline_gates.items() if ok and not gates.get(k, False)]
        if regressed:
            print(f"REGRESSION: gates newly failing vs {args.check}: {regressed}")
            return 1
        print(f"check OK: all {sum(map(bool, baseline_gates.values()))} baseline gates hold")
        return 0
    if args.smoke:
        return 0  # smoke without --check: artifact only, gates don't bind
    return 0 if all(gates.values()) else 1


if __name__ == "__main__":
    sys.exit(main())

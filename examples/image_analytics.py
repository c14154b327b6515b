"""Image-analytics deployment (paper §3.2) on the SmolRuntime facade.

The full SMOL loop, end to end through one object: train the model family
at two input-fidelity conditions (regular + low-res-augmented, §5.3), hand
the runtime the model set 𝒟, the native format set ℱ, and an accuracy
constraint — it calibrates decode/exec throughputs, generates and ranks the
𝒟 × ℱ plan space, splits preprocessing across host/device, and runs the
corpus through the pipelined engine.  A second pass serves the same corpus
request-by-request with span capture on, prints the per-stage latency
breakdown (queue/decode/stage/dispatch/drain p50/p99 from the streaming
histograms), and writes a Perfetto-loadable trace of the run.

    PYTHONPATH=src python examples/image_analytics.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from repro.compile_cache import enable_compile_cache
from benchmarks import vision_common as V
from repro.core.planner import ModelSpec
from repro.preprocessing.formats import (
    FULL_JPEG_Q95,
    THUMB_JPEG_161_Q75,
    THUMB_JPEG_161_Q95,
    THUMB_PNG_161,
)
from repro.runtime import (
    AggregationQuery,
    CascadeQuery,
    CascadeStageSpec,
    ClassificationQuery,
    RecalConfig,
    RuntimeConfig,
    SmolRuntime,
    TelemetryConfig,
)

FORMATS = [FULL_JPEG_Q95, THUMB_PNG_161, THUMB_JPEG_161_Q95, THUMB_JPEG_161_Q75]
COND_BY_KEY = {
    FULL_JPEG_Q95.key: "full",
    THUMB_PNG_161.key: "png161",
    THUMB_JPEG_161_Q95.key: "jq95",
    THUMB_JPEG_161_Q75.key: "jq75",
}


def build_model_set(ds: str):
    """𝒟: each architecture trained regular (full-res only) and low-res-
    augmented (§5.3, accurate on the thumbnail formats too)."""
    models, model_fns = [], {}
    for arch in ("cnn-s", "cnn-l"):
        _, reg_accs, reg_fwd = V.train_model(ds, arch, "reg")
        _, aug_accs, aug_fwd = V.train_model(ds, arch, "png161")
        exec_tput = V.measure_exec_throughput(reg_fwd)

        name = f"{arch}-reg"
        models.append(
            ModelSpec(name, V.INPUT, exec_tput, {FULL_JPEG_Q95.key: reg_accs["full"]})
        )
        model_fns[name] = reg_fwd

        name = f"{arch}-aug"
        models.append(
            ModelSpec(
                name,
                V.INPUT,
                exec_tput,
                {k: aug_accs[c] for k, c in COND_BY_KEY.items() if k != FULL_JPEG_Q95.key},
            )
        )
        model_fns[name] = aug_fwd
    return models, model_fns


def main():
    enable_compile_cache()
    ds = "animals-10"
    stored = V.dataset_cache(ds, 8, 96)[4]
    models, model_fns = build_model_set(ds)

    naive_acc = max(
        m.accuracy_by_format[FULL_JPEG_Q95.key] for m in models if m.name.endswith("-reg")
    )
    floor = naive_acc - 0.02

    runtime = SmolRuntime(
        models,
        FORMATS,
        model_fns,
        calibration=stored[:8],
        config=RuntimeConfig(
            batch_size=16,
            num_workers=2,
            min_accuracy=floor,
            recal=RecalConfig(every=48),
            telemetry=TelemetryConfig(spans=True),  # capture the demo trace
        ),
    )

    print("Pareto frontier (estimated throughput x accuracy):")
    for p in runtime.pareto():
        print("  ", p)

    plan = runtime.plan()
    print(f"\naccuracy-constrained selection (floor {floor:.3f}): {plan}")
    print(
        f"placement: {plan.placement.split} host op(s), "
        f"{len(plan.placement.device_ops)} device op(s)"
    )
    program = runtime.compile().device_program
    print(
        f"device program: backend={program.backend} impl={program.impl} "
        f"fused={program.fused} ({program.dispatches_per_batch} dispatch/batch)"
    )
    if program.stages:
        print(f"  lowering: {' -> '.join(program.stages)}")

    outputs, report = runtime.run(stored)
    preds = [int(np.argmax(o)) for o in outputs]
    print(f"\npipelined run: {report.stats.num_items} images "
          f"@ {report.throughput:.1f} im/s (plan {report.plan_key})")
    print(f"stage occupancy: host {report.stats.host_busy_seconds:.2f}s, "
          f"device {report.stats.device_busy_seconds:.2f}s "
          f"over {report.stats.wall_seconds:.2f}s wall")
    moved = [ev for ev in report.recalibrations if ev.changed]
    for ev in moved:
        print(f"recalibration: split {ev.old_split} -> {ev.new_split}")
    if report.recalibrations and not moved:
        print(f"recalibration: split stable at "
              f"{report.recalibrations[-1].new_split} ({len(report.recalibrations)} checks)")
    print(f"class histogram: {np.bincount(preds).tolist()}")

    # context: what the naive full-res plan would have cost
    naive = [p for p in runtime.planner().generate() if p.model.name.endswith("-reg")]
    if naive:
        best_naive = max(naive, key=lambda p: p.estimate.throughput)
        print(f"\nest. speedup over naive full-res plan: "
              f"{plan.estimate.throughput / best_naive.estimate.throughput:.2f}x")

    # ---- request-level serving with tracing on ---------------------------
    # typed query API (§3.2): classification per item, a cascade pass whose
    # uncertain items progressively refetch the full-res rendition, and an
    # aggregation query that closes its CI on the serving path
    # the briefly-trained probe is diffident (max-softmax ~0.13 over 10
    # classes), so the demo threshold sits at its median confidence; a
    # converged probe would use something like 0.85
    stages = (
        CascadeStageSpec(threshold=0.127, model="cnn-s-aug"),
        CascadeStageSpec(model="cnn-l-reg"),
    )
    runtime.start_serving()
    try:
        for s in stored:
            runtime.submit(ClassificationQuery(image=s))
        runtime.flush()
        served = runtime.drain()

        for s in stored:
            runtime.submit(CascadeQuery(image=s, stages=stages))
        runtime.flush()
        cascaded = runtime.drain()

        agg = runtime.submit(AggregationQuery(corpus=stored, eps=0.25))
    finally:
        runtime.stop_serving()

    exits = sum(1 for r in cascaded if r.ok and r.exit_stage == 0)
    refetched = sum(1 for r in cascaded if r.ok and r.refetched)
    print(f"\ncascade: {exits}/{len(cascaded)} items exited from the cheap "
          f"rendition, {refetched} refetched full resolution")
    sec = runtime.stats().cascade
    if sec is not None:
        for st in sec.stages:
            print(f"  stage {st.stage}: {st.items} items, {st.exits} exits "
                  f"(pass-through {st.pass_fraction:.2f})")
    print(f"aggregation: estimate {agg.estimate:.3f} +/- {agg.ci_halfwidth:.3f} "
          f"({agg.num_target_invocations}/{agg.num_specialized_invocations} "
          f"target refetches)")

    ok = sum(1 for r in served if r.error is None)
    lat = runtime.stats().latency
    print(f"\nserved {ok}/{len(served)} requests; per-stage latency breakdown:")
    print(f"  {'stage':9s} {'p50 ms':>9s} {'p99 ms':>9s}")
    for stage in ("queue", "decode", "stage", "dispatch", "drain", "e2e"):
        h = lat.stages.get(stage)
        if h is not None and h.count:
            print(f"  {stage:9s} {h.p50 * 1e3:9.2f} {h.p99 * 1e3:9.2f}")

    trace_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "image_analytics_trace.json"
    )
    n_spans = runtime.dump_trace(trace_path)
    print(f"wrote {n_spans} spans to {trace_path} — open in https://ui.perfetto.dev")


if __name__ == "__main__":
    main()

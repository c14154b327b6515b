#!/usr/bin/env python3
"""Readings behind a cell's limits, in one process.

    python3 smolbench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
        [--int8-seeds <n> [<n> ...]]

For each of ``--seeds``: a fresh corpus through the cell's runtime, built
once, at the cell's own traffic for ``--seconds``, held to the reference by
``harness.compare`` as a run holds it (reading ``program``).  Then, on the
same answers, the preprocessing control put in the program's place: the
reference's decode and preprocessing with its products at ``high`` (three
bfloat16 passes, ``control_high``) and at the default (one pass,
``control_default``), each answer being that input's sample and the
reference network's logits on it, compared by ``harness.compare`` in the same
way; and both readings' ``pixel_off_share`` at other tie bands
(``tie_bands``).  For the first three seeds, the smallest gap between two
corpus items' reference logits, on the measure of ``logit_gap``
(``item_separation``).

For each of ``--int8-seeds``: the runtime rebuilt with the family's reference
network at int8 operands served in the program's place, driven and compared as
a run is (``control_int8``).

One JSON line per reading on standard output.  Run it on the chip; it is not
part of a benchmark run.
"""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # the checkout's own cache, never evicted

import numpy as np  # noqa: E402

from smolbench import corpus, harness, traffic  # noqa: E402
from smolbench.reference import lowp, preproc  # noqa: E402


TIE_BANDS = (1e-4, 1e-3)  # beside the cell's own ``pixel_tie_levels``


def int8_forward(reference):
    """``forward(params, cfg, x)`` of the int8 control, for ``harness.Run``:
    the family's ``reference`` network with int8 operands."""

    def forward(params, cfg, x):
        return reference.forward(params, cfg, x, quant=True)

    return forward


def control_records(records, items, served, params, cfg: dict, passes: int) -> list:
    """The run's records with every answer replaced by the preprocessing
    control's: the reference's decode and preprocessing at ``passes``
    bfloat16 passes, and the reference network's logits on that input."""
    answered = [r for r in records if r[5] is not None and r[4] is None]
    idx = sorted({r[0] for r in answered})
    x = np.stack([preproc.normalize(lowp.resized(lowp.decode(items[i].variants[served], passes),
                                                 passes, cfg["input_size"], cfg["resize_short"]))
                  for i in idx])
    logits = harness.family(cfg["family"]).reference.logits(params, cfg, x)
    out = {i: np.concatenate([lg, px]) for i, lg, px in zip(idx, logits, harness.pixel_sample(x))}
    return [r[:5] + [out[r[0]]] if r[5] is not None and r[4] is None else list(r) for r in records]


def tie_bands(answers: dict, items, served, cfg: dict, bands) -> dict:
    """``pixel_off_share`` of the program's and the ``high`` control's
    answers at other tie bands than the cell's, to choose the band by."""
    out = {}
    for tie in bands:
        recs = {k: [r for r in v if r[5] is not None and r[4] is None]
                for k, v in answers.items() if k in ("program", "control_high")}
        idx = sorted({r[0] for r in recs["program"]})
        _x, lo, hi = harness.reference_inputs(items, idx, served, cfg, tie)
        out[f"tie_{tie:g}"] = {k: harness.pixel_off_share(v, idx, lo, hi, cfg["num_classes"])
                               for k, v in recs.items()}
    return out


def item_separation(items, served, params, cfg: dict) -> float:
    """The smallest, over pairs of corpus items, of the widest gap between
    their reference logits, as a share of the first one's largest logit."""
    x, _lo, _hi = harness.reference_inputs(items, range(len(items)), served, cfg, 0.0)
    lg = harness.family(cfg["family"]).reference.logits(params, cfg, x)
    scale = np.abs(lg).max(axis=1)
    best = float("inf")
    for i in range(len(lg)):
        d = np.abs(lg - lg[i]).max(axis=1) / scale[i]
        d[i] = np.inf
        best = min(best, float(d.min()))
    return best


def drive(run, seed: int):
    """The cell's traffic through ``run``'s runtime on the corpus of ``seed``;
    returns the records and the corpus."""
    from repro.runtime import ClassificationQuery

    items = run.items if seed == run.seed else corpus.build(run.traffic["corpus"], seed)
    client = traffic.Client(run.rt, items, ClassificationQuery, run.jax.profiler.TraceAnnotation)
    traffic.run(client, run.traffic["arrivals"], len(items), seed, run.traffic["warm"],
                run.seconds, lambda opening: None)
    return list(client.records.values()), items


def reading(name: str, seed: int, limits: dict, correct: bool, t: float, **extra) -> None:
    line = {"reading": name, "seed": seed, "correct": correct,
            **{k: v["value"] for k, v in limits.items()}, **extra,
            "seconds": round(time.perf_counter() - t, 2)}
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--int8-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    check = cell.workload["check"]

    run = harness.Run(cell, args.seeds[0], args.seconds, False, T_START)
    run.build()
    print(json.dumps({"workload": cell.name, "device": run.device, "setup": run.setup_parts}), flush=True)
    for seed in args.seeds:
        t = time.perf_counter()
        records, items = drive(run, seed)
        limits, correct, _ = harness.compare(records, items, run.served, run.params, run.cfg, check)
        reading("program", seed, limits, correct, t)
        answers = {"program": records}
        for passes, name in ((3, "control_high"), (1, "control_default")):
            t = time.perf_counter()
            answers[name] = control_records(records, items, run.served, run.params, run.cfg, passes)
            limits, correct, _ = harness.compare(answers[name], items, run.served, run.params,
                                                 run.cfg, check)
            reading(name, seed, limits, correct, t)
        t = time.perf_counter()
        print(json.dumps({"reading": "tie_bands", "seed": seed,
                          **tie_bands(answers, items, run.served, run.cfg, TIE_BANDS),
                          "seconds": round(time.perf_counter() - t, 2)}), flush=True)
        if seed in args.seeds[:3]:
            t = time.perf_counter()
            sep = item_separation(items, run.served, run.params, run.cfg)
            print(json.dumps({"reading": "item_separation", "seed": seed, "value": sep,
                              "seconds": round(time.perf_counter() - t, 2)}), flush=True)
    run.finish_serving()  # freed before the control's runtime is built

    if args.int8_seeds:
        t = time.perf_counter()
        control = int8_forward(run.family.reference)
        ctl = harness.Run(cell, args.int8_seeds[0], args.seconds, False, t, control)
        ctl.build()
        print(json.dumps({"reading": "control_int8_setup", "setup": ctl.setup_parts}), flush=True)
        for seed in args.int8_seeds:
            t = time.perf_counter()
            records, items = drive(ctl, seed)
            limits, correct, _ = harness.compare(records, items, ctl.served, ctl.params, ctl.cfg, check)
            reading("control_int8", seed, limits, correct, t)
        ctl.finish_serving()
    return 0


if __name__ == "__main__":
    sys.exit(main())

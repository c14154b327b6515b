#!/usr/bin/env python3
"""Chip smoke test: SmolRuntime's served path, end to end, on a TPU.

ResNet-50 (random weights from ``--seed``) classifies a synthetic
``imagenet-sim`` corpus (256x256, made from ``--seed``) stored as 4:2:0
JPEG at full resolution (q95) and as 161-px thumbnails (q75).  Everything
goes through the runtime's own entry points:

* ``run(corpus)`` and ``start_serving()`` / ``submit()`` / ``drain()`` on the
  split-decode path (host entropy stage; dequant+IDCT and resample Pallas
  kernels on the device) and on the pixel path (host decode; resample
  kernel on the device);
* preprocessing parity: the same device program with an identity model
  against the host chain over host ``jpeg.decode``, within one uint8 step,
  and both paths over the thumbnail, whose upsample exercises the
  resample kernel's interpolation;
* DNN parity: served logits against ``resnet_forward`` at "highest" matmul
  precision over the host-preprocessed corpus;
* bucket parity: the same rows through every batch-bucket program of the
  split path, which differ only in batch size.

``--chips 4`` runs only replicated serving: four one-chip replicas against
one replica on the same corpus (same predictions per uid, one program per
distinct device, every replica dispatching).

The last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU.  On any other backend the script runs every phase
as a dry run at a tiny size (TINY_RESNET, 16 items, Pallas kernels
interpreted) and exits non-zero.  Numbers marked ``informal`` are single
timings, not benchmark results.

    python3 chip_smoke.py [--seed N]
    python3 chip_smoke.py --chips 4
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import dag as dag_mod
from repro.core import device_compiler as DC
from repro.core.planner import ModelSpec, standard_chain
from repro.data.datasets import image_dataset
from repro.models.resnet import RESNET50, TINY_RESNET, init_resnet, resnet_forward
from repro.preprocessing import jpeg
from repro.preprocessing import ops as P
from repro.preprocessing.formats import ImageFormat
from repro.preprocessing.ops import TensorMeta
from repro.runtime import (
    ClassificationQuery,
    DeviceCompilerConfig,
    MeshConfig,
    RuntimeConfig,
    SmolRuntime,
)

FULL = ImageFormat("jpeg", None, 95, subsample=True)
THUMB = ImageFormat("jpeg", 161, 75, subsample=True)
# Planning inputs, not measurements (random weights classify nothing):
# ResNet-50's published ImageNet top-1 at full resolution, and a lower
# figure for the thumbnail, so that MIN_ACCURACY selects the full rendition.
ACCURACY = {FULL.key: 0.761, THUMB.key: 0.70}
MIN_ACCURACY = 0.75

# Preprocessing parity (the repo's device-compiler tests use the same
# bounds): the device may differ from the host chain by one uint8 step
# through the steepest Normalize std, where float association flips a value
# sitting on a rounding tie — on a small share of outputs.  The split path
# adds f32 (device) against f64 (host) IDCT ties.
QSTEP = (1.0 / 255.0) / 0.224
TIE_SHARE = {"pixel": 1e-3, "split": 1e-2}

# DNN parity.  The served program runs the convolutions at the chip's
# default f32 matmul precision, which rounds operands to bf16 (2^-9
# relative) and accumulates in f32; the reference runs every product in
# f32.  Through ResNet-50's 54 matmul layers (53 convolutions and the head)
# that rounding moves the logits by 3.9e-3 to 5.1e-3 of the largest
# reference logit on a TPU v5e (seeds 0 and 1); LOGIT_REL_TOL allows 4x
# that.  The bound catches a wrong plane order, a missing normalization or
# a lost bias, which are O(1) relative; it does not catch a lower DNN
# precision: the same DNN wholly in bf16 reads 4.8e-3 to 6.1e-3
# (benchmarks/chip_probes.py dnn).  Preprocessing precision is held by the
# one-step parity above.  With random weights the top two logits can sit
# within that rounding, so top-1 agreement is required on TOP1_MIN of the
# items.
LOGIT_REL_TOL = 2e-2
TOP1_MIN = 0.95


def _identity(x):
    return x


class Smoke:
    def __init__(self, args):
        self.args = args
        self.failures: list[str] = []
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
        self.on_tpu = dev.platform == "tpu"
        # off a TPU: a tiny dry run of the same phases, which never reports ok
        tiny = not self.on_tpu
        self.cfg = TINY_RESNET if tiny else RESNET50
        if args.chips == 4:
            self.n_items, self.batch = (16, 2) if tiny else (128, 8)
        else:
            self.n_items, self.batch = (16, 4) if tiny else (256, 32)
        self.workers = 2 if tiny else min(8, os.cpu_count() or 1)
        # "auto" resolves to the Pallas kernels on a TPU; the dry run forces
        # them (interpreted) so the same programs run
        self.impl = "pallas" if tiny else "auto"

    # ------------------------------------------------------------ reporting
    def report(self, phase: str, **kv) -> None:
        print(json.dumps({"phase": phase, **kv}, default=float), flush=True)

    def check(self, ok: bool, what: str, **kv) -> None:
        if not ok:
            self.failures.append(what)
            self.report("check_failed", what=what, **kv)

    # --------------------------------------------------------------- set-up
    def build(self) -> None:
        t0 = time.perf_counter()
        self.corpus, _labels = image_dataset(
            "imagenet-sim", self.n_items, seed=self.args.seed, formats=[FULL, THUMB]
        )
        params = init_resnet(self.cfg, jax.random.PRNGKey(self.args.seed))
        self.model_fn = lambda x: resnet_forward(params, self.cfg, x)
        self._ref_fn = jax.jit(self.model_fn)
        self.report(
            "setup",
            model=self.cfg.name,
            items=self.n_items,
            batch=self.batch,
            workers=self.workers,
            formats=[FULL.key, THUMB.key],
            seconds_informal=time.perf_counter() - t0,
        )

    def runtime(self, split_decode: str, warmup: str, mesh=None) -> SmolRuntime:
        spec = ModelSpec(self.cfg.name, 224, self.exec_throughput, dict(ACCURACY))
        cfg = RuntimeConfig(
            batch_size=self.batch,
            num_workers=self.workers,
            min_accuracy=MIN_ACCURACY,
            warmup=warmup,
            program_cache_entries=64,
            device=DeviceCompilerConfig(fused_impl=self.impl, split_decode=split_decode),
            mesh=mesh or MeshConfig(),
        )
        return SmolRuntime(
            [spec], [FULL, THUMB], {self.cfg.name: self.model_fn},
            calibration=self.corpus[:4], config=cfg,
        )

    def compile(self, rt: SmolRuntime, label: str):
        """Compile (and, under warmup="full", warm every bucket) — timed."""
        t0 = time.perf_counter()
        compiled = rt.compile()
        rt.wait_warm(timeout=1200.0)  # raises when a background compile failed
        seconds = time.perf_counter() - t0
        progs = list(compiled.device_programs)
        for ps in compiled.program_sets:
            progs += [p for p in ps.programs.values() if p not in progs]
        interpret = not self.on_tpu
        self.check(
            all(p.impl == "pallas" and p.interpret == interpret for p in progs),
            f"{label}: every bound program is pallas, interpret={interpret}",
            programs=[(p.impl, p.interpret) for p in progs],
        )
        self.check(rt.stats().warmup_failures == 0, f"{label}: no warmup failures")
        prog = compiled.device_program
        self.report(
            f"{label}.compile",
            plan=compiled.plan.key,
            split=compiled.placement.split,
            coeff=None if compiled.coeff is None else {
                "factor": compiled.coeff.factor, "layout": compiled.coeff.layout
            },
            impl=prog.impl,
            interpret=prog.interpret,
            stages=list(prog.stages),
            programs=len(progs),
            devices=sorted({-1 if p.device is None else p.device.id for p in progs}),
            compile_seconds_informal=seconds,
        )
        return compiled, seconds

    def serve(self, rt: SmolRuntime, label: str) -> dict[int, np.ndarray]:
        """Submit every corpus item as a ClassificationQuery and drain all."""
        rt.start_serving()
        try:
            t0 = time.perf_counter()
            uids = [rt.submit(ClassificationQuery(item)) for item in self.corpus]
            results = []
            deadline = time.perf_counter() + 600.0
            while len(results) < len(uids) and time.perf_counter() < deadline:
                results += rt.drain(timeout=5.0)
            seconds = time.perf_counter() - t0
        finally:
            rt.stop_serving()
        errors = [repr(r.error) for r in results if r.error is not None]
        self.check(len(results) == len(uids), f"{label}: every request completed",
                   completed=len(results), submitted=len(uids))
        self.check(not errors, f"{label}: no request failed", errors=errors[:3])
        self.check([r.uid for r in results] == uids[: len(results)], f"{label}: drained in uid order")
        self.check(rt.programs_compiled_post_warmup == 0 or rt.config.warmup != "full",
                   f"{label}: no request-path compile after a full warmup")
        scores = {r.uid: np.asarray(r.scores) for r in results if r.error is None}
        finite = all(s.shape == (self.cfg.num_classes,) and np.isfinite(s).all() for s in scores.values())
        self.check(finite, f"{label}: finite logits of shape ({self.cfg.num_classes},)")
        self.report(f"{label}.serve", requests=len(uids), failed=len(errors),
                    items_per_s_informal=len(results) / seconds)
        return scores

    # ----------------------------------------------------------- one chip
    def measure_exec(self) -> None:
        t0 = time.perf_counter()
        self.exec_throughput = SmolRuntime.measure_exec_throughput(
            self.model_fn, 224, batch_size=self.batch
        )
        self.report("exec_throughput", model=self.cfg.name, batch=self.batch,
                    items_per_s_informal=self.exec_throughput,
                    seconds_with_compile_informal=time.perf_counter() - t0)

    def reference(self) -> None:
        """Host decode + host chain, then ResNet at "highest" precision."""
        t0 = time.perf_counter()
        rt = self.runtime("off", "off")
        plan = rt.plan()
        self.ops, self.fmt = list(plan.dag_plan.ops), plan.fmt
        self.check(self.fmt == FULL, "the planner serves the full-resolution rendition",
                   plan=plan.key)
        self.host_pre = np.stack(
            [P.apply_chain_host(self.ops, jpeg.decode(item.variants[self.fmt])) for item in self.corpus]
        )
        logits = []
        with jax.default_matmul_precision("highest"):
            for lo in range(0, self.n_items, self.batch):
                logits.append(np.asarray(self._ref_fn(self.host_pre[lo : lo + self.batch])))
        self.ref_logits = np.concatenate(logits)
        self.report("reference", plan=plan.key, ops=[op.name for op in self.ops],
                    seconds_informal=time.perf_counter() - t0)

    def parity(self, label: str, path: str, prog, staged: np.ndarray, ref: np.ndarray) -> None:
        """Run a preprocessing-only program and hold it to the host chain."""
        diff = np.abs(np.asarray(prog(staged)) - ref)
        share = float((diff > 1e-4).mean())
        self.check(prog.impl == "pallas", f"{label}: parity program is pallas")
        self.check(float(diff.max()) <= QSTEP + 1e-4, f"{label}: preprocessing within one uint8 step",
                   max_abs=float(diff.max()))
        self.check(share < TIE_SHARE[path], f"{label}: rounding ties on few outputs", share=share)
        self.report(f"{label}.preproc_parity", items=len(staged), stages=list(prog.stages),
                    max_abs=float(diff.max()), one_step=QSTEP, tie_share=share,
                    tie_share_bound=TIE_SHARE[path])

    def preproc_parity(self, compiled, label: str) -> None:
        """The bound program's preprocessing with an identity model."""
        n = self.batch
        staged = np.stack([compiled.host_fn(item) for item in self.corpus[:n]])
        if compiled.coeff is not None:
            header = jpeg.peek_header(self.corpus[0].variants[self.fmt])
            prog = DC.compile_coeff_program(
                header, self.ops, _identity, n, factor=compiled.coeff.factor,
                layout=compiled.coeff.layout, impl=self.impl,
            )
        else:
            prog = DC.compile_device_program(
                list(compiled.placement.device_ops), compiled.device_program.in_meta,
                _identity, n, impl=self.impl,
            )
        self.parity(label, label, prog, staged, self.host_pre[:n])

    def thumb_parity(self) -> None:
        """Both paths over the thumbnail rendition.  Its 141->224 upsample
        exercises the resample kernel's interpolation; at full resolution
        the resize is 224->224, an identity."""
        items = self.corpus[: self.batch]
        pixels = np.stack([jpeg.decode(item.variants[THUMB]) for item in items])
        meta = TensorMeta(pixels.shape[1:], "uint8", "HWC")
        ops = list(dag_mod.optimize(standard_chain(224), meta).ops)
        ref = np.stack([P.apply_chain_host(ops, x) for x in pixels])
        header = jpeg.peek_header(items[0].variants[THUMB])
        coeffs = np.stack([
            jpeg.stage_coefficients(item.decode_to_coefficients(THUMB)[1], header, "packed")
            for item in items
        ])
        n = len(items)
        split = DC.compile_coeff_program(header, ops, _identity, n, layout="packed", impl=self.impl)
        pixel = DC.compile_device_program(ops, meta, _identity, n, impl=self.impl)
        self.parity("thumb.split", "split", split, coeffs, ref)
        self.parity("thumb.pixel", "pixel", pixel, pixels, ref)

    def dnn_parity(self, scores: dict[int, np.ndarray], label: str) -> None:
        served = np.stack([scores[u] for u in sorted(scores)])
        ref = self.ref_logits[: len(served)]
        rel = float(np.abs(served - ref).max() / np.abs(ref).max())
        top1 = float((served.argmax(1) == ref.argmax(1)).mean())
        self.check(len(served) == self.n_items, f"{label}: logits for every item")
        self.check(rel <= LOGIT_REL_TOL, f"{label}: logits within tolerance", max_rel=rel)
        self.check(top1 >= TOP1_MIN, f"{label}: top-1 agreement", top1=top1)
        self.report(f"{label}.dnn_parity", items=len(served), max_rel=rel, max_rel_bound=LOGIT_REL_TOL,
                    top1_agreement=top1, top1_bound=TOP1_MIN)

    def bucket_parity(self, compiled, label: str) -> None:
        """The same rows through every bucket program of the split path.
        The programs differ only in batch size, so any gap between them is
        the compiler's per-shape rounding; a ragged batch lands in a smaller
        bucket than a full one."""
        ps = compiled.program_sets[0]
        staged = np.stack([compiled.host_fn(item) for item in self.corpus[: ps.max_batch]])
        full = np.asarray(ps.programs[ps.max_batch](staged))
        gaps = {b: float(np.abs(np.asarray(p(staged[:b])) - full[:b]).max())
                for b, p in ps.programs.items()}
        largest = float(np.abs(full).max())
        rel = max(gaps.values()) / largest
        self.check(rel <= LOGIT_REL_TOL, f"{label}: bucket programs agree within tolerance",
                   max_rel=rel)
        self.report(f"{label}.bucket_parity", max_abs_by_bucket=gaps, largest_logit=largest,
                    max_rel=rel, max_rel_bound=LOGIT_REL_TOL)

    def path(self, split_decode: str, warmup: str) -> None:
        label = {"full": "split", "off": "pixel"}[split_decode]
        rt = self.runtime(split_decode, warmup)
        compiled, _ = self.compile(rt, label)
        self.check((compiled.coeff is not None) == (split_decode == "full"),
                   f"{label}: split decode {'on' if split_decode == 'full' else 'off'}")
        t0 = time.perf_counter()
        outs, _report = rt.run(self.corpus)
        run_s = time.perf_counter() - t0
        outs = np.stack([np.asarray(o) for o in outs])
        self.check(outs.shape == (self.n_items, self.cfg.num_classes) and np.isfinite(outs).all(),
                   f"{label}: run() returns finite logits for every item")
        self.report(f"{label}.run", items=len(outs), items_per_s_informal=self.n_items / run_s,
                    first_dispatch_seconds_informal=compiled.device_program.first_dispatch_seconds)
        scores = self.serve(rt, label)
        self.preproc_parity(compiled, label)
        self.dnn_parity(scores, label)
        if compiled.program_sets:
            self.bucket_parity(compiled, label)

    def one_chip(self) -> None:
        self.measure_exec()
        self.reference()
        self.path("full", "full")
        self.path("off", "off")
        self.thumb_parity()

    # -------------------------------------------------------- four chips
    def replicas(self) -> None:
        self.check(len(jax.devices()) >= 4, "four devices visible", devices=len(jax.devices()))
        # placeholder planning rate: this path compares replicas, not speed
        self.exec_throughput = 1000.0
        served = {}
        for label, mesh in (
            ("replicas4", MeshConfig(replicas=4)),
            ("replicas1", MeshConfig(replicas=1, devices=(0,))),
        ):
            rt = self.runtime("full", "full", mesh=mesh)
            compiled, _ = self.compile(rt, label)
            ids = [p.device.id for p in compiled.device_programs]
            self.check(len(set(ids)) == len(ids) == mesh.replicas,
                       f"{label}: one program per distinct device", devices=ids)
            served[label] = self.serve(rt, label)
            snaps = rt.stats().mesh.replicas
            self.check(all(s.batches >= 1 for s in snaps), f"{label}: every replica dispatched",
                       batches=[s.batches for s in snaps])
            self.report(f"{label}.mesh", devices=ids, batches=[s.batches for s in snaps],
                        items=[s.items for s in snaps])
        a, b = served["replicas4"], served["replicas1"]
        same = sorted(a) == sorted(b) and all(a[u].argmax() == b[u].argmax() for u in a)
        self.check(same, "replicas4 and replicas1 predict the same class per uid")
        # a row may land in a different bucket program in the two layouts
        # (ragged batches), and bucket programs round differently (the
        # one-chip bucket_parity phase measures by how much): logits agree
        # to the DNN tolerance, relative to the largest logit
        diff = max(float(np.abs(a[u] - b[u]).max()) for u in a if u in b)
        largest = max(float(np.abs(b[u]).max()) for u in b)
        rel = diff / largest
        self.check(rel <= LOGIT_REL_TOL, "replica logits agree within tolerance", max_rel=rel)
        self.report("replicas.compare", uids=len(a), same_predictions=same,
                    max_abs_logit_diff=diff, largest_logit=largest, max_rel_logit_diff=rel,
                    max_rel_bound=LOGIT_REL_TOL)

    def run(self) -> int:
        self.report("devices", **self.device)
        self.report("compile_cache", dir=enable_compile_cache())
        try:
            self.build()
            if self.args.chips == 4:
                self.replicas()
            else:
                self.one_chip()
        except Exception:  # noqa: BLE001 — any phase error fails the smoke
            traceback.print_exc()
            self.failures.append("a phase raised")
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            self.report("device_memory", device=dev.id, bytes_in_use=stats.get("bytes_in_use"),
                        peak_bytes_in_use=stats.get("peak_bytes_in_use"))
        if self.failures or not self.on_tpu:
            self.report("failed" if self.failures else "dry_run_no_tpu",
                        failures=self.failures, device=self.device)
            return 1
        print(json.dumps({"ok": True, "device": self.device}), flush=True)
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    return Smoke(ap.parse_args(argv)).run()


if __name__ == "__main__":
    sys.exit(main())

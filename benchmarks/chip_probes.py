#!/usr/bin/env python3
"""Chip probes behind the precision choices and the compile-cache notes in
PERF.md.  Each prints JSON lines; run them on a TPU (on a CPU every f32
matmul is already f32, so the precision probes show nothing).

    python3 benchmarks/chip_probes.py [precision] [dnn] [compile_cache]

precision      preprocessing parity against the host chain with one
               ``Precision.HIGHEST`` site at a time put back at the default
               f32 matmul precision (identity model, 32 items, the full q95
               and the 161-px q75 4:2:0 renditions, split and pixel paths).
dnn            ResNet-50 logits at the default precision, and with bf16
               weights and activations as a lower-precision control, against
               "highest" over host-preprocessed inputs, for two seeds.
compile_cache  one ResNet-50 split-decode bucket program lowered and compiled
               twice with the in-memory caches cleared between, counting the
               persistent cache's events and the growth of its directory.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.compile_cache import enable_compile_cache
from repro.core import dag as dag_mod
from repro.core import device_compiler as DC
from repro.core.planner import standard_chain
from repro.data.datasets import image_dataset
from repro.kernels.fused_preproc import fused_preproc as FP
from repro.kernels.idct import idct as IK
from repro.models.resnet import RESNET50, init_resnet, resnet_forward
from repro.preprocessing import jpeg
from repro.preprocessing import ops as P
from repro.preprocessing.formats import ImageFormat
from repro.preprocessing.ops import TensorMeta

FULL = ImageFormat("jpeg", None, 95, subsample=True)
THUMB = ImageFormat("jpeg", 161, 75, subsample=True)
QSTEP = (1.0 / 255.0) / 0.224  # one uint8 step through the steepest std


def emit(**kv) -> None:
    print(json.dumps(kv, default=float), flush=True)


def _identity(x):
    return x


def _host_case(corpus, fmt):
    """(meta, ops, host-chain reference, header, staged coefficients, pixels)."""
    pixels = np.stack([jpeg.decode(c.variants[fmt]) for c in corpus])
    meta = TensorMeta(pixels.shape[1:], "uint8", "HWC")
    ops = list(dag_mod.optimize(standard_chain(224), meta).ops)
    ref = np.stack([P.apply_chain_host(ops, p) for p in pixels])
    header = jpeg.peek_header(corpus[0].variants[fmt])
    staged = np.stack(
        [jpeg.stage_coefficients(c.decode_to_coefficients(fmt)[1], header, "packed") for c in corpus]
    )
    return meta, ops, ref, header, staged, pixels


# ------------------------------------------------------------- precision
def _idct_default(x_ref, m_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], m_ref[...], preferred_element_type=jnp.float32)


def _resample_default(x_ref, ry_ref, rxt_ref, scale_ref, bias_ref, o_ref, *, round_uint8):
    ci = pl.program_id(0)
    y = jnp.dot(ry_ref[...], x_ref[0], preferred_element_type=jnp.float32)
    z = jnp.dot(y, rxt_ref[...], preferred_element_type=jnp.float32)
    if round_uint8:
        z = jnp.clip(jnp.round(z), 0.0, 255.0)
    o_ref[0] = z * scale_ref[ci] + bias_ref[ci]


class _DefaultEinsum:
    """``jnp`` as the device compiler sees it, with einsum's precision dropped."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def einsum(*args, precision=None, **kw):
        return jnp.einsum(*args, **kw)


def probe_precision(n: int = 32) -> None:
    originals = {"idct": IK._idct_kernel, "resample": FP._kernel, "einsum": DC.jnp}
    corpus, _ = image_dataset("imagenet-sim", n, seed=0, formats=[FULL, THUMB])
    cases = [(fmt, *_host_case(corpus, fmt)) for fmt in (FULL, THUMB)]
    try:
        for sites in ((), ("idct",), ("einsum",), ("resample",), ("idct", "einsum", "resample")):
            IK._idct_kernel = _idct_default if "idct" in sites else originals["idct"]
            FP._kernel = _resample_default if "resample" in sites else originals["resample"]
            DC.jnp = _DefaultEinsum() if "einsum" in sites else originals["einsum"]
            jax.clear_caches()
            for fmt, meta, ops, ref, header, staged, pixels in cases:
                split = DC.compile_coeff_program(header, ops, _identity, n, layout="packed",
                                                 impl="pallas")
                pixel = DC.compile_device_program(ops, meta, _identity, n, impl="pallas")
                for path, prog, x in (("split", split, staged), ("pixel", pixel, pixels)):
                    d = np.abs(np.asarray(prog(x)) - ref)
                    emit(probe="precision", default_precision_at=list(sites), format=fmt.key,
                         path=path, interpret=prog.interpret, max_abs_in_steps=d.max() / QSTEP,
                         share_off=float((d > 1e-4).mean()))
    finally:
        IK._idct_kernel, FP._kernel, DC.jnp = (
            originals["idct"], originals["resample"], originals["einsum"]
        )
        jax.clear_caches()


# ------------------------------------------------------------------- dnn
def probe_dnn(n: int = 64, batch: int = 32, seeds=(0, 1)) -> None:
    forward = jax.jit(lambda params, x: resnet_forward(params, RESNET50, x))

    def logits(params, x, dtype=jnp.float32):
        params = jax.tree.map(lambda a: a.astype(dtype), params)
        return np.concatenate([
            np.asarray(forward(params, jnp.asarray(x[lo : lo + batch], dtype)), np.float32)
            for lo in range(0, len(x), batch)
        ])

    for seed in seeds:
        corpus, _ = image_dataset("imagenet-sim", n, seed=seed, formats=[FULL])
        _meta, _ops, x, *_ = _host_case(corpus, FULL)
        params = init_resnet(RESNET50, jax.random.PRNGKey(seed))
        with jax.default_matmul_precision("highest"):
            ref = logits(params, x)
        largest = float(np.abs(ref).max())
        for variant, out in (("default", logits(params, x)),
                             ("bf16", logits(params, x, jnp.bfloat16))):
            emit(probe="dnn", seed=seed, variant=variant, items=n, largest_logit=largest,
                 max_rel=float(np.abs(out - ref).max()) / largest,
                 top1_agreement=float((out.argmax(1) == ref.argmax(1)).mean()))


# --------------------------------------------------------- compile cache
def _dir_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total


def probe_compile_cache(batch: int = 32) -> None:
    cache_dir = enable_compile_cache()
    events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **_kw: events.update([name]) if "compilation_cache" in name else None
    )
    corpus, _ = image_dataset("imagenet-sim", 2, seed=0, formats=[FULL])
    meta, ops, *_ = _host_case(corpus, FULL)
    header = jpeg.peek_header(corpus[0].variants[FULL])
    params = init_resnet(RESNET50, jax.random.PRNGKey(0))
    for attempt in ("first", "after_clear_caches"):
        jax.clear_caches()
        events.clear()
        before = _dir_bytes(cache_dir)
        prog = DC.compile_coeff_program(
            header, ops, lambda x: resnet_forward(params, RESNET50, x), batch, layout="packed"
        )
        zeros = np.zeros((batch, *prog.in_meta.shape), prog.in_meta.dtype)
        t0 = time.perf_counter()
        lowered = prog.fn.lower(zeros)
        t1 = time.perf_counter()
        lowered.compile()
        t2 = time.perf_counter()
        emit(probe="compile_cache", attempt=attempt, dir=cache_dir, batch=batch,
             trace_lower_seconds=t1 - t0, compile_seconds=t2 - t1,
             cache_events=dict(events), dir_bytes_added=_dir_bytes(cache_dir) - before)


PROBES = {"precision": probe_precision, "dnn": probe_dnn, "compile_cache": probe_compile_cache}


def main(argv=None) -> int:
    names = (argv if argv is not None else sys.argv[1:]) or list(PROBES)
    unknown = [n for n in names if n not in PROBES]
    if unknown:
        print(f"unknown probe(s) {unknown}; choose from {list(PROBES)}", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    emit(probe="devices", platform=dev.platform, kind=dev.device_kind, count=len(jax.devices()))
    for name in names:
        PROBES[name]()
    return 0


if __name__ == "__main__":
    sys.exit(main())

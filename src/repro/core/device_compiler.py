"""Device preprocessing compiler: Placement suffix -> ONE compiled program.

The placement optimizer (core/placement.py) splits a preprocessing chain at
k: ops[:k] run on host workers, ops[k:] on the accelerator.  Before this
module, the device half executed as a fold of per-op ``apply_device`` calls
vmapped under one jit — correct, but structured as an interpretive chain:
every op materializes an intermediate, the resample is a gather, and the
elementwise tail runs as separate passes.  This compiler *lowers* the
device suffix instead (paper §6.2's fusion, pushed device-side):

* the suffix is partitioned into fusion groups (core/dag.py
  ``device_fusion_groups``) via each op's ``lowering_spec()`` protocol;
* a single-group suffix matching ``[crop?] resize? [crop?] affine* layout?``
  lowers to ONE fused resample+affine stage — on TPU the
  ``kernels/fused_preproc`` Pallas kernel (matmul bilinear against
  precomputed interpolation matrices, folded ToFloat/Normalize riding in
  the same VMEM pass), on CPU/interpret a gather lowering that matches the
  host chain's arithmetic bit-for-bit;
* crops fold into the interpolation matrices (a crop after resize is a row
  slice of R_y and a column slice of R_x — zero cost), and the
  ChannelsFirst layout change is absorbed structurally because the fused
  stage computes in planar CHW throughout;
* non-fusible suffixes fall back to the per-op reference chain, still
  traced into the same jitted program;
* the DNN apply-fn is fused into the same XLA program, so preproc + DNN is
  exactly one device dispatch per batch (donated input on accelerators).

:func:`compile_coeff_program` extends the lowering upstream of pixels: the
host stops after the entropy stage (``jpeg.decode_to_coefficients``) and
the program runs dequantize+IDCT on the ``kernels/idct`` MXU kernel, JFIF
color conversion, then the fused preprocessing stage and the DNN — the
paper's §6.4 split-decode placement, compiled instead of interpreted.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, MutableMapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dag as dag_mod
from repro.kernels.fused_preproc.ops import bilinear_matrix, fused_resize_affine
from repro.kernels.idct.ops import dequant_idct
from repro.kernels.mode import resolve_interpret
from repro.preprocessing import ops as P
from repro.preprocessing.ops import PreprocOp, TensorMeta


def resolve_impl(impl: str = "auto") -> str:
    """Pick the fused-stage implementation: 'pallas' (TPU, or forced via the
    REPRO_FUSED_IMPL env var — the CI interpret leg) or 'jnp'."""
    if impl != "auto":
        return impl
    env = os.environ.get("REPRO_FUSED_IMPL", "").strip().lower()
    if env in ("pallas", "jnp"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


# ------------------------------------------------------- dispatch calibration
_MEASURED_DISPATCH_S: dict[tuple[str, str], float] = {}


def _dispatch_memo_key(device: Any = None) -> tuple[str, str]:
    """Memo identity for dispatch-overhead measurements: (platform, kind).

    A mesh over heterogeneous or virtual devices must not reuse one
    device's measured overhead for another kind — the memo is keyed by
    what is actually being dispatched to, not cached process-wide.
    """
    if device is not None and hasattr(device, "device_set"):
        device = min(device.device_set, key=lambda d: d.id)
    if device is None:
        devices = jax.devices()
        device = devices[0] if devices else None
    if device is None:
        return (jax.default_backend(), "")
    return (
        getattr(device, "platform", jax.default_backend()),
        str(getattr(device, "device_kind", "")),
    )


def measure_dispatch_overhead(
    iters: int = 24, force: bool = False, device: Any = None
) -> float:
    """Measured per-dispatch launch overhead: one *empty* device dispatch.

    Times a trivial jitted program (compile + first run outside the clock)
    and takes the best of ``iters`` dispatch→completion round trips — the
    floor any device dispatch pays before doing work.  The result feeds the
    placement cost model's ``device_dispatch_overhead_s`` so fused-group
    costing binds by *measurement* instead of a config knob (ROADMAP item).
    Cached per (backend, device kind): the overhead is a property of the
    dispatch target, not of any one plan — and not of the whole process,
    which may host a mesh of unlike devices.
    """
    key = _dispatch_memo_key(device)
    if key in _MEASURED_DISPATCH_S and not force:
        return _MEASURED_DISPATCH_S[key]
    import time

    x = jnp.zeros((8,), jnp.float32)
    if device is not None and not hasattr(device, "device_set"):
        x = jax.device_put(x, device)
    fn = jax.jit(lambda v: v + 1.0)
    jax.block_until_ready(fn(x))  # compile + warm outside the clock
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t0)
    _MEASURED_DISPATCH_S[key] = best
    return best


# ------------------------------------------------------------- program cache
@dataclasses.dataclass(frozen=True)
class ProgramCacheStats:
    max_entries: int
    entries: int
    hits: int  # program reuses (cache lookups that found a program)
    misses: int  # compiles (insertions of a freshly-built program)
    evictions: int  # LRU removals forced by max_entries
    pinned: int = 0  # entries held non-evictable by a bound ProgramSet


class ProgramCache(MutableMapping):
    """Bounded LRU cache for compiled device programs.

    Drop-in for the plain dict ``compile_device_program`` /
    ``compile_coeff_program`` accept as ``cache``: lookups refresh recency,
    insertions evict the least-recently-used program once ``max_entries``
    is exceeded.  Multi-tenant serving churns programs (tenants pin
    different models/plans), and compiled XLA executables hold device
    memory — unbounded growth is the ROADMAP's "batched-shape program
    eviction" hazard.  LRU keeps every *active* tenant's program resident:
    a program serving traffic is re-looked-up on each placement move or
    scheduler rebind and therefore never at the cold end.

    Warm AOT :class:`ProgramSet` entries are *pinned* (refcounted, one pin
    per bound set): eviction skips pinned keys, so LRU churn from other
    tenants can never silently undo a startup warmup.  When every entry is
    pinned the cache is allowed to exceed ``max_entries`` rather than
    evict a warm program — the facade warns at warmup time when the
    configured bound is smaller than the warmup set.
    """

    def __init__(self, max_entries: int = 16):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = int(max_entries)
        self._data: dict = {}  # insertion/recency ordered (py3.7+ dicts)
        self._pins: dict = {}  # key -> pin refcount
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    def __getitem__(self, key):
        prog = self._data.pop(key)  # KeyError propagates
        self._data[key] = prog  # re-insert at the hot end
        self._hits += 1
        return prog

    def __setitem__(self, key, program) -> None:
        if key in self._data:
            self._data.pop(key)
        else:
            self._misses += 1
        self._data[key] = program
        while len(self._data) > self.max_entries:
            # never victimise the entry being inserted: when everything
            # older is pinned, warmup's compile-then-pin sequence must find
            # its fresh program still resident
            victim = next(
                (k for k in self._data if k != key and k not in self._pins), None
            )
            if victim is None:
                break  # everything else resident is pinned: grow past the bound
            self._data.pop(victim)
            self._evictions += 1

    def pin(self, key) -> None:
        """Hold ``key`` non-evictable (refcounted; raises when absent)."""
        if key not in self._data:
            raise KeyError(key)
        self._pins[key] = self._pins.get(key, 0) + 1

    def unpin(self, key) -> None:
        """Drop one pin on ``key`` (no-op when not pinned)."""
        n = self._pins.get(key, 0)
        if n <= 1:
            self._pins.pop(key, None)
        else:
            self._pins[key] = n - 1

    def __delitem__(self, key) -> None:
        del self._data[key]
        self._pins.pop(key, None)

    def __contains__(self, key) -> bool:  # no stats: peek, not use
        return key in self._data

    def __iter__(self):
        return iter(self._data)

    def __len__(self) -> int:
        return len(self._data)

    def stats(self) -> ProgramCacheStats:
        return ProgramCacheStats(
            max_entries=self.max_entries,
            entries=len(self._data),
            hits=self._hits,
            misses=self._misses,
            evictions=self._evictions,
            pinned=len(self._pins),
        )


# ----------------------------------------------------------- device placement
def device_cache_key(device: Any) -> Any:
    """Hashable cache identity of a program's device placement.

    ``None`` (the process-default device), one ``jax.Device`` (a replica
    pinned to that accelerator), or a ``jax.sharding.Sharding`` (a replica
    group sharding one program across its devices) all key differently, so
    a mesh compiles one program instance per replica group.
    """
    if device is None:
        return None
    if hasattr(device, "device_set"):  # a Sharding spanning a replica group
        return ("sharded", tuple(sorted(d.id for d in device.device_set)))
    return ("device", device.id)


def _place(batch: Any, device: Any):
    """Commit a staged host batch to a program's device placement."""
    if device is None:
        return batch
    return jax.device_put(batch, device)


# ------------------------------------------------------------------- lowering
@dataclasses.dataclass(frozen=True)
class Lowering:
    """Fused-stage plan for one device suffix: static geometry + folded affine."""

    in_meta: TensorMeta
    out_meta: TensorMeta
    pre_crop: tuple[int, int, int, int] | None  # (top, left, h, w) before resize
    resize: tuple[int, int] | None  # (oh, ow) resample target
    post_crop: tuple[int, int, int, int] | None  # (top, left, h, w) after resize
    round_uint8: bool  # resample re-quantizes to the integer pixel grid
    scale: tuple[float, ...]  # per-channel folded multiplier
    bias: tuple[float, ...]  # per-channel folded offset
    stages: tuple[str, ...]  # human-readable lowering description


def _compose_crop(first, second):
    """second applied after first: offsets accumulate, extent is second's."""
    if first is None:
        return second
    ft, fl, _, _ = first
    st, sl, sh, sw = second
    return (ft + st, fl + sl, sh, sw)


def lower_device_ops(device_ops: Sequence[PreprocOp], in_meta: TensorMeta) -> Lowering | None:
    """Pattern-match a device suffix into one fused stage, or None.

    Accepts any single fusion group (``dag.device_fusion_groups``): at most
    one resize, crops on either side of it (composed when repeated), any
    number of affine/layout ops anywhere — bilinear resampling is affine-
    invariant (weights sum to 1), so folded scale/bias commute past it.
    """
    if not device_ops:
        return None
    groups = dag_mod.device_fusion_groups(device_ops, in_meta)
    if len(groups) != 1:
        return None  # opaque op or second resample: reference chain fallback
    m = in_meta
    pre_crop = resize = post_crop = None
    round_uint8 = False
    affine_ops: list[PreprocOp] = []
    stages: list[str] = []
    for op in device_ops:
        spec = op.lowering_spec(m)
        assert spec is not None  # single group => every op lowered
        if spec.kind == "resize":
            resize = spec.out_hw
            round_uint8 = m.dtype == "uint8"
            stages.append(f"resize{m.spatial}->{spec.out_hw}" + ("+requant" if round_uint8 else ""))
        elif spec.kind == "crop":
            if resize is None:
                pre_crop = _compose_crop(pre_crop, spec.crop)
                stages.append(f"crop{spec.crop}")
            else:
                post_crop = _compose_crop(post_crop, spec.crop)
                stages.append(f"crop{spec.crop}<-folded-into-resize")
        elif spec.kind == "affine":
            affine_ops.append(op)
            stages.append(op.name)
        elif spec.kind == "layout":
            stages.append("chw")
        m = op.out_meta(m)
    scale, bias, _ = P.fold_affine(affine_ops, in_meta.channels)
    return Lowering(
        in_meta=in_meta,
        out_meta=m,
        pre_crop=pre_crop,
        resize=resize,
        post_crop=post_crop,
        round_uint8=round_uint8,
        scale=tuple(float(s) for s in scale),
        bias=tuple(float(b) for b in bias),
        stages=tuple(stages),
    )


# ------------------------------------------------------------ stage builders
def _resize_affine_jnp(x, out_h, out_w, row_win, col_win, scale, bias, round_uint8):
    """Gather-based fused resample+affine on planar (N, C, H, W) input.

    Per-element arithmetic mirrors ``preprocessing.ops._bilinear_resize``
    exactly (same expression tree), so the fused program is bit-compatible
    with the host/reference chain even at uint8 re-quantization boundaries.
    Only the output window ``(row_win, col_win)`` is computed — a crop after
    resize costs nothing.
    """
    h, w = x.shape[2], x.shape[3]
    r0, rows = row_win
    c0, cols = col_win
    y0, y1, wy = (v[r0 : r0 + rows] for v in P.bilinear_coords(h, out_h, jnp))
    x0, x1, wx = (v[c0 : c0 + cols] for v in P.bilinear_coords(w, out_w, jnp))
    wy = wy[:, None]
    wx = wx[None, :]
    a = x[:, :, y0][:, :, :, x0]
    b = x[:, :, y0][:, :, :, x1]
    c = x[:, :, y1][:, :, :, x0]
    d = x[:, :, y1][:, :, :, x1]
    top = a + (b - a) * wx
    bot = c + (d - c) * wx
    out = top + (bot - top) * wy
    if round_uint8:
        out = jnp.clip(jnp.round(out), 0.0, 255.0)
    return out * scale[None, :, None, None] + bias[None, :, None, None]


def build_fused_stage(
    low: Lowering,
    impl: str,
    interpret: bool,
    input_planar: bool = False,
) -> Callable[[Any], jnp.ndarray]:
    """The lowered preprocessing stage: (N, *in_meta.shape) -> out_meta batch.

    All geometry is static (shapes come from the calibration meta), so the
    whole stage traces into whatever program calls it.
    """
    channels = low.in_meta.channels
    scale = jnp.asarray(np.asarray(low.scale, np.float32))
    bias = jnp.asarray(np.asarray(low.bias, np.float32))

    def stage(batch):
        x = jnp.asarray(batch).astype(jnp.float32)
        if not input_planar and low.in_meta.layout == "HWC":
            x = jnp.transpose(x, (0, 3, 1, 2))  # planar CHW compute layout
        n = x.shape[0]
        if low.pre_crop is not None:
            t, l, ch, cw = low.pre_crop
            x = x[:, :, t : t + ch, l : l + cw]
        if low.resize is not None:
            oh, ow = low.resize
            h, w = x.shape[2], x.shape[3]
            t, l, rows, cols = low.post_crop if low.post_crop is not None else (0, 0, oh, ow)
            if impl == "pallas":
                ry = bilinear_matrix(h, oh)[t : t + rows]
                rxt = np.ascontiguousarray(bilinear_matrix(w, ow)[l : l + cols].T)
                y = fused_resize_affine(
                    x.reshape(n * channels, h, w),
                    ry,
                    rxt,
                    jnp.tile(scale, n),
                    jnp.tile(bias, n),
                    round_uint8=low.round_uint8,
                    interpret=interpret,
                )
                y = y.reshape(n, channels, rows, cols)
            else:
                y = _resize_affine_jnp(
                    x, oh, ow, (t, rows), (l, cols), scale, bias, low.round_uint8
                )
        else:
            y = x * scale[None, :, None, None] + bias[None, :, None, None]
        if low.out_meta.layout == "HWC":
            y = jnp.transpose(y, (0, 2, 3, 1))
        if low.out_meta.dtype == "uint8":
            y = jnp.clip(jnp.round(y), 0, 255).astype(jnp.uint8)
        elif low.out_meta.dtype != "float32":
            y = y.astype(low.out_meta.dtype)
        return y

    return stage


def _build_chain_stage(device_ops: Sequence[PreprocOp]) -> Callable[[Any], jnp.ndarray]:
    """Reference fallback: per-op apply_device fold, vmapped over the batch
    (still traced into the surrounding jitted program — one dispatch)."""
    ops = list(device_ops)

    def stage(batch):
        return jax.vmap(lambda im: P.apply_chain_device(ops, im))(batch)

    return stage


# ------------------------------------------------------------------ programs
@dataclasses.dataclass
class DevicePreprocProgram:
    """One compiled, donated, jitted device program: preproc suffix + DNN.

    Calling the program dispatches the whole batch once; ``dispatch_count``
    tracks Python-side dispatches so tests (and the engine) can assert the
    one-dispatch-per-batch contract.  ``build_seconds`` is the host-side
    lowering/wrapping cost paid at compile time; ``first_dispatch_seconds``
    is the wall time of dispatch #1 — jax.jit traces and XLA-compiles
    synchronously on first call, so this is the cold-start cost a request
    that misses the program cache actually experiences (telemetry tags the
    dispatch span with it).
    """

    fn: Callable[[Any], Any]  # jitted (batch,) -> model outputs
    backend: str  # "fused" | "reference"
    impl: str  # "pallas" | "jnp" | "chain" | "model-only"
    fused: bool  # True when the lowered resample+affine stage engaged
    stages: tuple[str, ...]
    key: tuple
    in_meta: TensorMeta
    out_meta: TensorMeta  # preprocessing output (the DNN's input)
    dispatch_count: int = 0
    build_seconds: float = 0.0
    first_dispatch_seconds: float | None = None
    # the staged batch size this program was compiled for (a ProgramSet
    # holds one program per bucketed size)
    batch_size: int = 0
    # True when the program's Pallas kernels run in interpret mode (part of
    # the cache key; False on a TPU)
    interpret: bool = False
    # invoked as listener(program, first_dispatch_seconds) when dispatch #1
    # pays the jit trace + XLA compile — the facade counts post-warmup
    # compiles and emits "compile" telemetry spans through it
    compile_listener: Callable[["DevicePreprocProgram", float], None] | None = None
    # True while ProgramSet.warm() is executing this program: the listener
    # can tell a startup warmup compile from a cold request-path compile
    _warming: bool = False
    # split-decode programs only: the scaled-IDCT resolution divisor and the
    # coefficient staging layout this program was compiled for
    coeff_factor: int | None = None
    coeff_layout: str | None = None
    # replica placement: None = process default; a jax.Device pins this
    # program instance to one replica's accelerator; a Sharding spans a
    # replica group (sharded-model mode) — staged batches are committed
    # there before dispatch, so XLA compiles/partitions per placement
    device: Any = None

    @property
    def dispatches_per_batch(self) -> int:
        return 1  # the whole suffix + DNN is one XLA program

    def __call__(self, batch):
        if self.dispatch_count == 0:
            t0 = time.perf_counter()
            out = self.fn(_place(batch, self.device))
            jax.block_until_ready(out)
            self.first_dispatch_seconds = time.perf_counter() - t0
            # counted only once it ran: a compile that raised leaves the
            # program unready, so a ProgramSet never serves it
            self.dispatch_count = 1
            if self.compile_listener is not None:
                self.compile_listener(self, self.first_dispatch_seconds)
            return out
        self.dispatch_count += 1
        return self.fn(_place(batch, self.device))

    def lower(self, batch):
        """Lower (without executing) — for HLO inspection tooling."""
        return self.fn.lower(batch)


def _jit(raw: Callable, donate: bool) -> Callable:
    # donation lets XLA reuse the staged batch's device allocation; the CPU
    # backend can't honor it and warns, so only donate on accelerators
    if donate and jax.default_backend() != "cpu":
        return jax.jit(raw, donate_argnums=(0,))
    return jax.jit(raw)


# ------------------------------------------------------------- program sets
def batch_buckets(batch_size: int) -> tuple[int, ...]:
    """Bucketed dispatch sizes for one configured max batch, ascending.

    Every power of two strictly below ``batch_size`` plus the exact size —
    the SHARK-Engine ``prefill_bs{N}`` idiom.  A partial batch of ``n``
    items dispatches through the smallest covering bucket instead of
    tracing a fresh program for every ragged tail shape.
    """
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    buckets = {int(batch_size)}
    b = 1
    while b < batch_size:
        buckets.add(b)
        b <<= 1
    return tuple(sorted(buckets))


@dataclasses.dataclass
class ProgramSet:
    """AOT program set for one (plan geometry, replica device) pair.

    One :class:`DevicePreprocProgram` per bucketed batch size, compiled
    ahead of time so steady-state serving never pays a jit trace or XLA
    compile: batch formation closes a ragged batch to :meth:`bucket_for`'s
    smallest covering bucket, dispatches the staged buffer's ``[:bucket]``
    prefix, and reads back only the real rows — padded lanes never reach a
    retired result.  ``warm()`` (``RuntimeConfig.warmup="full"``) executes
    each entry once on zeros, moving every first-dispatch compile into
    startup.

    ``require_ready=True`` makes :meth:`program_for` serve only *warmed*
    buckets until :meth:`warm` has covered the whole set — the background-
    warmer contract: a dispatcher never triggers a request-path compile
    while warmup is still running; a ragged batch falls forward to the
    smallest ready covering bucket (the warmer runs largest-first, so the
    full-size program is ready before serving starts and always covers).
    """

    programs: dict[int, DevicePreprocProgram]  # bucket -> program, ascending
    geometry: tuple = ()  # the plan's staging-geometry bin (shape, dtype)
    device: Any = None
    # serve only warmed buckets until warm() completes (background warmer)
    require_ready: bool = False

    def __post_init__(self):
        if not self.programs:
            raise ValueError("ProgramSet needs at least one program")
        self.programs = dict(sorted(self.programs.items()))
        self._warm_done = not self.require_ready

    @property
    def buckets(self) -> tuple[int, ...]:
        return tuple(self.programs)

    @property
    def max_batch(self) -> int:
        return next(reversed(self.programs))

    def bucket_for(self, n: int) -> int | None:
        """Smallest bucket covering ``n`` rows (None when n exceeds the set)."""
        for b in self.programs:
            if b >= n:
                return b
        return None

    @staticmethod
    def _is_ready(prog: DevicePreprocProgram) -> bool:
        """Dispatched at least once and not mid-warm — no compile risk."""
        return prog.dispatch_count > 0 and not prog._warming

    @property
    def fully_warm(self) -> bool:
        """True once every bucket is safe to dispatch without compiling."""
        return self._warm_done or all(self._is_ready(p) for p in self.programs.values())

    def program_for(self, n: int) -> tuple[DevicePreprocProgram, int] | None:
        """(program, bucket) dispatching ``n`` staged rows, or None.

        Under ``require_ready`` (background warmup still running) only
        warmed buckets are served: the smallest *ready* bucket covering
        ``n``.  None means no ready bucket covers — the caller falls back
        to its plain per-replica program.
        """
        if self._warm_done:
            b = self.bucket_for(n)
            if b is None:
                return None
            return self.programs[b], b
        for b, prog in self.programs.items():
            if b >= n and self._is_ready(prog):
                return prog, b
        return None

    def keys(self) -> tuple:
        """Program-cache keys of every entry (for pin/unpin bookkeeping)."""
        return tuple(p.key for p in self.programs.values())

    def warm(self, buckets: tuple[int, ...] | None = None) -> int:
        """Execute each not-yet-dispatched entry once on zeros.

        The first dispatch of a jitted program traces and XLA-compiles
        synchronously; running it here (blocking until ready) is what turns
        "compiled at startup" into "never compiles on the request path".
        ``buckets`` restricts the pass (the facade warms the full-size
        bucket inline at startup and hands the rest to the background
        warmer, largest-first).  Returns the number of programs warmed.
        """
        warmed = 0
        targets = (
            self.programs.items()
            if buckets is None
            else [(b, self.programs[b]) for b in buckets if b in self.programs]
        )
        for bucket, prog in targets:
            if prog.dispatch_count:
                continue
            zeros = np.zeros(
                (bucket, *prog.in_meta.shape), np.dtype(prog.in_meta.dtype)
            )
            prog._warming = True
            try:
                jax.block_until_ready(prog(zeros))
            finally:
                prog._warming = False
            warmed += 1
        if all(p.dispatch_count for p in self.programs.values()):
            self._warm_done = True
        return warmed


def program_cache_key(
    device_ops: Sequence[PreprocOp],
    in_meta: TensorMeta,
    batch_size: int,
    backend: str,
    impl: str,
    model_key: str = "",
    interpret: bool = True,
    donate: bool = True,
    device: Any = None,
) -> tuple:
    """Compile-cache identity: op specs + input meta + batch + backend +
    the compile-mode flags that change the emitted program + the replica
    device placement (a mesh holds one program instance per replica)."""
    return (
        tuple(op.spec() for op in device_ops),
        in_meta.shape,
        in_meta.dtype,
        in_meta.layout,
        batch_size,
        backend,
        impl,
        model_key,
        interpret,
        donate,
        device_cache_key(device),
    )


def compile_device_program(
    device_ops: Sequence[PreprocOp],
    in_meta: TensorMeta,
    model_fn: Callable,
    batch_size: int,
    backend: str = "fused",
    impl: str = "auto",
    interpret: bool | None = None,
    donate: bool = True,
    model_key: str = "",
    cache: MutableMapping[tuple, "DevicePreprocProgram"] | None = None,
    device: Any = None,
) -> DevicePreprocProgram:
    """Lower ``device_ops`` + ``model_fn`` into one jitted device program.

    ``backend='fused'`` engages the lowering (Pallas or host-matched jnp per
    ``impl``); ``'reference'`` keeps the per-op apply_device chain.  Either
    way the result is ONE program / one dispatch per batch; the backends
    differ in how the preprocessing *inside* it is structured.  ``cache``
    (keyed by :func:`program_cache_key`) makes recompiles after placement
    moves free when the split returns to a previously-seen point.
    ``device`` pins the program to one replica's accelerator (or, given a
    Sharding, spans a replica group) — each placement is its own cache
    entry, so a mesh gets one program instance per replica.
    """
    if backend not in ("fused", "reference"):
        raise ValueError(f"device_backend must be 'fused' or 'reference', got {backend!r}")
    impl = resolve_impl(impl) if backend == "fused" else "chain"
    interpret = resolve_interpret(interpret)
    key = program_cache_key(
        device_ops, in_meta, batch_size, backend, impl, model_key, interpret, donate,
        device,
    )
    if cache is not None and key in cache:
        return cache[key]

    t_build = time.perf_counter()
    low = lower_device_ops(device_ops, in_meta) if backend == "fused" else None
    if low is not None:
        stage = build_fused_stage(low, impl, interpret)
        fused, stages, out_meta = True, low.stages, low.out_meta
    elif device_ops:
        stage = _build_chain_stage(device_ops)
        impl, fused = "chain", False
        stages = tuple(op.name for op in device_ops)
        out_meta = P.chain_out_meta(list(device_ops), in_meta)
    else:
        stage, impl, fused, stages, out_meta = None, "model-only", False, (), in_meta

    def raw(batch):
        x = stage(batch) if stage is not None else jnp.asarray(batch)
        return model_fn(x)

    program = DevicePreprocProgram(
        fn=_jit(raw, donate),
        backend=backend,
        impl=impl,
        fused=fused,
        stages=stages,
        key=key,
        in_meta=in_meta,
        out_meta=out_meta,
        device=device,
        batch_size=batch_size,
        interpret=interpret,
        build_seconds=time.perf_counter() - t_build,
    )
    if cache is not None:
        cache[key] = program
    return program


# ------------------------------------------------- split-decode (DCT) program
_YCBCR_TO_RGB = np.array(
    # rows: R, G, B; cols: Y, Cb-128, Cr-128 (JFIF, matches dct.ycbcr_to_rgb)
    [[1.0, 0.0, 1.402], [1.0, -0.344136, -0.714136], [1.0, 1.772, 0.0]],
    dtype=np.float32,
)


def compile_coeff_program(
    header: Any,  # jpeg.JpegHeader from a calibration sample
    device_ops: Sequence[PreprocOp],
    model_fn: Callable,
    batch_size: int,
    factor: int = 1,  # scaled-IDCT resolution divisor: 1 full, 2 half, 4 quarter
    layout: str = "padded",  # coefficient staging layout ("padded" | "packed")
    impl: str = "auto",
    interpret: bool | None = None,
    donate: bool = True,
    model_key: str = "",
    cache: MutableMapping[tuple, "DevicePreprocProgram"] | None = None,
    device: Any = None,
) -> DevicePreprocProgram:
    """Split-decode program: quantized DCT coefficients in, predictions out.

    The host stops after the entropy stage (``jpeg.decode_to_coefficients``)
    and stages one int16 zigzag-coefficient tensor per item
    (``jpeg.stage_coefficients``: the padded luma-grid layout or the packed
    per-plane layout — 4:2:0's quarter-density chroma fits either way);
    this program runs the dense remainder on the accelerator in ONE
    dispatch: unzigzag -> fused dequantize + (scaled) IDCT
    (``kernels/idct`` MXU kernel at ``point = 8 // factor``, one call per
    quant table) -> unblockify -> 2x2 nearest chroma upsample (4:2:0) ->
    JFIF color conversion -> the fused resize/normalize stage -> DNN.
    ``factor > 1`` decodes straight to reduced resolution (paper §6.4 /
    libjpeg draft): the pixel grid entering the preprocessing chain is
    ``(ceil(h/factor), ceil(w/factor))``, so a plan that immediately
    downsamples never pays for full-resolution pixels at all.
    """
    from repro.preprocessing import dct as dct_np
    from repro.preprocessing import jpeg as jpeg_mod

    if header.channels != 3:
        raise ValueError("split-decode program supports 3-channel streams")
    if factor not in (1, 2, 4):
        raise ValueError(f"scaled-IDCT factor must be 1, 2 or 4, got {factor}")
    if layout not in ("padded", "packed"):
        raise ValueError(f"layout must be 'padded' or 'packed', got {layout!r}")
    interpret = resolve_interpret(interpret)
    impl = resolve_impl(impl)
    n_br, n_bc = header.n_br, header.n_bc
    cbr, cbc = jpeg_mod.chroma_grid(header)
    subsample = bool(header.subsample)
    point = 8 // factor
    hs = jpeg_mod.scaled_size(header.height, factor)
    ws = jpeg_mod.scaled_size(header.width, factor)
    qtables = jpeg_mod._qtables(header.quality, header.channels)
    pixel_meta = TensorMeta((hs, ws, 3), "uint8", "HWC")
    in_shape = jpeg_mod.staged_coeff_shape(header, layout)
    key = (
        ("CoeffDecode", header.quality, n_br, n_bc, header.height, header.width,
         subsample, factor, layout),
        program_cache_key(
            device_ops, pixel_meta, batch_size, "fused", impl, model_key, interpret,
            donate, device,
        ),
    )
    if cache is not None and key in cache:
        return cache[key]

    t_build = time.perf_counter()
    unzigzag = np.asarray(dct_np.UNZIGZAG)
    rgb_mat = jnp.asarray(_YCBCR_TO_RGB)
    low = lower_device_ops(device_ops, pixel_meta)
    if low is not None:
        preproc = build_fused_stage(low, impl, interpret, input_planar=True)
        fused, out_meta = True, low.out_meta
        pre_stages = low.stages
    else:
        chain = _build_chain_stage(device_ops)
        # the chain fallback must see the same uint8 pixel grid the pixel
        # path stages (ops.Resize only re-quantizes uint8 inputs): cast the
        # already clip/rounded RGB down before applying the per-op chain
        preproc = lambda x: chain(  # noqa: E731
            jnp.transpose(x, (0, 2, 3, 1)).astype(jnp.uint8)
        )
        fused = False
        out_meta = P.chain_out_meta(list(device_ops), pixel_meta)
        pre_stages = tuple(op.name for op in device_ops)

    n_luma = n_br * n_bc
    n_chroma = cbr * cbc

    def raw(batch):  # one staged int16 zigzag-coefficient tensor per item
        n = batch.shape[0]
        zz = jnp.asarray(batch)
        if layout == "packed":  # (N, n_luma + 2*n_chroma, 64)
            luma_zz = zz[:, :n_luma]
            chroma_zz = zz[:, n_luma:]
        else:  # (N, 3, n_br, n_bc, 64); 4:2:0 chroma occupies the top-left
            luma_zz = zz[:, 0].reshape(n, n_luma, 64)
            chroma_zz = zz[:, 1:, :cbr, :cbc].reshape(n, 2 * n_chroma, 64)
        # one fused dequant+(scaled-)IDCT kernel call per quant table
        luma = dequant_idct(
            luma_zz[..., unzigzag].reshape(-1, 8, 8),
            qtables[0], point=point, interpret=interpret,
        )
        chroma = dequant_idct(
            chroma_zz[..., unzigzag].reshape(-1, 8, 8),
            qtables[1], point=point, interpret=interpret,
        )
        y = (
            luma.reshape(n, n_br, n_bc, point, point)
            .transpose(0, 1, 3, 2, 4)
            .reshape(n, n_br * point, n_bc * point)
        )
        c = (
            chroma.reshape(n, 2, cbr, cbc, point, point)
            .transpose(0, 1, 2, 4, 3, 5)
            .reshape(n, 2, cbr * point, cbc * point)
        )
        if subsample:  # 2x2 nearest upsample back to the (scaled) luma grid
            c = jnp.repeat(jnp.repeat(c, 2, axis=2), 2, axis=3)
        ycc = jnp.concatenate([y[:, None, :hs, :ws], c[:, :, :hs, :ws]], axis=1) + 128.0
        # HIGHEST: a bf16 pass (the TPU's f32 default) moves the color
        # conversion by up to a pixel level before the round below
        rgb = jnp.einsum(
            "rc,nchw->nrhw",
            rgb_mat,
            ycc - jnp.asarray([0.0, 128.0, 128.0])[:, None, None],
            precision=jax.lax.Precision.HIGHEST,
        )
        rgb = jnp.clip(jnp.round(rgb), 0.0, 255.0)  # the decoded uint8 pixel grid
        return model_fn(preproc(rgb))

    idct_stage = "dequant_idct[mxu]" if point == 8 else f"dequant_idct[mxu]/{point}pt"
    decode_stages = ("unzigzag", idct_stage, "unblockify")
    if subsample:
        decode_stages += ("chroma_upsample[2x2]",)
    program = DevicePreprocProgram(
        fn=_jit(raw, donate),
        backend="fused",
        impl=impl,
        fused=fused,
        stages=decode_stages + ("ycbcr->rgb",) + pre_stages,
        key=key,
        in_meta=TensorMeta(in_shape, "int16", "CHW"),
        out_meta=out_meta,
        coeff_factor=factor,
        coeff_layout=layout,
        device=device,
        batch_size=batch_size,
        interpret=interpret,
        build_seconds=time.perf_counter() - t_build,
    )
    if cache is not None:
        cache[key] = program
    return program

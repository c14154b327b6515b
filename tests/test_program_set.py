"""AOT program sets + double-buffered dispatch (PR 8).

Covers the warmup surface end to end: bucket enumeration, tail-batch →
smallest-covering-bucket mapping, padded lanes never leaking into retired
outputs, bucketed ≡ unbucketed results, warmup=full leaving zero
post-startup compiles, program-cache pinning vs LRU churn, the keyed
dispatch-overhead memo, the bounded transfer pool, and double-buffered vs
synchronous engine equivalence.
"""

import threading
import time

import jax
import numpy as np
import pytest

from conftest import smooth_image
from repro.core import device_compiler
from repro.core.device_compiler import (
    ProgramCache,
    ProgramSet,
    batch_buckets,
    measure_dispatch_overhead,
)
from repro.core.engine import PipelinedEngine
from repro.core.planner import ModelSpec
from repro.preprocessing.formats import ImageFormat, StoredImage
from repro.runtime import (
    MemoryConfig,
    RuntimeConfig,
    SmolRuntime,
    TelemetryConfig,
    TransferPool,
)

INPUT = 32

FMT_FULL = ImageFormat("jpeg", None, 95)
FMT_THUMB = ImageFormat("jpeg", 48, 75)
FORMATS = [FMT_FULL, FMT_THUMB]


# ------------------------------------------------------------ bucket algebra
def test_batch_buckets_powers_of_two_plus_exact():
    assert batch_buckets(32) == (1, 2, 4, 8, 16, 32)
    assert batch_buckets(12) == (1, 2, 4, 8, 12)
    assert batch_buckets(1) == (1,)
    with pytest.raises(ValueError):
        batch_buckets(0)


class _FakeProg:
    """Stand-in program: ProgramSet's bucket algebra never inspects values."""

    def __init__(self, bucket):
        self.key = ("fake", bucket)
        self.dispatch_count = 1  # pre-warmed: warm() skips it


def _fake_set(buckets=(1, 2, 4, 8)):
    return ProgramSet(programs={b: _FakeProg(b) for b in buckets})


def test_program_set_tail_maps_to_smallest_covering_bucket():
    ps = _fake_set()
    assert ps.buckets == (1, 2, 4, 8)
    assert ps.max_batch == 8
    for n, expect in [(1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (7, 8), (8, 8)]:
        assert ps.bucket_for(n) == expect
        prog, bucket = ps.program_for(n)
        assert bucket == expect and prog.key == ("fake", expect)
    assert ps.bucket_for(9) is None and ps.program_for(9) is None


def test_program_set_rejects_empty_and_sorts():
    with pytest.raises(ValueError):
        ProgramSet(programs={})
    ps = ProgramSet(programs={4: _FakeProg(4), 1: _FakeProg(1)})
    assert ps.buckets == (1, 4)  # insertion order normalised ascending


# -------------------------------------------------------- program-cache pins
def test_program_cache_pin_survives_lru_churn():
    cache = ProgramCache(max_entries=2)
    cache["keep"] = "A"
    cache.pin("keep")
    for i in range(10):  # churn well past the bound
        cache[f"churn{i}"] = i
    assert "keep" in cache
    assert cache.stats().pinned == 1
    assert cache.stats().entries == 2
    cache.unpin("keep")
    assert cache.stats().pinned == 0
    cache["one-more"] = "B"  # unpinned now: next insert evicts it (oldest)
    assert "keep" not in cache


def test_program_cache_pin_refcounts_and_errors():
    cache = ProgramCache(max_entries=4)
    with pytest.raises(KeyError):
        cache.pin("absent")
    cache["k"] = 1
    cache.pin("k")
    cache.pin("k")
    cache.unpin("k")
    assert cache.stats().pinned == 1  # second ref still holds
    cache.unpin("k")
    assert cache.stats().pinned == 0
    cache.unpin("k")  # over-unpin is a tolerated no-op


def test_program_cache_all_pinned_grows_past_bound():
    cache = ProgramCache(max_entries=2)
    for i in range(4):
        cache[i] = i
        cache.pin(i)
    # nothing evictable: the cache holds above its bound rather than
    # silently undoing warmup
    assert cache.stats().entries == 4
    assert all(i in cache for i in range(4))


# ------------------------------------------------- dispatch-overhead keying
def test_measure_dispatch_overhead_keyed_by_backend_and_device_kind():
    device_compiler._MEASURED_DISPATCH_S.clear()
    v1 = measure_dispatch_overhead(iters=4)
    key = device_compiler._dispatch_memo_key()
    assert v1 > 0
    assert device_compiler._MEASURED_DISPATCH_S == {key: v1}
    assert measure_dispatch_overhead(iters=4) == v1  # memo hit, same key
    # a different (backend, kind) key must NOT alias this device's number
    device_compiler._MEASURED_DISPATCH_S[("other", "virt")] = 123.0
    assert measure_dispatch_overhead(iters=4) == v1
    device_compiler._MEASURED_DISPATCH_S.pop(("other", "virt"))


# ------------------------------------------------------------- transfer pool
def test_transfer_pool_bounds_concurrent_leases():
    tp = TransferPool(2, buffers=None)
    a = tp.lease((4,), np.float32)
    b = tp.lease((4,), np.float32)
    assert tp.lease((4,), np.float32, timeout=0.05) is None  # both slots held
    s = tp.stats()
    assert s.slots == 2 and s.leases_active == 2 and s.blocked_seconds > 0
    b.release()
    c = tp.lease((4,), np.float32, timeout=1.0)
    assert c is not None
    a.release()
    c.release()
    assert tp.stats().leases_active == 0
    with pytest.raises(RuntimeError):
        c.release()  # strict release-once


def test_transfer_pool_blocked_lease_wakes_on_release():
    tp = TransferPool(1, buffers=None)
    first = tp.lease((8,), np.float32)
    got = []

    def waiter():
        lease = tp.lease((8,), np.float32, timeout=5.0)
        got.append(lease)
        lease.release()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    first.release()
    t.join(timeout=5.0)
    assert got and got[0] is not None


def test_transfer_pool_reuses_backing_buffer_pool():
    cfg = MemoryConfig(pooling=True, bucket_min_bytes=256)
    tp = cfg.build_transfer_pool(default_slots=3)
    assert tp.slots == 3
    with tp.lease((3, 8, 8), np.float32) as arr:
        arr[:] = 1.0
    with tp.lease((3, 8, 8), np.float32) as arr2:
        pass
    ps = tp.stats().pool
    assert ps is not None and ps.buffers_allocated == 1  # round-tripped
    assert MemoryConfig(transfer_slots=5).build_transfer_pool(3).slots == 5
    with pytest.raises(ValueError):
        MemoryConfig(transfer_slots=-1)


# ------------------------------------------- engine double-buffered dispatch
def _engine(double_buffer, stage_delay=0.0):
    def host_fn(item):
        return np.full((3, 8, 8), float(item), np.float32)

    def device_fn(batch):
        if stage_delay:
            time.sleep(stage_delay)
        return batch.sum(axis=(1, 2, 3))

    return PipelinedEngine(
        host_fn,
        device_fn,
        (3, 8, 8),
        np.float32,
        batch_size=4,
        num_workers=2,
        jit=False,
        memory=MemoryConfig(pooling=True, bucket_min_bytes=256),
        double_buffer=double_buffer,
    )

def test_engine_double_buffered_matches_sync_outputs():
    items = list(range(30))  # ragged tail: 30 = 7*4 + 2
    out_db, stats_db = _engine(True).run(items)
    out_sync, stats_sync = _engine(False).run(items)
    assert len(out_db) == len(out_sync) == 30
    for a, b in zip(out_db, out_sync):
        np.testing.assert_allclose(a, b)
    assert stats_db.num_items == stats_sync.num_items == 30


def test_engine_double_buffered_zero_leaked_leases():
    eng = _engine(True)
    _, _ = eng.run(list(range(50)), return_outputs=False)
    ts = eng.transfer_stats()
    assert ts.leases_active == 0
    assert ts.leases_issued >= 13  # ceil(50/4) batches each leased a slot


def test_engine_double_buffered_propagates_device_errors():
    def host_fn(item):
        return np.full((3, 8, 8), float(item), np.float32)

    calls = []

    def device_fn(batch):
        calls.append(len(batch))
        if len(calls) == 2:
            raise ValueError("device boom")
        return batch.sum(axis=(1, 2, 3))

    eng = PipelinedEngine(
        host_fn, device_fn, (3, 8, 8), np.float32,
        batch_size=4, num_workers=2, jit=False, double_buffer=True,
    )
    with pytest.raises(ValueError, match="device boom"):
        eng.run(list(range(40)))
    assert eng.transfer_stats().leases_active == 0  # error path released all


# ---------------------------------------------------- runtime warmup (E2E)
@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(7)
    return [
        StoredImage.from_array(smooth_image(rng, 80, 80), FORMATS) for _ in range(11)
    ]


def _linear_model(seed=0, classes=7):
    w = np.asarray(
        jax.random.normal(jax.random.PRNGKey(seed), (3 * INPUT * INPUT, classes)) * 0.02
    )

    def fn(x):
        return x.reshape(x.shape[0], -1) @ w

    return fn


def _runtime(corpus, **cfg_kwargs):
    cfg_kwargs.setdefault("telemetry", TelemetryConfig(spans=True))
    cfg = RuntimeConfig(batch_size=4, num_workers=2, **cfg_kwargs)
    models = [
        ModelSpec(
            "fast", INPUT, exec_throughput=10_000.0,
            accuracy_by_format={FMT_FULL.key: 0.95, FMT_THUMB.key: 0.70},
        )
    ]
    return SmolRuntime(
        models,
        FORMATS,
        {"fast": _linear_model(0)},
        calibration=corpus[:3],
        config=cfg,
        decode_time=lambda fmt: 1e-4 if fmt.short_side else 2e-3,
    )


def test_warmup_full_compiles_program_set_at_startup(corpus):
    rt = _runtime(corpus, warmup="full")
    compiled = rt.compile()
    assert len(compiled.program_sets) == 1
    ps = compiled.program_sets[0]
    assert ps.buckets == batch_buckets(4) == (1, 2, 4)
    # the largest bucket warms inline (serving can start on it at once);
    # the rest drain through the background warmer
    assert ps.programs[ps.max_batch].dispatch_count >= 1
    assert rt.wait_warm(timeout=60.0)
    assert ps.fully_warm
    # every entry executed once during warm(): no first-dispatch left
    assert all(p.dispatch_count >= 1 for p in ps.programs.values())
    assert rt.stats().program_cache.pinned == len(ps.buckets)
    # warmup compiles are observable but don't count as post-warmup
    assert rt.program_compile_seconds_total > 0
    assert rt.programs_compiled_post_warmup == 0


def test_warmup_full_serving_never_compiles_post_startup(corpus):
    rt = _runtime(corpus, warmup="full")
    rt.start_serving()
    try:
        for item in corpus:  # 11 items: full batches + ragged tails
            rt.submit(item)
        rt.flush()
        done = rt.drain()
    finally:
        rt.stop_serving()
    assert len(done) == 11 and not any(r.error for r in done)
    # the acceptance invariant: zero request-path jit compiles, asserted
    # via the facade counter fed by DevicePreprocProgram build/compile
    # accounting
    assert rt.programs_compiled_post_warmup == 0
    ps = rt.compile().program_sets[0]
    assert all(p.build_seconds >= 0 for p in ps.programs.values())
    text = rt.metrics_text()
    assert "smol_programs_compiled_post_warmup_total 0" in text
    assert "smol_program_compile_seconds_total" in text


def test_warmup_bucketed_results_match_unbucketed(corpus):
    # same corpus through warmup=full (bucketed ragged dispatch) and
    # warmup=off (full-buffer dispatch): identical outputs per request,
    # i.e. padded bucket lanes never leak into retired results
    out_warm, _ = _runtime(corpus, warmup="full").run(corpus)
    out_cold, _ = _runtime(corpus, warmup="off").run(corpus)
    assert len(out_warm) == len(out_cold) == len(corpus)
    for a, b in zip(out_warm, out_cold):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_warmup_serving_tail_batch_uses_covering_bucket(corpus):
    rt = _runtime(corpus, warmup="full", max_wait_ms=200.0)
    rt.start_serving()
    rt.wait_warm()  # all buckets ready: tails use the exact covering bucket
    try:
        for item in corpus[:3]:  # < batch_size: a ragged tail batch
            rt.submit(item)
        rt.flush()
        done = rt.drain()
    finally:
        rt.stop_serving()
    assert len(done) == 3 and not any(r.error for r in done)
    batch_spans = [s for s in rt.telemetry.spans() if s.kind == "batch" and s.name == "batch"]
    assert batch_spans, "serving should emit batch spans"
    ps = rt.compile().program_sets[0]
    for s in batch_spans:
        bucket = s.args.get("bucket")
        if bucket is not None:  # bucketed dispatch: smallest covering bucket
            assert bucket == ps.bucket_for(s.args["size"])


def test_background_warm_failure_is_raised_and_counted(corpus):
    # a bucket whose compile raises in the background warmer must not be
    # swallowed: wait_warm() re-raises it and stats() counts it
    linear = _linear_model(0)

    def model_fn(x):
        if x.shape[0] < 4:  # trace-time failure: only the small buckets
            raise ValueError("bucket compile boom")
        return linear(x)

    rt = _runtime(corpus, warmup="full")
    rt.model_fns["fast"] = model_fn
    compiled = rt.compile()  # the full-size bucket warms inline and works
    with pytest.raises(RuntimeError, match="bucket compile boom"):
        rt.wait_warm(timeout=60.0)
    assert rt.stats().warmup_failures == 1
    ps = compiled.program_sets[0]
    assert not ps.fully_warm
    # dispatch still falls back to the warm full-size bucket
    prog, bucket = ps.program_for(1)
    assert bucket == ps.max_batch


def test_background_warm_failure_is_raised_once(corpus):
    # wait_warm() raises a failure once; a later warm that succeeds (here a
    # forced recompile of the same plan) does not raise it again, while
    # stats() keeps the running count
    linear = _linear_model(0)
    failing = [True]

    def model_fn(x):
        if failing[0] and x.shape[0] < 4:
            raise ValueError("bucket compile boom")
        return linear(x)

    rt = _runtime(corpus, warmup="full")
    rt.model_fns["fast"] = model_fn
    rt.compile()
    with pytest.raises(RuntimeError, match="bucket compile boom"):
        rt.wait_warm(timeout=60.0)
    failing[0] = False
    compiled = rt.compile(force=True)
    assert rt.wait_warm(timeout=60.0)
    assert compiled.program_sets[0].fully_warm
    assert rt.stats().warmup_failures == 1


def test_warmup_off_is_legacy_lazy_compile(corpus):
    rt = _runtime(corpus, warmup="off")
    compiled = rt.compile()
    assert compiled.program_sets == ()
    assert rt.stats().program_cache.pinned == 0


def test_warmup_lazy_builds_but_does_not_execute(corpus):
    rt = _runtime(corpus, warmup="lazy")
    compiled = rt.compile()
    ps = compiled.program_sets[0]
    assert ps.buckets == (1, 2, 4)
    # lazy: programs staged + pinned but not yet dispatched
    assert all(p.dispatch_count == 0 for p in ps.programs.values())


def test_warmup_warns_when_cache_smaller_than_warm_set(corpus):
    rt = _runtime(corpus, warmup="lazy", program_cache_entries=2)
    with pytest.warns(RuntimeWarning, match="program_cache_entries"):
        rt.compile()
    # pinned warmup entries held the cache above its configured bound
    # instead of silently dropping warm programs
    assert rt.stats().program_cache.entries >= 3


def test_compile_spans_appear_in_trace(tmp_path, corpus):
    rt = _runtime(corpus, warmup="full")
    rt.compile()
    assert rt.wait_warm(timeout=60.0)  # background buckets emit spans too
    spans = rt.telemetry.spans()
    compile_spans = [s for s in spans if s.kind == "compile"]
    assert len(compile_spans) == 3  # one per bucket
    import json

    p = tmp_path / "trace.json"
    assert rt.dump_trace(str(p)) > 0
    events = json.loads(p.read_text())
    if isinstance(events, dict):
        events = events["traceEvents"]
    procs = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert "compiler" in procs

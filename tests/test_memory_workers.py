"""Memory & worker subsystem (paper §6.1(c)): buffer-lease discipline,
arena reuse, byte-budget admission, the work-stealing host pool, and their
integration into the pipelined engine and the request scheduler."""

import threading
import time

import numpy as np
import pytest

from repro.core.engine import PipelinedEngine
from repro.runtime import (
    BufferPool,
    FrameArena,
    MemoryBudget,
    MemoryConfig,
    RequestScheduler,
    SchedulerSaturated,
    StageMeasurement,
    WorkerPool,
    WorkerRecalibrator,
)


def _data_ptr(arr: np.ndarray) -> int:
    return arr.__array_interface__["data"][0]


# ------------------------------------------------------------------ BufferPool
def test_pool_lease_release_reuse():
    pool = BufferPool(bucket_min_bytes=64)
    lease = pool.lease((4, 4), np.float32)
    assert lease.array.shape == (4, 4) and lease.array.dtype == np.float32
    lease.release()
    again = pool.lease((4, 4), np.float32)
    s = pool.stats()
    assert s.buffers_allocated == 1  # second lease reused the first buffer
    assert s.leases_issued == 2 and s.leases_reused == 1
    assert s.leases_active == 1
    again.release()
    assert pool.stats().bytes_in_use == 0


def test_pool_never_double_issues_live_buffers():
    pool = BufferPool(bucket_min_bytes=64, max_buffers_per_bucket=16)
    leases = [pool.lease((8,), np.float32) for _ in range(8)]
    ptrs = {_data_ptr(lease.array) for lease in leases}
    assert len(ptrs) == 8, "two live leases share a backing buffer"
    assert pool.stats().leases_active == 8
    for lease in leases:
        lease.release()
    # a full re-lease cycle reuses every buffer and still never aliases
    leases = [pool.lease((8,), np.float32) for _ in range(8)]
    assert len({_data_ptr(lease.array) for lease in leases}) == 8
    s = pool.stats()
    assert s.buffers_allocated == 8 and s.leases_reused == 8
    for lease in leases:
        lease.release()


def test_pool_double_release_raises():
    pool = BufferPool()
    lease = pool.lease((2, 2), np.uint8)
    lease.release()
    with pytest.raises(RuntimeError, match="released twice"):
        lease.release()


def test_pool_hoard_cap_returns_buffers_to_allocator():
    pool = BufferPool(bucket_min_bytes=64, max_buffers_per_bucket=2)
    leases = [pool.lease((16,), np.float32) for _ in range(4)]
    assert pool.stats().buffers_allocated == 4
    for lease in leases:
        lease.release()
    assert pool.stats().buffers_allocated == 2  # cap: 2 hoarded, 2 freed


def test_pool_buckets_by_size():
    pool = BufferPool(bucket_min_bytes=64)
    small = pool.lease((4,), np.float32)  # 16B -> 64B bucket
    large = pool.lease((100,), np.float32)  # 400B -> 512B bucket
    small.release()
    large.release()
    # a small request must not be satisfied from the large bucket's buffer
    small2 = pool.lease((4,), np.float32)
    assert small2.array.nbytes == 16
    assert pool.stats().buffers_allocated == 2
    small2.release()


# ------------------------------------------------------------------ FrameArena
def test_arena_zero_net_allocation_growth_across_100_batches():
    arena = FrameArena(block_bytes=1 << 14)
    rng = np.random.default_rng(0)
    sizes = rng.integers(100, 2000, size=16)
    baseline = None
    for batch in range(100):
        slices = [arena.alloc(int(s)) for s in sizes]
        for sl in slices:
            sl.array[:8] = batch % 256  # touch the memory
            sl.release()
        if batch == 1:
            baseline = arena.stats().blocks_allocated
    final = arena.stats()
    assert final.blocks_allocated == baseline, "arena grew under steady-state reuse"
    assert final.bytes_in_use == 0
    assert final.high_water_bytes <= final.blocks_allocated * (1 << 14) + max(sizes)


def test_arena_oversize_allocation_freed_on_release():
    arena = FrameArena(block_bytes=1024)
    sl = arena.alloc(5000)  # bigger than a block: dedicated allocation
    assert sl.array.nbytes == 5000
    blocks_with_oversize = arena.stats().blocks_allocated
    sl.release()
    assert arena.stats().blocks_allocated == blocks_with_oversize - 1


def test_arena_double_release_raises():
    arena = FrameArena()
    sl = arena.alloc(128)
    sl.release()
    with pytest.raises(RuntimeError, match="released twice"):
        sl.release()


# ---------------------------------------------------------------- MemoryBudget
def test_budget_blocks_admission_at_byte_cap():
    budget = MemoryBudget(100)
    assert budget.try_admit(60)
    assert not budget.try_admit(60)  # 120 > 100: shed
    assert budget.stats().rejected == 1

    admitted_late = threading.Event()

    def blocked_admit():
        assert budget.admit(60, timeout=5.0)
        admitted_late.set()

    t = threading.Thread(target=blocked_admit, daemon=True)
    t.start()
    time.sleep(0.05)
    assert not admitted_late.is_set(), "admit() must block while over the cap"
    budget.release(60)
    t.join(timeout=5.0)
    assert admitted_late.is_set()
    assert budget.in_flight_bytes == 60
    budget.release(60)
    assert budget.stats().high_water_bytes <= 100


def test_budget_admit_timeout_and_oversize_degrades_to_serial():
    budget = MemoryBudget(100)
    assert budget.try_admit(100)
    assert not budget.admit(1, timeout=0.05)  # full: times out
    budget.release(100)
    # an item larger than the whole budget is admitted alone, not deadlocked
    assert budget.admit(500, timeout=0.05)
    assert not budget.try_admit(1)
    budget.release(500)


def test_budget_over_release_raises():
    budget = MemoryBudget(10)
    with pytest.raises(RuntimeError, match="more bytes than admitted"):
        budget.release(1)


# ------------------------------------------------------------------ WorkerPool
def _square(item):
    return np.full((4,), float(item) ** 2, np.float32)


def test_worker_pool_matches_single_threaded_outputs():
    items = list(range(37))
    expected = [_square(i) for i in items]
    for workers in (1, 4):
        got, busy = WorkerPool(_square, num_workers=workers, queue_depth=8).map(items)
        assert busy >= 0.0
        for g, e in zip(got, expected):
            np.testing.assert_array_equal(g, e)


def test_worker_pool_steals_from_slow_shard():
    # worker 0's entire round-robin shard is slow; stealing spreads it
    def host_fn(item):
        if item % 4 == 0:
            time.sleep(0.06)
        return np.full((2,), float(item), np.float32)

    items = list(range(32))  # 8 slow items = 0.48s if one worker kept them all
    t0 = time.perf_counter()
    got, _ = WorkerPool(host_fn, num_workers=4, queue_depth=64).map(items)
    wall = time.perf_counter() - t0
    assert all(got[i][0] == i for i in items)
    assert wall < 0.4, f"no work stealing: slow shard serialized ({wall:.2f}s)"


def test_worker_pool_per_worker_state():
    made = []

    def factory():
        state = {"id": len(made), "calls": 0}
        made.append(state)
        return state

    seen_states = {}
    lock = threading.Lock()

    def host_fn(item, state):
        state["calls"] += 1
        with lock:
            seen_states[item] = state["id"]
        return np.zeros(1, np.float32)

    pool = WorkerPool(host_fn, num_workers=3, worker_state_factory=factory)
    pool.map(list(range(30)))
    assert len(made) == 3  # exactly one state per worker thread
    assert sum(s["calls"] for s in made) == 30
    assert set(seen_states.values()) <= {0, 1, 2}


def test_worker_pool_propagates_errors():
    def host_fn(item):
        if item == 5:
            raise ValueError("bad item 5")
        return np.zeros(1, np.float32)

    with pytest.raises(ValueError, match="bad item 5"):
        WorkerPool(host_fn, num_workers=2).map(list(range(10)))


def test_worker_pool_respects_budget():
    item_nbytes = 64
    budget = MemoryBudget(2 * item_nbytes)  # at most 2 decoded items in flight

    def host_fn(item):
        return np.zeros(16, np.float32)

    pool = WorkerPool(host_fn, num_workers=4, budget=budget, item_nbytes=item_nbytes)
    out, _ = pool.map(list(range(20)))
    assert len(out) == 20
    s = budget.stats()
    assert s.in_flight_bytes == 0
    assert s.high_water_bytes <= budget.max_bytes


# ---------------------------------------------------------- engine integration
def _engine(pooling: bool, budget_bytes=None, **kw):
    def host_fn(item):
        return np.full((3, 8, 8), float(item), np.float32)

    def device_fn(batch):
        return batch.sum(axis=(1, 2, 3), keepdims=False)

    return PipelinedEngine(
        host_fn,
        device_fn,
        (3, 8, 8),
        np.float32,
        batch_size=4,
        num_workers=2,
        jit=False,
        memory=MemoryConfig(pooling=pooling, budget_bytes=budget_bytes, bucket_min_bytes=256),
        **kw,
    )


def test_engine_pooled_and_unpooled_outputs_agree():
    items = list(range(30))
    out_pooled, stats_pooled = _engine(pooling=True).run(items)
    out_unpooled, stats_unpooled = _engine(pooling=False).run(items)
    for a, b in zip(out_pooled, out_unpooled):
        np.testing.assert_allclose(a, b)
    assert stats_pooled.pool_stats is not None
    assert stats_unpooled.pool_stats is None  # baseline has no pool to report


def test_engine_staging_zero_net_growth_across_100_batches():
    eng = _engine(pooling=True)
    items = list(range(400))  # batch_size=4 -> 100 batches
    _, stats = eng.run(items, return_outputs=False)
    s = stats.pool_stats
    assert s.leases_issued >= 100
    # staging leases never exceed the dispatch ring: allocation plateaus
    assert s.buffers_allocated <= eng.ring_slots + 1
    assert s.leases_active == 0 and s.bytes_in_use == 0
    # a second pass must allocate nothing new at all
    _, stats2 = eng.run(items, return_outputs=False)
    assert stats2.pool_stats.buffers_allocated == s.buffers_allocated
    assert stats2.pool_stats.leases_reused > s.leases_reused


def test_engine_budget_bounds_inflight_decoded_bytes():
    item_nbytes = 3 * 8 * 8 * 4
    eng = _engine(pooling=True, budget_bytes=3 * item_nbytes)
    out, stats = eng.run(list(range(25)))
    assert len(out) == 25 and all(o is not None for o in out)
    b = stats.budget_stats
    assert b is not None
    assert b.in_flight_bytes == 0  # everything admitted was released
    assert b.high_water_bytes <= b.max_bytes


def test_engine_budget_survives_host_errors():
    # admissions taken by items that error (or never reach the consumer)
    # must be reconciled — a failed run must not shrink budget headroom
    item_nbytes = 3 * 8 * 8 * 4

    def host_fn(item):
        if item == 7:
            raise ValueError("bad 7")
        return np.full((3, 8, 8), float(item), np.float32)

    eng = PipelinedEngine(
        host_fn,
        lambda b: b.sum(axis=(1, 2, 3)),
        (3, 8, 8),
        np.float32,
        batch_size=4,
        num_workers=2,
        jit=False,
        memory=MemoryConfig(budget_bytes=2 * item_nbytes),
    )
    with pytest.raises(ValueError, match="bad 7"):
        eng.run(list(range(16)))
    assert eng.budget_stats().in_flight_bytes == 0, "failed run leaked budget bytes"
    out, _ = eng.run(list(range(7)))  # headroom intact: no deadlock
    assert len(out) == 7 and all(o is not None for o in out)


def test_engine_per_worker_state_reaches_host_fn():
    created = []

    def factory():
        created.append(object())
        return created[-1]

    def host_fn(item, state):
        assert state is not None
        return np.full((2,), float(item), np.float32)

    eng = PipelinedEngine(
        host_fn,
        lambda b: b,
        (2,),
        np.float32,
        batch_size=4,
        num_workers=2,
        jit=False,
        worker_state_factory=factory,
    )
    out, _ = eng.run(list(range(10)))
    assert len(created) == 2
    assert all(o[0] == i for i, o in enumerate(out))


# ------------------------------------------------------- scheduler admission
def _scheduler(**kw):
    def host_fn(item):
        time.sleep(0.05)
        return np.full((4,), float(item), np.float32)

    sched = RequestScheduler(
        host_fn,
        lambda b: b * 2.0,
        (4,),
        np.float32,
        max_batch=2,
        num_workers=1,
        max_wait_ms=1.0,
        **kw,
    )
    sched.start()
    return sched


def test_scheduler_reject_mode_sheds_load_at_max_pending():
    sched = _scheduler(max_pending=2, admission="reject")
    try:
        sched.submit(1)
        sched.submit(2)
        with pytest.raises(SchedulerSaturated):
            sched.submit(3)
        assert sched.stats.rejected == 1
        sched.flush(timeout=30.0)
        sched.submit(4)  # headroom is back after completions
        sched.flush(timeout=30.0)
        done = sched.drain()
    finally:
        sched.stop()
    assert [d.uid for d in done] == [0, 1, 2]
    assert all(d.error is None for d in done)


def test_scheduler_block_mode_backpressures_at_max_pending():
    sched = _scheduler(max_pending=2, admission="block", admission_timeout_s=30.0)
    try:
        t0 = time.perf_counter()
        for i in range(5):
            sched.submit(i)
        submit_wall = time.perf_counter() - t0
        sched.flush(timeout=30.0)
        done = sched.drain()
    finally:
        sched.stop()
    assert [d.uid for d in done] == list(range(5))
    # 5 submits through a 2-deep window over a 50ms host stage must block
    assert submit_wall > 0.1
    assert sched.stats.admission_blocked_seconds > 0.0
    assert sched.stats.rejected == 0


def test_scheduler_block_mode_times_out():
    sched = _scheduler(max_pending=1, admission="block", admission_timeout_s=0.02)
    try:
        sched.submit(1)
        with pytest.raises(TimeoutError):
            sched.submit(2)
    finally:
        sched.stop()


def test_scheduler_budget_gates_submit():
    item_nbytes = 4 * 4  # out_shape (4,) float32
    sched = _scheduler(admission="reject", budget=MemoryBudget(item_nbytes))
    try:
        sched.submit(1)
        with pytest.raises(SchedulerSaturated, match="memory budget"):
            sched.submit(2)
        sched.flush(timeout=30.0)
        sched.submit(3)  # bytes released on completion
        sched.flush(timeout=30.0)
    finally:
        sched.stop()
    assert sched.budget.stats().in_flight_bytes == 0
    assert sched.stats.rejected == 1


def test_scheduler_resize_workers_online():
    sched = _scheduler()
    try:
        for i in range(4):
            sched.submit(i)
        sched.resize_workers(3)
        for i in range(4, 8):
            sched.submit(i)
        sched.flush(timeout=30.0)
        sched.resize_workers(1)
        for i in range(8, 10):
            sched.submit(i)
        sched.flush(timeout=30.0)
        done = sched.drain()
    finally:
        sched.stop()
    assert [d.uid for d in done] == list(range(10))
    assert all(d.error is None for d in done)


# -------------------------------------------------------- worker recalibration
def test_worker_recalibrator_jumps_to_knee_when_host_bound():
    # ideal = 10 workers: the pool jumps straight to the (clamped) knee in
    # ONE window instead of walking +1 per window (the ROADMAP item)
    r = WorkerRecalibrator(num_workers=2, max_workers=8, alpha=1.0)
    m = StageMeasurement(host_seconds_per_item=1.0, device_seconds_per_item=0.1)
    n, changed = r.update(m)
    assert changed and n == 8
    assert r.events[-1].knee_workers == pytest.approx(10.0)


def test_worker_recalibrator_jumps_down_when_device_bound():
    r = WorkerRecalibrator(num_workers=4, max_workers=8, alpha=1.0)
    m = StageMeasurement(host_seconds_per_item=0.1, device_seconds_per_item=0.5)
    n, changed = r.update(m)
    assert changed and n == 1  # straight to the knee (ratio 0.2 -> 1 worker)


def test_worker_recalibrator_fits_contention_curve():
    # the fitted host_spi(w) = a + b*w curve must cap the knee below the
    # naive perfect-scaling ratio once contention is observed
    r = WorkerRecalibrator(num_workers=1, max_workers=16, alpha=1.0, dead_band=0.0)
    n, changed = r.update(StageMeasurement(0.5, 0.2))  # ratio 2.5 -> knee 3
    assert changed and n == 3
    # at 3 workers decode got dearer (GIL/contention): naive ratio says 4,
    # but the fit (b = 0.15/worker, device 0.2) solves the knee at 7
    n, changed = r.update(StageMeasurement(0.8, 0.2))
    assert changed and n == 7
    assert r.events[-1].knee_workers == pytest.approx(7.0)
    # contention growing as fast as capacity: adding workers cannot catch
    # up; the knee caps at max_workers rather than diverging
    r2 = WorkerRecalibrator(num_workers=1, max_workers=6, alpha=1.0, dead_band=0.0)
    r2.update(StageMeasurement(0.5, 0.1))
    n, _ = r2.update(StageMeasurement(0.5 + 0.1 * 4, 0.1))  # b == device_spi
    assert n == 6 and r2.events[-1].knee_workers == 6.0


def test_worker_recalibrator_holds_on_degenerate_window():
    r = WorkerRecalibrator(num_workers=2, max_workers=8)
    n, changed = r.update(StageMeasurement(0.0, 1e-3))  # zero host busy-time
    assert not changed and n == 2
    n, changed = r.update(StageMeasurement(1e-3, 0.0))  # no completions
    assert not changed and n == 2


def test_worker_recalibrator_damps_oscillation():
    r = WorkerRecalibrator(num_workers=2, max_workers=8, alpha=0.5, dead_band=0.5)
    flips = 0
    for i in range(20):  # window straddles the 2<->3 boundary every sample
        ideal = 2.4 if i % 2 == 0 else 2.6
        _, changed = r.update(StageMeasurement(ideal, 1.0))
        flips += int(changed)
    assert flips <= 1, "worker count flapped between adjacent values"
    assert r.num_workers in (2, 3)


# --------------------------------------------------- arena-backed codec scratch
def test_codec_band_scratch_reaches_steady_state():
    # SPNG's band payloads route through the thread-local FrameArena (SJPG
    # decodes into one buffer per call and leaves the arena alone): after
    # warmup, repeated decodes of both must not grow the arena (zero
    # per-band system allocations) and must leak nothing
    from conftest import smooth_image
    from repro.preprocessing import jpeg, png, scratch

    rng = np.random.default_rng(0)
    img = smooth_image(rng, 128, 160)
    dj = jpeg.encode(img, quality=85)
    dp = png.encode(img)
    for _ in range(30):  # warm: block-boundary positions cycle through
        jpeg.decode(dj)
        png.decode(dp)
        jpeg.decode_to_coefficients(dj, max_rows=40)
    before = scratch.arena_stats()
    assert before.bytes_in_use == 0, "scratch leaked outside its band scope"
    for _ in range(100):
        jpeg.decode(dj)
        png.decode(dp)
        jpeg.decode_to_coefficients(dj, max_rows=40)
    after = scratch.arena_stats()
    assert after.blocks_allocated == before.blocks_allocated, "arena grew in steady state"
    assert after.bytes_in_use == 0

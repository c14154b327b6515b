"""Telemetry subsystem: streaming-histogram quantile accuracy, span rings
and the zero-allocation telemetry-off guard, traced-request span tiling,
occupancy measurement windows, the trace/metrics export surfaces, and the
runtime's ``smol.*`` phase spans in a ``jax.profiler`` trace beside the
scheduler's phase counters.

Timing tests use sleep-controlled stage functions (policy, not box
throughput); distribution tests check the histogram against exact
percentiles of the same samples.
"""

import glob
import json
import time
import warnings

import jax
import numpy as np
import pytest

from conftest import smooth_image
from repro.core.planner import ModelSpec
from repro.preprocessing.formats import ImageFormat, StoredImage
from repro.runtime import (
    ClassificationQuery,
    DeviceCompilerConfig,
    HistogramSummary,
    LatencySection,
    RequestScheduler,
    RuntimeConfig,
    RuntimeStats,
    SmolRuntime,
    StreamingHistogram,
    Telemetry,
    TelemetryConfig,
    TenantConfig,
)
from repro.runtime import telemetry as telemetry_mod
from repro.runtime.telemetry import REQUEST_STAGES, _SpanRing


# ------------------------------------------------------------- histograms
@pytest.mark.parametrize(
    "name,samples",
    [
        ("uniform", np.random.default_rng(7).uniform(1e-3, 0.1, 5000)),
        ("lognormal", np.exp(np.random.default_rng(11).normal(-5.0, 1.0, 5000))),
    ],
)
def test_histogram_quantiles_track_exact_percentiles(name, samples):
    h = StreamingHistogram()
    for s in samples:
        h.record(float(s))
    assert h.count == len(samples)
    # log-bucketed estimate vs the exact order statistic: the bucket
    # geometry (2^(1/8) growth) bounds relative error well under 12%
    for q in (0.50, 0.95, 0.99):
        exact = float(np.percentile(samples, q * 100))
        est = h.quantile(q)
        assert abs(est - exact) / exact < 0.12, (name, q, est, exact)
    assert abs(h.mean - samples.mean()) / samples.mean() < 1e-6
    assert h.max == pytest.approx(samples.max())
    # the top quantile is a bucket-midpoint estimate, clamped by max
    assert samples.max() * 0.88 < h.quantile(1.0) <= samples.max()


def test_histogram_single_value_is_exact():
    h = StreamingHistogram()
    h.record(0.0123)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(0.0123)
    s = h.summary()
    assert s.count == 1 and s.p50 == s.p99 == s.max == pytest.approx(0.0123)


def test_histogram_empty_and_negative():
    h = StreamingHistogram()
    assert h.summary() == HistogramSummary(0, 0.0, 0.0, 0.0, 0.0, 0.0)
    h.record(-1.0)  # clock skew degrades to zero, never throws
    assert h.count == 1 and h.min == 0.0


def test_histogram_merge_matches_combined_stream():
    rng = np.random.default_rng(3)
    a, b = rng.uniform(1e-4, 1e-2, 400), rng.uniform(1e-2, 1.0, 400)
    ha, hb, hall = StreamingHistogram(), StreamingHistogram(), StreamingHistogram()
    for s in a:
        ha.record(float(s))
        hall.record(float(s))
    for s in b:
        hb.record(float(s))
        hall.record(float(s))
    ha.merge(hb)
    assert ha.count == hall.count and ha.sum == pytest.approx(hall.sum)
    for q in (0.5, 0.95, 0.99):
        assert ha.quantile(q) == pytest.approx(hall.quantile(q))


# ----------------------------------------------------------------- config
def test_telemetry_config_validation():
    with pytest.raises(ValueError, match="sample_rate"):
        TelemetryConfig(sample_rate=0.0)
    with pytest.raises(ValueError, match="sample_rate"):
        TelemetryConfig(sample_rate=1.5)
    with pytest.raises(ValueError, match="ring_capacity"):
        TelemetryConfig(ring_capacity=4)
    cfg = TelemetryConfig(spans=True, sample_rate=0.25, ring_capacity=64)
    assert cfg.spans and cfg.histograms


def test_sampling_is_deterministic_by_uid():
    tel = Telemetry(TelemetryConfig(spans=True, sample_rate=0.25))
    picked = {uid for uid in range(100) if tel.sampled(uid)}
    assert picked == {uid for uid in range(100) if uid % 4 == 0}
    # spans off -> nothing sampled regardless of rate
    assert not Telemetry(TelemetryConfig(spans=False)).sampled(0)


# ------------------------------------------------------------- span rings
def test_span_ring_overwrites_oldest():
    tel = Telemetry(TelemetryConfig(spans=True, ring_capacity=16))
    for uid in range(20):
        tel.emit_span("request", "queue", "t", uid, 0.0, 1.0)
    assert tel.ring_allocations == 1
    spans = tel.spans()
    assert len(spans) == 16
    assert {s.uid for s in spans} == set(range(4, 20))
    (ring,) = tel._rings
    assert ring.dropped == 4


def test_ring_capacity_is_fixed():
    ring = _SpanRing(16)
    assert len(ring.buf) == 16 and ring.snapshot() == []


# ----------------------------------------------------- scheduler integration
def _sched(telemetry, host_sleep=0.002, device_sleep=0.004, tenants=None):
    def host_fn(item):
        time.sleep(host_sleep)
        return np.full((4,), float(item), np.float32)

    class DeviceFn:
        # mimics DevicePreprocProgram's dispatch counter so the scheduler's
        # cache-cold batch marking is exercised
        dispatch_count = 0

        def __call__(self, batch):
            DeviceFn.dispatch_count += 1
            time.sleep(device_sleep)
            return batch * 2.0

    sched = RequestScheduler(
        host_fn,
        DeviceFn(),
        (4,),
        np.float32,
        max_batch=4,
        num_workers=2,
        max_wait_ms=1.0,
        tenants=tenants,
        telemetry=telemetry,
    )
    sched.start()
    return sched


def test_telemetry_off_allocates_no_rings():
    tel = Telemetry(TelemetryConfig(histograms=False, spans=False))
    sched = _sched(tel, host_sleep=0.0, device_sleep=0.0)
    try:
        for i in range(32):
            sched.submit(i)
        sched.flush(timeout=30.0)
        done = sched.drain()
    finally:
        sched.stop()
    assert len(done) == 32
    assert tel.ring_allocations == 0  # the overhead guard CI asserts
    assert tel.spans() == []
    assert tel.summary() == {"stages": {}, "tenants": {}}
    # occupancy accumulators stay live for recalibration even with
    # histograms off
    host_s, host_n, _, dev_n = tel.occupancy_totals()
    assert host_n == 32 and dev_n == 32


def test_traced_request_spans_tile_wall_latency():
    tel = Telemetry(TelemetryConfig(spans=True))
    tenants = [TenantConfig("lat", max_wait_ms=2.0), TenantConfig("thru", weight=2.0)]
    sched = _sched(tel, tenants=tenants)
    t_submit = {}
    try:
        for i in range(24):
            uid = sched.submit(i, tenant="lat" if i % 2 else "thru")
            t_submit[uid] = time.perf_counter()
        sched.flush(timeout=30.0)
        done = sched.drain()
        t_end = time.perf_counter()
    finally:
        sched.stop()
    assert len(done) == 24

    per_uid = {}
    for s in tel.spans():
        if s.kind == "request":
            per_uid.setdefault(s.uid, {})[s.name] = s.t1 - s.t0
    assert len(per_uid) == 24
    for d in done:
        parts = per_uid[d.uid]
        assert set(parts) == set(REQUEST_STAGES)
        # queue+decode+stage+dispatch tile submit -> completion exactly
        pipeline = sum(parts[k] for k in ("queue", "decode", "stage", "dispatch"))
        assert pipeline == pytest.approx(d.latency, rel=1e-6, abs=1e-6)
        # + drain reaches the client-observed wall (within 10%)
        wall = t_end - t_submit[d.uid]
        total = pipeline + parts["drain"]
        assert abs(total - wall) <= 0.10 * wall + 2e-3

    # batch spans link members and carry a replica id
    batches = [s for s in tel.spans() if s.kind == "batch"]
    assert batches
    linked = sorted(uid for s in batches for uid in s.args["uids"])
    assert linked == sorted(per_uid)
    assert all(s.args["replica"] == 0 for s in batches)
    # the first dispatched batch is marked cache-cold
    assert any(s.args.get("cold") for s in batches)

    # per-tenant histograms saw every request
    digest = tel.summary()
    assert digest["tenants"]["lat"]["e2e"].count == 12
    assert digest["tenants"]["thru"]["e2e"].count == 12
    for stage in REQUEST_STAGES + ("e2e",):
        assert digest["stages"][stage].count == 24


def test_measurement_window_deltas_per_consumer():
    tel = Telemetry()
    tel.observe_host("a", 0.010)
    tel.observe_host("a", 0.030)
    tel.observe_device_batch(0.008, {"a": 2})
    host_s, host_n, dev_s, dev_n = tel.measurement_window("c1")
    assert host_n == 2 and host_s == pytest.approx(0.040)
    assert dev_n == 2 and dev_s == pytest.approx(0.008)
    # same consumer again: empty delta
    assert tel.measurement_window("c1") == (0.0, 0, 0.0, 0)
    # a different consumer still sees everything
    assert tel.measurement_window("c2")[1] == 2
    # per-tenant windows are independent keys
    assert tel.measurement_window("c1", "a")[1] == 2


def test_device_batch_occupancy_attributed_proportionally():
    tel = Telemetry()
    tel.observe_device_batch(0.012, {"a": 3, "b": 1})
    a = tel.occupancy_totals("a")
    b = tel.occupancy_totals("b")
    assert a[2] == pytest.approx(0.009) and a[3] == 3
    assert b[2] == pytest.approx(0.003) and b[3] == 1


# ----------------------------------------------------------------- export
def test_dump_trace_chrome_json(tmp_path):
    tel = Telemetry(TelemetryConfig(spans=True))
    tel.emit_span("request", "queue", "gold", 1, 0.0, 0.001)
    tel.emit_span("request", "decode", "gold", 1, 0.001, 0.003, worker=0)
    tel.emit_span("batch", "batch", None, 1, 0.003, 0.007, replica=2, uids=[1])
    path = tmp_path / "trace.json"
    assert tel.dump_trace(str(path)) == 3
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and isinstance(e["ts"], float) for e in xs)
    procs = {e["args"]["name"] for e in events if e["name"] == "process_name"}
    assert procs == {"tenant:gold", "replica mesh"}
    batch = next(e for e in xs if e["cat"] == "batch")
    assert batch["tid"] == 2 and batch["args"]["uids"] == [1]


def test_metrics_text_prometheus_exposition():
    tel = Telemetry()
    for ms in (1, 2, 5, 80):
        tel.record("e2e", ms / 1e3, tenant="gold")
    text = tel.metrics_text(extra_lines=['smol_requests_total{tenant="gold"} 4'])
    lines = text.strip().splitlines()
    assert lines[0].startswith("# HELP smol_stage_latency_seconds")
    assert lines[1] == "# TYPE smol_stage_latency_seconds histogram"
    assert lines[-1] == 'smol_requests_total{tenant="gold"} 4'
    gold = [ln for ln in lines if 'tenant="gold"' in ln and "_bucket" in ln]
    # cumulative counts are monotone and terminate at +Inf == count
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in gold]
    assert counts == sorted(counts) and 'le="+Inf"' in gold[-1] and counts[-1] == 4
    assert 'smol_stage_latency_seconds_count{stage="e2e",tenant="gold"} 4' in lines
    # runtime-wide series (tenant="") rides alongside
    assert any('tenant=""' in ln and "_bucket" in ln for ln in lines)


# ------------------------------------------------------------ stats schema
def test_runtime_stats_v2_roundtrip_with_latency():
    tel = Telemetry()
    tel.record("e2e", 0.005, tenant="gold")
    digest = tel.summary()
    stats = RuntimeStats(
        latency=LatencySection(stages=digest["stages"], tenants=digest["tenants"])
    )
    assert stats.schema_version == 4
    d = stats.to_dict()
    json.dumps(d)  # wire-safe with the latency section populated
    assert d["latency"]["tenants"]["gold"]["e2e"]["count"] == 1
    assert d["latency"]["stages"]["e2e"]["p50"] > 0


def test_stats_dict_access_warns_even_under_error_filter():
    stats = RuntimeStats()
    with warnings.catch_warnings():
        # the -W error::DeprecationWarning regime: dict access must warn
        # (and only warn) through the documented DeprecationWarning
        warnings.simplefilter("error", DeprecationWarning)
        with pytest.raises(DeprecationWarning):
            stats["num_workers"]
        with pytest.raises(DeprecationWarning):
            stats.get("num_workers")
        # attribute access stays silent
        assert stats.num_workers == 0
        assert stats.get("no_such_section", 42) == 42
        with pytest.raises(KeyError):
            stats["no_such_section"]


# ------------------------------------------------- profiler-clock phase spans
BATCHER_SPANS = ("smol.starved", "smol.batch_form", "smol.launch", "smol.readback", "smol.complete")
SPAN_NAMES = ("smol.decode", "smol.compile") + BATCHER_SPANS
WINDOW = "test.serving"


@pytest.fixture(scope="module")
def profiled_serving(tmp_path_factory):
    """One tiny served ``submit()``/``drain()`` run over split-decoded JPEG
    (warmup off, so dispatch #1 compiles while serving) inside an enclosing
    annotation, under a ``jax.profiler`` trace.  Returns the window, the host
    plane's events per line (one line per thread) and the scheduler's
    counters."""
    fmt = ImageFormat("jpeg", None, 95)
    rng = np.random.default_rng(5)
    corpus = [StoredImage.from_array(smooth_image(rng, 72, 88), [fmt]) for _ in range(24)]
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (3 * 32 * 32, 5)) * 0.02)
    rt = SmolRuntime(
        [ModelSpec("m", 32, exec_throughput=50_000.0, accuracy_by_format={fmt.key: 0.9})],
        [fmt],
        {"m": lambda x: x.reshape(x.shape[0], -1) @ w},
        calibration=corpus[:3],
        config=RuntimeConfig(
            batch_size=4,
            num_workers=2,
            max_wait_ms=2.0,
            device=DeviceCompilerConfig(backend="fused", split_decode="full"),
        ),
        decode_time=lambda fmt: 1e-4,
    )
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW):
            rt.start_serving()
            try:
                for wave in (corpus[:12], corpus[12:]):
                    for item in wave:
                        rt.submit(ClassificationQuery(image=item))
                    rt.flush(timeout=120.0)
                    time.sleep(0.05)  # the batcher starves between waves
                done = rt.drain()
                stats = rt.stats().scheduler.stats
            finally:
                rt.stop_serving()
    assert len(done) == 24 and not any(r.error for r in done)
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.end_ns) for e in line.events])
    (window,) = [(s, e) for line in lines for n, s, e in line if n == WINDOW]
    return window, lines, stats


def test_profiler_trace_holds_every_phase_span_inside_the_window(profiled_serving):
    (lo, hi), lines, _stats = profiled_serving
    seen = {n for line in lines for n, s, e in line if lo <= s and e <= hi}
    assert set(SPAN_NAMES) <= seen, set(SPAN_NAMES) - seen
    # one thread's line carries every batcher phase; the decode spans sit
    # on the host workers' lines, apart from it
    batcher = [line for line in lines if any(n == "smol.launch" for n, _s, _e in line)]
    assert len(batcher) == 1
    assert set(BATCHER_SPANS) <= {n for n, _s, _e in batcher[0]}
    assert not any(n == "smol.decode" for n, _s, _e in batcher[0])


def test_batcher_spans_tile_the_batcher_thread(profiled_serving):
    _window, lines, _stats = profiled_serving
    (line,) = [line for line in lines if any(n == "smol.launch" for n, _s, _e in line)]
    phases = sorted((s, e) for n, s, e in line if n in BATCHER_SPANS)
    # from the end of the first batch, whose launch compiled the program, to
    # the end of the last: the compile would hide a gap between phases
    completes = sorted(e for n, _s, e in line if n == "smol.complete")
    assert len(completes) >= 3
    lo, hi = completes[0], completes[-1]
    covered, reach = 0.0, lo
    for s, e in phases:
        s, e = max(s, reach), min(e, hi)
        if e > s:
            covered += e - s
            reach = e
    assert covered >= 0.9 * (hi - lo), covered / (hi - lo)


def test_phase_counters_split_the_blocking_call(profiled_serving):
    _window, _lines, st = profiled_serving
    assert st.batches > 0 and st.launch_seconds > 0 and st.readback_seconds > 0
    assert st.batch_form_seconds > 0 and st.starved_seconds > 0
    split = st.launch_seconds + st.readback_seconds
    assert abs(split - st.device_busy_seconds) <= 0.05 * st.device_busy_seconds
    assert 0 < st.host_cpu_seconds <= st.host_busy_seconds + 1e-3


def test_no_profiler_session_records_no_span_and_no_ring(monkeypatch):
    made = []

    class Counting:
        is_enabled = staticmethod(telemetry_mod.TraceAnnotation.is_enabled)

        def __init__(self, name, **args):
            made.append(name)

    monkeypatch.setattr(telemetry_mod, "TraceAnnotation", Counting)
    assert not Counting.is_enabled()
    assert telemetry_mod.span("smol.decode", uid=1) is telemetry_mod.span("smol.launch")
    tel = Telemetry()
    sched = _sched(tel, host_sleep=0.0, device_sleep=0.0)
    try:
        for i in range(16):
            sched.submit(i)
        sched.flush(timeout=30.0)
        done = sched.drain()
    finally:
        sched.stop()
    assert len(done) == 16
    assert made == [] and tel.ring_allocations == 0
    assert sched.stats.launch_seconds > 0 and sched.stats.host_items == 16


def test_argument_weights_are_placed_once_per_replica_under_a_span(tmp_path):
    """A ``ModelEntry``'s weights are placed once on the replica, under one
    ``smol.weights`` span carrying their bytes, however many bucket programs
    compile there; ``stats().weight_bytes`` counts that one copy."""
    from repro.core.device_compiler import ModelEntry

    fmt = ImageFormat("jpeg", None, 95)
    rng = np.random.default_rng(9)
    corpus = [StoredImage.from_array(smooth_image(rng, 40, 40), [fmt]) for _ in range(3)]
    params = {"w": np.asarray(jax.random.normal(jax.random.PRNGKey(1), (3 * 16 * 16, 5)) * 0.02),
              "b": np.arange(5, dtype=np.float32)}
    entry = ModelEntry(lambda p, x: x.reshape(x.shape[0], -1) @ p["w"] + p["b"], params)
    rt = SmolRuntime(
        [ModelSpec("m", 16, exec_throughput=50_000.0, accuracy_by_format={fmt.key: 0.9})],
        [fmt],
        {"m": entry},
        calibration=corpus[:2],
        config=RuntimeConfig(batch_size=4, num_workers=1, warmup="full",
                             device=DeviceCompilerConfig(backend="fused", split_decode="full")),
        decode_time=lambda fmt: 1e-4,
    )
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW):
            compiled = rt.compile()
            assert rt.wait_warm(timeout=120.0)
    assert len(compiled.program_sets[0].programs) == 3  # buckets 1, 2, 4
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    events = [e for plane in jax.profiler.ProfileData.from_file(path).planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    (window,) = [(e.start_ns, e.end_ns) for e in events if e.name == WINDOW]
    (placed,) = [e for e in events if e.name == "smol.weights"]
    assert window[0] <= placed.start_ns and placed.end_ns <= window[1]
    nbytes = params["w"].nbytes + params["b"].nbytes
    assert dict(placed.stats) == {"bytes": nbytes, "device": "default"}
    assert rt.stats().weight_bytes == entry.nbytes == nbytes

"""Signals-layer suite (ISSUE 9): streaming log-bucket histograms and the
rolling windows under ``Metrics``, the SLO burn-rate monitor + health
state machine, Prometheus exposition (render + format lint + live
``/prom`` / ``/health`` endpoints), the recompile watchdog, the
``bench_compare`` perf-regression gate, and the journal ``--stage``
filter.

Everything runs over ``runtime.fakes.InstantPipeline`` and fake clocks —
fast, deterministic, no hardware. The one property the whole layer hangs
on — "a rolling-histogram quantile matches the exact sample quantile
within one bucket width" — is tested as a randomized property over
several distributions, not a point check.
"""

import importlib.util
import json
import math
import os
import random
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime.connector import FakeConnector
from opencv_facerecognizer_tpu.runtime.expo import ExpoServer
from opencv_facerecognizer_tpu.runtime.fakes import (
    InstantPipeline,
    build_overload_stack,
)
from opencv_facerecognizer_tpu.runtime.journal import DeadLetterJournal
from opencv_facerecognizer_tpu.runtime.promtext import (
    lint_prometheus_text,
    render,
)
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    STATUS_TOPIC,
    RecognizerService,
)
from opencv_facerecognizer_tpu.runtime.resilience import ServiceSupervisor
from opencv_facerecognizer_tpu.runtime.slo import (
    SLO,
    SLOMonitor,
    STATE_CRITICAL,
    STATE_OK,
    STATE_WARN,
    default_objectives,
    loop_liveness_objective,
)
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.histogram import (
    BUCKET_BOUNDS,
    BUCKET_GROWTH,
    BUCKET_HI,
    BUCKET_LO,
    LogBucketHistogram,
    RollingHistogram,
    bucket_index,
)
from opencv_facerecognizer_tpu.utils.metrics import Metrics
from opencv_facerecognizer_tpu.utils.tracing import LIFECYCLE_TOPIC, Tracer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench_compare", os.path.join(REPO_ROOT, "scripts", "bench_compare.py"))
bench_compare = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_compare)

FRAME_HW = (16, 16)


class FakeClock:
    """A settable monotonic clock for the rolling rings and the monitor."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


# ---------- log-bucket histogram: schema + property test ----------


def test_bucket_index_total_and_consistent_with_bounds():
    # Totality: clock hiccups (NaN, negative, zero) land in the underflow
    # bucket instead of raising on the serving path.
    assert bucket_index(float("nan")) == 0
    assert bucket_index(-1.0) == 0
    assert bucket_index(0.0) == 0
    assert bucket_index(BUCKET_LO) == 0
    assert bucket_index(BUCKET_HI * 10) == len(BUCKET_BOUNDS) - 1
    # Containment invariant on a dense sweep including exact boundaries:
    # BUCKET_BOUNDS[idx-1] < value <= BUCKET_BOUNDS[idx].
    probes = list(BUCKET_BOUNDS[:-1])
    probes += [b * 1.0000001 for b in BUCKET_BOUNDS[:-1]]
    probes += [10 ** e for e in np.linspace(-4.9, 2.0, 200)]
    last_idx = 0
    for value in sorted(probes):
        idx = bucket_index(value)
        assert value <= BUCKET_BOUNDS[idx]
        if idx > 0:
            assert value > BUCKET_BOUNDS[idx - 1]
        assert idx >= last_idx  # monotone in the value
        last_idx = idx


def test_quantiles_match_exact_within_one_bucket_property():
    """The acceptance property: for randomized data across distributions,
    every reported quantile lies within one bucket width (a factor of
    ``BUCKET_GROWTH`` in log space) of the exact nearest-rank sample
    quantile."""
    distributions = {
        "uniform": lambda rng: rng.uniform(1e-4, 10.0),
        "lognormal": lambda rng: min(100.0, max(2e-5,
                                                math.exp(rng.gauss(-3, 2)))),
        "bimodal": lambda rng: (rng.uniform(0.8e-3, 1.2e-3) if rng.random()
                                < 0.7 else rng.uniform(0.3, 0.8)),
    }
    for seed in (0, 7, 1234):
        for name, draw in distributions.items():
            rng = random.Random(seed)
            values = [draw(rng) for _ in range(2000)]
            hist = LogBucketHistogram()
            for v in values:
                hist.observe(v)
            exact = sorted(values)
            for q in (1, 25, 50, 90, 95, 99):
                rank = min(len(exact) - 1, int(q / 100.0 * len(exact)))
                e = exact[rank]
                r = hist.quantile(q)
                assert e / BUCKET_GROWTH * (1 - 1e-9) <= r \
                    <= e * BUCKET_GROWTH * (1 + 1e-9), \
                    (name, seed, q, e, r)


def test_histogram_merge_equals_union_and_snapshot_shape():
    rng = random.Random(3)
    a, b, union = (LogBucketHistogram(), LogBucketHistogram(),
                   LogBucketHistogram())
    for _ in range(500):
        v = math.exp(rng.uniform(math.log(2e-5), math.log(50.0)))
        target = a if rng.random() < 0.5 else b
        target.observe(v)
        union.observe(v)
    merged = LogBucketHistogram().merge(a).merge(b)
    assert merged.counts == union.counts
    assert merged.count == union.count == 500
    assert merged.sum == pytest.approx(union.sum)
    for q in (50, 95, 99):
        assert merged.quantile(q) == union.quantile(q)
    snap = merged.snapshot()
    assert len(snap["bounds"]) == len(BUCKET_BOUNDS) - 1  # +Inf implied
    assert sum(snap["counts"]) == snap["count"] == 500


def test_empty_histogram_reads():
    hist = LogBucketHistogram()
    assert math.isnan(hist.quantile(50))
    assert hist.fraction_above(0.1) == 0.0


def test_fraction_above_is_bucket_conservative():
    hist = LogBucketHistogram()
    for _ in range(50):
        hist.observe(0.001)
    for _ in range(50):
        hist.observe(1.0)
    # A clean split reads exactly; observations in the threshold's OWN
    # bucket count as not-above (a breach must be provable from counts).
    assert hist.fraction_above(0.01) == pytest.approx(0.5)
    assert hist.fraction_above(1.0) == 0.0
    assert hist.fraction_above(2.0) == 0.0


def test_rolling_window_expiry_and_horizons():
    clock = FakeClock()
    ring = RollingHistogram(window_s=80.0, slices=8, clock=clock)  # 10 s/slice
    ring.observe(0.001)
    clock.t = 25.0
    ring.observe(1.0)
    # Full window sees both; a short horizon reads only the recent slices
    # (the current partial slice always counts).
    assert ring.count() == 2
    assert ring.count(horizon_s=10.0) == 1
    assert ring.fraction_above(0.1) == pytest.approx(0.5)
    assert ring.fraction_above(0.1, horizon_s=10.0) == pytest.approx(1.0)
    # Lazy expiry: once the window rotates past an epoch, reads skip it.
    clock.t = 84.0  # first observation's slice (epoch 0) is now expired
    assert ring.count() == 1
    clock.t = 200.0
    assert ring.count() == 0
    ring.observe(0.5)
    assert ring.count() == 1


def test_metrics_memory_flat_under_100k_observation_soak():
    """The unbounded-window fix: 100k observations into one Metrics
    window hold exactly as many bucket cells as one observation does."""
    rng = random.Random(11)
    metrics = Metrics()
    metrics.observe(mn.QUEUE_WAIT, 0.001)
    window = metrics._latencies[mn.QUEUE_WAIT]
    cells_after_one = window.memory_cells()
    for _ in range(100_000):
        metrics.observe(mn.QUEUE_WAIT, math.exp(rng.uniform(-10, 4)))
    assert window.memory_cells() == cells_after_one
    assert len(window._hists[0].counts) == len(BUCKET_BOUNDS)
    assert metrics.window_count(mn.QUEUE_WAIT) == 100_001
    summary = metrics.summary()
    assert summary[f"{mn.QUEUE_WAIT}_p99_ms"] is not None


# ---------- Metrics surface over the rolling windows ----------


def test_metrics_percentiles_fractions_and_export_state():
    metrics = Metrics()
    for _ in range(90):
        metrics.observe("w", 0.010)
    for _ in range(10):
        metrics.observe("w", 1.0)
    assert metrics.percentile("w", 50) == pytest.approx(0.010, rel=0.1)
    assert metrics.percentile("w", 99) == pytest.approx(1.0, rel=0.1)
    assert metrics.fraction_above("w", 0.1) == pytest.approx(0.10)
    assert metrics.window_count("w") == 100
    # Unknown windows: NaN / 0.0 / 0 — never a raise, never a fake zero
    # percentile.
    assert math.isnan(metrics.percentile("nope", 50))
    assert metrics.fraction_above("nope", 0.1) == 0.0
    assert metrics.window_count("nope") == 0
    metrics.incr(mn.FRAMES_COMPLETED, 3)
    metrics.set_gauge(mn.HEALTH_STATE, 1)
    counters, gauges, hists = metrics.export_state()
    assert counters[mn.FRAMES_COMPLETED] == 3
    assert gauges[mn.HEALTH_STATE] == 1
    assert hists["w"]["count"] == 100
    # A known-but-reset window still exports (count 0) and summaries as
    # explicit nulls — the PR-8 contract preserved over histograms.
    metrics.reset_window("w")
    assert metrics.export_state()[2]["w"]["count"] == 0
    assert metrics.summary()["w_p50_ms"] is None


# ---------- SLO monitor: burn rates + health state machine ----------


def _ratio_slo(**kw):
    defaults = dict(name="completion", kind="ratio", target=0.9,
                    bad_counters=("frames_dropped_brownout",),
                    total_counters=(mn.FRAMES_ADMITTED,),
                    short_s=5.0, long_s=5.0, warn_burn=1.0,
                    critical_burn=2.0)
    defaults.update(kw)
    return SLO(**defaults)


def test_slo_validation():
    with pytest.raises(ValueError):
        SLO(name="x", kind="nope")
    with pytest.raises(ValueError):
        SLO(name="x", kind="latency")  # no window
    with pytest.raises(ValueError):
        SLO(name="x", kind="gauge")  # no value_fn
    with pytest.raises(ValueError):
        SLO(name="x", kind="latency", window="w", target=1.5)


def test_slo_monitor_rejects_windows_beyond_metrics_horizon():
    # A latency horizon longer than the metrics rolling window would read
    # only window_s of data — the monitor must refuse it loudly at
    # construction, not evaluate a quietly-weaker long window.
    metrics = Metrics(window_s=60.0)
    over = SLO(name="p99", kind="latency", window="w", threshold_s=0.1,
               short_s=30.0, long_s=120.0)
    with pytest.raises(ValueError, match="rolling horizon"):
        SLOMonitor(metrics, [over])
    # ...and a window below one ring slice would silently aggregate a
    # full slice anyway — reaction ~slice_s/short_s slower than asked.
    with pytest.raises(ValueError, match="ring resolution"):
        SLOMonitor(Metrics(window_s=600.0, window_slices=20),  # 30 s/slice
                   [SLO(name="p99", kind="latency", window="w",
                        threshold_s=0.1, short_s=5.0, long_s=60.0)])
    # At-or-under the horizon constructs fine; so does a metrics object
    # without a readable window_s (duck-typed fakes) or no metrics at all.
    SLOMonitor(metrics, [SLO(name="p99", kind="latency", window="w",
                             threshold_s=0.1, short_s=30.0, long_s=60.0)])
    class NoWindow:
        def counters(self):
            return {}
    SLOMonitor(NoWindow(), [over])
    SLOMonitor(None, [over])


def test_slo_swapped_windows_rejected():
    # A swapped pair is symmetric for burn severity so it would never
    # surface as a runtime error — but the reported horizons invert and
    # the watchdog-event hold window inflates. Loud constructor instead.
    with pytest.raises(ValueError, match="short-first"):
        SLO(name="x", kind="latency", window="w", threshold_s=0.1,
            short_s=600.0, long_s=60.0)


def test_add_objective_validates_and_rederives():
    metrics = Metrics(window_s=600.0)
    monitor = SLOMonitor(metrics, [SLO(
        name="p99", kind="latency", window="w", threshold_s=0.1,
        short_s=60.0, long_s=300.0)], interval_s=5.0)
    # Post-construction registration runs the same loud validation as
    # __init__ — and a refused objective must not be half-added.
    with pytest.raises(ValueError, match="rolling horizon"):
        monitor.add_objective(SLO(name="over", kind="latency", window="w",
                                  threshold_s=0.1, short_s=60.0,
                                  long_s=1200.0))
    assert len(monitor.objectives) == 1
    ring_before = monitor._counter_ring.maxlen
    assert monitor.event_window_s == 60.0
    monitor.add_objective(SLO(name="g", kind="gauge",
                              value_fn=lambda: 0.0, bound=1.0,
                              short_s=30.0, long_s=600.0))
    assert len(monitor.objectives) == 2
    # The counter ring re-derives to cover the new longest long window,
    # and the watchdog-event hold window follows the new min short_s.
    assert monitor._counter_ring.maxlen > ring_before
    assert monitor.event_window_s == 30.0


def test_loop_liveness_objective_flags_wedged_loop():
    # Empty latency windows read as burn 0 and the ratio objective sees
    # no counter growth, so a wedged serving loop scores a clean /health
    # forever — only the loop_liveness gauge (evaluated by whichever
    # ticker still runs, i.e. the expo backstop) can escalate it.
    metrics = Metrics()
    # The monitor is deliberately NOT wired into the service: this test
    # plays the expo-backstop ticker itself, and a live serving loop both
    # contends the non-blocking evaluation claim and keeps refreshing the
    # stamp the wedge simulation rewinds.
    monitor = SLOMonitor(metrics, [], interval_s=0.01, recovery_evals=1)
    _pipeline, service, connector = build_overload_stack(
        frame_shape=FRAME_HW, batch_size=4, dispatch_s=0.0,
        metrics=metrics)
    monitor.add_objective(loop_liveness_objective(
        service, stale_s=30.0, short_s=5.0, long_s=5.0))
    assert service.loop_staleness_s == 0.0  # stopped: no signal
    service.start(warmup=False)
    try:
        frame = np.zeros(FRAME_HW, np.float32)
        connector.inject(FRAME_TOPIC, {"frame": frame, "meta": {"seq": 0}})
        assert service.drain(timeout=10.0)
        obj = monitor.evaluate()["objectives"]["loop_liveness"]
        assert obj["state"] == "ok" and obj["burn"] < 1.0
    finally:
        service.stop()
    # Simulate a wedged-but-running loop by setting the flags on the
    # stopped service directly: staleness is all the gauge reads, and a
    # real deadlocked thread could not be un-wedged for teardown.
    service._running = True
    try:
        service._loop_progress_t = time.monotonic() - 31.0
        assert (monitor.evaluate()["objectives"]["loop_liveness"]["state"]
                == "warn")
        service._loop_progress_t = time.monotonic() - 200.0
        assert (monitor.evaluate()["objectives"]["loop_liveness"]
                ["state_code"] == STATE_CRITICAL)
    finally:
        service._running = False
    assert service.loop_staleness_s == 0.0  # stopped again: no signal


def test_slo_min_events_floor_suppresses_low_volume_severity():
    # One dropped frame on an idle replica is a huge burn against a tight
    # budget but not an outage: severity needs min_events in BOTH windows;
    # the burn is still reported, flagged low_volume.
    metrics = Metrics()
    clock = FakeClock()
    monitor = SLOMonitor(metrics, [_ratio_slo(target=0.999)],
                         interval_s=5.0, clock=clock)
    monitor.evaluate()
    clock.t = 10.0
    metrics.incr(mn.FRAMES_ADMITTED, 2)
    metrics.incr("frames_dropped_brownout", 1)
    verdict = monitor.evaluate()
    obj = verdict["objectives"]["completion"]
    assert obj["burn_short"] > 100 and obj["low_volume"] is True
    assert monitor.state == "ok"
    # The same rate at volume escalates: the floor gates volume, not rate.
    clock.t = 20.0
    metrics.incr(mn.FRAMES_ADMITTED, 100)
    metrics.incr("frames_dropped_brownout", 50)
    verdict = monitor.evaluate()
    assert "low_volume" not in verdict["objectives"]["completion"]
    assert monitor.state_code == STATE_CRITICAL
    # Gauge objectives are point-in-time reads — exempt from the floor.
    gauge_mon = SLOMonitor(Metrics(), [SLO(
        name="lag", kind="gauge", value_fn=lambda: 2048.0, bound=1024.0)],
        clock=FakeClock())
    gauge_mon.evaluate()
    assert gauge_mon.state_code == STATE_WARN


def test_slo_latency_breach_detected_within_one_interval():
    metrics = Metrics()
    clock = FakeClock()
    monitor = SLOMonitor(metrics, [SLO(
        name="p99", kind="latency", window="w", threshold_s=0.1,
        target=0.99, short_s=30.0, long_s=60.0)],
        interval_s=5.0, clock=clock)
    assert monitor.tick() is not None  # first tick evaluates
    assert monitor.state == "ok"
    # The tick cadence: nothing happens inside the interval.
    clock.t = 2.0
    assert monitor.tick() is None
    # Inject a p99 breach (every observation over threshold -> the whole
    # budget and then some); the NEXT evaluation must see it.
    for _ in range(200):
        metrics.observe("w", 1.0)
    clock.t = 5.1
    verdict = monitor.tick()
    assert verdict is not None and monitor.state_code == STATE_CRITICAL
    obj = verdict["objectives"]["p99"]
    assert obj["burn_short"] >= 6.0 and obj["burn_long"] >= 6.0
    assert metrics.counter(mn.SLO_EVALUATIONS) == 2
    assert metrics.summary()[mn.HEALTH_STATE] == STATE_CRITICAL


def test_slo_severity_requires_both_windows():
    class SplitWindows:
        """Short window burning, long window calm — the flap filter."""

        def counters(self):
            return {}

        def set_gauge(self, name, value):
            pass

        def incr(self, name, value=1.0):
            pass

        def window_count(self, name, horizon_s=None):
            return 100

        def fraction_above(self, name, threshold_s, horizon_s=None):
            return 1.0 if horizon_s <= 30.0 else 0.0

    monitor = SLOMonitor(SplitWindows(), [SLO(
        name="p99", kind="latency", window="w", threshold_s=0.1,
        target=0.99, short_s=30.0, long_s=600.0)], clock=FakeClock())
    verdict = monitor.evaluate()
    assert monitor.state_code == STATE_OK
    assert verdict["objectives"]["p99"]["burn_short"] >= 6.0
    assert verdict["objectives"]["p99"]["burn_long"] == 0.0


def test_slo_ratio_objective_and_hysteresis_recovery():
    metrics = Metrics()
    clock = FakeClock()
    monitor = SLOMonitor(metrics, [_ratio_slo()], interval_s=5.0,
                         recovery_evals=2, clock=clock)
    metrics.incr(mn.FRAMES_ADMITTED, 100)
    monitor.evaluate()
    assert monitor.state == "ok"
    # A drop storm: half the admitted frames die -> frac 0.5 against a
    # 0.1 budget -> burn 5 on both windows -> critical, immediately.
    clock.t = 10.0
    metrics.incr(mn.FRAMES_ADMITTED, 50)
    metrics.incr("frames_dropped_brownout", 25)
    monitor.evaluate()
    assert monitor.state_code == STATE_CRITICAL
    # Recovery de-escalates ONE level per recovery_evals calm evaluations
    # — critical -> warn -> ok takes four calm evals, never a flap.
    states = []
    for i in range(4):
        clock.t = 20.0 + 10.0 * i  # each eval's 5 s windows see no drops
        monitor.evaluate()
        states.append(monitor.state)
    assert states == ["critical", "warn", "warn", "ok"]
    assert metrics.counter(mn.SLO_TRANSITIONS) == 3  # up, down, down


def test_slo_gauge_objective_and_probe_failure_counted():
    metrics = Metrics()
    lag = {"rows": 2048.0}
    monitor = SLOMonitor(metrics, [SLO(
        name="durability_lag", kind="gauge",
        value_fn=lambda: lag["rows"], bound=1024.0,
        warn_burn=1.0, critical_burn=6.0)], clock=FakeClock())
    verdict = monitor.evaluate()
    assert verdict["objectives"]["durability_lag"]["burn"] == 2.0
    assert monitor.state_code == STATE_WARN
    # A dead probe reads burn 0 (no data is not a breach) but is counted.
    lag["rows"] = 0.0

    def boom():
        raise RuntimeError("probe died")

    monitor.objectives[0].value_fn = boom
    monitor.evaluate()
    assert metrics.counter(mn.SLO_PROBE_FAILURES) == 1


def test_slo_watchdog_events_hold_warn_then_expire():
    metrics = Metrics()
    clock = FakeClock()
    monitor = SLOMonitor(metrics, [], interval_s=1.0, recovery_evals=1,
                         event_window_s=10.0, clock=clock)
    monitor.evaluate()
    assert monitor.state == "ok"
    monitor.note_event("recompile_post_warmup")
    assert metrics.counter(
        mn.SLO_EVENTS_PREFIX + "recompile_post_warmup") == 1
    clock.t = 1.0
    verdict = monitor.evaluate()
    assert monitor.state == "warn"
    assert verdict["events"] == {"recompile_post_warmup": 1}
    # Outside the event window the hold releases (one calm eval at
    # recovery_evals=1).
    clock.t = 12.0
    monitor.evaluate()
    assert monitor.state == "ok"


def test_slo_critical_transition_emits_span_and_flight_dump(tmp_path):
    metrics = Metrics()
    tracer = Tracer(sample=1.0, dump_dir=str(tmp_path),
                    min_dump_interval_s=0.0)
    clock = FakeClock()
    monitor = SLOMonitor(metrics, [_ratio_slo()], tracer=tracer,
                         clock=clock)
    metrics.incr(mn.FRAMES_ADMITTED, 100)
    monitor.evaluate()
    clock.t = 10.0
    metrics.incr(mn.FRAMES_ADMITTED, 50)
    metrics.incr("frames_dropped_brownout", 50)
    monitor.evaluate()
    assert monitor.state_code == STATE_CRITICAL
    spans = [s for s in tracer.snapshot(topic=LIFECYCLE_TOPIC)
             if s["stage"] == "health"]
    assert spans and spans[-1]["to_state"] == "critical"
    dumps = [f for f in os.listdir(tmp_path) if "slo_critical" in f]
    assert len(dumps) == 1
    with open(tmp_path / dumps[0]) as fh:
        rec = json.load(fh)
    assert rec["extra"]["verdict"]["objectives"]["completion"]["burn"] > 2.0


def test_default_objectives_composition():
    objectives = default_objectives(drop_counters=("a",), state=None)
    assert [o.name for o in objectives] == ["interactive_p99",
                                            "queue_wait_p99", "completion"]

    class StateStub:
        rows_since_checkpoint = 7

    objectives = default_objectives(drop_counters=("a",), state=StateStub())
    assert objectives[-1].name == "durability_lag"
    assert objectives[-1].value_fn() == 7.0


# ---------- recompile watchdog over the serving loop ----------


def test_recompile_watchdog_silent_when_prewarmed_then_flags_injection():
    metrics = Metrics()
    tracer = Tracer(sample=1.0)
    monitor = SLOMonitor(metrics, [], interval_s=0.05, tracer=tracer)
    pipeline, service, connector = build_overload_stack(
        frame_shape=FRAME_HW, batch_size=4, dispatch_s=0.0,
        metrics=metrics, slo_monitor=monitor, tracer=tracer)
    # The warmup contract, minus the jax graphs: every ladder bucket
    # compiled, then the watchdog armed (exactly what warmup() does).
    pipeline.prewarm_batch_shapes(service._bucket_ladder, FRAME_HW,
                                  np.float32)
    service._warmed = True
    service.start(warmup=False)
    try:
        frame = np.zeros(FRAME_HW, np.float32)
        for i in range(8):
            connector.inject(FRAME_TOPIC, {"frame": frame,
                                           "meta": {"seq": i}})
        assert service.drain(timeout=10.0)
        # The whole prewarmed ladder served cache hits: silence.
        assert set(pipeline.batch_sizes_seen) <= set(service._bucket_ladder)
        assert metrics.counter(mn.RECOMPILES_POST_WARMUP) == 0
        # Injected post-warmup compile: losing the jit cache makes the
        # next dispatch a miss — counted, spanned, and a warn-level SLO
        # event visible on the next evaluation.
        pipeline.compiled_batch_sizes.clear()
        for i in range(8, 12):
            connector.inject(FRAME_TOPIC, {"frame": frame,
                                           "meta": {"seq": i}})
        assert service.drain(timeout=10.0)
        assert metrics.counter(mn.RECOMPILES_POST_WARMUP) >= 1
        assert metrics.counter(
            mn.SLO_EVENTS_PREFIX + "recompile_post_warmup") >= 1
        # The serving loop is ticking the monitor concurrently and
        # evaluate() yields to an in-flight evaluation (returns None) —
        # either way the event lands in the verdict within an interval.
        deadline = time.monotonic() + 5.0
        while ("recompile_post_warmup" not in monitor.verdict()["events"]
               and time.monotonic() < deadline):
            monitor.evaluate()
            time.sleep(0.01)
        assert "recompile_post_warmup" in monitor.verdict()["events"]
        assert monitor.state_code >= STATE_WARN
        spans = [s for s in tracer.snapshot(topic=LIFECYCLE_TOPIC)
                 if s["stage"] == "recompile"]
        assert spans and spans[0]["bucket"] in service._bucket_ladder
    finally:
        service.stop()


# ---------- supervisor publishes health transitions ----------


def test_supervisor_announces_health_transitions_edge_triggered():
    metrics = Metrics()
    monitor = SLOMonitor(metrics, [], interval_s=0.01, recovery_evals=1,
                         event_window_s=0.05)
    _pipeline, service, connector = build_overload_stack(
        frame_shape=FRAME_HW, batch_size=4, dispatch_s=0.0,
        metrics=metrics, slo_monitor=monitor)
    supervisor = ServiceSupervisor(service, poll_interval_s=10.0)
    monitor.evaluate()
    supervisor._check_health(service, STATUS_TOPIC)
    # The boring initial "ok" is not announced; unchanged state neither.
    supervisor._check_health(service, STATUS_TOPIC)
    assert not [m for m in connector.messages(STATUS_TOPIC)
                if m.get("status") == "health"]
    monitor.note_event("recompile_post_warmup")
    monitor.evaluate()
    supervisor._check_health(service, STATUS_TOPIC)
    supervisor._check_health(service, STATUS_TOPIC)  # no re-announce
    announcements = [m for m in connector.messages(STATUS_TOPIC)
                     if m.get("status") == "health"]
    assert len(announcements) == 1
    assert announcements[0]["state"] == "warn"
    assert announcements[0]["events"] == {"recompile_post_warmup": 1}


def test_supervisor_check_health_ticks_the_monitor_itself():
    # The supervisor is the always-on backstop ticker: without expo, a
    # wedged serving loop (the primary ticker) would otherwise freeze the
    # verdict at its last state and loop_liveness could never escalate.
    metrics = Metrics()
    monitor = SLOMonitor(metrics, [], interval_s=0.01)
    _pipeline, service, _connector = build_overload_stack(
        frame_shape=FRAME_HW, batch_size=4, dispatch_s=0.0,
        metrics=metrics, slo_monitor=monitor)
    supervisor = ServiceSupervisor(service, poll_interval_s=10.0)
    assert monitor.verdict()["evaluations"] == 0
    supervisor._check_health(service, STATUS_TOPIC)
    # The service was never started: only the supervisor's own tick can
    # have driven this evaluation.
    assert monitor.verdict()["evaluations"] >= 1


# ---------- Prometheus exposition: render + format lint ----------


def test_prom_render_families_labels_and_lint_clean():
    metrics = Metrics()
    metrics.incr(mn.FRAMES_COMPLETED, 5)
    metrics.set_gauge(mn.BROWNOUT_LEVEL, 1)
    metrics.set_gauge(mn.SLO_BURN_PREFIX + "completion", 1.5)
    metrics.incr(mn.FRAMES_REJECTED_PREFIX + "overload", 2)
    metrics.incr(mn.SLO_EVENTS_PREFIX + "recompile_post_warmup")
    for v in (0.001, 0.01, 0.1):
        metrics.observe(mn.QUEUE_WAIT, v)
    text = render(metrics)
    assert lint_prometheus_text(text) == []
    assert "# TYPE ocvf_frames_completed_total counter" in text
    assert "ocvf_frames_completed_total 5" in text
    assert "# TYPE ocvf_brownout_level gauge" in text
    # Dynamic prefix families fold into labels, one family each.
    assert 'ocvf_frames_rejected_total{reason="overload"} 2' in text
    assert 'ocvf_slo_burn{objective="completion"} 1.5' in text
    assert 'ocvf_slo_events_total{reason="recompile_post_warmup"} 1' in text
    # Histograms: cumulative buckets, +Inf == _count, sum present.
    assert "# TYPE ocvf_queue_wait_seconds histogram" in text
    assert 'ocvf_queue_wait_seconds_bucket{le="+Inf"} 3' in text
    assert "ocvf_queue_wait_seconds_count 3" in text


def test_prom_label_value_escaping():
    metrics = Metrics()
    weird = 'bad"reason\\with\nnewline'
    metrics.incr(mn.FRAMES_REJECTED_PREFIX + weird)
    text = render(metrics)
    assert lint_prometheus_text(text) == []
    assert r'reason="bad\"reason\\with\nnewline"' in text


def test_prom_format_lint_catches_malformations():
    cases = {
        "no TYPE": "ocvf_x_total 1\n",
        "TYPE after samples": ("ocvf_x_total 1\n"
                               "# TYPE ocvf_x_total counter\n"),
        "duplicate TYPE": ("# TYPE ocvf_x counter\n"
                           "# TYPE ocvf_x counter\nocvf_x 1\n"),
        "bogus kind": "# TYPE ocvf_x bogus\nocvf_x 1\n",
        "unparseable value": "# TYPE ocvf_x gauge\nocvf_x twelve\n",
        "illegal escape": ('# TYPE ocvf_h histogram\n'
                           'ocvf_h_bucket{le="a\\q"} 1\n'
                           'ocvf_h_bucket{le="+Inf"} 1\n'
                           'ocvf_h_sum 1\nocvf_h_count 1\n'),
        "missing +Inf": ('# TYPE ocvf_h histogram\n'
                         'ocvf_h_bucket{le="0.1"} 1\n'
                         'ocvf_h_sum 1\nocvf_h_count 1\n'),
        "non-cumulative": ('# TYPE ocvf_h histogram\n'
                           'ocvf_h_bucket{le="0.1"} 5\n'
                           'ocvf_h_bucket{le="+Inf"} 3\n'
                           'ocvf_h_sum 1\nocvf_h_count 3\n'),
        "+Inf != count": ('# TYPE ocvf_h histogram\n'
                          'ocvf_h_bucket{le="0.1"} 1\n'
                          'ocvf_h_bucket{le="+Inf"} 2\n'
                          'ocvf_h_sum 1\nocvf_h_count 3\n'),
    }
    for label, text in cases.items():
        assert lint_prometheus_text(text), f"lint missed: {label}"


# ---------- live expo endpoints: /prom, /health, /spans bounds ----------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=5.0) as resp:
        return resp.status, json.loads(resp.read().decode())


def _get_raw(url):
    with urllib.request.urlopen(url, timeout=5.0) as resp:
        return (resp.status, resp.headers.get("Content-Type"),
                resp.read().decode())


def _service_with_expo(slo_interval_s=0.05, refresh_s=10.0):
    metrics = Metrics()
    tracer = Tracer(sample=1.0)
    monitor = SLOMonitor(metrics, [SLO(
        name="queue_wait_p99", kind="latency", window=mn.QUEUE_WAIT,
        threshold_s=0.5, target=0.9, short_s=30.0, long_s=60.0)],
        interval_s=slo_interval_s, tracer=tracer)
    pipeline, service, connector = build_overload_stack(
        frame_shape=FRAME_HW, batch_size=4, dispatch_s=0.0,
        metrics=metrics, slo_monitor=monitor, tracer=tracer)
    expo = ExpoServer(service, port=0, refresh_s=refresh_s)
    return pipeline, service, connector, expo, monitor, metrics


def test_expo_prom_and_health_endpoints_live():
    _pipeline, service, connector, expo, monitor, metrics = \
        _service_with_expo()
    service.start(warmup=False)
    expo.start()
    base = f"http://{expo.host}:{expo.port}"
    try:
        frame = np.zeros(FRAME_HW, np.float32)
        for i in range(8):
            connector.inject(FRAME_TOPIC, {"frame": frame,
                                           "meta": {"seq": i}})
        assert service.drain(timeout=10.0)

        status, index = _get_json(base + "/")
        assert "/prom" in index["endpoints"] and "/health" in index["endpoints"]
        # /prom: Prometheus content type, lints clean, carries the live
        # counters and the e2e histogram family.
        status, ctype, text = _get_raw(base + "/prom")
        assert status == 200 and ctype.startswith("text/plain")
        assert lint_prometheus_text(text) == []
        assert "ocvf_frames_completed_total 8" in text
        assert "# TYPE ocvf_e2e_latency_seconds histogram" in text
        # /health: ok after the serving loop's tick evaluated.
        status, health = _get_json(base + "/health")
        assert status == 200 and health["state"] == "ok"
        assert "queue_wait_p99" in health["objectives"]
        # An injected p99 breach flips the verdict within one evaluation
        # interval — and critical answers 503 for probes/load balancers.
        for _ in range(200):
            metrics.observe(mn.QUEUE_WAIT, 5.0)
        monitor.evaluate()
        with pytest.raises(urllib.error.HTTPError) as err:
            _get_json(base + "/health")
        assert err.value.code == 503
        body = json.loads(err.value.read().decode())
        assert body["state"] == "critical"
        assert body["objectives"]["queue_wait_p99"]["burn_short"] >= 6.0
    finally:
        expo.stop()
        service.stop()


def test_expo_health_without_monitor():
    expo = ExpoServer(metrics=Metrics(), port=0, refresh_s=10.0)
    expo.start()
    try:
        status, health = _get_json(
            f"http://{expo.host}:{expo.port}/health")
        assert status == 200 and health["state"] is None
    finally:
        expo.stop()


def test_expo_spans_limit_bounds_checking():
    metrics = Metrics()
    tracer = Tracer(sample=1.0)
    for _ in range(20):
        tracer.emit(tracer.new_trace(), "receive", topic="t")
    expo = ExpoServer(tracer=tracer, metrics=metrics, port=0,
                      refresh_s=10.0)
    expo.start()
    base = f"http://{expo.host}:{expo.port}"
    try:
        status, spans = _get_json(base + "/spans?topic=t&limit=5")
        assert status == 200 and len(spans["spans"]) == 5
        status, spans = _get_json(base + "/spans?n=7")  # legacy alias
        assert status == 200 and len(spans["spans"]) == 7
        status, spans = _get_json(base + "/spans?limit=999999")  # clamped
        assert status == 200 and len(spans["spans"]) == 20
        for bad in ("abc", "0", "-3", "1.5"):
            with pytest.raises(urllib.error.HTTPError) as err:
                _get_json(base + f"/spans?limit={bad}")
            assert err.value.code == 400, bad
            assert "limit" in json.loads(err.value.read().decode())["error"]
    finally:
        expo.stop()


def test_expo_concurrent_get_hammer_no_500s_counters_consistent():
    _pipeline, service, connector, expo, _monitor, metrics = \
        _service_with_expo()
    service.start(warmup=False)
    expo.start()
    base = f"http://{expo.host}:{expo.port}"
    paths = ("/metrics", "/prom", "/health", "/ledger", "/brownout",
             "/spans?limit=50")
    statuses = []
    lock = threading.Lock()

    def hammer(worker):
        got = []
        for i in range(24):
            url = base + paths[(worker + i) % len(paths)]
            try:
                with urllib.request.urlopen(url, timeout=10.0) as resp:
                    resp.read()
                    got.append(resp.status)
            except urllib.error.HTTPError as err:
                got.append(err.code)
        with lock:
            statuses.extend(got)

    try:
        frame = np.zeros(FRAME_HW, np.float32)
        for i in range(8):
            connector.inject(FRAME_TOPIC, {"frame": frame,
                                           "meta": {"seq": i}})
        threads = [threading.Thread(target=hammer, args=(w,))
                   for w in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(statuses) == 8 * 24
        assert set(statuses) == {200}  # no 500s, no flapping health
        assert service.drain(timeout=10.0)
        assert metrics.counter(mn.EXPO_ERRORS) == 0
        assert metrics.counter(mn.EXPO_REQUESTS) >= len(statuses)
    finally:
        expo.stop()
        service.stop()


# ---------- bench_compare: the perf-regression gate ----------


def _smoke_doc(e2e=10.0, ready=3.0, dropped=0, p99=80.0, done=120,
               offered=120, ratio=1.0, scaleout_x2=2.0, parity=1.0,
               cutover_ratio=0.95, ingest_p99=0.6, ingest_uplift=2.5,
               cascade_uplift=4.0, video_uplift=2.8, failover_s=0.25,
               registry_parity=1.0, registry_ratio=0.93):
    return {
        "modes": {"overlapped": {
            "e2e_p50_ms": e2e, "dropped_frames": dropped,
            "decomposition_ms": {"ready_wait_p50_ms": ready}}},
        "overload_sweep": {"rows": [
            {"offered_multiplier": 4.0, "interactive_e2e_p99_ms": p99,
             "interactive_offered": offered,
             "interactive_completed": done}]},
        "tracing_overhead": {"p50_ratio": ratio},
        "replica_scaleout": {"scaling": {"x2": scaleout_x2}},
        "rollout": {"parity_agreement": parity,
                    "cutover_window_completed_ratio": cutover_ratio},
        "registry": {"parity_agreement": registry_parity,
                     "swap_window_completed_ratio": registry_ratio},
        "ingest": {"h2d": {"32": {"uint8_ring": {"p99_ms": ingest_p99}}},
                   "uplift": {"b32": {"uplift": ingest_uplift}}},
        "cascade": {"uplift": {"d0": {"uplift": cascade_uplift}}},
        "video": {"cells": {"c90": {"uplift": video_uplift}}},
        "partition": {"failover_s": failover_s},
    }


def _write(tmp_path, name, doc):
    path = tmp_path / name
    path.write_text(json.dumps(doc))
    return str(path)


def test_bench_compare_self_compare_is_clean(tmp_path):
    base = _write(tmp_path, "base.json", _smoke_doc())
    assert bench_compare.main([base, base]) == 0
    report = bench_compare.compare(_smoke_doc(), _smoke_doc())
    assert report["ok"] and not report["regressions"]
    assert all(r["verdict"] == "ok" for r in report["metrics"])


def test_bench_compare_flags_each_regression_direction(tmp_path):
    base = _write(tmp_path, "base.json", _smoke_doc())
    # e2e p50 doubled: above 1.10x + 0.5 ms.
    assert bench_compare.main(
        [base, _write(tmp_path, "a.json", _smoke_doc(e2e=25.0))]) == 1
    # completion ratio collapsed: below 0.98x (a higher-is-better
    # metric). The ratio — not the raw completed count — is what gates:
    # the offer loop is time-based, so counts drift between clean runs.
    assert bench_compare.main(
        [base, _write(tmp_path, "b.json", _smoke_doc(done=50))]) == 1
    # A clean run that simply OFFERED fewer frames (run-to-run drift at
    # 100% completion) stays green — the absolute-count false positive.
    assert bench_compare.main(
        [base, _write(tmp_path, "b2.json",
                      _smoke_doc(done=100, offered=100))]) == 0
    # tracing overhead ratio drifted past the absolute threshold.
    assert bench_compare.main(
        [base, _write(tmp_path, "c.json", _smoke_doc(ratio=1.05))]) == 1
    # Replica scale-out collapsed: below 0.90x of the baseline's 2.0x
    # (a candidate may not quietly lose the router's scaling win).
    assert bench_compare.main(
        [base, _write(tmp_path, "e.json", _smoke_doc(scaleout_x2=1.2))]) == 1
    # Small jitter inside thresholds stays green.
    assert bench_compare.main(
        [base, _write(tmp_path, "d.json",
                      _smoke_doc(e2e=10.6, p99=85.0, done=118,
                                 scaleout_x2=1.9))]) == 0


def test_bench_compare_missing_metric_and_overrides(tmp_path):
    base = _write(tmp_path, "base.json", _smoke_doc())
    gone = _smoke_doc()
    del gone["tracing_overhead"]
    candidate = _write(tmp_path, "gone.json", gone)
    # The candidate stopped measuring something: structural regression...
    assert bench_compare.main([base, candidate]) == 1
    # ...unless explicitly allowed.
    assert bench_compare.main([base, candidate, "--allow-missing"]) == 0
    # Absent from BOTH artifacts: skipped, not failed.
    both = _write(tmp_path, "both.json", gone)
    assert bench_compare.main([both, both]) == 0
    # Asymmetry: a BASELINE predating the metric (older artifact) has
    # nothing to regress from — skipped, the gate stays green.
    assert bench_compare.main([candidate, base]) == 0
    report = bench_compare.compare(gone, _smoke_doc())
    (row,) = [r for r in report["metrics"]
              if r["metric"] == "tracing_p50_ratio"]
    assert row["verdict"] == "skipped" and "predates" in row["note"]
    # Threshold override loosens one metric's gate.
    slow = _write(tmp_path, "slow.json", _smoke_doc(e2e=25.0))
    assert bench_compare.main(
        [base, slow, "--threshold", "overlapped_e2e_p50_ms=3.0"]) == 0
    # Unusable input: unknown threshold, bad number, garbage file -> rc 2.
    assert bench_compare.main([base, slow, "--threshold", "nope=1"]) == 2
    assert bench_compare.main(
        [base, slow, "--threshold", "overlapped_e2e_p50_ms=x"]) == 2
    garbage = tmp_path / "garbage.json"
    garbage.write_text("not json")
    assert bench_compare.main([base, str(garbage)]) == 2
    assert bench_compare.main([base, str(tmp_path / "missing.json")]) == 2


def test_bench_compare_json_report_shape(tmp_path, capsys):
    base = _write(tmp_path, "base.json", _smoke_doc())
    cand = _write(tmp_path, "cand.json", _smoke_doc(e2e=25.0))
    assert bench_compare.main([base, cand, "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["ok"] is False
    by_name = {r["metric"]: r for r in report["metrics"]}
    assert by_name["overlapped_e2e_p50_ms"]["verdict"] == "regression"
    assert by_name["overlapped_e2e_p50_ms"]["limit"] == pytest.approx(11.5)


@pytest.mark.skipif(
    not os.path.exists(os.path.join(REPO_ROOT, "BENCH_SERVING_smoke.json")),
    reason="no committed smoke artifact")
def test_bench_compare_real_artifact_self_compare():
    artifact = os.path.join(REPO_ROOT, "BENCH_SERVING_smoke.json")
    assert bench_compare.main([artifact, artifact]) == 0


# ---------- journal --stage filter ----------


def test_journal_cli_stage_filter_and_composition(tmp_path, capsys):
    from opencv_facerecognizer_tpu.runtime import journal as journal_mod

    path = str(tmp_path / "dead.jsonl")
    journal = DeadLetterJournal(path)
    journal.append("stale", [journal.frame_entry(
        meta={"seq": 1}, trace_id=11, stage="batcher.stale")])
    journal.append("dead_letter", [journal.frame_entry(
        meta={"seq": 2}, trace_id=22, stage="readback.dead_letter")])
    journal.append("stale", [journal.frame_entry(
        meta={"seq": 3}, trace_id=33, stage="batcher.stale")])
    journal.close()

    assert journal_mod.main([path, "--stage", "batcher.stale"]) == 0
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["frames"][0]["meta"]["seq"] for r in rows] == [1, 3]
    # Filters compose (AND): stage + trace narrows to one frame.
    assert journal_mod.main(
        [path, "--stage", "batcher.stale", "--trace", "33"]) == 0
    rows = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["frames"][0]["meta"]["seq"] for r in rows] == [3]
    # An unmatched stage prints nothing and still exits 0.
    assert journal_mod.main([path, "--stage", "nope"]) == 0
    assert capsys.readouterr().out.strip() == ""

"""Ingest-pipeline suite (ISSUE 12): the pre-allocated staging ring (zero
steady-state allocations, exhaustion backpressure), uint8 end-to-end
staging with the recompile watchdog green, compressed-frame intake through
the off-thread decode pool (corrupt payloads dead-letter with exact ledger
settlement), the ``decode: slow``/``decode: corrupt`` chaos pair, and the
bench_compare tracking of the ingest gate's numbers.

Everything runs over ``runtime.fakes.InstantPipeline`` — the ingest layer
is host-side control flow; nothing here needs hardware.
"""

import time

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime import (
    AdmissionController,
    FakeConnector,
    FaultInjector,
    IngestConfig,
    RecognizerService,
    ResiliencePolicy,
    StagingRing,
)
from opencv_facerecognizer_tpu.runtime import ingest as ingest_mod
from opencv_facerecognizer_tpu.runtime.fakes import (
    InstantPipeline,
    synthetic_jpeg_frames,
)
from opencv_facerecognizer_tpu.runtime.ingest import (
    decode_jpeg,
    encode_jpeg,
    encode_jpeg_message,
    jpeg_supported,
)
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    RESULT_TOPIC,
)
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.metrics import Metrics

FRAME_HW = (16, 16)

needs_jpeg = pytest.mark.skipif(not jpeg_supported(),
                                reason="no JPEG codec (PIL/cv2) available")


def _wait(cond, timeout=10.0, interval=0.01) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _frame():
    return np.zeros(FRAME_HW, np.float32)


def _service(pipeline=None, **kwargs):
    pipeline = pipeline or InstantPipeline(FRAME_HW)
    connector = FakeConnector()
    kwargs.setdefault("batch_size", 4)
    kwargs.setdefault("metrics", Metrics())
    kwargs.setdefault("resilience", ResiliencePolicy(readback_deadline_s=2.0))
    service = RecognizerService(
        pipeline, connector, frame_shape=FRAME_HW,
        flush_timeout=0.02, similarity_threshold=0.0, **kwargs,
    )
    return pipeline, service, connector


def _assert_settled(service):
    ledger = service.ledger()
    assert ledger["in_system"] == 0, ledger


# ---------- StagingRing ----------


def test_staging_ring_preallocates_per_rung_and_recycles():
    metrics = Metrics()
    ring = StagingRing([4, 8], FRAME_HW, np.uint8, depth=2, metrics=metrics)
    assert ring.preallocated == 4
    assert metrics.counter(mn.INGEST_STAGING_ALLOCS) == 4
    # Smallest fitting rung wins; the buffer is rung-sized, not padded.
    buf = ring.acquire(3)
    assert buf.shape == (4, *FRAME_HW) and buf.dtype == np.uint8
    big = ring.acquire(5)
    assert big.shape == (8, *FRAME_HW)
    ring.release(buf)
    again = ring.acquire(2)
    assert again is not None and again.shape == (4, *FRAME_HW)
    assert ring.alloc_count == ring.preallocated  # recycled, no new alloc
    assert metrics.counter(mn.INGEST_STAGING_REUSE) >= 3
    # Foreign shapes/dtypes are dropped silently, like the legacy pool.
    ring.release(np.zeros((4, 3, 3), np.uint8))
    ring.release(np.zeros((4, *FRAME_HW), np.float32))
    assert ring.stats()["free"] == {4: 1, 8: 1}


def test_staging_ring_exhaustion_never_allocates_and_heals_on_forfeit():
    metrics = Metrics()
    ring = StagingRing([4], FRAME_HW, np.uint8, depth=1, metrics=metrics)
    held = ring.acquire(4)
    assert held is not None
    # Every buffer in flight: acquire refuses (backpressure), no alloc.
    assert ring.acquire(1) is None
    assert ring.alloc_count == ring.preallocated
    assert metrics.counter(mn.INGEST_STAGING_EXHAUSTED) == 1
    assert ring.free_slots() == 0
    # A release notification wakes parked consumers.
    woken = []
    ring.add_notify(lambda: woken.append(1))
    ring.release(held)
    assert woken == [1]
    assert ring.acquire(1) is not None
    # Forfeit (dead-letter path): the lost buffer opens ONE replacement
    # allocation credit — the ring heals instead of shrinking forever.
    lost = ring.acquire(4)
    assert lost is None  # still held by the earlier acquire
    ring.forfeit(held)
    replacement = ring.acquire(4)
    assert replacement is not None and replacement is not held
    assert ring.alloc_count == ring.preallocated + 1
    assert metrics.counter(mn.INGEST_STAGING_FORFEITS) == 1
    assert metrics.counter(mn.INGEST_STAGING_ALLOCS) == ring.preallocated + 1


def test_batcher_rejects_mismatched_ring():
    from opencv_facerecognizer_tpu.runtime.batcher import FrameBatcher

    ring = StagingRing([4], FRAME_HW, np.uint8, depth=1)
    with pytest.raises(ValueError):
        FrameBatcher(4, FRAME_HW, dtype=np.float32, staging_ring=ring)
    with pytest.raises(ValueError):
        FrameBatcher(8, FRAME_HW, dtype=np.uint8, staging_ring=ring)


# ---------- uint8 mode end-to-end ----------


def test_uint8_mode_zero_steady_state_allocs_and_watchdog_green():
    metrics = Metrics()
    pipeline, service, connector = _service(
        metrics=metrics, ingest=IngestConfig(mode="uint8"))
    assert service.batcher.dtype == np.uint8
    # warmup() prewarms the ladder at the INGEST dtype (the uint8 entry
    # signatures), then the watchdog arms — mirrored here without jax.
    pipeline.prewarm_batch_shapes(service._bucket_ladder, FRAME_HW,
                                  service.batcher.dtype)
    service._warmed = True
    service.start(warmup=False)
    try:
        for i in range(64):
            connector.inject(FRAME_TOPIC, {"frame": _frame(),
                                           "meta": {"seq": i}})
        assert service.drain(timeout=20.0)
    finally:
        service.stop()
    c = metrics.counters()
    assert c[mn.FRAMES_COMPLETED] == 64
    # The acceptance assertion: steady-state staging allocated NOTHING
    # beyond the ring's construction-time preallocation, and every
    # dispatch was a jit-cache hit at the uint8 signature.
    assert c[mn.INGEST_STAGING_ALLOCS] == service.ingest.staging.preallocated
    assert c[mn.INGEST_STAGING_REUSE] > 0
    assert c.get(mn.RECOMPILES_POST_WARMUP, 0) == 0
    assert c[mn.INGEST_UPLOAD_BYTES] > 0  # frames crossed as uint8
    _assert_settled(service)


def test_f32_prewarm_with_uint8_serving_trips_watchdog():
    """The dtype IS a compile signature: prewarming only f32 while the
    ingest mode stages uint8 must read as a post-warmup recompile — the
    exact hole the uint8 prewarm coverage exists to close."""
    metrics = Metrics()
    pipeline, service, connector = _service(
        metrics=metrics, ingest=IngestConfig(mode="uint8"))
    pipeline.prewarm_batch_shapes(service._bucket_ladder, FRAME_HW,
                                  np.float32)  # the WRONG dtype
    service._warmed = True
    service.start(warmup=False)
    try:
        connector.inject(FRAME_TOPIC, {"frame": _frame(), "meta": {}})
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert metrics.counter(mn.RECOMPILES_POST_WARMUP) >= 1


# ---------- compressed-frame intake ----------


@needs_jpeg
def test_synthetic_jpeg_generator_is_seeded_and_roundtrips():
    a = synthetic_jpeg_frames(3, FRAME_HW, seed=5, faces_per_frame=1)
    b = synthetic_jpeg_frames(3, FRAME_HW, seed=5, faces_per_frame=1)
    assert [p for p, _ in a] == [p for p, _ in b]  # byte-identical
    assert [p for p, _ in a] != [
        p for p, _ in synthetic_jpeg_frames(3, FRAME_HW, seed=6,
                                            faces_per_frame=1)]
    payload, src = a[0]
    decoded = decode_jpeg(payload)
    assert decoded.shape == FRAME_HW
    # Lossy but close: the decoded frame is the source frame, not noise.
    assert float(np.abs(decoded.astype(np.int32)
                        - src.astype(np.int32)).mean()) < 16.0


@needs_jpeg
def test_jpeg_intake_decodes_off_thread_and_completes():
    metrics = Metrics()
    from opencv_facerecognizer_tpu.utils.tracing import Tracer

    tracer = Tracer(sample=1.0)
    pipeline, service, connector = _service(
        metrics=metrics, tracer=tracer, ingest=IngestConfig(mode="jpeg"))
    service.start(warmup=False)
    n = 16
    try:
        for i, (payload, _src) in enumerate(
                synthetic_jpeg_frames(n, FRAME_HW, seed=2)):
            connector.inject(FRAME_TOPIC, {**encode_jpeg_message(payload),
                                           "meta": {"seq": i}})
        assert service.drain(timeout=20.0)
    finally:
        service.stop()
    c = metrics.counters()
    assert c[mn.DECODE_FRAMES] == n
    assert c[mn.FRAMES_COMPLETED] == n
    assert not np.isnan(metrics.percentile(mn.DECODE_LATENCY, 50))
    # Every frame carries a decode span off the connector thread.
    spans = [s for s in tracer.snapshot(topic=FRAME_TOPIC)
             if s["stage"] == "decode"]
    assert len(spans) == n and all(s["ok"] for s in spans)
    assert len(connector.messages(RESULT_TOPIC)) == n
    _assert_settled(service)


@needs_jpeg
def test_corrupt_jpeg_dead_letters_with_exact_settlement(tmp_path):
    from opencv_facerecognizer_tpu.runtime import DeadLetterJournal
    from opencv_facerecognizer_tpu.utils.tracing import Tracer

    metrics = Metrics()
    tracer = Tracer(sample=1.0)
    journal = DeadLetterJournal(str(tmp_path / "dead.jsonl"),
                                metrics=metrics)
    pipeline, service, connector = _service(
        metrics=metrics, tracer=tracer, dead_letter_journal=journal,
        ingest=IngestConfig(mode="jpeg"))
    service.start(warmup=False)
    good = synthetic_jpeg_frames(4, FRAME_HW, seed=9)
    try:
        for i, (payload, _src) in enumerate(good):
            connector.inject(FRAME_TOPIC, {**encode_jpeg_message(payload),
                                           "meta": {"seq": i}})
        # Truncated and garbage payloads: both must dead-letter.
        connector.inject(FRAME_TOPIC, {
            **encode_jpeg_message(good[0][0][:12]), "meta": {"seq": 96}})
        connector.inject(FRAME_TOPIC, {
            **encode_jpeg_message(b"not a jpeg"), "meta": {"seq": 97}})
        assert service.drain(timeout=20.0)
    finally:
        service.stop()
        journal.close()
    c = metrics.counters()
    assert c[mn.FRAMES_COMPLETED] == 4
    assert c[mn.FRAMES_DROPPED_DECODE] == 2
    assert c[mn.DECODE_ERRORS] == 2
    _assert_settled(service)  # admitted == completed + drops, exactly
    # Journal rows carry the decode_error reason + the frame's meta.
    records = [r for r in journal.records() if r["reason"] == "decode_error"]
    assert len(records) == 2
    seqs = {e["meta"]["seq"] for r in records for e in r["frames"]}
    assert seqs == {96, 97}
    assert all(e["stage"] == "ingest.decode"
               for r in records for e in r["frames"])
    # Terminal spans mirror the ledger split.
    outcomes = [s.get("outcome") for s in tracer.snapshot(topic=FRAME_TOPIC)
                if s["stage"] == "settle"]
    assert outcomes.count(mn.FRAMES_DROPPED_DECODE) == 2
    assert outcomes.count("completed") == 4


@needs_jpeg
def test_decode_fault_pair_slow_and_corrupt_chaos():
    """The fast chaos variant of the ``decode`` boundary: one scripted
    slow decode (completes, just late — absorbed off the hot thread) and
    one scripted corrupt decode (dead-letters), with the injector's
    counts matching the metrics exactly."""
    injector = FaultInjector(slow_decode_s=0.15)
    injector.script("decode", "slow", "corrupt")
    metrics = Metrics()
    pipeline, service, connector = _service(
        metrics=metrics, fault_injector=injector,
        ingest=IngestConfig(mode="jpeg", decode_workers=1))
    service.start(warmup=False)
    payloads = synthetic_jpeg_frames(3, FRAME_HW, seed=4)
    t0 = time.monotonic()
    try:
        for i, (payload, _src) in enumerate(payloads):
            connector.inject(FRAME_TOPIC, {**encode_jpeg_message(payload),
                                           "meta": {"seq": i}})
        assert service.drain(timeout=20.0)
    finally:
        service.stop()
    assert time.monotonic() - t0 >= 0.15  # the slow fault really stalled
    c = metrics.counters()
    assert injector.injected == {"decode:slow": 1, "decode:corrupt": 1}
    assert c[mn.FRAMES_COMPLETED] == 2  # slow one still completed
    assert c[mn.FRAMES_DROPPED_DECODE] == 1
    _assert_settled(service)


@needs_jpeg
def test_decode_backlog_overflow_is_an_explicit_ledger_drop():
    metrics = Metrics()
    injector = FaultInjector(slow_decode_s=0.2)
    injector.script("decode", *["slow"] * 8)
    pipeline, service, connector = _service(
        metrics=metrics, fault_injector=injector,
        ingest=IngestConfig(mode="jpeg", decode_workers=1, decode_queue=2))
    service.start(warmup=False)
    payloads = synthetic_jpeg_frames(8, FRAME_HW, seed=7)
    try:
        for i, (payload, _src) in enumerate(payloads):
            connector.inject(FRAME_TOPIC, {**encode_jpeg_message(payload),
                                           "meta": {"seq": i}})
        assert service.drain(timeout=30.0)
    finally:
        service.stop()
    c = metrics.counters()
    assert c[mn.FRAMES_DROPPED_DECODE] >= 1  # backlog overflow, counted
    _assert_settled(service)


@needs_jpeg
def test_raising_sink_never_kills_a_decode_worker():
    """A raising intake continuation (journal IOError under stress, a
    brownout-path bug) must cost that FRAME — settled through on_error —
    never the worker thread: a dead pool with submit() still accepting
    would silently stop all camera traffic."""
    from opencv_facerecognizer_tpu.runtime import DecodeWorkerPool
    from opencv_facerecognizer_tpu.runtime.ingest import encode_jpeg_message

    metrics = Metrics()
    pool = DecodeWorkerPool(workers=1, metrics=metrics)
    settled = []

    def bad_sink(frame, message, priority, tid):
        raise RuntimeError("intake bug")

    def on_error(message, priority, tid, reason):
        settled.append((message.get("meta"), reason))
        if len(settled) == 2:
            raise RuntimeError("settlement bug too")  # worker survives this

    pool.start(bad_sink, on_error)
    try:
        payloads = synthetic_jpeg_frames(3, FRAME_HW, seed=8)
        for i, (p, _src) in enumerate(payloads):
            assert pool.submit({**encode_jpeg_message(p),
                                "meta": {"seq": i}}, 0, 0)
        assert _wait(pool.idle, timeout=10.0)
    finally:
        pool.stop()
    # Every frame hit the failing sink; each one was routed to on_error
    # (even after on_error itself raised once) and the worker outlived
    # all of it.
    assert [m["seq"] for m, _r in settled] == [0, 1, 2]
    assert all(r == "decode_error" for _m, r in settled)
    assert metrics.counter(mn.DECODE_ERRORS) >= 3


def test_publish_crash_recycles_the_staging_buffer():
    """A publish crash after a COMPLETED readback must return the
    staging buffer to the bounded ring — dropping it would shrink the
    ring by one per crash (no heal credit) until every frame sheds
    against a ring that can never refill."""
    from opencv_facerecognizer_tpu.runtime.recognizer import STATUS_TOPIC

    class ExplodingConnector(FakeConnector):
        explode = True

        def publish(self, topic, message):
            if topic == RESULT_TOPIC and self.explode:
                raise RuntimeError("result sink down")
            super().publish(topic, message)

    metrics = Metrics()
    connector = ExplodingConnector()
    service = RecognizerService(
        InstantPipeline(FRAME_HW), connector, batch_size=4,
        frame_shape=FRAME_HW, flush_timeout=0.02, similarity_threshold=0.0,
        metrics=metrics,
        resilience=ResiliencePolicy(readback_deadline_s=2.0),
        ingest=IngestConfig(mode="uint8", ring_depth=1))
    service.start(warmup=False)
    try:
        connector.inject(FRAME_TOPIC, {"frame": _frame(), "meta": {"seq": 0}})
        assert _wait(lambda: service.loop_crashed, timeout=10.0)
        # The crash path recycled: the depth-1 ring is whole again.
        assert _wait(lambda: service.ingest.staging.free_slots() == 1,
                     timeout=5.0)
        assert service.ingest.staging.alloc_count == 1
        # And after the supervisor-style restart, the SAME buffer serves.
        connector.explode = False
        service.restart_loop()
        connector.inject(FRAME_TOPIC, {"frame": _frame(), "meta": {"seq": 1}})
        assert _wait(lambda: metrics.counter(mn.FRAMES_COMPLETED) >= 1,
                     timeout=10.0)
    finally:
        service.stop()
    assert any(m.get("status") == "crashed"
               for m in connector.messages(STATUS_TOPIC))


# ---------- ring exhaustion under flood -> admission backpressure ----------


def test_ring_exhaustion_floods_backpressure_through_admission():
    """Flood a slow backend with a depth-1 ring: in-flight batches hold
    every staging buffer, the exhausted ring keeps new batches queued,
    and admission rejects at the front door with reason ``staging`` —
    zero allocations beyond the preallocation, exact settlement after."""
    metrics = Metrics()
    pipeline, service, connector = _service(
        pipeline=InstantPipeline(FRAME_HW, compute_s=0.15),
        metrics=metrics, inflight_depth=4,
        admission=AdmissionController(),
        ingest=IngestConfig(mode="uint8", ring_depth=1))
    assert (service.admission.staging_free_fn.__self__
            is service.ingest.staging)
    service.start(warmup=False)
    offered = 0
    staging_reason = mn.FRAMES_REJECTED_PREFIX + "staging"
    try:
        # Opening burst: admitted while the ring still has its one free
        # buffer, so several batches' worth QUEUE — the consumer then
        # finds the ring exhausted and waits, never allocates. (The
        # exhaustion-episode COUNTER is pinned by the deterministic ring
        # unit tests above; asserting it here would race serve-loop
        # scheduling on a noisy box.)
        for _ in range(16):
            connector.inject(FRAME_TOPIC, {"frame": _frame(),
                                           "meta": {"seq": offered}})
            offered += 1
        # Paced flood until the front door demonstrably closed: each
        # in-flight batch holds the only buffer for compute_s at a time,
        # so offers keep landing while free_slots == 0 until admission
        # rejects one with reason ``staging`` — deadline-bounded instead
        # of a fixed count, so a scheduler stall between batches cannot
        # let every offer slip through a momentarily-free ring.
        deadline = time.monotonic() + 20.0
        while (metrics.counter(staging_reason) == 0
               and time.monotonic() < deadline):
            connector.inject(FRAME_TOPIC, {"frame": _frame(),
                                           "meta": {"seq": offered}})
            offered += 1
            time.sleep(0.005)
        assert service.drain(timeout=60.0)
    finally:
        service.stop()
    c = metrics.counters()
    rejected = c.get(staging_reason, 0)
    assert rejected > 0, c
    # Never an allocation: the flood was absorbed by shedding, not memory.
    assert c[mn.INGEST_STAGING_ALLOCS] == service.ingest.staging.preallocated
    assert c[mn.FRAMES_COMPLETED] + rejected == offered
    _assert_settled(service)


def test_ring_depth_auto_sizes_to_cover_pipeline_overlap():
    """The default (auto) ring depth must never cap overlap below the
    in-flight window: every overlapped batch holds a buffer, plus the
    batch being assembled — inflight_depth + 2 per rung. An explicit
    depth is honored as given."""
    assert IngestConfig(mode="uint8").resolve_ring_depth(4) == 6
    assert IngestConfig(mode="uint8", ring_depth=1).resolve_ring_depth(4) == 1
    pipeline, service, connector = _service(
        inflight_depth=3, ingest=IngestConfig(mode="uint8"))
    assert service.ingest.staging.depth == 5


def test_free_slots_tracks_the_top_rung_only():
    """The admission 'staging' signal is the TOP rung's availability:
    acquire only falls upward, so small-rung buffers can never stage a
    full batch — counting them would leave the front door open while
    every full-batch flush is parked."""
    ring = StagingRing([4, 8], FRAME_HW, np.uint8, depth=1)
    assert ring.free_slots() == 1  # one top-rung buffer, not two buffers
    held = ring.acquire(8)
    assert ring.free_slots() == 0  # the rung-4 buffer doesn't count
    assert ring.acquire(2) is not None  # ...but partial batches still stage
    ring.forfeit(held)
    assert ring.free_slots() == 1  # heal credit: not wedged


def test_exhaustion_counts_episodes_not_polls():
    metrics = Metrics()
    ring = StagingRing([4], FRAME_HW, np.uint8, depth=1, metrics=metrics)
    ring.acquire(4)
    assert ring.acquire(4) is None  # episode starts: counted
    for _ in range(10):  # the parked consumer's re-checks: quiet
        assert ring.acquire(4, quiet=True) is None
    assert metrics.counter(mn.INGEST_STAGING_EXHAUSTED) == 1


# ---------- registry / wiring / bench plumbing ----------


def test_ingest_metric_names_registered_and_in_ledger():
    names = set(mn.all_names())
    for name in (mn.INGEST_STAGING_ALLOCS, mn.INGEST_STAGING_REUSE,
                 mn.INGEST_STAGING_EXHAUSTED, mn.INGEST_STAGING_FORFEITS,
                 mn.INGEST_STAGING_FREE, mn.INGEST_UPLOAD,
                 mn.INGEST_UPLOAD_BYTES, mn.DECODE_LATENCY,
                 mn.DECODE_QUEUE_DEPTH, mn.DECODE_FRAMES, mn.DECODE_ERRORS,
                 mn.FRAMES_DROPPED_DECODE):
        assert name in names
    assert mn.FRAMES_DROPPED_DECODE in RecognizerService.LEDGER_DROP_COUNTERS


def test_lint_wiring_knows_the_ingest_attrs():
    from tools.ocvf_lint.wiring import ATTR_HINTS, HOT_PATH_SUFFIXES

    assert ATTR_HINTS["ingest"] == "IngestPipeline"
    assert ATTR_HINTS["staging"] == "StagingRing"
    assert ATTR_HINTS["decoder"] == "DecodeWorkerPool"
    assert any(s.endswith("runtime/ingest.py") for s in HOT_PATH_SUFFIXES)


def test_bench_compare_tracks_ingest_metrics():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "bench_compare.py"))
    bench_compare = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_compare)

    def artifact(p99, uplift):
        return {"ingest": {
            "h2d": {"32": {"uint8_ring": {"p99_ms": p99}}},
            "uplift": {"b32": {"uplift": uplift}}}}

    # Self-compare: exact zero regression.
    report = bench_compare.compare(artifact(0.5, 2.0), artifact(0.5, 2.0))
    verdicts = {r["metric"]: r["verdict"] for r in report["metrics"]}
    assert verdicts["ingest_h2d_p99_ms"] == "ok"
    assert verdicts["ingest_completed_uplift"] == "ok"
    # A blown p99 tail or lost uplift is a tracked regression.
    report = bench_compare.compare(artifact(0.5, 2.0), artifact(5.0, 2.0))
    assert not report["ok"]
    report = bench_compare.compare(artifact(0.5, 2.0), artifact(0.5, 1.0))
    assert not report["ok"]
    # The candidate silently dropping the measurement fails structurally.
    report = bench_compare.compare(artifact(0.5, 2.0), {})
    assert not report["ok"]


@needs_jpeg
def test_ingest_smoke_section_shape():
    """A miniature run of the smoke's ingest section: structure + the
    verdicts that are counts (the timed gates, uplift among them, run
    full-size in ``bench_serving.py --smoke``: a CPU's timing under six
    test workers decides nothing here)."""
    import bench_serving

    out = bench_serving.run_ingest_smoke(
        rungs=(4, 8), frame_hw=FRAME_HW, h2d_iters=48, h2d_warmup=8,
        uplift_batches=(8,), uplift_seconds=0.5, uplift_frame_hw=(64, 64),
        uplift_h2d_gb_s=0.005, jpeg_frames=8)
    for rung in ("4", "8"):
        row = out["h2d"][rung]
        assert set(row) >= {"f32_fresh", "uint8_unpinned", "uint8_ring"}
        assert row["f32_fresh"]["bytes_per_frame"] == (
            4 * row["uint8_ring"]["bytes_per_frame"])
    b8 = out["uplift"]["b8"]
    assert b8["uint8"]["completed"] > 0 and b8["f32"]["completed"] > 0
    assert b8["zero_steady_state_allocs"]
    assert out["jpeg"]["completed"] == out["jpeg"]["offered"] == 8
    assert isinstance(out["ingest_ok"], bool)


def test_jpeg_payload_without_decode_pool_counts_malformed():
    """A compressed payload hitting a non-jpeg service is a loud,
    counted malformed frame — never a silent hang."""
    metrics = Metrics()
    pipeline, service, connector = _service(
        metrics=metrics, ingest=IngestConfig(mode="uint8"))
    service.start(warmup=False)
    try:
        connector.inject(FRAME_TOPIC, {ingest_mod.JPEG_KEY: "AAAA",
                                       "meta": {"seq": 0}})
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert metrics.counter(mn.FRAMES_MALFORMED) == 1
    _assert_settled(service)

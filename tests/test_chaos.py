"""Chaos suite: the serving loop under injected faults (runtime.faults /
runtime.resilience).

The acceptance scenario (ISSUE 1): one stuck readback, three consecutive
UNAVAILABLE dispatches, and a corrupt frame into a running
RecognizerService over FakeConnector — the service never deadlocks,
dead-letters exactly the stuck batch, retries then enters degraded mode
with a STATUS_TOPIC message, and every healthy frame submitted afterwards
still gets a result, with metrics matching the injected fault counts
exactly. Plus: supervisor restart with gallery restore, degraded-mode
backend probe + CPU fallback, the fault injector's determinism contract,
and the seed-logged chaos soak (fast deterministic variant in tier-1, the
long randomized soak marked slow).
"""

import importlib.util
import os
import sys
import time

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime import (
    FakeConnector,
    FaultInjector,
    RecognizerService,
    ResiliencePolicy,
    ServiceSupervisor,
)
from opencv_facerecognizer_tpu.runtime.connector import encode_frame
from opencv_facerecognizer_tpu.runtime.faults import (
    InjectedUnavailableError,
    StuckReadback,
)
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    RESULT_TOPIC,
    STATUS_TOPIC,
)
from opencv_facerecognizer_tpu.runtime.resilience import is_transient_error

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "chaos_soak", os.path.join(REPO_ROOT, "scripts", "chaos_soak.py"))
chaos_soak = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chaos_soak)

FRAME_SHAPE = (64, 64)
RNG = np.random.default_rng(11)


def _wait(cond, timeout=20.0, interval=0.02) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


@pytest.fixture(scope="module")
def chaos_stack():
    """Tiny untrained serving stack — chaos tests exercise control flow,
    not recognition quality (see scripts/chaos_soak.build_stack)."""
    return chaos_soak.build_stack(frame_shape=FRAME_SHAPE, seed=0)


def _frame_msg(meta=None):
    frame = RNG.uniform(0, 255, FRAME_SHAPE).astype(np.float32)
    return {**encode_frame(frame), "meta": meta}


def _make_service(pipe, injector=None, policy=None, **kwargs):
    connector = FakeConnector()
    service = RecognizerService(
        pipe, connector, batch_size=2, frame_shape=FRAME_SHAPE,
        # Wide enough that two back-to-back injects always land in ONE
        # batch (the acceptance assertions count whole batches).
        flush_timeout=0.08, inflight_depth=2,
        resilience=policy or ResiliencePolicy(
            dispatch_retries=3, backoff_base_s=0.01, backoff_max_s=0.05,
            readback_deadline_s=0.6, degraded_after=3,
        ),
        fault_injector=injector,
        **kwargs,
    )
    return service, connector


# ---------- the acceptance scenario ----------


def test_chaos_acceptance_stuck_unavailable_corrupt(chaos_stack):
    pipe, _ = chaos_stack
    injector = FaultInjector(seed=1)
    service, connector = _make_service(pipe, injector)
    metrics = service.metrics
    service.start()
    try:
        # (a) one stuck readback: the whole batch must be dead-lettered at
        # its deadline — and ONLY that batch.
        injector.script("readback", "stuck")
        connector.inject(FRAME_TOPIC, _frame_msg({"phase": "stuck", "i": 0}))
        connector.inject(FRAME_TOPIC, _frame_msg({"phase": "stuck", "i": 1}))
        assert _wait(lambda: metrics.counter("batches_dead_lettered") >= 1), \
            "stuck readback was never dead-lettered (loop wedged?)"
        assert metrics.counter("batches_dead_lettered") == 1

        # (b) three consecutive UNAVAILABLE dispatches: retried with
        # backoff, degraded mode published at the third failure, then the
        # fourth attempt succeeds and the service recovers.
        injector.script("dispatch", "unavailable", "unavailable", "unavailable")
        connector.inject(FRAME_TOPIC, _frame_msg({"phase": "unavail", "i": 0}))
        connector.inject(FRAME_TOPIC, _frame_msg({"phase": "unavail", "i": 1}))
        assert _wait(lambda: metrics.counter("degraded_recoveries") >= 1), \
            "service never recovered from the UNAVAILABLE burst"
        statuses = [m["status"] for m in connector.messages(STATUS_TOPIC)]
        assert "degraded" in statuses and "recovered" in statuses
        degraded = next(m for m in connector.messages(STATUS_TOPIC)
                        if m["status"] == "degraded")
        assert degraded["consecutive_failures"] == 3

        # (c) one corrupt frame: counted malformed, never batched.
        injector.script("receive", "corrupt")
        connector.inject(FRAME_TOPIC, _frame_msg({"phase": "corrupt"}))
        assert _wait(lambda: metrics.counter("frames_malformed") >= 1)

        # Every healthy frame submitted afterwards still gets a result.
        n_before = len(connector.messages(RESULT_TOPIC))
        for i in range(4):
            connector.inject(FRAME_TOPIC, _frame_msg({"phase": "healthy", "i": i}))
        assert _wait(lambda: len(
            [m for m in connector.messages(RESULT_TOPIC)
             if (m.get("meta") or {}).get("phase") == "healthy"]) >= 4), \
            "healthy frames after the fault sequence got no results"
    finally:
        service.stop()

    # Metrics match the injected fault counts EXACTLY.
    injected = injector.summary()
    counters = metrics.counters()
    assert injected == {"readback:stuck": 1, "dispatch:unavailable": 3,
                        "receive:corrupt": 1}
    assert counters["batches_dead_lettered"] == injected["readback:stuck"]
    assert counters["frames_dead_lettered"] == 2  # both frames of the batch
    assert counters["dispatch_failures"] == injected["dispatch:unavailable"]
    assert counters["dispatch_retries"] == 3
    assert counters.get("batches_failed", 0) == 0  # retried, never abandoned
    assert counters["frames_malformed"] == injected["receive:corrupt"]
    assert counters["degraded_transitions"] == 1
    assert counters["degraded_recoveries"] == 1
    # The unavailable-phase and healthy-phase frames all published.
    metas = [m.get("meta") or {} for m in connector.messages(RESULT_TOPIC)]
    assert sum(m.get("phase") == "unavail" for m in metas) == 2
    assert sum(m.get("phase") == "healthy" for m in metas) == 4
    assert sum(m.get("phase") == "stuck" for m in metas) == 0  # dead-lettered


def test_receive_drop_and_duplicate(chaos_stack):
    pipe, _ = chaos_stack
    injector = FaultInjector(seed=2)
    service, connector = _make_service(pipe, injector)
    service.start()
    try:
        injector.script("receive", "drop", "duplicate")
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "dropped"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "doubled"}))
        assert _wait(lambda: len(connector.messages(RESULT_TOPIC)) >= 2)
    finally:
        service.stop()
    metas = [m.get("meta") or {} for m in connector.messages(RESULT_TOPIC)]
    assert sum(m.get("k") == "doubled" for m in metas) == 2
    assert sum(m.get("k") == "dropped" for m in metas) == 0


def test_poisoned_batch_put_boundary(chaos_stack):
    """A frame corrupted at the batcher-put boundary is dropped by shape
    validation (counted on the shared metrics surface) and never poisons
    its batch — peers still get results."""
    pipe, _ = chaos_stack
    injector = FaultInjector(seed=3)
    service, connector = _make_service(pipe, injector)
    service.start()
    try:
        injector.script("put", "corrupt")
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "poisoned"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "fine"}))
        assert _wait(lambda: len(connector.messages(RESULT_TOPIC)) >= 1)
    finally:
        service.stop()
    counters = service.metrics.counters()
    assert counters["batcher_dropped_malformed"] == 1
    assert counters["frames_dropped"] == 1  # the service-side mirror
    metas = [m.get("meta") or {} for m in connector.messages(RESULT_TOPIC)]
    assert sum(m.get("k") == "fine" for m in metas) == 1
    assert sum(m.get("k") == "poisoned" for m in metas) == 0


def test_dispatch_exhaustion_abandons_batch(chaos_stack):
    """More consecutive UNAVAILABLEs than the retry budget: the batch is
    abandoned (batches_failed), the loop keeps serving."""
    pipe, _ = chaos_stack
    injector = FaultInjector(seed=4)
    policy = ResiliencePolicy(dispatch_retries=1, backoff_base_s=0.01,
                              backoff_max_s=0.02, readback_deadline_s=0.6,
                              degraded_after=2)
    service, connector = _make_service(pipe, injector, policy)
    service.start()
    try:
        injector.script("dispatch", "unavailable", "unavailable")
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "doomed"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "doomed"}))
        assert _wait(lambda: service.metrics.counter("batches_failed") >= 1)
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "after"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "after"}))
        assert _wait(lambda: len(
            [m for m in connector.messages(RESULT_TOPIC)
             if (m.get("meta") or {}).get("k") == "after"]) >= 2)
    finally:
        service.stop()
    counters = service.metrics.counters()
    assert counters["batches_failed"] == 1
    assert counters["dispatch_failures"] == 2
    assert counters["degraded_transitions"] == 1  # hit degraded_after=2


def test_slow_readbacks_pipeline_through_worker(chaos_stack):
    """Injected slow readbacks (delayed-ready, not stuck) must neither
    dead-letter nor serialize the loop: the readback worker waits them out
    event-driven while the dispatch loop keeps feeding the in-flight
    queue, and every frame still publishes exactly once."""
    pipe, _ = chaos_stack
    injector = FaultInjector(seed=11, slow_readback_s=0.15)
    service, connector = _make_service(pipe, injector)
    service.start()
    try:
        injector.script("readback", "slow", "slow", "slow")
        t0 = time.monotonic()
        for i in range(6):  # three 2-frame batches, all slow
            connector.inject(FRAME_TOPIC, _frame_msg({"k": "slow", "i": i}))
        assert _wait(lambda: len(
            [m for m in connector.messages(RESULT_TOPIC)
             if (m.get("meta") or {}).get("k") == "slow"]) >= 6)
        elapsed = time.monotonic() - t0
    finally:
        service.stop()
    assert injector.summary() == {"readback:slow": 3}
    counters = service.metrics.counters()
    assert counters.get("batches_dead_lettered", 0) == 0
    assert counters["batches_dispatched"] >= 3
    # Overlap check: three 150 ms readbacks served well under 3 x 150 ms
    # plus slack would only hold if they pipelined; allow generous CI
    # headroom while still ruling out full serialization with the 80 ms
    # batch window on top (serialized would be >= ~0.7 s).
    assert elapsed < 3 * 0.15 + 0.35, elapsed


# ---------- supervisor ----------


class _CrashOnceConnector(FakeConnector):
    """Raises from the first RESULT publish — an exception escaping the
    loop body via a subscriber, the crash class the supervisor exists for."""

    def __init__(self):
        super().__init__()
        self.crashes_left = 1

    def publish(self, topic, message):
        if topic == RESULT_TOPIC and self.crashes_left:
            self.crashes_left -= 1
            raise RuntimeError("result consumer blew up")
        super().publish(topic, message)

    inject = publish


def test_supervisor_restarts_crashed_loop_and_restores_gallery(chaos_stack):
    pipe, _ = chaos_stack
    connector = _CrashOnceConnector()
    service = RecognizerService(
        pipe, connector, batch_size=2, frame_shape=FRAME_SHAPE,
        flush_timeout=0.02,
        resilience=ResiliencePolicy(readback_deadline_s=5.0),
    )
    supervisor = ServiceSupervisor(service, max_restarts=3,
                                   poll_interval_s=0.05)
    supervisor.start()
    size_at_checkpoint = pipe.gallery.size
    try:
        # Rows added after the checkpoint simulate a half-done enrolment
        # the crash interrupts; the restart must roll them back.
        pipe.gallery.add(RNG.normal(size=(3, 16)).astype(np.float32),
                         np.full(3, 3, np.int32))
        assert pipe.gallery.size == size_at_checkpoint + 3
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "crash-bait"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "crash-bait"}))
        assert _wait(lambda: service.metrics.counter("supervisor_restarts") >= 1), \
            "supervisor never restarted the crashed loop"
        assert pipe.gallery.size == size_at_checkpoint  # restored
        # The restarted loop still serves.
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "after-restart"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "after-restart"}))
        assert _wait(lambda: len(
            [m for m in connector.messages(RESULT_TOPIC)
             if (m.get("meta") or {}).get("k") == "after-restart"]) >= 2)
    finally:
        supervisor.stop()
    assert service.metrics.counter("loop_crashes") == 1
    assert supervisor.restarts == 1
    assert not supervisor.gave_up
    statuses = [m["status"] for m in connector.messages(STATUS_TOPIC)]
    assert "crashed" in statuses and "supervisor_restart" in statuses


def test_degraded_probe_and_cpu_fallback(chaos_stack):
    pipe, _ = chaos_stack
    injector = FaultInjector(seed=5)
    policy = ResiliencePolicy(dispatch_retries=3, backoff_base_s=0.01,
                              backoff_max_s=0.02, readback_deadline_s=0.6,
                              degraded_after=3,
                              probe_backend_on_degraded=True)
    fallbacks = []
    service, connector = _make_service(
        pipe, injector, policy,
        backend_probe_fn=lambda: (False, "injected-dead"),
        cpu_fallback=lambda svc: fallbacks.append(svc),
    )
    service.start()
    try:
        injector.script("dispatch", "unavailable", "unavailable", "unavailable")
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "x"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "x"}))
        assert _wait(lambda: service.metrics.counter("degraded_recoveries") >= 1)
    finally:
        service.stop()
    degraded = next(m for m in connector.messages(STATUS_TOPIC)
                    if m["status"] == "degraded")
    assert degraded["backend_usable"] is False
    assert degraded["backend_reason"] == "injected-dead"
    assert degraded["cpu_fallback"] is True
    assert fallbacks == [service]
    assert service.metrics.counter("cpu_fallbacks") == 1


# ---------- fault injector contract ----------


def test_fault_injector_scripted_order_and_counts():
    fi = FaultInjector(seed=0)
    fi.script("receive", "drop", "duplicate", "corrupt")
    msg = {"__frame__": "x", "shape": [1], "dtype": "uint8", "meta": 7}
    assert fi.on_receive(msg) == []
    assert fi.on_receive(msg) == [msg, msg]
    corrupted = fi.on_receive(msg)
    assert len(corrupted) == 1 and corrupted[0]["__frame__"] != "x"
    assert corrupted[0]["meta"] == 7  # provenance survives corruption
    assert fi.on_receive(msg) == [msg]  # script exhausted -> passthrough
    with pytest.raises(ValueError):
        fi.script("dispatch", "stuck")  # wrong boundary
    with pytest.raises(ValueError):
        fi.script("bogus", "drop")
    assert fi.summary() == {"receive:drop": 1, "receive:duplicate": 1,
                            "receive:corrupt": 1}


def test_fault_injector_seeded_rates_reproducible():
    rates = {"dispatch": {"unavailable": 0.5}}
    outcomes = []
    for _ in range(2):
        fi = FaultInjector(seed=42, rates=rates)
        run = []
        for _ in range(32):
            try:
                fi.on_dispatch()
                run.append(False)
            except InjectedUnavailableError:
                run.append(True)
        outcomes.append(run)
    assert outcomes[0] == outcomes[1]  # same seed, same fault sequence
    assert any(outcomes[0]) and not all(outcomes[0])


def test_fault_injector_flood_amplifies_delivery():
    fi = FaultInjector(seed=0, flood_factor=4)
    fi.script("receive", "flood")
    msg = {"__frame__": "x", "shape": [1], "dtype": "uint8", "meta": 3}
    assert fi.on_receive(msg) == [msg] * 4
    assert fi.on_receive(msg) == [msg]  # script exhausted -> passthrough
    assert fi.summary() == {"receive:flood": 1}
    # Rates accept it too (the overload soak's knob).
    fi2 = FaultInjector(seed=1, rates={"receive": {"flood": 1.0}},
                        flood_factor=3)
    assert fi2.on_receive(msg) == [msg] * 3


def test_fault_injector_disarm():
    fi = FaultInjector(seed=0, rates={"dispatch": {"unavailable": 1.0}})
    fi.script("readback", "stuck")
    fi.disarm()
    fi.on_dispatch()  # no raise
    arr = np.zeros(2)
    assert fi.on_readback(arr) is arr
    assert fi.summary() == {}
    fi.arm()
    assert isinstance(fi.on_readback(arr), StuckReadback)


def test_stuck_readback_never_materializes_silently():
    stuck = StuckReadback(np.zeros(3))
    assert stuck.is_ready() is False
    stuck.copy_to_host_async()  # no-op, no raise
    with pytest.raises(RuntimeError, match="stuck"):
        np.asarray(stuck)


def test_transient_error_classification():
    assert is_transient_error(InjectedUnavailableError())
    assert is_transient_error(RuntimeError("UNAVAILABLE: socket closed"))
    assert is_transient_error(ConnectionResetError("connection reset by peer"))
    assert not is_transient_error(ValueError("shape mismatch [8, 64, 64]"))
    assert not is_transient_error(TypeError("not an array"))
    # A local chip's OOM / scoped-VMEM refusal is permanent for that shape.
    assert not is_transient_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Resource exhausted: out of memory in HBM"))


def test_probe_device_ok_raises_and_deadline(monkeypatch):
    """The degraded-mode probe runs IN this process against the device the
    service holds (a child process could never open a locally attached
    chip): healthy -> ok, a failing device op -> the error as the reason,
    a device call that never returns -> bounded by the deadline."""
    import threading

    import jax

    from opencv_facerecognizer_tpu.runtime.resilience import probe_device

    usable, reason = probe_device(jax.devices()[0], timeout_s=30.0)
    assert usable and reason == "ok"

    usable, reason = probe_device("not-a-device", timeout_s=30.0)
    assert not usable and reason.startswith("device op failed")

    release = threading.Event()
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: release.wait(timeout=60.0))
    try:
        t0 = time.monotonic()
        usable, reason = probe_device(jax.devices()[0], timeout_s=0.3)
        assert not usable and "deadline" in reason
        assert time.monotonic() - t0 < 5.0  # bounded, not the call's 60 s
    finally:
        release.set()


def test_default_backend_probe_uses_the_held_device(chaos_stack):
    """No injected probe fn: the service asks the device its gallery lives
    on, in-process, and gets a (usable, reason) pair back."""
    pipe, _ = chaos_stack
    service, _connector = _make_service(pipe)
    assert service._probe_backend() == (True, "ok")


# ---------- chaos soak ----------


def test_chaos_soak_fast_deterministic():
    """Tier-1 variant: short chaos window, pinned seed — rc-0 semantics of
    scripts/chaos_soak.py (no wedge, no unsupervised crash, accounting,
    and the admission ledger reconciling exactly at quiescence)."""
    report = chaos_soak.run_soak(seconds=1.5, seed=7)
    assert report["ok"], report["failures"]
    assert report["seed"] == 7
    assert report["results"] > 0
    assert report["ledger"]["in_system"] == 0


def test_overload_soak_fast_deterministic():
    """Tier-1 overload smoke: the ``--scenario overload`` flood soak
    (seed-logged receive:flood amplification to ~4x a deterministic
    capacity wall) passes the criteria that are counts — no wedge, no
    crash, explicit sheds, exact ledger, journal covering every shed.
    Criterion 3 of ``run_overload`` (flood-phase interactive p99 within 2x
    the unloaded baseline) is a CPU's timing under six test workers: it is
    held by the ``slow`` twin, ``test_overload_soak_long_randomized``."""
    report = chaos_soak.run_overload(seconds=2.0, seed=7)
    failures = [f for f in report["failures"]
                if not f.startswith("interactive p99 blew the budget")]
    assert not failures, failures
    # Under ~4x offered load bulk must actually shed (reject or brownout).
    shed = (sum(report["rejected"].values())
            + sum(report["ledger"]["drops_by_reason"].values()))
    assert shed > 0
    assert report["ledger"]["in_system"] == 0
    # Every journaled frame carries its reason (replayable).
    assert report["journal_frames"] == sum(
        report["counters"].get(k, 0) for k in (
            "frames_dead_lettered", "frames_failed",
            "frames_dropped_brownout", "batcher_dropped_stale",
            "batcher_dropped_overflow"))


@pytest.mark.slow
def test_chaos_soak_long_randomized():
    report = chaos_soak.run_soak(seconds=30.0)
    assert report["ok"], report["failures"]


@pytest.mark.slow
def test_overload_soak_long_randomized():
    report = chaos_soak.run_overload(seconds=15.0)
    assert report["ok"], report["failures"]


@pytest.mark.slow
def test_replication_soak_long_randomized():
    """Random-seed replication soak (``--scenario replication``): 1 writer
    + 2 WAL-tailing read replicas behind the topic router, reader killed
    mid-traffic, writer killed mid-enrollment and restarted — survivor
    p99, zero acked loss on every survivor, split-brain fail-closed, and
    per-replica ledger exactness, at a fresh seed per run (the fast
    pinned-seed variant lives in tests/test_replication.py)."""
    report = chaos_soak.run_replication(seconds=10.0)
    assert report["ok"], report["failures"]


# ---------- review-hardening: degraded-path edges ----------


def test_status_subscriber_raising_never_crashes_loop(chaos_stack):
    """Degraded/recovered/dead-letter statuses publish from the serving
    thread into arbitrary app subscribers — one that raises must cost a
    logged error, not the serving loop."""
    pipe, _ = chaos_stack
    injector = FaultInjector(seed=6)
    service, connector = _make_service(pipe, injector)

    def angry_subscriber(topic, message):
        raise RuntimeError("status consumer blew up")

    connector.subscribe(STATUS_TOPIC, angry_subscriber)
    service.start()
    try:
        # Both degraded entry and recovery publish through the subscriber.
        injector.script("dispatch", "unavailable", "unavailable", "unavailable")
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "x"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "x"}))
        assert _wait(lambda: service.metrics.counter("degraded_recoveries") >= 1)
        # ...and a dead-letter announcement too.
        injector.script("readback", "stuck")
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "y"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "y"}))
        assert _wait(lambda: service.metrics.counter("batches_dead_lettered") >= 1)
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "alive"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "alive"}))
        assert _wait(lambda: len(
            [m for m in connector.messages(RESULT_TOPIC)
             if (m.get("meta") or {}).get("k") == "alive"]) >= 2)
    finally:
        service.stop()
    assert service.metrics.counter("loop_crashes") == 0


def test_cpu_fallback_rebuilds_pipeline_and_keeps_serving(chaos_stack):
    """The stock rebuild_pipeline_on_cpu hook (what ocvf-recognize wires
    for --probe-on-degraded): a dead-backend verdict swaps in a pipeline
    on a single host CPU device with the gallery copied through the
    host-mirror snapshot path, and serving continues on it."""
    from opencv_facerecognizer_tpu.runtime.resilience import (
        rebuild_pipeline_on_cpu,
    )

    pipe, _ = chaos_stack
    injector = FaultInjector(seed=8)
    policy = ResiliencePolicy(dispatch_retries=3, backoff_base_s=0.01,
                              backoff_max_s=0.02, readback_deadline_s=0.6,
                              degraded_after=3,
                              probe_backend_on_degraded=True)
    service, connector = _make_service(
        pipe, injector, policy,
        backend_probe_fn=lambda: (False, "injected-dead"),
        cpu_fallback=rebuild_pipeline_on_cpu,
    )
    old_pipe = service.pipeline
    old_size = old_pipe.gallery.size
    service.start()
    try:
        injector.script("dispatch", "unavailable", "unavailable", "unavailable")
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "x"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "x"}))
        assert _wait(lambda: service.metrics.counter("cpu_fallbacks") >= 1)
        # The swap is visible and serving continues on the new pipeline.
        assert service.pipeline is not old_pipe
        assert service.pipeline.gallery.mesh.size == 1
        assert service.pipeline.gallery.size == old_size
        # The injector MOVED with the swap (an armed one left behind would
        # leak faults into the next service built on the shared pipeline).
        assert old_pipe.fault_injector is None
        assert service.pipeline.fault_injector is injector
        # The enrolment embed graph follows to the fallback device too.
        assert service._embed_device is not None
        # The recompile watchdog stayed armed across the swap: the new
        # pipeline's ladder was prewarmed inside the hook, so the
        # fallback's own compiles never fire it and later mid-serving
        # compiles still would.
        assert service._warmed
        assert service.metrics.counter("recompiles_post_warmup") == 0
        chunk = np.zeros((service._enrol_chunk, *service.pipeline.face_size),
                         np.float32)
        emb = np.asarray(service._run_embed_chunk(
            service.pipeline.embed_params, chunk))
        assert emb.shape[0] == service._enrol_chunk
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "after"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "after"}))
        assert _wait(lambda: len(
            [m for m in connector.messages(RESULT_TOPIC)
             if (m.get("meta") or {}).get("k") == "after"]) >= 2, timeout=60)
    finally:
        service.stop()
    degraded = next(m for m in connector.messages(STATUS_TOPIC)
                    if m["status"] == "degraded")
    assert degraded["cpu_fallback"] is True
    assert service.metrics.counter("loop_crashes") == 0


def test_supervisor_recheckpoints_on_committed_changes(chaos_stack):
    """A committed enrolment/reload advances last-known-good: a crash
    afterwards must restore the post-commit gallery, not roll back every
    subject enrolled since startup."""
    pipe, _ = chaos_stack
    connector = _CrashOnceConnector()
    service = RecognizerService(
        pipe, connector, batch_size=2, frame_shape=FRAME_SHAPE,
        flush_timeout=0.02,
        resilience=ResiliencePolicy(readback_deadline_s=5.0),
    )
    supervisor = ServiceSupervisor(service, max_restarts=3,
                                   poll_interval_s=0.05)
    supervisor.start()
    base_size = pipe.gallery.size
    try:
        # Commit rows exactly as _finish_enrolment does: gallery change,
        # then the service's commit hooks fire (direct callback — wire
        # connectors never dispatch their own publishes locally, so this
        # must NOT depend on a status subscription).
        checkpoints = service.metrics.counter("supervisor_checkpoints")
        pipe.gallery.add(RNG.normal(size=(2, 16)).astype(np.float32),
                         np.full(2, 3, np.int32))
        service._run_commit_hooks()
        assert _wait(lambda: service.metrics.counter("supervisor_checkpoints")
                     > checkpoints)
        # Crash the loop AFTER the commit checkpoint (first RESULT publish
        # raises): restore must keep the enrolled rows.
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "crash-bait"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "crash-bait"}))
        assert _wait(lambda: service.metrics.counter("supervisor_restarts") >= 1)
        assert pipe.gallery.size == base_size + 2
    finally:
        supervisor.stop()


def test_supervisor_stall_watchdog_surfaces_no_progress(chaos_stack):
    """Call-time-hang surfacing: frames pending with zero processing
    progress past stall_warn_s publishes a one-shot 'stalled' status —
    the deploy-level liveness signal (the shape cannot be fixed
    in-process; see ServiceSupervisor docstring)."""
    pipe, _ = chaos_stack
    connector = FakeConnector()
    service = RecognizerService(pipe, connector, batch_size=2,
                                frame_shape=FRAME_SHAPE, flush_timeout=0.02)
    supervisor = ServiceSupervisor(service)
    supervisor.stall_warn_s = 0.1
    # Loop never started: queued frames can make no progress — the stall
    # signature, without needing a real native-code hang.
    service.batcher.put(np.zeros(FRAME_SHAPE, np.float32))
    supervisor._check_stall(service, STATUS_TOPIC)  # baselines progress
    time.sleep(0.15)
    supervisor._check_stall(service, STATUS_TOPIC)
    assert service.metrics.counter("supervisor_stalls") == 1
    stalled = [m for m in connector.messages(STATUS_TOPIC)
               if m["status"] == "stalled"]
    assert len(stalled) == 1 and stalled[0]["pending_frames"] == 1
    # One-shot: no repeat warning while still stalled.
    supervisor._check_stall(service, STATUS_TOPIC)
    assert service.metrics.counter("supervisor_stalls") == 1
    # An abandoned batch IS progress: a loop surviving a fast-fail outage
    # (dispatch fails, batch abandoned) is degraded, not stalled.
    service.metrics.incr("batches_failed")
    supervisor._check_stall(service, STATUS_TOPIC)  # progress: re-arms
    time.sleep(0.15)
    service.metrics.incr("batches_failed")
    supervisor._check_stall(service, STATUS_TOPIC)  # still advancing
    assert service.metrics.counter("supervisor_stalls") == 1


def test_supervisor_waits_for_crashed_thread_to_exit(chaos_stack):
    """A crash flag raised while the serving thread is still unwinding
    (slow 'crashed'-status subscriber) must not burn phantom restarts:
    restart_loop would no-op on the alive thread, desyncing restarts vs
    loop_crashes — the soak's unsupervised-crash signature."""
    pipe, _ = chaos_stack
    connector = FakeConnector()
    service = RecognizerService(
        pipe, connector, batch_size=2, frame_shape=FRAME_SHAPE,
        flush_timeout=0.02,
        resilience=ResiliencePolicy(readback_deadline_s=5.0),
    )
    supervisor = ServiceSupervisor(service, max_restarts=3,
                                   poll_interval_s=0.05)
    supervisor.start()
    try:
        service._crashed = True  # flag up, thread alive and healthy
        time.sleep(0.4)  # several monitor polls
        assert supervisor.restarts == 0
        assert service.metrics.counter("supervisor_restarts") == 0
        service._crashed = False
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "fine"}))
        connector.inject(FRAME_TOPIC, _frame_msg({"k": "fine"}))
        assert _wait(lambda: len(
            [m for m in connector.messages(RESULT_TOPIC)
             if (m.get("meta") or {}).get("k") == "fine"]) >= 2)
    finally:
        supervisor.stop()


# ---------- dynamic lock-order backstop (ocvf-lint cross-check) ----------


def test_debug_lock_backstop_no_inversions(chaos_stack):
    """Dynamic backstop to the static ``lock-order`` rule: run real traffic
    through a service whose locks are swapped for instrumented DebugLocks
    (named with the same ids the static analyzer uses), then assert (a) no
    acquisition-order inversion was *observed* at runtime, and (b) the
    union of the observed edges with the statically derived graph is still
    free of two-lock cycles — orders the AST can't see (hooks, callbacks)
    get checked here, orders the runtime didn't happen to exercise stay
    covered statically."""
    import threading

    from opencv_facerecognizer_tpu.utils.debug_lock import LockOrderMonitor

    pipe, _ = chaos_stack
    monitor = LockOrderMonitor()
    service, connector = _make_service(pipe)

    m = service.metrics
    m._lock = monitor.debug_lock("utils.metrics.Metrics._lock")
    m._sink_lock = monitor.debug_lock("utils.metrics.Metrics._sink_lock")
    service._enrol_lock = monitor.debug_lock(
        "runtime.recognizer.RecognizerService._enrol_lock")
    service._reject_lock = monitor.debug_lock(
        "runtime.recognizer.RecognizerService._reject_lock")
    service._inflight_cv = threading.Condition(monitor.debug_lock(
        "runtime.recognizer.RecognizerService._inflight_cv"))
    batcher = service.batcher
    batcher_lock = monitor.debug_lock("runtime.batcher.FrameBatcher._lock")
    batcher._lock = batcher_lock
    batcher._not_empty = threading.Condition(batcher_lock)
    gallery = pipe.gallery
    saved_write_lock = gallery._write_lock  # module-scoped fixture: restore
    gallery._write_lock = monitor.debug_lock(
        "parallel.gallery.ShardedGallery._write_lock")

    service.start()
    try:
        for i in range(10):
            connector.inject(FRAME_TOPIC, _frame_msg({"k": f"f{i}"}))
        assert _wait(lambda: len(connector.messages(RESULT_TOPIC)) >= 10)
    finally:
        service.stop()
        gallery._write_lock = saved_write_lock

    # The clean path keeps metrics OUT of lock bodies (that discipline is
    # the point); the closed-batcher drop is the one sanctioned nesting —
    # drive it so the cross-check below is provably non-vacuous.
    assert batcher.put(np.zeros(FRAME_SHAPE, np.float32)) is False
    assert service.metrics.counter("batcher_dropped_closed") >= 1

    monitor.check()  # no runtime inversion among the instrumented locks
    observed = monitor.edges()
    assert observed, "instrumentation was vacuous — no edges recorded"

    sys.path.insert(0, REPO_ROOT)
    from tools.ocvf_lint.checkers.lock_order import build_lock_graph

    static_edges = set(build_lock_graph(
        [os.path.join(REPO_ROOT, "opencv_facerecognizer_tpu")]))
    # The static analyzer names the batcher's Condition `_not_empty` and its
    # Lock `_lock` as two nodes; physically they are ONE lock
    # (Condition(self._lock) in FrameBatcher.__init__).  Merge the alias
    # before combining, or an inversion split across the two names would
    # form no cycle and slip through.
    alias = {"runtime.batcher.FrameBatcher._not_empty":
             "runtime.batcher.FrameBatcher._lock"}

    def canon(node):
        return alias.get(node, node)

    combined = ({(canon(a), canon(b)) for a, b in static_edges}
                | {(canon(a), canon(b)) for a, b in observed})
    # sanity: the two sources actually share the namespace — a silent
    # divergence (e.g. checkout-dir-prefixed static ids) would make this
    # cross-check vacuous
    static_nodes = {n for e in static_edges for n in e}
    observed_nodes = {canon(n) for e in observed for n in e}
    assert static_nodes & observed_nodes, (
        f"static and dynamic graphs share no nodes:\n{static_nodes}\n"
        f"{observed_nodes}")
    inverted = sorted((a, b) for (a, b) in combined
                      if a != b and (b, a) in combined)
    assert not inverted, f"static+dynamic lock graph has cycles: {inverted}"

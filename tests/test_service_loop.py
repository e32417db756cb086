"""The serving loop's order (ISSUE 30): the chip is handed its next work
before the loop does host work the chip does not wait on.

- gate n+1 goes onto the device's queue ahead of step n when a closed
  batch is already waiting, and is never waited for;
- early exits (track-cache hits, gate rejections) are published after
  their batch's step enqueue, and before the next batch's lookups;
- every admitted frame settles exactly once on every exit.

Fakes record the order of device enqueues, tracker calls and publishes in
one list; frames carry their sequence number in pixel [0, 0]."""

import threading
import time

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime.connector import FakeConnector
from opencv_facerecognizer_tpu.runtime.fakes import InstantPipeline
from opencv_facerecognizer_tpu.runtime.ingest import IngestConfig
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    READY_POLL_S,
    RESULT_TOPIC,
    RecognizerService,
    _ReadbackBlocker,
)
from opencv_facerecognizer_tpu.runtime.resilience import ResiliencePolicy
from opencv_facerecognizer_tpu.runtime.tracker import IdentityTracker
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.metrics import Metrics

HW = (16, 16)
BATCH = 4


def _wait(cond, timeout=10.0, interval=0.005) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


class Events:
    """What happened, in order, with the instant of each."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows = []

    def add(self, kind, what=None):
        with self._lock:
            self.rows.append((kind, what, time.monotonic()))

    def kinds(self, *kinds):
        with self._lock:
            return [(k, w) for k, w, _t in self.rows if k in kinds]

    def index(self, kind, what):
        return self.kinds(*{k for k, _w, _t in self.rows}).index((kind, what))

    def at(self, kind, what):
        with self._lock:
            return next(t for k, w, t in self.rows if (k, w) == (kind, what))


def _seqs(frames):
    host = np.asarray(frames)
    return tuple(int(v) - 1 for v in host[:, 0, 0] if v > 0)


def _frame(seq, face=True):
    """Frame ``seq``: the brightness stub keeps it iff it has a face."""
    frame = np.zeros(HW, np.float32)
    frame[0, 0] = seq + 1
    if face:
        frame[4:9, 4:9] = 200.0
    return frame


class RecordingPipeline(InstantPipeline):
    """Records each device enqueue. ``hold_step`` = (n, Event) makes the
    n-th step's enqueue wait, ``step_fault`` is raised out of the first,
    ``gate_fault`` = (n, "enqueue" | "readback") fails the n-th gate,
    ``chip_s`` is how long every gate's scores take to come back."""

    def __init__(self, events, hold_step=(0, None), step_fault=None,
                 gate_fault=(0, None), chip_s=0.03, **kw):
        super().__init__(HW, cascade_stub=True, faces_per_frame=1, **kw)
        #: what the loop waits at the scores' readback: the chip's queue
        self.chip_s = chip_s
        self.events = events
        self.hold_step = hold_step
        self.step_fault = step_fault
        self.gate_fault = gate_fault

    def cascade_scores(self, frames):
        self.events.add("gate", _seqs(frames))
        scores = super().cascade_scores(frames)
        nth, kind = self.gate_fault
        if self.cascade_calls == nth and kind == "enqueue":
            raise RuntimeError("stage 1 refused")
        if self.cascade_calls == nth and kind == "readback":
            return _Unreadable()
        return _OnTheChip(scores, self.chip_s) if self.chip_s else scores

    def recognize_batch_packed(self, frames):
        self.events.add("step", _seqs(frames))
        nth, hold = self.hold_step
        if len(self.events.kinds("step")) == nth:
            assert hold.wait(10.0)
        fault, self.step_fault = self.step_fault, None
        if fault is not None:
            raise fault
        return super().recognize_batch_packed(frames)


class _OnTheChip:
    """Scores that take ``seconds`` to come back, as behind a step."""

    def __init__(self, scores, seconds):
        self.scores, self.seconds = scores, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return self.scores


class _Unreadable:
    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("scores lost on their way back")


class RecordingConnector(FakeConnector):
    """Records each result publish; raises out of the publish of
    ``crash_seq`` once (a subscriber blowing up: the loop's crash)."""

    def __init__(self, events, crash_seq=None):
        super().__init__()
        self.events = events
        self.crash_seq = crash_seq

    def publish(self, topic, message):
        if topic == RESULT_TOPIC:
            seq = message["meta"]["seq"]
            if seq == self.crash_seq:
                self.crash_seq = None
                raise RuntimeError("result consumer blew up")
            self.events.add("publish", (seq, message.get("exit")))
        super().publish(topic, message)

    inject = publish

    def published(self):
        return sorted(seq for _k, (seq, _exit) in self.events.kinds("publish"))


class FakeTracker:
    """A track cache that answers for the frames in ``hits``."""

    class config:
        brownout_stretch = 1.0

    def __init__(self, events, hits=()):
        self.events = events
        self.hits = set(hits)

    def lookup(self, key, frame, embedder_version=None, reverify_stretch=1.0):
        seq = int(frame[0, 0]) - 1
        self.events.add("lookup", seq)
        if seq in self.hits:
            return {"faces": [{"label": 0}], "track_id": 7,
                    "embedder_version": embedder_version}
        return None

    def note_misses(self, keys):
        for key in keys:
            self.events.add("note_miss", key)

    def update(self, key, faces, frame, embedder_version=None):
        self.events.add("update", int(frame[0, 0]) - 1)

    def stats(self):
        return {}

    def flush_all(self, reason=None):
        return 0


def _stack(frames, events=None, tracker_hits=None, crash_seq=None,
           flush_timeout=5.0, inflight_depth=2, streams=True,
           make_tracker=None, **kw):
    """A service with ``frames`` (pairs of seq, face) already queued:
    every batch they fill is closed before the loop starts. With
    ``tracker_hits`` given it has a track cache, consulted for the frames
    that name a stream (all, or none with ``streams`` off);
    ``make_tracker(events, metrics)`` puts another in the fake's place."""
    events = events or Events()
    pipe_kw = {k: kw.pop(k) for k in ("hold_step", "step_fault", "gate_fault",
                                      "compute_s", "chip_s", "cascade_score_s")
               if k in kw}
    pipeline = RecordingPipeline(events, **pipe_kw)
    connector = RecordingConnector(events, crash_seq=crash_seq)
    metrics = Metrics()
    tracker = (FakeTracker(events, tracker_hits)
               if tracker_hits is not None else None)
    if make_tracker is not None:
        tracker = make_tracker(events, metrics)
    service = RecognizerService(
        pipeline, connector, batch_size=BATCH, frame_shape=HW,
        flush_timeout=flush_timeout, inflight_depth=inflight_depth,
        similarity_threshold=0.0, metrics=metrics, bucket_sizes=(2, 4),
        cascade=True, tracker=tracker,
        resilience=ResiliencePolicy(readback_deadline_s=5.0), **kw)
    for seq, face in frames:
        meta = {"seq": seq, "stream": f"cam{seq}"} if streams else {"seq": seq}
        connector.inject(FRAME_TOPIC, {"frame": _frame(seq, face),
                                       "meta": meta})
    return service, pipeline, connector, metrics, events


def _faces(n, start=0):
    return [(seq, True) for seq in range(start, start + n)]


def _assert_settled_once(service, connector, published):
    """The ledger balances and no frame was published twice."""
    assert service.ledger()["in_system"] == 0, service.ledger()
    assert connector.published() == sorted(published)
    assert service.batcher.delivered_batches == service._completed_batches


# ---- the order of the feed -------------------------------------------------


def test_gate_of_a_waiting_batch_is_enqueued_before_this_batchs_step():
    service, _p, connector, metrics, events = _stack(_faces(8))
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    first, second = (0, 1, 2, 3), (4, 5, 6, 7)
    assert events.kinds("gate", "step") == [
        ("gate", first), ("gate", second), ("step", first), ("step", second)]
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 1
    assert metrics.counter(mn.BATCHES_DISPATCHED) == 2
    _assert_settled_once(service, connector, range(8))


def test_with_nothing_closed_the_step_goes_to_the_chip_without_a_wait():
    # six frames: one closed batch, and two that no deadline closes in time
    service, _p, _c, metrics, events = _stack(_faces(6), flush_timeout=30.0)
    pops = []
    get_batch = service.batcher.get_batch

    def recorded(block=True):
        batch = get_batch(block)
        pops.append((block, batch is not None))
        return batch

    service.batcher.get_batch = recorded
    service.start(warmup=False)
    try:
        assert _wait(lambda: metrics.counter(mn.FRAMES_COMPLETED) == 4)
        first = (0, 1, 2, 3)
        assert events.kinds("gate", "step") == [("gate", first),
                                                ("step", first)]
        # one look, not blocking, that found nothing; no wait for frames
        assert pops[:2] == [(True, True), (False, False)]
        assert events.at("step", first) - events.at("gate", first) < 1.0
        assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 0
    finally:
        service.stop()


def test_a_looked_ahead_batch_is_served_without_another_pop_and_counted():
    # three closed batches; the middle one has no survivor: it enqueues no
    # step, so nothing is looked ahead from it and the third is popped
    frames = _faces(4) + [(seq, False) for seq in range(4, 8)] + _faces(4, 8)
    service, _p, connector, metrics, events = _stack(frames)
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert [w for _k, w in events.kinds("step")] == [(0, 1, 2, 3),
                                                     (8, 9, 10, 11)]
    assert events.index("gate", (4, 5, 6, 7)) < events.index("step",
                                                             (0, 1, 2, 3))
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 1
    assert metrics.counter(mn.CASCADE_BATCH_EXITS) == 1
    assert metrics.counter(mn.LOOP_BATCHES) == 3
    # a batch without a step settles in the step's place: not deferred
    assert metrics.counter(mn.FRAMES_COMPLETED_EMPTY) == 4
    assert metrics.counter(mn.EARLY_EXITS_DEFERRED) == 0
    _assert_settled_once(service, connector, range(12))


@pytest.mark.parametrize("fault", ["enqueue", "readback"])
def test_a_gate_ahead_that_fails_serves_its_batch_in_full(fault):
    frames = _faces(4) + [(4, True), (5, False), (6, False), (7, True)]
    service, _p, connector, metrics, events = _stack(
        frames, gate_fault=(2, fault))  # the gate that is put ahead
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert metrics.counter(mn.CASCADE_ERRORS) == 1
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 1
    # failed open: the empty frames of the second batch took the full step
    assert events.kinds("step") == [("step", (0, 1, 2, 3)),
                                    ("step", (4, 5, 6, 7))]
    assert metrics.counter(mn.FRAMES_COMPLETED_EMPTY) == 0
    _assert_settled_once(service, connector, range(8))


def test_no_gate_is_put_ahead_while_the_chip_waits_for_the_loop():
    # scores that are back at once behind 10 ms of host work a batch: the
    # loop never waits for the chip, so once the first iteration has said
    # so every step goes first
    service, _p, connector, metrics, events = _stack(
        _faces(16), chip_s=0.0, cascade_score_s=0.01)
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    batches = [tuple(range(i, i + 4)) for i in range(0, 16, 4)]
    assert events.kinds("gate", "step") == [
        ("gate", batches[0]), ("gate", batches[1]), ("step", batches[0]),
        ("step", batches[1]),
        ("gate", batches[2]), ("step", batches[2]),
        ("gate", batches[3]), ("step", batches[3])]
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 1
    assert service._chip_wait_s < 0.5 * service._iteration_s
    _assert_settled_once(service, connector, range(16))


# ---- the order of the settle -----------------------------------------------


@pytest.fixture()
def tracked_run():
    """Two closed batches on a service with a track cache: in the first,
    frame 0 is a cache hit, frame 1 is face-free, 2 and 3 survive."""
    frames = [(0, True), (1, False), (2, True), (3, True)] + _faces(4, 4)
    service, _p, connector, metrics, events = _stack(frames,
                                                     tracker_hits={0})
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    return service, connector, metrics, events


def test_early_exits_are_published_after_the_step_and_before_the_next_lookup(
        tracked_run):
    service, connector, metrics, events = tracked_run
    step = events.index("step", (2, 3))
    cached = events.index("publish", (0, "track_cache"))
    empty = events.index("publish", (1, "cascade"))
    assert step < cached < empty < events.index("lookup", 4)
    assert metrics.counter(mn.EARLY_EXITS_DEFERRED) == 2
    assert (metrics.counter(mn.FRAMES_COMPLETED_CACHED),
            metrics.counter(mn.FRAMES_COMPLETED_EMPTY)) == (1, 1)
    # a batch the tracker was consulted for puts no gate ahead of its
    # step: the next lookups have to follow its misses and publishes
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 0
    assert events.index("step", (2, 3)) < events.index("gate", (4, 5, 6, 7))
    _assert_settled_once(service, connector, range(8))


def test_note_miss_keeps_its_place_among_the_loops_tracker_calls(tracked_run):
    _service, _connector, _metrics, events = tracked_run
    calls = events.kinds("lookup", "note_miss")
    # lookups of batch n, the gate's misses of batch n, lookups of batch n+1
    assert calls == ([("lookup", seq) for seq in range(4)]
                     + [("note_miss", "cam1")]
                     + [("lookup", seq) for seq in range(4, 8)])
    # told when the verdict is read: ahead of the step's enqueue, so ahead
    # of the tracker's updates from that step's results
    first_gate = events.kinds("gate")[0]
    assert first_gate[1][:3] == (1, 2, 3)  # the hit left the buffer's front
    assert (events.index(*first_gate) < events.index("note_miss", "cam1")
            < events.index("step", (2, 3)) < events.index("update", 2))


class _SlowMisses(IdentityTracker):
    """The real tracker, whose ``note_misses`` is recorded and takes
    ``SECONDS`` (so the leaf it runs under can be told by its time)."""

    SECONDS = 0.05

    def __init__(self, events, metrics):
        super().__init__(metrics=metrics)
        self.events = events

    def note_misses(self, stream_keys):
        stream_keys = list(stream_keys)
        self.events.add("note_misses", tuple(stream_keys))
        time.sleep(self.SECONDS)
        super().note_misses(stream_keys)


def test_a_batchs_gate_misses_reach_the_tracker_once_under_track_miss():
    # one gated batch: frames 0, 1 and 3 are face-free, on three streams
    frames = [(0, False), (1, False), (2, True), (3, False)]
    service, _p, connector, metrics, events = _stack(
        frames, make_tracker=_SlowMisses)
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    # every rejected frame's stream, in the batch's order, in ONE call
    assert events.kinds("note_misses") == [
        ("note_misses", ("cam0", "cam1", "cam3"))]
    # at the verdict: after the gate, ahead of the survivor's step
    (step,) = events.kinds("step")  # frame 2, and the rung's padding
    assert step[1][0] == 2
    assert (events.index("gate", (0, 1, 2, 3))
            < events.index("note_misses", ("cam0", "cam1", "cam3"))
            < events.index(*step))
    # inside the leaf ``track_miss``: its seconds hold the call's
    counters = metrics.counters()
    assert counters[mn.LOOP_S_PREFIX + "track_miss"] >= _SlowMisses.SECONDS
    # what the callers waited for the tracker's lock, and how often they
    # took it: 4 lookups, the batch's misses, the survivor's update
    assert counters[mn.TRACKER_LOCK_ACQUIRES] == 6
    assert 0.0 <= counters[mn.TRACKER_LOCK_WAIT_S] < 1.0
    assert metrics.counter(mn.TRACK_ERRORS) == 0
    _assert_settled_once(service, connector, range(4))


def test_a_tracker_that_no_frame_consults_does_not_hold_the_gate_back():
    # frames that name no stream are never looked up (the crowd cells)
    service, _p, connector, metrics, events = _stack(
        _faces(8), tracker_hits=set(), streams=False)
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    first, second = (0, 1, 2, 3), (4, 5, 6, 7)
    assert events.kinds("gate", "step", "lookup") == [
        ("gate", first), ("gate", second), ("step", first), ("step", second)]
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 1
    _assert_settled_once(service, connector, range(8))


# ---- every admitted frame settles exactly once ------------------------------


def test_an_abandoned_step_still_answers_its_early_exits():
    frames = [(0, True), (1, False), (2, False), (3, True)] + _faces(4, 4)
    service, _p, connector, metrics, events = _stack(
        frames, step_fault=ValueError("poisoned batch"))
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert metrics.counter(mn.BATCHES_FAILED) == 1
    assert metrics.counter(mn.FRAMES_FAILED) == 2
    assert metrics.counter(mn.FRAMES_COMPLETED_EMPTY) == 2
    assert metrics.counter(mn.FRAMES_COMPLETED) == 4  # the batch ahead
    assert metrics.counter(mn.EARLY_EXITS_DEFERRED) == 0  # no step went
    _assert_settled_once(service, connector, [1, 2, 4, 5, 6, 7])


def test_a_crash_between_decision_and_enqueue_settles_batch_and_early_exits():
    frames = [(0, True), (1, False), (2, False), (3, True)] + _faces(4, 4)
    service, _p, connector, metrics, events = _stack(frames)
    stamp = service._model_stamp
    crashes = [RuntimeError("stamp lookup blew up")]

    def stamp_crashes_once(ver):
        if crashes:
            raise crashes.pop()
        return stamp(ver)

    service._model_stamp = stamp_crashes_once
    service.start(warmup=False)
    try:
        assert _wait(lambda: service.restart_pending())
        # survivors and the undelivered early exits: the crash bucket
        assert metrics.counter(mn.FRAMES_DROPPED_CRASHED) == 4
        assert connector.published() == []
        # the batch whose gate went ahead waits for the restarted loop
        assert service._ahead is not None
        assert not service.drain(timeout=0.05)
        service.restart_loop()
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert metrics.counter(mn.FRAMES_COMPLETED) == 4
    assert metrics.counter(mn.FRAMES_DROPPED_CRASHED) == 4
    assert events.kinds("step") == [("step", (4, 5, 6, 7))]
    _assert_settled_once(service, connector, range(4, 8))


@pytest.mark.parametrize("tracker_hits, crash_seq, completed_early", [
    (None, 1, 0),      # the first gate rejection's publish raises
    (None, 2, 1),      # the second: the first one stays published
    ({0}, 0, 0),       # the cache hit's: the gate's rows were never tried
])
def test_a_crash_inside_the_deferred_settle_settles_the_rest_as_crashed(
        tracker_hits, crash_seq, completed_early):
    first = ([(0, True), (1, False), (2, False), (3, True)]
             if tracker_hits is None
             else [(0, True), (1, False), (2, True), (3, True)])
    early = 2
    service, _p, connector, metrics, events = _stack(
        first + _faces(4, 4), tracker_hits=tracker_hits, crash_seq=crash_seq)
    service.start(warmup=False)
    try:
        assert _wait(lambda: service.restart_pending())
        service.restart_loop()
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert metrics.counter(mn.LOOP_CRASHES) == 1
    assert metrics.counter(mn.FRAMES_DROPPED_CRASHED) == early - completed_early
    assert (metrics.counter(mn.FRAMES_COMPLETED_EMPTY)
            + metrics.counter(mn.FRAMES_COMPLETED_CACHED)) == completed_early
    # the step had gone: its survivors are served all the same
    assert metrics.counter(mn.FRAMES_COMPLETED) == (4 - early) + 4
    assert service.ledger()["in_system"] == 0, service.ledger()
    assert service.batcher.delivered_batches == service._completed_batches
    seqs = connector.published()
    assert len(seqs) == len(set(seqs)) == 8 - (early - completed_early)


def test_stop_with_a_looked_ahead_batch_in_hand_serves_it():
    # The first step is slow on the device, so the readback worker has a
    # batch to wait for (it leaves once stopped with nothing in flight)
    # while the loop, held inside the second step's enqueue, already has
    # the third batch opened ahead.
    release = threading.Event()
    service, _p, connector, metrics, events = _stack(
        _faces(12), hold_step=(2, release), compute_s=0.5)
    service.start(warmup=False)
    stopper = threading.Thread(target=service.stop)
    try:
        assert _wait(lambda: ("step", (4, 5, 6, 7)) in events.kinds("step"))
        assert events.kinds("gate")[-1] == ("gate", (8, 9, 10, 11))
        assert service._ahead is not None
        stopper.start()
        assert _wait(lambda: not service._running)
    finally:
        release.set()
        stopper.join(timeout=15.0)
    assert not stopper.is_alive()
    assert service._ahead is None
    assert events.kinds("step")[-1] == ("step", (8, 9, 10, 11))
    assert metrics.counter(mn.FRAMES_COMPLETED) == 12
    _assert_settled_once(service, connector, range(12))


def test_brownout_trim_of_a_looked_ahead_batch_sheds_each_frame_once():
    service, _p, connector, metrics, events = _stack(_faces(8))
    service._brownout_bucket_cap = lambda: 2  # max level: the smallest rung
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert events.kinds("gate", "step") == [
        ("gate", (0, 1)), ("gate", (4, 5)), ("step", (0, 1)), ("step", (4, 5))]
    assert metrics.counter(mn.FRAMES_DROPPED_BROWNOUT) == 4
    assert metrics.counter(mn.FRAMES_COMPLETED) == 4
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 1
    _assert_settled_once(service, connector, [0, 1, 4, 5])


def test_drain_balances_with_batches_ahead_and_early_exits():
    frames = [(seq, seq % 3 != 1) for seq in range(20)]  # 5 closed batches
    service, _p, connector, metrics, events = _stack(
        frames, compute_s=0.002)
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
        assert service.batcher.pending == 0
        assert service.batcher.delivered_batches == 5
        assert service._completed_batches == 5
    finally:
        service.stop()
    empties = sum(1 for _seq, face in frames if not face)
    assert metrics.counter(mn.FRAMES_COMPLETED_EMPTY) == empties
    assert metrics.counter(mn.FRAMES_COMPLETED) == 20 - empties
    # every batch had survivors: all but the first were opened ahead, and
    # every early exit followed its step
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) == 4
    assert metrics.counter(mn.EARLY_EXITS_DEFERRED) == empties
    for gate, step in zip(events.kinds("gate")[1:], events.kinds("step")):
        assert events.index(*gate) < events.index(*step)
    _assert_settled_once(service, connector, range(20))


def test_a_looked_ahead_batchs_buffer_fits_the_staging_ring():
    # ring depth = inflight_depth + 2: the steps in flight, the batch in
    # hand and the one opened ahead of it
    frames = [(seq, seq % 4 != 2) for seq in range(32)]
    service, _p, connector, metrics, events = _stack(
        frames, inflight_depth=1, compute_s=0.004,
        ingest=IngestConfig(mode="f32"))
    ring = service.ingest.staging
    service.start(warmup=False)
    try:
        assert service.drain(timeout=10.0)
    finally:
        service.stop()
    assert ring.alloc_count == ring.preallocated
    assert _wait(lambda: set(ring.stats()["free"].values()) == {ring.depth})
    assert metrics.counter(mn.BATCHES_GATED_AHEAD) >= 1
    assert metrics.counter(mn.BATCHES_DEAD_LETTERED) == 0
    _assert_settled_once(service, connector, range(32))


# ---- the readback worker's bounded wait ------------------------------------


class _Readback:
    """A device array as ``_await_ready`` sees it. ``block`` is what
    ``block_until_ready`` does: "returns", "hangs" (a wedged chip: until
    ``release`` is set) or "raises" (a proxy that refuses to block);
    ``ready_after`` is how many ``is_ready`` polls answer False first
    (None: never ready). Every poll's instant is kept."""

    def __init__(self, block, ready_after=0):
        self.block, self.ready_after = block, ready_after
        self.release = threading.Event()
        self.polls = []

    def block_until_ready(self):
        if self.block == "hangs":
            self.release.wait(30.0)
        elif self.block == "raises":
            raise RuntimeError("this readback cannot be blocked on")
        return self

    def is_ready(self):
        self.polls.append(time.monotonic())
        return (self.ready_after is not None
                and len(self.polls) > self.ready_after)


@pytest.mark.parametrize("block, ready_after, ready", [
    ("returns", 0, True),
    ("hangs", None, False),
    ("raises", 4, True),
    ("raises", None, False),
], ids=["ready", "hangs", "raises_then_ready", "raises_never_ready"])
def test_await_ready_outcomes(block, ready_after, ready):
    service, *_ = _stack([])
    service.start(warmup=False)
    packed = _Readback(block, ready_after)
    window = 0.5
    try:
        blocker = service._blocker
        t0 = time.monotonic()
        assert service._await_ready(packed, t0 + window) is ready
        waited = time.monotonic() - t0
        if block == "hangs":
            # the deadline won: the wedged helper is abandoned, and the
            # next batch blocks on a fresh one
            assert window <= waited < window + 1.0
            assert not packed.polls
            assert isinstance(service._blocker, _ReadbackBlocker)
            assert service._blocker is not blocker
            assert service._await_ready(_Readback("returns"),
                                        time.monotonic() + 5.0)
        else:
            assert service._blocker is blocker
        if block == "returns":
            assert waited < window and not packed.polls
        if block == "raises":
            # polled at the constant's interval, until ready or the deadline
            gaps = np.diff(packed.polls)
            assert (gaps >= READY_POLL_S * 0.9).all(), gaps
            if ready:
                assert len(packed.polls) == ready_after + 1
                assert waited < window
            else:
                assert window <= waited < window + 1.0
                assert 3 <= len(packed.polls) <= window / READY_POLL_S + 2
    finally:
        packed.release.set()
        service.stop()

"""Temporal identity cache (ISSUE 17): the ``IdentityTracker`` unit
contract (confirmation, re-verify window + brownout stretch, median-
signature drift, embedder-version fence, ambiguity sweep, miss aging,
teleport re-acquisition), the synthetic video generator + oracle, the
serving gate's ``completed_cached`` ledger settlement, the fast seed-7
chaos-video variant, and the registry/bench plumbing."""

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from opencv_facerecognizer_tpu.runtime.connector import FakeConnector
from opencv_facerecognizer_tpu.runtime.fakes import (
    InstantPipeline,
    synthetic_video_stream,
)
from opencv_facerecognizer_tpu.runtime.recognizer import (
    FRAME_TOPIC,
    RESULT_TOPIC,
    RecognizerService,
)
from opencv_facerecognizer_tpu.runtime.tracker import (
    IdentityTracker,
    TrackerConfig,
)
from opencv_facerecognizer_tpu.utils import metric_names as mn
from opencv_facerecognizer_tpu.utils.metrics import Metrics

HW = (64, 64)
CAM = "cam0"


def _frame(box=(10, 8, 26, 24), value=160.0, seed=0):
    """Noise background + one identity blob (the oracle encoding:
    ``160 + 24 * label``)."""
    frame = np.random.default_rng(seed).integers(
        20, 90, size=HW).astype(np.uint8).astype(np.float32)
    if box is not None:
        y0, x0, y1, x1 = box
        frame[y0:y1, x0:x1] = value
    return frame


def _face(box=(10, 8, 26, 24), label=0, name="id0", sim=0.9, det=0.9):
    """Publish-path face dict (x-first box), as ``update`` consumes."""
    y0, x0, y1, x1 = box
    return {"box": [x0, y0, x1, y1], "label": label, "name": name,
            "similarity": sim, "detection_score": det}


def _tracker(metrics=None, **cfg):
    cfg.setdefault("reverify_frames", 4)
    return IdentityTracker(TrackerConfig(**cfg),
                           metrics=metrics or Metrics())


def _confirm(tracker, box=(10, 8, 26, 24), label=0, value=160.0,
             version=None):
    """Two full frames: seed + confirm one track (confirm_hits=2)."""
    frame = _frame(box, value)
    for _ in range(2):
        tracker.update(CAM, [_face(box, label)], frame,
                       embedder_version=version)
    return frame


# ---- unit: lifecycle, window, drift, fences --------------------------------


def test_lookup_requires_confirmation_then_hits():
    tracker = _tracker()
    frame = _frame()
    assert tracker.lookup(CAM, frame) is None          # no tracks yet
    tracker.update(CAM, [_face()], frame)
    assert tracker.lookup(CAM, frame) is None          # tentative
    tracker.update(CAM, [_face()], frame)
    hit = tracker.lookup(CAM, frame)
    assert hit is not None
    face = hit["faces"][0]
    # Payload shaped exactly like the publish path's, plus track_id.
    assert face["box"] == [8.0, 10.0, 24.0, 26.0]      # x-first
    assert face["label"] == 0 and face["name"] == "id0"
    assert face["track_id"] == hit["track_id"]
    assert tracker.stats()["tracks_live"] == 1


def test_reverify_window_and_brownout_stretch():
    tracker = _tracker(reverify_frames=4)
    frame = _confirm(tracker)
    hits = sum(tracker.lookup(CAM, frame) is not None for _ in range(6))
    assert hits == 3                                   # interval 4: 3 cached
    assert tracker.metrics.counter(mn.TRACK_REVERIFIES) == 1
    # The window edge parks the track until the next FULL frame...
    assert tracker.lookup(CAM, frame) is None
    tracker.update(CAM, [_face()], frame)
    # ...and a brownout stretch of 2.0 doubles the cached run.
    hits = sum(tracker.lookup(CAM, frame, reverify_stretch=2.0) is not None
               for _ in range(10))
    assert hits == 7


def test_drift_flags_identity_swap_but_tolerates_motion():
    tracker = _tracker(reverify_frames=100)
    frame = _confirm(tracker)
    # Ordinary 1px motion: only edge cells of the pooled signature move,
    # the MEDIAN stays ~0 — still a hit.
    assert tracker.lookup(CAM, _frame((10, 9, 26, 25))) is not None
    # In-place identity swap (same box, new fill): every cell moves by
    # the full label delta — forced verify on this very frame.
    assert tracker.lookup(CAM, _frame(value=232.0)) is None
    assert tracker.metrics.counter(mn.TRACK_REVERIFIES) >= 1
    # Parked (never served stale) until a full frame re-verifies; the
    # verify flushes the old identity and seeds the new one, which must
    # confirm (two full frames) before it serves.
    assert tracker.lookup(CAM, _frame(value=232.0)) is None
    tracker.update(CAM, [_face(label=3, name="id3")], _frame(value=232.0))
    assert tracker.metrics.counter(
        mn.TRACK_FLUSHES_PREFIX + "identity") == 1
    tracker.update(CAM, [_face(label=3, name="id3")], _frame(value=232.0))
    hit = tracker.lookup(CAM, _frame(value=232.0))
    assert hit is not None and hit["faces"][0]["label"] == 3


def test_embedder_version_fence_flushes():
    tracker = _tracker(reverify_frames=100)
    frame = _confirm(tracker, version=1)
    assert tracker.lookup(CAM, frame, embedder_version=1) is not None
    # Cutover: entries stamped v1 are dead on arrival under v2.
    assert tracker.lookup(CAM, frame, embedder_version=2) is None
    assert tracker.metrics.counter(
        mn.TRACK_FLUSHES_PREFIX + "version") == 1
    assert tracker.stats()["tracks_live"] == 0


def test_ambiguity_flushes_both_tracks():
    tracker = _tracker()
    a, b = (10, 4, 34, 28), (10, 36, 30, 56)
    frame = _frame(a)
    frame[10:30, 36:56] = 184.0
    for _ in range(2):
        tracker.update(CAM, [_face(a, 0), _face(b, 1, "id1")], frame)
    assert tracker.lookup(CAM, frame) is not None
    # The small face moves inside the big one (IoU ~0.69 > ceiling):
    # neither fails the identity check, only the sweep catches it —
    # BOTH flush, before either can capture the other's identity.
    nested = (12, 6, 32, 26)
    tracker.update(CAM, [_face(a, 0), _face(nested, 1, "id1")], frame)
    assert tracker.metrics.counter(
        mn.TRACK_FLUSHES_PREFIX + "ambiguity") == 2
    assert tracker.stats()["tracks_live"] == 0


def test_note_miss_parks_then_ttl_flushes_lost():
    tracker = _tracker(reverify_frames=100)
    frame = _confirm(tracker)
    tracker.note_miss(CAM)
    # Occlusion parks the track out of the cache without burning it...
    assert tracker.lookup(CAM, frame) is None
    tracker.update(CAM, [_face()], frame)
    assert tracker.lookup(CAM, frame) is not None
    # ...but past the TTL (miss_ttl=2) the subject is gone: flush lost.
    for _ in range(3):
        tracker.note_miss(CAM)
    assert tracker.metrics.counter(mn.TRACK_FLUSHES_PREFIX + "lost") == 1
    assert tracker.stats()["tracks_live"] == 0


def test_reacquisition_after_teleport_keeps_confirmed_state():
    tracker = _tracker(reverify_frames=100)
    _confirm(tracker)
    # The subject teleports (admission drop gap, scene cut): no IoU, no
    # centroid reach — but the FULL pipeline just verified this label at
    # the new box, so the unique unmatched track re-seeds there instead
    # of orphaning + cold-starting.
    far = (40, 40, 56, 56)
    tracker.update(CAM, [_face(far, 0)], _frame(far))
    reg = tracker.registry()
    assert len(reg) == 1 and reg[0]["confirmed"]
    assert reg[0]["box"] == [40.0, 40.0, 56.0, 56.0]
    assert tracker.lookup(CAM, _frame(far)) is not None


def test_flush_all_cold_starts():
    tracker = _tracker()
    frame = _confirm(tracker)
    assert tracker.flush_all() == 1
    assert tracker.lookup(CAM, frame) is None
    assert tracker.metrics.counter(mn.TRACK_FLUSHES_PREFIX + "reset") == 1


# ---- the lock is for bookkeeping (ISSUE 36) ---------------------------------


def _integral_image_signature(frame, box, pool=8):
    """The signature as it was pooled until ISSUE 36 (an integral image
    and a four-corner gather per cell): the reference ``_signature`` has
    to equal bit for bit."""
    h, w = frame.shape[:2]
    y0 = min(max(int(box[0]), 0), max(0, h - 1))
    x0 = min(max(int(box[1]), 0), max(0, w - 1))
    y1 = min(max(int(np.ceil(box[2])), y0 + 1), h)
    x1 = min(max(int(np.ceil(box[3])), x0 + 1), w)
    patch = np.asarray(frame[y0:y1, x0:x1], dtype=np.float32)
    ys = np.linspace(0, patch.shape[0], pool + 1).astype(int)
    xs = np.linspace(0, patch.shape[1], pool + 1).astype(int)
    r1s = np.minimum(np.maximum(ys[1:], ys[:-1] + 1), patch.shape[0])
    r0s = np.minimum(ys[:-1], r1s - 1)
    c1s = np.minimum(np.maximum(xs[1:], xs[:-1] + 1), patch.shape[1])
    c0s = np.minimum(xs[:-1], c1s - 1)
    ii = np.zeros((patch.shape[0] + 1, patch.shape[1] + 1), np.float64)
    np.cumsum(patch, axis=0, out=ii[1:, 1:])
    np.cumsum(ii[1:, 1:], axis=1, out=ii[1:, 1:])
    sums = (ii[np.ix_(r1s, c1s)] - ii[np.ix_(r0s, c1s)]
            - ii[np.ix_(r1s, c0s)] + ii[np.ix_(r0s, c0s)])
    areas = np.outer(r1s - r0s, c1s - c0s)
    return (sums / areas).astype(np.float32)


#: (y0, x0, y1, x1) on a 64 x 48 frame.
SIGNATURE_BOXES = {
    "inside": (10, 8, 38, 36),
    "inside_uneven_bins": (5, 7, 34, 30),
    "clipped_top": (-6, 10, 20, 30),
    "clipped_left": (12, -9.5, 40, 17),
    "clipped_bottom": (50, 10, 80, 40),
    "clipped_right": (8, 30, 30, 70),
    "clipped_every_edge": (-20, -20, 90, 90),
    "outside_the_frame": (70, 55, 90, 80),
    "fractional_corners": (10.3, 8.7, 37.9, 35.2),
    "fractional_under_one_pixel": (20.2, 20.4, 20.6, 20.9),
    "short_of_the_pool_in_rows": (10, 8, 15, 36),
    "short_of_the_pool_in_columns": (10, 8, 38, 11),
    "short_of_the_pool_in_both": (10, 8, 13, 14),
    "one_row": (10, 8, 11, 36),
    "one_by_one": (30, 30, 31, 31),
    "corner_pixel": (63, 47, 64, 48),
}


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("name", sorted(SIGNATURE_BOXES))
def test_signature_equals_the_integral_image_reference(name, dtype):
    frame = np.random.default_rng(36).integers(
        0, 256, size=(64, 48)).astype(dtype)
    box = np.asarray(SIGNATURE_BOXES[name], np.float32)
    for pool in (8, 5):
        got = _tracker(sig_pool=pool)._signature(frame, box)
        want = _integral_image_signature(frame, box, pool)
        assert got.dtype == np.float32 and got.shape == (pool, pool)
        assert np.array_equal(got, want), name


def test_signature_equals_the_reference_on_random_boxes_and_fractions():
    rng = np.random.default_rng(3600)
    tracker = _tracker()
    frames = [rng.integers(0, 256, size=(96, 80)).astype(np.uint8),
              rng.uniform(0, 255, size=(96, 80)).astype(np.float32)]
    for _ in range(400):
        centre = rng.uniform(-8, 104, size=2)
        half = rng.uniform(0.2, 30, size=2)
        box = np.concatenate([centre - half, centre + half]).astype(np.float32)
        for frame in frames:
            assert np.array_equal(tracker._signature(frame, box),
                                  _integral_image_signature(frame, box))


def _busy_registry(metrics):
    """Three streams: two confirmed tracks, one tentative, one at the
    edge of its miss budget."""
    tracker = _tracker(metrics=metrics, reverify_frames=100)
    for cam, box in (("a", (10, 8, 26, 24)), ("b", (30, 30, 50, 50))):
        for _ in range(2):
            tracker.update(cam, [_face(box, 1)], _frame(box))
    tracker.update("c", [_face((4, 4, 20, 20), 2)], _frame((4, 4, 20, 20)))
    tracker.note_miss("b")
    tracker.note_miss("b")
    assert tracker.lookup("a", _frame((10, 8, 26, 24))) is not None
    return tracker


def _observable(tracker):
    metrics = tracker.metrics
    counters = {k: v for k, v in metrics.counters().items()
                if not k.startswith("tracker_lock_")}
    return (tracker.registry(), tracker.stats(), counters,
            metrics.gauge(mn.TRACKS_LIVE),
            metrics.gauge(mn.TRACK_CACHE_HIT_RATE))


@pytest.mark.parametrize("keys", [
    ("a", "b", "c"),
    ("b", "nobody", "b", "a", "a", "a", "b"),   # repeats run past the TTL
    ("nobody", "nowhere"),
    (),
], ids=["each_once", "repeated_and_unknown", "unknown_only", "none"])
def test_note_misses_is_note_miss_key_by_key(keys):
    one_by_one, together = _busy_registry(Metrics()), _busy_registry(Metrics())
    for key in keys:
        one_by_one.note_miss(key)
    together.note_misses(keys)
    assert _observable(together) == _observable(one_by_one)
    # the batch took the lock once, whatever it named
    before = together.metrics.counter(mn.TRACKER_LOCK_ACQUIRES)
    together.note_misses(iter(keys))
    assert together.metrics.counter(mn.TRACKER_LOCK_ACQUIRES) == before + 1
    assert together.metrics.gauge(mn.TRACKS_LIVE) == \
        together.stats()["tracks_live"]


def test_the_lock_is_free_while_update_pools_a_signature():
    tracker = _busy_registry(Metrics())
    pooling, release = threading.Event(), threading.Event()
    pool_signature = tracker._signature

    def held_up(frame, box):
        pooling.set()
        assert release.wait(10.0)
        return pool_signature(frame, box)

    tracker._signature = held_up
    box = (10, 8, 26, 24)
    updater = threading.Thread(
        target=tracker.update, args=("a", [_face(box, 1)], _frame(box)))
    updater.start()
    try:
        assert pooling.wait(10.0)      # update is inside _signature now
        replies = []
        others = [
            threading.Thread(target=tracker.note_misses, args=(["b", "c"],)),
            threading.Thread(target=lambda: replies.append(
                tracker.lookup("c", _frame((4, 4, 20, 20))))),
        ]
        for thread in others:
            thread.start()
        for thread in others:
            thread.join(5.0)
            assert not thread.is_alive()   # neither waited for the pooling
        assert replies == [None]           # "c" is tentative, and now parked
        assert updater.is_alive()          # still held up, lock not taken
    finally:
        release.set()
        updater.join(10.0)
    assert not updater.is_alive()
    by_stream = {}
    for row in tracker.registry():
        by_stream.setdefault(row["stream"], []).append(row)
    assert by_stream["a"][0]["hits"] == 3 and "b" not in by_stream
    assert by_stream["c"][0]["misses"] == 1


def test_threads_on_every_entry_point_keep_the_registry_and_its_count():
    """More threads than cores on ``update`` / ``lookup`` / ``note_misses``
    / ``flush_all`` with the interpreter switching every 10 us: nothing
    raises, and the running count behind ``tracks_live`` is the registry's
    (a lost update to it, or a track appended outside the lock, shows)."""
    import sys

    metrics = Metrics()
    tracker = _tracker(metrics, reverify_frames=3, max_tracks_per_stream=3)
    cams = ["cam%d" % i for i in range(4)]
    boxes = [(4 + 14 * i, 6 + 12 * i, 18 + 14 * i, 20 + 12 * i)
             for i in range(4)]
    frame = _frame(boxes[0])
    errors, stop = [], threading.Event()

    def run(body):
        rng = np.random.default_rng(threading.get_ident() % 2**32)
        try:
            while not stop.is_set():
                body(rng)
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    def update(rng):
        picked = rng.permutation(4)[:int(rng.integers(0, 4))]
        tracker.update(cams[int(rng.integers(4))],
                       [_face(boxes[i], int(rng.integers(-1, 3)))
                        for i in picked], frame)

    bodies = [update] * 6 + [
        lambda rng: tracker.lookup(cams[int(rng.integers(4))], frame),
        lambda rng: tracker.lookup(cams[int(rng.integers(4))], frame),
        lambda rng: tracker.note_misses(
            [cams[int(i)] for i in rng.integers(0, 4, size=5)]),
        lambda rng: tracker.note_misses(["nobody", cams[0]]),
        lambda rng: tracker.flush_all() if rng.random() < 0.01 else None,
        lambda rng: (tracker.registry(), tracker.stats()),
    ]
    threads = [threading.Thread(target=run, args=(body,)) for body in bodies]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for thread in threads:
            thread.start()
        stop.wait(1.5)
    finally:
        stop.set()
        for thread in threads:
            thread.join(10.0)
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    assert errors == []
    live = tracker.stats()["tracks_live"]
    assert tracker._live == live == len(tracker.registry())
    tracker.note_misses(cams)                # a call that sets the gauges
    assert metrics.gauge(mn.TRACKS_LIVE) == tracker.stats()["tracks_live"]
    counters = metrics.counters()
    flushed = sum(v for k, v in counters.items()
                  if k.startswith(mn.TRACK_FLUSHES_PREFIX))
    assert counters[mn.TRACKS_CREATED] - flushed == tracker._live
    assert counters[mn.TRACKER_LOCK_ACQUIRES] > len(threads)


def _recorded_sequence(tracker):
    """``lookup`` / ``update`` / ``note_miss`` over three streams, through
    association, identity flush, re-acquisition, ambiguity, the miss TTL
    and the registry bound. Returns every cache reply and the registry
    after every step."""
    a, b, far = (10, 8, 26, 24), (30, 30, 50, 50), (40, 4, 56, 20)
    replies, registries = [], []

    def scene(*blobs):
        frame = _frame(None, seed=7)
        for (y0, x0, y1, x1), value in blobs:
            frame[y0:y1, x0:x1] = value
        return frame

    def step(kind, cam, *args, **kw):
        if kind == "lookup":
            hit = tracker.lookup(cam, *args, **kw)
            replies.append(None if hit is None else
                           (hit["track_id"], hit["embedder_version"],
                            [(f["track_id"], f["label"], f["name"], f["box"])
                             for f in hit["faces"]]))
        else:
            getattr(tracker, kind)(cam, *args, **kw)
        registries.append([
            (r["stream"], r["track_id"], r["box"], r["label"], r["confirmed"],
             r["hits"], r["misses"], r["frames_since_verify"],
             r["embedder_version"]) for r in tracker.registry()])

    two = scene((a, 160.0), (b, 184.0))
    # cam0: two subjects associate and confirm, then serve from the cache
    for _ in range(2):
        step("update", "cam0", [_face(a, 0), _face(b, 1, "id1")], two,
             embedder_version=1)
    for _ in range(4):                      # interval 4: three cached
        step("lookup", "cam0", two, embedder_version=1)
    # a slides by centroid; b's box now holds another identity: flush + seed
    a2 = (13, 11, 29, 27)
    swapped = scene((a2, 160.0), (b, 232.0))
    step("update", "cam0", [_face(a2, 0), _face(b, 3, "id3")], swapped,
         embedder_version=1)
    step("lookup", "cam0", swapped, embedder_version=1)   # id3 tentative
    step("update", "cam0", [_face(a2, 0), _face(b, 3, "id3")], swapped,
         embedder_version=1)
    step("lookup", "cam0", swapped, embedder_version=1)
    step("lookup", "cam0", scene((a2, 232.0), (b, 232.0)),
         embedder_version=1)                 # a repainted in place: drift
    # cam1: teleport re-acquisition, then the gate's misses to the TTL
    for _ in range(2):
        step("update", "cam1", [_face(a, 5, "id5")], scene((a, 200.0)))
    step("update", "cam1", [_face(far, 5, "id5")], scene((far, 200.0)))
    step("lookup", "cam1", scene((far, 200.0)))
    step("note_miss", "cam1")
    step("lookup", "cam1", scene((far, 200.0)))           # parked
    step("note_miss", "cam1")
    step("note_miss", "cam1")                             # past miss_ttl
    step("note_miss", "nobody")
    # cam2: an unknown face seeds nothing; nested boxes flush both tracks;
    # five known faces overflow a registry bound of four
    step("update", "cam2", [_face(a, -1, "unknown")], scene((a, 160.0)))
    big, nested = (10, 4, 34, 28), (12, 6, 32, 26)
    for _ in range(2):
        step("update", "cam2", [_face(big, 0), _face(b, 1, "id1")], two)
    step("update", "cam2", [_face(big, 0), _face(nested, 1, "id1")], two)
    row = [(2, 2 + 12 * i, 12, 12 + 12 * i) for i in range(5)]
    step("update", "cam2",
         [_face(box, 10 + i, "id%d" % (10 + i)) for i, box in enumerate(row)],
         scene(*((box, 150.0 + 10 * i) for i, box in enumerate(row))))
    step("lookup", "cam0", swapped, embedder_version=2)   # version fence
    return replies, registries


#: What the tracker of the commit before ISSUE 36 answered and held over
#: ``_recorded_sequence`` (recorded by running it there): the cache's
#: replies, the registry after steps 9 and 14 and at the end, the live
#: tracks after every step, and its counters.
_TWO = [(2, 0, "id0", [8.0, 10.0, 24.0, 26.0]),
        (3, 1, "id1", [30.0, 30.0, 50.0, 50.0])]
RECORDED_REPLIES = [
    (2, 1, _TWO), (2, 1, _TWO), (2, 1, _TWO), None, None,
    (2, 1, [(2, 0, "id0", [11.0, 13.0, 27.0, 29.0]),
            (4, 3, "id3", [30.0, 30.0, 50.0, 50.0])]),
    None,
    (5, None, [(5, 5, "id5", [4.0, 40.0, 20.0, 56.0])]),
    None, None]
RECORDED_REGISTRY = {
    8: [("cam0", 2, [11.0, 13.0, 27.0, 29.0], 0, True, 4, 0, 0, 1),
        ("cam0", 4, [30.0, 30.0, 50.0, 50.0], 3, True, 2, 0, 0, 1)],
    13: [("cam0", 2, [11.0, 13.0, 27.0, 29.0], 0, True, 4, 0, 1, 1),
         ("cam0", 4, [30.0, 30.0, 50.0, 50.0], 3, True, 2, 0, 1, 1),
         ("cam1", 5, [4.0, 40.0, 20.0, 56.0], 5, True, 3, 0, 0, None)],
    25: [("cam2", 9, [14.0, 2.0, 24.0, 12.0], 11, False, 1, 0, 0, None),
         ("cam2", 10, [26.0, 2.0, 36.0, 12.0], 12, False, 1, 0, 0, None),
         ("cam2", 11, [38.0, 2.0, 48.0, 12.0], 13, False, 1, 0, 0, None),
         ("cam2", 12, [50.0, 2.0, 60.0, 12.0], 14, False, 1, 0, 0, None)]}
RECORDED_TRACKS_LIVE = [2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 3, 3, 3, 3, 3, 3, 3,
                        2, 2, 2, 4, 4, 2, 6, 4]
RECORDED_COUNTERS = {
    "track_cache_hits": 5.0, "track_flushes_ambiguity": 2.0,
    "track_flushes_identity": 1.0, "track_flushes_lost": 2.0,
    "track_flushes_version": 2.0, "track_lookups": 10.0,
    "track_reverifies": 3.0, "tracks_confirmed": 6.0, "tracks_created": 11.0}


def test_recorded_sequence_replies_and_registry_as_before_the_change():
    metrics = Metrics()
    replies, registries = _recorded_sequence(
        _tracker(metrics, reverify_frames=4, max_tracks_per_stream=4))
    assert replies == RECORDED_REPLIES
    assert {at: registries[at] for at in RECORDED_REGISTRY} == \
        RECORDED_REGISTRY
    assert [len(r) for r in registries] == RECORDED_TRACKS_LIVE
    counters = metrics.counters()
    assert {k: counters.get(k, 0) for k in RECORDED_COUNTERS} == \
        RECORDED_COUNTERS


# ---- video generator + oracle ----------------------------------------------


def test_synthetic_video_stream_deterministic_and_coherent():
    a = synthetic_video_stream(30, HW, streams=2, coherence=0.9, seed=3)
    b = synthetic_video_stream(30, HW, streams=2, coherence=0.9, seed=3)
    assert len(a) == 30
    for (fa, ka, na), (fb, kb, nb) in zip(a, b):
        assert ka == kb and na == nb
        np.testing.assert_array_equal(fa, fb)
    assert {k for _f, k, _n in a} == {"cam0", "cam1"}
    # Identity blobs use the oracle encoding (160 + 24 * label).
    for frame, _k, n in a:
        if n:
            vals = set(np.unique(frame[frame >= 150]).tolist())
            assert vals <= {160, 184, 208, 232}


def test_synthetic_video_stream_identity_swap_in_place():
    rows = synthetic_video_stream(12, HW, coherence=1.0, seed=5,
                                  identity_swap_at=6)
    def blob_val(frame):
        return int(frame[frame >= 150].max())
    before, after = blob_val(rows[5][0]), blob_val(rows[6][0])
    assert before != after                             # identity changed


def test_instant_pipeline_video_oracle_decodes_labels():
    pipeline = InstantPipeline(HW, cascade_stub=True, video_oracle=True)
    # The oracle is what lets tests assert identity CORRECTNESS, not
    # just settlement: label = (fill - 160) / 24 at the blob's bbox.
    batch = np.stack([_frame(value=160.0), _frame(value=208.0)])
    packed = np.asarray(pipeline.recognize_batch_packed(batch))
    from opencv_facerecognizer_tpu.parallel.pipeline import unpack_result
    result = unpack_result(packed, pipeline.top_k)
    assert bool(result.valid[0, 0]) and bool(result.valid[1, 0])
    assert int(result.labels[0, 0, 0]) == 0
    assert int(result.labels[1, 0, 0]) == 2


# ---- serving gate: completed_cached settlement -----------------------------


def _service(tracker):
    metrics = tracker.metrics
    pipeline = InstantPipeline(HW, cascade_stub=True, video_oracle=True)
    connector = FakeConnector()
    service = RecognizerService(
        pipeline, connector, batch_size=4, frame_shape=HW,
        flush_timeout=0.01, inflight_depth=2, similarity_threshold=0.0,
        metrics=metrics, bucket_sizes=(1, 2, 4), cascade=True,
        subject_names=["id0", "id1", "id2", "id3"], tracker=tracker)
    pipeline.prewarm_batch_shapes(service._bucket_ladder, HW,
                                  service.batcher.dtype)
    service._warmed = True
    return service, connector


def test_service_settles_cache_hits_as_completed_cached():
    tracker = _tracker(reverify_frames=6)
    service, connector = _service(tracker)
    results = []
    connector.subscribe(RESULT_TOPIC, lambda t, m: results.append(m))
    service.start(warmup=False)
    rows = synthetic_video_stream(24, HW, coherence=1.0, seed=1)
    for i, (frame, key, _n) in enumerate(rows):
        connector.inject(FRAME_TOPIC, {"frame": frame,
                                       "meta": {"seq": i, "stream": key}})
        assert service.drain(timeout=20.0)
    service.stop()
    ledger = service.ledger()
    assert ledger["completed_cached"] > 0 and ledger["completed"] > 0
    drops = sum(ledger["drops_by_reason"].values())
    # The extended invariant: every admitted frame lands in exactly one
    # terminal bucket, cached included.
    assert ledger["admitted"] == (ledger["completed"]
                                  + ledger["completed_empty"]
                                  + ledger["completed_cached"] + drops)
    assert ledger["in_system"] == 0
    assert len(results) == 24
    cached = [m for m in results if m.get("exit") == "track_cache"]
    assert len(cached) == ledger["completed_cached"]
    full_label = next(m for m in results
                      if m.get("exit") is None)["faces"][0]["label"]
    for m in cached:
        assert "track_id" in m
        assert m["faces"][0]["label"] == full_label  # never a wrong identity
    assert tracker.metrics.counter(mn.TRACK_BATCH_EXITS) >= 0


def test_service_without_stream_key_takes_full_path():
    tracker = _tracker()
    service, connector = _service(tracker)
    service.start(warmup=False)
    for i in range(8):
        connector.inject(FRAME_TOPIC, {"frame": _frame(seed=i),
                                       "meta": {"seq": i}})
        assert service.drain(timeout=20.0)
    service.stop()
    ledger = service.ledger()
    # No stream identity -> no temporal coherence to exploit: the cache
    # must stand aside, not guess.
    assert ledger["completed_cached"] == 0
    assert ledger["completed"] == 8


# ---- chaos: the fast seed-7 video variant ----------------------------------

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
_spec = importlib.util.spec_from_file_location(
    "chaos_soak_video", os.path.join(REPO_ROOT, "scripts", "chaos_soak.py"))
chaos_soak = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chaos_soak)


def test_chaos_video_fast_deterministic():
    """Seed-7 tier-1 variant of ``--scenario video``: identity swap with
    the drift check armed (zero stale) and disabled (stale bounded by
    the re-verify window), ambiguity flushing both, failover cold-start
    + version fence, exact extended ledgers and span accounting."""
    report = chaos_soak.run_video(seconds=1.0, seed=7)
    assert report["ok"], report["failures"]
    assert report["swap_drift"]["stale_after_swap"] == 0
    assert report["swap_drift"]["cached_total"] > 0
    assert report["ambiguity"]["flushes"] >= 2
    assert report["ambiguity"]["cached_past_window"] == 0
    assert report["failover"]["version_flushes"] >= 1
    acct = report["span_accounting"]
    assert acct["completed_cached"] > 0
    assert acct["traced"] == (acct["completed"] + acct["completed_empty"]
                              + acct["completed_cached"]
                              + sum(acct["drops"].values()))


# ---- registry / plumbing ---------------------------------------------------


def test_track_metric_names_registered():
    names = set(mn.all_names())
    for name in (mn.TRACK_LOOKUPS, mn.TRACK_CACHE_HITS,
                 mn.TRACK_CACHE_HIT_RATE, mn.TRACK_REVERIFIES,
                 mn.TRACK_BATCH_EXITS, mn.TRACK_ERRORS,
                 mn.FRAMES_COMPLETED_CACHED):
        assert name in names
    assert mn.TRACK_FLUSHES_PREFIX in set(mn.all_prefixes())
    from tools.ocvf_lint.wiring import ATTR_HINTS, HOT_PATH_SUFFIXES

    assert ATTR_HINTS["tracker"] == "IdentityTracker"
    assert any(s.endswith("runtime/tracker.py") for s in HOT_PATH_SUFFIXES)


@pytest.mark.parametrize("metric, numerator", [
    ("track_miss_ms_per_batch.backlog", mn.LOOP_S_PREFIX + "track_miss"),
    ("tracker_lock_wait_ms_per_batch.backlog", mn.TRACKER_LOCK_WAIT_S),
])
def test_per_batch_tracker_metrics_read_registered_counters(metric,
                                                            numerator):
    """The benchmark's two readings of this layer are data over the reader
    ``counter_quotient``: milliseconds a popped batch, in the replay cell
    (the only one whose frames name a camera)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        (declared,) = [m for m in json.load(fh)["per_layer"]
                       if m["name"] == metric]
    assert declared["workloads"] == ["watchlist8m.replay"]
    assert (declared["layer"], declared["source"], declared["better"],
            declared["moves"]) == ("service loop", "program_counter",
                                   "lower", "served_fps")
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           metric + ".json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "counter_quotient" and spec["scale"] == 1000
    assert spec["numerator"] == [numerator]
    assert spec["denominator"] == [mn.LOOP_BATCHES]
    assert "track_miss" in mn.LOOP_LEAVES
    assert {mn.TRACKER_LOCK_WAIT_S, mn.LOOP_BATCHES} <= set(mn.all_names())
    # a window of 40 batches that waited 0.1 s reads 2.5 ms a batch; a
    # program without the counter (the parent) gives no reading
    from benchmark.readers import counter_quotient
    assert counter_quotient.read(
        spec, {"counters": {numerator: 0.1, mn.LOOP_BATCHES: 40}}) == 2.5
    assert counter_quotient.read(
        spec, {"counters": {mn.LOOP_BATCHES: 40}}) is None


def test_expo_tracks_endpoint_and_null_shape():
    import urllib.request

    from opencv_facerecognizer_tpu.runtime.expo import ExpoServer

    tracker = _tracker()
    _confirm(tracker)

    class _Svc:  # the expo surface only reads .tracker
        pass

    svc = _Svc()
    svc.tracker = tracker
    expo = ExpoServer(metrics=Metrics(), service=svc, port=0)
    expo.start()
    try:
        with urllib.request.urlopen(
                f"http://{expo.host}:{expo.port}/tracks", timeout=5) as r:
            body = json.loads(r.read())
        assert len(body["tracks"]) == 1
        assert body["tracks"][0]["confirmed"]
        assert body["stats"]["tracks_live"] == 1
    finally:
        expo.stop()
    # Unwired tracker answers the null shape, not a 404.
    bare = ExpoServer(metrics=Metrics(), port=0)
    bare.start()
    try:
        with urllib.request.urlopen(
                f"http://{bare.host}:{bare.port}/tracks", timeout=5) as r:
            assert json.loads(r.read())["tracks"] is None
    finally:
        bare.stop()


def test_bench_compare_tracks_video_uplift():
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(REPO_ROOT, "scripts",
                                      "bench_compare.py"))
    bench_compare = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_compare)
    assert "video_cache_uplift" in bench_compare.METRICS
    doc = {"video": {"cells": {"c90": {"uplift": 2.5}}}}
    extract = bench_compare.METRICS["video_cache_uplift"][0]
    assert extract(doc) == 2.5
    # Regression direction: candidate losing the uplift fails.
    report = bench_compare.compare(doc, {"video": {"cells": {
        "c90": {"uplift": 1.0}}}})
    assert any(r["metric"] == "video_cache_uplift"
               and r["verdict"] == "regression" for r in report["metrics"])

"""Fake serving backends for deterministic perf tests and smoke benches.

``InstantPipeline`` stands in for ``RecognitionPipeline`` in front of
``RecognizerService``: dispatch returns immediately with a packed result
array whose "device" behavior is scripted — optionally a simulated compute
delay before readiness, and optionally a **sync-poll cost** charged on
every ``is_ready`` call (a backend whose readiness poll has a fixed
cost, reproduced on CPU). That makes the serving loop's host-side overheads —
batching delay, poll sleeps vs event-driven readback, publish — measurable
in isolation, fast, and deterministic: the tier-1 perf smoke asserts the
overlapped readback worker keeps ``ready_wait`` off the poll floor without
needing real hardware (see ``bench_serving.run_smoke`` and
``tests/test_serving_perf.py``).

No recognition happens: every frame comes back with zero detected faces,
which is exactly what the loop-perf surfaces need (results still publish
per frame, so end-to-end latency is real).
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Tuple

import numpy as np


class FakePacked:
    """A packed-result device array stand-in with scripted readiness.

    ``is_ready`` reports completion of the simulated compute (charging
    ``poll_cost_s`` per call — the sync-poll floor); ``block_until_ready``
    sleeps exactly the remaining compute time (the event-driven wait);
    ``__array__`` materializes after blocking.
    """

    def __init__(self, arr: np.ndarray, ready_at: float,
                 poll_cost_s: float = 0.0):
        self._arr = arr
        self._ready_at = ready_at
        self._poll_cost_s = float(poll_cost_s)

    def is_ready(self) -> bool:
        if self._poll_cost_s > 0.0:
            time.sleep(self._poll_cost_s)
        return time.monotonic() >= self._ready_at

    def block_until_ready(self) -> "FakePacked":
        delay = self._ready_at - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        return self

    def copy_to_host_async(self) -> None:
        pass

    def __array__(self, dtype=None):
        self.block_until_ready()
        return self._arr if dtype is None else self._arr.astype(dtype)


class _GalleryStub:
    size = 0
    grow_count = 0

    # Enough of the ShardedGallery surface that a ServiceSupervisor can
    # checkpoint/restore over a fake pipeline (the overload soak wraps
    # the service in one): nothing to snapshot, nothing to restore.
    def snapshot(self):
        return ()

    def load_snapshot(self, *parts, embedder_version=None) -> None:
        pass


class InstantPipeline:
    """Drop-in pipeline for RecognizerService with scripted device timing.

    ``compute_s`` — seconds after dispatch until the batch's readback is
    ready (simulated device compute + D2H). ``sync_poll_floor_s`` — cost
    charged on EVERY ``is_ready`` call, emulating a backend whose
    readiness poll has a fixed cost: a loop that polled readiness would pay
    it per check, while the readback worker's event-driven
    ``block_until_ready`` never does.
    """

    def __init__(self, frame_shape: Tuple[int, int], top_k: int = 1,
                 max_faces: int = 2, compute_s: float = 0.0,
                 sync_poll_floor_s: float = 0.0, dispatch_s: float = 0.0,
                 faces_per_frame: int = 0,
                 h2d_gb_s: Optional[float] = None,
                 dispatch_per_frame_s: float = 0.0,
                 cascade_stub: bool = False,
                 cascade_score_s: float = 0.0,
                 video_oracle: bool = False,
                 oracle_sim: float = 0.9):
        self.frame_shape = tuple(frame_shape)
        self.top_k = int(top_k)
        self.max_faces = int(max_faces)
        self.compute_s = float(compute_s)
        self.sync_poll_floor_s = float(sync_poll_floor_s)
        #: host-side seconds charged PER FRAME inside each dispatch call,
        #: on top of ``dispatch_s`` — models the per-frame device cost
        #: the pre-PR-1 stage table attributed to detect,
        #: so the cascade's survivor compaction actually buys capacity
        #: against this fake's wall the way it does on the chip: a
        #: smaller dispatched bucket costs proportionally less.
        self.dispatch_per_frame_s = float(dispatch_per_frame_s)
        #: stage-1 cascade stand-in (the serving gate duck-types
        #: ``pipeline.cascade`` + ``cascade_scores``): scores each frame
        #: by peak brightness — the synthetic face blobs are stamped at
        #: 200 on a <=90 background (``_stamp_faces``), so a brightness
        #: threshold is a deterministic, training-free oracle for the
        #: perf smokes. ``cascade_score_s`` is the scripted cost of one
        #: stage-1 pass (charged per call, whole-batch).
        self.cascade = "brightness-stub" if cascade_stub else None
        self.cascade_score_s = float(cascade_score_s)
        self.cascade_calls = 0
        #: (batch, dtype) stage-1 signatures already "compiled" — the
        #: cascade mirror of ``compiled_batch_sizes``, feeding
        #: ``last_cascade_info`` for the recompile watchdog.
        self.compiled_cascade_sigs: set = set()
        self.last_cascade_info: dict = {}
        #: simulated H2D bandwidth (GB/s): each dispatch additionally
        #: sleeps frames.nbytes / bandwidth, making the fake backend
        #: TRANSFER-bound (the regime the ingest subsystem targets) — a
        #: uint8 batch (4x fewer bytes) then completes ~4x more frames
        #: against the same wall, which is what the ingest smoke's
        #: uplift arm measures. None = no transfer cost (the historical
        #: behavior; dispatch_s alone is the wall).
        self.h2d_gb_s = None if h2d_gb_s is None else float(h2d_gb_s)
        #: scripted detections: the first N face slots of every frame come
        #: back valid (fixed box, det_score 1, label 0, sim 1) instead of
        #: the default zero-face result — what the rollout parity hook and
        #: the enrolment-collection paths need to fire without a real
        #: detector. 0 keeps the historical zero-face behavior.
        self.faces_per_frame = min(int(faces_per_frame), int(max_faces))
        #: host-side seconds charged INSIDE each dispatch call (the serve
        #: thread sleeps it out). ``compute_s`` is pure latency — batches
        #: overlap through the in-flight queue and never limit throughput;
        #: ``dispatch_s`` models a saturated dispatch pipe, giving the fake
        #: backend a hard capacity of ``batch_size / dispatch_s`` frames/s
        #: — the deterministic overload wall the admission/brownout tests
        #: and the overload soak push against.
        self.dispatch_s = float(dispatch_s)
        self.face_size = (8, 8)
        self.gallery = _GalleryStub()
        self.fault_injector = None
        self.dispatches = 0
        #: batch dimension of every dispatch, in order — lets tests assert
        #: the service's bucket ladder sliced partial batches as designed.
        self.batch_sizes_seen: list = []
        #: (batch, dtype) signatures already "compiled" (first dispatch of
        #: a signature is a cache miss, like the real packed-step cache,
        #: whose ``_step_key`` includes the input dtype — a uint8 ingest
        #: dispatch against an f32-only prewarm MUST read as a recompile)
        #: — drives the ``last_dispatch_info`` provenance the recompile
        #: watchdog reads, so the watchdog is testable without hardware.
        #: Tests clear this to inject a post-warmup compile.
        self.compiled_batch_sizes: set = set()
        self.last_dispatch_info: dict = {}
        #: video oracle (ISSUE 17): derive detections host-side from the
        #: frame pixels instead of scripting them — each identity in a
        #: ``synthetic_video_stream`` frame is a blob filled with the
        #: distinct value ``160 + 24*i`` (all >= the brightness-stub's
        #: 150 floor), so the oracle recovers box AND label exactly:
        #: label ``i`` at the mask's bounding box, fixed ``oracle_sim``
        #: similarity. This is what lets the tracker bench/chaos runs
        #: assert identity-correctness end-to-end without a trained
        #: embedder: the pipeline "recognizes" whoever is actually in
        #: the frame, and an in-place fill swap IS an identity change.
        self.video_oracle = bool(video_oracle)
        self.oracle_sim = float(oracle_sim)

    @staticmethod
    def _sig(batch, dtype) -> tuple:
        return (int(batch), str(np.dtype(dtype)))

    def prewarm_batch_shapes(self, ladder, frame_shape,
                             dtype=np.float32) -> None:
        """Mirror ``RecognitionPipeline.prewarm_batch_shapes``: mark every
        (ladder bucket, transfer dtype) signature compiled — BOTH stages
        when the cascade stub is armed, like the real pipeline — so
        post-warmup serving dispatches are cache hits: the recompile
        watchdog's armed-and-silent baseline."""
        for bucket in ladder:
            self.compiled_batch_sizes.add(self._sig(bucket, dtype))
            if self.cascade is not None:
                self.compiled_cascade_sigs.add(self._sig(bucket, dtype))

    def cascade_scores(self, frames) -> np.ndarray:
        """Scripted stage-1 pass: [B, H, W] -> [B] scores (1.0 for frames
        carrying a bright face blob, 0.0 otherwise — see ``cascade`` in
        ``__init__``). Charges ``cascade_score_s`` per call and records
        compile provenance like the packed path."""
        host = np.asarray(frames)
        if self.cascade_score_s > 0.0:
            time.sleep(self.cascade_score_s)
        self.cascade_calls += 1
        sig = self._sig(host.shape[0], host.dtype)
        self.last_cascade_info = {
            "cache_hit": sig in self.compiled_cascade_sigs}
        self.compiled_cascade_sigs.add(sig)
        return (host.reshape(host.shape[0], -1).max(axis=1)
                >= 150).astype(np.float32)

    def recognize_batch_packed(self, frames) -> FakePacked:
        if self.fault_injector is not None:
            self.fault_injector.on_dispatch()
        host = np.asarray(frames)
        if self.dispatch_s > 0.0:
            time.sleep(self.dispatch_s)  # capacity wall (see __init__)
        if self.dispatch_per_frame_s > 0.0:
            # Per-frame device-cost wall: a compacted/bucketed batch pays
            # for the frames it actually carries (see __init__).
            time.sleep(host.shape[0] * self.dispatch_per_frame_s)
        if self.h2d_gb_s:
            # Transfer wall: the scripted host->device link cost of shipping
            # this batch's actual bytes (so uint8 staging pays 1/4 the
            # f32 price, like the real link).
            time.sleep(host.nbytes / (self.h2d_gb_s * 1e9))
        self.dispatches += 1
        b = int(host.shape[0])
        self.batch_sizes_seen.append(b)
        sig = self._sig(b, host.dtype)
        self.last_dispatch_info = {"cache_hit": sig in self.compiled_batch_sizes,
                                   "mode": "fake"}
        self.compiled_batch_sizes.add(sig)
        # pack_result layout: boxes(4) | det_score | valid | labels(k) |
        # sims(k) in int32 lanes, the floats as their bits (written
        # through ``packed``, a float32 view of the lanes; a label is
        # written through ``lanes`` itself); valid=0 everywhere -> zero
        # faces per frame (unless faces_per_frame scripts some
        # detections in).
        lanes = np.zeros((b, self.max_faces, 6 + 2 * self.top_k), np.int32)
        packed = lanes.view(np.float32)
        if self.video_oracle:
            # Pixel-derived detections (see __init__): one face per
            # distinct identity fill value present in the frame.
            for fi in range(b):
                slot = 0
                for v in np.unique(host[fi]):
                    fv = float(v)
                    if fv < 160.0 or fv > 232.0 or (fv - 160.0) % 24.0:
                        continue
                    if slot >= self.max_faces:
                        break
                    ys, xs = np.nonzero(host[fi] == v)
                    packed[fi, slot, 0:4] = (float(ys.min()), float(xs.min()),
                                             float(ys.max()) + 1.0,
                                             float(xs.max()) + 1.0)
                    packed[fi, slot, 4] = 1.0   # det_score
                    packed[fi, slot, 5] = 1.0   # valid
                    lanes[fi, slot, 6] = int((fv - 160.0) / 24.0)  # label
                    packed[fi, slot, 6 + self.top_k] = self.oracle_sim
                    slot += 1
            return FakePacked(lanes, time.monotonic() + self.compute_s,
                              poll_cost_s=self.sync_poll_floor_s)
        if self.faces_per_frame:
            h, w = self.frame_shape
            for j in range(self.faces_per_frame):
                packed[:, j, 0:4] = (2.0, 2.0, max(6.0, h - 2.0),
                                     max(6.0, w - 2.0))  # y0 x0 y1 x1
                packed[:, j, 4] = 1.0   # det_score
                packed[:, j, 5] = 1.0   # valid
                lanes[:, j, 6] = 0      # top-1 label
                packed[:, j, 6 + self.top_k] = 1.0  # top-1 similarity
        return FakePacked(lanes, time.monotonic() + self.compute_s,
                          poll_cost_s=self.sync_poll_floor_s)


def _stamp_faces(rng, frame: np.ndarray, n_faces: int) -> None:
    """Stamp ``n_faces`` bright face-ish blobs (a light square with
    darker eye dots) onto ``frame`` in place at seeded positions. The
    blob peak (200) sits far above the 20-90 background, so both the
    ``InstantPipeline`` brightness-stub cascade and a trained
    ``FaceGate`` separate stamped from face-free frames cleanly."""
    h, w = frame.shape
    for _face in range(int(n_faces)):
        side = int(rng.integers(max(6, h // 8), max(8, h // 3)))
        y0 = int(rng.integers(0, max(1, h - side)))
        x0 = int(rng.integers(0, max(1, w - side)))
        frame[y0:y0 + side, x0:x0 + side] = 200
        ey = y0 + side // 3
        for ex in (x0 + side // 4, x0 + 3 * side // 4):
            frame[max(0, ey - 1):ey + 1, max(0, ex - 1):ex + 1] = 60


def synthetic_jpeg_frames(n: int, frame_hw: Tuple[int, int] = (64, 64),
                          seed: int = 0, quality: int = 85,
                          faces_per_frame: int = 0):
    """Seeded synthetic camera payloads as REAL JPEG bytes: ``n`` pairs of
    ``(jpeg_bytes, source_frame)`` (uint8 grayscale). Deterministic per
    seed — the same seed always produces byte-identical payloads, so the
    ingest tests and the smoke bench replay exactly.

    ``faces_per_frame`` stamps that many bright face-ish blobs
    (``_stamp_faces``) onto each frame at seeded positions — the knob the
    face-density traffic mix (``synthetic_frame_stream``) composes with.
    """
    from opencv_facerecognizer_tpu.runtime.ingest import encode_jpeg

    rng = np.random.default_rng(seed)
    h, w = int(frame_hw[0]), int(frame_hw[1])
    out = []
    for _ in range(int(n)):
        frame = rng.integers(20, 90, size=(h, w)).astype(np.uint8)
        _stamp_faces(rng, frame, faces_per_frame)
        out.append((encode_jpeg(frame, quality=quality), frame))
    return out


def synthetic_frame_stream(n: int, frame_hw: Tuple[int, int] = (64, 64),
                           face_density: float = 0.3, seed: int = 0,
                           faces_per_frame: int = 1, jpeg: bool = False,
                           quality: int = 85):
    """Seeded face-density traffic mix (ISSUE 13; reusable by the video
    workload of ROADMAP item #3): ``n`` frames of which EXACTLY
    ``round(n * face_density)`` carry ``faces_per_frame`` stamped face
    blobs, the rest pure background — the deterministic mixed stream the
    cascade uplift bench sweeps density over. Which positions carry
    faces is a seeded permutation, so the mix is interleaved, not a
    prefix, and byte-identical per seed.

    Returns ``[(frame, n_faces)]`` (uint8 grayscale), or with
    ``jpeg=True`` ``[(jpeg_bytes, frame, n_faces)]`` — composing with
    the PR 12 compressed-intake path the way ``synthetic_jpeg_frames``
    payloads do."""
    n = int(n)
    rng = np.random.default_rng(seed)
    h, w = int(frame_hw[0]), int(frame_hw[1])
    n_faced = int(round(n * float(face_density)))
    faced = np.zeros(n, dtype=bool)
    faced[rng.permutation(n)[:n_faced]] = True
    out = []
    for i in range(n):
        frame = rng.integers(20, 90, size=(h, w)).astype(np.uint8)
        k = int(faces_per_frame) if faced[i] else 0
        _stamp_faces(rng, frame, k)
        if jpeg:
            from opencv_facerecognizer_tpu.runtime.ingest import encode_jpeg

            out.append((encode_jpeg(frame, quality=quality), frame, k))
        else:
            out.append((frame, k))
    return out


def synthetic_video_stream(n: int, frame_hw: Tuple[int, int] = (64, 64),
                           streams: int = 1, tracks_per_stream: int = 1,
                           coherence: float = 0.9, face_density: float = 1.0,
                           seed: int = 0, step_px: int = 1,
                           identity_swap_at: Optional[int] = None,
                           track_churn: float = 0.0, jpeg: bool = False,
                           quality: int = 85):
    """Seeded multi-stream video traffic (ISSUE 17): ``n`` frames
    round-robined across ``streams`` camera keys, each carrying
    ``tracks_per_stream`` persistent identity blobs whose motion is
    temporally coherent — the workload the temporal identity cache is
    built to exploit, and the one its chaos arms attack.

    Identity encoding: blob ``i`` is filled with the constant value
    ``160 + 24*(identity % 4)``, which ``InstantPipeline(video_oracle=
    True)`` decodes back into (box, label) exactly — so recognition
    results track frame CONTENT, and the knobs below change what the
    pipeline reports, not just the pixels:

    - ``coherence``: per-frame probability a blob takes a small
      ``±step_px`` walk instead of teleporting to a random position.
      0.9 ~ video, 0.0 ~ shuffled stills (every frame a jump, so box
      association — and with it the cache — finds nothing to reuse).
    - ``track_churn``: per-frame probability a blob is replaced
      outright (new position AND next identity) — scene-cut churn.
    - ``identity_swap_at``: per-stream frame index at which track 0
      changes identity IN PLACE (same box, new fill) — the cache-
      poisoning probe: a tracker that trusts box association alone
      would keep publishing the old name.
    - ``face_density``: probability a frame carries its blobs at all;
      blob-free frames are pure background (the cascade rejects them).

    Returns ``[(frame, stream_key, n_faces)]`` (uint8), or with
    ``jpeg=True`` ``[(jpeg_bytes, frame, stream_key, n_faces)]`` —
    composing with the PR 12 compressed-intake path like
    ``synthetic_frame_stream``. (JPEG is lossy: feed the oracle the
    raw ``frame``, not the decode, when identity exactness matters.)"""
    n = int(n)
    streams = max(1, int(streams))
    rng = np.random.default_rng(seed)
    h, w = int(frame_hw[0]), int(frame_hw[1])
    side = max(8, h // 4)
    step = max(1, int(step_px))

    def _spawn(ident):
        return {"ident": int(ident) % 4,
                "y": int(rng.integers(0, max(1, h - side))),
                "x": int(rng.integers(0, max(1, w - side)))}

    state = []
    for _s in range(streams):
        tracks = [_spawn(i) for i in range(int(tracks_per_stream))]
        state.append({"tracks": tracks, "frame_idx": 0,
                      "next_ident": int(tracks_per_stream)})

    out = []
    for i in range(n):
        s = i % streams
        st = state[s]
        for ti, t in enumerate(st["tracks"]):
            if track_churn and rng.random() < float(track_churn):
                st["tracks"][ti] = _spawn(st["next_ident"])
                st["next_ident"] += 1
                continue
            if rng.random() < float(coherence):
                t["y"] = int(np.clip(t["y"] + rng.integers(-step, step + 1),
                                     0, max(0, h - side)))
                t["x"] = int(np.clip(t["x"] + rng.integers(-step, step + 1),
                                     0, max(0, w - side)))
            else:
                t["y"] = int(rng.integers(0, max(1, h - side)))
                t["x"] = int(rng.integers(0, max(1, w - side)))
        if (identity_swap_at is not None
                and st["frame_idx"] == int(identity_swap_at)
                and st["tracks"]):
            t0 = st["tracks"][0]
            t0["ident"] = (t0["ident"] + 1) % 4
        frame = rng.integers(20, 90, size=(h, w)).astype(np.uint8)
        faced = rng.random() < float(face_density)
        k = 0
        if faced:
            for t in st["tracks"]:
                fill = 160 + 24 * (t["ident"] % 4)
                frame[t["y"]:t["y"] + side, t["x"]:t["x"] + side] = fill
                k += 1
        st["frame_idx"] += 1
        key = "cam%d" % s
        if jpeg:
            from opencv_facerecognizer_tpu.runtime.ingest import encode_jpeg

            out.append((encode_jpeg(frame, quality=quality), frame, key, k))
        else:
            out.append((frame, key, k))
    return out


def build_overload_stack(frame_shape=(32, 32), batch_size: int = 8,
                         dispatch_s: float = 0.04,
                         max_inflight_frames: int = 24,
                         brownout_queue_wait_s: float = 0.05,
                         brownout_dwell_s: float = 0.3,
                         stale_after_s: float = 0.25,
                         fault_injector=None, journal=None, tracer=None,
                         slo_monitor=None, metrics=None):
    """The canonical deterministic overload harness: an
    ``InstantPipeline`` with a hard ``batch_size / dispatch_s`` frames/s
    capacity wall behind a ``RecognizerService`` with the full protection
    stack armed (admission bound with interactive reserve, brownout with
    hysteresis, stale shedding, halved bucket ladder). Single-sourced so
    ``scripts/chaos_soak.run_overload`` and
    ``bench_serving.run_overload_sweep`` exercise — and their notes/pass
    criteria describe — the exact same configuration. Returns
    ``(pipeline, service, connector)``."""
    from opencv_facerecognizer_tpu.runtime.admission import AdmissionController
    from opencv_facerecognizer_tpu.runtime.connector import FakeConnector
    from opencv_facerecognizer_tpu.runtime.recognizer import RecognizerService
    from opencv_facerecognizer_tpu.runtime.resilience import (
        BrownoutPolicy,
        ResiliencePolicy,
    )

    pipeline = InstantPipeline(frame_shape, dispatch_s=dispatch_s)
    connector = FakeConnector()
    service = RecognizerService(
        pipeline, connector, batch_size=batch_size, frame_shape=frame_shape,
        flush_timeout=0.03, inflight_depth=2, similarity_threshold=0.0,
        metrics=metrics,
        resilience=ResiliencePolicy(readback_deadline_s=2.0),
        fault_injector=fault_injector,
        admission=AdmissionController(max_inflight_frames=max_inflight_frames),
        brownout=BrownoutPolicy(queue_wait_s=brownout_queue_wait_s,
                                dwell_s=brownout_dwell_s),
        dead_letter_journal=journal,
        shed_stale_after_s=stale_after_s,
        bucket_sizes=(max(1, batch_size // 2), batch_size),
        tracer=tracer,
        slo_monitor=slo_monitor,
    )
    return pipeline, service, connector


def build_replica_fleet(n_replicas: int, frame_shape=(32, 32),
                        batch_size: int = 8, dispatch_s: float = 0.04,
                        health_interval_s: float = 0.1,
                        budget_fps=None, router_metrics=None,
                        tracer=None, replica_fault_injectors=None,
                        router_fault_injector=None,
                        link_deadline_s=None, hedge_deadline_s=None,
                        dedup_window: int = 4096):
    """N in-process serving replicas behind one ``TopicRouter`` — the
    deterministic scale-out harness: each replica is the canonical
    overload stack (``build_overload_stack``: a hard ``batch_size /
    dispatch_s`` frames/s capacity wall with admission/brownout armed)
    with its OWN ``Metrics``, and the router spreads camera topics across
    them with rendezvous hashing + in-process health probes. Shared by
    ``bench_serving.run_replica_scaleout`` and the replication chaos
    scenario, so the bench ladder and the soak's failover assertions
    exercise one configuration. Returns ``(router, stacks)`` where each
    stack is ``(pipeline, service, connector, metrics)``.

    Partition-chaos knobs (ISSUE 16): ``replica_fault_injectors`` (list
    or per-index dict) arms each replica's OWN fault boundary;
    ``router_fault_injector`` arms the router's transport crossings;
    ``link_deadline_s``/``hedge_deadline_s``/``dedup_window`` pass
    straight through to ``TopicRouter`` — all default off/inert so the
    scale-out bench keeps its exact pre-16 configuration."""
    from opencv_facerecognizer_tpu.runtime.replication import (
        ReplicaHandle, TopicRouter, service_health_probe,
    )
    from opencv_facerecognizer_tpu.utils.metrics import Metrics

    stacks = []
    handles = []
    for i in range(n_replicas):
        metrics = Metrics()
        if isinstance(replica_fault_injectors, dict):
            faults = replica_fault_injectors.get(i)
        elif replica_fault_injectors is not None:
            faults = replica_fault_injectors[i]
        else:
            faults = None
        pipeline, service, connector = build_overload_stack(
            frame_shape=frame_shape, batch_size=batch_size,
            dispatch_s=dispatch_s, metrics=metrics,
            fault_injector=faults)
        stacks.append((pipeline, service, connector, metrics))
        handles.append(ReplicaHandle(
            f"replica-{i}", connector,
            health_fn=service_health_probe(service),
            budget_fps=budget_fps))
    router = TopicRouter(handles, metrics=router_metrics, tracer=tracer,
                         health_interval_s=health_interval_s,
                         fault_injector=router_fault_injector,
                         link_deadline_s=link_deadline_s,
                         hedge_deadline_s=hedge_deadline_s,
                         dedup_window=dedup_window)
    return router, stacks


class TrafficRecorder:
    """Seq-tagged send/receive recorder for driving a service under
    offered load: stamps each frame at offer time, collects its result
    publish time, and reduces to completion counts and latency
    percentiles. Shared by ``scripts/chaos_soak.run_overload`` and
    ``bench_serving.run_overload_sweep`` so the soak's pass criteria and
    the bench's rows measure traffic identically."""

    def __init__(self, connector):
        from opencv_facerecognizer_tpu.runtime.recognizer import RESULT_TOPIC

        self.send_t: dict = {}
        self.done_t: dict = {}
        self._lock = threading.Lock()
        connector.subscribe(RESULT_TOPIC, self._on_result)

    def _on_result(self, topic, message) -> None:
        seq = (message.get("meta") or {}).get("seq")
        if seq is not None:
            with self._lock:
                self.done_t.setdefault(seq, time.monotonic())

    def offer(self, connector, payload: dict, seq, priority: str,
              meta_extra: Optional[dict] = None) -> None:
        """Stamp + inject one frame message (``payload`` carries the frame
        encoding; priority rides both the admission field and the meta).
        ``meta_extra`` merges additional meta keys — the video bench
        stamps ``stream`` so the tracker can scope its cache."""
        from opencv_facerecognizer_tpu.runtime.recognizer import FRAME_TOPIC

        self.send_t[seq] = time.monotonic()
        meta = {"seq": seq, "pri": priority}
        if meta_extra:
            meta.update(meta_extra)
        connector.inject(FRAME_TOPIC, {**payload, "priority": priority,
                                       "meta": meta})

    def completed(self, seqs) -> int:
        with self._lock:
            return sum(1 for s in seqs if s in self.done_t)

    def latencies(self, seqs):
        with self._lock:
            return [self.done_t[s] - self.send_t[s]
                    for s in seqs if s in self.done_t]

    def percentile_ms(self, seqs, q: float) -> float:
        """Latency percentile in ms over the completed subset of ``seqs``
        — NaN when nothing completed (callers must treat that as its own
        verdict, never compare it)."""
        lat = self.latencies(seqs)
        if not lat:
            return float("nan")
        return float(np.percentile(lat, q)) * 1e3

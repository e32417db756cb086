"""Ingest pipeline: the subsystem between connector/admission and the
dispatch ladder (ROADMAP item #1 — the serving loop is transfer-bound).

The record this was built on (pre-PR-1 backend, source deleted in PR 21,
not reproducible; not re-measured on the local chip): b32 H2D crossed at
6.2 ms p50 (1.3 GB/s, f32) against ~0.64 ms of device compute — and the
first uint8 shortcut, which cut bytes 4x, paid a catastrophic
118 ms p99 because every batch staged through a freshly-allocated host
array (page faults + allocator churn on the hot path) and synchronized
under load. This module is the real fix, three pieces:

- **Staging ring** (``StagingRing``): a recycled, double-buffered ring of
  pre-allocated host staging buffers, one small pool per dispatch-bucket
  rung, grown out of the PR-2 zero-alloc pool seam in
  ``runtime/batcher.py``. Batch n+1 assembles into a warm recycled buffer
  while batch n's dispatch is in flight, so steady-state ingest allocates
  NOTHING (``ingest_staging_allocs`` stays at the construction-time
  preallocation — asserted by test). Exhaustion under flood is explicit
  backpressure: the batch waits queued and admission rejects new intake
  (reason ``staging``) — never a fresh allocation.
- **uint8 end-to-end upload** (``IngestPipeline.upload``): frames cross
  host->device as uint8 (4x fewer bytes) through one explicit
  ``jax.device_put`` per dispatch attempt, with the cast/normalize fused
  into the detect prologue on device (``RecognitionPipeline``'s in-graph
  ``astype``) and the frames argument donated through the bucketed ladder
  on backends that support donation (``donate_frames``).
- **Compressed-frame intake** (``DecodeWorkerPool``): JPEG camera payloads
  (the live-video workload of PAPERS.md 1811.07339 — what real camera
  fleets actually send) are accepted at the connector and decoded OFF the
  hot thread by a small worker pool directly into the staging path. Decode
  failures dead-letter through the journal/ledger machinery with reason
  ``decode_error``; depth and latency ride the shared Metrics surface.

Lock order: the batcher acquires ring buffers while holding its own queue
lock, so the sanctioned nesting is ``FrameBatcher._lock -> StagingRing
._lock``; the ring NEVER calls back into the batcher (or Metrics) under
its own lock — release notifications and counter mirrors fire after the
lock is dropped.
"""

from __future__ import annotations

import base64
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from opencv_facerecognizer_tpu.utils import metric_names as mn

#: the three ingest modes ``ocvf-recognize --ingest-mode`` exposes.
INGEST_MODES = ("f32", "uint8", "jpeg")

#: wire key of a compressed-frame payload (base64 JPEG bytes) — the
#: compressed sibling of ``connector.encode_frame``'s ``__frame__``.
JPEG_KEY = "__jpeg__"


def encode_jpeg_message(jpeg_bytes: bytes) -> Dict[str, Any]:
    """JPEG bytes -> the wire payload dict a camera producer publishes on
    the frame topic (merge ``meta``/``priority`` in alongside)."""
    return {JPEG_KEY: base64.b64encode(bytes(jpeg_bytes)).decode("ascii")}


def decode_jpeg_payload(message: Dict[str, Any]) -> bytes:
    return base64.b64decode(message[JPEG_KEY])


#: resolved-once (encode, decode) pair — the decode pool calls
#: ``decode_jpeg`` per frame, so the import probing must not re-run on
#: the hot path.
_CODEC_CACHE: Optional[Tuple[Any, Any]] = None


def _jpeg_codec():
    """(encode_fn, decode_fn) over whatever codec this container ships —
    PIL first, cv2 second — or (None, None). Nothing is installed for
    this; environments without either get a loud construction-time error
    from the decode pool instead of a hot-path surprise. Resolution runs
    once per process (cached)."""
    global _CODEC_CACHE
    if _CODEC_CACHE is None:
        _CODEC_CACHE = _resolve_jpeg_codec()
    return _CODEC_CACHE


def _resolve_jpeg_codec():
    try:
        import io

        from PIL import Image

        def encode(frame: np.ndarray, quality: int = 85) -> bytes:
            buf = io.BytesIO()
            Image.fromarray(np.asarray(frame, np.uint8), mode="L").save(
                buf, format="JPEG", quality=int(quality))
            return buf.getvalue()

        def decode(data: bytes) -> np.ndarray:
            with Image.open(io.BytesIO(data)) as img:
                return np.asarray(img.convert("L"))

        return encode, decode
    except ImportError:
        pass
    try:
        import cv2

        def encode(frame: np.ndarray, quality: int = 85) -> bytes:
            ok, buf = cv2.imencode(".jpg", np.asarray(frame, np.uint8),
                                   [int(cv2.IMWRITE_JPEG_QUALITY),
                                    int(quality)])
            if not ok:
                raise ValueError("cv2.imencode failed")
            return buf.tobytes()

        def decode(data: bytes) -> np.ndarray:
            arr = cv2.imdecode(np.frombuffer(data, np.uint8),
                               cv2.IMREAD_GRAYSCALE)
            if arr is None:
                raise ValueError("cv2.imdecode failed")
            return arr

        return encode, decode
    except ImportError:
        return None, None


def jpeg_supported() -> bool:
    return _jpeg_codec()[0] is not None


def encode_jpeg(frame: np.ndarray, quality: int = 85) -> bytes:
    """Grayscale [H, W] uint8-ish frame -> baseline JPEG bytes."""
    encode, _ = _jpeg_codec()
    if encode is None:
        raise RuntimeError("no JPEG codec available (PIL or cv2 required)")
    return encode(np.clip(np.asarray(frame), 0, 255).astype(np.uint8),
                  quality)


def decode_jpeg(data: bytes) -> np.ndarray:
    """JPEG bytes -> grayscale [H, W] uint8 frame (raises on corrupt or
    truncated payloads — the decode pool's dead-letter trigger)."""
    _, decode = _jpeg_codec()
    if decode is None:
        raise RuntimeError("no JPEG codec available (PIL or cv2 required)")
    arr = np.asarray(decode(bytes(data)))
    if arr.ndim != 2 or arr.size == 0:
        raise ValueError(f"decoded JPEG has shape {arr.shape}, "
                         "expected a 2-D grayscale frame")
    return arr


@dataclass
class IngestConfig:
    """Knobs of the ingest subsystem (``ocvf-recognize --ingest-*``)."""

    #: ``f32`` (legacy transfer dtype), ``uint8`` (4x cheaper H2D, cast
    #: fused on device), ``jpeg`` (uint8 + compressed intake decoded off
    #: the hot thread).
    mode: str = "f32"
    #: staging buffers preallocated per dispatch-bucket rung. None (the
    #: default) = auto: the service sizes it to ``inflight_depth + 2``
    #: (every overlapped in-flight batch holds a buffer, plus the batch
    #: being assembled and one completing), so the bounded ring never
    #: caps pipeline overlap below the in-flight window. An explicit
    #: value is honored as given (floor 1).
    ring_depth: Optional[int] = None
    #: decode worker threads (jpeg mode only).
    decode_workers: int = 2
    #: bounded decode intake queue; beyond it admitted compressed frames
    #: drop with ledger reason ``frames_dropped_decode`` (journal reason
    #: ``decode_backlog``) instead of growing an unbounded backlog.
    decode_queue: int = 128
    #: route dispatches through one explicit ``jax.device_put`` per
    #: attempt (measured as the ``upload`` span + ``ingest_upload``
    #: window). False keeps the implicit jit-internal transfer.
    upload: bool = True

    def __post_init__(self):
        if self.mode not in INGEST_MODES:
            raise ValueError(f"unknown ingest mode {self.mode!r} "
                             f"(valid: {INGEST_MODES})")
        if self.ring_depth is not None:
            self.ring_depth = max(1, int(self.ring_depth))
        self.decode_workers = max(1, int(self.decode_workers))
        self.decode_queue = max(1, int(self.decode_queue))

    def resolve_ring_depth(self, inflight_depth: int) -> int:
        """The effective per-rung depth: the explicit knob, or the
        auto-sizing rule (``inflight_depth + 2`` — see ``ring_depth``)."""
        if self.ring_depth is not None:
            return self.ring_depth
        return max(1, int(inflight_depth)) + 2

    @property
    def transfer_dtype(self):
        """Host staging / H2D dtype the mode implies."""
        return np.float32 if self.mode == "f32" else np.uint8


class StagingRing:
    """Recycled ring of pre-allocated host staging buffers, one pool per
    dispatch-bucket rung (module docstring).

    ``acquire(count)`` hands back a free buffer of the smallest rung that
    fits ``count`` real frames (falling upward to a bigger rung before
    reporting exhaustion — a large buffer carries a small batch fine; the
    dispatch bucket is picked by count, not buffer length), or ``None``
    when every fitting rung is in flight: the caller must WAIT, never
    allocate. ``release`` returns a buffer to its rung's pool;
    ``forfeit`` tells the ring a buffer is gone for good (dead-letter /
    crash paths must not recycle a staging array whose async H2D read may
    still be pending) so a later exhausted acquire may heal with ONE
    replacement allocation — the only post-construction allocation path,
    and it only opens on outages.

    Thread-safe; never calls out (notify hooks, Metrics) under its lock.
    """

    def __init__(self, rung_sizes: Sequence[int],
                 frame_shape: Tuple[int, int], dtype, depth: int = 2,
                 metrics=None):
        rungs = sorted({int(r) for r in rung_sizes if int(r) > 0})
        if not rungs:
            raise ValueError("StagingRing needs at least one rung size")
        self.frame_shape = tuple(frame_shape)
        self.dtype = np.dtype(dtype)
        self.depth = max(1, int(depth))
        self.rungs = rungs
        self.metrics = metrics
        self._lock = threading.Lock()
        self._free: Dict[int, deque] = {
            r: deque(np.zeros((r, *self.frame_shape), self.dtype)
                     for _ in range(self.depth))
            for r in rungs
        }
        self._forfeited: Dict[int, int] = {r: 0 for r in rungs}
        self._notify: List[Callable[[], None]] = []
        # Lock-free mirror of the TOP rung's free+heal count for the
        # per-frame admission read (``free_slots``): written under the
        # ring lock by every mutation, read bare (an int load is atomic
        # in CPython; a transiently stale read only shifts WHICH frame a
        # flood sheds, which is fine for a bound).
        self._top_free = self.depth
        #: total buffers ever allocated (preallocation + outage heals) —
        #: the zero-steady-state-allocation assertion reads this.
        self.alloc_count = len(rungs) * self.depth
        self.preallocated = self.alloc_count
        if metrics is not None:
            metrics.incr(mn.INGEST_STAGING_ALLOCS, self.preallocated)
            metrics.set_gauge(mn.INGEST_STAGING_FREE, self.preallocated)

    def add_notify(self, fn: Callable[[], None]) -> None:
        """Register a release notification (the batcher wakes its consumer
        wait on it). Called OUTSIDE the ring lock."""
        self._notify.append(fn)

    def _fitting(self, count: int) -> List[int]:
        fits = [r for r in self.rungs if r >= count]
        return fits or [self.rungs[-1]]

    def _refresh_top_free_locked(self) -> None:
        """Caller holds the lock: refresh the lock-free admission mirror
        after any mutation of the top rung's free/heal state."""
        top = self.rungs[-1]
        self._top_free = len(self._free[top]) + self._forfeited[top]

    def acquire(self, count: int, quiet: bool = False) -> Optional[np.ndarray]:
        """A free staging buffer of the smallest fitting rung, or None
        (exhausted — wait and retry; the ring refuses to allocate).
        ``quiet=True`` marks a parked consumer's RE-check: a miss there
        is the same exhaustion episode still in progress, so the
        ``ingest_staging_exhausted`` counter stays per-episode (alertable
        as a rate) instead of ticking once per 10 ms poll."""
        buf = None
        healed = False
        with self._lock:
            fits = self._fitting(count)
            for rung in fits:
                if self._free[rung]:
                    buf = self._free[rung].popleft()
                    break
            if buf is None:
                # Outage heal: a forfeited buffer (dead-lettered batch)
                # will never come back — replace it, once, here, so a
                # chaos window cannot permanently shrink the ring.
                for rung in fits:
                    if self._forfeited[rung] > 0:
                        self._forfeited[rung] -= 1
                        buf = np.zeros((rung, *self.frame_shape), self.dtype)
                        self.alloc_count += 1
                        healed = True
                        break
            self._refresh_top_free_locked()
            free_now = sum(len(q) for q in self._free.values())
        if self.metrics is not None:
            if buf is None:
                if not quiet:
                    self.metrics.incr(mn.INGEST_STAGING_EXHAUSTED)
            elif healed:
                self.metrics.incr(mn.INGEST_STAGING_ALLOCS)
            else:
                self.metrics.incr(mn.INGEST_STAGING_REUSE)
            self.metrics.set_gauge(mn.INGEST_STAGING_FREE, free_now)
        return buf

    def release(self, buf) -> None:
        """Return a buffer once its batch's readback completed and every
        view was copied out. Foreign shapes/dtypes are dropped silently
        (mirrors the legacy pool's recycle contract)."""
        if (not isinstance(buf, np.ndarray) or buf.dtype != self.dtype
                or buf.ndim != 1 + len(self.frame_shape)
                or buf.shape[1:] != self.frame_shape
                or buf.shape[0] not in self._free):
            return
        rung = buf.shape[0]
        returned = False
        with self._lock:
            if len(self._free[rung]) < self.depth + self._forfeited[rung]:
                self._free[rung].append(buf)
                returned = True
            self._refresh_top_free_locked()
            free_now = sum(len(q) for q in self._free.values())
        if returned:
            for fn in self._notify:
                fn()
        if self.metrics is not None:
            self.metrics.set_gauge(mn.INGEST_STAGING_FREE, free_now)

    def forfeit(self, buf) -> None:
        """Mark one in-flight buffer as never coming back (dead-letter /
        crash: the backend's async read of it may still be pending, so it
        must stay out of circulation). Opens one replacement-allocation
        credit for its rung."""
        if (not isinstance(buf, np.ndarray)
                or buf.ndim != 1 + len(self.frame_shape)
                or buf.shape[0] not in self._free):
            return
        with self._lock:
            self._forfeited[buf.shape[0]] += 1
            self._refresh_top_free_locked()
        if self.metrics is not None:
            self.metrics.incr(mn.INGEST_STAGING_FORFEITS)

    def free_slots(self) -> int:
        """Free buffers in the LARGEST rung (plus its heal credits) — the
        admission backpressure signal (reason ``staging`` at 0). The top
        rung is the binding constraint: ``acquire`` only falls UPWARD, so
        small-rung buffers can never stage a full batch — counting them
        would leave the front door open while every full-batch flush is
        parked (and top-rung exhaustion with smaller rungs still free
        already means >= depth full batches are in flight: overload). A
        heal credit counts because an exhausted ring that can still
        self-replace is not wedged.

        LOCK-FREE on purpose: this runs on the connector thread for
        every offered frame (the documented lock-free admit path), so it
        reads the mirror the mutators maintain under the ring lock — a
        transiently stale value only shifts which frame a flood sheds."""
        return self._top_free

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "rungs": list(self.rungs),
                "depth": self.depth,
                "free": {r: len(q) for r, q in self._free.items()},
                "forfeited": dict(self._forfeited),
                "alloc_count": self.alloc_count,
                "preallocated": self.preallocated,
            }


class DecodeWorkerPool:
    """Small worker pool decoding compressed camera payloads OFF the
    serving hot thread, directly into the staging path.

    ``submit`` enqueues one admitted payload (returns False when the
    bounded queue is full — the caller settles the ledger drop); workers
    decode and hand the pixel frame to ``sink`` (the service's intake
    continuation: brownout check + batcher put). A payload that fails to
    decode goes to ``on_error`` instead — corrupt camera bytes must cost
    one frame, one counted ledger drop, one journal row, never a worker.

    The chaos boundary ``decode`` (``runtime.faults``) installs here:
    ``slow`` sleeps the injector's ``slow_decode_s`` before decoding (the
    congested-decoder shape the off-thread pool must absorb without
    stalling dispatch), ``corrupt`` replaces the payload with bytes no
    decoder accepts.

    A worker counts as busy until its sink/on_error call RETURNS, so
    ``idle()`` has no in-transit gap — ``RecognizerService.drain`` relies
    on that to cover frames mid-decode.
    """

    def __init__(self, workers: int = 2, max_queue: int = 128,
                 decode_fn: Optional[Callable[[bytes], np.ndarray]] = None,
                 metrics=None, tracer=None, trace_topic: Optional[str] = None,
                 fault_injector=None):
        if decode_fn is None and not jpeg_supported():
            raise RuntimeError(
                "compressed-frame intake needs a JPEG codec (PIL or cv2); "
                "neither is importable here — pass decode_fn explicitly "
                "or use --ingest-mode uint8")
        self.workers = max(1, int(workers))
        self.max_queue = max(1, int(max_queue))
        self._decode = decode_fn or decode_jpeg
        self.metrics = metrics
        self._tracer = tracer
        self._trace_topic = trace_topic
        self._faults = fault_injector
        self._cv = threading.Condition()
        self._q: deque = deque()
        self._busy = 0
        self._running = False
        self._threads: List[threading.Thread] = []
        self._sink: Optional[Callable] = None
        self._on_error: Optional[Callable] = None

    def start(self, sink: Callable, on_error: Callable) -> None:
        """``sink(frame, message, priority, trace_id)`` on success;
        ``on_error(message, priority, trace_id, reason)`` on failure."""
        if self._running:
            return
        self._sink = sink
        self._on_error = on_error
        self._running = True
        for i in range(self.workers):
            thread = threading.Thread(target=self._run, daemon=True,
                                      name=f"ocvf-decode-{i}")
            thread.start()
            self._threads.append(thread)

    def stop(self) -> None:
        with self._cv:
            self._running = False
            self._cv.notify_all()
        for thread in self._threads:
            thread.join(timeout=2.0)
        self._threads.clear()

    def submit(self, message: Dict[str, Any], priority: int,
               trace_id: int) -> bool:
        """Enqueue one admitted compressed frame; False = queue full (the
        caller owns the ledger settlement of the drop)."""
        with self._cv:
            if not self._running or len(self._q) >= self.max_queue:
                accepted = False
            else:
                self._q.append((message, int(priority), int(trace_id),
                                time.monotonic()))
                accepted = True
                depth = len(self._q)
                self._cv.notify()
        if accepted and self.metrics is not None:
            self.metrics.set_gauge(mn.DECODE_QUEUE_DEPTH, depth)
        return accepted

    def idle(self) -> bool:
        """Queue empty AND no worker mid-decode (including mid-sink)."""
        with self._cv:
            return not self._q and self._busy == 0

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._q)

    def _run(self) -> None:
        while True:
            with self._cv:
                while self._running and not self._q:
                    self._cv.wait()
                if not self._q:
                    if not self._running:
                        return
                    continue
                message, priority, tid, t_enq = self._q.popleft()
                self._busy += 1
                depth = len(self._q)
            try:
                if self.metrics is not None:
                    self.metrics.set_gauge(mn.DECODE_QUEUE_DEPTH, depth)
                self._decode_one(message, priority, tid)
            except Exception:  # noqa: BLE001 — backstop: _decode_one contains every expected failure; anything escaping is a bug that must cost one frame's accounting, never the worker
                logging.getLogger(__name__).exception(
                    "decode worker iteration failed")
                if self.metrics is not None:
                    self.metrics.incr(mn.DECODE_ERRORS)
            finally:
                with self._cv:
                    self._busy -= 1

    def _decode_one(self, message, priority: int, tid: int) -> None:
        t0 = time.perf_counter()
        try:
            payload = decode_jpeg_payload(message)
            if self._faults is not None:
                payload = self._faults.on_decode(payload)
            frame = self._decode(payload)
        except Exception:  # noqa: BLE001 — corrupt payloads are the failure mode this pool exists to contain
            if self.metrics is not None:
                self.metrics.incr(mn.DECODE_ERRORS)
                self.metrics.observe(mn.DECODE_LATENCY,
                                     time.perf_counter() - t0)
            if self._tracer is not None and tid:
                self._tracer.emit(tid, "decode", topic=self._trace_topic,
                                  dur=time.perf_counter() - t0, ok=False)
            self._settle_error(message, priority, tid)
            return
        dur = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.incr(mn.DECODE_FRAMES)
            self.metrics.observe(mn.DECODE_LATENCY, dur)
        if self._tracer is not None and tid:
            self._tracer.emit(tid, "decode", topic=self._trace_topic,
                              dur=dur, ok=True)
        try:
            self._sink(frame, message, priority, tid)
        except Exception:  # noqa: BLE001 — a raising intake continuation (journal IOError under stress, a brownout-path bug) must cost this FRAME, never a worker thread: a dead pool with submit() still accepting would silently stop all camera traffic
            logging.getLogger(__name__).exception(
                "decode sink failed; settling the frame as a decode drop")
            if self.metrics is not None:
                self.metrics.incr(mn.DECODE_ERRORS)
            self._settle_error(message, priority, tid)

    def _settle_error(self, message, priority: int, tid: int) -> None:
        """Route one failed frame to ``on_error`` (the service's ledger
        settlement). Its own failure is logged, never raised — the ledger
        leak is the service's bug to find via the error log + counter,
        and a worker thread must survive it either way."""
        try:
            self._on_error(message, priority, tid, "decode_error")
        except Exception:  # noqa: BLE001 — see _settle_error docstring: the worker must outlive a broken settlement callback
            logging.getLogger(__name__).exception(
                "decode on_error callback failed; frame may be "
                "unsettled in the admission ledger")
            if self.metrics is not None:
                self.metrics.incr(mn.DECODE_ERRORS)


class IngestPipeline:
    """The assembled ingest subsystem one ``RecognizerService`` owns:
    staging ring + (jpeg mode) decode pool + the explicit device uploader.
    Construction is pure wiring; ``start``/``stop`` manage the decode
    workers; ``upload`` runs on the dispatch path (one call per dispatch
    attempt, so a retry after a donated-buffer dispatch re-uploads from
    the host staging view)."""

    def __init__(self, config: IngestConfig, rung_sizes: Sequence[int],
                 frame_shape: Tuple[int, int], metrics=None, tracer=None,
                 trace_topic: Optional[str] = None, fault_injector=None,
                 decode_fn=None, inflight_depth: int = 4):
        self.config = config
        self.metrics = metrics
        self.transfer_dtype = np.dtype(config.transfer_dtype)
        self.staging = StagingRing(
            rung_sizes, frame_shape, self.transfer_dtype,
            depth=config.resolve_ring_depth(inflight_depth),
            metrics=metrics)
        self.decoder = None
        if config.mode == "jpeg":
            self.decoder = DecodeWorkerPool(
                workers=config.decode_workers,
                max_queue=config.decode_queue,
                decode_fn=decode_fn, metrics=metrics, tracer=tracer,
                trace_topic=trace_topic, fault_injector=fault_injector)
        # Upload placement override (None = the default device). The
        # CPU-fallback path (resilience.rebuild_pipeline_on_cpu) pins
        # this to the CPU device it rebuilt the pipeline on: a bare
        # device_put would otherwise keep committing frames to the DEAD
        # accelerator — every dispatch attempt failing against the very
        # fallback built to survive it (the same retargeting the
        # enrolment graph's _embed_device does).
        self.upload_device = None

    def start(self, sink: Callable, on_error: Callable) -> None:
        if self.decoder is not None:
            self.decoder.start(sink, on_error)

    def stop(self) -> None:
        if self.decoder is not None:
            self.decoder.stop()

    def idle(self) -> bool:
        return self.decoder is None or self.decoder.idle()

    def submit_decode(self, message: Dict[str, Any], priority: int,
                      trace_id: int) -> bool:
        if self.decoder is None:
            return False
        return self.decoder.submit(message, priority, trace_id)

    def upload(self, frames) -> Tuple[Any, int, float]:
        """Ship one staged batch view host->device explicitly: returns
        ``(device_frames, nbytes, enqueue_seconds)``. The put is async —
        the duration is the host enqueue cost, not transfer completion
        (that lands in ``ready_wait``, where it always did). With
        ``config.upload`` off this is a passthrough."""
        if not self.config.upload:
            return frames, int(getattr(frames, "nbytes", 0)), 0.0
        import jax

        nbytes = int(frames.nbytes)
        t0 = time.perf_counter()
        device_frames = jax.device_put(frames, self.upload_device)
        dur = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.incr(mn.INGEST_UPLOAD_BYTES, nbytes)
            self.metrics.observe(mn.INGEST_UPLOAD, dur)
        return device_frames, nbytes, dur

    def stats(self) -> Dict[str, Any]:
        out = {"mode": self.config.mode,
               "transfer_dtype": str(self.transfer_dtype),
               "staging": self.staging.stats()}
        if self.decoder is not None:
            out["decode_queue_depth"] = self.decoder.queue_depth()
        return out

"""Frame batcher: the host-side stage that turns an async frame stream into
fixed-size device batches (BASELINE.json:5: "buffers incoming sensor_msgs/
Image into fixed-size device batches"; SURVEY.md §5.2 — this queue is the
one real concurrency point, so it is small, locked, and directly tested).

Semantics:
- ``put`` validates shape/dtype and drops malformed frames (SURVEY.md §5.3
  graceful skip) — a camera glitch must not poison a whole batch.
- ``get_batch`` implements **continuous batching**: it blocks until
  ``batch_size`` frames are buffered OR the oldest undelivered frame's age
  reaches the current flush deadline, then returns a zero-padded [B, H, W]
  batch plus the metadata list and real count. The deadline is either the
  fixed ``flush_timeout`` (legacy mode) or, with ``target_latency_s`` set,
  **adaptive**: the remaining per-frame latency budget after subtracting an
  EWMA of the downstream service time the consumer reports via
  ``report_service_time`` — under trickle load a batch waits only as long
  as the end-to-end target can afford, never a fixed window. Fixed B keeps
  XLA from recompiling (static shapes); padding lanes are dead weight the
  TPU shrugs off (partial batches can additionally be *sliced* down to a
  bucket ladder by the consumer — see RecognizerService).
- Bounded queue with **priority-aware shedding**: beyond ``max_pending`` a
  victim is evicted in preference order — already-stale frames first (queue
  age past ``stale_after_s``), then the lowest-priority class (bulk before
  interactive), oldest within a class. An incoming frame less important
  than everything queued is itself the victim (rejected). Without
  priorities or a stale bound this degrades exactly to the old
  drop-oldest-first rule: a live recognizer wants fresh frames, not a
  growing latency debt.
- **Deadline-aware dispatch**: with ``stale_after_s`` set, ``get_batch``
  discards frames whose queue age already exceeds it BEFORE forming a
  batch — a frame that has blown its latency budget must not waste a
  dispatch slot that a fresh frame could use (``batcher_dropped_stale``).
- Every drop is observable twice: per-reason counters on the shared
  Metrics surface, and (when ``drop_log`` is wired) the dropped frames'
  metadata handed to the service's dead-letter journal.
- **Buffer pool**: the [B, H, W] staging array a batch rides in can be
  handed back via ``recycle`` once the consumer is done with it (after the
  batch's readback completed — the host-side analog of a donated input
  buffer). Steady-state batching then does zero per-batch allocations;
  consumers that never recycle just get a fresh array each time, exactly
  the old behavior. A recycled buffer's padding lanes are re-zeroed before
  reuse.

Coalescing stats ride the shared ``Metrics`` surface so tests can reconcile
them exactly: ``batcher_frames_offered`` (every ``put`` attempt) equals
frames batched + malformed drops + overflow drops + stale drops + closed
drops + pending.
``batcher_batches_size`` / ``batcher_batches_deadline`` split batches by
what triggered the flush; ``batcher_flush_deadline_ms`` is a gauge of the
current (possibly adaptive) deadline.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np
from opencv_facerecognizer_tpu.utils import metric_names as mn

#: in-loop marker that a staging-ring acquire already missed this pop
#: attempt — later re-checks of the same episode go ``quiet`` so the
#: exhaustion counter stays per-episode (see StagingRing.acquire).
_EXHAUSTED = object()


class Batch(NamedTuple):
    """One device-ready batch plus the provenance the latency decomposition
    needs: ``enqueue_ts`` are the ``time.monotonic()`` stamps from ``put``
    for the ``count`` real frames (queue-wait = pop time - enqueue time);
    ``trace_ids`` are their frame-trace ids (0 = untraced/sampled out) so
    the consumer can record which batch carried each frame; ``priorities``
    are their admission priority classes (the SLO layer's per-class e2e
    histograms split on them at publish time)."""

    frames: np.ndarray  # [B, H, W] in the batcher's dtype, zero-padded
    metas: List[Any]
    count: int
    enqueue_ts: List[float]
    trace_ids: List[int]
    priorities: List[int]


class FrameBatcher:
    def __init__(
        self,
        batch_size: int,
        frame_shape: Tuple[int, int],
        flush_timeout: float = 0.05,
        max_pending: int = 256,
        dtype=np.float32,
        # Shared Metrics mirror of the drop/coalescing counters (None =
        # stats-only): the chaos/connector/batching tests assert through
        # ONE metrics surface instead of poking per-component attributes.
        metrics=None,
        # Chaos hook (runtime.faults): may poison a frame before the
        # shape/dtype validation that must then drop it.
        fault_injector=None,
        # Continuous-batching target: when set, the flush deadline adapts
        # to ``target_latency_s - EWMA(downstream service time)`` instead
        # of the fixed flush_timeout (which then acts as the CAP). The
        # consumer feeds the EWMA via report_service_time after each
        # batch completes end-to-end.
        target_latency_s: Optional[float] = None,
        # Floor of the adaptive deadline: even with no latency budget left
        # a flush waits this long so back-to-back frames still coalesce.
        min_deadline_s: float = 0.002,
        # EWMA smoothing for the reported service time.
        service_time_alpha: float = 0.2,
        # Staging buffers kept for reuse (recycle); ~inflight_depth + the
        # batch being formed is plenty.
        buffer_pool_size: int = 8,
        # Ingest staging ring (runtime.ingest.StagingRing): when set, it
        # REPLACES the ad-hoc buffer pool — batches assemble into
        # pre-allocated per-rung buffers, recycle/forfeit route to the
        # ring, and an exhausted ring makes the consumer WAIT (explicit
        # backpressure) instead of allocating. Must match this batcher's
        # frame_shape/dtype, and its largest rung must be batch_size.
        staging_ring=None,
        # Freshness bound (seconds): a queued frame older than this is shed
        # (reason ``stale``) — preferentially at overflow-eviction time, and
        # always before it can consume a dispatch slot. None disables.
        stale_after_s: Optional[float] = None,
        # Drop observer: called OUTSIDE the lock as ``drop_log(reason,
        # entries)`` with entries = [{"meta", "enqueue_ts", "priority",
        # "trace_id", "stage"}] for overflow/stale sheds (the service
        # wires its dead-letter journal here). None = counters only.
        drop_log=None,
        # Frame-lifecycle tracer (utils.tracing.Tracer): every drop the
        # batcher counts also emits the frame's terminal ``settle`` span
        # (outcome = the ledger counter it landed in), outside the queue
        # lock. ``trace_topic`` is the ring topic frame spans ride on
        # (the service passes its FRAME_TOPIC). None = no spans.
        tracer=None,
        trace_topic: Optional[str] = None,
    ):
        self.batch_size = int(batch_size)
        self.frame_shape = tuple(frame_shape)
        self.flush_timeout = float(flush_timeout)
        self.max_pending = int(max_pending)
        # uint8 halves memory 4x AND rides host->device 4x cheaper (the
        # pipeline casts to f32 in-graph); camera frames are uint8 anyway.
        self.dtype = np.dtype(dtype)
        self.metrics = metrics
        self._faults = fault_injector
        self.target_latency_s = (None if target_latency_s is None
                                 else float(target_latency_s))
        self.min_deadline_s = float(min_deadline_s)
        self._alpha = float(service_time_alpha)
        self._service_time_ewma: Optional[float] = None
        self._pool_cap = int(buffer_pool_size)
        self._buffer_pool: List[np.ndarray] = []
        self._ring = staging_ring
        if self._ring is not None:
            if (tuple(self._ring.frame_shape) != self.frame_shape
                    or np.dtype(self._ring.dtype) != self.dtype):
                raise ValueError(
                    "staging_ring shape/dtype "
                    f"({self._ring.frame_shape}, {self._ring.dtype}) does "
                    f"not match batcher ({self.frame_shape}, {self.dtype})")
            if max(self._ring.rungs) < self.batch_size:
                raise ValueError(
                    f"staging_ring's largest rung {max(self._ring.rungs)} "
                    f"cannot stage a full batch of {self.batch_size}")
            # Wake a consumer parked on ring exhaustion when a buffer
            # returns (called by the ring OUTSIDE its own lock).
            self._ring.add_notify(self._wake_consumer)
        self.stale_after_s = (None if stale_after_s is None
                              else float(stale_after_s))
        self._drop_log = drop_log
        self._tracer = tracer
        self._trace_topic = trace_topic
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        # Seconds ``put`` and ``get_batch`` waited to acquire the lock and
        # the acquisitions (one clock pair round each acquire), and the
        # seconds ``get_batch`` spent in the condition's waits, summed
        # under the lock itself; ``get_batch`` hands them to ``metrics``
        # once a batch (``batcher_lock_wait_s``, ``batcher_lock_acquires``,
        # ``batcher_pop_wait_s``), so the producers pay no counter call a
        # frame for them.
        self._lock_wait_s = 0.0
        self._lock_acquires = 0
        self._pop_wait_s = 0.0
        self._frames: deque = deque()
        self._dropped_malformed = 0
        self._dropped_overflow = 0
        self._dropped_stale = 0
        self._delivered = 0
        self._batches_size = 0
        self._batches_deadline = 0
        self._closed = False

    # ---- producer side ----

    def put(self, frame: np.ndarray, meta: Any = None, priority: int = 0,
            trace_id: int = 0) -> bool:
        """Enqueue one frame (smaller ``priority`` = more important);
        returns False when dropped (malformed/closed/rejected-at-overflow).
        ``trace_id`` is the frame's trace (0 = untraced); every drop path
        emits its terminal span so traced frames never vanish silently."""
        if self.metrics is not None:
            self.metrics.incr(mn.BATCHER_FRAMES_OFFERED)
        if self._faults is not None:
            frame = self._faults.on_put(frame)
        frame = np.asarray(frame)
        if frame.shape != self.frame_shape or not np.issubdtype(frame.dtype, np.number):
            with self._lock:
                self._dropped_malformed += 1
            if self.metrics is not None:
                self.metrics.incr(mn.BATCHER_DROPPED_MALFORMED)
            self._emit_settle(trace_id, mn.BATCHER_DROPPED_MALFORMED,
                              "batcher.malformed")
            return False
        dropped = None  # (reason, entry) settled outside the lock
        accepted = True
        closed = False
        t_lock = time.monotonic()
        with self._not_empty:
            self._lock_wait_s += time.monotonic() - t_lock
            self._lock_acquires += 1
            if self._closed:
                # Counted under the lock (the one sanctioned
                # FrameBatcher._lock -> Metrics._lock nesting, cross-checked
                # by the DebugLock backstop); the span emits outside below.
                closed = True
                if self.metrics is not None:
                    self.metrics.incr(mn.BATCHER_DROPPED_CLOSED)
            elif len(self._frames) >= self.max_pending:
                dropped = self._evict_for(int(priority))
                accepted = dropped is not None
            if not closed and accepted:
                if np.issubdtype(self.dtype, np.integer) and not np.issubdtype(
                        frame.dtype, np.integer):
                    # A bare astype would WRAP out-of-range floats (-3.0 ->
                    # 253) — clip to the integer range instead (producers may
                    # send slight out-of-[0,255] values from preprocessing
                    # headroom).
                    info = np.iinfo(self.dtype)
                    frame = np.clip(frame, info.min, info.max)
                self._frames.append((frame.astype(self.dtype), meta,
                                     time.monotonic(), int(priority),
                                     int(trace_id)))
                self._not_empty.notify()
        if closed:
            self._emit_settle(trace_id, mn.BATCHER_DROPPED_CLOSED,
                              "batcher.closed")
            return False
        if not accepted:
            # The incoming frame was the least important thing in sight:
            # IT is the overflow victim, not a queued frame.
            with self._lock:
                self._dropped_overflow += 1
            if self.metrics is not None:
                self.metrics.incr(mn.BATCHER_DROPPED_OVERFLOW)
            self._emit_settle(trace_id, mn.BATCHER_DROPPED_OVERFLOW,
                              "batcher.overflow")
            self._log_drop("overflow", [(meta, None, int(priority),
                                         int(trace_id))])
            return False
        if dropped is not None:
            reason, entry = dropped
            if self.metrics is not None:
                self.metrics.incr(mn.BATCHER_DROPPED_PREFIX + reason)
            self._emit_settle(entry[3], mn.BATCHER_DROPPED_PREFIX + reason,
                              f"batcher.{reason}")
            self._log_drop(reason, [entry])
        return True

    def _evict_for(self, incoming_priority: int):
        """Caller holds the lock; the queue is full. Pick and remove the
        overflow victim: the oldest already-stale frame if any, else the
        oldest frame of the least-important queued class — but only when
        that class is at least as unimportant as the incoming frame.
        Returns ``(reason, (meta, enqueue_ts, priority, trace_id))`` for
        the evicted frame, or None when the INCOMING frame should be
        rejected instead (everything queued outranks it)."""
        if self.stale_after_s is not None and self._frames:
            # Only the head can be stale: enqueue stamps are nondecreasing,
            # so staleness is a deque prefix (same fact _shed_stale uses) —
            # no O(max_pending) scan on the per-put overflow path.
            _f, meta, ts, pri, tid = self._frames[0]
            if time.monotonic() - ts > self.stale_after_s:
                self._frames.popleft()
                self._dropped_stale += 1
                return "stale", (meta, ts, pri, tid)
        victim_idx, victim_pri = None, -1
        for idx, (_f, _meta, _ts, pri, _tid) in enumerate(self._frames):
            if pri > victim_pri:  # strictly-greater keeps the OLDEST of a class
                victim_idx, victim_pri = idx, pri
        if victim_pri < incoming_priority:
            return None  # incoming is the least important: reject it
        _f, meta, ts, pri, tid = self._frames[victim_idx]
        del self._frames[victim_idx]
        self._dropped_overflow += 1
        return "overflow", (meta, ts, pri, tid)

    def _emit_settle(self, trace_id: int, outcome: str, where: str) -> None:
        """Terminal span for a frame the batcher dropped (no-op untraced).
        Always called OUTSIDE the queue lock — span emission is lock-free
        but must never nest inside serving-path locks anyway."""
        if self._tracer is not None and trace_id:
            self._tracer.emit(trace_id, "settle", topic=self._trace_topic,
                              outcome=outcome, where=where)

    def _log_drop(self, reason: str, items) -> None:
        """Hand dropped frames' metadata to the drop observer (journal).
        Called OUTSIDE the queue lock; a raising observer is its own bug
        and must not poison the producer thread. Entries carry the frame's
        ``trace_id`` and the ``stage`` it died at, so a journal replay can
        reconstruct where each dropped frame died."""
        if self._drop_log is None:
            return
        entries = [{"meta": meta, "enqueue_ts": ts, "priority": pri,
                    "trace_id": tid or None, "stage": f"batcher.{reason}"}
                   for meta, ts, pri, tid in items]
        try:
            self._drop_log(reason, entries)
        except Exception:  # noqa: BLE001 — observer bugs stay theirs, but a
            # lost journal write must leave a trace: the soak's "journal
            # covers every shed frame" check needs to know entries went
            # missing (ocvf-lint swallowed-exception).
            if self.metrics is not None:
                self.metrics.incr(mn.JOURNAL_ERRORS)

    def close(self) -> None:
        with self._not_empty:
            self._closed = True
            self._not_empty.notify_all()

    # ---- adaptive deadline (continuous batching) ----

    def report_service_time(self, seconds: float) -> None:
        """Feed one batch's downstream time (pop -> published) into the
        EWMA the adaptive flush deadline subtracts from the latency target.
        Cheap and lock-free on purpose: a float store is atomic in CPython,
        and the deadline only needs a recent estimate, not a serialized
        one."""
        if seconds < 0:
            return
        prev = self._service_time_ewma
        self._service_time_ewma = (seconds if prev is None
                                   else prev + self._alpha * (seconds - prev))

    def current_flush_deadline(self) -> float:
        """Seconds the oldest frame may age before a partial batch flushes.
        Fixed ``flush_timeout`` without a latency target; with one, the
        remaining budget after the estimated downstream service time,
        clamped to [min_deadline_s, flush_timeout]."""
        if self.target_latency_s is None:
            return self.flush_timeout
        est = self._service_time_ewma or 0.0
        deadline = min(self.flush_timeout,
                       max(self.min_deadline_s, self.target_latency_s - est))
        if self.metrics is not None:
            self.metrics.set_gauge(mn.BATCHER_FLUSH_DEADLINE_MS, deadline * 1e3)
        return deadline

    # ---- buffer pool (host-side donated staging) ----

    def recycle(self, buf: np.ndarray) -> None:
        """Return a batch's staging array for reuse once the consumer is
        completely done with it (readback finished, no views kept — crops
        must be copied out first). Wrong shape/dtype or a full pool just
        drops it; never an error. With a staging ring installed the buffer
        goes back to its rung's pre-allocated pool instead."""
        if self._ring is not None:
            self._ring.release(buf)
            return
        if (not isinstance(buf, np.ndarray)
                or buf.shape != (self.batch_size, *self.frame_shape)
                or buf.dtype != self.dtype):
            return
        with self._lock:
            if len(self._buffer_pool) < self._pool_cap:
                self._buffer_pool.append(buf)

    def forfeit(self, buf) -> None:
        """Tell the staging ring one in-flight buffer will never come back
        (dead-letter/crash paths: the backend's async H2D read of it may
        still be pending, so it must not recirculate). No-op without a
        ring — the legacy pool refills from completed batches anyway."""
        if self._ring is not None:
            self._ring.forfeit(buf)

    def _wake_consumer(self) -> None:
        """Ring release notification: a consumer parked on ring
        exhaustion inside ``get_batch`` re-checks for a free buffer."""
        with self._not_empty:
            self._not_empty.notify_all()

    # ---- consumer side ----

    def get_batch(self, block: bool = True) -> Optional[Batch]:
        """Next ``Batch`` or None when closed and drained (or when
        non-blocking and nothing is flushable). With ``stale_after_s``
        set, frames that outlived their freshness bound while queued are
        shed here — counted, journaled, and never dispatched."""
        stale: List[tuple] = []
        lock_wait_s, lock_acquires, pop_wait_s = 0.0, 0, 0.0
        try:
            t_lock = time.monotonic()
            with self._not_empty:
                self._lock_wait_s += time.monotonic() - t_lock
                self._lock_acquires += 1
                popped = self._pop_batch_locked(block, stale)
                if popped is not None:
                    lock_wait_s, self._lock_wait_s = self._lock_wait_s, 0.0
                    lock_acquires, self._lock_acquires = (
                        self._lock_acquires, 0)
                    pop_wait_s, self._pop_wait_s = self._pop_wait_s, 0.0
        finally:
            if stale:
                if self.metrics is not None:
                    self.metrics.incr(mn.BATCHER_DROPPED_STALE, len(stale))
                for _meta, _ts, _pri, tid in stale:
                    self._emit_settle(tid, mn.BATCHER_DROPPED_STALE,
                                      "batcher.stale")
                self._log_drop("stale", stale)
        if popped is None:
            return None
        items, count, full, buf = popped
        if self.metrics is not None:
            self.metrics.incr_many(
                (mn.BATCHER_LOCK_WAIT_S, lock_wait_s),
                (mn.BATCHER_LOCK_ACQUIRES, lock_acquires),
                (mn.BATCHER_POP_WAIT_S, pop_wait_s))
            self.metrics.incr(mn.BATCHER_BATCHES_SIZE if full
                              else mn.BATCHER_BATCHES_DEADLINE)
            self.metrics.incr(mn.BATCHER_FRAMES_BATCHED, count)
            if buf is not None:
                self.metrics.incr(mn.BATCHER_BUFFER_REUSE)
        if buf is None:
            frames = np.zeros((self.batch_size, *self.frame_shape), dtype=self.dtype)
        else:
            # A ring buffer may be RUNG-sized (the smallest dispatch
            # bucket >= count) rather than batch_size — the consumer's
            # bucket slicing handles either length.
            frames = buf
            frames[count:] = 0  # re-zero a reused buffer's padding lanes
        metas: List[Any] = [None] * self.batch_size
        enqueue_ts: List[float] = []
        trace_ids: List[int] = []
        priorities: List[int] = []
        for i, (frame, meta, ts, pri, tid) in enumerate(items):
            frames[i] = frame
            metas[i] = meta
            enqueue_ts.append(ts)
            trace_ids.append(tid)
            priorities.append(pri)
        return Batch(frames, metas, count, enqueue_ts, trace_ids, priorities)

    def _shed_stale(self, collector: List[tuple]) -> None:
        """Caller holds the lock. Frames are FIFO by enqueue time, so
        staleness is always a prefix of the deque."""
        if self.stale_after_s is None:
            return
        now = time.monotonic()
        while self._frames and now - self._frames[0][2] > self.stale_after_s:
            _frame, meta, ts, pri, tid = self._frames.popleft()
            self._dropped_stale += 1
            collector.append((meta, ts, pri, tid))

    def _pop_batch_locked(self, block: bool, stale: List[tuple]):
        """Caller holds the lock: the wait/flush decision + the pop.
        Returns ``(items, count, full, pooled_buf)`` or None (closed /
        nothing flushable / idle tick). With a staging ring, the buffer
        is acquired BEFORE the pop — an exhausted ring keeps the frames
        queued (backpressure: admission sheds new intake upstream) and
        waits for a recycled buffer instead of ever allocating."""
        buf = None
        while True:
            self._shed_stale(stale)
            n = len(self._frames)
            if n >= self.batch_size:
                pass  # full batch: flush now
            elif n > 0:
                deadline = self.current_flush_deadline()
                age = time.monotonic() - self._frames[0][2]
                if age < deadline:
                    if not block:
                        return None
                    self._wait_not_empty(deadline - age)
                    continue
            else:
                if self._closed or not block:
                    return None
                self._wait_not_empty(self.flush_timeout)
                if not self._frames:
                    # Idle tick: give the caller a turn (the fallback
                    # serving loop drains its in-flight queue on None).
                    return None
                continue
            count = min(len(self._frames), self.batch_size)
            if self._ring is None:
                break
            # The one sanctioned FrameBatcher._lock -> StagingRing._lock
            # nesting; the ring never calls back under its own lock.
            # ``quiet`` after the first miss: one exhaustion EPISODE
            # counts once, not once per 10 ms re-check below.
            buf = self._ring.acquire(count, quiet=buf is _EXHAUSTED)
            if buf is not None:
                break
            buf = _EXHAUSTED
            if self._closed or not block:
                # Shutdown with an exhausted ring: surrender the tick
                # (same as legacy stop semantics — queued frames are the
                # drain/stop caller's problem, never an allocation here).
                return None
            # Exhausted: park until recycle()/release wakes us (the ring
            # notifies this cv) or the timeout re-checks; the queued
            # frames age meanwhile, which is exactly the backpressure
            # signal admission + stale shedding act on.
            self._wait_not_empty(min(self.flush_timeout, 0.01))
        count = min(len(self._frames), self.batch_size)
        full = count >= self.batch_size
        items = [self._frames.popleft() for _ in range(count)]
        # Counted under the lock, atomically with the pop: consumers
        # (RecognizerService.drain) compare this against their own
        # completion count, so a popped-but-not-yet-dispatched batch is
        # never invisible to both ``pending`` and the in-flight queue.
        self._delivered += 1
        if full:
            self._batches_size += 1
        else:
            self._batches_deadline += 1
        if self._ring is None:
            buf = self._buffer_pool.pop() if self._buffer_pool else None
        return items, count, full, buf

    def _wait_not_empty(self, timeout: float) -> None:
        """Caller holds the lock: one wait on the condition, its seconds
        added to ``_pop_wait_s``."""
        t0 = time.monotonic()
        self._not_empty.wait(timeout=timeout)
        self._pop_wait_s += time.monotonic() - t0

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._frames)

    @property
    def delivered_batches(self) -> int:
        """Batches handed out by ``get_batch`` (incremented under the lock,
        atomically with the pop)."""
        with self._lock:
            return self._delivered

    @property
    def stats(self):
        with self._lock:
            return {
                "pending": len(self._frames),
                "dropped_malformed": self._dropped_malformed,
                "dropped_overflow": self._dropped_overflow,
                "dropped_stale": self._dropped_stale,
                "batches_size": self._batches_size,
                "batches_deadline": self._batches_deadline,
            }

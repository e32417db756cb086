"""Resilience policy + supervised serving (SURVEY.md §5.3 extended to
steady state).

A backend can fail two ways — fast (``UNAVAILABLE`` raised at dispatch) or
silently (a call that never returns); this module is the serving loop's
posture against both:

- ``ResiliencePolicy`` — the retry/deadline/degraded knobs threaded through
  ``RecognizerService``: a dispatch failure retries with exponential
  backoff, a readback that outlives its deadline is dead-lettered (the loop
  keeps serving), and N consecutive dispatch failures flip the service into
  **degraded mode** (status published on ``STATUS_TOPIC``, optional bounded
  backend probe, optional CPU-fallback hook) instead of wedging.
- ``BrownoutPolicy`` — the overload-degradation knobs (queue-wait EWMA
  threshold, hysteresis, per-level shedding) the recognizer's brownout
  controller runs on; the *client-side* sibling of ``ResiliencePolicy``'s
  backend-side knobs (see ``runtime.admission`` for the front door).
- ``is_transient_error`` — classifies an exception as retryable
  (backend/transport outage shaped) vs permanent (a poisoned batch: retrying
  a shape error burns the retry budget for nothing).
- ``ServiceSupervisor`` — restarts a crashed serving loop with the
  last-known-good gallery snapshot, reusing the existing double-buffered
  ``reload_gallery`` swap. Restart count is bounded; giving up publishes a
  terminal status rather than flapping forever.
- ``DurabilityMonitor`` — the degraded-DURABILITY state machine
  (ISSUE 15): the backend-outage machinery above assumes the *disk* is
  fine; this class owns the case where it is not (ENOSPC, EIO, a
  2-second fsync).  Sustained WAL append failure (or a critical disk
  watermark) flips the writer to ``durability_degraded``: enrollments
  are refused closed with an explicit status (the ack never lies),
  serving/read traffic continues, and non-critical sinks (dead-letter
  journal, span JSONL, flight dumps) shed with exact per-sink
  accounting. A background probe (tmp-file write + fsync in the state
  dir) detects recovery and re-arms with a lifecycle span and a status
  announcement — the same degrade/announce/recover shape as the
  dispatch-side degraded mode. Disk-pressure watermarks ride the same
  tick: below the low watermark the monitor preemptively compacts the
  WAL (forced checkpoint) and shrinks checkpoint/flight/journal
  retention; below ``watermark / critical_divisor`` it pre-empts the
  degraded flip BEFORE ENOSPC ever lands.

Every transition is counted in the service's ``Metrics`` (``dispatch_
retries``, ``batches_dead_lettered``, ``degraded_transitions``,
``supervisor_restarts``), so chaos tests can assert fault handling exactly
(see ``tests/test_chaos.py`` and ``scripts/chaos_soak.py``).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional, Tuple
from opencv_facerecognizer_tpu.utils import metric_names as mn

#: substrings (lowercased) that mark an exception as outage-shaped and
#: therefore worth retrying. "unavailable" covers both the real PJRT
#: fast-fail string and faults.InjectedUnavailableError; the rest are
#: transport shapes. "resource exhausted" is deliberately NOT here: on a
#: locally attached chip it is an HBM out-of-memory or a Mosaic scoped-VMEM
#: refusal for that shape — retrying cannot succeed and only hides it.
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline exceeded",
    "connection reset",
    "connection refused",
    "broken pipe",
    "socket closed",
    "internal: failed to",
)


#: default deadline of the degraded-mode device probe: the serving loop is
#: already failing, so a quick verdict beats a precise one.
DEFAULT_PROBE_TIMEOUT_S = 15.0


def probe_device(device, timeout_s: float = DEFAULT_PROBE_TIMEOUT_S
                 ) -> Tuple[bool, str]:
    """Deadline-bounded ``(usable, reason)`` verdict on ``device``, taken IN
    this process: one tiny op plus ``block_until_ready`` on a sacrificial
    daemon thread (the ``_ReadbackBlocker`` pattern), so a device call that
    never returns costs one abandoned thread, not the caller. The serving
    process owns its chip — a child process could only ever fail to open
    it, so the question "is the accelerator alive?" must be asked of the
    device the service already holds."""
    done = threading.Event()
    verdict = {}

    def run() -> None:
        try:
            import jax
            import numpy as np

            x = jax.device_put(np.zeros((), np.float32), device)
            (x + 1).block_until_ready()  # ocvf-lint: boundary=host-sync -- the probe IS a bounded device round trip, on its own daemon thread; the serving loop waits on the event with a deadline, never on the device
        except Exception as exc:  # noqa: BLE001 — any failure is the verdict
            verdict["error"] = f"{type(exc).__name__}: {exc}"
        done.set()

    threading.Thread(target=run, daemon=True,
                     name="ocvf-device-probe").start()
    if not done.wait(timeout=max(0.0, float(timeout_s))):
        return False, (f"device probe exceeded {float(timeout_s):.1f}s "
                       f"deadline (device call did not return)")
    if "error" in verdict:
        return False, f"device op failed: {verdict['error'][:300]}"
    return True, "ok"


def is_transient_error(exc: BaseException) -> bool:
    """True when ``exc`` looks like a backend/transport outage (retry it),
    False for permanent errors like a shape mismatch from a poisoned batch
    (retrying those can never succeed — abandon the batch instead)."""
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(marker in text for marker in _TRANSIENT_MARKERS)


@dataclass
class ResiliencePolicy:
    """Steady-state failure-handling knobs for ``RecognizerService``.

    Defaults are serving-shaped (seconds-scale deadlines, a few retries);
    chaos tests shrink them to keep wall time short.
    """

    #: retry attempts per batch after the first dispatch failure; the
    #: batch is abandoned (``batches_failed``) once exhausted.
    dispatch_retries: int = 3
    #: exponential backoff between dispatch retries: base * mult^attempt,
    #: capped at ``backoff_max_s``. The wait keeps draining readbacks.
    backoff_base_s: float = 0.05
    backoff_max_s: float = 2.0
    backoff_multiplier: float = 2.0
    #: a dispatched batch whose readback is not ready this long after
    #: dispatch is dead-lettered (``batches_dead_lettered``) and the loop
    #: moves on — the hang-mode outage must cost one deadline, not wedge
    #: the service. Generous: a healthy readback on the local chip takes
    #: milliseconds, but one queued behind a large gallery upload can wait
    #: for it (how long: not measured on the local chip).
    readback_deadline_s: float = 30.0
    #: consecutive failed dispatch *attempts* (across batches) that flip
    #: the service into degraded mode.
    degraded_after: int = 3
    #: on entering degraded mode, run the deadline-bounded in-process
    #: device probe (``probe_device``) and attach its verdict to the status
    #: message; a dead device then triggers ``cpu_fallback`` when wired.
    probe_backend_on_degraded: bool = False
    #: deadline for that probe.
    probe_timeout_s: float = DEFAULT_PROBE_TIMEOUT_S

    def backoff(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (0-based)."""
        return min(self.backoff_max_s,
                   self.backoff_base_s * self.backoff_multiplier ** attempt)


@dataclass
class BrownoutPolicy:
    """Load-shedding degradation knobs for ``RecognizerService`` (the
    overload layer's §2 — see the recognizer docstring's "Overload
    protection" block).

    The controller watches a queue-wait EWMA (frame enqueue -> batch pop:
    the term that balloons first when offered load exceeds capacity).
    Crossing ``queue_wait_s`` raises the brownout level (1, then 2 at the
    next dwell); dropping below ``exit_ratio * queue_wait_s`` lowers it.
    The asymmetric thresholds plus the ``dwell_s`` minimum between
    transitions are the hysteresis — a load hovering at the threshold must
    not flap the service in and out of brownout every batch.

    Degradation per level:

    - level 1: bulk-priority frames are skip-``bulk_skip`` shed at intake
      (keep one of every ``bulk_skip``), reason ``brownout``;
    - level 2 (``max_level``): ALL bulk frames shed at intake, and the
      dispatch bucket ladder is capped at its smallest rung — an
      oversized partial batch is trimmed to one small fast device call
      (the trimmed frames shed with reason ``brownout``), keeping
      per-batch latency low for the interactive traffic that remains.

    Interactive frames are never shed by the INTAKE skip (levels 1-2 drop
    only bulk there). The level-2 ladder trim, however, is class-blind: a
    popped batch carries no per-frame priority, so when interactive
    traffic alone still overfills the smallest bucket (bulk is already
    gone at intake by then), the trimmed excess is interactive — counted
    and journaled under the same explicit ``brownout`` reason so
    producers can retry. Keeping interactive loss at zero is the
    admission bound's job (``max_inflight_frames`` with its interactive
    reserve), not the brownout's.
    """

    #: queue-wait EWMA (seconds) above which the brownout level rises.
    queue_wait_s: float = 0.25
    #: the level drops once the EWMA falls below ``exit_ratio *
    #: queue_wait_s`` (hysteresis band).
    exit_ratio: float = 0.5
    #: minimum seconds between level changes (both directions).
    dwell_s: float = 0.5
    #: highest level (2 = shed-all-bulk + capped bucket ladder).
    max_level: int = 2
    #: level 1 keeps one of every ``bulk_skip`` bulk frames.
    bulk_skip: int = 2
    #: EWMA smoothing for the queue-wait signal.
    ewma_alpha: float = 0.3


class DurabilityDegradedError(RuntimeError):
    """An enrollment was refused CLOSED because durability is degraded
    (sustained WAL/storage failure or a critical disk watermark). The
    caller must surface an explicit refusal status — never acknowledge,
    never queue for later: the acknowledged == fsync-durable promise is
    exactly what degraded mode exists to protect."""


#: disk-pressure severity codes (the ``disk_pressure_state`` gauge).
DISK_OK, DISK_WARN, DISK_CRITICAL = 0, 1, 2


class DurabilityMonitor:
    """Degraded-durability state machine + disk-pressure watermarks for
    one writer's state dir (module docstring; README "Degraded-durability
    runbook").

    Construction attaches to the ``StateLifecycle``: ``state.durability``
    becomes this monitor, so ``append_enrollment`` refuses closed while
    degraded and feeds WAL append outcomes back in (from outside the
    enroll lock — the flip publishes a status and emits a span, I/O that
    must never run under durability locks).

    Two independent triggers flip ``armed -> durability_degraded``:

    - ``degraded_after`` CONSECUTIVE strict-WAL-append ``OSError``s
      (ENOSPC/EIO — each one already refused its enrollment; the flip
      stops new appends from even being attempted);
    - the disk falling below ``low_watermark_bytes / critical_divisor``
      free (the preemptive flip: refuse BEFORE ENOSPC tears a line).

    While degraded: serving and read traffic continue untouched;
    enrollments are refused closed (``enrollments_refused_degraded``,
    status reason ``durability_degraded``); sinks wired via
    ``attach_sinks`` shed with exact per-sink ``*_shed`` counters.

    Recovery is PROBED, never assumed: every ``probe_interval_s`` the
    monitor durably writes + fsyncs + unlinks a tmp file in the state
    dir (through the same fault injector as every durable path, so chaos
    controls it). A probe success while the disk is above the critical
    watermark re-arms durability — lifecycle span, ``durability_rearms``,
    and a ``durability_restored`` status announcement.

    Disk pressure rides the same tick: a ``statvfs`` free-bytes gauge
    (``disk_free_bytes``) and the ``disk_pressure_state`` 0/1/2 gauge.
    Crossing into warn fires ONE preemptive WAL compaction (forced
    checkpoint — its success truncates the WAL) and one retention shrink
    (checkpoint keep / flight-dump keep / journal backups to their
    floor) per pressure episode; recovery above the watermark restores
    the original retention. The ``slo.disk_free_objective`` gauge SLO
    reads the same free-bytes probe, so /health and /prom carry the
    pressure verdict without a second statvfs.
    """

    PROBE_NAME = ".durability_probe"

    def __init__(self, state, metrics=None, tracer=None,
                 degraded_after: int = 3,
                 probe_interval_s: float = 5.0,
                 low_watermark_bytes: int = 0,
                 critical_divisor: float = 6.0,
                 publish: Optional[Callable[[dict], None]] = None,
                 fault_injector=None,
                 statvfs_fn=None):
        self.state = state
        self.metrics = metrics
        self.tracer = tracer
        self.degraded_after = max(1, int(degraded_after))
        self.probe_interval_s = float(probe_interval_s)
        self.low_watermark_bytes = max(0, int(low_watermark_bytes))
        self.critical_divisor = max(1.0, float(critical_divisor))
        #: status-announcement hook ({"status": ...} dicts). The service
        #: wires its ``_publish_status`` here at construction; bare
        #: lifecycles (chaos scenarios) may leave it None or capture it.
        self.publish = publish
        self._faults = fault_injector
        self._statvfs = statvfs_fn if statvfs_fn is not None else os.statvfs
        self._degraded = False
        self._degraded_reason: Optional[str] = None
        self._consecutive_wal_failures = 0
        self._consecutive_lease_failures = 0
        self._disk_state = DISK_OK
        self._retention_shrunk = False
        self._saved_retention: dict = {}
        #: sinks registered by attach_sinks, kept for retention shrink.
        self._journal = None
        self._tracer_sink = None
        self._lock = threading.Lock()
        #: one tick cycle at a time (non-blocking claim, like the SLO
        #: monitor's evaluation lock): the serving loop and the background
        #: thread both tick, and the watermark transitions +
        #: shrink/restore bookkeeping are check-then-act — two threads
        #: crossing the warn watermark together would double-fire the
        #: compaction and save the already-shrunk retention values as
        #: "originals", pinning retention at the floor forever.
        self._tick_lock = threading.Lock()
        self._last_tick_t = 0.0
        self._free_bytes: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        if state is not None:
            state.durability = self
        if self.metrics is not None:
            self.metrics.set_gauge(mn.DURABILITY_STATE, 0)

    # ---- readers (any thread) ----

    @property
    def degraded(self) -> bool:
        return self._degraded

    @property
    def degraded_reason(self) -> Optional[str]:
        return self._degraded_reason

    @property
    def disk_state(self) -> int:
        return self._disk_state

    def free_bytes(self) -> float:
        """Last observed free bytes on the state volume (refreshing once
        when never sampled) — the ``disk_free_objective`` probe, shared
        with the gauge so /health and /prom agree without a second
        statvfs per evaluation."""
        if self._free_bytes is None:
            self._sample_disk()
        return float(self._free_bytes if self._free_bytes is not None
                     else float("inf"))

    def status(self) -> dict:
        return {
            "degraded": self._degraded,
            "reason": self._degraded_reason,
            "consecutive_wal_failures": self._consecutive_wal_failures,
            "consecutive_lease_failures": self._consecutive_lease_failures,
            "disk_state": self._disk_state,
            "free_bytes": self._free_bytes,
            "low_watermark_bytes": self.low_watermark_bytes,
            "retention_shrunk": self._retention_shrunk,
        }

    # ---- sink wiring ----

    def attach_sinks(self, journal=None, span_sink=None, tracer=None) -> None:
        """Point the non-critical sinks' shed hooks at this monitor: while
        degraded they drop writes with exact per-sink accounting instead
        of one swallowed OSError per attempt. The WAL is deliberately NOT
        sheddable — its failures are the signal."""
        shed = lambda: self._degraded  # noqa: E731 — the one-line contract
        if journal is not None:
            journal.shed_fn = shed
            self._journal = journal
        if span_sink is not None:
            span_sink.shed_fn = shed
        if tracer is not None:
            tracer.shed_fn = shed
            self._tracer_sink = tracer

    # ---- WAL outcome feed (called by StateLifecycle, outside its locks) --

    def note_wal_failure(self, exc: BaseException) -> None:
        """One strict WAL append failed with a storage-shaped error. At
        ``degraded_after`` consecutive failures the writer flips."""
        with self._lock:
            self._consecutive_wal_failures += 1
            should_flip = (not self._degraded
                           and self._consecutive_wal_failures
                           >= self.degraded_after)
        if should_flip:
            self._flip_degraded(
                "wal_append_failures",
                error=repr(exc),
                consecutive=self._consecutive_wal_failures)

    def note_wal_success(self) -> None:
        with self._lock:
            self._consecutive_wal_failures = 0

    # ---- transitions ----

    def _flip_degraded(self, reason: str, **detail) -> None:
        with self._lock:
            if self._degraded:
                return
            self._degraded = True
            self._degraded_reason = reason
        if self.metrics is not None:
            self.metrics.incr(mn.DURABILITY_DEGRADED_TRANSITIONS)
            self.metrics.set_gauge(mn.DURABILITY_STATE, 1)
        logging.getLogger(__name__).error(
            "durability DEGRADED (%s): enrollments refused closed, "
            "serving continues, recovery probe armed (%s)", reason, detail)
        if self.tracer is not None:
            self.tracer.emit(self.tracer.new_trace(), "durability",
                             topic=_lifecycle_topic(),
                             from_state="armed", to_state="degraded",
                             reason=reason, **detail)
        self._announce({"status": "durability_degraded", "reason": reason,
                        **detail})

    def _rearm(self) -> None:
        with self._lock:
            if not self._degraded:
                return
            self._degraded = False
            reason = self._degraded_reason
            self._degraded_reason = None
            self._consecutive_wal_failures = 0
        if self.metrics is not None:
            self.metrics.incr(mn.DURABILITY_REARMS)
            self.metrics.set_gauge(mn.DURABILITY_STATE, 0)
        logging.getLogger(__name__).warning(
            "durability RE-ARMED (probe write+fsync succeeded; was "
            "degraded: %s) — enrollments accepted again", reason)
        if self.tracer is not None:
            self.tracer.emit(self.tracer.new_trace(), "durability",
                             topic=_lifecycle_topic(),
                             from_state="degraded", to_state="armed",
                             was=reason)
        self._announce({"status": "durability_restored", "was": reason})

    def _announce(self, status: dict) -> None:
        publish = self.publish
        if publish is None:
            return
        try:
            publish(status)
        except Exception:  # noqa: BLE001 — a dead transport never blocks a flip
            logging.getLogger(__name__).exception(
                "durability status publish failed")

    # ---- the recovery probe ----

    def probe_now(self) -> bool:
        """One durable tmp-file write + fsync + unlink in the state dir —
        proof the volume accepts durable writes again. Routed through the
        shared storage fault boundary so chaos owns the verdict. A
        success while the disk sits above the critical watermark re-arms
        degraded durability."""
        if self.metrics is not None:
            self.metrics.incr(mn.DURABILITY_PROBES)
        path = os.path.join(getattr(self.state, "state_dir", "."),
                            self.PROBE_NAME)
        try:
            if self._faults is not None:
                self._faults.on_storage("durability_probe")
            with open(path, "wb") as fh:  # ocvf-lint: disable=non-atomic-write -- the probe file IS the test: its only purpose is this write+fsync round trip, it is unlinked on the next line, and a torn remnant carries no state (readers never exist)
                fh.write(b"probe\n")
                fh.flush()
                os.fsync(fh.fileno())
            os.unlink(path)
        except OSError:
            if self.metrics is not None:
                self.metrics.incr(mn.DURABILITY_PROBE_FAILURES)
            return False
        if self._degraded and self._disk_state < DISK_CRITICAL:
            self._rearm()
        return True

    # ---- split-brain lease guard (ISSUE 16) ----

    def _check_lease(self) -> None:
        """Writer split-brain safety: a writer whose state dir (home of
        ``writer.lease``) has become unreachable can no longer PROVE it
        still owns enrollment — a healed partition may find a second
        writer leased over the same volume. After ``degraded_after``
        consecutive reachability failures the writer flips
        durability-degraded, which fails enrollments closed (the same
        machinery as WAL failures) while recognition serving continues.
        Recovery rides the existing probe: a durable write+fsync in the
        state dir is strictly stronger proof than this stat."""
        state_dir = getattr(self.state, "state_dir", None)
        if state_dir is None:
            return
        try:
            if self._faults is not None:
                self._faults.on_storage_read("lease_check")
            os.stat(state_dir)
        except OSError:
            if self.metrics is not None:
                self.metrics.incr(mn.DURABILITY_LEASE_CHECK_FAILURES)
            with self._lock:
                self._consecutive_lease_failures += 1
                should_flip = (not self._degraded
                               and self._consecutive_lease_failures
                               >= self.degraded_after)
            if should_flip:
                self._flip_degraded(
                    "lease_unreachable",
                    consecutive=self._consecutive_lease_failures)
            return
        with self._lock:
            self._consecutive_lease_failures = 0

    # ---- disk-pressure watermarks ----

    def _sample_disk(self) -> None:
        state_dir = getattr(self.state, "state_dir", None)
        if state_dir is None:
            return
        try:
            st = self._statvfs(state_dir)
            self._free_bytes = float(st.f_bavail) * float(st.f_frsize)
        except OSError:
            return  # keep the last sample; the probe owns hard failures
        if self.metrics is not None:
            self.metrics.set_gauge(mn.DISK_FREE_BYTES, self._free_bytes)

    def _check_watermarks(self) -> None:
        if not self.low_watermark_bytes or self._free_bytes is None:
            return
        free = self._free_bytes
        critical_at = self.low_watermark_bytes / self.critical_divisor
        new_state = (DISK_CRITICAL if free < critical_at
                     else DISK_WARN if free < self.low_watermark_bytes
                     else DISK_OK)
        prev = self._disk_state
        self._disk_state = new_state
        if self.metrics is not None:
            self.metrics.set_gauge(mn.DISK_PRESSURE_STATE, new_state)
        if new_state >= DISK_WARN and prev < DISK_WARN:
            self._on_disk_warn(free)
        if new_state >= DISK_CRITICAL and not self._degraded:
            # Preempt ENOSPC: flip BEFORE a torn WAL line ever lands. The
            # probe still owns recovery — and refuses to re-arm while the
            # disk stays critical.
            self._flip_degraded("disk_critical", free_bytes=int(free),
                                low_watermark_bytes=self.low_watermark_bytes)
        if new_state == DISK_OK and prev > DISK_OK:
            self._restore_retention()

    def _on_disk_warn(self, free: float) -> None:
        """Entering warn: one preemptive WAL compaction (forced
        checkpoint — success truncates the WAL below its sequence) and
        one retention shrink per pressure episode."""
        logging.getLogger(__name__).warning(
            "disk pressure: %d bytes free < %d watermark — forcing a "
            "checkpoint (WAL compaction) and shrinking retention",
            int(free), self.low_watermark_bytes)
        if self.state is not None:
            try:
                self.state.maybe_checkpoint(force=True)
                if self.metrics is not None:
                    self.metrics.incr(mn.DISK_PRESSURE_COMPACTIONS)
            except Exception:  # noqa: BLE001 — pressure relief is best-effort
                logging.getLogger(__name__).exception(
                    "disk-pressure checkpoint trigger failed")
        self._shrink_retention()
        self._announce({"status": "disk_pressure", "state": "warn",
                        "free_bytes": int(free),
                        "low_watermark_bytes": self.low_watermark_bytes})

    def _shrink_retention(self) -> None:
        if self._retention_shrunk:
            return
        self._retention_shrunk = True
        store = getattr(self.state, "store", None)
        if store is not None:
            self._saved_retention["store_keep"] = store.keep
            store.keep = 1
        tracer = self._tracer_sink if self._tracer_sink is not None else self.tracer
        if tracer is not None and hasattr(tracer, "keep_dumps"):
            self._saved_retention["keep_dumps"] = tracer.keep_dumps
            tracer.keep_dumps = 1
        if self._journal is not None:
            self._saved_retention["journal_backups"] = self._journal.backups
            self._journal.backups = 0
        if self.metrics is not None:
            self.metrics.incr(mn.DISK_PRESSURE_RETENTION_SHRINKS)

    def _restore_retention(self) -> None:
        if not self._retention_shrunk:
            return
        self._retention_shrunk = False
        store = getattr(self.state, "store", None)
        if store is not None and "store_keep" in self._saved_retention:
            store.keep = self._saved_retention["store_keep"]
        tracer = self._tracer_sink if self._tracer_sink is not None else self.tracer
        if tracer is not None and "keep_dumps" in self._saved_retention:
            tracer.keep_dumps = self._saved_retention["keep_dumps"]
        if self._journal is not None and "journal_backups" in self._saved_retention:
            self._journal.backups = self._saved_retention["journal_backups"]
        self._saved_retention.clear()

    # ---- ticking ----

    def tick(self, force: bool = False, probe: bool = False) -> None:
        """Interval-gated cycle (the serving loop calls this beside
        ``state.tick()``; the non-due path is one clock read): refresh the
        disk gauges + watermark actions, and — only with ``probe`` and
        while degraded — run the recovery probe. The serving loop always
        calls with ``probe=False``: the probe is a blocking write+fsync
        against a disk already known broken, and a hung device would
        wedge the very serving this machine promises to keep running —
        probing belongs exclusively to the background thread
        (``start()``, which the service runs alongside the loop).
        Concurrent tickers are serialized by a NON-BLOCKING claim — the
        loser skips, nobody waits, and the watermark transitions fire
        exactly once."""
        now = time.monotonic()
        if not force and now - self._last_tick_t < self.probe_interval_s:
            return
        if not self._tick_lock.acquire(blocking=False):
            return  # another ticker owns this cycle
        try:
            self._last_tick_t = now
            self._sample_disk()
            self._check_watermarks()
            should_probe = probe and self._degraded
        finally:
            self._tick_lock.release()
        if probe:
            # Split-brain guard (ISSUE 16): like the recovery probe, real
            # I/O against a possibly-dead volume — background thread only,
            # outside the claim.
            self._check_lease()
        if should_probe:
            # Outside the claim: the probe is file I/O (possibly a slow
            # fsync) and must never hold the tick lock against the
            # serving loop's cheap watermark refresh.
            self.probe_now()

    def start(self) -> None:
        """Background ticker (daemon): keeps watermarks fresh and the
        recovery probe running even when the serving loop is busy riding
        out a slow_fsync. Idempotent."""
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="durability-monitor")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=5.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(timeout=max(0.05, self.probe_interval_s)):
            try:
                self.tick(force=True, probe=True)
            except Exception:  # noqa: BLE001 — the monitor thread must live
                logging.getLogger(__name__).exception(
                    "durability monitor tick failed")


def _lifecycle_topic() -> str:
    from opencv_facerecognizer_tpu.utils.tracing import LIFECYCLE_TOPIC

    return LIFECYCLE_TOPIC


def rebuild_pipeline_on_cpu(service) -> None:
    """The stock ``cpu_fallback`` hook: rebuild the service's recognition
    pipeline on host CPU devices when degraded mode finds the accelerator
    dead (``ocvf-recognize --probe-on-degraded`` wires this).

    Reuses the live nets/params as-is, copies the gallery through the
    host-mirror ``snapshot``/``load_snapshot`` path onto a fresh
    single-CPU-device mesh (no device readback — the dead accelerator may
    not answer one), and swaps ``service.pipeline`` between batches. The
    swap itself pays the ladder's XLA compiles (prewarm, below) so the
    recompile watchdog stays armed; after that the job is degraded
    (CPU-speed) but serving. Raises when no CPU backend exists — the
    caller treats a failed fallback as best-effort (``cpu_fallback:
    False`` in the degraded status)."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    from opencv_facerecognizer_tpu.parallel.gallery import ShardedGallery
    from opencv_facerecognizer_tpu.parallel.mesh import DP_AXIS, TP_AXIS
    from opencv_facerecognizer_tpu.parallel.pipeline import RecognitionPipeline

    old = service.pipeline
    cpu_device = jax.devices("cpu")[0]
    cpu_mesh = Mesh(np.asarray([cpu_device]).reshape(1, 1),
                    (DP_AXIS, TP_AXIS))
    # default_device(cpu) for the WHOLE rebuild: gallery init and snapshot
    # install run jnp ops whose placement would otherwise go through the
    # default (dead) accelerator backend — hanging or raising inside the
    # very hook that exists to escape it.
    with jax.default_device(cpu_device):
        gallery = ShardedGallery(capacity=old.gallery.capacity,
                                 dim=old.gallery.dim, mesh=cpu_mesh,
                                 store_dtype=old.gallery.store_dtype)
        gallery.load_snapshot(*old.gallery.snapshot())
    pipeline = RecognitionPipeline(old.detector, old.embed_net,
                                   old.embed_params, gallery,
                                   face_size=old.face_size, top_k=old.top_k)
    # The chaos boundary FOLLOWS the swap — moved, not copied: an armed
    # injector left on the abandoned pipeline would leak faults into the
    # next service built on it (production leaves both None).
    pipeline.fault_injector = getattr(old, "fault_injector", None)
    old.fault_injector = None
    service.pipeline = pipeline
    # Keep the recompile watchdog armed ACROSS the swap by prewarming the
    # fresh pipeline's ladder here: its jit cache starts empty, and those
    # by-design compiles are the documented cost of the fallback — paid up
    # front, not smeared over the first serving dispatches. Simply
    # disarming instead would silence the watchdog for the rest of the
    # process, losing exactly the mid-serving-compile coverage it exists
    # for. If the prewarm itself fails, disarm and keep serving — a CPU
    # fallback that serves with a quiet watchdog beats one that crashed
    # in its own escape hook.
    if service._warmed:
        try:
            with jax.default_device(cpu_device):
                pipeline.prewarm_batch_shapes(
                    service._bucket_ladder, service.batcher.frame_shape,
                    service.batcher.dtype)
        except Exception:  # noqa: BLE001 — fallback must finish
            logging.getLogger(__name__).exception(
                "CPU-fallback ladder prewarm failed; "
                "recompile watchdog disarmed")
            service._warmed = False
    # The enrolment embed graph must follow too: the service's jitted
    # chunk embedder would otherwise keep dispatching on the dead
    # accelerator (see RecognizerService._run_embed_chunk).
    service._embed_device = cpu_device
    # And the ingest uploader: its explicit per-dispatch device_put would
    # otherwise keep committing frames to the dead default device —
    # every batch failing against the very fallback built to survive it.
    if getattr(service, "ingest", None) is not None:
        service.ingest.upload_device = cpu_device


class ServiceSupervisor:
    """Restart a crashed serving loop with the last-known-good gallery.

    The service loop already survives per-batch failures; what it cannot
    survive is an exception escaping the loop body itself (a connector
    handler raising inside ``publish``, a batcher bug, ...) — the thread
    dies and frames pile up unserved. The supervisor watches for that
    crash flag and restarts the loop, first restoring the gallery from the
    snapshot taken at the last ``checkpoint()`` — start, every committed
    change (the supervisor subscribes to ``STATUS_TOPIC`` and checkpoints
    on ``enrolled``/``reloaded``), plus any point the operator/app calls
    it — via the existing ``reload_gallery``/``swap_from`` double-buffer
    path. A crash mid-enrolment cannot leave a half-written gallery
    serving, and a crash AFTER a committed enrolment rolls back only to
    that commit, not to startup.

    Restarts are bounded: after ``max_restarts`` the supervisor publishes
    ``{"status": "supervisor_gave_up"}`` and stops intervening (a crash
    loop almost always means a real bug, and flapping hides it).

    With a durable state lifecycle wired (``state=``,
    ``runtime.state_store``), the in-memory snapshot stays the primary
    in-process restore and the lifecycle's checkpoint+WAL recovery is the
    fallback when that snapshot is missing or fails to install — the same
    path a full process restart takes, so both rungs of the restart
    ladder land on consistent state.

    Honest limitation — the **call-time hang**: a backend that blocks
    forever *inside* the dispatch call itself (not the readback) cannot be
    preempted from within the process — the serving thread is stuck in
    native code, alive, so neither the readback deadline nor the crash
    watchdog fires. The supervisor's stall watchdog at least SURFACES that
    shape: frames pending with zero progress for ``stall_warn_s`` publishes
    ``{"status": "stalled"}`` (``supervisor_stalls``), the signal a
    deploy-level supervisor (systemd/k8s liveness) needs to restart the
    process.
    """

    def __init__(self, service, max_restarts: int = 5,
                 poll_interval_s: float = 0.2,
                 restart_backoff_s: float = 0.1,
                 commit_wait_s: float = 30.0,
                 state=None):
        self.service = service
        self.max_restarts = int(max_restarts)
        self.poll_interval_s = float(poll_interval_s)
        self.restart_backoff_s = float(restart_backoff_s)
        #: optional runtime.state_store.StateLifecycle — the DURABLE
        #: last-known-good. The in-memory snapshot stays the primary
        #: restore (cheap, no disk); the lifecycle is the fallback when
        #: that snapshot is missing or its install fails, and the source
        #: of process-restart recovery either way.
        self.state = state
        #: bounded wait for async-grow staged rows to land before a
        #: post-commit checkpoint (a snapshot taken mid-grow would MISS
        #: the rows the commit announced); on timeout the previous
        #: checkpoint is kept — never a partial one.
        self.commit_wait_s = float(commit_wait_s)
        #: frames pending with zero processing progress for this long
        #: publishes a one-shot ``stalled`` status (see class docstring:
        #: the call-time-hang shape can only be surfaced, not fixed,
        #: in-process).
        self.stall_warn_s = 60.0
        self.restarts = 0
        self.gave_up = False
        self._last_processed = -1.0
        self._last_progress_t = time.monotonic()
        self._stall_warned = False
        #: last SLO health state seen by the watchdog (edge detection for
        #: the health status publishes; -1 = not yet observed).
        self._last_health = -1
        self._snapshot: Optional[Tuple] = None
        self._snapshot_wal_seq: Optional[int] = None
        self._snapshot_version: Optional[int] = None
        self._subject_names: Optional[list] = None
        self._thread: Optional[threading.Thread] = None
        self._running = False

    # ---- lifecycle ----

    def start(self, warmup: bool = True) -> None:
        """Start the service (if not already running) and the monitor."""
        if self._thread is not None:
            return
        self.service.start(warmup=warmup)
        self.checkpoint()
        # Every committed gallery change (a finished enrolment, a retrain
        # reload) advances last-known-good: a later crash must roll back
        # only half-done work, not every subject enrolled since startup.
        # Registered as a DIRECT service hook, not a STATUS_TOPIC
        # subscription: wire connectors publish outbound only and never
        # dispatch their own publishes locally, so a subscription would
        # silently never fire in production.
        self.service.commit_hooks.append(self._on_commit)
        self._running = True
        self._thread = threading.Thread(target=self._monitor, daemon=True,
                                        name="service-supervisor")
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        if self._on_commit in self.service.commit_hooks:
            self.service.commit_hooks.remove(self._on_commit)
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self.service.stop()

    def checkpoint(self) -> None:
        """Record the current gallery + subject names as last-known-good.
        Host-mirror copies only — no device readback (a crashed loop may
        mean a device that no longer answers one). With a state lifecycle
        wired, the snapshot is STAMPED with the WAL sequence it covers —
        a restore then replays the acknowledged tail past the stamp, so
        rolling back to this snapshot can never desync the gallery from
        the WAL coverage the next durable checkpoint claims."""
        if self.state is not None:
            (self._snapshot_wal_seq, self._snapshot,
             self._subject_names,
             self._snapshot_version) = self.state.stamped_snapshot()
        else:
            self._snapshot_wal_seq = None
            self._snapshot = self.service.pipeline.gallery.snapshot()
            self._subject_names = list(self.service.subject_names)
            self._snapshot_version = getattr(
                self.service.pipeline.gallery, "embedder_version", None)
        self.service.metrics.incr(mn.SUPERVISOR_CHECKPOINTS)

    def _on_commit(self) -> None:
        """Advance last-known-good after a committed gallery change. Runs
        on whatever thread committed (enrolment worker, reload caller) —
        checkpoint() only copies host mirrors, so that is cheap there.
        Under ``async_grow`` the committing add() may have only STAGED its
        rows; wait (bounded) for the grow to land them, and on timeout
        keep the previous checkpoint rather than capture a snapshot that
        silently misses the rows this commit announced."""
        if not self._running:
            return
        gallery = self.service.pipeline.gallery
        wait_ready = getattr(gallery, "wait_ready", None)
        if wait_ready is not None and not wait_ready(timeout=self.commit_wait_s):
            logging.getLogger(__name__).warning(
                "post-commit checkpoint skipped: staged rows not landed "
                "within %.0fs; keeping previous snapshot", self.commit_wait_s)
            return
        self.checkpoint()

    # ---- the watchdog ----

    def _monitor(self) -> None:
        from opencv_facerecognizer_tpu.runtime.recognizer import STATUS_TOPIC

        service = self.service
        while self._running:
            time.sleep(self.poll_interval_s)
            self._check_stall(service, STATUS_TOPIC)
            self._check_health(service, STATUS_TOPIC)
            if not service.loop_crashed or not service._running:
                continue
            if not service.restart_pending():
                # Crash flagged but every serving-side thread (dispatch
                # loop AND readback worker) is still unwinding (e.g. a
                # slow 'crashed' status subscriber): restart_loop would
                # no-op on the alive threads, so acting now would burn a
                # phantom restart (and desync restarts vs loop_crashes,
                # which the soak treats as an unsupervised crash). Wait
                # for a thread to actually exit.
                continue
            if self.restarts >= self.max_restarts:
                if not self.gave_up:
                    self.gave_up = True
                    service.metrics.incr(mn.SUPERVISOR_GAVE_UP)
                    self._publish(STATUS_TOPIC, {
                        "status": "supervisor_gave_up",
                        "restarts": self.restarts,
                    })
                continue
            self.restarts += 1
            # Flight-recorder dump BEFORE the restore/restart mutate
            # anything: the rings hold exactly what was in flight when
            # the loop died — the evidence a post-mortem needs.
            tracer = getattr(service, "tracer", None)
            if tracer is not None:
                tracer.dump("supervisor_restart",
                            extra={"restarts": self.restarts,
                                   "ledger": service.ledger()})
            try:
                self._restore_gallery()
            except Exception:
                logging.getLogger(__name__).exception(
                    "gallery restore failed; trying durable state")
                if not self._restore_durable():
                    logging.getLogger(__name__).exception(
                        "durable restore unavailable; restarting with "
                        "current state")
            service.restart_loop()
            # Counter flips only once the restore + restart are done, so a
            # watcher seeing it can rely on the last-known-good gallery
            # already being live (the chaos test's synchronization point).
            service.metrics.incr(mn.SUPERVISOR_RESTARTS)
            self._publish(STATUS_TOPIC, {
                "status": "supervisor_restart",
                "restarts": self.restarts,
            })
            time.sleep(self.restart_backoff_s)

    def _check_stall(self, service, status_topic: str) -> None:
        """One-shot ``stalled`` announcement when frames are pending but
        the loop has made no progress for ``stall_warn_s`` — the
        call-time-hang signature a deploy-level liveness check keys on.
        Progress is ANY batch outcome, including abandons and dead-letters:
        a loop actively surviving a fast-fail outage (every batch retried
        then abandoned) is degraded, not stalled — flagging it would make
        the deploy layer kill a process that is degrading gracefully."""
        m = service.metrics
        processed = (m.counter("frames_processed")
                     + m.counter("batches_failed")
                     + m.counter("batches_dead_lettered"))
        now = time.monotonic()
        if processed != self._last_processed:
            self._last_processed = processed
            self._last_progress_t = now
            self._stall_warned = False
            return
        if (not self._stall_warned
                and service.batcher.pending > 0
                and now - self._last_progress_t > self.stall_warn_s):
            self._stall_warned = True
            service.metrics.incr(mn.SUPERVISOR_STALLS)
            # Wedge detection is a flight-recorder trigger: the dump is
            # the answer to "what was in flight when the soak wedged" —
            # the spans of every undrained frame/batch at stall time.
            tracer = getattr(service, "tracer", None)
            if tracer is not None:
                tracer.dump("wedge_stall", extra={
                    "pending_frames": service.batcher.pending,
                    "seconds_without_progress":
                        round(now - self._last_progress_t, 1),
                    "ledger": service.ledger(),
                })
            self._publish(status_topic, {
                "status": "stalled",
                "pending_frames": service.batcher.pending,
                "seconds_without_progress": round(now - self._last_progress_t, 1),
            })

    def _check_health(self, service, status_topic: str) -> None:
        """Publish the SLO monitor's health transitions on the status
        topic — the supervisor is the component a deploy layer already
        listens to, so the health verdict rides the same channel as
        ``stalled``/``supervisor_restart``. Edge-triggered: one status per
        state change, carrying the per-objective burn rates, so an
        orchestrator can act (drain this replica, route around it)
        without polling ``/health``. The monitor itself owns evaluation,
        spans, gauges, and the critical flight dump; the supervisor only
        ANNOUNCES."""
        monitor = getattr(service, "slo", None)
        if monitor is None:
            return
        # Backstop tick before reading: the serving loop is the primary
        # ticker, but a wedged loop stops ticking — and a wedged loop is
        # exactly what the loop_liveness gauge exists to escalate. The
        # expo refresh thread also backstops, but expo is optional; the
        # supervisor's poll loop is the always-on ticker when supervised.
        # tick() is interval-gated and its evaluation claim is
        # non-blocking, so this is cheap and never double-evaluates.
        try:
            monitor.tick()
        except Exception:  # noqa: BLE001 — the watchdog thread must live
            logging.getLogger(__name__).exception(
                "supervisor slo backstop tick failed")
            service.metrics.incr(mn.SLO_TICK_ERRORS)
        state = monitor.state_code
        if state == self._last_health:
            return
        first = self._last_health < 0
        self._last_health = state
        if first and state == 0:
            return  # don't announce the boring initial "ok"
        verdict = monitor.verdict()
        self._publish(status_topic, {
            "status": "health",
            "state": monitor.state,
            "objectives": {
                name: obj.get("burn")
                for name, obj in verdict.get("objectives", {}).items()},
            "events": verdict.get("events", {}),
        })

    def _restore_gallery(self) -> None:
        if self._snapshot is None:
            # No in-memory last-known-good (possible when start() raced a
            # crash before its first checkpoint): fall back to the durable
            # lifecycle when one is wired.
            self._restore_durable()
            return
        service = self.service
        # Rows + embedder version re-install in ONE atomic publish: a
        # snapshot taken before a cutover restores the OLD version stamp
        # with the old-space rows (never old rows under the new stamp),
        # and replay_tail's version fence then keeps post-cutover records
        # from mixing in.
        service.pipeline.gallery.load_snapshot(
            *self._snapshot,
            embedder_version=getattr(self, "_snapshot_version", None))
        if self._subject_names is not None:
            # Same in-place trim/extend rule as the gallery restore: names
            # enrolled after the checkpoint have no committed rows anymore.
            service.subject_names[:] = self._subject_names
        if self.state is not None and self._snapshot_wal_seq is not None:
            # Enrollments ACKNOWLEDGED after this snapshot was stamped
            # (crash raced the commit hook) must come back: without the
            # tail replay they would vanish from serving and the next
            # durable checkpoint would truncate their WAL records.
            self.state.replay_tail(self._snapshot_wal_seq)
        # load_snapshot invalidated any attached IVF quantizer (derived
        # state): schedule the background retrain here, or a match-heavy
        # workload with no further enrolments (the other poke site) stays
        # pinned to the linear exact scan forever.
        poke = getattr(service.pipeline.gallery, "_poke_quantizer", None)
        if poke is not None:
            poke()

    def _restore_durable(self) -> bool:
        """Fallback restore from the durable state lifecycle (checkpoint +
        WAL replay) — the same path a process restart takes. Returns True
        when it ran."""
        if self.state is None:
            return False
        try:
            self.state.recover(self.service.pipeline.gallery,
                               self.service.subject_names)
            self.service.metrics.incr(mn.SUPERVISOR_DURABLE_RESTORES)
            return True
        except Exception:  # noqa: BLE001 — restore is best-effort here
            logging.getLogger(__name__).exception("durable restore failed")
            return False

    def _publish(self, topic: str, message: dict) -> None:
        try:
            self.service.connector.publish(topic, message)
        except Exception:  # a dead transport must not kill the watchdog
            logging.getLogger(__name__).exception("supervisor publish failed")
